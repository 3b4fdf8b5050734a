"""Layer "seqrec step": seconds in which an operation ran on the
device during ONE traced train of the sequence cell — the union of the
device-op intervals of the profiler's trace."""


def read(obs):
    trace = obs.get("trace")
    if trace is None or not trace.n_devices or "scopes" not in obs:
        return None
    return trace.busy_s
