"""Layer "ALS iteration": seconds in which an operation ran on the
device during ONE traced train — the union of the device-op intervals
of the profiler's trace, averaged over the chips used."""


def read(obs):
    trace = obs.get("trace")
    return trace.busy_s if trace is not None and trace.n_devices else None
