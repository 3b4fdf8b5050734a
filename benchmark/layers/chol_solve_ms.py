"""Layer "kernels": device milliseconds of the trace's operations named
``chol_solve`` (the Pallas batched Cholesky solve of
``ops/cholesky.py``) in ONE traced train. Absent where the XLA solve
runs, and on a program whose kernel has no such name."""


def read(obs):
    trace = obs.get("trace")
    secs = trace.seconds_of("chol_solve") if trace is not None else None
    return None if secs is None else secs * 1e3
