"""Layer "kernels": device milliseconds of the trace's operations named
``gather_gram`` (the fused Pallas gather→Gram kernel of ``ops/gram.py``)
in ONE traced train. Absent when no such operation ran (``gram=off``)."""


def read(obs):
    trace = obs.get("trace")
    secs = trace.seconds_of("gather_gram") if trace is not None else None
    return None if secs is None else secs * 1e3
