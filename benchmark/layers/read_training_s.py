"""Layer "training read": seconds of ``read_training`` in the
workflow's own ``train phases:`` line, median over the window's trains
(event store → columnar arrays, through the scan snapshot)."""


def read(obs):
    return obs.get("read_training_s")
