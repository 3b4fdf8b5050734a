"""What the readers of the train step's OWN scopes share (PR 36): the
device seconds ``scope_reduce.scope_seconds`` gave a traced train
(``obs["scopes"]``: self seconds per innermost ``seqrec.*`` scope,
``other`` for an operation under none), summed over a metric's scopes.

``seq_layers.SCOPES`` and ``roofline_lfm2.SCOPES`` name the scopes of
the block's operators; the ones here are what lies between and around
them — the step, the scan over a run of layers, its in-loop casts, the
norm and the residual add on the expert side."""

from __future__ import annotations


def seconds(obs, *scopes: str):
    """Device seconds under ``scopes``; None where the trace names none
    of them (a program without the scope) or gave no scopes."""
    found = obs.get("scopes")
    if not found:
        return None
    hit = [found[s] for s in scopes if s in found]
    return sum(hit) if hit else None


def milliseconds(obs, *scopes: str):
    secs = seconds(obs, *scopes)
    return None if secs is None else secs * 1e3


def ragged_dot_seconds(obs) -> float:
    """Device seconds of XLA's ``ragged-dot`` kernels and of the group
    metadata they are given, by the operations' OWN names: the compiler
    writes them with the ``tf_op`` path ``ragged-dot-none``, so no scope
    of the program reaches them and they lie under ``other``."""
    trace = obs.get("trace")
    secs = trace.seconds_of("ragged-dot") if trace is not None else None
    return secs or 0.0
