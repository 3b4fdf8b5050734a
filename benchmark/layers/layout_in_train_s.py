"""Layer "layout": seconds of the program's ``als.prepare`` span —
``als_prepare(coo)`` INSIDE the traced verb, where ``als_prepare_s`` is
the benchmark's clock around a second, direct call outside the
window."""

import spans


def read(obs):
    return spans.seconds_of(spans.tree_of(obs), "als.prepare")
