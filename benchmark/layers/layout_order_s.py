"""Layer "layout": summed seconds of the program's
``als.prepare.order`` spans in the traced train (PR 28) — the entities'
count-descending permutations, then per side the stable radix order of
the interactions and the two gathers that follow it
(``models/als.py _stable_order``, ``_bucket_side``). None on a program
that opens no such span (the parent)."""

import spans


def read(obs):
    return spans.seconds_of(spans.tree_of(obs), "als.prepare.order")
