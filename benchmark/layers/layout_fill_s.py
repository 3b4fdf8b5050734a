"""Layer "layout": summed seconds of the program's ``als.prepare.fill``
spans in the traced train (PR 28), one per side — every bucket's
``other_idx``, ``vals`` and ``mask`` written through its rows' prefix
mask (``models/als.py _fill_rows``), and the segmented bucket's one-hot
``seg``. None on a program that opens no such span (the parent)."""

import spans


def read(obs):
    return spans.seconds_of(spans.tree_of(obs), "als.prepare.fill")
