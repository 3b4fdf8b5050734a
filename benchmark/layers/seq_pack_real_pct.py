"""Layer "packing": real tokens over slots of the packed sequences, in
percent (the ``seqrec.pack`` span's counters)."""

import spans


def read(obs):
    tree = spans.tree_of(obs)
    real = spans.attr_of(tree, "seqrec.pack", "real_tokens")
    slots = spans.attr_of(tree, "seqrec.pack", "slots")
    return None if not slots or real is None else 100.0 * real / slots
