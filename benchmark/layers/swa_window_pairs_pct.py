"""Layer "packing": the (query, key) pairs a window layer sees over the
pairs a global layer sees, in percent — how hard the window binds on
this store's histories (the ``seqrec.pack`` span's counters
``attn_pairs_window`` ÷ ``attn_pairs``; None where the program counts no
window)."""

import spans


def read(obs):
    tree = spans.tree_of(obs)
    real = spans.attr_of(tree, "seqrec.pack", "attn_pairs_window")
    over = spans.attr_of(tree, "seqrec.pack", "attn_pairs")
    return None if not over or real is None else 100.0 * real / over
