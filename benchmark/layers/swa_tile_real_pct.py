"""Layer "kernels": the pairs a window leaves over the pairs inside the
tiles the window layers' kernels visit, in percent (the ``seqrec.pack``
span's counters ``attn_pairs_window`` ÷ ``attn_tile_pairs_window``, the
tiles counted on the host by ``seq_attention.tile_pairs``, the function
that makes the kernels' intervals; None where the program counts no
window)."""

import spans


def read(obs):
    tree = spans.tree_of(obs)
    real = spans.attr_of(tree, "seqrec.pack", "attn_pairs_window")
    over = spans.attr_of(tree, "seqrec.pack", "attn_tile_pairs_window")
    return None if not over or real is None else 100.0 * real / over
