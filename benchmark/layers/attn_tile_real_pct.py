"""Layer "kernels": real (query, key) pairs over the pairs inside the
tiles attention visits, in percent (the ``seqrec.pack`` span's
counters; None where the program counts no tiles)."""

import spans


def read(obs):
    tree = spans.tree_of(obs)
    real = spans.attr_of(tree, "seqrec.pack", "attn_pairs")
    tile = spans.attr_of(tree, "seqrec.pack", "attn_tile_pairs")
    return None if not tile or real is None else 100.0 * real / tile
