"""Layer "kernels": the pairs the block rule leaves over the pairs
inside the tiles its kernels visit, both streams, in percent (the
``seqrec.pack`` span's counters ``attn_pairs_bd`` ÷
``attn_tile_pairs_bd``, the tiles counted on the host by
``seq_attention.block_tile_pairs``, the function that makes the
kernels' runs of tiles; a causal walk over the two streams'
concatenation would read about 45; None where the program counts no
block rule)."""

import spans


def read(obs):
    tree = spans.tree_of(obs)
    real = spans.attr_of(tree, "seqrec.pack", "attn_pairs_bd")
    over = spans.attr_of(tree, "seqrec.pack", "attn_tile_pairs_bd")
    return None if not over or real is None else 100.0 * real / over
