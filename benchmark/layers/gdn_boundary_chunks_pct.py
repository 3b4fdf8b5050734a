"""Layer "packing": the chunks of the recurrence's scan that hold a
segment's first row after their own — where the state is reset INSIDE
the chunk, by masks — over all the chunks the scan walks, in percent
(the ``seqrec.pack`` span's counters ``gdn_boundary_chunks`` ÷
``gdn_chunks``; None where the program counts no chunks)."""

import spans


def read(obs):
    tree = spans.tree_of(obs)
    inside = spans.attr_of(tree, "seqrec.pack", "gdn_boundary_chunks")
    chunks = spans.attr_of(tree, "seqrec.pack", "gdn_chunks")
    return None if not chunks or inside is None else 100.0 * inside / chunks
