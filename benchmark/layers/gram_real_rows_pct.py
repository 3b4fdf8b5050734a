"""Layer "kernels": of the slots the fused gather→Gram kernel is handed
per iteration, the share that hold an interaction, in percent:
100 × ``kernel_real_rows`` ÷ ``kernel_padded_rows`` of the program's
``als.prepare`` span (counted by the routing predicate ``_make_half``
itself uses). None where the fused mode did not run (``gram`` of
``als.iterate``) or no bucket is wide enough for the kernel."""

import spans

FUSED = ("pallas", "interpret")


def read(obs):
    tree = spans.tree_of(obs)
    if spans.attr_of(tree, "als.iterate", "gram") not in FUSED:
        return None
    real = spans.attr_of(tree, "als.prepare", "kernel_real_rows")
    padded = spans.attr_of(tree, "als.prepare", "kernel_padded_rows")
    if real is None or not padded:
        return None
    return 100.0 * real / padded
