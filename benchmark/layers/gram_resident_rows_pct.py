"""Layer "kernels": the share of the fused gather→Gram kernel's factor
lines that it reads from a table held in VMEM for the dispatch — a
vector load a line, no copy, no wait — in percent: 100 ×
``kernel_resident_rows`` ÷ ``kernel_real_rows`` of the program's
``als.prepare`` span (``kernel_resident_rows``: the real interactions of
the kernel buckets on a side whose gathered table
``ops/gram.table_is_resident`` keeps in VMEM, the predicate the kernel
branches on). 100 where both sides' tables fit, 0 where neither does.
None where the program has no such counter (before PR 41 every line
was copied), where the fused mode did not run (``gram`` of
``als.iterate``) or no bucket is wide enough for the kernel."""

import spans

FUSED = ("pallas", "interpret")


def read(obs):
    tree = spans.tree_of(obs)
    if spans.attr_of(tree, "als.iterate", "gram") not in FUSED:
        return None
    held = spans.attr_of(tree, "als.prepare", "kernel_resident_rows")
    real = spans.attr_of(tree, "als.prepare", "kernel_real_rows")
    if held is None or not real:
        return None
    return 100.0 * held / real
