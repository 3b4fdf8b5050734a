"""Layer "kernels": of the factor-line copies the fused gather→Gram
kernel STARTS per iteration, the share that fetch a row somebody rated,
in percent: 100 × ``kernel_real_rows`` ÷ ``kernel_dma_rows`` of the
program's ``als.prepare`` span (``kernel_dma_rows``: what the program
says its kernel starts for the bucket rows' real lengths; 100 when it
fetches no padding). Beside it, ``gram_real_rows_pct`` stays the
layout's padding, which still streams as index and weights. None where the
program has no such counter (before PR 25 the kernel copied every
padded slot), where the fused mode did not run (``gram`` of
``als.iterate``) or no bucket is wide enough for the kernel."""

import spans

FUSED = ("pallas", "interpret")


def read(obs):
    tree = spans.tree_of(obs)
    if spans.attr_of(tree, "als.iterate", "gram") not in FUSED:
        return None
    real = spans.attr_of(tree, "als.prepare", "kernel_real_rows")
    dma = spans.attr_of(tree, "als.prepare", "kernel_dma_rows")
    if real is None or not dma:
        return None
    return 100.0 * real / dma
