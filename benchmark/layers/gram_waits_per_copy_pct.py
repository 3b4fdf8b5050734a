"""Layer "kernels": the DMA waits the fused gather→Gram kernel makes per
factor-line copy it starts, in percent: 100 × ``kernel_dma_waits`` ÷
``kernel_dma_rows`` of the program's ``als.prepare`` span
(``kernel_dma_waits``: counted on the host from the bucket rows' real
lengths by ``ops/gram.dma_waits``, the function the kernel takes its
group sizes from). A kernel that retires every copy with a wait of its
own reads 100; one wait for a full tile of 256 copies reads 0.4. None
where the program has no such counter (before PR 37 one wait retired
one copy), where the fused mode did not run (``gram`` of
``als.iterate``) or no bucket is wide enough for the kernel."""

import spans

FUSED = ("pallas", "interpret")


def read(obs):
    tree = spans.tree_of(obs)
    if spans.attr_of(tree, "als.iterate", "gram") not in FUSED:
        return None
    waits = spans.attr_of(tree, "als.prepare", "kernel_dma_waits")
    copies = spans.attr_of(tree, "als.prepare", "kernel_dma_rows")
    if waits is None or not copies:
        return None
    return 100.0 * waits / copies
