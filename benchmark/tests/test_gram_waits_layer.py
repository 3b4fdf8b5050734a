"""``gram_waits_per_copy_pct`` (PR 37) on made-up span trees: the DMA
waits the kernel makes per line copy it starts; None — never an error —
on a program without the counter (the parent), on a train whose Gram is
not fused, and on no tree at all."""

import pytest

import harness
from test_gram_dma_layer import tree


def read(obs):
    return harness.load_module("layers", "gram_waits_per_copy_pct").read(obs)


@pytest.mark.parametrize("prepare,gram,want", [
    # a wait retires a group of copies
    (dict(kernel_real_rows=400, kernel_dma_rows=400, kernel_dma_waits=20),
     "pallas", 5.0),
    # one wait a copy: what the parent's kernel would count
    (dict(kernel_real_rows=45, kernel_dma_rows=45, kernel_dma_waits=45),
     "interpret", 100.0),
    # the parent's span: no such counter
    (dict(kernel_real_rows=45, kernel_dma_rows=45), "pallas", None),
    # XLA gather + einsum: no kernel, nothing to read
    (dict(kernel_real_rows=45, kernel_dma_rows=45, kernel_dma_waits=5),
     "off", None),
    # no kernel-width bucket
    (dict(kernel_real_rows=0, kernel_dma_rows=0, kernel_dma_waits=0),
     "pallas", None),
])
def test_reader_on_a_made_up_tree(prepare, gram, want):
    got = read({"spans": tree(gram, **prepare)})
    assert got == (pytest.approx(want) if want is not None else None)


def test_no_tree_reads_none():
    assert read({"spans": []}) is None
