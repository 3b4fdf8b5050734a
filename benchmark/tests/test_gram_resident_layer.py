"""``gram_resident_rows_pct`` (PR 41) on made-up span trees: the share
of the kernel's factor lines read from a table the dispatch holds in
VMEM; None — never an error — on a program without the counter (the
parent), on a train whose Gram is not fused, and on no tree at all."""

import pytest

import harness
from test_gram_dma_layer import tree


def read(obs):
    return harness.load_module("layers", "gram_resident_rows_pct").read(obs)


@pytest.mark.parametrize("prepare,gram,want", [
    # both sides' tables fit: every line a vector load
    (dict(kernel_real_rows=400, kernel_dma_rows=400, kernel_dma_waits=0,
          kernel_resident_rows=400), "pallas", 100.0),
    # one side's table fits
    (dict(kernel_real_rows=400, kernel_dma_rows=400, kernel_dma_waits=9,
          kernel_resident_rows=290), "interpret", 72.5),
    # neither fits: every line copied, and the share is a number
    (dict(kernel_real_rows=45, kernel_dma_rows=45, kernel_dma_waits=5,
          kernel_resident_rows=0), "pallas", 0.0),
    # the parent's span: no such counter
    (dict(kernel_real_rows=45, kernel_dma_rows=45, kernel_dma_waits=5),
     "pallas", None),
    # XLA gather + einsum: no kernel, nothing to read
    (dict(kernel_real_rows=45, kernel_resident_rows=45), "off", None),
    # no kernel-width bucket
    (dict(kernel_real_rows=0, kernel_dma_rows=0, kernel_resident_rows=0),
     "pallas", None),
])
def test_reader_on_a_made_up_tree(prepare, gram, want):
    got = read({"spans": tree(gram, **prepare)})
    assert got == (pytest.approx(want) if want is not None else None)


def test_no_tree_reads_none():
    assert read({"spans": []}) is None
