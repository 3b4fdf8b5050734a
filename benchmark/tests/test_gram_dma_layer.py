"""``gram_dma_real_pct`` (PR 25) on made-up span trees: the share of
the kernel's line copies that fetch a real interaction; None — never
an error — on a program without the counter (the parent), on a train
whose Gram is not fused, and on no tree at all."""

import pytest

import harness
from test_span_layers import span


def tree(gram="pallas", **prepare):
    return [
        span("r", None, "train.run", 0, 1000, status="COMPLETED"),
        span("f", "r", "train.fit", 211, 900),
        span("ap", "f", "als.prepare", 211, 511, nnz=100, **prepare),
        span("i1", "f", "als.iterate", 531, 681, iterations=5, gram=gram),
    ]


def read(obs):
    return harness.load_module("layers", "gram_dma_real_pct").read(obs)


@pytest.mark.parametrize("prepare,gram,want", [
    # one copy per real interaction: nothing fetched is padding
    (dict(kernel_real_rows=45, kernel_padded_rows=100,
          kernel_dma_rows=45), "pallas", 100.0),
    # lengths rounded up by the kernel: the rounding shows
    (dict(kernel_real_rows=45, kernel_padded_rows=100,
          kernel_dma_rows=50), "interpret", 90.0),
    # the parent's span: no such counter
    (dict(kernel_real_rows=45, kernel_padded_rows=100), "pallas", None),
    # XLA gather + einsum: no kernel, nothing to read
    (dict(kernel_real_rows=45, kernel_padded_rows=100,
          kernel_dma_rows=45), "off", None),
    # no kernel-width bucket
    (dict(kernel_real_rows=0, kernel_padded_rows=0,
          kernel_dma_rows=0), "pallas", None),
])
def test_reader_on_a_made_up_tree(prepare, gram, want):
    got = read({"spans": tree(gram, **prepare)})
    assert got == (pytest.approx(want) if want is not None else None)


def test_no_tree_reads_none():
    assert read({"spans": []}) is None
