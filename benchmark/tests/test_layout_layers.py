"""``layout_order_s`` and ``layout_fill_s`` (PR 28) on made-up span
trees: the children of ``als.prepare`` summed by name over both sides;
None — never an error — on a program whose ``als.prepare`` is a leaf
(the parent), and on no tree at all. And what the children leave of
their parent: ``spans.untraced_seconds`` subtracts LEAF spans only, so
whatever ``als.prepare`` does outside its children is host time no
span names."""

import pytest

import harness
import spans
from test_span_layers import span


def tree(children=True):
    kids = [
        span("o0", "ap", "als.prepare.order", 211, 221),      # the perms
        span("o1", "ap", "als.prepare.order", 221, 301),      # user side
        span("d1", "ap", "als.prepare.dense", 301, 311),
        span("f1", "ap", "als.prepare.fill", 311, 341),
        span("o2", "ap", "als.prepare.order", 341, 441),      # item side
        span("d2", "ap", "als.prepare.dense", 441, 481),
        span("f2", "ap", "als.prepare.fill", 481, 506),
    ] if children else []
    return [
        span("r", None, "train.run", 0, 1000, status="COMPLETED"),
        span("f", "r", "train.fit", 211, 1000),
        span("ap", "f", "als.prepare", 211, 511, nnz=100, radix_passes_u=2,
             radix_passes_i=1, dense_fill_u="assign", dense_fill_i="assign"),
        *kids,
        span("i1", "f", "als.iterate", 511, 1000, iterations=10,
             gram="pallas"),
    ]


def read(name, obs):
    return harness.load_module("layers", name).read(obs)


@pytest.mark.parametrize("name,want", [
    ("layout_order_s", 0.190),       # 10 + 80 + 100 ms
    ("layout_fill_s", 0.055),        # 30 + 25 ms
])
def test_reader_sums_both_sides(name, want):
    assert read(name, {"spans": tree()}) == pytest.approx(want)


@pytest.mark.parametrize("name", ["layout_order_s", "layout_fill_s"])
def test_reader_gives_none_without_the_spans(name):
    assert read(name, {"spans": tree(children=False)}) is None
    assert read(name, {"spans": []}) is None


def test_the_parent_span_keeps_its_length_and_its_rest_is_untraced():
    with_kids, leaf = {"spans": tree()}, {"spans": tree(children=False)}
    assert read("layout_in_train_s", with_kids) == pytest.approx(0.300)
    assert read("layout_in_train_s", leaf) == pytest.approx(0.300)
    # als.prepare a leaf: train.run 0-211 is all that no leaf covers
    assert spans.untraced_seconds(leaf["spans"]) == pytest.approx(0.211)
    # with children: als.prepare 506-511 lies in none of them either
    assert spans.untraced_seconds(with_kids["spans"]) == pytest.approx(0.216)
