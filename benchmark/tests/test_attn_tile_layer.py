"""``attn_tile_real_pct`` (PR 32) on made-up span trees: the real
(query, key) pairs over the pairs inside the tiles attention visits;
None — never an error — on a program that counts no tiles (the
parent) and on no tree at all."""

import pytest

import harness
from test_span_layers import span


def tree(**pack):
    return [
        span("r", None, "train.run", 0, 1000, status="COMPLETED"),
        span("f", "r", "train.fit", 211, 900),
        span("p", "f", "seqrec.pack", 211, 261, **pack),
    ]


def read(obs):
    return harness.load_module("layers", "attn_tile_real_pct").read(obs)


@pytest.mark.parametrize("pack,want", [
    (dict(attn_pairs=300, attn_tile_pairs=1000, attn_dense_pairs=2100), 30.0),
    # every visited pair real: one segment filling its tiles
    (dict(attn_pairs=64, attn_tile_pairs=64, attn_dense_pairs=64), 100.0),
    # the parent's span: real pairs, no count of tiles
    (dict(attn_pairs=300), None),
    (dict(attn_pairs=300, attn_tile_pairs=0), None),
    (dict(), None),
])
def test_reader_on_a_made_up_tree(pack, want):
    got = read({"spans": tree(**pack)})
    assert got == (pytest.approx(want) if want is not None else None)


def test_no_tree_reads_none():
    assert read({"spans": []}) is None
