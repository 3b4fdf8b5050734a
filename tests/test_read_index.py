"""``data/pipeline.py interactions_from_columnar`` (ISSUE 30: first-seen
order without a sort over the events, no copy where nothing is dropped)
held to the function it replaced, ``tests/read_index_oracle.py``: the
same BiMaps, arrays, dtypes and chunk boundaries for every input, and
the path it says it took."""

import numpy as np
import pytest

from predictionio_tpu.data.pipeline import (ColumnarEvents,
                                            interactions_from_columnar)

from read_index_oracle import interactions_from_columnar_oracle

NAN, INF = float("nan"), float("inf")


def _cols(ent, tgt, values=None, name_idx=None, names=("rate",),
          n_ent=None, n_tgt=None):
    """A scan's columns from plain code lists; the tables are
    ``u<k>`` / ``i<k>``, as long as the codes need or as told."""
    ent = np.asarray(ent, np.uint32)
    tgt = np.asarray(tgt, np.uint32)
    n = ent.shape[0]
    n_ent = int(ent.max()) + 1 if n_ent is None else n_ent
    n_tgt = int(tgt.max()) + 1 if n_tgt is None else n_tgt
    return ColumnarEvents(
        entity_idx=ent, target_idx=tgt,
        name_idx=np.asarray([0] * n if name_idx is None else name_idx,
                            np.uint16),
        values=np.asarray([3.5] * n if values is None else values,
                          np.float64),
        times_us=np.arange(n, dtype=np.int64),
        entity_ids=[f"u{k}" for k in range(n_ent)],
        target_ids=[f"i{k}" for k in range(n_tgt)],
        names=list(names))


def _scan_order(raw):
    """Renumber raw ids to first-seen codes, as a scan hands them out."""
    seen = {}
    return [seen.setdefault(int(x), len(seen)) for x in raw]


def _wide(shuffle):
    """70,000 entities (codes past 2**16) over 300 targets."""
    rng = np.random.default_rng(30)
    ent = np.concatenate([np.arange(70_000), rng.integers(0, 70_000, 9_000)])
    tgt = _scan_order(rng.integers(0, 300, ent.shape[0]))
    if shuffle:
        ent = rng.permutation(ent)
    return _cols(ent, tgt, values=rng.integers(1, 11, ent.shape[0]) / 2.0)


def _random_drops():
    rng = np.random.default_rng(3030)
    n = 5_000
    values = rng.integers(1, 11, n) / 2.0
    values[rng.random(n) < 0.3] = NAN
    return _cols(_scan_order(rng.integers(0, 900, n)),
                 _scan_order(rng.integers(0, 120, n)), values=values,
                 name_idx=rng.integers(0, 2, n), names=("rate", "buy"))


PROP = {"rate": "prop"}
#: id → (columns, value_spec, default_spec, densify_e, densify_t, masked)
CASES = {
    "dense_nothing_dropped": lambda: (
        _cols([0, 1, 0, 2, 1, 3], [0, 0, 1, 2, 1, 0],
              values=[1, 2.5, 3, 4, 5, 0.5]),
        PROP, 4.0, "identity", "identity", 0),
    # u0's first event goes, so u1 is seen first; i0 keeps a later one
    "dropped_first_event_reorders": lambda: (
        _cols([0, 1, 0, 2], [0, 1, 1, 0], values=[NAN, 2, 3, 4]),
        PROP, 4.0, "first_pos", "first_pos", 1),
    # dropping a LATER event of an id leaves the order as it was
    "dropped_later_event_keeps_order": lambda: (
        _cols([0, 1, 0, 2], [0, 1, 1, 0], values=[1, 2, NAN, 4]),
        PROP, 4.0, "identity", "identity", 1),
    # u1 and i1 lose their every event: a hole in the kept codes
    "id_with_every_event_dropped": lambda: (
        _cols([0, 1, 2, 1, 0], [0, 1, 0, 1, 2], values=[1, NAN, 3, NAN, 5]),
        PROP, 4.0, "first_pos", "first_pos", 1),
    # the last id's only event dropped: the kept codes are still dense
    "last_id_dropped": lambda: (
        _cols([0, 1, 0, 2], [0, 1, 1, 2], values=[1, 2, 3, INF]),
        PROP, 4.0, "identity", "identity", 1),
    "table_tail_unreferenced": lambda: (
        _cols([0, 1, 1, 2], [0, 0, 1, 1], n_ent=7, n_tgt=5),
        PROP, 4.0, "identity", "identity", 0),
    "table_hole_unreferenced": lambda: (
        _cols([0, 2, 2, 3], [0, 1, 3, 1], n_ent=6, n_tgt=4),
        PROP, 4.0, "first_pos", "first_pos", 0),
    "codes_out_of_scan_order": lambda: (
        _cols([2, 0, 1, 0, 2], [1, 0, 1, 2, 0]),
        PROP, 4.0, "first_pos", "first_pos", 0),
    "first_code_not_zero": lambda: (
        _cols([1, 0, 1], [0, 1, 2]),
        PROP, 4.0, "first_pos", "identity", 0),
    # ratings.csv order: every user's events together, items as met
    "user_sorted": lambda: (
        _cols([0, 0, 0, 1, 1, 2, 2, 2, 2], [0, 1, 2, 1, 3, 0, 4, 2, 5]),
        PROP, 4.0, "identity", "identity", 0),
    "names_mix_prop_and_constants": lambda: (
        _cols([0, 1, 2, 0, 3, 1], [0, 1, 0, 2, 1, 3],
              values=[2.5, NAN, NAN, 4, NAN, 1],
              name_idx=[0, 1, 2, 0, 1, 0], names=("rate", "buy", "view")),
        {"rate": "prop", "buy": 4.0}, 1.0, "identity", "identity", 0),
    "names_mix_with_drop": lambda: (
        _cols([0, 1, 2, 0, 3, 1], [0, 1, 0, 2, 1, 3],
              values=[NAN, NAN, NAN, 4, NAN, 1],
              name_idx=[0, 1, 2, 0, 1, 0], names=("rate", "buy", "view")),
        {"rate": "prop", "buy": 4.0}, 1.0, "first_pos", "first_pos", 1),
    # no name reads the property: a NaN there drops nothing
    "constants_only": lambda: (
        _cols([0, 1, 0, 2], [0, 1, 2, 0], values=[NAN, 1, INF, 2],
              name_idx=[0, 1, 1, 0], names=("buy", "view")),
        {"buy": 4.0}, 0.25, "identity", "identity", 0),
    "nan_and_inf_values": lambda: (
        _cols([0, 1, 2, 3, 4, 1], [0, 1, 2, 3, 0, 2],
              values=[NAN, INF, -INF, 1e39, -0.0, 2]),
        PROP, 4.0, "first_pos", "first_pos", 1),
    "all_dropped": lambda: (
        _cols([0, 1], [0, 0], values=[NAN, INF]),
        PROP, 4.0, "identity", "identity", 1),
    "codes_past_2_16": lambda: (
        _wide(shuffle=False), PROP, 4.0, "identity", "identity", 0),
    "codes_past_2_16_shuffled": lambda: (
        _wide(shuffle=True), PROP, 4.0, "first_pos", "identity", 0),
    "random_drops": lambda: (
        _random_drops(), PROP, 2.0, "first_pos", "first_pos", 1),
    "one_event": lambda: (
        _cols([0], [0], values=[5]), PROP, 4.0, "identity", "identity", 0),
    "no_event": lambda: (
        _cols([], [], n_ent=0, n_tgt=0), PROP, 4.0,
        "identity", "identity", 0),
    "no_event_no_name": lambda: (
        _cols([], [], names=(), n_ent=3, n_tgt=0), None, 1.0,
        "identity", "identity", 0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_index_equals_oracle(case):
    cols, spec, default, densify_e, densify_t, masked = CASES[case]()
    before = [a.copy() for a in (cols.entity_idx, cols.target_idx,
                                 cols.name_idx, cols.values)]
    with np.errstate(over="ignore"):    # 1e39 → inf in float32, both
        new = interactions_from_columnar(cols, spec, default, chunk_size=4)
        old = interactions_from_columnar_oracle(cols, spec, default,
                                                chunk_size=4)

    assert new.index_paths == {"densify_e": densify_e,
                               "densify_t": densify_t, "masked": masked}
    assert new.n_events == old.n_events
    for mine, theirs in ((new.user_ids, old.user_ids),
                         (new.item_ids, old.item_ids)):
        assert list(mine) == list(theirs)       # first-seen order
        assert mine.to_dict() == theirs.to_dict()
    new_chunks, old_chunks = list(new.chunks()), list(old.chunks())
    assert len(new_chunks) == len(old_chunks) == -(-old.n_events // 4)
    for mine, theirs in zip(new_chunks + [new.arrays()],
                            old_chunks + [old.arrays()]):
        for a, b, dtype in zip(mine, theirs,
                               (np.int32, np.int32, np.float32)):
            assert a.dtype == b.dtype == dtype
            assert a.shape == b.shape and a.tobytes() == b.tobytes()
    # a second walk yields the same chunks; the scan's columns (the
    # snapshot cache's arrays) are neither written nor handed out
    assert [c[0].tobytes() for c in new.chunks()] == [
        c[0].tobytes() for c in new_chunks]
    for col, was in zip((cols.entity_idx, cols.target_idx, cols.name_idx,
                         cols.values), before):
        assert col.tobytes() == was.tobytes()
        assert not any(np.may_share_memory(col, a)
                       for chunk in new_chunks for a in chunk)
