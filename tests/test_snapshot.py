"""Incremental columnar snapshot cache (docs/perf.md "scan cache"):
disk-format validation, every invalidation rule, and snapshot+delta ==
cold-rescan parity across the three columnar backends."""

import datetime as dt
import json
import os

import numpy as np
import pytest

from predictionio_tpu.data import snapshot as snap
from predictionio_tpu.data import store as store_mod
from predictionio_tpu.data.event import Event, parse_event_time
from predictionio_tpu.data.pipeline import ColumnarEvents, concat_columnar

APP = 7


def _t(s):
    return parse_event_time(s)


def _ev(i, name="rate", sec=None):
    sec = i if sec is None else sec
    return Event(event=name, entity_type="user", entity_id=f"u{i % 5}",
                 target_entity_type="item", target_entity_id=f"i{i % 7}",
                 properties={"rating": float(i % 5)},
                 event_time=_t("2026-01-01T00:00:00Z")
                 + dt.timedelta(seconds=sec))


@pytest.fixture(params=["eventlog", "sqlite", "format_sql", "es"])
def store(request, tmp_path):
    """The columnar-scan backends (the memory store has no
    scan_columnar and never reaches the cache layer)."""
    if request.param == "sqlite":
        from predictionio_tpu.data.events import SqliteEventStore

        yield SqliteEventStore(str(tmp_path / "events.db"))
    elif request.param == "format_sql":
        from predictionio_tpu.data.events import SQLEventStore
        from tests.test_sqldialect import FormatSqliteDialect

        yield SQLEventStore(FormatSqliteDialect(str(tmp_path / "f.db")))
    elif request.param == "es":
        from predictionio_tpu.storage.indexed import (ESEventStore,
                                                      IndexedStorageClient)

        s = ESEventStore(IndexedStorageClient(str(tmp_path / "es")))
        yield s
        s.close()
    else:
        try:
            from predictionio_tpu.data.filestore import NativeEventLogStore

            s = NativeEventLogStore(str(tmp_path / "eventlog"))
        except RuntimeError as e:  # no g++ in this environment
            pytest.skip(str(e))
        yield s
        s.close()


@pytest.fixture
def cache(tmp_path, monkeypatch):
    """Point the snapshot cache at a private directory."""
    d = tmp_path / "scan_cache"
    monkeypatch.setenv("PIO_SCAN_CACHE_DIR", str(d))
    monkeypatch.setattr(store_mod, "_scan_cache_override", None)
    return d


class _St:
    """The slice of Storage the cache layer touches."""

    def __init__(self, events):
        self.events = events


def _cached(store, event_names=None, value_key="rating"):
    return store_mod._cached_scan(
        store.scan_columnar, _St(store), APP, None, None, None,
        event_names, value_key)


def _plain(store, event_names=None, value_key="rating"):
    return store.scan_columnar(APP, event_names=event_names,
                               value_key=value_key)


def _hits():
    return store_mod._SNAP_HITS._values.get((), 0.0)


def _misses(reason):
    return store_mod._SNAP_MISSES._values.get((reason,), 0.0)


def _assert_cols_equal(a, b):
    """Array-for-array equality, including vocabulary order."""
    assert a.n == b.n
    assert (a.entity_idx == b.entity_idx).all()
    assert (a.target_idx == b.target_idx).all()
    assert (a.name_idx == b.name_idx).all()
    assert (a.times_us == b.times_us).all()
    av, bv = np.asarray(a.values), np.asarray(b.values)
    assert ((av == bv) | (np.isnan(av) & np.isnan(bv))).all()
    assert list(a.entity_ids) == list(b.entity_ids)
    assert list(a.target_ids) == list(b.target_ids)
    assert list(a.names) == list(b.names)


# -- disk format (backend-independent) ----------------------------------------


def _cols():
    return ColumnarEvents(
        entity_idx=np.array([0, 1, 0], np.uint32),
        target_idx=np.array([0, 0, 1], np.uint32),
        name_idx=np.array([0, 0, 1], np.uint16),
        values=np.array([1.0, np.nan, 3.0], np.float64),
        times_us=np.array([10, 20, 30], np.int64),
        entity_ids=["u1", "ü∞"], target_ids=["i1", "i2"],
        names=["rate", "buy"])


class TestDiskFormat:
    KEY = "k" * 64

    def test_roundtrip(self, tmp_path):
        d = str(tmp_path)
        assert snap.save_snapshot(d, self.KEY, _cols(), 123, 3)
        got = snap.load_snapshot(d, self.KEY)
        assert got is not None
        cols, man = got
        _assert_cols_equal(cols, _cols())
        assert man.watermark_us == 123 and man.pre_count == 3
        assert man.n_rows == 3 and man.schema == snap.SCHEMA_VERSION

    def test_missing_is_none(self, tmp_path):
        assert snap.load_snapshot(str(tmp_path), self.KEY) is None

    def test_fingerprint_mismatch(self, tmp_path):
        d = str(tmp_path)
        snap.save_snapshot(d, self.KEY, _cols(), 1, 3)
        assert snap.load_snapshot(d, "x" * 64) is None

    def test_schema_bump(self, tmp_path):
        d = str(tmp_path)
        snap.save_snapshot(d, self.KEY, _cols(), 1, 3)
        _npz, man_path = snap._paths(d, self.KEY)
        doc = json.load(open(man_path))
        doc["schema"] = snap.SCHEMA_VERSION + 1
        json.dump(doc, open(man_path, "w"))
        assert snap.load_snapshot(d, self.KEY) is None

    def test_truncated_npz(self, tmp_path):
        d = str(tmp_path)
        snap.save_snapshot(d, self.KEY, _cols(), 1, 3)
        npz_path, _man = snap._paths(d, self.KEY)
        raw = open(npz_path, "rb").read()
        open(npz_path, "wb").write(raw[: len(raw) // 2])
        assert snap.load_snapshot(d, self.KEY) is None

    def test_row_count_mismatch(self, tmp_path):
        d = str(tmp_path)
        snap.save_snapshot(d, self.KEY, _cols(), 1, 3)
        _npz, man_path = snap._paths(d, self.KEY)
        doc = json.load(open(man_path))
        doc["n_rows"] = 99
        json.dump(doc, open(man_path, "w"))
        assert snap.load_snapshot(d, self.KEY) is None

    def test_index_out_of_bounds(self, tmp_path):
        d = str(tmp_path)
        bad = _cols()
        bad.entity_idx = np.array([0, 5, 0], np.uint32)  # 5 ≥ 2 ids
        snap.save_snapshot(d, self.KEY, bad, 1, 3)
        assert snap.load_snapshot(d, self.KEY) is None

    def test_update_manifest_advances_watermark(self, tmp_path):
        d = str(tmp_path)
        snap.save_snapshot(d, self.KEY, _cols(), 10, 3)
        assert snap.update_manifest(d, self.KEY, 20, 5, 3)
        _cols2, man = snap.load_snapshot(d, self.KEY)
        assert man.watermark_us == 20 and man.pre_count == 5

    def test_fingerprint_sensitivity(self):
        base = snap.filter_fingerprint("id", 1, None, None, None,
                                       ["rate"], "rating")
        for variant in (
            snap.filter_fingerprint("id2", 1, None, None, None,
                                    ["rate"], "rating"),
            snap.filter_fingerprint("id", 2, None, None, None,
                                    ["rate"], "rating"),
            snap.filter_fingerprint("id", 1, 3, None, None,
                                    ["rate"], "rating"),
            snap.filter_fingerprint("id", 1, None, "user", None,
                                    ["rate"], "rating"),
            snap.filter_fingerprint("id", 1, None, None, None,
                                    ["rate", "buy"], "rating"),
            snap.filter_fingerprint("id", 1, None, None, None,
                                    ["rate"], None),
        ):
            assert variant != base


# -- the column file (schema 3): one buffer, verified where it lies ------------


def _counter(c):
    return c._values.get(("snapshot",), 0.0)


def _wide_cols(n=1000):
    """Every dtype with more than a line of bytes a column."""
    rng = np.random.default_rng(42)
    return ColumnarEvents(
        entity_idx=(np.arange(n) % 37).astype(np.uint32),
        target_idx=(np.arange(n) % 11).astype(np.uint32),
        name_idx=(np.arange(n) % 2).astype(np.uint16),
        values=rng.integers(1, 11, n) / 2.0,
        times_us=np.arange(n, dtype=np.int64) + 1_700_000_000_000_000,
        entity_ids=[f"user-{k}" for k in range(37)],
        target_ids=[f"ü∞{k}" for k in range(11)], names=["rate", "buy"])


def _load_traced(d, key):
    """``load_snapshot`` under a span: what it returned, and the
    counters it left on the span."""
    from predictionio_tpu.utils import tracing

    with tracing.verb("snapshot.test"):
        with tracing.span("storage.scan.load") as sp:
            got = snap.load_snapshot(d, key)
        return got, dict(sp.attrs)


class TestColumnFile:
    KEY = "c" * 64
    SECTIONS = (snap._HEADER, *snap._ARRAY_FIELDS, *snap._TABLE_FIELDS)

    def _saved(self, tmp_path, cols=None):
        d = str(tmp_path)
        assert snap.save_snapshot(d, self.KEY, cols or _wide_cols(), 5, 1000)
        path, _man = snap._paths(d, self.KEY)
        with open(path, "rb") as f:
            raw = f.read()
        return d, path, raw

    def test_columns_are_views_of_one_verified_buffer(self, tmp_path):
        d, path, raw = self._saved(tmp_path)
        got, attrs = _load_traced(d, self.KEY)
        cols, man = got
        _assert_cols_equal(cols, _wide_cols())
        assert man.schema == snap.SCHEMA_VERSION == 3
        buffers = set()
        for k in snap._ARRAY_FIELDS:
            a = getattr(cols, k)
            assert a.dtype == np.dtype(snap._DTYPES[k])
            assert not a.flags.writeable and not a.flags.owndata
            assert a.flags.aligned and a.flags.c_contiguous
            assert a.ctypes.data % 64 == 0
            buffers.add(id(a.base.obj))
            with pytest.raises(ValueError):
                a[0] = 1
        assert len(buffers) == 1                    # ONE buffer, mapped
        assert attrs == {"schema": 3, "mapped": 1, "copied_bytes": 0,
                         "bytes": attrs["bytes"]}
        # every byte of the file is digested or a gap of zeros
        n_rows, where = snap._sections(raw)
        assert n_rows == 1000 and list(where) == list(self.SECTIONS)
        assert attrs["bytes"] == sum(c * t.itemsize
                                     for _at, c, t in where.values())
        assert 0 <= len(raw) - attrs["bytes"] < 64 * len(where)
        assert all(at % 64 == 0 for at, _c, _t in where.values())

    def test_digests_are_those_of_the_arrays_bytes(self, tmp_path):
        import hashlib

        d, _path, _raw = self._saved(tmp_path)
        _cols, man = snap.load_snapshot(d, self.KEY)
        want = _wide_cols()
        for k in snap._ARRAY_FIELDS:
            assert man.digests[k] == hashlib.sha256(
                getattr(want, k).tobytes()).hexdigest()
        for k in snap._TABLE_FIELDS:
            assert man.digests[k] == hashlib.sha256(
                np.asarray(getattr(want, k), np.str_).tobytes()).hexdigest()
        assert set(man.digests) == set(self.SECTIONS)

    @pytest.mark.parametrize("section", SECTIONS)
    def test_flipped_byte_is_a_counted_miss(self, tmp_path, section):
        from predictionio_tpu.utils.integrity import (INTEGRITY_FAILED,
                                                      INTEGRITY_VERIFIED)

        d, path, raw = self._saved(tmp_path)
        at, count, dtype = snap._sections(raw)[1][section]
        damaged = bytearray(raw)
        damaged[at + count * dtype.itemsize // 2] ^= 0x01
        with open(path, "wb") as f:
            f.write(damaged)
        failed, verified = (_counter(INTEGRITY_FAILED),
                            _counter(INTEGRITY_VERIFIED))
        assert snap.load_snapshot(d, self.KEY) is None
        assert _counter(INTEGRITY_FAILED) == failed + 1
        assert _counter(INTEGRITY_VERIFIED) == verified
        with open(path, "wb") as f:                 # and healed: a hit
            f.write(raw)
        assert snap.load_snapshot(d, self.KEY) is not None
        assert _counter(INTEGRITY_VERIFIED) == verified + 1

    @pytest.mark.parametrize("damage", [
        "gap", "tail", "in_header", "in_column", "empty", "foreign"])
    def test_file_not_laid_out_as_saved_is_a_counted_miss(self, tmp_path,
                                                          damage):
        from predictionio_tpu.utils.integrity import INTEGRITY_FAILED

        d, path, raw = self._saved(tmp_path)
        _n, where = snap._sections(raw)
        head_end = where[snap._HEADER][1]
        assert head_end % 64, "the header ends on a line: no gap to test"
        at_values = where["values"][0]
        bad = {
            "gap": raw[:head_end] + b"\x01" + raw[head_end + 1:],
            "tail": raw + b"\x00",
            "in_header": raw[:head_end // 2],
            "in_column": raw[:at_values + 100],
            "empty": b"",
            "foreign": b"PK\x03\x04" + raw[4:],
        }[damage]
        with open(path, "wb") as f:
            f.write(bad)
        failed = _counter(INTEGRITY_FAILED)
        assert snap.load_snapshot(d, self.KEY) is None
        assert _counter(INTEGRITY_FAILED) == failed + 1

    def test_armed_fault_site_copies_and_trips(self, tmp_path):
        from predictionio_tpu.utils import faults
        from predictionio_tpu.utils.integrity import INTEGRITY_FAILED

        d, _path, _raw = self._saved(tmp_path)
        failed = _counter(INTEGRITY_FAILED)
        faults.FAULTS.arm("data.corrupt.snapshot", count=1)
        try:
            assert snap.load_snapshot(d, self.KEY) is None
            assert _counter(INTEGRITY_FAILED) == failed + 1
            assert faults.FAULTS.fired("data.corrupt.snapshot") == 1
            # armed but spent: the buffer is the mapping again
            got, attrs = _load_traced(d, self.KEY)
            assert got is not None and attrs["mapped"] == 1
        finally:
            faults.FAULTS.disarm()

    def test_unmappable_file_is_read_once_into_one_buffer(self, tmp_path,
                                                          monkeypatch):
        import mmap

        d, _path, raw = self._saved(tmp_path)

        def refused(*a, **kw):
            raise OSError("no map on this file system")

        monkeypatch.setattr(mmap, "mmap", refused)
        got, attrs = _load_traced(d, self.KEY)
        cols, _man = got
        _assert_cols_equal(cols, _wide_cols())
        assert attrs["mapped"] == 0 and attrs["copied_bytes"] == len(raw)
        assert not cols.values.flags.writeable
        assert {id(getattr(cols, k).base)
                for k in snap._ARRAY_FIELDS} == {id(cols.values.base)}
        assert isinstance(cols.values.base, bytes)  # the ONE read

    def test_empty_snapshot_roundtrip(self, tmp_path):
        empty = ColumnarEvents(
            entity_idx=np.empty(0, np.uint32),
            target_idx=np.empty(0, np.uint32),
            name_idx=np.empty(0, np.uint16),
            values=np.empty(0, np.float64),
            times_us=np.empty(0, np.int64),
            entity_ids=[], target_ids=[], names=[])
        d = str(tmp_path)
        assert snap.save_snapshot(d, self.KEY, empty, 1, 0)
        cols, man = snap.load_snapshot(d, self.KEY)
        assert cols.n == 0 and man.n_rows == 0
        assert cols.entity_ids == cols.target_ids == cols.names == []

    def test_columns_of_another_dtype_are_not_saved(self, tmp_path):
        cols = _wide_cols()
        cols.entity_idx = cols.entity_idx.astype(np.int64)
        assert not snap.save_snapshot(str(tmp_path), self.KEY, cols, 1, 1000)
        assert os.listdir(tmp_path) == []

    def test_strided_columns_are_saved_contiguous(self, tmp_path):
        cols = _wide_cols(2000)
        for k in snap._ARRAY_FIELDS:
            setattr(cols, k, getattr(cols, k)[::2])
        d, _path, _raw = self._saved(tmp_path, cols)
        got, _man = snap.load_snapshot(d, self.KEY)
        _assert_cols_equal(got, cols)
        assert got.values.flags.c_contiguous


# -- concat_columnar ----------------------------------------------------------


class TestConcat:
    def test_remaps_delta_into_base_tables(self):
        base = _cols()
        delta = ColumnarEvents(
            entity_idx=np.array([0, 1], np.uint32),
            target_idx=np.array([0, 1], np.uint32),
            name_idx=np.array([0, 1], np.uint16),
            values=np.array([7.0, 8.0], np.float64),
            times_us=np.array([40, 50], np.int64),
            entity_ids=["ü∞", "u9"],        # ü∞ already in base (idx 1)
            target_ids=["i2", "i1"],        # both shared, swapped order
            names=["view", "rate"])         # one new, one shared
        m = concat_columnar(base, delta)
        assert m.n == 5
        assert m.entity_ids == ["u1", "ü∞", "u9"]
        assert m.target_ids == ["i1", "i2"]
        assert m.names == ["rate", "buy", "view"]
        assert m.entity_idx.tolist() == [0, 1, 0, 1, 2]
        assert m.target_idx.tolist() == [0, 0, 1, 1, 0]
        assert m.name_idx.tolist() == [0, 0, 1, 2, 0]
        assert m.times_us.tolist() == [10, 20, 30, 40, 50]

    def test_empty_sides(self):
        base, empty = _cols(), ColumnarEvents(
            entity_idx=np.empty(0, np.uint32),
            target_idx=np.empty(0, np.uint32),
            name_idx=np.empty(0, np.uint16),
            values=np.empty(0, np.float64),
            times_us=np.empty(0, np.int64),
            entity_ids=[], target_ids=[], names=[])
        assert concat_columnar(base, empty) is base
        assert concat_columnar(empty, base) is base

    def test_name_table_overflow_declines(self):
        base = _cols()
        delta = ColumnarEvents(
            entity_idx=np.zeros(1, np.uint32),
            target_idx=np.zeros(1, np.uint32),
            name_idx=np.zeros(1, np.uint16),
            values=np.zeros(1, np.float64),
            times_us=np.array([40], np.int64),
            entity_ids=["u1"], target_ids=["i1"],
            names=[f"n{i}" for i in range(65535)])
        assert concat_columnar(base, delta) is None


# -- cache policy over real backends ------------------------------------------


class TestCachedScan:
    def test_cold_build_then_warm_hit(self, store, cache):
        store.insert_batch([_ev(i) for i in range(20)], APP)
        h0, m0 = _hits(), _misses("cold")
        cold = _cached(store)
        assert _misses("cold") == m0 + 1
        _assert_cols_equal(cold, _plain(store))
        assert any(f.endswith(".cols") for f in os.listdir(cache))
        warm = _cached(store)
        assert _hits() == h0 + 1
        _assert_cols_equal(warm, cold)

    def test_delta_append_parity(self, store, cache):
        store.insert_batch([_ev(i) for i in range(20)], APP)
        _cached(store)
        store.insert_batch([_ev(i) for i in range(20, 30)], APP)
        d0 = store_mod._SNAP_DELTA_ROWS._values.get((), 0.0)
        merged = _cached(store)
        assert store_mod._SNAP_DELTA_ROWS._values.get((), 0.0) == d0 + 10
        _assert_cols_equal(merged, _plain(store))
        # and the merged snapshot itself re-serves identically
        _assert_cols_equal(_cached(store), _plain(store))

    def test_filter_key_isolation(self, store, cache):
        store.insert_batch([_ev(i) for i in range(10)], APP)
        store.insert_batch([_ev(i, name="buy", sec=100 + i)
                            for i in range(5)], APP)
        a = _cached(store, event_names=["rate"])
        b = _cached(store, event_names=["buy"])
        _assert_cols_equal(a, _plain(store, event_names=["rate"]))
        _assert_cols_equal(b, _plain(store, event_names=["buy"]))
        # two distinct snapshots on disk, and each warm-load stays true
        assert sum(f.endswith(".cols") for f in os.listdir(cache)) == 2
        _assert_cols_equal(_cached(store, event_names=["rate"]), a)
        _assert_cols_equal(_cached(store, event_names=["buy"]), b)

    def test_filtered_out_delta_still_advances_watermark(self, store, cache):
        store.insert_batch([_ev(i) for i in range(10)], APP)
        _cached(store, event_names=["rate"])
        key = snap.filter_fingerprint(
            store.cache_identity, APP, None, None, None, ["rate"], "rating")
        _cols0, man0 = snap.load_snapshot(str(cache), key)
        store.insert_batch([_ev(i, name="view", sec=100 + i)
                            for i in range(3)], APP)
        h0 = _hits()
        _cached(store, event_names=["rate"])  # delta scans 0 matching rows
        assert _hits() == h0 + 1
        _cols1, man1 = snap.load_snapshot(str(cache), key)
        assert man1.watermark_us > man0.watermark_us

    def test_corrupt_npz_falls_back(self, store, cache):
        store.insert_batch([_ev(i) for i in range(12)], APP)
        _cached(store)
        npz = next(str(cache / f) for f in os.listdir(cache)
                   if f.endswith(".cols"))
        open(npz, "wb").write(b"not a zipfile")
        m0 = _misses("cold")
        again = _cached(store)
        assert _misses("cold") == m0 + 1  # corrupt == cold, never wrong
        _assert_cols_equal(again, _plain(store))
        # the rescan re-primed the cache
        h0 = _hits()
        _cached(store)
        assert _hits() == h0 + 1

    def test_schema2_pair_is_a_cold_miss_rebuilt_as_schema3(self, store,
                                                             cache):
        """What an older tree left — ``snap_<key>.npz`` under a schema-2
        manifest — is never read: cold miss, rebuilt, then a hit."""
        import hashlib

        store.insert_batch([_ev(i) for i in range(12)], APP)
        truth = _plain(store)
        key = snap.filter_fingerprint(
            store.cache_identity, APP, None, None, None, None, "rating")
        os.makedirs(cache)
        arrays = {k: getattr(truth, k) for k in snap._ARRAY_FIELDS}
        arrays.update((k, np.asarray(getattr(truth, k), np.str_))
                      for k in snap._TABLE_FIELDS)
        old = snap.legacy_path(str(cache), key)
        with open(old, "wb") as f:
            np.savez(f, **arrays)
        count, max_c = store.creation_stats(APP, None)
        with open(cache / f"snap_{key}.json", "w") as f:
            json.dump({"schema": 2, "filter": key, "watermark_us": max_c,
                       "pre_count": count, "n_rows": truth.n,
                       "created_at": 0.0,
                       "digests": {k: hashlib.sha256(a.tobytes()).hexdigest()
                                   for k, a in arrays.items()}}, f)
        assert snap.load_snapshot(str(cache), key) is None
        m0, h0 = _misses("cold"), _hits()
        _assert_cols_equal(_cached(store), truth)
        assert _misses("cold") == m0 + 1 and _hits() == h0
        assert sorted(os.listdir(cache)) == [f"snap_{key}.cols",
                                             f"snap_{key}.json"]
        assert json.load(open(cache / f"snap_{key}.json"))["schema"] == 3
        _assert_cols_equal(_cached(store), truth)
        assert _hits() == h0 + 1

    def test_delta_over_mapped_columns_equals_a_full_rescan(self, store,
                                                            cache):
        from predictionio_tpu.utils import tracing

        store.insert_batch([_ev(i) for i in range(40)], APP)
        _cached(store)
        key = snap.filter_fingerprint(
            store.cache_identity, APP, None, None, None, None, "rating")
        path, _man = snap._paths(str(cache), key)
        on_disk = open(path, "rb").read()
        store.insert_batch([_ev(i) for i in range(40, 43)], APP)
        with tracing.verb("snapshot.test"):
            with tracing.span("storage.scan") as sp:
                merged = _cached(store)
        assert sp.attrs["scan_cache"] == "hit:delta"
        _assert_cols_equal(merged, _plain(store))
        assert merged.entity_idx.flags.writeable    # a fresh array
        # 3 rows of 40: no compaction, and the mapped base was not
        # written through (it could not be: its pages are read-only)
        assert open(path, "rb").read() == on_disk

    def test_delete_invalidates(self, store, cache):
        ids = store.insert_batch([_ev(i) for i in range(15)], APP)
        _cached(store)
        assert store.delete(ids[3], APP)
        m0 = _misses("mutated")
        after = _cached(store)
        assert _misses("mutated") == m0 + 1
        _assert_cols_equal(after, _plain(store))

    def test_out_of_order_event_falls_back(self, store, cache):
        store.insert_batch([_ev(i) for i in range(10)], APP)
        _cached(store)
        # arrives later (new creationTime) but SORTS before the
        # snapshot's last event — appending would break scan order
        store.insert(_ev(99, sec=-50), APP)
        m0 = _misses("out_of_order")
        after = _cached(store)
        assert _misses("out_of_order") == m0 + 1
        _assert_cols_equal(after, _plain(store))

    def test_empty_store_then_grow(self, store, cache):
        empty = _cached(store)
        assert empty.n == 0
        store.insert_batch([_ev(i) for i in range(5)], APP)
        grown = _cached(store)
        _assert_cols_equal(grown, _plain(store))

    def test_unsupported_backend_passes_through(self, store, cache):
        class _NoStats:
            cache_identity = None

            def __init__(self, inner):
                self._inner = inner

            def creation_stats(self, *a, **kw):
                return None

            def scan_columnar(self, *a, **kw):
                return self._inner.scan_columnar(*a, **kw)

        store.insert_batch([_ev(i) for i in range(8)], APP)
        wrapped = _NoStats(store)
        m0 = _misses("unsupported")
        out = store_mod._cached_scan(
            wrapped.scan_columnar, _St(wrapped), APP, None, None, None,
            None, "rating")
        assert _misses("unsupported") == m0 + 1
        _assert_cols_equal(out, _plain(store))
        assert not os.path.exists(cache) or not os.listdir(cache)

    def test_time_window_bypasses_cache(self, store, cache):
        store.insert_batch([_ev(i) for i in range(10)], APP)
        out = store_mod._scan_with_cache(
            store.scan_columnar, _St(store), APP, None,
            _t("2026-01-01T00:00:03Z"), None, None, None, None, "rating")
        assert out.n == 7  # startTime honored
        assert not os.path.exists(cache) or not os.listdir(cache)

    def test_disabled_cache_bypasses(self, store, cache):
        store.insert_batch([_ev(i) for i in range(10)], APP)
        prev = store_mod.set_scan_cache(False)
        try:
            out = store_mod._scan_with_cache(
                store.scan_columnar, _St(store), APP, None, None, None,
                None, None, None, "rating")
            _assert_cols_equal(out, _plain(store))
            assert not os.path.exists(cache) or not os.listdir(cache)
        finally:
            store_mod.set_scan_cache(prev)


class TestSetScanCache:
    def test_override_and_env(self, monkeypatch):
        monkeypatch.setattr(store_mod, "_scan_cache_override", None)
        monkeypatch.delenv("PIO_SCAN_CACHE", raising=False)
        assert store_mod.scan_cache_enabled()
        monkeypatch.setenv("PIO_SCAN_CACHE", "0")
        assert not store_mod.scan_cache_enabled()
        prev = store_mod.set_scan_cache(True)
        assert prev is None and store_mod.scan_cache_enabled()
        store_mod.set_scan_cache(prev)
        assert not store_mod.scan_cache_enabled()


class TestESCoverageRule:
    def test_numeric_stats_declines_partial_coverage(self):
        """Old-format ES docs (no creationTimeUs) must disable the
        cache, not miscount it."""
        from predictionio_tpu.storage.indexed import EmbeddedIndex

        idx = EmbeddedIndex()
        idx.index("a", {"creationTimeUs": 10.0})
        idx.index("b", {"creationTimeUs": 20.0})
        assert idx.numeric_stats("creationTimeUs") == (2, 20)
        assert idx.numeric_stats("creationTimeUs", until=10.0) == (1, 10)
        assert idx.numeric_stats("creationTimeUs", until=5.0) == (0, None)
        idx.index("c", {"other": 1.0})  # doc without the field
        assert idx.numeric_stats("creationTimeUs") is None

    def test_empty_index(self):
        from predictionio_tpu.storage.indexed import EmbeddedIndex

        assert EmbeddedIndex().numeric_stats("creationTimeUs") == (0, None)
