"""Native (C++) event-log engine specifics: durability, index rebuild,
and the native $set/$unset/$delete fold vs the Python reference fold."""

import datetime as dt
import json
import time
import numpy as np

import pytest

from predictionio_tpu.data.event import Event, aggregate_properties, parse_event_time


def _t(s):
    return parse_event_time(s)


@pytest.fixture
def store(tmp_path):
    from predictionio_tpu.data.filestore import NativeEventLogStore

    try:
        s = NativeEventLogStore(str(tmp_path / "log"))  # builds the engine
    except RuntimeError as e:  # no g++ in this environment
        pytest.skip(str(e))
    yield s
    s.close()


APP = 1


def test_reopen_rebuilds_index(tmp_path, store):
    ids = store.insert_batch(
        [Event(event="rate", entity_type="user", entity_id=str(i),
               target_entity_type="item", target_entity_id="x",
               properties={"rating": float(i)},
               event_time=_t(f"2026-01-0{i+1}T00:00:00Z"))
         for i in range(3)],
        APP)
    store.delete(ids[1], APP)
    store.close()

    from predictionio_tpu.data.filestore import NativeEventLogStore

    s2 = NativeEventLogStore(str(tmp_path / "log"))
    evs = list(s2.find(APP))
    assert [e.event_id for e in evs] == [ids[0], ids[2]]
    assert s2.get(ids[1], APP) is None
    assert s2.get(ids[2], APP).properties == {"rating": 2.0}
    s2.close()


def test_overwrite_by_id(store):
    e = Event(event="$set", entity_type="user", entity_id="u",
              properties={"a": 1}, event_time=_t("2026-01-01T00:00:00Z"))
    eid = store.insert(e, APP)
    e2 = Event(event_id=eid, event="$set", entity_type="user", entity_id="u",
               properties={"a": 2}, event_time=_t("2026-01-01T00:00:00Z"))
    store.insert(e2, APP)
    evs = list(store.find(APP))
    assert len(evs) == 1 and evs[0].properties == {"a": 2}


def test_nul_and_unicode_roundtrip(store):
    e = Event(event="note", entity_type="user", entity_id="ué中",
              properties={"text": 'quote " backslash \\ newline \n tab \t',
                          "nested": {"k": [1, 2, {"d": None}]},
                          "num": 1.5, "bool": True},
              event_time=_t("2026-01-01T00:00:00Z"))
    eid = store.insert(e, APP)
    got = store.get(eid, APP)
    assert got.entity_id == "ué中"
    assert got.properties == e.properties


def test_native_fold_matches_python_fold(store):
    evs = [
        Event(event="$set", entity_type="user", entity_id="a",
              properties={"x": 1, "name": "A"},
              event_time=_t("2026-01-01T00:00:00Z")),
        Event(event="$set", entity_type="user", entity_id="a",
              properties={"x": 2, "y": [1, 2]},
              event_time=_t("2026-01-03T00:00:00Z")),
        Event(event="$unset", entity_type="user", entity_id="a",
              properties={"name": None},
              event_time=_t("2026-01-04T00:00:00Z")),
        Event(event="$set", entity_type="user", entity_id="b",
              properties={"deep": {"n": {"m": "q\"uote"}}},
              event_time=_t("2026-01-02T00:00:00Z")),
        Event(event="$set", entity_type="user", entity_id="gone",
              properties={"z": 1}, event_time=_t("2026-01-02T00:00:00Z")),
        Event(event="$delete", entity_type="user", entity_id="gone",
              event_time=_t("2026-01-05T00:00:00Z")),
        Event(event="rate", entity_type="user", entity_id="a",
              target_entity_type="item", target_entity_id="i",
              event_time=_t("2026-01-02T12:00:00Z")),
        Event(event="$set", entity_type="item", entity_id="other-type",
              properties={"w": 1}, event_time=_t("2026-01-01T00:00:00Z")),
    ]
    store.insert_batch(evs, APP)

    native = store.aggregate_properties(APP, "user")
    ref = aggregate_properties(
        e for e in evs if e.entity_type == "user")

    assert set(native) == set(ref) == {"a", "b"}
    for eid in native:
        assert native[eid].properties == ref[eid].properties, eid
        assert native[eid].first_updated == ref[eid].first_updated
        assert native[eid].last_updated == ref[eid].last_updated


def test_fold_backslash_and_unicode_ids(store):
    # literal backslash text and non-ASCII must survive the native fold
    evs = [
        Event(event="$set", entity_type="user", entity_id="C:\\users",
              properties={"p\\u0041th": "a\\u0042", "中文": "漢"},
              event_time=_t("2026-01-01T00:00:00Z")),
    ]
    store.insert_batch(evs, APP)
    native = store.aggregate_properties(APP, "user")
    ref = aggregate_properties(evs)
    assert set(native) == set(ref) == {"C:\\users"}
    assert native["C:\\users"].properties == ref["C:\\users"].properties


def test_microsecond_roundtrip(store):
    t = _t("2005-03-28T19:42:50.536110Z")  # float-timestamp rounding victim
    eid = store.insert(
        Event(event="e", entity_type="t", entity_id="1", event_time=t), APP)
    assert store.get(eid, APP).event_time == t


def test_limit_zero_returns_nothing(store):
    store.insert(Event(event="e", entity_type="t", entity_id="1",
                       event_time=_t("2026-01-01T00:00:00Z")), APP)
    assert list(store.find(APP, limit=0)) == []


def test_fold_time_window(store):
    for day, val in ((1, 1), (2, 2), (3, 3)):
        store.insert(
            Event(event="$set", entity_type="user", entity_id="u",
                  properties={"v": val},
                  event_time=_t(f"2026-01-0{day}T00:00:00Z")), APP)
    agg = store.aggregate_properties(
        APP, "user", until_time=_t("2026-01-03T00:00:00Z"))
    assert agg["u"].properties == {"v": 2}


def test_find_filters_and_limits(store):
    store.insert_batch(
        [Event(event="view", entity_type="user", entity_id="u1",
               target_entity_type="item", target_entity_id=f"i{k}",
               event_time=_t(f"2026-02-0{k}T00:00:00Z"))
         for k in range(1, 6)], APP)
    got = list(store.find(APP, limit=2, reversed=True))
    assert [e.target_entity_id for e in got] == ["i5", "i4"]
    got = list(store.find(APP, target_entity_id="i3"))
    assert len(got) == 1
    got = list(store.find(APP, start_time=_t("2026-02-02T00:00:00Z"),
                          until_time=_t("2026-02-04T00:00:00Z")))
    assert [e.target_entity_id for e in got] == ["i2", "i3"]


def test_torn_tail_write_is_ignored(tmp_path, store):
    ids = store.insert_batch(
        [Event(event="e", entity_type="t", entity_id="1",
               event_time=_t("2026-01-01T00:00:00Z")),
         Event(event="e", entity_type="t", entity_id="2",
               event_time=_t("2026-01-02T00:00:00Z"))], APP)
    store.close()
    path = tmp_path / "log" / "events_1.pel"
    raw = path.read_bytes()
    path.write_bytes(raw + b"\x40\x00\x00\x00\x00partial")  # truncated record

    from predictionio_tpu.data.filestore import NativeEventLogStore

    s2 = NativeEventLogStore(str(tmp_path / "log"))
    assert [e.event_id for e in s2.find(APP)] == ids
    # the torn tail is truncated at open: writes after it survive reopen
    new_id = s2.insert(Event(event="e", entity_type="t", entity_id="3",
                             event_time=_t("2026-01-03T00:00:00Z")), APP)
    s2.close()
    s3 = NativeEventLogStore(str(tmp_path / "log"))
    assert [e.event_id for e in s3.find(APP)] == ids + [new_id]
    s3.close()


def test_quickstart_on_eventlog_storage(tmp_path):
    """End-to-end train → query with EVENTDATA on the C++ event log —
    the deployment docs recommend for bulk events (the SPI tests cover
    the store alone; this proves the whole workflow path, env-config →
    registry → native store → streaming read → ALS → serving)."""
    import numpy as np

    from predictionio_tpu.core.workflow import prepare_deploy, run_train
    from predictionio_tpu.storage.registry import (Storage, StorageConfig,
                                                   set_storage)
    from tests.test_workflow import FACTORY, seed_ratings

    cfg = StorageConfig.from_env({
        "PIO_HOME": str(tmp_path),
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "NATIVE",
        "PIO_STORAGE_SOURCES_NATIVE_TYPE": "EVENTLOG",
    })
    assert cfg.eventdata_type == "EVENTLOG"
    st = Storage(cfg)
    set_storage(st)
    built = False
    try:
        try:
            st.events  # builds the C++ engine lazily
            built = True
        except RuntimeError as e:  # only the no-g++ signal may skip
            pytest.skip(f"native engine unavailable: {e}")
        seed_ratings(st)
        run_train(FACTORY, variant={
            "id": "elq", "engineFactory": FACTORY,
            "datasource": {"params": {"appName": "TestApp"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 4, "numIterations": 3, "lambda": 0.05}}],
        }, storage=st, use_mesh=False)
        res = prepare_deploy(engine_factory=FACTORY,
                             storage=st).query({"user": "0", "num": 3})
        assert len(res["itemScores"]) == 3
        assert np.isfinite([s["score"] for s in res["itemScores"]]).all()
    finally:
        if built:
            st.events.close()
        set_storage(None)


@pytest.fixture(params=["eventlog", "sqlite", "indexed"])
def col_store(request, tmp_path):
    """Every scan_columnar provider — the C++ EVENTLOG engine, the SQL
    store (default SQLITE backend), and the embedded index — under one
    parity contract."""
    if request.param == "eventlog":
        from predictionio_tpu.data.filestore import NativeEventLogStore

        try:
            s = NativeEventLogStore(str(tmp_path / "log"))
        except RuntimeError as e:
            pytest.skip(str(e))
    elif request.param == "sqlite":
        from predictionio_tpu.data.events import SqliteEventStore

        s = SqliteEventStore(str(tmp_path / "ev.db"))
        s.init_channel(APP)
    else:
        from predictionio_tpu.storage.indexed import (ESEventStore,
                                                      IndexedStorageClient)

        s = ESEventStore(IndexedStorageClient(str(tmp_path / "idx")))
        s.init_channel(APP)
    yield s
    s.close()


class TestColumnarScan:
    """The columnar training read (C++ EVENTLOG engine AND the SQL
    store's SELECT-only variant) must be indistinguishable from the
    generic two-pass Python reader over find() — same vocabularies
    (content AND first-seen order), same arrays, same drop
    semantics."""

    def _mixed_workload(self, store):
        rng_events = [
            # (event, ent, tgt, props)
            ("rate", "u1", "i1", {"rating": 4.0}),
            ("rate", "u2", "i2", {"rating": 3}),          # int rating
            ("rate", "u1", "i2", {"rating": "4.5"}),      # numeric string
            ("rate", "u3", "i3", {}),                      # missing → drop
            ("rate", "u4", "i1", {"rating": "bad"}),       # malformed → drop
            ("rate", "u∞", "i☂", {"rating": 2.0}),         # unicode ids
            ("buy", "u2", "i3", {}),                       # const value
            ("buy", "u5", "i1", {"rating": 9.0}),          # const ignores prop
            ("view", "u1", "i1", {}),                      # filtered out
            ("rate", "u1", None, {"rating": 5.0}),         # no target → skip
            ("rate", "u6", "i4", {"rating": {"nested": 1}}),  # non-num → drop
            # the shared value grammar is NARROWER than Python float()
            # so both paths drop the same exotica (r5 review):
            ("rate", "u7", "i1", {"rating": "0x10"}),      # hex → drop
            ("rate", "u8", "i2", {"rating": "1_5"}),       # underscore → drop
            ("rate", "u9", "i3", {"rating": "inf"}),       # inf word → drop
            ("rate", "uA", "i4", {"rating": "nan"}),       # nan word → drop
            ("rate", "uB", "i1", {"rating": float("inf")}),  # inf value → drop
            ("rate", "uC", "i2", {"rating": True}),        # bool → 1.0
            ("rate", "uD", "i3", {"rating": " 2.5 "}),     # padded str → 2.5
            ("rate", "uE", "i4", {"rating": "1e2"}),       # exponent → 100.0
        ]
        t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
        for k, (name, ent, tgt, props) in enumerate(rng_events):
            store.insert(Event(
                event=name, entity_type="user", entity_id=ent,
                target_entity_type="item" if tgt else None,
                target_entity_id=tgt, properties=props,
                event_time=t0 + dt.timedelta(seconds=k)), APP)

    def test_matches_generic_reader(self, col_store):
        from predictionio_tpu.data.pipeline import (
            interactions_from_columnar, read_interactions)

        store = col_store
        self._mixed_workload(store)
        spec = {"rate": "prop"}
        cols = store.scan_columnar(
            APP, entity_type="user", target_entity_type="item",
            event_names=["rate", "buy"], value_key="rating")
        fast = interactions_from_columnar(cols, spec, default_spec=4.0)

        import math

        from predictionio_tpu.data.store import _parse_value

        def value_fn(e):
            if e.event == "rate":
                v = _parse_value(e.properties.get("rating"))
                return v if v is not None and math.isfinite(v) else None
            return 4.0

        slow = read_interactions(
            lambda: store.find(APP, entity_type="user",
                               target_entity_type="item",
                               event_names=["rate", "buy"]),
            value_fn=value_fn)

        assert fast.n_events == slow.n_events
        assert list(fast.user_ids) == list(slow.user_ids)
        assert list(fast.item_ids) == list(slow.item_ids)
        fu, fi, fv = fast.arrays()
        su, si, sv = slow.arrays()
        assert (fu == su).all() and (fi == si).all()
        assert (fv == sv).all()

    def test_store_entry_point_both_paths(self, col_store, storage):
        """read_training_interactions dispatch: each scan_columnar
        provider (EVENTLOG, SQLITE) takes the fast path through the
        ENTRY POINT, MEMORY takes the generic path — identical."""
        store = col_store
        from predictionio_tpu.data.store import read_training_interactions

        a = storage.meta.create_app("ColApp")
        storage.events.init_channel(a.id)
        mem = storage.events

        t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
        events = []
        for k in range(50):
            if k % 7 == 0:
                e = Event(event="buy", entity_type="user",
                          entity_id=f"u{k % 11}",
                          target_entity_type="item",
                          target_entity_id=f"i{k % 5}",
                          event_time=t0 + dt.timedelta(seconds=k))
            else:
                e = Event(event="rate", entity_type="user",
                          entity_id=f"u{k % 11}", target_entity_type="item",
                          target_entity_id=f"i{k % 5}",
                          properties={"rating": float(k % 5) + 0.5},
                          event_time=t0 + dt.timedelta(seconds=k))
            events.append(e)
        # same events into both stores; fix ids so overwrite semantics agree
        for e in events:
            e = e.with_id()
            store.insert(e, a.id)
            mem.insert(e, a.id)

        kw = dict(entity_type="user", target_entity_type="item",
                  event_names=["rate", "buy"], value_key="rating",
                  value_spec={"rate": "prop"}, default_spec=4.0,
                  storage=storage)
        generic = read_training_interactions("ColApp", **kw)
        storage._events = store  # swap the backend under the same app
        fast = read_training_interactions("ColApp", **kw)
        assert list(fast.user_ids) == list(generic.user_ids)
        assert list(fast.item_ids) == list(generic.item_ids)
        for (a1, b1) in zip(fast.arrays(), generic.arrays()):
            assert (a1 == b1).all()

    def test_event_groups_parity(self, col_store):
        store = col_store
        """Grouped multi-event read (Universal Recommender shape):
        columnar demux must equal the generic two-scan reader — same
        per-name pairs, same SHARED vocabulary pair, same order."""
        from predictionio_tpu.data.pipeline import (
            event_groups_from_columnar, read_event_groups)

        self._mixed_workload(store)
        names = ["rate", "buy", "view"]
        cols = store.scan_columnar(
            APP, entity_type="user", target_entity_type="item",
            event_names=names)
        f_pairs, f_u, f_i = event_groups_from_columnar(cols, names)
        s_pairs, s_u, s_i = read_event_groups(
            lambda: store.find(APP, entity_type="user",
                               target_entity_type="item",
                               event_names=names),
            names)
        assert list(f_u) == list(s_u) and list(f_i) == list(s_i)
        for n in names:
            assert (f_pairs[n][0] == s_pairs[n][0]).all(), n
            assert (f_pairs[n][1] == s_pairs[n][1]).all(), n
        assert f_pairs["view"][0].size == 1  # the one view event

    def test_times_us_microsecond_parity(self, col_store):
        """µs-precision parity: every scan_columnar provider must
        return the EXACT integer microsecond timestamps — the same
        expected array pins all three backends (EVENTLOG, SQLITE, ES)
        to bit-identical ``times_us``. Regression for the ES float-
        second epoch field, which rounded sub-second times (≈0.5 µs
        spacing) until the exact ``eventTimeUs`` doc field landed."""
        import numpy as np

        store = col_store
        stamps = [
            "2026-01-02T03:04:05Z",             # whole second
            "2026-01-02T03:04:05.123Z",         # millis
            "2026-01-02T03:04:05.123456Z",      # full micros
            "2026-01-02T03:04:05.123457Z",      # 1 µs later — must differ
            "2026-01-02T03:04:05.000001Z",      # 1 µs past the second
            "2026-01-02T08:34:05.999999+05:30", # tz-shifted, .999999
        ]
        from predictionio_tpu.data.event import parse_event_time

        for k, s in enumerate(stamps):
            store.insert(Event(
                event="rate", entity_type="user", entity_id=f"u{k}",
                target_entity_type="item", target_entity_id=f"i{k}",
                properties={"rating": 1.0},
                event_time=parse_event_time(s)), APP)
        cols = store.scan_columnar(
            APP, entity_type="user", target_entity_type="item",
            event_names=["rate"], value_key="rating")
        epoch = dt.datetime(1970, 1, 1, tzinfo=dt.timezone.utc)
        want = np.sort(np.asarray(
            [int((parse_event_time(s) - epoch).total_seconds() * 1e6
                 + 0.5) for s in stamps], np.int64))
        got = np.sort(np.asarray(cols.times_us, np.int64))
        assert got.dtype == np.int64
        np.testing.assert_array_equal(got, want)
        # the two 1-µs-apart events stayed distinct (the old float-
        # second ES field collapsed them)
        assert len(np.unique(got)) == len(stamps)


class TestNativeJsonlImport:
    """`pio import` NDJSON parity: the C++ fast path must produce
    events FIELD-IDENTICAL to the Python Event.from_json path (modulo
    generated eventId / creationTime), fall back on anything unusual,
    and surface Python's validation errors for invalid lines."""

    LINES = [
        '{"event":"rate","entityType":"user","entityId":"u1",'
        '"targetEntityType":"item","targetEntityId":"i1",'
        '"properties":{"rating":4.5},"eventTime":"2026-01-02T03:04:05Z"}',
        '',  # blank → skipped
        '{"event":"buy","entityType":"user","entityId":"u\\u221e",'
        '"targetEntityType":"item","targetEntityId":"i☂",'
        '"eventTime":"2026-01-02T03:04:05.5+05:30"}',
        '{"event":"view","entityType":"user","entityId":"u2",'
        '"targetEntityType":"item","targetEntityId":"i2",'
        '"eventTime":"2026-01-02T03:04:05.123456-08:00",'
        '"tags":["a","b"],"prId":"pr-9"}',
        '{"event":"note","entityType":"user","entityId":"u3",'
        '"properties":{"nested":{"k":[1,2]},"s":"q\\"uote"},'
        '"eventTime":"2026-01-02 03:04:05"}',  # space sep, no tz
        '{"event":"$set","entityType":"user","entityId":"u4",'
        '"properties":{"plan":"pro"},'
        '"eventTime":"2026-01-03T00:00:00Z"}',  # $-event → fallback
        '{"eventId":"deadbeefdeadbeefdeadbeefdeadbeef","event":"pin",'
        '"entityType":"user","entityId":"u5",'
        '"eventTime":"2026-01-04T00:00:00+00:00"}',
    ]

    def _import(self, store, text):
        import io

        from predictionio_tpu.tools.export_import import import_events

        class _St:
            events = store

        return import_events(APP, io.StringIO(text), storage=_St())

    def test_field_parity_with_python_path(self, store):
        from predictionio_tpu.data.event import Event
        import json as _json

        n = self._import(store, "\n".join(self.LINES) + "\n")
        assert n == 6  # 7 lines minus the blank
        native = sorted(store.find(APP),
                        key=lambda e: (e.event_time, e.event))
        ref = sorted((Event.from_json(_json.loads(l))
                      for l in self.LINES if l),
                     key=lambda e: (e.event_time, e.event))
        assert len(native) == len(ref) == 6
        for a, b in zip(native, ref):
            assert a.event == b.event
            assert a.entity_type == b.entity_type
            assert a.entity_id == b.entity_id
            assert a.target_entity_type == b.target_entity_type
            assert a.target_entity_id == b.target_entity_id
            assert a.properties == b.properties
            assert a.tags == b.tags
            assert a.pr_id == b.pr_id
            assert a.event_time == b.event_time  # µs-exact incl. tz
        # explicit eventId preserved
        assert store.get("deadbeefdeadbeefdeadbeefdeadbeef", APP) is not None
        # every generated id is unique
        ids = [e.event_id for e in native]
        assert len(set(ids)) == len(ids)

    def test_invalid_lines_raise_python_errors(self, store):
        import json as _json

        from predictionio_tpu.data.event import EventValidationError

        with pytest.raises(EventValidationError):
            self._import(store, '{"event":"x","entityType":"user",'
                                '"entityId":"u","bogusField":1}\n')
        with pytest.raises(EventValidationError):
            self._import(store, '{"event":"x","entityType":"user"}\n')
        with pytest.raises(EventValidationError):  # one-sided target
            self._import(store, '{"event":"x","entityType":"u",'
                                '"entityId":"1","targetEntityId":"i"}\n')
        with pytest.raises(EventValidationError):  # bad timestamp
            self._import(store, '{"event":"x","entityType":"u",'
                                '"entityId":"1","eventTime":"yesterday"}\n')
        # NOTHING the strict C++ grammar accepts may be a line Python
        # rejects (r5 review: each of these was once natively accepted
        # — the first POISONED every later read of the namespace)
        for bad in (
            '{"event":"e","entityType":"user","entityId":"u1",'
            '"properties":{"a":}}',                    # malformed nested
            '{"event":"e","entityType":"u","entityId":"a\\uZZZZ"}',
            '{"event":"e","entityType":"user","entityId":"u1"}GARBAGE',
            '{"event":"e","entityType":"u","entityId":"1" "prId":"x"}',
            '{"event":"e","entityType":"u","entityId":"1",'
            '"eventTime":"2026-02-30T00:00:00Z"}',     # nonexistent date
            '{"event":"e","entityType":"u","entityId":"1",'
            '"properties":{"n":01}}',                  # leading zero
        ):
            with pytest.raises((_json.JSONDecodeError,
                                EventValidationError)):
                self._import(store, bad + "\n")
        # a LONE surrogate escape: json.loads accepts it but the
        # Python serialize path dies at utf-8 encode — the native path
        # must fall back (it once emitted raw surrogate bytes into the
        # frame, making the whole namespace unreadable)
        with pytest.raises(UnicodeEncodeError):
            self._import(store, '{"event":"e","entityType":"u",'
                                '"entityId":"a\\ud800"}\n')
        # and the store must still read back cleanly afterwards
        assert list(store.find(APP)) == []

    def test_formfeed_only_lines_are_blank(self, store):
        """Lines that strip() to empty but aren't space/tab (\\f, \\xa0)
        were silently skipped by the legacy loop — same here."""
        n = self._import(store,
                         '{"event":"e","entityType":"u","entityId":"1"}\n'
                         '\f\n\xa0\n'
                         '{"event":"e","entityType":"u","entityId":"2"}\n')
        assert n == 2
        assert len(list(store.find(APP))) == 2

    def test_py310_incompatible_timestamps_fall_back(self, store):
        """Timestamp shapes Python 3.10's fromisoformat rejects (±HHMM
        offset, 1-digit fraction) must NOT be consumed natively — on
        this interpreter the fallback parses them, on 3.10 it raises;
        either way the native path never decides."""
        import json as _json

        from predictionio_tpu.data.event import Event

        lines = ['{"event":"e","entityType":"u","entityId":"1",'
                 '"eventTime":"2026-01-02T03:04:05.5+05:30"}',
                 '{"event":"e","entityType":"u","entityId":"2",'
                 '"eventTime":"2026-01-02T03:04:05+0530"}']
        n = self._import(store, "\n".join(lines) + "\n")
        assert n == 2
        got = sorted(store.find(APP), key=lambda e: e.entity_id)
        ref = sorted((Event.from_json(_json.loads(l)) for l in lines),
                     key=lambda e: e.entity_id)
        for a, b in zip(got, ref):
            assert a.event_time == b.event_time

    def test_export_reimport_native_parity(self, store, tmp_path):
        """Re-importing this tool's own export (every line carries
        eventId + creationTime) must match what Event.from_json makes
        of the same lines, field for field INCLUDING creationTime —
        i.e. the export shape stays on the native path and parses
        identically to the Python path."""
        import io
        import json as _json

        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data.filestore import NativeEventLogStore
        from predictionio_tpu.tools.export_import import (export_events,
                                                          import_events)

        self._import(store, "\n".join(l for l in self.LINES if l))
        out = io.StringIO()
        export_events(APP, out, storage=type("S", (), {"events": store}))
        lines = [l for l in out.getvalue().splitlines() if l]

        store2 = NativeEventLogStore(str(tmp_path / "reimport"))
        out.seek(0)
        n = import_events(APP, out, storage=type("S", (), {"events": store2}))
        assert n == len(lines) == 6
        got = {e.event_id: e for e in store2.find(APP)}
        ref = {e.event_id: e
               for e in (Event.from_json(_json.loads(l)) for l in lines)}
        assert got.keys() == ref.keys()
        for k, a in got.items():
            b = ref[k]
            for f in ("event", "entity_type", "entity_id",
                      "target_entity_type", "target_entity_id",
                      "properties", "tags", "pr_id", "event_time",
                      "creation_time"):
                assert getattr(a, f) == getattr(b, f), (k, f)
        store2.close()

    def test_import_then_train_read(self, store):
        """Imported events feed the columnar training read correctly."""
        lines = []
        for k in range(500):
            lines.append(
                '{"event":"rate","entityType":"user","entityId":"u%d",'
                '"targetEntityType":"item","targetEntityId":"i%d",'
                '"properties":{"rating":%d}}' % (k % 20, k % 12, k % 5 + 1))
        n = self._import(store, "\n".join(lines))
        assert n == 500
        cols = store.scan_columnar(APP, entity_type="user",
                                   target_entity_type="item",
                                   event_names=["rate"],
                                   value_key="rating")
        assert cols.n == 500
        assert np.isfinite(cols.values).all()
        assert set(cols.names) == {"rate"}

    def test_duplicate_property_keys_fall_back(self, store):
        """json.loads keeps the LAST duplicate key; the C++ scanner's
        first-match property extraction would keep the FIRST — so a
        line with duplicate keys must never be consumed natively.
        (json.dumps can't emit duplicates; the lines are hand-built.)"""
        import json as _json

        line = ('{"event":"rate","entityType":"user","entityId":"u1",'
                '"targetEntityType":"item","targetEntityId":"i1",'
                '"properties":{"rating":1,"rating":2},'
                '"eventTime":"2026-01-02T03:04:05Z"}')
        assert _json.loads(line)["properties"] == {"rating": 2}
        n = self._import(store, line + "\n")
        assert n == 1
        evs = list(store.find(APP))
        assert len(evs) == 1
        assert evs[0].properties == {"rating": 2}  # last wins, as Python
        cols = store.scan_columnar(APP, value_key="rating")
        assert cols.values.tolist() == [2.0]

    def test_duplicate_top_level_keys_fall_back(self, store):
        import json as _json

        line = ('{"event":"rate","entityType":"user","entityId":"u1",'
                '"entityId":"u2","eventTime":"2026-01-02T03:04:05Z"}')
        assert _json.loads(line)["entityId"] == "u2"
        n = self._import(store, line + "\n")
        assert n == 1
        evs = list(store.find(APP))
        assert len(evs) == 1 and evs[0].entity_id == "u2"

    def test_escaped_key_duplicates_detected(self, store):
        """Duplicate detection must compare UNESCAPED key text:
        "\\u0072ating" and "rating" are the same key."""
        line = ('{"event":"rate","entityType":"user","entityId":"u1",'
                '"properties":{"\\u0072ating":1,"rating":2},'
                '"eventTime":"2026-01-02T03:04:05Z"}')
        n = self._import(store, line + "\n")
        assert n == 1
        evs = list(store.find(APP))
        assert evs[0].properties == {"rating": 2}

    def test_distinct_keys_stay_native(self, store):
        """Non-duplicate multi-key objects must not be rejected by the
        duplicate check (no false positives)."""
        line = ('{"event":"rate","entityType":"user","entityId":"u1",'
                '"properties":{"rating":1,"rating2":2,"ratin":3},'
                '"eventTime":"2026-01-02T03:04:05Z"}')
        n = self._import(store, line + "\n")
        assert n == 1
        evs = list(store.find(APP))
        assert evs[0].properties == {"rating": 1, "rating2": 2, "ratin": 3}

    @pytest.mark.parametrize("durable", [False, True])
    def test_append_jsonl_counts_its_own_seconds(self, store, durable):
        """The import's clock is the program's (``REGISTRY``): the C++
        call, its fsync (durable stores only) and the Python around
        them, and the lines that landed."""
        from predictionio_tpu.utils.metrics import REGISTRY

        events = REGISTRY.counter("pio_ingest_events_total", "", ("path",))
        seconds = REGISTRY.counter("pio_ingest_seconds_total", "",
                                   ("stage",))
        store.set_durable(durable)
        n_lines = 300
        blob = "".join(
            '{"event":"rate","entityType":"user","entityId":"u%d",'
            '"targetEntityType":"item","targetEntityId":"i%d",'
            '"properties":{"rating":3.5}}\n' % (i, i % 7)
            for i in range(n_lines)).encode()
        before = {k: seconds.get((k,))
                  for k in ("native", "sync", "python")}
        had = events.get(("native",))
        t0 = time.perf_counter()
        assert store.append_jsonl(blob, n_lines, APP) == (n_lines, [])
        wall = time.perf_counter() - t0
        assert events.get(("native",)) - had == n_lines
        moved = {k: seconds.get((k,)) - v for k, v in before.items()}
        assert moved["native"] > 0 and moved["python"] > 0
        assert (moved["sync"] > 0) == durable
        assert sum(moved.values()) <= wall
        assert 'pio_ingest_seconds_total{stage="native"}' in \
            REGISTRY.render()

    def test_batch_creation_times_strictly_increase(self, store):
        """Defaulted creationTimes within one import batch must be
        distinct and follow line order (now_us + line index), and a
        back-to-back second batch must not collide with the first —
        the snapshot cache's watermark math needs creationTime to be
        a usable tiebreaker, not a pile of equal timestamps."""
        def batch(tag, k):
            return "\n".join(
                '{"event":"e","entityType":"u","entityId":"%s%d"}' % (tag, i)
                for i in range(k)) + "\n"

        self._import(store, batch("a", 50))
        self._import(store, batch("b", 50))
        evs = sorted(store.find(APP), key=lambda e: e.creation_time)
        times = [e.creation_time for e in evs]
        assert len(set(times)) == 100  # all distinct
        order = [e.entity_id for e in evs]
        assert order == [f"a{i}" for i in range(50)] + \
            [f"b{i}" for i in range(50)]


class TestNativeJsonlExport:
    """`pio export` native parity: every line must json-loads-equal
    what Event.to_json_str would emit for the same event (key order,
    ms-truncated +00:00 timestamps, omitted-empty fields), across the
    cursor-chunk boundary."""

    def test_loads_equal_with_python_export(self, store):
        import io
        import json as _json

        from predictionio_tpu.tools.export_import import export_events

        t0 = dt.datetime(2026, 2, 3, 4, 5, 6, 789123,
                         tzinfo=dt.timezone.utc)
        evs = [
            Event(event="rate", entity_type="user", entity_id="u∞",
                  target_entity_type="item", target_entity_id='i"q',
                  properties={"rating": 4.5, "nested": {"a": [1, None]}},
                  event_time=t0),
            Event(event="note", entity_type="user", entity_id="u2",
                  properties={}, tags=["a", "b\\c"], pr_id="pr-1",
                  event_time=t0 + dt.timedelta(seconds=1)),
            Event(event="plain", entity_type="t", entity_id="x",
                  event_time=t0 + dt.timedelta(seconds=2,
                                               microseconds=999)),
        ]
        store.insert_batch(evs, APP)

        out = io.StringIO()
        n = export_events(APP, out, storage=type("S", (), {"events": store}))
        assert n == 3
        native_lines = [l for l in out.getvalue().splitlines() if l]
        ref_lines = [e.to_json_str() for e in store.find(APP)]
        assert len(native_lines) == len(ref_lines) == 3
        for a, b in zip(native_lines, ref_lines):
            da, db = _json.loads(a), _json.loads(b)
            assert da == db
            # key ORDER parity too (consumers may stream-parse)
            assert list(da) == list(db)

    def test_chunk_boundary_and_reimport(self, store, tmp_path):
        import io
        import json as _json

        from predictionio_tpu.data.filestore import NativeEventLogStore
        from predictionio_tpu.tools.export_import import (export_events,
                                                          import_events)

        t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
        store.insert_batch(
            [Event(event="e", entity_type="u", entity_id=str(k),
                   target_entity_type="i", target_entity_id=str(k % 7),
                   event_time=t0 + dt.timedelta(seconds=k))
             for k in range(257)], APP)
        chunks = list(store.iter_jsonl_chunks(APP, chunk_events=100))
        assert len(chunks) == 3  # 100 + 100 + 57
        text = "".join(chunks)
        assert text.count("\n") == 257

        s2 = NativeEventLogStore(str(tmp_path / "re"))
        n = import_events(APP, io.StringIO(text),
                          storage=type("S", (), {"events": s2}))
        assert n == 257
        a = [e.event_id for e in store.find(APP)]
        b = [e.event_id for e in s2.find(APP)]
        assert a == b
        s2.close()


def test_universal_workflow_on_eventlog(tmp_path):
    """Universal Recommender end-to-end on the C++ event log: the
    grouped columnar read feeds the real run_train → prepare_deploy →
    query path (the r5 verify flow, cemented as suite coverage)."""
    import numpy as np

    from predictionio_tpu.core.workflow import prepare_deploy, run_train
    from predictionio_tpu.storage.registry import (Storage, StorageConfig,
                                                   set_storage)

    st = Storage(StorageConfig(metadata_type="MEMORY",
                               modeldata_type="MEMORY",
                               eventdata_type="EVENTLOG",
                               home=str(tmp_path)))
    try:
        st.events  # builds the C++ engine (skip when no g++)
    except RuntimeError as e:
        pytest.skip(str(e))
    set_storage(st)
    a = st.meta.create_app("URLog")
    st.events.init_channel(a.id)
    rng = np.random.default_rng(2)
    st.events.insert_batch([
        Event(event=["buy", "view", "view", "like"][k % 4],
              entity_type="user",
              entity_id=f"u{int(rng.integers(0, 40))}",
              target_entity_type="item",
              target_entity_id=f"i{int(rng.integers(0, 30))}")
        for k in range(1200)], a.id)
    factory = "predictionio_tpu.templates.universal.engine:engine_factory"
    variant = {"id": "default", "engineFactory": factory,
               "datasource": {"params": {
                   "appName": "URLog",
                   "eventNames": ["buy", "view", "like"]}},
               "algorithms": [{"name": "ur",
                               "params": {"maxIndicatorsPerItem": 20}}]}
    try:
        iid = run_train(factory, variant=variant, storage=st,
                        use_mesh=False)
        eng = prepare_deploy(factory, instance_id=iid, storage=st)
        out = eng.query({"user": "u3", "num": 5})
        assert out["itemScores"], "UR query must return scored items"
    finally:
        st.events.close()
        set_storage(None)


class TestImportFuzzParity:
    """Randomized check of the strict-narrower contract: for ANY line,
    if the native path consumed it, the Python path must also accept
    it and produce the same event fields; if Python rejects a line,
    the native path must not have consumed it. Seeded → deterministic."""

    def test_random_event_lines(self, store, tmp_path):
        import io
        import json as _json
        import random

        from predictionio_tpu.data.event import (Event,
                                                  EventValidationError)
        from predictionio_tpu.data.filestore import NativeEventLogStore
        from predictionio_tpu.tools.export_import import import_events

        rnd = random.Random(77)
        names = ["rate", "buy", "$set", "$unset", "$delete", "e-x", "вид"]
        ids = ["u1", "ü", "a b", 'q"t', "x\\y", "", "0", "日本", "a\tb"]
        props_pool = [{}, {"rating": 4.5}, {"rating": "3"},
                      {"rating": "bad"}, {"n": {"d": [1, None]}},
                      {"s": 'esc"\\'}, {"rating": True}]
        times = ["2026-01-02T03:04:05Z", "2026-01-02T03:04:05.123Z",
                 "2026-13-01T00:00:00Z", "2026-02-30T00:00:00Z",
                 "2026-01-02 03:04:05", "bogus", "2026-01-02T03:04:05+0230",
                 "2026-01-02T03:04:05.123456-08:00", None]
        lines = []
        for k in range(400):
            d = {"event": rnd.choice(names),
                 "entityType": rnd.choice(["user", "item", ""]),
                 "entityId": rnd.choice(ids)}
            if rnd.random() < 0.7:
                d["targetEntityType"] = rnd.choice(["item", ""])
                d["targetEntityId"] = rnd.choice(ids)
            elif rnd.random() < 0.2:
                d["targetEntityId"] = "half"   # one-sided
            if rnd.random() < 0.6:
                d["properties"] = rnd.choice(props_pool)
            t = rnd.choice(times)
            if t is not None:
                d["eventTime"] = t
            if rnd.random() < 0.3:
                d["prId"] = rnd.choice(["pr-1", "", 5, "ü"])
            if rnd.random() < 0.2:
                d["eventId"] = rnd.choice(
                    ["deadbeefdeadbeefdeadbeefdeadbeef", "", 0, "short"])
            if rnd.random() < 0.2:
                d["tags"] = rnd.choice([[], ["a"], ["a", 'b"c']])
            if rnd.random() < 0.15:
                d["creationTime"] = rnd.choice(
                    ["2026-01-01T00:00:00.500Z", "nope", ""])
            if rnd.random() < 0.1:
                d["bogus"] = 1
            line = _json.dumps(d, ensure_ascii=rnd.random() < 0.5)
            if rnd.random() < 0.05:
                line = line + "garbage"          # corrupt some lines
            lines.append((line, d))

        for i, (line, d) in enumerate(lines):
            s = NativeEventLogStore(str(tmp_path / f"fz{i}"))
            try:
                # what does Python say?
                try:
                    ref = Event.from_json(_json.loads(line))
                except (ValueError, EventValidationError):
                    ref = None
                try:
                    n = import_events(APP, io.StringIO(line + "\n"),
                                      storage=type("S", (),
                                                   {"events": s}))
                except (ValueError, EventValidationError,
                        _json.JSONDecodeError):
                    n = -1  # import raised (must mean Python rejects)
                if ref is None:
                    assert n <= 0, (line, "native accepted what "
                                          "Python rejects")
                else:
                    assert n == 1, (line, "both should accept")
                    got = next(iter(s.find(APP)))
                    assert got.event == ref.event, line
                    assert got.entity_id == ref.entity_id, line
                    assert got.target_entity_type == \
                        ref.target_entity_type, line
                    assert got.target_entity_id == \
                        ref.target_entity_id, line
                    assert got.properties == ref.properties, line
                    assert got.tags == ref.tags, line
                    assert got.pr_id == ref.pr_id, line
                    if "eventTime" in d:
                        assert got.event_time == ref.event_time, line
                    if d.get("creationTime"):
                        assert got.creation_time == ref.creation_time, line
            finally:
                s.close()

    def test_duplicate_keys_last_wins(self, tmp_path):
        """Duplicate JSON keys — ``json.dumps`` can never emit them, so
        the random fuzz above is blind to this grammar corner. Python's
        ``json.loads`` keeps the LAST occurrence; the native parser
        must agree on every field it narrows (fixed in the native
        eventlog parser; this pins the behavior)."""
        import io
        import json as _json

        from predictionio_tpu.data.event import Event
        from predictionio_tpu.data.filestore import NativeEventLogStore
        from predictionio_tpu.tools.export_import import import_events

        lines = [
            # top-level dup: event name, last value wins
            '{"event": "rate", "event": "buy", "entityType": "user", '
            '"entityId": "u1"}',
            # dup entityId, including one non-string earlier occurrence
            '{"event": "rate", "entityType": "user", "entityId": "old", '
            '"entityId": "new"}',
            # dup eventTime: first invalid, last valid (accept) and the
            # reverse (reject) — narrowing must use the surviving value
            '{"event": "e", "entityType": "u", "entityId": "x", '
            '"eventTime": "bogus", "eventTime": "2026-01-02T03:04:05Z"}',
            '{"event": "e", "entityType": "u", "entityId": "x", '
            '"eventTime": "2026-01-02T03:04:05Z", "eventTime": "bogus"}',
            # dup inside properties objects
            '{"event": "e", "entityType": "u", "entityId": "x", '
            '"properties": {"rating": 1.5, "rating": 4.5}}',
            # the whole properties object duplicated
            '{"event": "e", "entityType": "u", "entityId": "x", '
            '"properties": {"a": 1}, "properties": {"b": 2}}',
            # dup targetEntityId where the first would be one-sided
            '{"event": "e", "entityType": "u", "entityId": "x", '
            '"targetEntityType": "item", "targetEntityId": "t1", '
            '"targetEntityId": "t2"}',
        ]
        for i, line in enumerate(lines):
            s = NativeEventLogStore(str(tmp_path / f"dup{i}"))
            try:
                try:
                    ref = Event.from_json(_json.loads(line))
                except ValueError:
                    ref = None
                try:
                    n = import_events(APP, io.StringIO(line + "\n"),
                                      storage=type("S", (), {"events": s}))
                except ValueError:
                    n = -1
                if ref is None:
                    assert n <= 0, (line, "native accepted what Python "
                                          "rejects")
                else:
                    assert n == 1, (line, "both should accept")
                    got = next(iter(s.find(APP)))
                    assert got.event == ref.event, line
                    assert got.entity_id == ref.entity_id, line
                    assert got.target_entity_id == ref.target_entity_id, \
                        line
                    assert got.properties == ref.properties, line
                    if '"eventTime"' in line:  # else defaults to now()
                        assert got.event_time == ref.event_time, line
            finally:
                s.close()
