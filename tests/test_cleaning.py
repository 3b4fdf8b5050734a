"""SelfCleaningDataSource / EventWindow tests (reference behavior:
[U] core/.../core/SelfCleaningDataSource.scala)."""

import datetime as dt

import pytest

from predictionio_tpu.data.cleaning import (
    EventWindow,
    SelfCleaningDataSource,
    clean_persisted_events,
    parse_duration,
)
from predictionio_tpu.data.event import Event

UTC = dt.timezone.utc
NOW = dt.datetime(2026, 7, 29, 12, 0, 0, tzinfo=UTC)


def ev(name, eid, t_days_ago, props=None, etype="user", target=None,
       now=NOW):
    return Event(
        event=name, entity_type=etype, entity_id=eid,
        target_entity_type="item" if target else None,
        target_entity_id=target,
        properties=props or {},
        event_time=now - dt.timedelta(days=t_days_ago),
    )


@pytest.fixture()
def app(storage):
    a = storage.meta.create_app("cleanapp")
    return a


class TestParseDuration:
    def test_strings(self):
        assert parse_duration("3 days") == dt.timedelta(days=3)
        assert parse_duration("12h") == dt.timedelta(hours=12)
        assert parse_duration("90 seconds") == dt.timedelta(seconds=90)
        assert parse_duration("2 weeks") == dt.timedelta(weeks=2)

    def test_passthrough(self):
        assert parse_duration(60) == dt.timedelta(minutes=1)
        assert parse_duration(dt.timedelta(days=1)) == dt.timedelta(days=1)

    def test_bad(self):
        with pytest.raises(ValueError):
            parse_duration("yesterday-ish")


class TestCleanPersistedEvents:
    def test_drops_old_non_property_events(self, storage, app):
        storage.events.insert(ev("rate", "u1", 10, target="i1"), app.id)
        storage.events.insert(ev("rate", "u1", 1, target="i2"), app.id)
        stats = clean_persisted_events(
            "cleanapp", EventWindow(duration="3 days"), storage=storage, now=NOW)
        left = list(storage.events.find(app.id))
        assert [e.target_entity_id for e in left] == ["i2"]
        assert stats == {"kept": 1, "dropped": 1, "compacted": 0}

    def test_compacts_old_property_events(self, storage, app):
        storage.events.insert(ev("$set", "u1", 30, {"a": 1, "b": 1}), app.id)
        storage.events.insert(ev("$set", "u1", 20, {"b": 2, "c": 3}), app.id)
        storage.events.insert(ev("$unset", "u1", 10, {"a": ""}), app.id)
        storage.events.insert(ev("$set", "u1", 1, {"d": 4}), app.id)
        before = storage.events.aggregate_properties(app.id, "user")
        clean_persisted_events(
            "cleanapp",
            EventWindow(duration="3 days", compress_properties=True),
            storage=storage, now=NOW)
        left = list(storage.events.find(app.id))
        assert len(left) == 2  # one compacted $set + one recent $set
        after = storage.events.aggregate_properties(app.id, "user")
        # the compacted store aggregates to the identical snapshot
        assert after["u1"].properties == before["u1"].properties == {
            "b": 2, "c": 3, "d": 4}

    def test_compaction_off_drops_old_property_events(self, storage, app):
        storage.events.insert(ev("$set", "u1", 30, {"a": 1}), app.id)
        clean_persisted_events(
            "cleanapp", EventWindow(duration="3 days"), storage=storage, now=NOW)
        assert list(storage.events.find(app.id)) == []

    def test_deleted_entity_not_resurrected(self, storage, app):
        storage.events.insert(ev("$set", "u1", 30, {"a": 1}), app.id)
        storage.events.insert(ev("$delete", "u1", 20), app.id)
        clean_persisted_events(
            "cleanapp",
            EventWindow(duration="3 days", compress_properties=True),
            storage=storage, now=NOW)
        assert list(storage.events.find(app.id)) == []

    def test_remove_duplicates(self, storage, app):
        storage.events.insert(ev("buy", "u1", 1, target="i1"), app.id)
        storage.events.insert(ev("buy", "u1", 1, target="i1"), app.id)
        storage.events.insert(ev("buy", "u1", 1, target="i2"), app.id)
        stats = clean_persisted_events(
            "cleanapp", EventWindow(remove_duplicates=True),
            storage=storage, now=NOW)
        assert stats["kept"] == 2

    def test_no_duration_keeps_everything(self, storage, app):
        storage.events.insert(ev("buy", "u1", 500, target="i1"), app.id)
        stats = clean_persisted_events(
            "cleanapp", EventWindow(), storage=storage, now=NOW)
        assert stats == {"kept": 1, "dropped": 0, "compacted": 0}


class TestMixin:
    def test_window_from_params_and_clean(self, storage, app):
        from predictionio_tpu.controller.base import WorkflowContext

        class DS(SelfCleaningDataSource):
            params = {"eventWindow": {"duration": "3 days",
                                      "removeDuplicates": True,
                                      "compressProperties": True}}

        # the mixin cleans against REAL wall-clock now (no injection
        # point — matching production), so these events must be
        # relative to real now, not the fixture's fixed NOW: with the
        # fixed date this test became a time bomb that started failing
        # the moment wall-clock crossed NOW - 3 days + 1 day
        real_now = dt.datetime.now(UTC)
        for days_ago, target in ((10, "i1"), (1, "i2")):
            storage.events.insert(
                Event(event="rate", entity_type="user", entity_id="u1",
                      target_entity_type="item", target_entity_id=target,
                      event_time=real_now - dt.timedelta(days=days_ago)),
                app.id)
        ds = DS()
        w = ds.event_window()
        assert w and w.remove_duplicates and w.compress_properties
        ctx = WorkflowContext(storage=storage)
        stats = ds.clean(ctx, "cleanapp")
        assert stats["kept"] == 1

    def test_recommendation_template_wiring(self, storage, app):
        from predictionio_tpu.controller.base import WorkflowContext
        from predictionio_tpu.templates.recommendation.engine import (
            DataSourceParams, RecDataSource)

        # read_training cleans against REAL wall-clock now (see above):
        # event times are relative to it, not to the fixed NOW
        real_now = dt.datetime.now(UTC)
        storage.events.insert(
            ev("rate", "u1", 100, {"rating": 5.0}, target="i1",
               now=real_now), app.id)
        storage.events.insert(
            ev("rate", "u1", 1, {"rating": 3.0}, target="i2",
               now=real_now), app.id)
        ds = RecDataSource(DataSourceParams(
            app_name="cleanapp",
            event_window={"duration": "30 days"}))
        td = ds.read_training(WorkflowContext(storage=storage))
        assert [r.item for r in td.ratings] == ["i2"]
        assert len(list(storage.events.find(app.id))) == 1

    def test_no_window_noop(self, storage):
        from predictionio_tpu.controller.base import WorkflowContext

        class DS(SelfCleaningDataSource):
            params = {}

        assert DS().clean(WorkflowContext(storage=storage), "x") is None
