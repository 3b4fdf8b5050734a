"""Request-scoped tracing: span propagation (threads + async), ring
bounds, sampling rules, traceparent round-trip, fail-open export, and
the e2e contract — one trace id links an event POST to its coalesced
commit, and a query to its engine/sink spans (ISSUE 5)."""

import asyncio
import json
import logging
import socket
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import pytest

from predictionio_tpu.core.workflow import run_train
from predictionio_tpu.data.event import Event
from predictionio_tpu.server.engine_server import EngineServer
from predictionio_tpu.server.event_server import EventServer
from predictionio_tpu.server.eventsink import DirectEventSink
from predictionio_tpu.utils import tracing
from predictionio_tpu.utils.faults import FAULTS
from predictionio_tpu.utils.metrics import REGISTRY

FACTORY = "predictionio_tpu.templates.recommendation.engine:engine_factory"


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracing.TRACER.reset()
    yield
    tracing.TRACER.reset()
    FAULTS.disarm()


def _export_failures() -> float:
    return sum(tracing._M_EXPORT_FAILURES._values.values())


# -- unit: span model ----------------------------------------------------------


class TestSpanBasics:
    def test_disabled_is_noop(self):
        assert not tracing.TRACER.enabled
        with tracing.span("anything") as sp:
            assert sp is tracing.NOOP_SPAN
            assert tracing.current_trace_id() is None
        assert len(tracing.TRACER.ring) == 0

    def test_nesting_shares_trace_and_links_parent(self):
        tracing.TRACER.configure(enabled=True)
        with tracing.span("outer") as outer:
            with tracing.span("inner", k="v") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
                assert tracing.current_span() is inner
            assert tracing.current_span() is outer
        spans = tracing.TRACER.ring.trace(outer.trace_id)
        # trace() orders by start time: outer opened first
        assert [s["name"] for s in spans] == ["outer", "inner"]
        assert spans[1]["attrs"]["k"] == "v"
        assert all(s["durationUs"] >= 0 for s in spans)

    def test_error_capture(self):
        tracing.TRACER.configure(enabled=True)
        with pytest.raises(ValueError):
            with tracing.span("boom") as sp:
                raise ValueError("bad input")
        d = tracing.TRACER.ring.trace(sp.trace_id)[0]
        assert d["status"] == "error"
        assert "bad input" in d["error"]

    def test_add_attrs_enriches_current_span(self):
        tracing.TRACER.configure(enabled=True)
        with tracing.span("scan") as sp:
            tracing.add_attrs(records=7, backend="sql")
        d = tracing.TRACER.ring.trace(sp.trace_id)[0]
        assert d["attrs"] == {"records": 7, "backend": "sql"}
        # no current span → silently dropped, never raises
        tracing.add_attrs(ignored=True)

    def test_detached_span_ignores_ambient_parent(self):
        tracing.TRACER.configure(enabled=True)
        with tracing.span("request") as req:
            with tracing.detached_span("commit", link_traces=[req.trace_id]) as c:
                assert c.trace_id != req.trace_id
                assert c.parent_id is None


class TestPropagation:
    def test_bind_current_carries_context_to_thread(self):
        tracing.TRACER.configure(enabled=True)
        pool = ThreadPoolExecutor(max_workers=1)
        with tracing.span("request") as sp:
            def work():
                with tracing.span("worker") as w:
                    return w.trace_id
            # a raw executor does NOT propagate contextvars...
            bare = pool.submit(work).result()
            assert bare != sp.trace_id
            # ...bind_current does
            bound = pool.submit(tracing.bind_current(work)).result()
            assert bound == sp.trace_id
        pool.shutdown()

    def test_async_tasks_and_to_thread_inherit(self):
        tracing.TRACER.configure(enabled=True)

        async def main():
            async with tracing.span("request") as sp:
                async def child():
                    return tracing.current_trace_id()

                def blocking():
                    return tracing.current_trace_id()

                in_task = await asyncio.create_task(child())
                in_thread = await asyncio.to_thread(blocking)
                return sp.trace_id, in_task, in_thread

        tid, in_task, in_thread = asyncio.run(main())
        assert in_task == tid
        assert in_thread == tid


class TestRingAndSampling:
    def test_ring_is_bounded(self):
        tracing.TRACER.configure(enabled=True, ring_capacity=8)
        for i in range(20):
            with tracing.span(f"s{i}"):
                pass
        assert len(tracing.TRACER.ring) == 8
        newest = tracing.TRACER.ring.spans(limit=1)[0]
        assert newest["name"] == "s19"

    def test_sampling_gates_exporters_not_ring(self):
        exported = []

        class Sink:
            def export(self, d):
                exported.append(d)

        tracing.TRACER.configure(enabled=True, sample_rate=0.0,
                                 slow_span_ms=10_000.0, exporters=[Sink()])
        with tracing.span("fast-ok"):
            pass
        assert exported == []          # unsampled, fast, ok → file skipped
        assert len(tracing.TRACER.ring) == 1   # ring sees everything

        with pytest.raises(RuntimeError):
            with tracing.span("failed"):
                raise RuntimeError("x")
        assert [d["name"] for d in exported] == ["failed"]  # errors always

        tracing.TRACER.slow_span_ms = 0.0      # everything is "slow" now
        with tracing.span("slow"):
            pass
        assert [d["name"] for d in exported] == ["failed", "slow"]

    def test_sample_rate_validated(self):
        with pytest.raises(ValueError):
            tracing.TRACER.configure(enabled=True, sample_rate=1.5)

    def test_export_by_trace_ids(self):
        """The incident-bundle pin: filter the ring to an exemplar's
        trace-id set, oldest first; an empty set is an empty list, not
        a full dump."""
        tracing.TRACER.configure(enabled=True)
        ids = []
        for i in range(3):
            with tracing.span(f"root{i}") as sp:
                ids.append(sp.trace_id)
                with tracing.span(f"child{i}"):
                    pass
        wanted = {ids[0], ids[2]}
        got = tracing.TRACER.ring.export_by_trace_ids(wanted)
        assert {d["traceId"] for d in got} == wanted
        assert [d["name"] for d in got] == \
            ["root0", "child0", "root2", "child2"]  # oldest first
        starts = [d["startUs"] for d in got]
        assert starts == sorted(starts)
        assert tracing.TRACER.ring.export_by_trace_ids(set()) == []
        assert tracing.TRACER.ring.export_by_trace_ids({"nope"}) == []


class TestTraceparent:
    def test_roundtrip(self):
        tracing.TRACER.configure(enabled=True)
        with tracing.span("a") as sp:
            header = sp.traceparent()
        parsed = tracing.parse_traceparent(header)
        assert parsed == (sp.trace_id, sp.span_id, True)

    @pytest.mark.parametrize("bad", [
        "", "garbage", "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",
        "00-" + "0" * 32 + "-" + "b" * 16 + "-01",
        "00-" + "a" * 32 + "-" + "0" * 16 + "-01",
        "00-short-span-01",
    ])
    def test_rejects_malformed(self, bad):
        assert tracing.parse_traceparent(bad) is None

    def test_extract_prefers_traceparent(self):
        tp = "00-" + "a" * 32 + "-" + "b" * 16 + "-01"
        tid, parent, sampled = tracing.extract_headers(
            {"traceparent": tp, "x-pio-trace-id": "c" * 32})
        assert (tid, parent, sampled) == ("a" * 32, "b" * 16, True)
        tid, parent, _ = tracing.extract_headers({"x-pio-trace-id": "c" * 32})
        assert (tid, parent) == ("c" * 32, None)


class TestFailOpen:
    def test_export_fault_never_fails_the_span(self):
        tracing.TRACER.configure(enabled=True)
        FAULTS.arm("trace.export", error="disk full")
        before = _export_failures()
        with tracing.span("guarded") as sp:
            got = sp.trace_id
        assert got  # the traced work completed normally
        assert _export_failures() > before

    def test_broken_exporter_is_contained(self):
        class Broken:
            def export(self, d):
                raise OSError("enospc")

        tracing.TRACER.configure(enabled=True, exporters=[Broken()])
        before = _export_failures()
        with tracing.span("ok"):
            pass
        assert _export_failures() == before + 1
        assert len(tracing.TRACER.ring) == 1


class TestJSONLExporter:
    def test_write_and_rotate(self, tmp_path):
        path = str(tmp_path / "spans.jsonl")
        exp = tracing.JSONLExporter(path, max_bytes=200)
        for i in range(10):
            exp.export({"traceId": "t" * 32, "name": f"s{i}", "pad": "x" * 80})
        exp.close()
        rotated = tmp_path / "spans.jsonl.1"
        assert rotated.exists()
        # every line in both files is intact JSON
        for p in (rotated, tmp_path / "spans.jsonl"):
            for line in p.read_text().splitlines():
                assert json.loads(line)["traceId"] == "t" * 32


class TestHistogramExemplars:
    def test_labels_and_exemplar(self):
        h = REGISTRY.histogram("test_tracing_hist", "t", buckets=[0.1, 1.0],
                               labelnames=("status",))
        h.observe(0.05, ("ok",), exemplar="f" * 32)
        h.observe(5.0, ("error",))
        assert h.exemplar(0.1, ("ok",)) == ("f" * 32, 0.05)
        assert h.exemplar("+Inf", ("error",)) is None
        rendered = "\n".join(h.render())
        assert 'status="ok"' in rendered and 'le="0.1"' in rendered
        assert "f" * 32 not in rendered  # exemplars stay out of exposition
        with pytest.raises(ValueError):
            REGISTRY.histogram("test_tracing_hist", "t", labelnames=("other",))


# -- unit: verbs (always recorded, fetched in-process) --------------------------


class TestVerb:
    def test_recorded_while_tracing_is_disabled(self):
        assert not tracing.TRACER.enabled
        with tracing.verb("unit.verb", who="test") as root:
            assert tracing.TRACER.active
            with tracing.span("unit.child", n=1) as child:
                tracing.add_attrs(deep=True)
                with tracing.span("unit.grandchild"):
                    pass
            root.set_attr("status", "done")
        assert not tracing.TRACER.active
        tree = tracing.last_verb("unit.verb")
        assert [s["name"] for s in tree] == [
            "unit.verb", "unit.child", "unit.grandchild"]
        r, c, g = tree
        assert r["parentId"] is None and c["parentId"] == r["spanId"]
        assert g["parentId"] == c["spanId"]
        assert r["attrs"] == {"who": "test", "status": "done"}
        assert c["attrs"] == {"n": 1, "deep": True}
        assert child.seconds == (c["endNs"] - c["startNs"]) / 1e9 > 0
        assert r["startNs"] <= c["startNs"] <= g["startNs"] <= g["endNs"] \
            <= c["endNs"] <= r["endNs"]
        # the request tracer saw none of it
        assert len(tracing.TRACER.ring) == 0

    def test_span_outside_a_verb_stays_the_noop(self):
        assert tracing.span("x") is tracing.NOOP_SPAN
        assert tracing.NOOP_SPAN.seconds is None
        with tracing.verb("unit.verb"):
            seen = []
            # another thread, no context carried: not under the verb
            t = threading.Thread(
                target=lambda: seen.append(tracing.span("elsewhere")))
            t.start()
            t.join()
            assert seen == [tracing.NOOP_SPAN]
        assert tracing.span("x") is tracing.NOOP_SPAN
        assert [s["name"] for s in tracing.last_verb("unit.verb")] == [
            "unit.verb"]

    def test_bound_thread_records_under_the_verb(self):
        with tracing.verb("unit.verb") as root:
            def work():
                with tracing.span("unit.pooled"):
                    pass
            with ThreadPoolExecutor(max_workers=1) as pool:
                pool.submit(tracing.bind_current(work)).result()
        tree = tracing.last_verb("unit.verb")
        assert [s["name"] for s in tree] == ["unit.verb", "unit.pooled"]
        assert tree[1]["parentId"] == root.span_id

    def test_newest_finished_verb_replaces_the_tree(self):
        assert tracing.last_verb("unit.never") is None
        with tracing.verb("unit.verb", run=1):
            pass
        first = tracing.last_verb("unit.verb")
        with tracing.verb("unit.verb", run=2):
            # an open verb has not displaced the finished one
            assert tracing.last_verb("unit.verb") == first
        second = tracing.last_verb("unit.verb")
        assert second[0]["attrs"] == {"run": 2} and len(second) == 1
        second.clear()     # a copy: the record is not the caller's
        assert len(tracing.last_verb("unit.verb")) == 1
        # roots are kept apart by name
        with tracing.verb("unit.other"):
            pass
        assert tracing.last_verb("unit.verb")[0]["attrs"] == {"run": 2}

    def test_first_verb_is_written_once(self):
        """The cold verb's record outlives the warm ones: a second verb
        of the name replaces ``last_verb`` only."""
        assert tracing.first_verb("unit.verb") is None
        with tracing.verb("unit.verb", run=1):
            with tracing.span("unit.cold.only"):
                pass
        first = tracing.first_verb("unit.verb")
        assert first == tracing.last_verb("unit.verb")
        assert [s["name"] for s in first] == ["unit.verb", "unit.cold.only"]
        for run in (2, 3):
            with tracing.verb("unit.verb", run=run):
                # an open verb displaces neither record
                assert tracing.first_verb("unit.verb") == first
        assert tracing.first_verb("unit.verb") == first
        assert tracing.last_verb("unit.verb")[0]["attrs"] == {"run": 3}
        first.clear()      # a copy, as last_verb's
        assert len(tracing.first_verb("unit.verb")) == 2
        # kept by root name, and a failed verb is a first verb too
        with pytest.raises(RuntimeError):
            with tracing.verb("unit.other"):
                raise RuntimeError("boom")
        assert tracing.first_verb("unit.other")[0]["status"] == "error"
        assert tracing.first_verb("unit.verb")[0]["attrs"] == {"run": 1}
        tracing.TRACER.reset()
        assert tracing.first_verb("unit.verb") is None

    def test_verb_knows_its_root(self):
        with tracing.verb("unit.verb") as root:
            with tracing.span("unit.child") as child:
                assert child.verb is root.verb and child.verb.root is root

    def test_error_closes_the_verb_and_is_recorded(self):
        with pytest.raises(RuntimeError):
            with tracing.verb("unit.verb"):
                with tracing.span("unit.child"):
                    raise RuntimeError("boom")
        tree = tracing.last_verb("unit.verb")
        assert [s["status"] for s in tree] == ["error", "error"]
        assert "boom" in tree[1]["error"]
        assert not tracing.TRACER.active

    def test_enabled_tracer_gets_the_verb_spans_too(self):
        tracing.TRACER.configure(enabled=True)
        before = sum(tracing._M_SPANS._values.values())
        with tracing.verb("unit.verb") as root:
            with tracing.span("unit.child"):
                pass
        assert [d["name"] for d in tracing.TRACER.ring.trace(
            root.trace_id)] == ["unit.verb", "unit.child"]
        assert sum(tracing._M_SPANS._values.values()) == before + 2
        assert len(tracing.last_verb("unit.verb")) == 2
        # and disabling again leaves a later verb recorded all the same
        tracing.TRACER.configure(enabled=False)
        with tracing.verb("unit.verb"):
            pass
        assert len(tracing.TRACER.ring) == 2
        assert len(tracing.last_verb("unit.verb")) == 1

    def test_tree_renders_in_start_order(self):
        with tracing.verb("unit.verb"):
            for name in ("b.first", "a.second", "c.third"):
                with tracing.span(name):
                    pass
        text = tracing.render_trace_tree(tracing.last_verb("unit.verb"))
        assert [ln.split()[0] for ln in text.splitlines()] == [
            "unit.verb", "b.first", "a.second", "c.third"]

    def test_importing_tracing_does_not_import_jax(self):
        """The event and router servers import this module and must
        stay off JAX; only opening a verb reaches for it."""
        import subprocess
        import sys

        code = ("import sys; import predictionio_tpu.utils.tracing as t; "
                "assert 'jax' not in sys.modules, 'import'; "
                "t.TRACER.configure(enabled=True); "
                "s = t.span('x'); s.__enter__(); s.__exit__(None,None,None); "
                "assert 'jax' not in sys.modules, 'span'; print('ok')")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


# -- e2e: one trace id through the servers ------------------------------------


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class ServerThread:
    def __init__(self, server):
        self.server = server
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        asyncio.set_event_loop(self.loop)
        self.loop.run_until_complete(self.server.serve_forever())

    def __enter__(self):
        self.thread.start()
        deadline = time.time() + 10
        while time.time() < deadline:
            try:
                with socket.create_connection(
                        ("127.0.0.1", self.server.http.port), timeout=0.2):
                    return self
            except OSError:
                time.sleep(0.02)
        raise TimeoutError("server did not start")

    def __exit__(self, *exc):
        self.loop.call_soon_threadsafe(self.server.http.request_shutdown)
        self.thread.join(timeout=5)


def http(method, url, body=None, headers=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json",
                                          **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, json.loads(r.read().decode() or "null"), r.headers
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode() or "null"), e.headers


def _trace_spans(base, trace_id):
    _, body, _ = http("GET", f"{base}/traces?trace_id={trace_id}&limit=100")
    return body["spans"]


@pytest.fixture()
def app(storage):
    a = storage.meta.create_app("QuickApp")
    storage.events.init_channel(a.id)
    key = storage.meta.create_access_key(a.id)
    return a, key


VARIANT = {
    "id": "default",
    "engineFactory": FACTORY,
    "datasource": {"params": {"appName": "QuickApp"}},
    "algorithms": [{"name": "als",
                    "params": {"rank": 4, "numIterations": 4, "lambda": 0.05}}],
}


def _seed_ratings(storage, app_id, n_users=10, n_items=8):
    evs = []
    for u in range(n_users):
        for i in range(n_items):
            if (u + i) % 2 == 0:
                evs.append(Event(event="rate", entity_type="user",
                                 entity_id=str(u), target_entity_type="item",
                                 target_entity_id=str(i),
                                 properties={"rating": 4.0}))
    storage.events.insert_batch(evs, app_id)


class TestEndToEnd:
    def test_event_post_links_coalesced_commit(self, storage, app):
        """Acceptance: the trace id of a single-event POST is recoverable
        from the group commit that actually persisted it."""
        tracing.TRACER.configure(enabled=True)
        a, key = app
        port = free_port()
        my_tid = "ab" * 16
        with ServerThread(EventServer(storage=storage, host="127.0.0.1",
                                      port=port, ingest_batching=True)):
            base = f"http://127.0.0.1:{port}"
            code, body, headers = http(
                "POST", f"{base}/events.json?accessKey={key.key}",
                {"event": "rate", "entityType": "user", "entityId": "1",
                 "targetEntityType": "item", "targetEntityId": "2",
                 "properties": {"rating": 5.0}},
                headers={"X-PIO-Trace-Id": my_tid})
            assert code == 201
            assert headers["X-PIO-Trace-Id"] == my_tid

            # the request's own spans carry our trace id
            spans = _trace_spans(base, my_tid)
            names = {s["name"] for s in spans}
            assert "http.request" in names
            assert "ingest.submit" in names

            # the detached commit span links back to our trace
            _, all_body, _ = http("GET", f"{base}/traces?limit=500")
            commits = [s for s in all_body["spans"]
                       if s["name"] == "ingest.commit"]
            assert commits, "no ingest.commit span exported"
            linked = [s for s in commits
                      if my_tid in s.get("attrs", {}).get("link_traces", [])]
            assert linked, f"commit spans did not link {my_tid}: {commits}"
            assert linked[0]["attrs"]["records"] >= 1

    def test_query_trace_links_engine_and_sink(self, storage, app):
        """Acceptance: one trace id covers query → predict → feedback
        sink, retrievable via /traces."""
        a, key = app
        _seed_ratings(storage, a.id)
        run_train(FACTORY, variant=VARIANT, storage=storage, use_mesh=False)
        tracing.TRACER.configure(enabled=True)
        port = free_port()
        my_tid = "cd" * 16
        with ServerThread(EngineServer(
                engine_factory=FACTORY, storage=storage,
                host="127.0.0.1", port=port,
                event_sink=DirectEventSink(storage, "QuickApp"))):
            base = f"http://127.0.0.1:{port}"
            code, pred, headers = http(
                "POST", f"{base}/queries.json", {"user": "2", "num": 3},
                headers={"X-PIO-Trace-Id": my_tid})
            assert code == 200 and "prId" in pred
            assert headers["X-PIO-Trace-Id"] == my_tid

            # feedback is async — poll until its spans land in the ring
            deadline = time.time() + 10
            names = set()
            while time.time() < deadline:
                names = {s["name"] for s in _trace_spans(base, my_tid)}
                if "sink.send" in names:
                    break
                time.sleep(0.05)
            assert {"http.request", "engine.query", "engine.predict",
                    "engine.feedback", "sink.send"} <= names

    def test_traceparent_header_adopted(self, storage, app):
        tracing.TRACER.configure(enabled=True)
        a, key = app
        port = free_port()
        tp_tid, tp_span = "12" * 16, "34" * 8
        with ServerThread(EventServer(storage=storage, host="127.0.0.1",
                                      port=port)):
            base = f"http://127.0.0.1:{port}"
            _, _, headers = http(
                "GET", f"{base}/", headers={
                    "traceparent": f"00-{tp_tid}-{tp_span}-01"})
            assert headers["X-PIO-Trace-Id"] == tp_tid
            spans = _trace_spans(base, tp_tid)
            root = [s for s in spans if s["name"] == "http.request"][0]
            assert root["parentId"] == tp_span

    def test_traces_endpoint_filters_and_validates(self, storage, app):
        tracing.TRACER.configure(enabled=True)
        port = free_port()
        with ServerThread(EventServer(storage=storage, host="127.0.0.1",
                                      port=port)):
            base = f"http://127.0.0.1:{port}"
            http("GET", f"{base}/")
            code, body, _ = http("GET", f"{base}/traces?error=1")
            assert code == 200 and body["enabled"] is True
            assert all(s["status"] == "error" for s in body["spans"])
            code, _, _ = http("GET", f"{base}/traces?min_ms=notanumber")
            assert code == 400

    def test_exporter_fault_never_fails_requests(self, storage, app):
        """Acceptance: an armed trace.export fault must not surface."""
        tracing.TRACER.configure(enabled=True)
        a, key = app
        port = free_port()
        FAULTS.arm("trace.export", error="injected export failure")
        before = _export_failures()
        with ServerThread(EventServer(storage=storage, host="127.0.0.1",
                                      port=port)):
            base = f"http://127.0.0.1:{port}"
            code, body, _ = http(
                "POST", f"{base}/events.json?accessKey={key.key}",
                {"event": "rate", "entityType": "user", "entityId": "1",
                 "targetEntityType": "item", "targetEntityId": "2"})
            assert code == 201
        assert _export_failures() > before

    def test_access_log_line(self, storage, app, caplog):
        port = free_port()
        with caplog.at_level(logging.INFO, logger="pio.access"):
            with ServerThread(EventServer(storage=storage, host="127.0.0.1",
                                          port=port, access_log=True)):
                http("GET", f"http://127.0.0.1:{port}/")
                deadline = time.time() + 5
                while time.time() < deadline and not caplog.records:
                    time.sleep(0.02)
        lines = [json.loads(r.getMessage()) for r in caplog.records
                 if r.name == "pio.access"]
        assert lines, "no access log line emitted"
        entry = [l for l in lines if l["path"] == "/"][0]
        assert entry["method"] == "GET"
        assert entry["status"] == 200
        assert entry["duration_ms"] >= 0
        # tracing disabled → no trace id, but the line still renders
        assert "trace_id" in entry

    def test_disabled_tracing_adds_no_spans_or_headers(self, storage, app):
        port = free_port()
        with ServerThread(EventServer(storage=storage, host="127.0.0.1",
                                      port=port)):
            base = f"http://127.0.0.1:{port}"
            _, _, headers = http("GET", f"{base}/")
            assert headers.get("X-PIO-Trace-Id") is None
            _, body, _ = http("GET", f"{base}/traces")
            assert body == {"enabled": False, "count": 0, "spans": []}
