"""Fleet-router tests (server/router.py): replica state machine driven
by active /health polling + passive breaker ejection, P2C routing,
retry budget, hedging, Retry-After honoring, deadline/trace
propagation, replica identity resets, manifest watching, and the
zero-downtime rolling reload (docs/operations.md "Fleet deployment")."""

import asyncio
import contextlib
import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.server.http import HTTPServer, Response, Router
from predictionio_tpu.server.router import (
    DOWN,
    OK,
    FleetRouter,
    Replica,
    _Attempt,
)
from predictionio_tpu.utils.faults import FAULTS
from tests.test_servers import ServerThread, free_port, http


@pytest.fixture(autouse=True)
def disarm_faults():
    FAULTS.disarm()
    yield
    FAULTS.disarm()


def http_full(method, url, body=None, headers=None, timeout=30):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json",
                                          **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode() or "null"), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode() or "null"), dict(e.headers)


def cval(counter, *labels):
    """Current value of one labelled counter series (counters are
    process-global, so tests assert DELTAS around the action)."""
    return counter._values.get(tuple(labels), 0)


def wait_until(cond, timeout=5.0, interval=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


class StubReplica:
    """A scriptable engine-server stand-in speaking the replica
    contract the router depends on: /health with identity fields,
    /queries.json, /events.json (non-idempotent), /reload."""

    def __init__(self, port, instance="stub", latency=0.0):
        self.port = port
        self.instance = instance
        self.health_status = "ok"
        self.health_retry_after = None   # retryAfterSec on not-ready
        self.latency = latency           # seconds per query
        self.query_status = 200
        self.query_retry_after = None    # Retry-After header on errors
        self.fail_first = 0              # answer 500 to the first N queries
        self.started_at = 1000.0
        self.reload_generation = 0
        self.queries = 0
        self.events = 0
        self.reloads = 0
        router = Router()
        router.route("GET", "/", self._root)
        router.route("GET", "/health", self._health)
        router.route("GET", "/reload", self._reload)
        router.route("POST", "/queries.json", self._query)
        router.route("POST", "/events.json", self._event)
        self.http = HTTPServer(router, "127.0.0.1", port,
                               access_log=False, server_name="stub")

    @property
    def url(self):
        return f"127.0.0.1:{self.port}"

    async def serve_forever(self):
        await self.http.serve_forever()

    async def _root(self, req):
        return Response.json({"status": "stub"})

    async def _health(self, req):
        body = {"status": self.health_status, "instance": self.instance,
                "startedAt": self.started_at,
                "reloadGeneration": self.reload_generation}
        if self.health_status == "not-ready":
            if self.health_retry_after is not None:
                body["retryAfterSec"] = self.health_retry_after
            resp = Response.json(body, status=503)
            resp.headers["Retry-After"] = "1"
            return resp
        return Response.json(body)

    async def _query(self, req):
        self.queries += 1
        if self.latency:
            await asyncio.sleep(self.latency)
        if self.fail_first > 0:
            self.fail_first -= 1
            return Response.json({"message": "induced failure"}, status=500)
        if self.query_status != 200:
            resp = Response.json({"message": "induced"},
                                 status=self.query_status)
            if self.query_retry_after is not None:
                resp.headers["Retry-After"] = self.query_retry_after
            return resp
        return Response.json({"instance": self.instance,
                              "seen": dict(req.headers)})

    async def _event(self, req):
        self.events += 1
        if self.query_status != 200:
            return Response.json({"message": "induced"},
                                 status=self.query_status)
        return Response.json({"eventId": "stub"}, status=201)

    async def _reload(self, req):
        self.reloads += 1
        self.reload_generation += 1
        return Response.json({"reloadGeneration": self.reload_generation})


@contextlib.contextmanager
def fleet(n=2, router_kwargs=None, stub_latency=None):
    """n live stub replicas + a router over them, all on daemon
    threads. Yields (router, stubs, threads)."""
    stubs = [StubReplica(free_port(), instance=f"stub-{i}",
                         latency=(stub_latency or [0.0] * n)[i])
             for i in range(n)]
    with contextlib.ExitStack() as stack:
        threads = [stack.enter_context(ServerThread(s)) for s in stubs]
        router = FleetRouter([s.url for s in stubs],
                             host="127.0.0.1", port=free_port(),
                             **(router_kwargs or {}))
        stack.enter_context(ServerThread(router))
        yield router, stubs, threads


class TestReplicaUnits:
    def test_parse_hostport_accepts_bare_and_url_forms(self):
        assert Replica.parse_hostport("10.0.0.1:8000") == ("10.0.0.1", 8000)
        assert Replica.parse_hostport("http://h:81") == ("h", 81)
        with pytest.raises(ValueError, match="host:port"):
            Replica.parse_hostport("no-port-here")

    def test_availability_gates(self):
        r = Replica(f"127.0.0.1:{free_port()}")
        r.state = OK
        assert r.available(now=0.0)
        r.draining = True
        assert not r.available(now=0.0)
        r.draining = False
        r.backoff_until = 10.0
        assert not r.available(now=0.0)       # inside Retry-After window
        assert r.available(now=10.0)
        r.state = DOWN
        assert not r.available(now=10.0)

    def test_attempt_retryable_classification(self):
        r = Replica(f"127.0.0.1:{free_port()}")
        assert _Attempt(r, 0, {}, b"").retryable       # transport
        assert _Attempt(r, 500, {}, b"").retryable
        assert _Attempt(r, 429, {}, b"").retryable
        assert not _Attempt(r, 200, {}, b"").retryable
        assert not _Attempt(r, 404, {}, b"").retryable  # client's problem


class TestRouting:
    def test_spreads_queries_over_healthy_replicas(self):
        with fleet(2, {"hedge": False}) as (router, stubs, _):
            base = f"http://127.0.0.1:{router.http.port}"
            for _ in range(20):
                code, body = http("POST", f"{base}/queries.json",
                                  {"user": "1"})
                assert code == 200
            assert stubs[0].queries + stubs[1].queries == 20
            # sequential load carries no inflight signal, so P2C may
            # legitimately favor the replica with the lower EWMA — but
            # the fresh-replica floor guarantees both get work
            assert stubs[0].queries >= 1 and stubs[1].queries >= 1

    def test_dead_replica_is_absorbed_by_passive_ejection(self):
        # passive path only: health polls far apart, so the breaker —
        # fed by live request failures — must do the ejecting. The
        # stopped stub's sockets stay half-open (the loop just stops),
        # so the per-try timeout is what surfaces the failure — the
        # worst case of a kill: a peer that neither answers nor resets.
        # The survivor answers in 50 ms, so the dark replica's last
        # EWMA (or its fresh floor) stays the lower P2C score and live
        # requests keep reaching it: on an idle host two equally fast
        # stubs let the survivor's EWMA sink under the dead one's and
        # no request ever failed (the breaker stayed closed).
        with fleet(2, {"hedge": False, "health_interval": 30.0,
                       "per_try_timeout_ms": 300.0},
                   stub_latency=[0.0, 0.05]) as (
                router, stubs, threads):
            base = f"http://127.0.0.1:{router.http.port}"
            assert http("POST", f"{base}/queries.json", {})[0] == 200
            threads[0].__exit__(None, None, None)  # stub-0 goes dark
            before = stubs[1].queries
            for _ in range(20):
                assert http("POST", f"{base}/queries.json", {})[0] == 200
            assert stubs[1].queries - before >= 15
            dead = next(r for r in router.replicas
                        if r.name == stubs[0].url)
            assert dead.breaker.state == "open"

    def test_injected_replica_down_is_retried_to_200(self):
        with fleet(2, {"hedge": False}) as (router, stubs, _):
            base = f"http://127.0.0.1:{router.http.port}"
            assert http("POST", f"{base}/queries.json", {})[0] == 200
            before = cval(router._m_retries, "transport", "-")
            FAULTS.arm("router.replica.down", error="replica gone", count=1)
            code, _ = http("POST", f"{base}/queries.json", {})
            assert code == 200
            assert cval(router._m_retries, "transport", "-") == before + 1

    def test_transient_500s_are_retried_until_success(self):
        with fleet(1, {"hedge": False}) as (router, stubs, _):
            base = f"http://127.0.0.1:{router.http.port}"
            stubs[0].fail_first = 2
            before = cval(router._m_retries, "500", "-")
            code, _ = http("POST", f"{base}/queries.json", {})
            assert code == 200
            assert stubs[0].queries == 3
            assert cval(router._m_retries, "500", "-") == before + 2


class TestRetryPolicy:
    def test_non_idempotent_post_is_never_retried(self):
        with fleet(1, {"hedge": False}) as (router, stubs, _):
            base = f"http://127.0.0.1:{router.http.port}"
            stubs[0].query_status = 500
            before = cval(router._m_retry_denied, "non_idempotent", "-")
            code, _ = http("POST", f"{base}/events.json", {"event": "buy"})
            assert code == 500          # passthrough, not masked
            assert stubs[0].events == 1  # exactly ONE delivery attempt
            assert cval(router._m_retry_denied,
                        "non_idempotent", "-") == before + 1

    def test_retry_budget_caps_amplification(self):
        with fleet(1, {"hedge": False, "retry_budget_ratio": 0.0,
                       "retry_budget_burst": 1.0}) as (router, stubs, _):
            base = f"http://127.0.0.1:{router.http.port}"
            stubs[0].query_status = 500
            denied = cval(router._m_retry_denied, "budget", "-")
            code, _ = http("POST", f"{base}/queries.json", {})
            assert code == 500
            # one original + the single budgeted retry, then denial
            assert stubs[0].queries == 2
            assert cval(router._m_retry_denied, "budget", "-") >= denied + 1
            # keep failing: the breaker (threshold 3) ejects the
            # replica, and with nothing left the router answers 503
            code, _ = http("POST", f"{base}/queries.json", {})
            assert code == 500
            code, body, headers = http_full(
                "POST", f"{base}/queries.json", {})
            assert code == 503
            assert "no replica available" in body["message"]
            assert int(headers["Retry-After"]) >= 1

    def test_replica_retry_after_is_honored(self):
        with fleet(2, {"hedge": False, "health_interval": 30.0}) as (
                router, stubs, _):
            base = f"http://127.0.0.1:{router.http.port}"
            stubs[0].query_status = 503
            stubs[0].query_retry_after = "30"
            # keep querying until the throttling replica has answered
            # one 503 (the retry masks it: the client still sees 200)
            for _ in range(20):
                assert http("POST", f"{base}/queries.json", {})[0] == 200
                if stubs[0].queries:
                    break
            assert stubs[0].queries >= 1
            throttled = next(r for r in router.replicas
                             if r.name == stubs[0].url)
            assert throttled.backoff_until > 0
            seen = stubs[0].queries
            for _ in range(10):
                assert http("POST", f"{base}/queries.json", {})[0] == 200
            # inside its Retry-After window the replica gets NOTHING
            assert stubs[0].queries == seen


class TestHedging:
    def test_slow_primary_is_hedged_first_answer_wins(self):
        with fleet(2, {"hedge_min_ms": 30.0}) as (router, stubs, _):
            base = f"http://127.0.0.1:{router.http.port}"
            assert http("POST", f"{base}/queries.json", {})[0] == 200
            won = cval(router._m_hedges, "won", "-")
            launched = cval(router._m_hedges, "launched", "-")
            FAULTS.arm("router.replica.slow", latency=0.8, count=1)
            t0 = time.perf_counter()
            code, _ = http("POST", f"{base}/queries.json", {})
            elapsed = time.perf_counter() - t0
            assert code == 200
            # answered at ~the 30ms hedge delay, not the 800ms stall
            assert elapsed < 0.6
            assert cval(router._m_hedges, "launched", "-") == launched + 1
            assert cval(router._m_hedges, "won", "-") == won + 1


class TestHealthAndIdentity:
    def test_health_flap_marks_down_then_recovers(self):
        with fleet(2, {"hedge": False, "health_interval": 0.1}) as (
                router, stubs, _):
            base = f"http://127.0.0.1:{router.http.port}"
            assert http("GET", f"{base}/health")[0] == 200
            FAULTS.arm("router.health.flap", error="partitioned")
            assert wait_until(lambda: all(r.state == DOWN
                                          for r in router.replicas))
            code, body, headers = http_full("GET", f"{base}/health")
            assert code == 503 and body["status"] == "not-ready"
            assert int(headers["Retry-After"]) >= 1
            assert http("POST", f"{base}/queries.json", {})[0] == 503
            FAULTS.disarm()
            assert wait_until(lambda: all(r.state == OK
                                          for r in router.replicas))
            assert http("POST", f"{base}/queries.json", {})[0] == 200

    def test_restarted_replica_identity_resets_breaker_and_ewma(self):
        with fleet(1, {"hedge": False, "health_interval": 0.1}) as (
                router, stubs, _):
            rep = router.replicas[0]
            assert wait_until(lambda: rep.instance == "stub-0")
            for _ in range(3):
                rep.breaker.record_failure()
            rep.ewma_sec = 1.5
            assert rep.breaker.state == "open"
            # same process flapping: the breaker stays open across polls
            time.sleep(0.3)
            assert rep.breaker.state == "open"
            # ...but a NEW process id means a restart: forgive the past
            stubs[0].instance = "stub-0-reborn"
            assert wait_until(lambda: rep.instance == "stub-0-reborn")
            assert rep.breaker.state == "closed"
            assert rep.ewma_sec == 0.0
            base = f"http://127.0.0.1:{router.http.port}"
            assert http("POST", f"{base}/queries.json", {})[0] == 200

    def test_not_ready_health_backs_off_by_its_hint(self):
        with fleet(2, {"hedge": False, "health_interval": 0.1}) as (
                router, stubs, _):
            stubs[0].health_status = "not-ready"
            stubs[0].health_retry_after = 30.0
            rep = next(r for r in router.replicas
                       if r.name == stubs[0].url)
            assert wait_until(lambda: rep.state == "not-ready"
                              and rep.backoff_until > 0)
            base = f"http://127.0.0.1:{router.http.port}"
            before = stubs[0].queries
            for _ in range(5):
                assert http("POST", f"{base}/queries.json", {})[0] == 200
            assert stubs[0].queries == before


class TestPropagation:
    def test_deadline_shrinks_and_trace_headers_flow_through(self):
        with fleet(1, {"hedge": False}) as (router, stubs, _):
            base = f"http://127.0.0.1:{router.http.port}"
            tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
            code, body = http(
                "POST", f"{base}/queries.json", {"user": "1"},
                headers={"X-PIO-Deadline-Ms": "5000", "traceparent": tp,
                         "X-PIO-Trace-Id": "trace-42"})
            assert code == 200
            seen = body["seen"]
            fwd = float(seen["x-pio-deadline-ms"])
            # the hop budget SHRINKS: below what the client sent, but
            # not collapsed (router overhead is a few ms)
            assert 4000 < fwd < 5000
            assert seen["traceparent"] == tp
            assert seen["x-pio-trace-id"] == "trace-42"


class TestRollingReload:
    def test_rolling_reload_serves_zero_errors(self):
        with fleet(3, {"hedge": False, "health_interval": 0.2,
                       "drain_timeout": 5.0, "ready_timeout": 10.0}) as (
                router, stubs, _):
            base = f"http://127.0.0.1:{router.http.port}"
            assert http("POST", f"{base}/queries.json", {})[0] == 200
            stop = threading.Event()
            statuses = []

            def hammer():
                while not stop.is_set():
                    statuses.append(
                        http("POST", f"{base}/queries.json", {})[0])

            t = threading.Thread(target=hammer)
            t.start()
            try:
                code, body, _ = http_full(
                    "POST", f"{base}/router/reload?rolling=1", timeout=60)
            finally:
                time.sleep(0.2)
                stop.set()
                t.join(timeout=10)
            assert code == 200 and body["ok"] is True
            assert len(body["replicas"]) == 3
            assert all(e["result"] == "ok" for e in body["replicas"])
            assert all(s.reloads == 1 for s in stubs)
            assert all(e["reloadGeneration"] == 1 for e in body["replicas"])
            # a full-fleet model swap served zero errors
            assert statuses and set(statuses) == {200}

    def test_non_rolling_reload_hits_every_replica(self):
        with fleet(2, {"hedge": False}) as (router, stubs, _):
            base = f"http://127.0.0.1:{router.http.port}"
            code, body, _ = http_full("POST", f"{base}/router/reload",
                                      timeout=60)
            assert code == 200 and body["ok"] is True
            assert body["rolling"] is False
            assert all(s.reloads == 1 for s in stubs)


class TestEndpointsAndManifest:
    def test_status_root_and_metrics(self):
        with fleet(2, {"hedge": False, "health_interval": 0.1}) as (
                router, stubs, _):
            base = f"http://127.0.0.1:{router.http.port}"
            assert wait_until(
                lambda: all(r.state == OK for r in router.replicas))
            code, body = http("GET", f"{base}/")
            assert code == 200
            assert body["status"] == "router" and body["available"] == 2
            code, body = http("GET", f"{base}/router/status")
            assert code == 200
            snaps = {s["url"]: s for s in body["replicas"]}
            assert set(snaps) == {f"http://{s.url}" for s in stubs}
            for i, s in enumerate(stubs):
                snap = snaps[f"http://{s.url}"]
                assert snap["state"] == "ok"
                assert snap["instance"] == f"stub-{i}"
                assert snap["breaker"] == "closed"
            assert body["retryBudgetTokens"] > 0
            req = urllib.request.Request(f"{base}/metrics")
            with urllib.request.urlopen(req, timeout=10) as r:
                text = r.read().decode()
            for name in ("pio_router_replica_state",
                         "pio_router_retry_budget_remaining",
                         "pio_router_replica_seconds"):
                assert name in text

    def test_manifest_watch_adds_and_removes_replicas(self, tmp_path):
        s1 = StubReplica(free_port(), instance="m-0")
        s2 = StubReplica(free_port(), instance="m-1")
        manifest = tmp_path / "fleet.txt"
        manifest.write_text(f"# fleet\n{s1.url}\n")
        with ServerThread(s1), ServerThread(s2):
            router = FleetRouter(manifest=str(manifest),
                                 host="127.0.0.1", port=free_port(),
                                 hedge=False, health_interval=0.1)
            with ServerThread(router):
                assert [r.name for r in router.replicas] == [s1.url]
                manifest.write_text(f"{s1.url}\n{s2.url}\n")
                os.utime(manifest, (time.time() + 5, time.time() + 5))
                assert wait_until(lambda: len(router.replicas) == 2)
                assert wait_until(
                    lambda: all(r.state == OK for r in router.replicas))
                manifest.write_text(f"{s2.url}\n")
                os.utime(manifest, (time.time() + 10, time.time() + 10))
                assert wait_until(lambda: len(router.replicas) == 1)
                assert router.replicas[0].name == s2.url
                base = f"http://127.0.0.1:{router.http.port}"
                code, body = http("POST", f"{base}/queries.json", {})
                assert code == 200 and body["instance"] == "m-1"
