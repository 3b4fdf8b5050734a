"""Serving-latency decomposition for recommendation top-10.

The driver metric's second half (BASELINE.md) is predict p50 over
`POST /queries.json`. A single number hides where the time goes, so
this harness measures the three layers separately (all warm, ML-20M
geometry factors):

1. ``device_ms``  — the fused gather→score→top-k program + one packed
   fetch (``models/als.py ResidentScorer``), the only part that
   changes with the accelerator.
2. ``host_ms``    — ``DeployedEngine.query()``: the REAL deploy path
   (model lookup, BiMap id translation, serving wrapper) around
   layer 1, no HTTP.
3. ``http_ms``    — end-to-end ``POST /queries.json`` against a live
   ``EngineServer`` on 127.0.0.1 (layers 1+2 plus JSON codec and the
   asyncio HTTP stack).

The model is fabricated at ML-20M shape (synthetic factors persisted
through the template's own ``save_model`` and a real EngineInstance
row) so the measurement drives the genuine serving path without a
20M-event ingest. Layer shares are reported as p50/p99 and the derived
``http_overhead_ms = http − host`` and ``host_overhead_ms = host −
device``.

Usage::

    python profile_serving.py [--queries 2000] [--platform cpu|tpu]

Fault-injection mode (the acceptance harness for docs/operations.md
"Failure modes and degradation")::

    python profile_serving.py --fault "eventsink.send:error=down"

measures a healthy baseline, arms the given ``PIO_FAULTS``-style spec,
re-runs the same load with feedback enabled, and reports the p50
ratio, per-status counts, feedback counters, and breaker states —
e.g. under a sustained sink failure the ``engine_feedback_sink``
breaker must open and serving p50 must stay within 2x of healthy.

Continuous-training chaos mode (the acceptance harness for
docs/operations.md "Continuous training")::

    python profile_serving.py --train-loop

drives real ``pio train --continuous`` subprocesses and a shared-home
replica through kill -9 mid-delta-train (resume + exactly one
promotion), an injected ``promote.regression`` (guardrail refusal,
fleet stays on the champion), and a fenced-out second trainer — all
under live serving load that must stay all-200s.

SLO burn-rate chaos mode (the acceptance harness for
docs/operations.md "Responding to an SLO fast-burn alert")::

    python profile_serving.py --slo

runs the synthetic prober against one replica behind a FleetRouter
with second-scale burn windows, injects ``router.replica.down``, and
proves the availability SLO trips its FAST burn within two scrape
intervals and degrades ``/health``, then that the page clears after
the fault is lifted and the fleet serves all-200 again — with zero
XLA compiles on the serving path across the whole drill.

Self-healing fleet chaos mode (the acceptance harness for
docs/operations.md "Self-healing fleet")::

    python profile_serving.py --autoscale

runs a ReplicaPool of real replica subprocesses behind a FleetRouter
with the SLO-driven autoscaler and auto-remediation enabled: a 10x
traffic ramp must scale the fleet 1→N with zero 5xx and post-scale
p99 within 2x of baseline; a kill -9'd replica under an armed
``remediate.storm`` must be remediated exactly once (the rate limit
is the storm guard); scale-down must never drop below one healthy
replica; and ``pio doctor --act`` WITHOUT ``--yes`` must print the
full remediation plan while executing nothing.

Prints ONE JSON line. Run with ``--platform cpu`` for the HTTP/host
shares and on the chip for the device share.
"""

from __future__ import annotations

import argparse
import http.client
import json
import time

import numpy as np


def fabricate_instance(storage, n_users: int, n_items: int, rank: int,
                       instance_id: str = "profile-serving", seed: int = 0):
    """Persist a synthetic ALS model + COMPLETED EngineInstance the way
    `pio train` would, so prepare_deploy loads the real thing."""
    from predictionio_tpu.core.workflow import frame_models
    from predictionio_tpu.storage.meta import EngineInstance
    from predictionio_tpu.templates.recommendation.engine import (
        ALSAlgorithm,
        ALSAlgorithmParams,
        ALSModel,
    )
    from predictionio_tpu.utils.bimap import BiMap
    from predictionio_tpu.data.event import utcnow

    rng = np.random.default_rng(seed)
    U = (rng.standard_normal((n_users, rank)) / np.sqrt(rank)).astype(
        np.float32)
    V = (rng.standard_normal((n_items, rank)) / np.sqrt(rank)).astype(
        np.float32)
    user_ids = BiMap({str(i): i for i in range(n_users)})
    item_ids = BiMap({str(i): i for i in range(n_items)})
    model = ALSModel(U, V, user_ids, item_ids)
    algo = ALSAlgorithm(ALSAlgorithmParams(rank=rank))
    blob = algo.save_model(model, None)

    factory = "predictionio_tpu.templates.recommendation.engine:engine_factory"
    ei = EngineInstance(
        id=instance_id, status="COMPLETED",
        start_time=utcnow(), end_time=utcnow(),
        engine_factory=factory, engine_variant="", batch="",
        env={}, mesh_conf={},
        data_source_params=json.dumps({"appName": "ProfileApp"}),
        preparator_params="{}",
        algorithms_params=json.dumps(
            [{"name": "als", "params": {"rank": rank}}]),
        serving_params="{}")
    storage.meta.insert_engine_instance(ei)
    storage.models.put_parts(ei.id, frame_models([blob]))
    return factory


def measure(fn, iters: int, warmup: int = 20):
    for _ in range(warmup):
        fn()
    lat = np.empty(iters)
    for i in range(iters):
        t0 = time.perf_counter()
        fn()
        lat[i] = time.perf_counter() - t0
    return (float(np.percentile(lat, 50) * 1e3),
            float(np.percentile(lat, 99) * 1e3))


def _client_proc(port: int, n_users: int, n: int, seed: int, outq) -> None:
    """One closed-loop HTTP client in its own process (own GIL)."""
    import http.client as hc
    import json as _json
    import time as _time

    import numpy as _np

    try:
        conn = hc.HTTPConnection("127.0.0.1", port, timeout=10)
        rng = _np.random.default_rng(seed)
        lats = []
        for _ in range(n):
            body = _json.dumps(
                {"user": str(int(rng.integers(0, n_users))), "num": 10})
            t0 = _time.perf_counter()
            conn.request("POST", "/queries.json", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
            dt = _time.perf_counter() - t0
            assert resp.status == 200, data[:200]
            lats.append(dt)
        conn.close()
        outq.put(lats)
    except BaseException as e:  # noqa: BLE001 — report, don't hang join
        outq.put(f"client {seed}: {type(e).__name__}: {e}")


def _tenant_client_proc(port: int, app: str, n_users: int, n: int,
                        seed: int, pace_s: float, outq) -> None:
    """One tenant-labelled closed-loop client in its own process.

    Sends ``X-PIO-App`` so the engine server's fair-admission gate can
    attribute the load; reports per-status counts, success latencies,
    and any throttle response that arrived WITHOUT a Retry-After."""
    import http.client as hc
    import json as _json
    import time as _time

    import numpy as _np

    try:
        conn = hc.HTTPConnection("127.0.0.1", port, timeout=30)
        rng = _np.random.default_rng(seed)
        lats = []
        statuses: dict = {}
        retry_after_missing = 0
        for _ in range(n):
            body = _json.dumps(
                {"user": str(int(rng.integers(0, n_users))), "num": 10})
            t0 = _time.perf_counter()
            conn.request("POST", "/queries.json", body,
                         {"Content-Type": "application/json",
                          "X-PIO-App": app})
            resp = conn.getresponse()
            resp.read()
            dt = _time.perf_counter() - t0
            statuses[str(resp.status)] = statuses.get(str(resp.status), 0) + 1
            if resp.status == 200:
                lats.append(dt)
            elif resp.getheader("Retry-After") is None:
                retry_after_missing += 1
            if pace_s > 0:
                _time.sleep(pace_s)
        conn.close()
        outq.put({"app": app, "lats": lats, "statuses": statuses,
                  "retry_after_missing": retry_after_missing})
    except BaseException as e:  # noqa: BLE001 — report, don't hang join
        outq.put(f"client {app}/{seed}: {type(e).__name__}: {e}")


def _replica_main(args) -> None:
    """Hidden subprocess entry (``--_replica-port``): one engine-server
    replica with its own in-memory storage. ``fabricate_instance`` is
    deterministic (seeded rng), so every replica serves the identical
    model — the router A/B compares routing, not models.

    With ``--_replica-home`` the replica instead shares an on-disk
    storage home (SQLITE + LOCALFS) with the continuous trainer and
    starts engine-less (``require_engine=False``): the trainer's
    ``/reload`` pushes are what make it serve, exactly as in
    production."""
    from profile_common import resolve_platform

    resolve_platform(args.platform)
    from predictionio_tpu.server.engine_server import EngineServer

    if args.replica_home:
        from predictionio_tpu.storage.registry import (Storage,
                                                       StorageConfig,
                                                       set_storage)

        st = Storage(StorageConfig(home=args.replica_home))
        set_storage(st)
        factory = ("predictionio_tpu.templates.recommendation.engine:"
                   "engine_factory")
        server = EngineServer(engine_factory=factory, storage=st,
                              host="127.0.0.1", port=args.replica_port,
                              require_engine=False)
    else:
        from profile_common import make_memory_storage

        st = make_memory_storage()
        factory = fabricate_instance(st, args.n_users, args.n_items,
                                     args.rank)
        st.meta.create_app("ProfileApp")
        server = EngineServer(engine_factory=factory, storage=st,
                              host="127.0.0.1", port=args.replica_port)
    server.run()


def _spawn_replicas(args, n: int):
    """N replica subprocesses on free ports; blocks until every
    ``/health`` answers 200."""
    import socket
    import subprocess
    import sys

    ports = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        s.close()
    procs = [subprocess.Popen(
        [sys.executable, __file__, "--_replica-port", str(p),
         "--platform", args.platform,
         "--n-users", str(args.n_users), "--n-items", str(args.n_items),
         "--rank", str(args.rank)],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        for p in ports]
    deadline = time.time() + 180  # jax import + model fabrication
    pending = set(ports)
    while pending and time.time() < deadline:
        for p in list(pending):
            try:
                conn = http.client.HTTPConnection("127.0.0.1", p, timeout=2)
                conn.request("GET", "/health")
                if conn.getresponse().status in (200, 503):
                    conn.close()
                    pending.discard(p)
            except OSError:
                pass
        if pending:
            time.sleep(0.3)
    if pending:
        for pr in procs:
            pr.kill()
        raise TimeoutError(f"replicas never came up on {sorted(pending)}")
    return ports, procs


def _router_load(port: int, n_users: int, n: int, threads: int = 3,
                 stop_when=None):
    """Closed-loop client threads against the router. Counts EVERY
    outcome (status 0 = transport error) — the chaos checks hinge on
    nothing hiding. With ``stop_when`` (a threading.Event), workers
    keep going past ``n`` until it is set, so the load provably spans
    the whole chaos window."""
    import threading

    lock = threading.Lock()
    results = []
    sent = [0]

    def worker(seed: int, count: int):
        import http.client as hc

        rng = np.random.default_rng(seed)
        conn = hc.HTTPConnection("127.0.0.1", port, timeout=30)
        out = []
        while True:
            with lock:
                if sent[0] >= count and (
                        stop_when is None or stop_when.is_set()):
                    break
                sent[0] += 1
            body = json.dumps(
                {"user": str(int(rng.integers(0, n_users))), "num": 10})
            t0 = time.perf_counter()
            try:
                conn.request("POST", "/queries.json", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                status = resp.status
            except Exception:
                conn.close()
                conn = hc.HTTPConnection("127.0.0.1", port, timeout=30)
                status = 0
            out.append((status, time.perf_counter() - t0))
        conn.close()
        with lock:
            results.extend(out)

    ts = [threading.Thread(target=worker, args=(100 + i, n), daemon=True)
          for i in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    statuses = {}
    for s, _ in results:
        statuses[str(s)] = statuses.get(str(s), 0) + 1
    lats = np.asarray([l for _, l in results])
    return statuses, lats, wall


def run_router_mode(args, st, factory) -> None:
    """Fleet-router chaos harness (ISSUE 8 acceptance): 3 replicas
    behind a FleetRouter; (a) steady-state baseline, (b) a rolling
    reload across the whole fleet under load, (c) kill -9 of one
    replica mid-load. Both chaos passes must serve 0 non-200s with
    p99 within 2x the steady-state baseline, and hedges must stay
    inside the retry budget."""
    import os
    import signal
    import socket
    import threading

    from predictionio_tpu.server.router import FleetRouter
    from profile_common import server_thread

    ports, procs = _spawn_replicas(args, 3)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    router_port = s.getsockname()[1]
    s.close()
    router = FleetRouter(
        [f"127.0.0.1:{p}" for p in ports],
        host="127.0.0.1", port=router_port,
        health_interval=0.25,
        retry_budget_ratio=0.2,
        hedge=True, hedge_min_ms=25.0,
        default_deadline_ms=15000.0,
        drain_timeout=10.0, ready_timeout=60.0)

    def counter(metric):
        return {"/".join(k): int(v) for k, v in metric._values.items()}

    try:
        with server_thread(router, router_port):
            # -- steady-state baseline --------------------------------
            _router_load(router_port, args.n_users, 100)  # warm
            base_status, base_lats, base_wall = _router_load(
                router_port, args.n_users, args.queries)
            base_p99 = float(np.percentile(base_lats, 99))

            # -- (b) rolling reload under load ------------------------
            reload_done = threading.Event()
            reload_out = {}

            def do_reload():
                try:
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", router_port, timeout=300)
                    conn.request("POST", "/router/reload?rolling=1", b"")
                    resp = conn.getresponse()
                    reload_out.update(json.loads(resp.read()))
                    reload_out["http_status"] = resp.status
                    conn.close()
                except Exception as e:  # noqa: BLE001 — recorded below
                    reload_out["error"] = f"{type(e).__name__}: {e}"
                finally:
                    reload_done.set()

            rt = threading.Thread(target=do_reload, daemon=True)
            rt.start()
            roll_status, roll_lats, _ = _router_load(
                router_port, args.n_users, args.queries,
                stop_when=reload_done)
            rt.join(timeout=300)
            roll_p99 = float(np.percentile(roll_lats, 99))

            # -- (c) kill -9 one replica mid-load ---------------------
            killer = threading.Timer(
                max(0.05, base_wall / 3),
                lambda: os.kill(procs[0].pid, signal.SIGKILL))
            killer.start()
            kill_status, kill_lats, _ = _router_load(
                router_port, args.n_users, args.queries)
            killer.cancel()
            procs[0].wait(timeout=10)
            kill_p99 = float(np.percentile(kill_lats, 99))

            hedges = counter(router._m_hedges)
            retries = counter(router._m_retries)
            denied = counter(router._m_retry_denied)
            budget_left = router._budget_tokens
    finally:
        for pr in procs:
            if pr.poll() is None:
                pr.terminate()
        for pr in procs:
            try:
                pr.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pr.kill()

    total_chaos = sum(roll_status.values()) + sum(kill_status.values())
    hedges_launched = hedges.get("launched", 0)
    retries_taken = sum(retries.values())
    # the budget admits ratio x traffic plus the initial burst
    budget_cap = router.retry_budget_ratio * (
        total_chaos + sum(base_status.values()) + 100) \
        + router.retry_budget_burst
    p99_bound = max(2 * base_p99, base_p99 + 0.05)
    checks = {
        "rolling_all_200": set(roll_status) == {"200"},
        "kill_all_200": set(kill_status) == {"200"},
        "rolling_reload_ok": bool(reload_out.get("ok")),
        "every_replica_reloaded": all(
            e.get("result") == "ok" and e.get("reloadGeneration", 0) >= 1
            for e in reload_out.get("replicas", [])) and len(
                reload_out.get("replicas", [])) == 3,
        "rolling_p99_bounded": roll_p99 <= p99_bound,
        "kill_p99_bounded": kill_p99 <= p99_bound,
        "hedges_within_budget":
            hedges_launched + retries_taken <= budget_cap,
    }
    ok = all(checks.values())
    print(json.dumps({
        "metric": "router_chaos",
        "replicas": 3,
        "queries_per_pass": args.queries,
        "baseline": {"statuses": base_status,
                     "p99_ms": round(base_p99 * 1e3, 3)},
        "rolling_reload": {"statuses": roll_status,
                           "p99_ms": round(roll_p99 * 1e3, 3),
                           "detail": reload_out},
        "kill_9": {"statuses": kill_status,
                   "p99_ms": round(kill_p99 * 1e3, 3)},
        "p99_bound_ms": round(p99_bound * 1e3, 3),
        "hedges": hedges,
        "retries": retries,
        "retries_denied": denied,
        "retry_budget_tokens_left": round(budget_left, 2),
        "checks": checks,
        "ok": ok,
    }))
    if not ok:
        raise SystemExit(1)


def run_train_loop_mode(args) -> None:
    """Continuous-training chaos harness (ISSUE 9 acceptance): a real
    engine-server replica and real ``pio train --continuous`` trainer
    subprocesses over one shared on-disk home. Proves, under live
    serving load:

    (a) kill -9 mid-delta-train → the restarted trainer resumes from
        the checkpoint and promotes exactly ONE new generation, with
        every query answered 200;
    (b) ``PIO_FAULTS=promote.regression`` → the guardrail refuses the
        candidate, the fleet never leaves the champion, zero errors;
    (c) a second trainer against a held lease never writes a model
        blob (fencing).
    """
    import os
    import shutil
    import signal
    import socket
    import subprocess
    import sys
    import tempfile
    import threading

    from predictionio_tpu.data.event import Event
    from predictionio_tpu.storage.registry import Storage, StorageConfig

    base = tempfile.mkdtemp(prefix="pio-train-loop-")
    home = os.path.join(base, "home")
    engine_dir = os.path.join(base, "engine")
    os.makedirs(home)
    os.makedirs(engine_dir)
    n_users, n_items = 24, 16
    variant = {
        "id": "default",
        "engineFactory": ("predictionio_tpu.templates.recommendation."
                          "engine:engine_factory"),
        "datasource": {"params": {"appName": "TrainLoopApp"}},
        "algorithms": [{"name": "als",
                        "params": {"rank": 4, "numIterations": 80,
                                   "lambda": 0.05, "checkpointEvery": 1}}],
    }
    with open(os.path.join(engine_dir, "engine.json"), "w") as f:
        json.dump(variant, f)

    st = Storage(StorageConfig(home=home))  # SQLITE meta/events, LOCALFS
    app = st.meta.create_app("TrainLoopApp")
    st.events.init_channel(app.id)

    def add_ratings(seed: int, n: int = 40):
        rng = np.random.default_rng(seed)
        evs = []
        for _ in range(n):
            u, i = int(rng.integers(n_users)), int(rng.integers(n_items))
            r = 5.0 if (u % 2) == (i % 2) else 1.0
            evs.append(Event(event="rate", entity_type="user",
                             entity_id=str(u), target_entity_type="item",
                             target_entity_id=str(i),
                             properties={"rating": r}))
        st.events.insert_batch(evs, app.id)

    add_ratings(0, 200)

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    child_env = {**os.environ, "JAX_PLATFORMS": "cpu", "PIO_HOME": home}
    replica_log = open(os.path.join(base, "replica.log"), "wb")
    replica = subprocess.Popen(
        [sys.executable, __file__, "--_replica-port", str(port),
         "--_replica-home", home, "--platform", args.platform],
        env=child_env, stdout=replica_log, stderr=replica_log)

    def health():
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=2)
            conn.request("GET", "/health")
            resp = conn.getresponse()
            body = json.loads(resp.read() or b"{}")
            conn.close()
            return resp.status, body
        except OSError:
            return 0, {}

    def wait_for(pred, what: str, deadline_sec: float):
        end = time.time() + deadline_sec
        while time.time() < end:
            if pred():
                return
            time.sleep(0.1)
        raise TimeoutError(f"timed out waiting for {what}")

    def spawn_trainer(name: str, extra_env=None, max_cycles=None):
        cmd = [sys.executable, "-m", "predictionio_tpu.tools.cli", "train",
               "--engine-dir", engine_dir, "--continuous", "--no-mesh",
               "--min-delta-events", "1", "--poll-interval", "0.2",
               "--lease-ttl", "5", "--guardrail-max-regress", "10.0",
               "--reload-url", f"http://127.0.0.1:{port}"]
        if max_cycles is not None:
            cmd += ["--max-cycles", str(max_cycles)]
        log = open(os.path.join(base, f"{name}.log"), "wb")
        return subprocess.Popen(cmd, env={**child_env, **(extra_env or {})},
                                stdout=log, stderr=log)

    reg_path = os.path.join(home, "model_registry", "registry.json")

    def registry():
        try:
            with open(reg_path, "r") as f:
                return json.load(f)
        except (OSError, ValueError):
            return {"champion": None, "generations": [],
                    "fence_token": 0}

    def champion():
        return registry().get("champion")

    def stop_clean(proc, grace: float = 60.0) -> int:
        proc.send_signal(signal.SIGTERM)
        try:
            return proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            proc.kill()
            return proc.wait()

    ckpt_root = os.path.join(home, "train_ckpt")

    def ckpt_steps() -> int:
        count = 0
        for dirpath, dirnames, _ in os.walk(ckpt_root):
            count += sum(1 for d in dirnames if d.isdigit())
        return count

    load_stop = None
    load_box = {}

    def start_load():
        nonlocal load_stop
        load_stop = threading.Event()
        box = {}

        def run():
            box["result"] = _router_load(port, n_users, 50,
                                         stop_when=load_stop)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        return t, box

    checks = {}
    detail = {}
    try:
        wait_for(lambda: health()[0] in (200, 503), "replica up", 180)

        # -- bootstrap: first trainer promotes gen 1 and reloads ------
        t0 = spawn_trainer("trainer-bootstrap")
        wait_for(lambda: champion() == 1, "bootstrap promotion", 300)
        rc0 = stop_clean(t0)
        wait_for(lambda: health()[1].get("modelGeneration") == 1,
                 "replica serving gen 1", 60)
        with open(os.path.join(home, "trainer.lease")) as f:
            lease_doc = json.load(f)
        checks["clean_shutdown_released_lease"] = (
            rc0 == 0 and lease_doc.get("expires") == 0)

        # -- (a) kill -9 mid-delta-train, restart, resume -------------
        add_ratings(1)
        lt, lbox = start_load()
        t1 = spawn_trainer("trainer-killed")
        wait_for(lambda: ckpt_steps() >= 2,
                 "mid-train checkpoints", 240)
        steps_at_kill = ckpt_steps()
        t1.send_signal(signal.SIGKILL)
        t1.wait()
        reg_at_kill = registry()
        t2 = spawn_trainer("trainer-resumed")
        wait_for(lambda: champion() == 2, "post-crash promotion", 300)
        rc2 = stop_clean(t2)
        wait_for(lambda: health()[1].get("modelGeneration") == 2,
                 "replica serving gen 2", 60)
        load_stop.set()
        lt.join(timeout=120)
        status_a, lats_a, _ = lbox["result"]
        reg_a = registry()
        checks["checkpointed_before_kill"] = steps_at_kill >= 2
        checks["crashed_run_published_nothing"] = (
            reg_at_kill["champion"] == 1
            and len(reg_at_kill["generations"]) == 1)
        checks["resumed_promoted_exactly_one"] = (
            rc2 == 0 and reg_a["champion"] == 2
            and len(reg_a["generations"]) == 2)
        checks["restart_bumped_fence_token"] = reg_a["fence_token"] >= 2
        checks["crash_pass_all_200"] = set(status_a) == {"200"}
        detail["kill_9"] = {
            "ckpt_steps_at_kill": steps_at_kill,
            "statuses": status_a,
            "p99_ms": round(float(np.percentile(lats_a, 99)) * 1e3, 3),
        }

        # -- (b) injected regression → guardrail refusal --------------
        add_ratings(2)
        lt, lbox = start_load()
        t3 = spawn_trainer(
            "trainer-regressed",
            extra_env={"PIO_FAULTS":
                       "promote.regression:error=injected,count=1"})
        wait_for(lambda: any(g["status"] == "refused"
                             for g in registry()["generations"]),
                 "guardrail refusal", 300)
        rc3 = stop_clean(t3)
        load_stop.set()
        lt.join(timeout=120)
        status_b, lats_b, _ = lbox["result"]
        reg_b = registry()
        _, hb = health()
        checks["regression_refused"] = (
            rc3 == 0
            and any(g["status"] == "refused"
                    for g in reg_b["generations"]))
        checks["fleet_stayed_on_champion"] = (
            reg_b["champion"] == 2
            and hb.get("modelGeneration") == 2)
        checks["regression_pass_all_200"] = set(status_b) == {"200"}
        detail["regression"] = {
            "statuses": status_b,
            "p99_ms": round(float(np.percentile(lats_b, 99)) * 1e3, 3),
            "generations": {str(g["gen"]): g["status"]
                            for g in reg_b["generations"]},
        }

        # -- (c) second trainer vs a held lease: fenced out -----------
        from predictionio_tpu.server.trainer import TrainerLease

        lease = TrainerLease(os.path.join(home, "trainer.lease"),
                             "harness", ttl=300.0)
        assert lease.acquire(), "harness could not take the lease"
        with open(reg_path, "rb") as f:
            reg_bytes_before = f.read()
        dirs_before = sorted(os.listdir(os.path.dirname(reg_path)))
        t4 = spawn_trainer("trainer-fenced", max_cycles=5)
        rc4 = t4.wait(timeout=180)
        with open(reg_path, "rb") as f:
            reg_bytes_after = f.read()
        dirs_after = sorted(os.listdir(os.path.dirname(reg_path)))
        lease.release()
        checks["fenced_trainer_wrote_nothing"] = (
            rc4 == 0 and reg_bytes_after == reg_bytes_before
            and dirs_after == dirs_before)
        detail["fenced"] = {"registry_dirs": dirs_after}
    finally:
        if load_stop is not None:
            load_stop.set()
        replica.terminate()
        try:
            replica.wait(timeout=10)
        except subprocess.TimeoutExpired:
            replica.kill()
        replica_log.close()

    ok = all(checks.values())
    print(json.dumps({
        "metric": "train_loop_chaos",
        "queries_min_per_pass": 50,
        **detail,
        "checks": checks,
        "ok": ok,
    }))
    if ok:
        shutil.rmtree(base, ignore_errors=True)
    else:
        print(f"[train-loop] logs kept in {base}", file=sys.stderr)
        raise SystemExit(1)


def run_fault_mode(args, st, factory) -> None:
    """Healthy baseline vs the same load under an armed fault spec."""
    from predictionio_tpu.server.engine_server import EngineServer
    from predictionio_tpu.utils.faults import FAULTS
    from profile_common import server_thread

    # the feedback loop's DirectEventSink resolves the app named in the
    # instance's data-source params — it must exist for feedback to land
    st.meta.create_app("ProfileApp")
    server = EngineServer(
        engine_factory=factory, storage=st,
        host="127.0.0.1", port=args.port,
        feedback=True,
        query_timeout_ms=args.fault_timeout_ms,
        max_inflight=args.max_inflight)
    rng = np.random.default_rng(2)

    def run_pass(n: int):
        conn = http.client.HTTPConnection("127.0.0.1", args.port, timeout=10)
        lats, statuses = [], {}
        for _ in range(n):
            body = json.dumps(
                {"user": str(int(rng.integers(0, args.n_users))), "num": 10})
            t0 = time.perf_counter()
            try:
                conn.request("POST", "/queries.json", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                status = resp.status
            except Exception:
                # timed-out/reset connection: reconnect, count as 0
                conn.close()
                conn = http.client.HTTPConnection("127.0.0.1", args.port,
                                                  timeout=10)
                status = 0
            lats.append(time.perf_counter() - t0)
            statuses[str(status)] = statuses.get(str(status), 0) + 1
        conn.close()
        arr = np.asarray(lats)
        return (float(np.percentile(arr, 50) * 1e3),
                float(np.percentile(arr, 99) * 1e3), statuses)

    with server_thread(server, args.port):
        run_pass(50)  # warm: compile + code paths hot before measuring
        h50, h99, h_status = run_pass(args.queries)
        FAULTS.arm_spec(args.fault)
        try:
            f50, f99, f_status = run_pass(args.queries)
        finally:
            FAULTS.disarm()
        time.sleep(0.5)  # let the feedback pool drain before reading counters
        feedback = {k[0]: int(v)
                    for k, v in server._m_feedback._values.items()}
        breakers = {n: b.state for n, b in server._breakers.items()}

    print(json.dumps({
        "metric": "serving_fault_injection",
        "fault": args.fault,
        "queries_per_pass": args.queries,
        "healthy_ms": {"p50": round(h50, 4), "p99": round(h99, 4)},
        "faulted_ms": {"p50": round(f50, 4), "p99": round(f99, 4)},
        "p50_ratio": round(f50 / h50, 3) if h50 > 0 else None,
        "statuses": {"healthy": h_status, "faulted": f_status},
        "feedback": feedback,
        "breakers": breakers,
        "shed": int(server._m_shed._values.get((), 0)),
        "deadline_exceeded": int(server._m_deadline._values.get((), 0)),
    }))


def run_tracing_mode(args, st, factory) -> None:
    """A/B overhead of request tracing: the same closed-loop HTTP load
    with the tracer disabled, then in the chosen mode (``sampled`` = 1%
    probabilistic file export, ``full`` = every trace exported). The
    ring buffer and root-span bookkeeping run in both traced modes —
    sampling only gates the JSONL write. Target: <2% p50 overhead at
    1% sampling (docs/perf.md)."""
    import os
    import tempfile

    from predictionio_tpu.server.engine_server import EngineServer
    from predictionio_tpu.utils import tracing
    from profile_common import server_thread

    server = EngineServer(engine_factory=factory, storage=st,
                          host="127.0.0.1", port=args.port)
    rng = np.random.default_rng(3)

    def run_pass(n: int):
        conn = http.client.HTTPConnection("127.0.0.1", args.port, timeout=10)
        lats = np.empty(n)
        for i in range(n):
            body = json.dumps(
                {"user": str(int(rng.integers(0, args.n_users))), "num": 10})
            t0 = time.perf_counter()
            conn.request("POST", "/queries.json", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
            assert resp.status == 200, data[:200]
            lats[i] = time.perf_counter() - t0
        conn.close()
        return lats * 1e3  # per-query latencies in ms

    sample = {"off": 0.0, "sampled": 0.01, "full": 1.0}[args.tracing]
    trace_path = os.path.join(tempfile.mkdtemp(prefix="pio-trace-"),
                              "spans.jsonl")

    def arm_traced():
        if args.tracing != "off":
            # file export included: the overhead quoted in docs/perf.md
            # is the whole traced path, not just span bookkeeping
            tracing.TRACER.configure(enabled=True, sample_rate=sample,
                                     jsonl_path=trace_path)

    # sequential A-then-B passes drift (thermal/scheduler): the second
    # pass measures ~10-20% slower with NO code change. Interleave
    # chunks in ABBA order so both arms see each position equally and
    # drift cancels out of the delta.
    chunks = 8
    per_chunk = max(50, args.queries // chunks)
    base_lat, traced_lat = [], []
    ring_spans = 0
    with server_thread(server, args.port):
        run_pass(100)  # warm: compile + code paths hot
        for c in range(chunks):
            order = ("base", "traced") if c % 2 == 0 else ("traced", "base")
            for arm in order:
                tracing.TRACER.reset()
                if arm == "base":
                    base_lat.append(run_pass(per_chunk))
                else:
                    arm_traced()
                    try:
                        traced_lat.append(run_pass(per_chunk))
                    finally:
                        ring_spans = max(ring_spans,
                                         len(tracing.TRACER.ring))
        tracing.TRACER.reset()
    exported_bytes = (os.path.getsize(trace_path)
                      if os.path.exists(trace_path) else 0)
    base = np.concatenate(base_lat)
    traced = np.concatenate(traced_lat)
    base50, base99 = (float(np.percentile(base, 50)),
                      float(np.percentile(base, 99)))
    t50, t99 = (float(np.percentile(traced, 50)),
                float(np.percentile(traced, 99)))

    print(json.dumps({
        "metric": "tracing_overhead",
        "mode": args.tracing,
        "sample_rate": sample,
        "queries_per_pass": args.queries,
        "baseline_ms": {"p50": round(base50, 4), "p99": round(base99, 4)},
        "traced_ms": {"p50": round(t50, 4), "p99": round(t99, 4)},
        "p50_overhead_pct": round((t50 - base50) / base50 * 100, 2)
        if base50 > 0 else None,
        "p99_overhead_pct": round((t99 - base99) / base99 * 100, 2)
        if base99 > 0 else None,
        "ring_spans": ring_spans,
        "exported_bytes": exported_bytes,
    }))


def run_aot_mode(args, st, factory) -> None:
    """AOT bucket-ladder profile (ROADMAP item 5 / docs/perf.md):
    cold-warm the ladder (real lower+compile wall time), re-warm a
    second deploy of the same geometry (must be pure executable-cache
    hits), then drive every bucket at its REAL batch size and report
    per-bucket device p50 — asserting zero XLA compiles happened on
    the serving path."""
    import os

    os.environ.setdefault("PIO_ALS_SERVE", "device")
    from predictionio_tpu.core.workflow import prepare_deploy
    from predictionio_tpu.server.aot import (
        EXECUTABLES,
        AOTWarmup,
        BucketLadder,
    )

    ladder = BucketLadder.parse(args.aot_buckets, args.batch_max)
    deployed = prepare_deploy(engine_factory=factory, storage=st)

    warmup = AOTWarmup(ladder, ks=(10,))
    t0 = time.perf_counter()
    cold = warmup.warm_sync(deployed)
    cold_wall = time.perf_counter() - t0

    # same geometry, fresh model objects → every (bucket, k) must hit
    # the process-wide executable cache: this is the /reload story
    deployed2 = prepare_deploy(engine_factory=factory, storage=st)
    t0 = time.perf_counter()
    warm = warmup.warm_sync(deployed2)
    warm_wall = time.perf_counter() - t0

    rng = np.random.default_rng(4)
    counts_before = EXECUTABLES.counts()
    per_bucket = {}
    for B in ladder:
        users = rng.integers(0, args.n_users, size=B)
        queries = [{"user": str(int(u)), "num": 10} for u in users]
        lat = np.empty(args.aot_iters)
        for i in range(-5, args.aot_iters):  # 5 warm laps per bucket
            t0 = time.perf_counter()
            out = deployed2.batch_query(queries)
            if i >= 0:
                lat[i] = time.perf_counter() - t0
        assert len(out) == B and all(r["itemScores"] for r in out)
        per_bucket[str(B)] = {
            "p50_ms": round(float(np.percentile(lat, 50) * 1e3), 4),
            "p99_ms": round(float(np.percentile(lat, 99) * 1e3), 4),
        }
    counts_after = EXECUTABLES.counts()
    serving_compiles = (counts_after.get("compile", 0)
                        - counts_before.get("compile", 0))

    print(json.dumps({
        "metric": "aot_serving_buckets",
        "geometry": {"n_users": args.n_users, "n_items": args.n_items,
                     "rank": args.rank},
        "buckets": list(ladder.buckets),
        "cold_warmup": {"wall_sec": round(cold_wall, 3),
                        "compiled": cold["compiled"],
                        "cached": cold["cached"]},
        "warm_warmup": {"wall_sec": round(warm_wall, 3),
                        "compiled": warm["compiled"],
                        "cached": warm["cached"]},
        "predict_p50_device_ms": {b: v["p50_ms"]
                                  for b, v in per_bucket.items()},
        "per_bucket_ms": per_bucket,
        "serving_path_compiles": serving_compiles,
    }))


def run_variants_mode(args) -> None:
    """Multi-model multiplexing chaos mode (ISSUE 11 acceptance):

    1. split fidelity — 20k all-200 queries against a 90/10
       champion/challenger split must land within ±1% of 90/10, and
       assignment must be sticky (same entity → same arm, always);
    2. mid-swap kill — arm ``variant.reload.partial`` and
       ``GET /reload?variant=challenger``: the swap must fail closed
       (500), the champion must keep serving, and the effective split
       must fall back to 100/0;
    3. compile hygiene — with TWO variants resident and both ladders
       warmed, the measured query run must trigger ZERO XLA compiles
       (same geometry ⇒ pure executable-cache sharing).
    """
    import os
    import shutil
    import tempfile

    if args.n_users < args.queries:
        raise SystemExit(
            "--variants needs --n-users >= --queries: the ±1% split "
            "proof is over DISTINCT entities (sticky assignment makes "
            "repeat queries correlated, not independent)")
    os.environ.setdefault("PIO_ALS_SERVE", "device")
    from predictionio_tpu.server.aot import EXECUTABLES
    from predictionio_tpu.server.engine_server import EngineServer
    from predictionio_tpu.storage.models import model_registry
    from predictionio_tpu.storage.registry import (Storage, StorageConfig,
                                                   set_storage)
    from predictionio_tpu.utils.faults import FAULTS
    from profile_common import server_thread

    home = tempfile.mkdtemp(prefix="pio-variants-")
    try:
        st = Storage(StorageConfig(home=home))
        set_storage(st)
        factory = fabricate_instance(
            st, args.n_users, args.n_items, args.rank,
            instance_id="variants-champ", seed=0)
        fabricate_instance(st, args.n_users, args.n_items, args.rank,
                           instance_id="variants-chal", seed=1)
        reg = model_registry(st)
        champ_gen = reg.register("variants-champ",
                                 st.models.get("variants-champ"))
        reg.promote(champ_gen)
        chal_gen = reg.register("variants-chal",
                                st.models.get("variants-chal"))

        server = EngineServer(
            engine_factory=factory, storage=st,
            host="127.0.0.1", port=args.port,
            aot_buckets="1", aot_topk=10,
            variants="champion:9,challenger:1")
        # deterministic harness: both ladders warmed before any
        # measurement, so phase 3 counts serving-path compiles only
        server._mux.warm_sync_all()

        def ask(conn, user: str):
            conn.request("POST", "/queries.json",
                         json.dumps({"user": user, "num": 10}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            resp.read()
            return resp.status, resp.getheader("X-PIO-Variant")

        with server_thread(server, args.port):
            conn = http.client.HTTPConnection("127.0.0.1", args.port,
                                              timeout=30)
            rng = np.random.default_rng(7)
            warm_users = rng.integers(0, args.n_users, 50)
            for u in warm_users:
                ask(conn, str(int(u)))

            # -- 1. split fidelity + stickiness -------------------------
            n = args.queries
            compiles_before = EXECUTABLES.counts().get("compile", 0)
            counts: dict = {}
            first_arm: dict = {}
            statuses: dict = {}
            t0 = time.perf_counter()
            for i in range(n):
                user = str(i)
                status, arm = ask(conn, user)
                statuses[str(status)] = statuses.get(str(status), 0) + 1
                counts[arm] = counts.get(arm, 0) + 1
                if user not in first_arm:
                    first_arm[user] = arm
            wall = time.perf_counter() - t0
            compiles = (EXECUTABLES.counts().get("compile", 0)
                        - compiles_before)
            sticky_violations = sum(
                1 for i in rng.integers(0, n, 200)
                if ask(conn, str(int(i)))[1] != first_arm[str(int(i))])
            chal_share = counts.get("challenger", 0) / n
            assert statuses.get("200") == n, \
                f"non-200s in split pass: {statuses}"
            assert abs(chal_share - 0.10) <= 0.01, \
                f"challenger share {chal_share:.4f} outside 10%±1%"
            assert sticky_violations == 0, \
                f"{sticky_violations} sticky-assignment violations"
            assert compiles == 0, \
                f"{compiles} XLA compiles on the serving path"

            # -- 2. mid-swap kill --------------------------------------
            FAULTS.arm("variant.reload.partial", error="mid-swap kill")
            try:
                conn.request("GET", "/reload?variant=challenger")
                r = conn.getresponse()
                reload_body = json.loads(r.read())
                reload_status = r.status
            finally:
                FAULTS.disarm()
            conn.request("GET", "/health")
            h = conn.getresponse()
            health = json.loads(h.read())
            chal_state = health["variants"]["variants"]["challenger"]["state"]
            after = {}
            for i in range(500):
                status, arm = ask(conn, str(i))
                assert status == 200, f"post-kill query {i} -> {status}"
                after[arm] = after.get(arm, 0) + 1
            conn.close()
            assert reload_status == 500, \
                f"partial swap answered {reload_status}, want 500"
            assert reload_body.get("swap") == "failed", reload_body
            assert chal_state == "failed", \
                f"challenger state {chal_state!r} after mid-swap kill"
            assert after == {"champion": 500}, \
                f"split did not fall back to 100/0: {after}"

        print(json.dumps({
            "metric": "variant_multiplexing",
            "geometry": {"n_users": args.n_users, "n_items": args.n_items,
                         "rank": args.rank},
            "generations": {"champion": champ_gen,
                            "challenger": chal_gen},
            "queries": n,
            "qps": round(n / wall, 1),
            "split": {"weights": "champion:9,challenger:1",
                      "observed": counts,
                      "challenger_share": round(chal_share, 4),
                      "sticky_violations": sticky_violations},
            "statuses": statuses,
            "serving_path_compiles": compiles,
            "mid_swap_kill": {"reload_status": reload_status,
                              "challenger_state": chal_state,
                              "post_kill_split": after},
            "ok": True,
        }))
    finally:
        shutil.rmtree(home, ignore_errors=True)


def run_tenants_mode(args) -> None:
    """Multi-tenant QoS chaos mode (ISSUE 12 acceptance):

    1. ingest isolation — three apps on one Event Server, the
       "burst" app quota'd and driven at 10x the background tenants'
       rate: only the burster sees 429s, its Retry-After is honest
       (sleep it and the next event lands), and the quiet tenants see
       zero 429/503;
    2. query isolation — three tenants against one engine server
       under a small ``max_inflight``: the flooding tenant is shed
       (503 + Retry-After) at its fair share while the quiet tenants
       serve all-200 with p99 <= 1.5x their solo baseline;
    3. compile hygiene — the whole contended run triggers ZERO XLA
       compiles on the serving path (AOT bucket 1 covers it).
    """
    import multiprocessing as mp
    import os
    import queue as _queue
    import shutil
    import tempfile

    os.environ.setdefault("PIO_ALS_SERVE", "device")
    from predictionio_tpu.server.aot import EXECUTABLES
    from predictionio_tpu.server.engine_server import EngineServer
    from predictionio_tpu.server.event_server import EventServer
    from predictionio_tpu.server.tenancy import TenantQuotas
    from predictionio_tpu.storage.registry import Storage, StorageConfig
    from profile_common import make_memory_storage, server_thread

    quota_rate, quota_burst = 200.0, 40.0
    home = tempfile.mkdtemp(prefix="pio-tenants-")
    quotas_path = os.path.join(home, "quotas.json")
    try:
        # -- 1. ingest QoS: quota'd burster vs quiet tenants ------------
        st = Storage(StorageConfig(home=home))
        apps = {}
        keys = {}
        for name in ("burst", "quiet-b", "quiet-c"):
            app = st.meta.create_app(name, "")
            st.events.init_channel(app.id)
            apps[name] = app
            keys[name] = st.meta.create_access_key(app.id).key
        TenantQuotas.for_home(home).set_quota(
            str(apps["burst"].id), rate=quota_rate, burst=quota_burst)
        es = EventServer(storage=st, host="127.0.0.1", port=args.port,
                         ingest_batching=True)

        def post_event(conn, key, i):
            conn.request(
                "POST", f"/events.json?accessKey={key}",
                json.dumps({"event": "rate", "entityType": "user",
                            "entityId": str(i),
                            "targetEntityType": "item",
                            "targetEntityId": str(i % 7)}),
                {"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, resp.getheader("Retry-After"), resp.read()

        ingest: dict = {n: {"statuses": {}, "bad_retry_after": 0}
                        for n in apps}
        with server_thread(es, args.port):
            conns = {n: http.client.HTTPConnection(
                "127.0.0.1", args.port, timeout=30) for n in apps}
            # 10x traffic: each round the burster posts 10 events for
            # the background tenants' 1 — enough rounds to blow well
            # past its burst allowance at any loop speed
            rounds = max(40, int(quota_burst) // 2)
            i = 0
            for _ in range(rounds):
                for name, batch in (("burst", 10),
                                    ("quiet-b", 1), ("quiet-c", 1)):
                    for _ in range(batch):
                        status, ra, _body = post_event(
                            conns[name], keys[name], i)
                        i += 1
                        rec = ingest[name]
                        rec["statuses"][str(status)] = \
                            rec["statuses"].get(str(status), 0) + 1
                        if status == 429 and (ra is None
                                              or float(ra) < 1.0):
                            rec["bad_retry_after"] += 1
            # Retry-After honesty: sleep exactly what the 429 said and
            # the SAME event must then be accepted
            status, _ra, body = post_event(conns["burst"], keys["burst"], i)
            retried = None
            if status == 429:
                hint = json.loads(body)["retryAfterSec"]
                assert hint > 0, f"429 with retryAfterSec={hint}"
                time.sleep(hint)
                retried, _, _ = post_event(conns["burst"], keys["burst"], i)
            for c in conns.values():
                c.close()
        st.events.close()
        burst_429 = ingest["burst"]["statuses"].get("429", 0)
        assert burst_429 > 0, \
            f"burster was never throttled: {ingest['burst']['statuses']}"
        assert ingest["burst"]["bad_retry_after"] == 0, \
            "429s without a sane Retry-After header"
        assert retried in (None, 201), \
            f"event after sleeping the advertised Retry-After -> {retried}"
        for name in ("quiet-b", "quiet-c"):
            assert set(ingest[name]["statuses"]) == {"201"}, \
                f"quiet tenant {name} saw {ingest[name]['statuses']}"

        # -- 2+3. query QoS under a shared max_inflight -----------------
        st2 = make_memory_storage()
        factory = fabricate_instance(st2, args.n_users, args.n_items,
                                     args.rank)
        # limit 3 over 3 active tenants → every tenant's fair share is
        # exactly 1 slot: the burster can never occupy more concurrency
        # than a quiet tenant, whatever its offered rate
        max_inflight = 3
        # batching matters here: admitted queries from every tenant
        # ride ONE device dispatch, so a quiet query's latency is one
        # batch, not a serial queue behind the burster's admitted work
        server = EngineServer(engine_factory=factory, storage=st2,
                              host="127.0.0.1", port=args.port + 1,
                              batching=True, batch_max=max_inflight,
                              aot_buckets="1,2,4", aot_topk=10,
                              max_inflight=max_inflight,
                              tenant_quotas=quotas_path)
        nq = max(400, min(args.queries, 1000))
        # quiet tenants offer ~50 q/s each; the burster offers 10x a
        # background tenant's rate (4 clients at ~125 q/s each). That
        # is a tenant-level flood the admission gate must absorb — NOT
        # an unbounded connection-level spin, which would saturate the
        # listener itself and is a different (kernel-level) defense.
        pace = 0.02
        flood_pace = 0.008
        ctx = mp.get_context("fork")

        def spawn(specs):
            q = ctx.Queue()
            procs = [ctx.Process(
                target=_tenant_client_proc,
                args=(args.port + 1, app, args.n_users, n, seed, pc, q),
                daemon=True) for app, n, seed, pc in specs]
            return q, procs

        def collect(q, procs, expect):
            outs = []
            for _ in range(expect):
                try:
                    outs.append(q.get(timeout=300))
                except _queue.Empty:
                    outs.append("client timed out (killed?)")
            for p in procs:
                p.join(timeout=30)
                if p.is_alive():
                    p.terminate()
                    p.join(timeout=10)
                    outs.append("client stuck (terminated)")
            errs = [o for o in outs if isinstance(o, str)]
            if errs:
                raise RuntimeError(
                    f"{len(errs)} client(s) failed; first: {errs[0]}")
            return outs

        def warm(conn, app, n=25):
            for k in range(n):
                conn.request("POST", "/queries.json",
                             json.dumps({"user": str(k), "num": 10}),
                             {"Content-Type": "application/json",
                              "X-PIO-App": app})
                conn.getresponse().read()

        with server_thread(server, args.port + 1):
            conn = http.client.HTTPConnection("127.0.0.1", args.port + 1,
                                              timeout=30)
            # the AOT ladder compiles asynchronously; wait for ready
            # so the compile-hygiene delta counts serving-path compiles
            # only, not tail-end warmup work
            deadline = time.time() + 120
            while time.time() < deadline:
                conn.request("GET", "/health")
                h = conn.getresponse()
                ready = json.loads(h.read()).get("status") != "not-ready"
                if ready and h.status == 200:
                    break
                time.sleep(0.2)
            for name in apps:
                warm(conn, name)
            compiles_before = EXECUTABLES.counts().get("compile", 0)

            # solo baseline: the quiet tenants as they run WITHOUT the
            # noisy neighbor — the isolation claim is that the
            # burster's arrival does not degrade them, so the baseline
            # keeps everything else (pacing, both tenants, the gate)
            # identical
            q, procs = spawn([("quiet-b", nq, 11, pace),
                              ("quiet-c", nq, 21, pace)])
            for p in procs:
                p.start()
            solo = {o["app"]: o for o in collect(q, procs, 2)}
            solo_p99 = {a: float(np.percentile(np.asarray(o["lats"]), 99))
                        for a, o in solo.items()}

            # contention: refresh the quiet tenants in the fair-share
            # active set, establish the flood, then measure the quiet
            # tenants through it
            warm(conn, "quiet-b", 3)
            warm(conn, "quiet-c", 3)
            fq, fprocs = spawn([("burst", nq * 4, 31 + k, flood_pace)
                                for k in range(4)])
            for p in fprocs:
                p.start()
            time.sleep(0.5)
            qq, qprocs = spawn([("quiet-b", nq, 12, pace),
                                ("quiet-c", nq, 13, pace)])
            for p in qprocs:
                p.start()
            quiet = collect(qq, qprocs, 2)
            flood = collect(fq, fprocs, 4)
            conn.close()
            compiles = (EXECUTABLES.counts().get("compile", 0)
                        - compiles_before)
            shed_by_app = dict(server._m_shed._values)

        quiet_by_app = {o["app"]: o for o in quiet}
        flood_statuses: dict = {}
        flood_missing_ra = 0
        for o in flood:
            flood_missing_ra += o["retry_after_missing"]
            for s, c in o["statuses"].items():
                flood_statuses[s] = flood_statuses.get(s, 0) + c
        quiet_p99 = {a: float(np.percentile(np.asarray(o["lats"]), 99))
                     for a, o in quiet_by_app.items()}
        assert flood_statuses.get("503", 0) > 0, \
            f"flooding tenant was never shed: {flood_statuses}"
        assert flood_missing_ra == 0, \
            f"{flood_missing_ra} sheds without Retry-After"
        for name in ("quiet-b", "quiet-c"):
            sts = quiet_by_app[name]["statuses"]
            assert set(sts) == {"200"}, \
                f"quiet tenant {name} saw non-200s: {sts}"
            assert quiet_p99[name] <= 1.5 * solo_p99[name], \
                (f"quiet tenant {name} p99 {quiet_p99[name] * 1e3:.2f}ms "
                 f"> 1.5x solo baseline {solo_p99[name] * 1e3:.2f}ms")
        assert compiles == 0, \
            f"{compiles} XLA compiles on the serving path"

        print(json.dumps({
            "metric": "tenant_qos_isolation",
            "geometry": {"n_users": args.n_users, "n_items": args.n_items,
                         "rank": args.rank},
            "ingest": {
                "quota": {"rate": quota_rate, "burst": quota_burst},
                "per_tenant": ingest,
                "retry_after_honored": retried == 201,
            },
            "query": {
                "max_inflight": max_inflight,
                "solo_p99_ms": {a: round(v * 1e3, 3)
                                for a, v in solo_p99.items()},
                "quiet_p99_ms": {a: round(v * 1e3, 3)
                                 for a, v in quiet_p99.items()},
                "quiet_statuses": {a: o["statuses"]
                                   for a, o in quiet_by_app.items()},
                "flood_statuses": flood_statuses,
                "shed_by_app": {"/".join(k): v
                                for k, v in shed_by_app.items()},
            },
            "serving_path_compiles": compiles,
            "ok": True,
        }))
    finally:
        shutil.rmtree(home, ignore_errors=True)


def run_slo_mode(args, st, factory) -> None:
    """SLO burn-rate chaos harness (ISSUE 14 acceptance): one engine
    replica behind a FleetRouter running the synthetic prober, the
    scraper, and an SLO config with second-scale burn windows so the
    drill fits in wall-clock seconds. Phases:

    1. healthy — the prober alone keeps every burn rate at 0 and
       ``/health`` at ok;
    2. ``router.replica.down`` armed — every probe fails, the
       availability SLO must trip its FAST burn within two scrape
       intervals, ``/health`` must report degraded (with
       ``sloFastBurn`` naming the SLO, the replica itself still
       polling healthy) and ``pio_slo_alerting`` must read 2;
    3. disarmed — the page must clear, ``/health`` return to ok, and
       real user traffic serve all-200 again.

    The whole drill (warmup excluded) triggers ZERO XLA compiles on
    the serving path.
    """
    import os
    import socket
    import tempfile

    from predictionio_tpu.server.aot import EXECUTABLES
    from predictionio_tpu.server.engine_server import EngineServer
    from predictionio_tpu.server.router import FleetRouter
    from predictionio_tpu.utils.faults import FAULTS
    from profile_common import server_thread

    scrape, probe = 0.5, 0.1
    # production windows are minutes-to-hours (conf/slo.json); the
    # drill shrinks them so a burn is visible in seconds. The 2 s long
    # window is what makes "trip within two scrapes" non-trivial: the
    # first post-fault scrape must already show a bad ratio above
    # 14.4x the 1% budget across BOTH fast windows.
    slo_cfg = {
        "windows": {"fast": ["1s", "2s"], "slow": ["10s"]},
        "thresholds": {"fast": 14.4, "slow": 6.0},
        "slos": [
            {"name": "probe-availability", "type": "availability",
             "objective": 0.99,
             "series": "pio_probe_requests_total",
             "labels": {"path": "/queries.json"},
             "bad": {"outcome": "error"}},
            {"name": "probe-latency", "type": "latency",
             "objective": 0.95,
             "histogram": "pio_probe_seconds",
             "labels": {"path": "/queries.json"},
             "threshold_ms": 1000},
        ],
    }

    server = EngineServer(engine_factory=factory, storage=st,
                          host="127.0.0.1", port=args.port)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    router_port = s.getsockname()[1]
    s.close()

    def slo_status():
        conn = http.client.HTTPConnection("127.0.0.1", router_port,
                                          timeout=10)
        conn.request("GET", "/slo/status")
        out = json.loads(conn.getresponse().read())
        conn.close()
        return out

    def health():
        conn = http.client.HTTPConnection("127.0.0.1", router_port,
                                          timeout=10)
        conn.request("GET", "/health")
        resp = conn.getresponse()
        out = (resp.status, json.loads(resp.read()))
        conn.close()
        return out

    def wait_for(pred, what: str, deadline_sec: float):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < deadline_sec:
            if pred():
                return time.perf_counter() - t0
            time.sleep(0.02)
        raise TimeoutError(f"timed out waiting for {what}")

    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(slo_cfg, f)
        cfg_path = f.name
    router = FleetRouter(
        [f"127.0.0.1:{args.port}"],
        host="127.0.0.1", port=router_port,
        health_interval=0.25, hedge=False,
        slo_config=cfg_path,
        scrape_interval=scrape, probe_interval=probe)
    try:
        with server_thread(server, args.port), \
                server_thread(router, router_port):
            # -- warmup: compile the serving buckets, let the prober
            # and the scraper establish a healthy history ------------
            _router_load(router_port, args.n_users, 50)
            wait_for(
                lambda: router._m_probe.get(("/queries.json", "ok")) >= 5,
                "the prober to land 5 ok probes", 30)

            def avail_quiet():
                doc = slo_status()
                a = {s["name"]: s for s in doc["slos"]}.get(
                    "probe-availability")
                return (not doc["fastBurning"] and a is not None
                        and all(b == 0 for b in a["burnRate"].values()))

            # warmup blips (a probe racing the model load can 503)
            # age out of even the 10 s slow window inside the deadline
            wait_for(avail_quiet, "a quiet healthy baseline", 30)
            healthy = slo_status()
            h_status, h_body = health()
            healthy_ok = h_status == 200 and h_body["status"] == "ok"
            compiles_before = EXECUTABLES.counts().get("compile", 0)

            # -- inject: replica down, every probe fails -------------
            FAULTS.arm("router.replica.down", error="slo-drill")
            trip_elapsed = wait_for(
                lambda: "probe-availability" in slo_status()["fastBurning"],
                "the fast burn to trip", 15)
            d_status, d_body = health()
            degraded = (d_status == 200
                        and d_body["status"] == "degraded"
                        and "probe-availability"
                        in d_body.get("sloFastBurn", []))
            conn = http.client.HTTPConnection("127.0.0.1", router_port,
                                              timeout=10)
            conn.request("GET", "/metrics")
            metrics_text = conn.getresponse().read().decode()
            conn.close()
            alerting_gauge = (
                'pio_slo_alerting{slo="probe-availability"} 2'
                in metrics_text)

            # -- lift: the page must clear on its own ----------------
            FAULTS.disarm()
            recovery_elapsed = wait_for(
                lambda: (not slo_status()["fastBurning"]
                         and health()[1]["status"] == "ok"),
                "the page to clear after disarm", 30)
            recovered = slo_status()
            # the fault dropped user traffic too (replica "down"); the
            # recovered fleet must serve real users all-200 again
            post_status, _, _ = _router_load(router_port, args.n_users,
                                             100)
            compiles = (EXECUTABLES.counts().get("compile", 0)
                        - compiles_before)
    finally:
        FAULTS.disarm()
        os.unlink(cfg_path)

    avail = {s["name"]: s for s in healthy["slos"]}["probe-availability"]
    checks = {
        "healthy_burn_zero": all(
            b == 0 for b in avail["burnRate"].values()),
        "healthy_health_ok": healthy_ok,
        "fast_burn_tripped_within_two_scrapes":
            trip_elapsed <= 2 * scrape + probe,
        "health_degraded_with_slo_named": degraded,
        "alerting_gauge_reads_2": alerting_gauge,
        "page_cleared_after_disarm":
            not recovered["fastBurning"],
        "serving_all_200_after_recovery":
            set(post_status) == {"200"},
        "serving_path_compiles_zero": compiles == 0,
    }
    ok = all(checks.values())
    print(json.dumps({
        "metric": "slo_burn_rate_drill",
        "geometry": {"n_users": args.n_users, "n_items": args.n_items,
                     "rank": args.rank},
        "scrape_interval_s": scrape,
        "probe_interval_s": probe,
        "windows": slo_cfg["windows"],
        "healthy": healthy["slos"],
        "trip_elapsed_s": round(trip_elapsed, 3),
        "trip_bound_s": round(2 * scrape + probe, 3),
        "degraded_health": d_body,
        "recovery_elapsed_s": round(recovery_elapsed, 3),
        "statuses_after_recovery": post_status,
        "recovered": recovered["slos"],
        "serving_path_compiles": compiles,
        "checks": checks,
        "ok": ok,
    }))
    if not ok:
        raise SystemExit(1)


def run_incident_mode(args, st, factory) -> None:
    """Incident flight-recorder chaos harness (ISSUE 15 acceptance):
    the SLO drill topology — one replica behind a router running the
    prober with second-scale burn windows — plus the capture plane.
    Phases:

    1. healthy — warmup traffic populates histogram exemplars (tracing
       on) and the scraper builds history; the incident store must
       stay EMPTY (steady-state overhead is zero);
    2. ``router.replica.down`` armed — the fast burn trips and, within
       two scrape intervals of the trip, EXACTLY ONE bundle appears
       whose manifest names the firing SLO, pins a >= 5 m history
       window for its series, carries >= 1 exemplar trace id
       resolvable in the bundled trace ring, and records the armed
       fault site;
    3. ``pio doctor --incident <id>`` (the real CLI, jax-free) must
       exit 2 with a finding naming the ``router.replica.down`` era.

    Zero serving-path compiles across the whole drill.
    """
    import os
    import shutil
    import socket
    import subprocess
    import sys as _sys
    import tempfile

    from predictionio_tpu.server.aot import EXECUTABLES
    from predictionio_tpu.server.engine_server import EngineServer
    from predictionio_tpu.server.router import FleetRouter
    from predictionio_tpu.utils import tracing
    from predictionio_tpu.utils.faults import FAULTS
    from predictionio_tpu.utils.incidents import IncidentStore
    from profile_common import server_thread

    scrape, probe = 0.5, 0.1
    slo_cfg = {
        "windows": {"fast": ["1s", "2s"], "slow": ["10s"]},
        "thresholds": {"fast": 14.4, "slow": 6.0},
        "slos": [
            {"name": "probe-availability", "type": "availability",
             "objective": 0.99,
             "series": "pio_probe_requests_total",
             "labels": {"path": "/queries.json"},
             "bad": {"outcome": "error"}},
        ],
    }
    # exemplars ride on histogram observations only while tracing is
    # on — the bundle's trace pin is part of what this drill proves
    tracing.TRACER.configure(enabled=True, sample_rate=1.0)

    server = EngineServer(engine_factory=factory, storage=st,
                          host="127.0.0.1", port=args.port)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    router_port = s.getsockname()[1]
    s.close()

    def slo_status():
        conn = http.client.HTTPConnection("127.0.0.1", router_port,
                                          timeout=10)
        conn.request("GET", "/slo/status")
        out = json.loads(conn.getresponse().read())
        conn.close()
        return out

    def wait_for(pred, what: str, deadline_sec: float):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < deadline_sec:
            if pred():
                return time.perf_counter() - t0
            time.sleep(0.02)
        raise TimeoutError(f"timed out waiting for {what}")

    inc_dir = tempfile.mkdtemp(prefix="pio-incident-drill-")
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(slo_cfg, f)
        cfg_path = f.name
    store = IncidentStore(inc_dir)

    def complete_bundles():
        return [i for i in store.ids()
                if store.load_manifest(i) is not None]

    router = FleetRouter(
        [f"127.0.0.1:{args.port}"],
        host="127.0.0.1", port=router_port,
        health_interval=0.25, hedge=False,
        slo_config=cfg_path,
        scrape_interval=scrape, probe_interval=probe,
        incident_dir=inc_dir)
    try:
        with server_thread(server, args.port), \
                server_thread(router, router_port):
            # -- warmup: compiles + exemplars + a healthy history ----
            _router_load(router_port, args.n_users, 50)
            wait_for(
                lambda: router._m_probe.get(("/queries.json", "ok")) >= 5,
                "the prober to land 5 ok probes", 30)
            wait_for(
                lambda: not slo_status()["fastBurning"],
                "a quiet healthy baseline", 30)
            time.sleep(2 * scrape)          # two quiet scrape ticks
            steady_state_empty = not store.ids()
            compiles_before = EXECUTABLES.counts().get("compile", 0)

            # -- inject: replica down -> fast burn -> capture --------
            FAULTS.arm("router.replica.down", error="incident-drill")
            wait_for(
                lambda: "probe-availability" in slo_status()["fastBurning"],
                "the fast burn to trip", 15)
            capture_elapsed = wait_for(
                lambda: complete_bundles(),
                "the incident bundle to land", 15)
            # give a racing coalesced re-capture (breaker-open on the
            # same fault) time to finish writing before reading
            time.sleep(0.5)
            router.incidents.join(5.0)
            bundles = complete_bundles()
            compiles = (EXECUTABLES.counts().get("compile", 0)
                        - compiles_before)
    finally:
        FAULTS.disarm()
        os.unlink(cfg_path)

    exactly_one = len(bundles) == 1 and len(store.ids()) == 1
    iid = bundles[0] if bundles else None
    bundle = store.load_bundle(iid) if iid else None
    manifest = (bundle or {}).get("manifest") or {}
    files = (bundle or {}).get("files") or {}

    slo_named = "probe-availability" in (manifest.get("sloFastBurning")
                                         or [])
    window_s = manifest.get("metricsWindowSeconds") or 0
    history = files.get("metrics_history.json") or {}
    history_ok = (window_s >= 300
                  and history.get("windowSeconds", 0) >= 300
                  and any(k.startswith("pio_probe_requests_total")
                          for k in (history.get("series") or {})))
    traces = files.get("traces.json") or {}
    ring_ids = {s.get("traceId") for s in traces.get("spans") or []}
    exemplar_ids = set(traces.get("exemplarTraceIds") or [])
    exemplar_resolvable = bool(exemplar_ids & ring_ids)
    fault_recorded = "router.replica.down" in (manifest.get("faults")
                                               or {})

    # -- the real CLI: pio doctor --incident <id> (jax-free) ---------
    doctor_exit, doctor_named = -1, False
    if iid:
        proc = subprocess.run(
            [_sys.executable, "-m", "predictionio_tpu.tools.cli",
             "doctor", "--incident", iid, "--dir", inc_dir, "--json"],
            capture_output=True, text=True, timeout=60)
        doctor_exit = proc.returncode
        try:
            doc = json.loads(proc.stdout)
            doctor_named = any(
                "router.replica.down" in f.get("title", "")
                for f in doc.get("findings", []))
        except ValueError:
            pass

    checks = {
        "steady_state_store_empty": steady_state_empty,
        "exactly_one_bundle": exactly_one,
        "captured_within_two_scrapes":
            capture_elapsed <= 2 * scrape + probe,
        "manifest_names_firing_slo": slo_named,
        "history_window_at_least_5m": history_ok,
        "exemplar_trace_resolvable_in_ring": exemplar_resolvable,
        "armed_fault_site_recorded": fault_recorded,
        "doctor_exits_2": doctor_exit == 2,
        "doctor_names_fault_era": doctor_named,
        "serving_path_compiles_zero": compiles == 0,
    }
    ok = all(checks.values())
    print(json.dumps({
        "metric": "incident_flight_recorder_drill",
        "geometry": {"n_users": args.n_users, "n_items": args.n_items,
                     "rank": args.rank},
        "scrape_interval_s": scrape,
        "probe_interval_s": probe,
        "incident_id": iid,
        "capture_elapsed_s": round(capture_elapsed, 3),
        "capture_bound_s": round(2 * scrape + probe, 3),
        "manifest_triggers": [t.get("trigger")
                              for t in manifest.get("triggers", [])],
        "manifest_slo_fast_burning": manifest.get("sloFastBurning"),
        "metrics_window_seconds": window_s,
        "exemplar_trace_ids": sorted(exemplar_ids),
        "doctor_exit": doctor_exit,
        "serving_path_compiles": compiles,
        "checks": checks,
        "ok": ok,
    }))
    shutil.rmtree(inc_dir, ignore_errors=True)
    if not ok:
        raise SystemExit(1)


def run_autoscale_mode(args) -> None:
    """Self-healing fleet chaos harness (ISSUE 19 acceptance). Real
    replica subprocesses under a :class:`ReplicaPool` behind a
    :class:`FleetRouter` running the autoscaler + auto-remediation
    control loop. Phases:

    1. baseline — paced low-rate traffic; the fleet must hold at one
       replica (no scale thrash at rest) while p99 is measured;
    2. 10x ramp — sustained pressure must scale 1→N (N >= 2) with zero
       5xx across the whole ramp and post-scale p99 <= 2x baseline;
    3. kill -9 + ``remediate.storm`` — the dead replica is detected
       (health → down), remediated through the restart playbook
       EXACTLY once (storm re-presents the finding every tick; the
       per-playbook rate limit alone bounds the blast radius), and
       backfilled by its supervisor;
    4. scale-down — traffic stops; the fleet drains back to one
       replica and no down decision ever fires with <= 1 healthy;
    5. ``pio doctor --act`` (no ``--yes``) against the incident bundle
       the remediation pinned — the full plan prints, every entry is
       ``dry-run``, and no replica is touched.

    The parent process stays jax-free: replicas are subprocesses.
    """
    import os
    import shutil
    import socket
    import subprocess
    import sys as _sys
    import tempfile
    import threading

    from predictionio_tpu.server.autoscale import AutoscaleConfig
    from predictionio_tpu.server.router import FleetRouter
    from predictionio_tpu.tools.supervise import ReplicaPool
    from predictionio_tpu.utils.faults import FAULTS
    from predictionio_tpu.utils.incidents import IncidentStore
    from profile_common import server_thread

    work = tempfile.mkdtemp(prefix="pio-autoscale-drill-")
    manifest = os.path.join(work, "fleet.txt")
    inc_dir = os.path.join(work, "incidents")
    rem_path = os.path.join(work, "remediations.json")
    with open(rem_path, "w") as f:
        # rateLimit max=1 makes "exactly once" a property of the
        # engine, not of lucky timing
        json.dump({"playbooks": [
            {"name": "restart-wedged-replica",
             "match": {"kinds": ["replica-down", "replica-not-ready",
                                 "breaker-open"], "minSeverity": 1},
             "action": "restart_replica",
             "rateLimit": {"max": 1, "windowSec": 600}},
        ]}, f)

    pool = ReplicaPool(
        [_sys.executable, __file__, "--_replica-port", "{port}",
         "--platform", args.platform, "--n-users", str(args.n_users),
         "--n-items", str(args.n_items), "--rank", str(args.rank)],
        manifest, ready_timeout=240.0, drain_grace=0.5,
        health_interval=0.5, health_grace=120.0, backoff=0.2,
        backoff_max=1.0, log=lambda *a: None)

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    router_port = s.getsockname()[1]
    s.close()

    def paced_load(rate_hz: float, threads: int = 4):
        """Open-loop paced client threads; unlike the closed-loop
        ``_router_load`` the offered rate is fixed, so the autoscaler's
        qps signal is the experiment variable, not a side effect of
        latency. Returns (stop_fn, samples, lock)."""
        stop = threading.Event()
        lock = threading.Lock()
        samples = []  # (status, latency_s, started_at)

        def worker(seed: int):
            import http.client as hc

            rng = np.random.default_rng(seed)
            conn = hc.HTTPConnection("127.0.0.1", router_port, timeout=30)
            interval = threads / rate_hz
            next_t = time.perf_counter()
            while not stop.is_set():
                now = time.perf_counter()
                if now < next_t:
                    time.sleep(min(0.01, next_t - now))
                    continue
                next_t += interval
                body = json.dumps(
                    {"user": str(int(rng.integers(0, args.n_users))),
                     "num": 10})
                t0 = time.perf_counter()
                try:
                    conn.request("POST", "/queries.json", body,
                                 {"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    resp.read()
                    status = resp.status
                except Exception:
                    conn.close()
                    conn = hc.HTTPConnection("127.0.0.1", router_port,
                                             timeout=30)
                    status = 0
                with lock:
                    samples.append((status, time.perf_counter() - t0, t0))
            conn.close()

        ts = [threading.Thread(target=worker, args=(31 + i,), daemon=True)
              for i in range(threads)]
        for t in ts:
            t.start()

        def stop_fn():
            stop.set()
            for t in ts:
                t.join(timeout=15)
            with lock:
                return list(samples)

        return stop_fn, samples, lock

    def wait_for(pred, what: str, deadline_sec: float):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < deadline_sec:
            if pred():
                return time.perf_counter() - t0
            time.sleep(0.05)
        raise TimeoutError(f"timed out waiting for {what}")

    def p99(lats):
        return float(np.percentile(np.asarray(lats), 99)) if lats else 0.0

    cfg = AutoscaleConfig(
        min_replicas=1, max_replicas=3, interval=0.5, window=5.0,
        up_qps_per_replica=25.0, down_qps_per_replica=4.0,
        sustain_ticks=3, quiet_ticks=6, cooldown_up=5.0,
        cooldown_down=6.0, flap_window=600.0, flap_max_actions=10)

    checks: dict = {}
    detail: dict = {}
    try:
        pool.add_replica()          # the fleet starts at min_replicas
        router = FleetRouter(
            manifest=manifest, host="127.0.0.1", port=router_port,
            health_interval=0.25, scrape_interval=0.25,
            probe_interval=0.0, incident_dir=inc_dir,
            pool=pool, autoscale=cfg, remediations=rem_path)
        with server_thread(router, router_port):
            wait_for(lambda: all(r.state == "ok"
                                 for r in router.replicas)
                     and len(router.replicas) == 1,
                     "the seed replica behind the router", 60)

            # -- phase 1: baseline at 1 replica ----------------------
            stop_fn, _, _ = paced_load(8.0, threads=2)
            time.sleep(6.0)
            base_samples = stop_fn()
            base_p99 = p99([l for _, l, _ in base_samples])
            checks["baseline_no_scale"] = pool.size() == 1
            detail["baseline_p99_ms"] = round(base_p99 * 1e3, 2)

            # -- phase 2: 10x ramp -----------------------------------
            stop_fn, samples, lock = paced_load(80.0, threads=8)
            scale_elapsed = wait_for(lambda: pool.size() >= 2,
                                     "the ramp to scale the fleet up",
                                     200)
            time.sleep(8.0)         # settle at the scaled size
            ramp_samples = stop_fn()
            t_end = max(t for _, _, t in ramp_samples)
            post = [l for st, l, t in ramp_samples if t >= t_end - 5.0]
            post_p99 = p99(post)
            bad = {str(st): sum(1 for s, _, _ in ramp_samples if s == st)
                   for st in {s for s, _, _ in ramp_samples}
                   if st == 0 or st >= 500}
            n_scaled = pool.size()
            checks["ramp_scaled_up"] = n_scaled >= 2
            checks["ramp_zero_5xx"] = not bad
            checks["post_scale_p99_within_2x"] = post_p99 <= 2 * base_p99
            detail.update(
                ramp_replicas=n_scaled,
                ramp_scale_elapsed_s=round(scale_elapsed, 1),
                ramp_bad_statuses=bad,
                post_scale_p99_ms=round(post_p99 * 1e3, 2))

            # -- phase 3: kill -9 under remediate.storm --------------
            victim = pool.names()[-1]
            pid0 = pool.child_pid(victim)
            eng = router.remediator
            executed = lambda: sum(  # noqa: E731
                1 for e in eng.log if e["result"] == "executed")
            FAULTS.arm("remediate.storm", error="storm drill")
            os.kill(pid0, 9)
            wait_for(lambda: executed() >= 1,
                     "the restart remediation to fire", 60)
            wait_for(lambda: pool.child_pid(victim) not in (None, pid0),
                     "the supervisor to backfill the victim", 120)
            wait_for(lambda: all(r.state == "ok"
                                 for r in router.replicas),
                     "the fleet to heal", 180)
            time.sleep(3.0)         # several more storm ticks
            FAULTS.disarm("remediate.storm")
            checks["kill_remediated_exactly_once"] = executed() == 1
            checks["storm_guard_rate_limited"] = all(
                e["result"] in ("executed", "rate-limited")
                for e in eng.log)
            checks["kill_backfilled"] = (
                pool.child_pid(victim) not in (None, pid0))
            detail["remediation_log"] = [
                {"playbook": e["playbook"], "target": e["target"],
                 "result": e["result"]} for e in eng.log]

            # -- phase 4: quiet -> scale-down to one healthy ---------
            wait_for(lambda: pool.size() == 1,
                     "the quiet fleet to scale back down", 120)
            time.sleep(2.0)
            downs = [d for d in router.autoscaler.decisions
                     if d["action"] == "down"]
            checks["scaled_down_to_min"] = pool.size() == 1
            checks["down_never_below_one_healthy"] = all(
                d["signals"]["healthy"] >= 2 for d in downs)
            detail["down_decisions"] = len(downs)

            # -- phase 5: doctor --act WITHOUT --yes -----------------
            store = IncidentStore(inc_dir)
            bundles = [i for i in store.ids()
                       if store.load_manifest(i) is not None]
            checks["incident_bundle_pinned"] = bool(bundles)
            plan, plan_ok = [], False
            pids_before = {n: pool.child_pid(n) for n in pool.names()}
            if bundles:
                proc = subprocess.run(
                    [_sys.executable, "-m",
                     "predictionio_tpu.tools.cli", "doctor",
                     "--incident", bundles[0], "--dir", inc_dir,
                     "--act", "--remediations", rem_path, "--json"],
                    capture_output=True, text=True, timeout=120)
                try:
                    plan = json.loads(proc.stdout).get("remediation", [])
                except ValueError:
                    pass
                plan_ok = bool(plan) and all(
                    e["result"] == "dry-run" for e in plan)
            checks["doctor_act_plans_without_executing"] = (
                plan_ok and executed() == 1
                and {n: pool.child_pid(n) for n in pool.names()}
                == pids_before)
            detail["doctor_plan"] = [
                {"playbook": e.get("playbook"), "target": e.get("target"),
                 "result": e.get("result")} for e in plan]
    finally:
        FAULTS.disarm()
        pool.stop_all()

    ok = all(checks.values())
    print(json.dumps({
        "metric": "self_healing_autoscale_drill",
        "geometry": {"n_users": args.n_users, "n_items": args.n_items,
                     "rank": args.rank},
        "autoscale": {"min": cfg.min_replicas, "max": cfg.max_replicas,
                      "interval_s": cfg.interval},
        **detail,
        "checks": checks,
        "ok": ok,
    }))
    shutil.rmtree(work, ignore_errors=True)
    if not ok:
        raise SystemExit(1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=2000)
    ap.add_argument("--platform", default="cpu",
                    help="jax platform (cpu|tpu); cpu isolates the "
                         "HTTP/host shares")
    ap.add_argument("--n-users", type=int, default=138493)
    ap.add_argument("--n-items", type=int, default=26744)
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--port", type=int, default=8971)
    ap.add_argument("--concurrency", type=int, default=0,
                    help="also measure N parallel HTTP clients against "
                         "a --batching server (micro-batcher + "
                         "one-dispatch batch_predict path)")
    ap.add_argument("--fault", default=None, metavar="SPEC",
                    help="fault-injection mode: a PIO_FAULTS-style spec "
                         "(e.g. 'eventsink.send:error=down'); measures "
                         "healthy vs faulted p50 with feedback enabled")
    ap.add_argument("--fault-timeout-ms", type=float, default=1000.0,
                    help="query deadline for the --fault server")
    ap.add_argument("--max-inflight", type=int, default=0,
                    help="inflight cap for the --fault server "
                         "(0 = unlimited)")
    ap.add_argument("--tracing", default=None,
                    choices=["off", "sampled", "full"],
                    help="tracing-overhead A/B mode: measure the same "
                         "HTTP load untraced, then with tracing off "
                         "(noise floor) / 1%% sampled / fully exported")
    ap.add_argument("--router", action="store_true",
                    help="fleet-router chaos mode: 3 replica "
                         "subprocesses behind a FleetRouter; rolling "
                         "reload + kill -9 under load must serve 0 "
                         "non-200s with bounded p99")
    ap.add_argument("--train-loop", action="store_true",
                    help="continuous-training chaos mode: a shared-home "
                         "replica + real `pio train --continuous` "
                         "subprocesses; kill -9 mid-delta-train, an "
                         "injected promote.regression, and a fenced "
                         "second trainer must all leave the fleet "
                         "serving the right champion with zero errors")
    ap.add_argument("--autoscale", action="store_true",
                    help="self-healing fleet chaos mode: a ReplicaPool "
                         "of replica subprocesses behind a FleetRouter "
                         "with the autoscaler + auto-remediation loop; "
                         "a 10x ramp must scale 1->N with zero 5xx and "
                         "post-scale p99 <= 2x baseline, a kill -9 "
                         "under remediate.storm must be remediated "
                         "exactly once, scale-down must never drop "
                         "below one healthy replica, and `pio doctor "
                         "--act` without --yes must plan only")
    ap.add_argument("--_replica-port", dest="replica_port", type=int,
                    default=0, help=argparse.SUPPRESS)
    ap.add_argument("--_replica-home", dest="replica_home", default="",
                    help=argparse.SUPPRESS)
    ap.add_argument("--variants", action="store_true",
                    help="multi-model multiplexing chaos mode: two "
                         "registry generations resident on one replica "
                         "under a 90/10 split; proves split fidelity "
                         "±1%% with sticky assignment, champion "
                         "survival of a mid-swap kill "
                         "(variant.reload.partial), and zero "
                         "serving-path compiles")
    ap.add_argument("--tenants", action="store_true",
                    help="multi-tenant QoS chaos mode: a quota'd "
                         "burster at 10x two background tenants' "
                         "traffic on one Event Server (only the "
                         "burster 429s, honest Retry-After), then a "
                         "query flood against one engine server's "
                         "max-inflight (burster shed at its fair "
                         "share, quiet tenants all-200 with p99 <= "
                         "1.5x solo, zero serving-path compiles)")
    ap.add_argument("--slo", action="store_true",
                    help="SLO burn-rate chaos mode: the synthetic "
                         "prober against one replica behind a router "
                         "with second-scale burn windows; an injected "
                         "router.replica.down must trip the fast burn "
                         "within two scrape intervals and degrade "
                         "/health, disarming must clear the page, and "
                         "the whole drill must trigger zero "
                         "serving-path compiles")
    ap.add_argument("--incident", action="store_true",
                    help="incident flight-recorder chaos mode: the "
                         "--slo topology plus the capture plane; an "
                         "armed router.replica.down must produce "
                         "exactly one postmortem bundle within two "
                         "scrape intervals of the fast-burn trip "
                         "(firing SLO named, >=5m history pinned, "
                         "exemplar traces resolvable, fault era "
                         "recorded) and `pio doctor --incident` must "
                         "exit 2; zero serving-path compiles")
    ap.add_argument("--aot", action="store_true",
                    help="AOT bucket-ladder mode: cold vs warm ladder "
                         "compile wall time + per-bucket device p50, "
                         "asserting zero serving-path compiles")
    ap.add_argument("--aot-buckets", default="auto",
                    help="ladder spec for --aot ('auto' or comma list)")
    ap.add_argument("--aot-iters", type=int, default=50,
                    help="measured dispatches per bucket in --aot mode")
    ap.add_argument("--batch-max", type=int, default=64,
                    help="top bucket for the 'auto' ladder in --aot mode")
    args = ap.parse_args()

    if args.replica_port:
        _replica_main(args)
        return
    if args.train_loop:
        # no jax in the parent: the trainers and the replica are real
        # subprocesses, the harness only seeds events and watches files
        run_train_loop_mode(args)
        return
    if args.autoscale:
        # likewise jax-free in the parent: the pool's replicas are
        # subprocesses, the router/autoscaler/remediator are pure host
        run_autoscale_mode(args)
        return

    from profile_common import make_memory_storage, resolve_platform

    jax = resolve_platform(args.platform)
    if args.variants:
        # home-backed storage of its own (the model registry lives on
        # the filesystem) — skips the shared memory-storage setup
        run_variants_mode(args)
        return
    if args.tenants:
        # builds its own event-server home + engine-server storage
        run_tenants_mode(args)
        return
    from predictionio_tpu.core.workflow import prepare_deploy
    from predictionio_tpu.models.als import ResidentScorer
    from predictionio_tpu.server.engine_server import EngineServer

    st = make_memory_storage()

    factory = fabricate_instance(st, args.n_users, args.n_items, args.rank)
    if args.router:
        run_router_mode(args, st, factory)
        return
    if args.slo:
        run_slo_mode(args, st, factory)
        return
    if args.incident:
        run_incident_mode(args, st, factory)
        return
    if args.fault:
        run_fault_mode(args, st, factory)
        return
    if args.tracing:
        run_tracing_mode(args, st, factory)
        return
    if args.aot:
        run_aot_mode(args, st, factory)
        return
    rng = np.random.default_rng(1)
    users = rng.integers(0, args.n_users, args.queries)

    # 1. device: fused gather→score→top-k + packed fetch
    deployed = prepare_deploy(engine_factory=factory, storage=st)
    model = deployed.models[0]
    scorer = ResidentScorer(model.U, model.V)
    it = iter(np.resize(users, args.queries + 200))
    dev_p50, dev_p99 = measure(lambda: scorer.recommend(int(next(it)), 10),
                               args.queries)

    # 2. host: the real deploy path, no HTTP
    it2 = iter(np.resize(users, args.queries + 200))
    host_p50, host_p99 = measure(
        lambda: deployed.query({"user": str(int(next(it2))), "num": 10}),
        args.queries)

    # 3. http: live EngineServer on localhost
    from profile_common import server_thread

    server = EngineServer(engine_factory=factory, storage=st,
                          host="127.0.0.1", port=args.port)
    with server_thread(server, args.port):
        conn = http.client.HTTPConnection("127.0.0.1", args.port,
                                          timeout=10)
        it3 = iter(np.resize(users, args.queries + 200))

        def http_query():
            body = json.dumps({"user": str(int(next(it3))), "num": 10})
            conn.request("POST", "/queries.json", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
            assert resp.status == 200, data[:200]

        http_p50, http_p99 = measure(http_query, args.queries)

    batched = None
    if args.concurrency > 0:
        # concurrent clients against a --batching server: the
        # MicroBatcher coalesces in-flight queries and batch_predict
        # serves each batch in ONE device dispatch. Clients run in
        # SEPARATE PROCESSES: in-process client threads share the
        # server's GIL and halve the apparent throughput (the r4
        # harness measured the harness, not the server).
        import multiprocessing as mp

        server2 = EngineServer(engine_factory=factory, storage=st,
                               host="127.0.0.1", port=args.port + 1,
                               batching=True)
        with server_thread(server2, args.port + 1):
            per_client = max(50, args.queries // args.concurrency)
            ctx = mp.get_context("fork")

            def burst():
                q = ctx.Queue()
                procs = [ctx.Process(target=_client_proc,
                                     args=(args.port + 1, args.n_users,
                                           per_client, ci, q),
                                     daemon=True)
                         for ci in range(args.concurrency)]
                t0 = time.perf_counter()
                for p in procs:
                    p.start()
                # timeout + exitcode checks: a client killed by the
                # kernel (OOM/SIGKILL) never puts — without these the
                # harness would wedge silently
                import queue as _queue

                outs = []
                for _ in procs:
                    try:
                        outs.append(q.get(timeout=120))
                    except _queue.Empty:
                        outs.append("client timed out (killed?)")
                for p in procs:
                    p.join(timeout=30)
                    if p.is_alive():  # stuck client: kill, don't hang
                        p.terminate()
                        p.join(timeout=10)
                        outs.append("client stuck (terminated)")
                    elif p.exitcode != 0:
                        outs.append(f"client exit code {p.exitcode}")
                wall = time.perf_counter() - t0
                errs = [o for o in outs if isinstance(o, str)]
                if errs:
                    raise RuntimeError(
                        f"{len(errs)} client(s) failed; first: {errs[0]}")
                return wall, [x for o in outs for x in o]

            # warm pass: the first concurrent burst compiles the
            # power-of-two batch-size buckets once (production pays
            # this once per deploy); measure the steady state
            burst()
            wall, lat_all = burst()
            flat = np.asarray(lat_all)
            batched = {
                "clients": args.concurrency,
                "queries": int(flat.size),
                "p50_ms": round(float(np.percentile(flat, 50) * 1e3), 4),
                "p99_ms": round(float(np.percentile(flat, 99) * 1e3), 4),
                "queries_per_sec": round(flat.size / wall),
            }

    print(json.dumps({
        "metric": "predict_latency_decomposition",
        "geometry": {"n_users": args.n_users, "n_items": args.n_items,
                     "rank": args.rank},
        "platform": jax.default_backend(),
        "queries": args.queries,
        "device_ms": {"p50": round(dev_p50, 4), "p99": round(dev_p99, 4)},
        "host_ms": {"p50": round(host_p50, 4), "p99": round(host_p99, 4)},
        "http_ms": {"p50": round(http_p50, 4), "p99": round(http_p99, 4)},
        "host_overhead_ms": round(host_p50 - dev_p50, 4),
        "http_overhead_ms": round(http_p50 - host_p50, 4),
        **({"batching_concurrent": batched} if batched else {}),
    }))


if __name__ == "__main__":
    main()
