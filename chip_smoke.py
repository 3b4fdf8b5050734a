#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the recommendation engine's whole path once, through the entry
points a user calls, in ONE process that owns the chip:

    pio app new → synthetic ML-20M clone as NDJSON (from --seed) →
    pio import (EVENTLOG backend, native parser) → pio train (rank 64,
    10 iterations, core/workflow.run_train) → a second pio train (must
    compile nothing) → prepare_deploy + EngineServer with the AOT ladder
    and the micro-batcher → POST /queries.json over HTTP → stop.

It checks what comes out: train RMSE on the training ratings under a
stated bound; the device ALS against a small float32 numpy ALS on a
seeded sub-sample; every query 200 with k items; /health ``ok`` with
warm-up ``ready``; ``pio_aot_dispatch_total`` moved and nothing compiled
on the query path after warm-up.

Output contract: every fact (sizes, seconds, RMSE, Gram/solve mode, cache
directory, HTTP answers) is printed on EARLIER lines; the LAST line of
stdout is one JSON object, on success exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``
with the device as JAX reports it in this process. The script never sets a
platform: on anything but a TPU the phases still run as a rehearsal (at the
``--tiny`` size unless ``--nnz`` asks for more), the verdict is failure and
the exit code non-zero.

``--chips 4`` (run by the builder, never by the driver) runs only the
sharded ``pio train`` on four devices and the single-device train it is
compared with; the last line then carries ``"count": 4``.

Seconds printed here are observations of one run, not metrics.
"""

from __future__ import annotations

import argparse
import http.client
import json
import logging
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
#: fixed, git-ignored; wiped at the start and removed at the end so no
#: rehearsal leaves gigabytes in the tree the chip tool copies
HOME = os.path.join(ROOT, ".chip_smoke_home")
APP = "ChipSmoke"
FACTORY = "predictionio_tpu.templates.recommendation.engine:engine_factory"
RANK, ITERATIONS, REG, K = 64, 10, 0.05, 10
#: the bound on the train RMSE is the ratings' own standard deviation
#: (1.436 for uniform {0.5 … 5.0}): a model that learned anything from
#: its training ratings beats the constant mean. The ratings are noise
#: by construction, so it cannot beat it by much (1.405 at 20M nnz).
#: device ALS vs the float32 numpy reference on the sub-sample
REF_RMSE_TOL = 0.02       # |RMSE_device − RMSE_reference|
REF_PRED_TOL = 0.10       # rms(prediction_device − prediction_reference)
#: four chips vs one chip, same data, same seed
PARITY_RMSE_TOL = 0.02
PARITY_OVERLAP = 0.8


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    say(f"check ok: {what}")


class _Capture(logging.Handler):
    """Keeps the trainer's own log lines (which Gram/solve ran, the
    per-device bytes after placement) so they can be printed."""

    def __init__(self) -> None:
        super().__init__(level=logging.INFO)
        self.lines: list = []

    def emit(self, record: logging.LogRecord) -> None:
        self.lines.append(record.getMessage())

    def take(self, prefix: str) -> list:
        out = [ln for ln in self.lines if ln.startswith(prefix)]
        self.lines = [ln for ln in self.lines if not ln.startswith(prefix)]
        return out


class _CompileCounter:
    """Programs that reached the backend's compiler, and how many of
    them the persistent cache answered (jax.monitoring events)."""

    def __init__(self) -> None:
        from jax import monitoring

        self.requests = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name: str, _secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.requests, self.hits


# -- data ---------------------------------------------------------------------


def synthetic_ratings(nnz: int, n_users: int, n_items: int, seed: int):
    """bench.py's synthetic ML-20M clone: power-law user/item
    popularity, ratings in {0.5 … 5.0}."""
    from bench import synthetic_ml20m

    return synthetic_ml20m(nnz, n_users, n_items, seed)


def write_ndjson(path: str, users, items, ratings) -> int:
    """One "rate" event per rating, in the wire format `pio import`
    reads. Every line has the same width, so lines are filled in bulk
    as a byte matrix (ids are strings: zero padding is legal)."""
    import numpy as np

    head = b'{"event":"rate","entityType":"user","entityId":"u'
    mid = b'","targetEntityType":"item","targetEntityId":"i'
    tail = b'","properties":{"rating":'
    end = b'}}\n'
    template = np.frombuffer(
        head + b"000000" + mid + b"00000" + tail + b"0.0" + end, np.uint8)
    u0 = len(head)
    i0 = u0 + 6 + len(mid)
    r0 = i0 + 5 + len(tail)
    written = 0
    with open(path, "wb") as f:
        for a in range(0, len(users), 1_000_000):
            u = users[a:a + 1_000_000].astype(np.int64)
            i = items[a:a + 1_000_000].astype(np.int64)
            r10 = np.rint(ratings[a:a + 1_000_000] * 10).astype(np.int64)
            block = np.tile(template, (len(u), 1))
            for d in range(6):
                block[:, u0 + 5 - d] = 48 + (u // 10 ** d) % 10
            for d in range(5):
                block[:, i0 + 4 - d] = 48 + (i // 10 ** d) % 10
            block[:, r0] = 48 + r10 // 10
            block[:, r0 + 2] = 48 + r10 % 10
            f.write(block.tobytes())
            written += block.size
    return written


def numpy_als(users, items, ratings, n_users, n_items, V0, iterations, reg):
    """Plain float32 ALS-WR (λ·n_e ridge), independent of the code under
    test: per entity, the normal equations of its ratings, solved by
    LAPACK. Entities with no rating keep zero factors."""
    import numpy as np

    def half(idx_self, idx_other, vals, n_self, F):
        order = np.argsort(idx_self, kind="stable")
        s, o, v = idx_self[order], idx_other[order], vals[order]
        bounds = np.flatnonzero(np.diff(s)) + 1
        starts = np.concatenate([[0], bounds])
        stops = np.concatenate([bounds, [len(s)]])
        X = np.zeros((n_self, F.shape[1]), np.float32)
        eye = np.eye(F.shape[1], dtype=np.float32)
        for a, b in zip(starts, stops):
            Fe = F[o[a:b]]
            A = Fe.T @ Fe + np.float32(reg * (b - a)) * eye
            X[s[a]] = np.linalg.solve(A, Fe.T @ v[a:b])
        return X

    V = V0.astype(np.float32)
    U = np.zeros((n_users, V.shape[1]), np.float32)
    for _ in range(iterations):
        U = half(users, items, ratings, n_users, V)
        V = half(items, users, ratings, n_items, U)
    return U, V


def rmse(U, V, users, items, ratings) -> float:
    import numpy as np

    se, n = 0.0, len(ratings)
    for a in range(0, n, 1_000_000):
        p = np.einsum("nk,nk->n", U[users[a:a + 1_000_000]],
                      V[items[a:a + 1_000_000]])
        se += float(np.sum((p - ratings[a:a + 1_000_000]) ** 2,
                           dtype=np.float64))
    return (se / n) ** 0.5


# -- the pio verbs, in-process ------------------------------------------------


def pio(*argv: str) -> None:
    """One `pio` verb through the CLI's own main(argv)."""
    from predictionio_tpu.tools import cli

    say("$ pio " + " ".join(argv))
    cli.main(list(argv))


def engine_dir(name: str, mesh_conf: dict, seed: int) -> str:
    d = os.path.join(HOME, name)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "engine.json"), "w") as f:
        json.dump({
            "id": "default",
            "description": "chip_smoke",
            "engineFactory": FACTORY,
            "datasource": {"params": {"appName": APP,
                                      "eventNames": ["rate"]}},
            "algorithms": [{"name": "als", "params": {
                "rank": RANK, "numIterations": ITERATIONS,
                "lambda": REG, "seed": seed}}],
            "meshConf": mesh_conf,
        }, f)
    return d


def load_model():
    """The newest COMPLETED instance's ALSModel, as `pio deploy` loads it."""
    from predictionio_tpu.core.workflow import prepare_deploy

    deployed = prepare_deploy(engine_factory=FACTORY, variant_id="default")
    return deployed.models[0]


def dense_ids(model, users, items):
    """The generator's integer ids → the trained model's dense indices
    (the event store assigns those in first-seen order)."""
    import numpy as np

    def table(bimap, prefix, width, n):
        t = np.full(n, -1, np.int64)
        for key, idx in bimap.to_dict().items():
            if key[0] != prefix or len(key) != width + 1:
                raise SmokeFailure(f"id {key!r} is not one of the "
                                   "generator's")
            t[int(key[1:])] = idx
        return t

    ut = table(model.user_ids, "u", 6, int(users.max()) + 1)
    it = table(model.item_ids, "i", 5, int(items.max()) + 1)
    return ut[users], it[items]


def setup_home() -> None:
    shutil.rmtree(HOME, ignore_errors=True)
    os.makedirs(HOME)
    os.environ["PIO_HOME"] = HOME
    # the bulk-data backend (the reference's HBase slot): C++ event log
    os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "SMOKELOG"
    os.environ["PIO_STORAGE_SOURCES_SMOKELOG_TYPE"] = "EVENTLOG"


def native_engine() -> None:
    """The event log's C++ engine is built from the tracked eventlog.cc
    at first use; say which engine serves the import."""
    from predictionio_tpu import native

    gxx = shutil.which("g++")
    lib = native.eventlog_library()
    if lib is None:
        check(gxx is None, "g++ is present, so the native event-log "
              "engine must build")
        say("import engine: python (no g++ here: SQLITE event store, "
            "Python line parser)")
        del os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"]
        del os.environ["PIO_STORAGE_SOURCES_SMOKELOG_TYPE"]
    else:
        say(f"import engine: native (eventlog.cc built with {gxx})")


def import_and_count(args, sizes):
    import numpy as np

    n_users, n_items, nnz = sizes
    t0 = time.perf_counter()
    users, items, ratings = synthetic_ratings(nnz, n_users, n_items,
                                              args.seed)
    path = os.path.join(HOME, "ratings.ndjson")
    nbytes = write_ndjson(path, users, items, ratings)
    say(f"data: synthetic ML-20M clone, seed {args.seed}: nnz={nnz:,} "
        f"users={n_users:,} ({len(np.unique(users)):,} rated) "
        f"items={n_items:,} ({len(np.unique(items)):,} rated); "
        f"{nbytes / 1e6:.0f} MB NDJSON in "
        f"{time.perf_counter() - t0:.1f} s")
    pio("app", "new", APP)
    t0 = time.perf_counter()
    pio("import", "--app-name", APP, "--input", path)
    say(f"import: {nnz:,} events in {time.perf_counter() - t0:.1f} s")
    os.remove(path)
    return users, items, ratings


# -- phases -------------------------------------------------------------------


def phase_train(edir: str, cap: _Capture, compiles: _CompileCounter,
                label: str, *extra: str):
    before = compiles.snapshot()
    t0 = time.perf_counter()
    pio("train", "--engine-dir", edir, "--verbose", *extra)
    secs = time.perf_counter() - t0
    req, hit = (a - b for a, b in zip(compiles.snapshot(), before))
    for ln in cap.take("ALS train:") + cap.take("sharded ALS placement:"):
        say(f"{label}: {ln}")
    say(f"{label}: {secs:.1f} s wall (read + prepare + compile + train + "
        f"save); programs sent to the compiler: {req}, of which the "
        f"persistent cache answered {hit}")
    return req, hit


def phase_reference(args, compiles: _CompileCounter) -> None:
    """The device ALS (library entry point, same rank and iterations)
    against the numpy reference on a seeded sub-sample."""
    import numpy as np

    from predictionio_tpu.models.als import (ALSParams, RatingsCOO,
                                             als_train, init_factors)

    n_u, n_i, nnz = (300, 400, 6_000) if args.tiny else (3_000, 2_000,
                                                          120_000)
    users, items, ratings = synthetic_ratings(nnz, n_u, n_i, args.seed + 1)
    t0 = time.perf_counter()
    U, V = als_train(RatingsCOO(users, items, ratings, n_u, n_i),
                     ALSParams(rank=RANK, iterations=ITERATIONS, reg=REG,
                               seed=args.seed))
    t_dev = time.perf_counter() - t0
    t0 = time.perf_counter()
    Ur, Vr = numpy_als(users, items, ratings, n_u, n_i,
                       init_factors(n_i, RANK, args.seed), ITERATIONS, REG)
    t_ref = time.perf_counter() - t0
    check(bool(np.isfinite(U).all() and np.isfinite(V).all()),
          "sub-sample factors are finite")
    r_dev = rmse(U, V, users, items, ratings)
    r_ref = rmse(Ur, Vr, users, items, ratings)
    p_dev = np.einsum("nk,nk->n", U[users], V[items])
    p_ref = np.einsum("nk,nk->n", Ur[users], Vr[items])
    d_pred = float(np.sqrt(np.mean((p_dev - p_ref) ** 2)))
    say(f"reference: sub-sample {n_u}x{n_i}, nnz={nnz:,}, rank {RANK}, "
        f"{ITERATIONS} iterations: RMSE device={r_dev:.5f} "
        f"numpy-f32={r_ref:.5f}, rms prediction difference={d_pred:.5f} "
        f"(device {t_dev:.1f} s incl. compile, numpy {t_ref:.1f} s)")
    check(abs(r_dev - r_ref) <= REF_RMSE_TOL,
          f"|RMSE device - RMSE numpy| <= {REF_RMSE_TOL}")
    check(d_pred <= REF_PRED_TOL,
          f"rms prediction difference <= {REF_PRED_TOL}")


def http_json(port: int, method: str, path: str, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request(method, path,
                     body=None if body is None else json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"null")
    finally:
        conn.close()


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def phase_serve(users, compiles: _CompileCounter) -> None:
    """`pio deploy`'s server class with the AOT ladder and the
    micro-batcher, on a thread of this process; queries over HTTP."""
    import asyncio

    import numpy as np

    from predictionio_tpu.server.engine_server import EngineServer

    port = free_port()
    t0 = time.perf_counter()
    server = EngineServer(engine_factory=FACTORY, variant_id="default",
                          host="127.0.0.1", port=port, batching=True,
                          aot_buckets="auto", aot_topk=16)
    loop = asyncio.new_event_loop()

    def run() -> None:
        asyncio.set_event_loop(loop)
        loop.run_until_complete(server.serve_forever())
        loop.close()

    thread = threading.Thread(target=run, name="smoke-engine-server")
    thread.start()
    try:
        # 503 not-ready while the ladder compiles; 200 once it settled
        # (ok when ready, degraded when the warm-up failed)
        deadline = time.time() + 600
        status, health = 0, None
        while time.time() < deadline:
            try:
                status, health = http_json(port, "GET", "/health")
            except OSError:
                status = 0      # not listening yet
            if status == 200:
                break
            time.sleep(0.5)
        say(f"deploy: /health {status} after "
            f"{time.perf_counter() - t0:.1f} s (model load + AOT "
            f"warm-up): {json.dumps(health)[:600]}")
        check(status == 200 and health["status"] == "ok",
              "/health is ok")
        check(health["warmup"]["state"] == "ready",
              "AOT warm-up is ready (not failed)")

        def dispatches():
            """pio_aot_dispatch_total by path, from GET /metrics."""
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=60)
            try:
                conn.request("GET", "/metrics")
                text = conn.getresponse().read().decode()
            finally:
                conn.close()
            out = {"aot": 0.0, "jit": 0.0}
            for ln in text.splitlines():
                if ln.startswith("pio_aot_dispatch_total{"):
                    path = "jit" if 'path="jit"' in ln else "aot"
                    out[path] += float(ln.rsplit(" ", 1)[1])
            return out

        d0, c0 = dispatches(), compiles.snapshot()
        known = [f"u{int(u):06d}" for u in np.unique(users)[:4]]
        answers = []

        def ask(user):
            st, body = http_json(port, "POST", "/queries.json",
                                 {"user": user, "num": K})
            answers.append((user, st, body))

        for user in known:
            ask(user)
        # a concurrent burst, so the micro-batcher fills a bucket > 1
        burst = [threading.Thread(target=ask, args=(u,))
                 for u in known * 2]
        for t in burst:
            t.start()
        for t in burst:
            t.join()
        ask("nobody-ever-rated")
        d1, c1 = dispatches(), compiles.snapshot()
        for user, st, body in answers[:4] + answers[-1:]:
            say(f"query: user={user} -> {st} "
                f"{json.dumps(body)[:160]}")
        def good(st, body):
            scores = [s["score"] for s in body.get("itemScores", [])]
            return (st == 200 and len(scores) == K
                    and bool(np.isfinite(scores).all())
                    and scores == sorted(scores, reverse=True))

        check(all(good(st, body) for _, st, body in answers[:-1]),
              f"all {len(answers) - 1} queries for known users: 200 with "
              f"{K} finite, ordered scores")
        user, st, body = answers[-1]
        check(st == 200 and body == {"itemScores": []},
              "unknown user: 200 with the template's cold answer "
              "(no items)")
        say(f"serving: pio_aot_dispatch_total aot {d0['aot']}->{d1['aot']} "
            f"jit {d0['jit']}->{d1['jit']}; programs sent to the compiler "
            f"during queries: {c1[0] - c0[0]}")
        check(d1["aot"] > d0["aot"], "pio_aot_dispatch_total moved")
        check(d1["jit"] == d0["jit"] and c1[0] == c0[0],
              "nothing compiled on the query path after warm-up")
    finally:
        loop.call_soon_threadsafe(server.http.request_shutdown)
        thread.join(timeout=30)
        say(f"deploy: server stopped (thread alive: {thread.is_alive()})")


def run_one_chip(args, sizes, cap, compiles, cache_dir) -> None:
    import numpy as np

    users, items, ratings = import_and_count(args, sizes)
    edir = engine_dir("engine", {}, args.seed)
    phase_train(edir, cap, compiles, "train 1")
    n_cache = len(os.listdir(cache_dir))
    say(f"compile cache: {cache_dir} holds {n_cache} entries after "
        "train 1")
    req2, _ = phase_train(edir, cap, compiles, "train 2")
    check(req2 == 0, "the second train of the same geometry compiled "
          "nothing new")
    model = load_model()
    check(model.U.shape[1] == RANK and model.V.shape[1] == RANK,
          f"factors have rank {RANK}")
    say(f"model: U {model.U.shape} V {model.V.shape}")
    check(bool(np.isfinite(model.U).all() and np.isfinite(model.V).all()),
          "factors are finite")
    uu, ii = dense_ids(model, users, items)
    check(bool((uu >= 0).all() and (ii >= 0).all()),
          "every rated user and item is in the model")
    r = rmse(model.U, model.V, uu, ii, ratings)
    bound = float(np.std(ratings))
    say(f"train RMSE on the {len(ratings):,} training ratings: {r:.5f} "
        f"(bound: the ratings' std, {bound:.5f})")
    check(r < bound, "train RMSE < std of the ratings")
    phase_reference(args, compiles)
    phase_serve(users, compiles)


def run_four_chips(args, sizes, cap, compiles) -> None:
    """Only what exists across chips: the sharded `pio train` (default
    meshConf → every local device on ``data`` → als_train_sharded) and
    the single-device train it is compared with."""
    import numpy as np

    users, items, ratings = import_and_count(args, sizes)
    edir = engine_dir("engine", {}, args.seed)
    phase_train(edir, cap, compiles, "train sharded x4")
    sharded = load_model()
    phase_train(edir, cap, compiles, "train single", "--no-mesh")
    single = load_model()
    out = {}
    for name, m in (("sharded", sharded), ("single", single)):
        check(bool(np.isfinite(m.U).all() and np.isfinite(m.V).all()),
              f"{name} factors are finite")
        uu, ii = dense_ids(m, users, items)
        out[name] = rmse(m.U, m.V, uu, ii, ratings)
    check(sharded.user_ids.to_dict() == single.user_ids.to_dict()
          and sharded.item_ids.to_dict() == single.item_ids.to_dict(),
          "both trains read the same id maps (same store, same order)")
    bound = float(np.std(ratings))
    say(f"parity: train RMSE sharded={out['sharded']:.5f} "
        f"single={out['single']:.5f} (bound: the ratings' std, "
        f"{bound:.5f})")
    check(out["sharded"] < bound, "sharded RMSE < std of the ratings")
    check(abs(out["sharded"] - out["single"]) <= PARITY_RMSE_TOL,
          f"|RMSE sharded - RMSE single| <= {PARITY_RMSE_TOL}")
    # top-k overlap for the heaviest raters (uu: shared dense ids)
    heavy = np.argsort(-np.bincount(uu))
    overlap = []
    for u in heavy[:64]:
        a = np.argsort(-(sharded.U[u] @ sharded.V.T))[:K]
        b = np.argsort(-(single.U[u] @ single.V.T))[:K]
        overlap.append(len(np.intersect1d(a, b)) / K)
    say(f"parity: top-{K} overlap over 64 users: mean "
        f"{float(np.mean(overlap)):.3f} min {float(np.min(overlap)):.3f}")
    check(float(np.mean(overlap)) >= PARITY_OVERLAP,
          f"mean top-{K} overlap >= {PARITY_OVERLAP}")


def device_report() -> dict:
    """The device as JAX reports it in this process — the one that does
    the work."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--nnz", type=int, default=None,
                    help="ratings (default 20,000,000); the only size "
                         "that may be cut")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal size (tests); never a result")
    args = ap.parse_args(argv)

    logging.basicConfig(stream=sys.stderr, level=logging.WARNING)
    cap = _Capture()
    for name in ("predictionio_tpu.models.als",
                 "predictionio_tpu.models.als_sharded"):
        lg = logging.getLogger(name)
        lg.setLevel(logging.INFO)
        lg.addHandler(cap)

    device = device_report()
    say(f"device: {json.dumps(device)}")
    if device["platform"] != "tpu":
        say("platform is not a TPU: the phases run as a rehearsal, the "
            "verdict is failure")
        if not args.tiny and args.nnz is None:
            # the real size is for the chip; a full-size CPU rehearsal
            # (about ten minutes) has to be asked for with --nnz
            say("no size asked for: rehearsing at the --tiny size")
            args.tiny = True
    nnz = args.nnz or 20_000_000
    sizes = ((4_000, 4_000, 120_000) if args.tiny
             else (138_493, 26_744, nnz))
    if not args.tiny and nnz != 20_000_000:
        say(f"CUT: nnz reduced from 20,000,000 to {nnz:,} (rank "
            f"{RANK} and the 138,493 x 26,744 catalog are not cut)")

    ok = False
    setup_home()
    try:
        check(device["count"] == args.chips,
              f"{args.chips} device(s) wanted, jax reports "
              f"{device['count']}")
        from predictionio_tpu.utils import compilecache

        compiles = _CompileCounter()
        cache_dir = compilecache.enable()
        say(f"compile cache: {cache_dir} "
            f"(JAX_COMPILATION_CACHE_DIR "
            f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
        native_engine()
        if args.chips == 4:
            run_four_chips(args, sizes, cap, compiles)
        else:
            run_one_chip(args, sizes, cap, compiles, cache_dir)
        ok = True
    except SmokeFailure as e:
        say(f"FAILED: {e}")
    except Exception as e:  # noqa: BLE001 — report, then fail
        import traceback

        traceback.print_exc()
        say(f"FAILED: {type(e).__name__}: {e}")
    finally:
        shutil.rmtree(HOME, ignore_errors=True)
    return finish(ok, device)


def finish(ok: bool, device: dict) -> int:
    """The verdict. Success needs every phase AND a TPU; only then is
    the contract's last line — the result — printed. A failure prints
    no result at all."""
    sys.stderr.flush()
    if ok and device["platform"] == "tpu":
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0
    say("verdict: FAILED (" + ("a phase failed" if not ok else
                               f"platform {device['platform']!r} is not "
                               "a TPU") + "); no result")
    return 1


if __name__ == "__main__":
    sys.exit(main())
