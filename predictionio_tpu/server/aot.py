"""AOT-bucketed serving executables: the deploy-time warmup layer.

Upstream PredictionIO serves its first query the instant ``pio deploy``
binds the port (akka-http → ``predictBase``, SURVEY.md §3.2) because
Spark ships pre-built JVM bytecode. The JAX port instead pays a full
XLA trace+compile the first time the serving program meets a NEW batch
shape — so first queries, rare batch sizes, and every probe-then-swap
``/reload`` eat a multi-second latency cliff on the hot path.

This module removes the cliff by construction:

- :class:`BucketLadder` — a geometric ladder of padded batch buckets
  (default 1, 2, 4, … max_batch; ``pio deploy --aot-buckets`` overrides).
  Every collected micro-batch is snapped UP to the nearest bucket and
  padded with masked rows, so the set of batch shapes that can ever
  reach the device is finite and known at deploy time.
- :class:`ExecutableCache` — a process-wide cache of AOT-compiled
  (``jax.jit(...).lower(...).compile()``) serving executables keyed by
  program geometry. Sharing by geometry means a probe-then-swap
  ``/reload`` of a same-shape candidate is pure cache hits: the swap
  causes ZERO compiles on the first post-swap query. The underlying
  XLA compile additionally lands in the persistent on-disk cache
  (``utils/compilecache``), so restarts warm-start from disk.
- :class:`AOTWarmup` — deploy-time orchestration: walks the deployed
  engine's algorithms, asks each (duck-typed ``aot_warm`` hook) to
  compile its serving program for every ladder bucket, and exposes
  progress for ``/health`` (``not-ready`` until the serving bucket set
  is compiled).
- ``PAD`` — the sentinel the :class:`~predictionio_tpu.server.batching.
  MicroBatcher` pads collected batches with; padded rows are masked on
  device and sliced off the fan-out, with a parity guarantee (padded
  results bitwise-identical to unpadded execution — tests/
  test_aot_serving.py).

Per-bucket device-program latency lands in the
``pio_predict_device_seconds{bucket,path}`` histogram (bench.py and
profile_serving.py --aot read it).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from predictionio_tpu.utils.metrics import REGISTRY

# -- padding sentinel ---------------------------------------------------------


class _PadQuery:
    """Sentinel appended by the MicroBatcher to fill a batch up to its
    bucket. Engine layers must never serve it: its result slot is
    sliced off before the fan-out. Singleton so ``q is PAD`` works
    across modules."""

    __slots__ = ()

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return "<PAD>"


PAD = _PadQuery()


def is_pad(query: Any) -> bool:
    return query is PAD


def strip_pads(queries: Sequence[Any]) -> Tuple[List[Any], List[int]]:
    """Split a padded batch into (real queries, their original
    positions). The complement positions are PAD slots."""
    real, pos = [], []
    for i, q in enumerate(queries):
        if q is not PAD:
            real.append(q)
            pos.append(i)
    return real, pos


# -- the bucket ladder --------------------------------------------------------


class BucketLadder:
    """A sorted ladder of padded batch buckets.

    ``snap(n)`` returns the smallest bucket ≥ n — the batch shape the
    dispatch will actually run at. The largest bucket doubles as the
    serving ``max_batch``: the MicroBatcher never collects more.
    """

    def __init__(self, buckets: Sequence[int]) -> None:
        cleaned = sorted({int(b) for b in buckets if int(b) >= 1})
        if not cleaned:
            raise ValueError("bucket ladder needs at least one bucket >= 1")
        self.buckets: Tuple[int, ...] = tuple(cleaned)

    @classmethod
    def geometric(cls, max_batch: int, base: int = 2) -> "BucketLadder":
        """1, base, base², … up to (and always including) max_batch."""
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        buckets = []
        b = 1
        while b < max_batch:
            buckets.append(b)
            b *= base
        buckets.append(max_batch)
        return cls(buckets)

    @classmethod
    def parse(cls, spec: Optional[str], max_batch: int) -> "BucketLadder":
        """``--aot-buckets`` grammar: ``auto`` (or empty) → geometric
        ladder up to ``max_batch``; else a comma-separated explicit
        ladder, e.g. ``1,2,4,8,16,32,64``. An explicit ladder defines
        its own max batch (its largest bucket)."""
        if not spec or spec.strip().lower() == "auto":
            return cls.geometric(max_batch)
        try:
            buckets = [int(tok) for tok in spec.split(",") if tok.strip()]
        except ValueError as e:
            raise ValueError(f"bad --aot-buckets spec {spec!r}: {e}") from None
        return cls(buckets)

    @property
    def max_batch(self) -> int:
        return self.buckets[-1]

    def snap(self, n: int) -> int:
        """Smallest bucket ≥ n (n > max_batch snaps to max_batch —
        callers cap collection at max_batch, so this is defensive)."""
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def __iter__(self):
        return iter(self.buckets)

    def __len__(self) -> int:
        return len(self.buckets)

    def __repr__(self) -> str:
        return f"BucketLadder({list(self.buckets)})"


# -- process-wide executable cache -------------------------------------------


class ExecutableCache:
    """AOT-compiled serving executables keyed by program geometry.

    The key must capture EVERYTHING that selects a distinct XLA
    program (shapes, statics, platform) — value arrays are passed at
    call time, so executables are safely shared across model instances
    with the same geometry. That sharing is what makes a same-geometry
    ``/reload`` compile-free: the candidate's warmup is pure hits.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._programs: Dict[Tuple, Any] = {}
        self._m_lookups = REGISTRY.counter(
            "pio_aot_cache_lookups_total",
            "AOT executable-cache lookups", ("result",))
        self._m_compile_s = REGISTRY.histogram(
            "pio_aot_compile_seconds",
            "Wall time of cold AOT lower+compile",
            buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0))

    def get(self, key: Tuple) -> Optional[Any]:
        with self._lock:
            return self._programs.get(key)

    def get_or_compile(self, key: Tuple, build: Callable[[], Any]) -> Any:
        """Return the cached executable for ``key``, compiling (and
        recording cold-compile wall time) on first use. ``build`` runs
        outside the lock — XLA compiles can take seconds and must not
        serialize unrelated lookups; a racing double-compile is benign
        (last write wins, both executables are equivalent)."""
        with self._lock:
            prog = self._programs.get(key)
        if prog is not None:
            self._m_lookups.inc(("hit",))
            return prog
        t0 = time.perf_counter()
        prog = build()
        self._m_compile_s.observe(time.perf_counter() - t0)
        self._m_lookups.inc(("compile",))
        with self._lock:
            self._programs.setdefault(key, prog)
            return self._programs[key]

    def counts(self) -> Dict[str, int]:
        """{"hit": n, "compile": m} — the zero-compile assertions in
        tests and the ``--aot`` profile read this."""
        vals = self._m_lookups._values
        return {k[0]: int(v) for k, v in vals.items()}

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._programs)


#: process-wide cache — all scorers/models share it so reloads and
#: repeated deploys in one process never recompile a known geometry
EXECUTABLES = ExecutableCache()


# -- per-bucket device latency ------------------------------------------------

#: the tracked serving metric (ROADMAP item 5): device-program latency
#: per padded batch bucket. ``path`` = aot (precompiled executable) |
#: jit (fell back to jax.jit dispatch — counts a warmup gap).
DEVICE_LATENCY = REGISTRY.histogram(
    "pio_predict_device_seconds",
    "Serving device-program latency (dispatch + packed fetch) per bucket",
    labelnames=("bucket", "path"))

_DISPATCHES = REGISTRY.counter(
    "pio_aot_dispatch_total",
    "Serving device dispatches", ("bucket", "path"))


def record_device_latency(bucket: int, seconds: float, path: str,
                          trace_exemplar: Optional[str] = None) -> None:
    labels = (str(bucket), path)
    DEVICE_LATENCY.observe(seconds, labels, exemplar=trace_exemplar)
    _DISPATCHES.inc(labels)


#: sharded-ANN serving layout (ann/scorer.ShardedANNScorer): shard
#: count of the serving mesh, padded item rows resident per device,
#: and the (k′ · shards) width of the distributed top-k merge — the
#: three numbers that size per-device HBM and the collective
#: (docs/observability.md; `pio index status --shards` predicts them
#: from the manifest alone).
ANN_SHARDS = REGISTRY.gauge(
    "pio_ann_shard_count",
    "Item shards in the sharded ANN serving mesh (0 = unsharded)")
ANN_SHARD_ITEMS = REGISTRY.gauge(
    "pio_ann_shard_items_per_device",
    "Padded item rows resident per device under sharded ANN serving")
ANN_SHARD_MERGE = REGISTRY.gauge(
    "pio_ann_shard_merge_candidates",
    "Distributed shortlist-merge width (k' x shards) per query row")


def record_shard_layout(shards: int, items_per_device: int,
                        shortlist: int) -> None:
    """Publish the sharded-ANN serving layout (called once per scorer
    construction, not per dispatch — layout only changes on /reload)."""
    ANN_SHARDS.set(shards)
    ANN_SHARD_ITEMS.set(items_per_device)
    ANN_SHARD_MERGE.set(shortlist * shards)


def device_p50_ms_by_bucket(path: str = "aot") -> Dict[str, float]:
    """Approximate per-bucket p50 (ms) from the histogram buckets —
    the ``predict_p50_device_ms`` series bench.py / profile_serving.py
    report. Median taken at the first bucket whose cumulative count
    crosses half the total (upper-bound estimate). ``path`` selects the
    dispatch path: ``"aot"`` = exact precompiled serving, ``"ann"`` =
    precompiled ADC-shortlist serving (predictionio_tpu/ann) — bench.py
    reads both to report the ANN-vs-exact per-bucket story."""
    out: Dict[str, float] = {}
    with DEVICE_LATENCY._lock:
        items = {k: list(c) for k, c in DEVICE_LATENCY._counts.items()}
    for key, counts in items.items():
        total = sum(counts)
        if not total or key[1] != path:
            continue
        half, cum = total / 2.0, 0
        p50 = DEVICE_LATENCY.buckets[-1]
        for b, c in zip(DEVICE_LATENCY.buckets, counts):
            cum += c
            if cum >= half:
                p50 = b
                break
        out[key[0]] = p50 * 1e3
    return out


# -- deploy-time warmup orchestration ----------------------------------------


class AOTWarmup:
    """Compiles the deployed engine's serving programs for every ladder
    bucket, tracking progress for ``/health``.

    States: ``idle`` (never started) → ``warming`` → ``ready`` |
    ``failed``. A deploy with AOT enabled reports ``not-ready`` until
    ``ready``; a reload warms the CANDIDATE through :meth:`warm_sync`
    before the swap, so the post-swap first query runs a precompiled
    bucket executable.

    Algorithms opt in by implementing ``aot_warm(model, ladder, ks)``
    → dict with ``compiled``/``cached`` counts (duck-typed — see
    ``controller/components.Algorithm.aot_warm``). Engines whose
    algorithms serve host-side (no device program) warm instantly.
    """

    def __init__(self, ladder: BucketLadder,
                 ks: Sequence[int] = (16,)) -> None:
        self.ladder = ladder
        self.ks = tuple(ks)
        self.state = "idle"
        self.error: Optional[str] = None
        self.compiled = 0
        self.cached = 0
        self.total_targets = 0
        self.wall_sec = 0.0
        self._started_at = 0.0
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._m_state = REGISTRY.gauge(
            "pio_aot_warmup_ready",
            "1 once the serving bucket ladder is fully compiled")
        self._m_state.set(0)
        self._m_warm_s = REGISTRY.gauge(
            "pio_aot_warmup_seconds", "Wall time of the last warmup pass")

    # -- sync core ----------------------------------------------------------

    def warm_sync(self, deployed: Any) -> Dict[str, Any]:
        """Warm every algorithm of ``deployed`` across the ladder; runs
        in the caller's thread (deploy startup uses :meth:`start`; the
        reload path calls this directly pre-swap). Raises on failure —
        a candidate whose serving program will not compile must never
        be swapped live."""
        from predictionio_tpu.utils import tracing

        t0 = time.perf_counter()
        with self._lock:
            self._started_at = t0
        compiled = cached = targets = 0
        with tracing.span("serving.aot_warmup",
                          buckets=len(self.ladder), ks=len(self.ks)):
            for name, algo in getattr(deployed, "algorithms", []):
                model = deployed.models[
                    [n for n, _ in deployed.algorithms].index(name)]
                hook = getattr(algo, "aot_warm", None)
                if hook is None:
                    continue
                stats = hook(model, self.ladder, self.ks) or {}
                compiled += int(stats.get("compiled", 0))
                cached += int(stats.get("cached", 0))
                targets += int(stats.get("targets", 0))
        wall = time.perf_counter() - t0
        with self._lock:
            self.compiled, self.cached = compiled, cached
            self.total_targets = targets
            self.wall_sec = wall
        self._m_warm_s.set(wall)
        return {"compiled": compiled, "cached": cached,
                "targets": targets, "wall_sec": wall}

    # -- background lifecycle -----------------------------------------------

    def start(self, deployed: Any) -> None:
        """Kick off (or restart) the deploy-time warmup in a daemon
        thread; ``/health`` turns ``ready`` when it completes."""
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self.state = "warming"
            self.error = None
            self._m_state.set(0)
            self._thread = threading.Thread(
                target=self._run, args=(deployed,),
                name="pio-aot-warmup", daemon=True)
            self._thread.start()

    def _run(self, deployed: Any) -> None:
        try:
            self.warm_sync(deployed)
        except Exception as e:  # noqa: BLE001 — surfaced via /health
            with self._lock:
                self.state = "failed"
                self.error = f"{type(e).__name__}: {e}"
            return
        with self._lock:
            self.state = "ready"
        self._m_state.set(1)

    def mark_ready(self) -> None:
        """Record a successful synchronous warm (the /reload pre-swap
        path calls :meth:`warm_sync` directly, with no background
        thread to flip the state)."""
        with self._lock:
            self.state = "ready"
        self._m_state.set(1)

    def wait(self, timeout: Optional[float] = None) -> bool:
        t = self._thread
        if t is not None:
            t.join(timeout)
        return self.state in ("ready", "failed")

    @property
    def ready(self) -> bool:
        return self.state == "ready"

    def retry_after(self) -> float:
        """Seconds a not-ready client should wait before re-probing:
        the last pass's wall time minus what has already elapsed of the
        current one (floored at 0.5 s so pollers don't spin), or the
        full estimate when no pass is in flight. 0 once settled."""
        with self._lock:
            if self.state in ("ready", "failed"):
                return 0.0
            est = self.wall_sec if self.wall_sec > 0 else 5.0
            if self.state == "warming" and self._started_at > 0:
                elapsed = time.perf_counter() - self._started_at
                return max(0.5, est - elapsed)
            return est

    def progress(self) -> Dict[str, Any]:
        """The ``/health`` warmup block."""
        with self._lock:
            return {
                "state": self.state,
                "buckets": list(self.ladder.buckets),
                "ks": list(self.ks),
                "compiled": self.compiled,
                "cached": self.cached,
                "targets": self.total_targets,
                "wallSec": round(self.wall_sec, 3),
                **({"error": self.error} if self.error else {}),
            }

    def release(self) -> None:
        """Drop the warmup thread reference (server shutdown). The
        process-wide :data:`EXECUTABLES` cache intentionally survives —
        a supervisor-restarted server in the same process re-warms from
        it for free."""
        with self._lock:
            self._thread = None
            self.state = "idle"
            self._m_state.set(0)
