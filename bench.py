"""Benchmark: ALS training throughput, MovieLens-20M-scale (driver metric).

Protocol (BASELINE.md): throughput = ratings × iterations / train
wall-clock (excluding event-store read / data prep — layout construction
is :func:`als_prepare`, MLlib-InBlock-equivalent, done once per dataset)
/ chips. Rank 64, 10 iterations, f32 solves. The reference (Apache
PredictionIO on Spark/MLlib) publishes no numbers and the environment
has no egress to fetch ML-20M, so the dataset is a synthetic clone of
its shape: 138,493 users × 26,744 items × 20M ratings, power-law degree
distribution, ratings in {0.5 … 5.0}. First measured run established
the baseline (see BENCH_BASELINE.json).

Also reported (VERDICT r1 asks):
- ``mfu`` / ``hbm_gbps``: progress measured against hardware rooflines
  (model flops / peak bf16; modeled HBM bytes / wall-clock), not against
  last round's self-baseline.
- ``predict_p50_device_ms``: device-program latency of the serving
  score→top-k dispatch, measured by chaining N dependent executions of
  the compiled program on device inside one fetch.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Flags: --quick (1/20 size, CI smoke), --rank, --iters, --nnz.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

BASELINE_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_BASELINE.json")

#: published peaks per chip, keyed by ``jax.devices()[0].device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 819 GB/s HBM). A device that is not in the table is an error, not a
#: default: an MFU against some other chip's peak is not a number.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bps": 819e9},
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"bench.py has no published peaks for device kind "
            f"{device_kind!r} (known: {sorted(DEVICE_PEAKS)}); refusing "
            "to report a utilization against another chip's peak")


def synthetic_ml20m(nnz: int, n_users: int = 138_493, n_items: int = 26_744,
                    seed: int = 7):
    """Power-law user/item popularity, Zipf-ish, like MovieLens."""
    rng = np.random.default_rng(seed)
    u_pop = rng.zipf(1.35, size=nnz * 2) % n_users
    i_pop = rng.zipf(1.25, size=nnz * 2) % n_items
    users = u_pop[:nnz].astype(np.int32)
    items = i_pop[:nnz].astype(np.int32)
    ratings = (rng.integers(1, 11, size=nnz) * 0.5).astype(np.float32)
    return users, items, ratings


def _train_flops(prep, rank: int, iterations: int) -> float:
    """Executed FLOPs: batched weighted Gram + rhs per padded rating
    slot, the dense-head GEMMs (weight rows × factor outer products),
    plus the per-entity Cholesky factor/inverse/apply."""
    k = rank
    padded = sum(b.n_slabs * b.slab * b.C
                 for side in (prep.u_side, prep.i_side)
                 for b in side.buckets)
    gram = 2.0 * padded * k * (k + 2)          # A (k×(k+1)) + b (k) builds
    dense = sum(2.0 * side.dense.nb * side.dense.n_other * k * (k + 1)
                + side.dense.n_other * k * k    # FF outer products
                for side in (prep.u_side, prep.i_side)
                if side.dense is not None)
    solves = (prep.n_users + prep.n_items) * (2 * k**3 / 3 + 4 * k**2)
    return iterations * (gram + dense + solves)


def _train_bytes(prep, rank: int, iterations: int) -> float:
    """Modeled HBM traffic: the factor gather (k·4 bytes per padded
    rating slot) + layout operands, the dense-head weight rows + FF
    write/read, and factor writes."""
    k = rank
    padded = sum(b.n_slabs * b.slab * b.C
                 for side in (prep.u_side, prep.i_side)
                 for b in side.buckets)
    dense = sum(side.dense.nb * side.dense.n_other * 8      # w_cnt+w_val
                + 2 * side.dense.n_other * k * k * 4        # FF w+r
                for side in (prep.u_side, prep.i_side)
                if side.dense is not None)
    per_iter = (padded * (k * 4 + 12) + dense
                + (prep.n_users + prep.n_items) * k * 4)
    return iterations * float(per_iter)


def _device_predict_latency(scorer, n_users: int, iters: int = 200) -> float:
    """Steady-state device latency (ms) of the serving score→top-k
    program: chain ``iters`` dependent executions on device, one fetch."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models.als import _gather_score_topk_impl

    k = 16
    n_valid = scorer.n_items

    def chained(U, Vp, uid, n):
        def body(_, uid):
            packed = _gather_score_topk_impl(
                U, Vp, uid, k=k, n_valid=n_valid, pallas=False,
                tile=scorer._TILE)
            # feed top item id back in as the next user id → dependency
            return (packed[:, k].astype(jnp.int32) % n_users)

        return jax.lax.fori_loop(0, n, body, uid)

    f = jax.jit(chained, static_argnames=("n",))
    uid = jnp.asarray([0], jnp.int32)
    # warm BOTH static-n variants (each is its own compile cache entry)
    np.asarray(f(scorer._U, scorer._V_padded, uid, 1))
    np.asarray(f(scorer._U, scorer._V_padded, uid, iters))
    t0 = time.perf_counter()
    np.asarray(f(scorer._U, scorer._V_padded, uid, 1))
    t_one = time.perf_counter() - t0
    t0 = time.perf_counter()
    np.asarray(f(scorer._U, scorer._V_padded, uid, iters))
    t_many = time.perf_counter() - t0
    return max(t_many - t_one, 0.0) / (iters - 1) * 1e3


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--nnz", type=int, default=20_000_000)
    args = ap.parse_args()

    from predictionio_tpu.models.als import (ALSParams, RatingsCOO,
                                             als_prepare, als_train_prepared)
    from predictionio_tpu.utils import compilecache

    xla_cache = compilecache.enable()

    import jax
    import jax.numpy as jnp

    peaks = device_peaks(jax.devices()[0].device_kind)

    nnz = args.nnz // 20 if args.quick else args.nnz
    n_users = 138_493 // (20 if args.quick else 1)
    n_items = 26_744 // (4 if args.quick else 1)
    users, items, ratings = synthetic_ml20m(nnz, n_users, n_items)
    coo = RatingsCOO(users, items, ratings, n_users, n_items)
    params = ALSParams(rank=args.rank, iterations=args.iters, reg=0.05, seed=1)

    import jax

    n_chips = 1  # single-chip bench; the sharded path covers multi
    t0 = time.perf_counter()
    prep = als_prepare(coo)
    t_prep = time.perf_counter() - t0

    t0 = time.perf_counter()
    U, V = als_train_prepared(prep, params)   # includes compile + h2d
    t_total = time.perf_counter() - t0

    # warm run: pure execute (compile cached, layout resident on device)
    t1 = time.perf_counter()
    U, V = als_train_prepared(prep, params)
    t_exec = time.perf_counter() - t1

    # measure the device→host transfer of the factors alone (a
    # same-size dummy fetch), so device execution time can be reported
    # alongside the wall time
    import jax
    import jax.numpy as jnp

    dummy = jnp.zeros(((prep.n_users + prep.n_items), args.rank),
                      jnp.float32) + 1.0
    np.asarray(dummy * 1.0)  # warm the transfer path
    t2 = time.perf_counter()
    np.asarray(dummy * 2.0)
    t_d2h = time.perf_counter() - t2
    t_dev = max(t_exec - t_d2h, 1e-9)

    assert np.isfinite(U).all() and np.isfinite(V).all()
    throughput = (coo.nnz * args.iters) / t_exec / n_chips
    flops = _train_flops(prep, args.rank, args.iters)
    mfu = flops / t_exec / (peaks["bf16_flops"] * n_chips)
    mfu_device = flops / t_dev / (peaks["bf16_flops"] * n_chips)
    hbm_gbps = _train_bytes(prep, args.rank, args.iters) / t_dev / 1e9

    # dispatch accounting (chip-free abstract trace, utils/opcount): the
    # r5 wall was device-op COUNT, not FLOPs, so the bench emits it as a
    # first-class metric next to mfu_device — both paths counted even
    # when only one actually ran on this chip
    from predictionio_tpu import ops as ops_mod
    from predictionio_tpu.utils import opcount as opcount_mod

    dispatch_rep = opcount_mod.als_dispatch_report(prep, params)
    gram_mode = ops_mod.resolve_gram_mode(jax.default_backend())

    # r4 grid contract on hardware: 3 extra reg candidates on the SAME
    # prep must pay ZERO compiles (reg is a traced scalar) — wall time
    # ≈ 3 × train_sec_warm. Measured here so the BENCH file carries the
    # proof without a separate harness run.
    from predictionio_tpu.models import als as als_mod

    grid_info = als_mod._compiled_bucketed.cache_info()
    t3 = time.perf_counter()
    for reg in (0.01, 0.1, 1.0):
        als_train_prepared(prep, ALSParams(
            rank=args.rank, iterations=args.iters, reg=reg, seed=1))
    t_grid3 = time.perf_counter() - t3
    grid_compiles = (als_mod._compiled_bucketed.cache_info().misses
                     - grid_info.misses)

    # second driver metric (BASELINE.md): predict p50, recommendation
    # top-10 from the resident model — the engine-server hot path minus
    # HTTP framing. Sequential single-query calls, warm.
    from predictionio_tpu.models.als import ResidentScorer

    scorer = ResidentScorer(U, V)
    rng = np.random.default_rng(3)
    n_queries = 1_000 if args.quick else 10_000
    qusers = rng.integers(0, n_users, n_queries + 100)
    for u in qusers[:100]:  # warm both compile and caches
        scorer.recommend_batch(np.asarray([u]), 10)
    lat = np.empty(n_queries)
    for i, u in enumerate(qusers[100:]):
        q0 = time.perf_counter()
        scorer.recommend_batch(np.asarray([u]), 10)
        lat[i] = time.perf_counter() - q0
    p50_ms = float(np.percentile(lat, 50) * 1e3)
    p99_ms = float(np.percentile(lat, 99) * 1e3)
    p50_dev_ms = _device_predict_latency(scorer, n_users)

    # AOT bucket flywheel (server/aot): warm the serving ladder the way
    # `pio deploy --aot-buckets auto` would, drive each bucket at its
    # real batch size, and report the per-bucket device-latency p50s
    # recorded by the pio_predict_device_seconds histogram. The compile
    # delta over the serving loop must be zero — any hot-path compile
    # is a warmup gap.
    from predictionio_tpu.server import aot as aot_mod

    def _jit_dispatches():
        # serving dispatches that did NOT run a precompiled executable —
        # each one is a potential on-path XLA compile (warmup gap)
        return sum(v for k, v in aot_mod._DISPATCHES._values.items()
                   if k[1] == "jit")

    ladder = aot_mod.BucketLadder.geometric(16 if args.quick else 64)
    scorer.warm_buckets(ladder, ks=(10,))
    gaps_before = _jit_dispatches()
    for B in ladder:
        users = rng.integers(0, n_users, size=B)
        for _ in range(20):
            scorer.recommend_batch(np.asarray(users, np.int32), 10)
    aot_gaps = _jit_dispatches() - gaps_before
    p50_by_bucket = aot_mod.device_p50_ms_by_bucket()

    # ANN flywheel (predictionio_tpu/ann): PQ-index the trained item
    # factors, warm the ANN ladder, and report recall@10 vs the exact
    # resident scorer plus the per-bucket ANN-vs-exact device p50 — the
    # PQ trade-off printed next to the exact numbers it trades against.
    from predictionio_tpu import ann as ann_mod

    ann_m = next(m for m in (8, 4, 2, 1) if args.rank % m == 0)
    ann_index = ann_mod.build_index(
        V, ann_m, 256, iters=4, sample=min(65536, n_items))
    ann_scorer = ann_mod.ANNScorer(U, V, ann_index, shortlist=128)
    ann_scorer.warm_buckets(ladder, ks=(10,))
    gaps_before = _jit_dispatches()
    ann_hits = ann_total = 0
    for B in ladder:
        busers = np.asarray(rng.integers(0, n_users, size=B), np.int32)
        for rep in range(5):
            er = scorer.recommend_batch(busers, 10)
            ar = ann_scorer.recommend_batch(busers, 10)
            if rep == 0:
                for (ei, _), (ai, _) in zip(er, ar):
                    ann_hits += np.intersect1d(ei, ai).size
                    ann_total += len(ei)
    ann_gaps = _jit_dispatches() - gaps_before
    ann_p50_by_bucket = aot_mod.device_p50_ms_by_bucket(path="ann")

    # Variant multiplexing flywheel (server/variants): two same-geometry
    # variants resident at once must share every executable. Preview the
    # 90/10 dispatch share with the exact assignment hash serving uses,
    # warm a challenger scorer (must be pure executable-cache hits),
    # and report each variant's single-query device-path p50.
    from predictionio_tpu.server.variants import weighted_assign

    arms = [("champion", 9.0), ("challenger", 1.0)]
    dispatch = {"champion": 0, "challenger": 0}
    for i in range(n_queries):
        dispatch[weighted_assign(str(i), arms)] += 1
    chal_scorer = ResidentScorer(U * 0.999, V)  # same geometry, new weights
    ex_before = aot_mod.EXECUTABLES.counts().get("compile", 0)
    chal_scorer.warm_buckets(ladder, ks=(10,))
    variant_warm_compiles = (aot_mod.EXECUTABLES.counts().get("compile", 0)
                             - ex_before)
    variant_p50 = {}
    m = 500 if args.quick else 2_000
    for vname, vscorer in (("champion", scorer), ("challenger", chal_scorer)):
        for u in qusers[:50]:
            vscorer.recommend_batch(np.asarray([u]), 10)
        vlat = np.empty(m)
        for i, u in enumerate(qusers[50:50 + m]):
            q0 = time.perf_counter()
            vscorer.recommend_batch(np.asarray([u]), 10)
            vlat[i] = time.perf_counter() - q0
        variant_p50[vname] = round(float(np.percentile(vlat, 50) * 1e3), 3)

    baseline = None
    if os.path.exists(BASELINE_FILE):
        try:
            with open(BASELINE_FILE) as f:
                baseline = json.load(f).get("value")
        except Exception:
            baseline = None
    vs = (throughput / baseline) if baseline else 1.0

    print(json.dumps({
        "metric": "als_train_throughput_ml20m_synthetic",
        "value": round(throughput, 1),
        "unit": "rating-updates/sec/chip (ratings x iters / train-sec / chips)",
        "vs_baseline": round(vs, 4),
        "detail": {
            "nnz": coo.nnz, "rank": args.rank, "iterations": args.iters,
            "n_users": n_users, "n_items": n_items,
            "train_sec_warm": round(t_exec, 3),
            "train_sec_incl_compile": round(t_total, 3),
            # first-class target (VERDICT r2 ask #2): the one-shot `pio
            # train` a user runs pays prepare+compile+train; compile_sec
            # is ~0 on a warm persistent cache (xla_cache_dir)
            "compile_sec": round(t_total - t_exec, 3),
            "cold_train_sec_end_to_end": round(t_prep + t_total, 3),
            "xla_cache_dir": xla_cache,
            "prepare_sec": round(t_prep, 3),
            "mfu": round(mfu, 4),
            # device-side accounting: train_sec_warm minus the measured
            # fetch of the 42MB factor output
            "train_sec_device": round(t_dev, 3),
            "d2h_fetch_sec": round(t_d2h, 3),
            "mfu_device": round(mfu_device, 4),
            "model_tflops": round(flops / 1e12, 2),
            "hbm_gbps": round(hbm_gbps, 1),
            # dispatch wall: device ops per iteration for the fused
            # gather→Gram path vs the XLA path (abstract jaxpr count,
            # utils/opcount) and the gram mode this run resolved to
            "device_ops_per_iter": dispatch_rep["device_ops_per_iter"],
            "device_ops_per_iter_xla":
                dispatch_rep["device_ops_per_iter_xla"],
            "dispatch_collapse_ratio":
                round(dispatch_rep["dispatch_collapse_ratio"], 1),
            "gram_mode": gram_mode,
            # reg-grid contract: 3 extra reg candidates on the same
            # prep; must show 0 extra compiles (traced scalars, r4)
            "grid_reg3_sec": round(t_grid3, 3),
            "grid_reg3_extra_compiles": int(grid_compiles),
            "predict_p50_ms": round(p50_ms, 3),
            "predict_p99_ms": round(p99_ms, 3),
            "predict_p50_device_ms": round(p50_dev_ms, 4),
            # per-bucket device p50 across the warmed AOT ladder
            # (histogram upper-bound estimate) + the zero-compile
            # contract over the bucketed serving loop
            "predict_p50_device_ms_by_bucket": p50_by_bucket,
            "aot_buckets": list(ladder.buckets),
            "aot_serving_jit_fallbacks": int(aot_gaps),
            # ANN retrieval: recall@10 of the PQ ADC+re-rank path vs
            # the exact scorer on the same query batches, and its
            # per-bucket device p50 (dispatch path="ann")
            "ann_recall_at_10": round(ann_hits / max(ann_total, 1), 4),
            "ann_p50_device_ms_by_bucket": ann_p50_by_bucket,
            "ann_serving_jit_fallbacks": int(ann_gaps),
            "ann_index_build_sec": ann_index.meta.get("build_sec"),
            # variant multiplexing: the 90/10 dispatch share the sticky
            # hash actually produces over n_queries distinct entities,
            # each resident variant's device-path p50, and the compile
            # cost of making the second variant resident (must be 0 —
            # same geometry ⇒ pure executable-cache adoption)
            "variant_dispatch_share": {
                k: round(v / n_queries, 4) for k, v in dispatch.items()},
            "variant_device_p50_ms": variant_p50,
            "variant_warm_extra_compiles": int(variant_warm_compiles),
            "predict_queries": n_queries,
            # predict_p50_ms is host clock around one query;
            # predict_p50_device_ms is the on-device program latency
            # (chained dependent executions, one fetch)
            # layout knobs in effect (r5: slab default 2^20 after the
            # on-device dispatch-granularity A/B — docs/perf.md)
            "slab_elems": als_mod._SLAB_ELEMS,
            "solve_chunk": als_mod._SOLVE_CHUNK,
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
        },
    }))


if __name__ == "__main__":
    main()
