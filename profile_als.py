"""Profile / A-B the ALS training program on the real chip.

Modes:

- default: run the ML-20M-shape train (bench.py protocol), print phase
  timings, and capture a JAX profiler trace of a short warm run.
- ``--ab``: run the optimization matrix and print one line per
  configuration — the decision data for flipping defaults:
    * baseline (materialized solve pass, XLA recursion, f32 gathers)
    * PIO_PALLAS_GRAM=1 (fused gather→Gram Pallas kernel)
    * PIO_PALLAS_SOLVE=1 (VMEM-resident Pallas solve kernel)
    * in-body solves (no solve-buffer materialization)
    * bf16 gathers
- ``--opcount``: CHIP-FREE — trace the TPU train program abstractly
  and report device ops/iteration for the XLA vs fused gather→Gram
  paths (the r5 dispatch-wall metric), then assert the ≥10× collapse
  regression guard. Runs on any host; no accelerator touched.
"""

import argparse
import glob
import os
import time

import numpy as np


def _measure(prep, params, label):
    from predictionio_tpu.models import als
    import jax

    from bench import _train_flops, device_peaks

    peak = device_peaks(jax.devices()[0].device_kind)["bf16_flops"]

    als._compiled_bucketed.cache_clear()
    t0 = time.perf_counter()
    U, V = als.als_train_prepared(prep, params)
    t_cold = time.perf_counter() - t0
    warms = []
    for _ in range(2):
        t0 = time.perf_counter()
        U, V = als.als_train_prepared(prep, params)
        warms.append(time.perf_counter() - t0)
    t_warm = min(warms)
    assert np.isfinite(U).all() and np.isfinite(V).all()
    flops = _train_flops(prep, params.rank, params.iterations)
    thr = prep.nnz * params.iterations / t_warm / 1e6
    print(f"{label:34} cold={t_cold:7.1f}s warm={t_warm:6.2f}s "
          f"thr={thr:7.1f}M/s mfu_wall={flops / t_warm / peak:.4f}",
          flush=True)
    return t_warm


def _measure_device(prep, params, label, repeats=3):
    """Device-side warm time: run the compiled train and fetch ONE
    scalar (U.sum()+V.sum()) instead of the 42 MB factor output, so
    the fetch stays out of the timing."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu import ops
    from predictionio_tpu.models import als

    u_bufs, i_bufs = prep.device_buffers()
    train = als._compiled_bucketed(
        prep.u_side.geometry, prep.i_side.geometry,
        prep.n_users, prep.n_items, params.rank, params.iterations,
        bool(params.implicit), bool(params.weighted_reg),
        None, bool(params.bf16_gather), als._gram_precision(),
        ops.resolve_gram_mode(jax.default_backend()))
    V0 = jnp.asarray(
        als.init_factors(prep.n_items, params.rank, params.seed)[
            prep.i_side.perm])
    reg = np.float32(params.reg)
    alpha = np.float32(params.alpha)

    def once():
        t0 = time.perf_counter()
        U, V = train(u_bufs, i_bufs, V0, reg, alpha)
        s = float(jnp.sum(U) + jnp.sum(V))   # 4-byte fetch forces exec
        return time.perf_counter() - t0, s

    t_cold, s = once()
    assert np.isfinite(s), label
    t_dev = min(once()[0] for _ in range(repeats))
    thr = prep.nnz * params.iterations / t_dev / 1e6
    print(f"{label:44} cold={t_cold:7.1f}s dev={t_dev:6.2f}s "
          f"thr_dev={thr:7.1f}M/s", flush=True)
    return t_dev


def _tune(args):
    """On-device A/B of the layout/solve knobs the r5 trace flagged:
    the chunked solve pass (41 chunks x ~50 small ops each) and the
    gather slab size. Prints one line per configuration; the winner
    becomes the default."""
    from bench import synthetic_ml20m
    from predictionio_tpu.models import als
    from predictionio_tpu.models.als import ALSParams, RatingsCOO
    from predictionio_tpu.utils import compilecache

    compilecache.enable()
    users, items, ratings = synthetic_ml20m(args.nnz)
    coo = RatingsCOO(users, items, ratings, 138_493, 26_744)
    params = ALSParams(rank=args.rank, iterations=args.iters, reg=0.05,
                       seed=1)

    chunks = [int(c) for c in args.chunks.split(",")] if args.chunks else []
    slabs = [int(s) for s in args.slabs.split(",")] if args.slabs else []
    entry_chunk, entry_slab = als._SOLVE_CHUNK, als._SLAB_ELEMS
    preps = {}

    def prep_for(slab):
        if slab not in preps:
            als._SLAB_ELEMS = slab
            preps[slab] = als.als_prepare(coo)
        return preps[slab]

    base_slab = als._SLAB_ELEMS
    for chunk in chunks or [als._SOLVE_CHUNK]:
        for slab in slabs or [base_slab]:
            als._SOLVE_CHUNK = chunk
            als._compiled_bucketed.cache_clear()
            try:
                _measure_device(prep_for(slab), params,
                                f"chunk={chunk} slab={slab}")
            except Exception as exc:  # OOM etc: report, keep going
                print(f"chunk={chunk} slab={slab}: {type(exc).__name__}: "
                      f"{str(exc)[:120]}", flush=True)
    # restore the values in effect at entry (not re-spelled literals,
    # which would silently revert a future default change — r5 review)
    als._SOLVE_CHUNK, als._SLAB_ELEMS = entry_chunk, entry_slab


def _sharded_ckpt_overhead(args):
    """Per-boundary cost of block-wise checkpointing on the sharded
    path: straight fused run vs checkpoint_every=1 (one boundary per
    iteration — worst case). Runs on a virtual 8-device CPU mesh so it
    works chip-free; the number to record is (ckpt - straight)/nblocks.
    """
    import tempfile

    import jax

    jax.config.update("jax_platforms", "cpu")
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

    # the 8-device shard_map programs take minutes of XLA-CPU compile
    # on a 1-core box; persist them so repeat measurements pay once
    from predictionio_tpu.utils import compilecache

    compilecache.enable()

    from jax.sharding import Mesh

    from bench import synthetic_ml20m
    from predictionio_tpu.models.als import ALSParams, RatingsCOO
    from predictionio_tpu.models.als_sharded import (
        als_prepare_sharded, als_train_sharded_prepared)
    from predictionio_tpu.utils.checkpoint import TrainCheckpointer

    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))
    n_dev = int(np.prod(mesh.devices.shape))
    users, items, ratings = synthetic_ml20m(args.nnz)
    coo = RatingsCOO(users, items, ratings, 138_493, 26_744)
    prep = als_prepare_sharded(coo, n_dev)
    p = ALSParams(rank=args.rank, iterations=args.iters, reg=0.05, seed=1)

    def run(ck=None, every=0):
        t0 = time.perf_counter()
        als_train_sharded_prepared(prep, p, mesh,
                                   checkpointer=ck, checkpoint_every=every)
        return time.perf_counter() - t0

    run()  # compile
    straight = min(run() for _ in range(2))
    with tempfile.TemporaryDirectory() as td:
        with TrainCheckpointer(os.path.join(td, "a")) as ck:
            run(ck, 1)  # compile the 1-iter block program
        times = []
        for sub in ("b", "c"):
            with TrainCheckpointer(os.path.join(td, sub)) as ck:
                times.append(run(ck, 1))
    ckpt = min(times)
    per = (ckpt - straight) / p.iterations * 1000
    print(f"sharded nnz={coo.nnz} rank={p.rank} iters={p.iterations} "
          f"mesh={n_dev}dev", flush=True)
    print(f"straight={straight:.2f}s blockwise(every=1)={ckpt:.2f}s "
          f"per_boundary_overhead={per:.1f}ms", flush=True)


def _opcount(args):
    """Chip-free dispatch-count report + regression guard.

    Traces the TPU train program abstractly (ShapeDtypeStructs, no
    device buffers) on the CPU host and counts device ops/iteration
    for the XLA path vs the fused gather→Gram path. Exits non-zero if
    the collapse ratio falls below the ISSUE-17 acceptance floor of
    10× — this is the device-ops-count regression guard, cheap enough
    for CI.
    """
    import sys

    import jax

    jax.config.update("jax_platforms", "cpu")

    from bench import synthetic_ml20m
    from predictionio_tpu.models.als import ALSParams, RatingsCOO, als_prepare
    from predictionio_tpu.utils import opcount

    users, items, ratings = synthetic_ml20m(args.nnz)
    coo = RatingsCOO(users, items, ratings, 138_493, 26_744)
    prep = als_prepare(coo)
    params = ALSParams(rank=args.rank, iterations=args.iters, reg=0.05,
                       seed=1)
    rep = opcount.als_dispatch_report(prep, params)
    print(f"nnz={coo.nnz} rank={params.rank} "
          f"geometry: u={[(b.C, b.nb) for b in prep.u_side.buckets]} "
          f"i={[(b.C, b.nb) for b in prep.i_side.buckets]}", flush=True)
    print(f"device_ops_per_iter_xla   = {rep['device_ops_per_iter_xla']}",
          flush=True)
    print(f"device_ops_per_iter_fused = {rep['device_ops_per_iter']}",
          flush=True)
    print(f"dispatch_collapse_ratio   = "
          f"{rep['dispatch_collapse_ratio']:.1f}x", flush=True)
    if rep["dispatch_collapse_ratio"] < 10:
        print("FAIL: dispatch collapse below the 10x acceptance floor",
              flush=True)
        sys.exit(1)
    print("OK: collapse >= 10x", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nnz", type=int, default=None,
                    help="ratings count (default 20M; 400k under "
                         "--sharded-ckpt, which runs on CPU)")
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--ab", action="store_true",
                    help="run the optimization A/B matrix")
    ap.add_argument("--trace-dir", default="/tmp/als_trace")
    ap.add_argument("--trace-iters", type=int, default=2)
    ap.add_argument("--tune", action="store_true",
                    help="on-device A/B of solve-chunk / slab-size "
                         "knobs (device-side timing, scalar fetch)")
    ap.add_argument("--chunks", default="",
                    help="comma list of PIO_ALS_SOLVE_CHUNK values "
                         "for --tune (default: current)")
    ap.add_argument("--slabs", default="",
                    help="comma list of PIO_ALS_SLAB_ELEMS values "
                         "for --tune (default: current)")
    ap.add_argument("--sharded-ckpt", action="store_true",
                    help="measure the per-boundary overhead of "
                         "block-wise checkpointing on the sharded "
                         "trainer (8-device CPU mesh)")
    ap.add_argument("--opcount", action="store_true",
                    help="chip-free device-ops/iter report (XLA vs "
                         "fused gather-Gram) + >=10x collapse guard")
    args = ap.parse_args()

    if args.sharded_ckpt:
        if args.nnz is None:
            args.nnz = 400_000  # CPU-mesh measurement, not TPU scale
        _sharded_ckpt_overhead(args)
        return
    if args.opcount:
        if args.nnz is None:
            args.nnz = 500_000  # abstract trace: geometry, not scale
        _opcount(args)
        return
    if args.nnz is None:
        args.nnz = 20_000_000

    # every mode below is a CHIP measurement: abort (don't mislabel)
    # if the backend silently fell back to CPU (r5 review)
    from profile_common import resolve_platform

    resolve_platform("")

    if args.tune:
        _tune(args)
        return

    from bench import synthetic_ml20m
    from predictionio_tpu.models import als
    from predictionio_tpu.models.als import (ALSParams, RatingsCOO,
                                             als_prepare,
                                             als_train_prepared)
    from predictionio_tpu.utils import compilecache

    compilecache.enable()

    users, items, ratings = synthetic_ml20m(args.nnz)
    coo = RatingsCOO(users, items, ratings, 138_493, 26_744)
    t0 = time.perf_counter()
    prep = als_prepare(coo)
    print(f"prepare_sec={time.perf_counter() - t0:.3f}", flush=True)
    for side, nm in ((prep.u_side, "u"), (prep.i_side, "i")):
        print(f"  {nm}: dense nb={side.dense.nb if side.dense else 0} "
              f"buckets={[(b.C, b.nb) for b in side.buckets]}", flush=True)

    params = ALSParams(rank=args.rank, iterations=args.iters, reg=0.05,
                       seed=1)

    if args.ab:
        os.environ["PIO_PALLAS_GRAM"] = "0"
        _measure(prep, params, "baseline (materialized, XLA solve)")
        os.environ["PIO_PALLAS_GRAM"] = "1"
        _measure(prep, params, "fused gather-Gram (pallas)")
        os.environ["PIO_PALLAS_GRAM"] = "0"
        os.environ["PIO_PALLAS_SOLVE"] = "1"
        _measure(prep, params, "pallas VMEM solve")
        del os.environ["PIO_PALLAS_SOLVE"]
        del os.environ["PIO_PALLAS_GRAM"]
        saved = als._SOLVE_BUF_MB
        als._SOLVE_BUF_MB = 0
        _measure(prep, params, "in-body solves (no solve buffer)")
        os.environ["PIO_PALLAS_SOLVE"] = "1"
        _measure(prep, params, "in-body + pallas solve")
        del os.environ["PIO_PALLAS_SOLVE"]
        als._SOLVE_BUF_MB = saved
        p16 = ALSParams(rank=args.rank, iterations=args.iters, reg=0.05,
                        seed=1, bf16_gather=True)
        _measure(prep, p16, "bf16 gathers")
        os.environ["PIO_PALLAS_SOLVE"] = "1"
        _measure(prep, p16, "bf16 gathers + pallas solve")
        del os.environ["PIO_PALLAS_SOLVE"]
        return

    t0 = time.perf_counter()
    U, V = als_train_prepared(prep, params)
    print(f"train_sec_incl_compile={time.perf_counter() - t0:.3f}",
          flush=True)
    _measure(prep, params, "warm")

    import jax

    tparams = ALSParams(rank=args.rank, iterations=args.trace_iters,
                        reg=0.05, seed=1)
    als_train_prepared(prep, tparams)  # compile outside the trace
    os.makedirs(args.trace_dir, exist_ok=True)
    with jax.profiler.trace(args.trace_dir):
        als_train_prepared(prep, tparams)
    print(f"trace written to {args.trace_dir}", flush=True)
    for f in glob.glob(os.path.join(args.trace_dir, "**", "*"),
                       recursive=True):
        if os.path.isfile(f):
            print("  ", f, os.path.getsize(f), flush=True)


if __name__ == "__main__":
    main()
