"""Profile two-tower retrieval training on the real chip.

ALS is gather-bound (the r5 trace: MXU ~3% occupied, the program
latency-bound); the two-tower trainer is the framework's dense-matmul
workload — in-batch sampled softmax is a (B, D) x (D, B) logits matmul
plus MLP towers, so it shows what the framework achieves when the
FLOPs actually exist. Measures a warm training epoch device-side (the
epoch program already returns a scalar mean loss — fetching it forces
execution without a bulk device→host fetch) and reports
pairs/s + model FLOPs utilization.

Run: ``python profile_twotower.py`` (defaults: 20M synthetic ML-20M
pairs, embed 64, hidden [128], out 64, batch 8192, bf16 off — the
towers train in f32; XLA runs the matmuls on the MXU either way).

``--ann`` switches to the retrieval acceptance harness instead: build a
product-quantized index over a synthetic clustered corpus (default 1M
items), serve the same query stream through the exact resident scorer
and the fused ADC scorer, and emit ONE JSON line with recall@10 vs
exact, per-query device p50 for both paths, and the
zero-compile-after-warmup audit (docs/perf.md "Approximate retrieval").
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def _tower_flops_per_pair(embed_dim: int, hidden, out_dim: int,
                          batch: int) -> float:
    """fwd+bwd model FLOPs per training pair (both towers + logits).

    Dense layers: 2*m*n FLOPs fwd per example, x3 for fwd+bwd. The
    in-batch logits matmul is (B, D) x (D, B): 2*B*D per example fwd,
    x3 bwd. Embedding lookups are gathers, not FLOPs.
    """
    dims = [embed_dim] + list(hidden) + [out_dim]
    mlp = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    per_tower = 3 * mlp
    logits = 3 * 2 * batch * out_dim
    return 2 * per_tower + logits


def _run_ann(args, jax) -> None:
    """``--ann`` acceptance harness (see module doc). Progress goes to
    stderr; stdout carries exactly one JSON result line."""
    import json

    from predictionio_tpu import ann
    from predictionio_tpu.models.als import ResidentScorer
    from predictionio_tpu.server import aot as aot_mod

    n, d, B = args.ann_items, args.ann_dim, args.batch
    nq = max(B, (args.ann_queries // B) * B)
    rng = np.random.default_rng(7)

    # clustered unit-norm corpus — recall@k is only a meaningful metric
    # when the corpus has neighborhood structure for the coarse ADC
    # scan to find; queries are perturbed corpus rows. Cluster size
    # ~n/centers stays near the shortlist so top-10 neighborhoods are
    # recoverable at the default k' (the real-corpus knob is --ann-shortlist)
    n_centers = min(16384, max(16, n // 128))
    centers = rng.standard_normal((n_centers, d), dtype=np.float32)
    V = (centers[rng.integers(0, n_centers, size=n)]
         + 0.25 * rng.standard_normal((n, d), dtype=np.float32))
    V /= np.linalg.norm(V, axis=1, keepdims=True) + 1e-9
    U = (V[rng.integers(0, n, size=nq)]
         + 0.1 * rng.standard_normal((nq, d), dtype=np.float32))
    U /= np.linalg.norm(U, axis=1, keepdims=True) + 1e-9
    print(f"corpus n={n} d={d} queries={nq} bucket={B}",
          file=sys.stderr, flush=True)

    index = ann.build_index(V, args.ann_m, args.ann_k,
                            iters=args.ann_iters,
                            sample=min(args.ann_sample, n))
    print(f"index built: m={index.m} k={index.k} "
          f"build_sec={index.meta['build_sec']}",
          file=sys.stderr, flush=True)

    exact = ResidentScorer(U, V)
    approx = ann.ANNScorer(U, V, index, shortlist=args.ann_shortlist)
    ladder = aot_mod.BucketLadder([B])
    exact.warm_buckets(ladder, ks=(10,))
    approx.warm_buckets(ladder, ks=(10,))

    sharded = shard1_parity = None
    if getattr(args, "shards", 0) and args.shards > 1:
        # the mesh-sharded serving path under the SAME acceptance
        # harness: recall vs exact, device p50, zero-compile audit —
        # plus a shards=1 scorer asserted BITWISE equal to the
        # single-device ANN program (degenerate collectives must not
        # perturb a single result bit)
        sharded = ann.ShardedANNScorer(U, V, index,
                                       shortlist=args.ann_shortlist,
                                       shards=args.shards)
        sharded.warm_buckets(ladder, ks=(10,))
        s1 = ann.ShardedANNScorer(U, V, index,
                                  shortlist=args.ann_shortlist, shards=1)
        s1.warm_buckets(ladder, ks=(10,))
        pids = np.arange(B, dtype=np.int32)
        bv, bi = approx._topk(pids, 10)
        sv, si = s1._topk(pids, 10)
        shard1_parity = bool(np.array_equal(bv, sv)
                             and np.array_equal(bi, si))
        print(f"sharded mesh: {sharded.shards}x{sharded.local_n} rows, "
              f"shard1_parity={shard1_parity}", file=sys.stderr,
              flush=True)

    def jit_gaps():
        return sum(v for key, v in aot_mod._DISPATCHES._values.items()
                   if key[1] == "jit")

    # one unmeasured dispatch per path past warmup (first-touch layout)
    exact.recommend_batch(np.arange(B, dtype=np.int32), 10)
    approx.recommend_batch(np.arange(B, dtype=np.int32), 10)
    if sharded is not None:
        sharded.recommend_batch(np.arange(B, dtype=np.int32), 10)

    compiles0 = aot_mod.EXECUTABLES.counts().get("compile", 0)
    gaps0 = jit_gaps()
    hits = sharded_hits = 0
    exact_lat, ann_lat, sharded_lat = [], [], []
    for rep in range(args.repeats):
        for s in range(0, nq, B):
            uids = np.arange(s, s + B, dtype=np.int32)
            t0 = time.perf_counter()
            er = exact.recommend_batch(uids, 10)
            exact_lat.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            ar = approx.recommend_batch(uids, 10)
            ann_lat.append(time.perf_counter() - t0)
            if sharded is not None:
                t0 = time.perf_counter()
                sr = sharded.recommend_batch(uids, 10)
                sharded_lat.append(time.perf_counter() - t0)
            if rep == 0:
                for (ei, _), (ai, _) in zip(er, ar):
                    hits += np.intersect1d(ei, ai).size
                if sharded is not None:
                    for (ei, _), (si_, _) in zip(er, sr):
                        sharded_hits += np.intersect1d(ei, si_).size
    # any compile (AOT cache miss OR jit-path dispatch) during the
    # serving sweep is a warmup gap — the acceptance bar is zero
    compiles = ((aot_mod.EXECUTABLES.counts().get("compile", 0)
                 - compiles0) + (jit_gaps() - gaps0))
    # wall p50 around the dispatch+fetch — on the CPU proxy this IS the
    # device-program latency; the pio_predict_device_seconds histogram
    # p50s are also reported but their geometric buckets are coarse
    exact_p50 = float(np.percentile(exact_lat, 50)) * 1e3
    ann_p50 = float(np.percentile(ann_lat, 50)) * 1e3
    sharded_fields = {}
    if sharded is not None:
        sharded_p50 = float(np.percentile(sharded_lat, 50)) * 1e3
        sharded_fields = {
            "shards": sharded.shards,
            "rows_per_shard": sharded.local_n,
            "sharded_recall_at_10": round(sharded_hits / (nq * 10), 4),
            "sharded_p50_device_ms": round(sharded_p50, 4),
            "shard1_parity": shard1_parity,
        }
    print(json.dumps({
        "metric": "ann_recall_latency",
        "recall_at_10": round(hits / (nq * 10), 4),
        **sharded_fields,
        "n_items": n, "dim": d, "m": index.m,
        "k_per_subspace": index.k, "shortlist": approx.shortlist,
        "queries": nq, "bucket": B, "repeats": args.repeats,
        "exact_p50_device_ms": round(exact_p50, 4),
        "ann_p50_device_ms": round(ann_p50, 4),
        "exact_per_query_p50_us": round(exact_p50 / B * 1e3, 2),
        "ann_per_query_p50_us": round(ann_p50 / B * 1e3, 2),
        "speedup_p50": round(exact_p50 / ann_p50, 3) if ann_p50 else None,
        "exact_p50_hist_ms": aot_mod.device_p50_ms_by_bucket().get(
            str(B), 0.0),
        "ann_p50_hist_ms": aot_mod.device_p50_ms_by_bucket(
            path="ann").get(str(B), 0.0),
        "serving_path_compiles": int(compiles),
        "index_build_sec": index.meta.get("build_sec"),
        "hbm_estimate_bytes": index.hbm_estimate_bytes(),
        "backend": jax.default_backend(),
    }), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairs", type=int, default=20_000_000)
    ap.add_argument("--embed", type=int, default=64)
    ap.add_argument("--hidden", default="128")
    ap.add_argument("--out", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--platform", default="",
                    help="jax platform override (cpu for a chip-free "
                         "smoke; default: jax's own choice)")
    ap.add_argument("--ann", action="store_true",
                    help="run the ANN retrieval acceptance harness "
                         "instead of the trainer profile (one JSON "
                         "line: recall@10, ANN-vs-exact device p50, "
                         "zero-compile audit); --batch becomes the "
                         "serving bucket (use e.g. --batch 64)")
    ap.add_argument("--ann-items", type=int, default=1_000_000)
    ap.add_argument("--ann-dim", type=int, default=64)
    ap.add_argument("--ann-m", type=int, default=8)
    ap.add_argument("--ann-k", type=int, default=256)
    ap.add_argument("--ann-shortlist", type=int, default=128)
    ap.add_argument("--ann-queries", type=int, default=1024)
    ap.add_argument("--ann-iters", type=int, default=4)
    ap.add_argument("--ann-sample", type=int, default=65536)
    ap.add_argument("--shards", type=int, default=0,
                    help="with --ann: also serve through the N-way "
                         "mesh-sharded scorer (N virtual CPU devices "
                         "when no multichip backend; implies "
                         "--platform cpu unless set) and assert "
                         "shards=1 bitwise parity")
    args = ap.parse_args()
    hidden = tuple(int(h) for h in args.hidden.split(",") if h)

    from profile_common import force_host_devices, resolve_platform

    if args.ann and args.shards and args.shards > 1:
        # XLA reads the virtual-device-count flag at backend init —
        # must precede the first jax import (resolve_platform)
        force_host_devices(args.shards)
        if not args.platform:
            args.platform = "cpu"

    jax = resolve_platform(args.platform)

    if args.ann:
        if args.batch > 4096:   # trainer default; serving bucket is small
            args.batch = 64
        _run_ann(args, jax)
        return

    import jax.numpy as jnp

    from bench import device_peaks, synthetic_ml20m
    from predictionio_tpu.models import two_tower as tt
    from predictionio_tpu.utils import compilecache

    compilecache.enable()
    n_users, n_items = 138_493, 26_744
    users, items, _ = synthetic_ml20m(args.pairs)

    p = tt.TwoTowerParams(embed_dim=args.embed, hidden=list(hidden),
                          out_dim=args.out, batch_size=args.batch,
                          epochs=1, learning_rate=0.01, seed=1)
    user_tower, item_tower, opt, epoch_fn = tt._compiled_train_epoch(
        n_users, n_items, p.embed_dim, tuple(p.hidden), p.out_dim)
    rng = jax.random.PRNGKey(p.seed)
    ru, ri = jax.random.split(rng)
    variables = (user_tower.init(ru, jnp.zeros((1,), jnp.int32)),
                 item_tower.init(ri, jnp.zeros((1,), jnp.int32)))
    opt_state = opt.init(variables)
    opt_state.hyperparams["learning_rate"] = jnp.float32(p.learning_rate)
    temperature = jnp.float32(p.temperature)

    n_steps = args.pairs // args.batch
    keep = n_steps * args.batch
    users_e = jnp.asarray(users[:keep].reshape(n_steps, args.batch))
    items_e = jnp.asarray(items[:keep].reshape(n_steps, args.batch))
    print(f"pairs={keep} steps/epoch={n_steps} batch={args.batch} "
          f"dims={args.embed}->{list(hidden)}->{args.out}", flush=True)

    def once():
        t0 = time.perf_counter()
        v, s, loss = epoch_fn(variables, opt_state, users_e, items_e,
                              temperature)
        loss = float(loss)   # scalar fetch forces device execution
        return time.perf_counter() - t0, loss

    t_cold, loss = once()
    print(f"cold epoch (incl compile): {t_cold:.1f}s loss={loss:.4f}",
          flush=True)
    t_dev = min(once()[0] for _ in range(args.repeats))
    flops = _tower_flops_per_pair(args.embed, hidden, args.out,
                                  args.batch) * keep
    print(f"warm epoch device-side: {t_dev:.2f}s  "
          f"{keep / t_dev / 1e6:.2f}M pairs/s  "
          f"model_tflops={flops / 1e12:.2f}  "
          f"mfu={flops / t_dev / device_peaks(jax.devices()[0].device_kind)['bf16_flops']:.3f}",
          flush=True)

    # single-step latency: chain on scalar dependency is built in (loss)
    one_u = users_e[:1]
    one_i = items_e[:1]
    float(epoch_fn(variables, opt_state, one_u, one_i, temperature)[2])
    lats = []
    for _ in range(20):
        t0 = time.perf_counter()
        float(epoch_fn(variables, opt_state, one_u, one_i,
                       temperature)[2])
        lats.append(time.perf_counter() - t0)
    print(f"single-step p50 (incl one round trip): "
          f"{np.percentile(lats, 50) * 1e3:.2f}ms", flush=True)


if __name__ == "__main__":
    main()
