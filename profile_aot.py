"""Chip-free AOT compile of the flagship programs against a REAL TPU
topology.

Compile times and compiler verdicts with zero chips:
``jax.experimental.topologies`` builds a v5e topology description, and
``jax.jit(...).lower(shapes).compile()`` runs the REAL XLA-TPU
compiler (the libtpu compiler is local; only execution needs silicon).
It also smoke-tests TPU *lowering* of the whole programs — the same
class of check the per-kernel Pallas lowering tests do — including the
sharded shard_map program with its all_gather collectives.

Measures, at ML-20M geometry (bench.py protocol):

- single-device ALS train program (rank 64, 10 iters): lower + compile
  wall time, XLA-estimated flops;
- the sharded 8-device ALS program over v5e:2x4;
- the two-tower contrastive epoch program (batch 8192, 64→[128]→64);
- the seq_rec ring-attention train program over the full topology;
- the serving gather→score→top-k program.

Prints ONE JSON line; see docs/perf.md "AOT compile validation".

Usage::

    python profile_aot.py [--nnz 20000000] [--rank 64] [--iters 10]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _sds_tree(tree, sharding_fn):
    """Mirror a pytree of host arrays as ShapeDtypeStructs with
    shardings — lowering needs only avals, never the (GB-sized) data."""
    import jax

    def one(a):
        a = np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=sharding_fn(a))

    return jax.tree.map(one, tree)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nnz", type=int, default=20_000_000)
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--topology", default="v5e:2x4")
    args = ap.parse_args()

    import jax

    # host-only: the compile targets a described chip, nothing runs
    jax.config.update("jax_platforms", "cpu")

    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from bench import synthetic_ml20m, _train_flops
    from predictionio_tpu.models import als
    from predictionio_tpu.models.als import ALSParams, RatingsCOO

    out = {"metric": "aot_compile", "topology": args.topology,
           "nnz": args.nnz, "rank": args.rank, "iters": args.iters}

    t0 = time.perf_counter()
    topo = topologies.get_topology_desc(args.topology, "tpu")
    out["topology_sec"] = round(time.perf_counter() - t0, 2)
    n_dev = len(topo.devices)
    out["device_kind"] = topo.devices[0].device_kind

    users, items, ratings = synthetic_ml20m(args.nnz)
    coo = RatingsCOO(users, items, ratings, 138_493, 26_744)
    t0 = time.perf_counter()
    prep = als.als_prepare(coo)
    out["prepare_sec"] = round(time.perf_counter() - t0, 2)

    p = ALSParams(rank=args.rank, iterations=args.iters, reg=0.05, seed=1)

    # -- single-device train program (the bench.py cold-train claim) ------
    mesh1 = Mesh(np.array(topo.devices[:1]), ("data",))
    rep1 = NamedSharding(mesh1, P())

    def host_bufs(side):
        dense = (() if side.dense is None else
                 (side.dense.w_cnt, side.dense.w_val, side.dense.counts))
        return (dense, tuple(
            tuple((b.other_idx, b.vals, b.mask, b.counts)
                  + ((b.seg, b.seg_off) if b.seg is not None else ()))
            for b in side.buckets))

    train = als._compiled_bucketed(
        prep.u_side.geometry, prep.i_side.geometry,
        prep.n_users, prep.n_items, p.rank, p.iterations,
        False, False, platform="tpu")
    sds = _sds_tree(
        (host_bufs(prep.u_side), host_bufs(prep.i_side),
         np.zeros((prep.n_items, p.rank), np.float32),
         np.float32(0.05), np.float32(1.0)),
        lambda a: rep1)
    t0 = time.perf_counter()
    lowered = train.lower(*sds)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    cost = compiled.cost_analysis() or {}
    out["single_device"] = {
        "lower_sec": round(t_lower, 2),
        "compile_sec": round(t_compile, 2),
        "xla_flops": cost.get("flops"),
        "model_flops": _train_flops(prep, p.rank, p.iterations),
    }

    # -- sharded program over the full topology --------------------------
    from predictionio_tpu.models import als_sharded

    meshN = Mesh(np.array(topo.devices).reshape(n_dev), ("data",))
    t0 = time.perf_counter()
    sprep = als_sharded.als_prepare_sharded(coo, n_dev)
    out["prepare_sharded_sec"] = round(time.perf_counter() - t0, 2)
    strain = als_sharded._compiled_sharded(
        meshN, sprep.geom_u, sprep.geom_i, p.rank, p.iterations,
        False, False)

    def stacked_host(sides):
        return sprep._stacked(sides)

    shard_rows = NamedSharding(meshN, P("data"))

    def sharding_for(a):
        # stacked arrays lead with the device axis
        return shard_rows if a.ndim >= 1 and a.shape[0] == n_dev \
            else NamedSharding(meshN, P())

    u_bufs = stacked_host(sprep.u_sides)
    i_bufs = stacked_host(sprep.i_sides)
    ssds = _sds_tree(
        (u_bufs, i_bufs,
         np.zeros((sprep.block_i * n_dev, p.rank), np.float32),
         np.float32(0.05), np.float32(1.0)),
        sharding_for)
    t0 = time.perf_counter()
    slowered = strain.lower(*ssds)
    st_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    scompiled = slowered.compile()
    st_compile = time.perf_counter() - t0
    scost = scompiled.cost_analysis() or {}
    out["sharded"] = {
        "n_devices": n_dev,
        "lower_sec": round(st_lower, 2),
        "compile_sec": round(st_compile, 2),
        "xla_flops": scost.get("flops"),
    }

    # -- two-tower epoch program (the dense-matmul model family) ---------
    import jax.numpy as jnp

    from predictionio_tpu.models import two_tower as tt

    user_tower, item_tower, opt, epoch_fn = tt._compiled_train_epoch(
        138_493, 26_744, 64, (128,), 64)
    rng = jax.random.PRNGKey(1)
    ru, ri = jax.random.split(rng)
    variables = (user_tower.init(ru, jnp.zeros((1,), jnp.int32)),
                 item_tower.init(ri, jnp.zeros((1,), jnp.int32)))
    opt_state = opt.init(variables)
    tt_sds = _sds_tree(
        (variables, opt_state,
         np.zeros((100, 8192), np.int32), np.zeros((100, 8192), np.int32),
         np.float32(0.1)),
        lambda a: rep1)
    t0 = time.perf_counter()
    tt_compiled = epoch_fn.lower(*tt_sds).compile()
    tt_cost = tt_compiled.cost_analysis() or {}
    out["two_tower"] = {
        "batch": 8192, "steps": 100, "dims": "64->[128]->64",
        "lower_compile_sec": round(time.perf_counter() - t0, 2),
        "xla_flops": tt_cost.get("flops"),
    }

    # -- seq_rec train program, ring attention over the topology ---------
    from predictionio_tpu.models import seq_rec as sr

    sp = sr.SeqRecParams()  # SASRec defaults: hidden 64, 2 blocks, seq 64
    assert sp.seq_len % n_dev == 0, "ring path needs seq_len % n_dev == 0"
    sr_params = sr.init_params(26_744, sp)
    sr_opt = sr._make_tx().init(jax.tree.map(jnp.asarray, sr_params))
    sr_train = sr._train_compiled(sp.hidden, sp.num_blocks, sp.num_heads,
                                  sp.seq_len, 1, False, meshN)
    sr_sds = _sds_tree(
        (sr_params, sr_opt,
         np.zeros((10, sp.batch_size, sp.seq_len), np.int32),
         np.zeros((10, sp.batch_size, sp.seq_len), np.int32),
         np.float32(0.0)),
        lambda a: NamedSharding(meshN, P()))
    t0 = time.perf_counter()
    sr_train.lower(*sr_sds).compile()
    out["seq_rec_ring"] = {
        "n_devices": n_dev, "seq_len": sp.seq_len,
        "lower_compile_sec": round(time.perf_counter() - t0, 2),
    }

    # -- serving program (gather → score → top-k, one dispatch) ----------
    serve = als._gather_score_topk_jit()
    serve_sds = (
        jax.ShapeDtypeStruct((prep.n_users, p.rank), np.float32,
                             sharding=rep1),
        jax.ShapeDtypeStruct((prep.n_items + (-prep.n_items % 2048),
                              p.rank), np.float32, sharding=rep1),
        jax.ShapeDtypeStruct((1,), np.int32, sharding=rep1),
    )
    t0 = time.perf_counter()
    scomp = serve.lower(*serve_sds, k=10, n_valid=prep.n_items,
                        pallas=False, tile=2048).compile()
    out["serving"] = {"lower_compile_sec": round(time.perf_counter() - t0, 2)}

    print(json.dumps(out))


if __name__ == "__main__":
    main()
