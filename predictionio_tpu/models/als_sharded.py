"""Sharded ALS: SPMD over a device mesh via shard_map + ICI collectives.

This is the TPU replacement for MLlib ALS's block-partitioned
shuffle-join (reference behavior: Spark ALS ``InBlock``/``OutBlock``
structures exchanged over the shuffle each half-iteration — SURVEY.md
§2d P2/C1), running the SAME bucketed MXU kernel as the single-device
path (:func:`predictionio_tpu.models.als._make_half`):

- Users (and items) are range-partitioned into ``n_dev`` equal blocks;
  each device owns one block of U rows and one of V rows, kept in
  count-descending PERMUTED order for the whole run (un-permuted once
  on the host at the end).
- Each device's rating rows are laid out in the bucketed format of
  :mod:`predictionio_tpu.models.als` — entity-width ladder, segmented
  heavy bucket, batched weighted-Gram einsums, one chunked Cholesky
  solve pass — with bucket boundaries MAX-MERGED across devices
  (:func:`als._merge_bounds`) so every device traces one identical
  program. Other-side indices are pre-mapped on the host to the
  counterpart's permuted GLOBAL positions, so the gathered factor
  matrix is indexed directly — partitioning happens once at data-prep
  time, not per iteration.
- Each half-step inside ``shard_map``: one ``all_gather`` of the
  counterpart factor blocks over the ``data`` axis (the only
  collective — riding ICI), then purely local bucketed Gram + solve
  for the local block.
- The full iteration loop is a single ``lax.scan`` under one jit: zero
  host round-trips, 2 all_gathers per iteration of size n·k.

Per-device memory: the local solve buffer (≤ block·k² floats, chunked)
plus the full counterpart factor matrix — the same asymptotics as
MLlib's per-executor blocks.

The previous padded-row + scatter-add layout this replaces measured
~40% of each iteration in TPU scatter cost and solved through XLA's
sequential Cholesky lowering; the bucketed port brings the sharded
path to parity with the round-2 single-chip redesign (VERDICT r2
ask #3).
"""

from __future__ import annotations

import functools
import logging
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

from predictionio_tpu.models.als import (
    ALSParams,
    RatingsCOO,
    _bucket_side,
    _BucketSide,
    _make_half,
    _merge_bounds,
    _perm_by_count_desc,
    init_factors,
    log_train_modes,
)

log = logging.getLogger(__name__)


def _pad_rows(arr: np.ndarray, n: int) -> np.ndarray:
    if arr.shape[0] == n:
        return arr
    pad = np.zeros((n - arr.shape[0],) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad], axis=0)


@dataclass
class ALSShardedPrepared:
    """Per-device bucketed layouts with common (max-merged) geometry."""

    n_users: int
    n_items: int
    nnz: int
    n_dev: int
    block_u: int
    block_i: int
    u_sides: List[_BucketSide]  # one per device, identical geometry
    i_sides: List[_BucketSide]
    _device_bufs: dict = None  # type: ignore[assignment]

    @property
    def geom_u(self):
        return self.u_sides[0].geometry

    @property
    def geom_i(self):
        return self.i_sides[0].geometry

    def _stacked(self, sides: List[_BucketSide]):
        """Per-bucket (and dense-head) arrays stacked over the leading
        device dim, in the (dense, buckets) structure ``_make_half``
        consumes (every device has the same one: forced bounds)."""
        import jax

        return jax.tree.map(lambda *per_device: np.stack(per_device),
                            *(s.arrays() for s in sides))

    def device_buffers(self, mesh):
        """Stacked layouts placed on the mesh, cached per mesh — a
        reused prep (e.g. a `pio eval` grid over rank/reg candidates)
        must not re-copy and re-upload GBs of rating layout per train
        call (mirrors ALSPrepared.device_buffers)."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        if self._device_bufs is None:
            self._device_bufs = {}
        if mesh not in self._device_bufs:
            def put(tree):
                dense, buckets = tree

                def place(a):
                    return jax.device_put(a, NamedSharding(
                        mesh, P("data", *([None] * (a.ndim - 1)))))

                return (tuple(place(a) for a in dense),
                        tuple(tuple(place(a) for a in bkt)
                              for bkt in buckets))

            self._device_bufs[mesh] = (put(self._stacked(self.u_sides)),
                                       put(self._stacked(self.i_sides)))
        return self._device_bufs[mesh]


def _device_perms(idx, block, n_dev):
    """Per-device local counts and count-desc permutations, plus the
    map from ORIGINAL global entity id → permuted global position
    (owner_block_start + inv_perm_owner[local_id]). Computed ONCE per
    side: the layout builder and the other side's index mapping must
    agree on these permutations exactly."""
    counts = np.bincount(idx, minlength=block * n_dev).astype(np.int64)
    locs, perms, invs = [], [], []
    pos = np.empty(block * n_dev, np.int32)
    for d in range(n_dev):
        c = counts[d * block:(d + 1) * block]
        perm, inv = _perm_by_count_desc(c.astype(np.float32))
        locs.append(c)
        perms.append(perm)
        invs.append(inv)
        pos[d * block:(d + 1) * block] = d * block + inv
    return locs, perms, invs, pos


def _side_prepared(idx_self, idx_other, vals, block, n_dev,
                   locs, perms, invs, other_pos, n_other):
    """Build all devices' bucketed layouts for one orientation.

    ``other_pos[j]`` maps an ORIGINAL other-entity id to its permuted
    global position in the gathered factor matrix; ``n_other`` is that
    matrix's height (padded global size)."""
    owner = idx_self // block
    bounds = _merge_bounds([locs[d][perms[d]] for d in range(n_dev)],
                           n_other)
    sides = []
    for d in range(n_dev):
        sel = owner == d
        sides.append(_bucket_side(
            (idx_self[sel] - d * block).astype(np.int32),
            idx_other[sel], other_pos,
            vals[sel].astype(np.float32),
            block, locs[d].astype(np.float32), perms[d], invs[d],
            n_other=n_other, bounds=bounds))
    geom = sides[0].geometry
    assert all(s.geometry == geom for s in sides), \
        "max-merged bounds must give every device the same geometry"
    return sides


def als_prepare_sharded(coo: RatingsCOO, n_dev: int) -> ALSShardedPrepared:
    """Host-side layout construction for the sharded path (the analogue
    of MLlib's InBlock build, partitioned; done once per dataset)."""
    block_u = -(-coo.n_users // n_dev)  # ceil
    block_i = -(-coo.n_items // n_dev)

    ulocs, uperms, uinvs, upos = _device_perms(coo.user_idx, block_u, n_dev)
    ilocs, iperms, iinvs, ipos = _device_perms(coo.item_idx, block_i, n_dev)

    u_sides = _side_prepared(coo.user_idx, coo.item_idx, coo.rating,
                             block_u, n_dev, ulocs, uperms, uinvs, ipos,
                             n_other=block_i * n_dev)
    i_sides = _side_prepared(coo.item_idx, coo.user_idx, coo.rating,
                             block_i, n_dev, ilocs, iperms, iinvs, upos,
                             n_other=block_u * n_dev)
    return ALSShardedPrepared(coo.n_users, coo.n_items, coo.nnz, n_dev,
                              block_u, block_i, u_sides, i_sides)


@functools.lru_cache(maxsize=16)  # chunked checkpointing adds block-size
def _compiled_sharded(mesh, geom_u, geom_i, rank: int, iterations: int,  # variants (full/block/remainder) per geometry
                      implicit: bool, weighted_reg: bool,
                      bf16_gather: bool = False, precision: str = "high",
                      gram_mode: str = "off"):
    """``reg``/``alpha`` are traced scalar inputs of the returned
    program (replicated into the shard_map body), so an eval grid over
    regularization shares one sharded executable — the cache keys only
    on geometry + program structure (see als._compiled_bucketed)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from predictionio_tpu.parallel.mesh import pvary

    k = rank
    block_u = geom_u[0]
    half = _make_half(k, implicit, weighted_reg,
                      pvary=lambda x: pvary(x, "data"),
                      bf16_gather=bf16_gather, precision=precision,
                      gram_mode=gram_mode)

    def body(u_bufs, i_bufs, V0_l, reg, alpha):
        # inside shard_map the stacked arrays arrive with a local
        # leading device dim of 1 → squeeze it
        def squeeze(side):
            dense, buckets = side
            return (tuple(a[0] for a in dense),
                    tuple(tuple(a[0] for a in bkt) for bkt in buckets))

        u_l = squeeze(u_bufs)
        i_l = squeeze(i_bufs)

        if iterations == 0:
            # match the single-device contract for iterations==0
            # (als._compiled_bucketed): U solved from the initial V,
            # not a zero-length scan's zeros. (The checkpoint-resume
            # path restores U directly and never dispatches this.)
            V_full = jax.lax.all_gather(V0_l, "data", tiled=True)
            return half(V_full, u_l, geom_u, reg, alpha), V0_l

        def step(carry, _):
            U_l, V_l = carry
            V_full = jax.lax.all_gather(V_l, "data", tiled=True)
            U_l = half(V_full, u_l, geom_u, reg, alpha)
            U_full = jax.lax.all_gather(U_l, "data", tiled=True)
            V_l = half(U_full, i_l, geom_i, reg, alpha)
            return (U_l, V_l), None

        U0 = pvary(jnp.zeros((block_u, k), jnp.float32), "data")
        (U_l, V_l), _ = jax.lax.scan(step, (U0, V0_l), None,
                                     length=iterations)
        return U_l, V_l

    def side_specs(geom):
        n_self, dense_geom, buckets = geom
        dense = (() if dense_geom is None else
                 (P("data", None, None),     # w_cnt
                  P("data", None, None),     # w_val
                  P("data", None)))          # counts
        specs = []
        for (C, nb, slab, n_slabs, is_seg) in buckets:
            s = [P("data", None, None, None)] * 3          # oi, vals, mask
            s.append(P("data", None) if is_seg
                     else P("data", None, None))           # counts
            if is_seg:
                s += [P("data", None, None, None),         # seg
                      P("data", None)]                     # seg_off
            specs.append(tuple(s))
        return (dense, tuple(specs))

    in_specs = (side_specs(geom_u), side_specs(geom_i),
                P("data", None), P(), P())
    out_specs = (P("data", None), P("data", None))
    if gram_mode in ("pallas", "interpret"):
        # pallas_call has no shard_map replication rule — the fused
        # gather→Gram (and the VMEM solve it prefers) run with the
        # checker off; specs are identical, only the static rep-type
        # verification is skipped
        from predictionio_tpu.parallel.mesh import shard_map_unchecked

        fn = shard_map_unchecked(body, mesh, in_specs, out_specs)
    else:
        fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs)
    return jax.jit(fn)


def als_train_sharded_prepared(
    prep: ALSShardedPrepared, p: ALSParams, mesh,
    checkpointer=None, checkpoint_every: int = 0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Train over the mesh; returns full (U, V) in original order.

    With ``checkpointer`` + ``checkpoint_every > 0`` the fused
    iteration scan is split at iteration boundaries: blocks of
    ``checkpoint_every`` iterations run device-resident, and after each
    block the (device-layout) factors are fetched and saved — the
    SURVEY §5 restart-from-checkpoint contract on the multi-chip path,
    where the failure unit is the whole slice. Exact by construction:
    V fully determines the next iteration (each half-step recomputes U
    from V), so resuming from a block boundary reproduces the
    uninterrupted run. Checkpoints store the PERMUTED per-device layout
    (deterministic for a given ratings matrix + device count); a resume
    with a different rank or device count restores nothing and falls
    back to a fresh start via the geometry protocol in
    ``restore_latest_compatible``. Checkpoint calls are COLLECTIVE
    under multi-process meshes: every process calls save/clear
    together (Orbax elects the writer and syncs internally;
    ``TrainCheckpointer.clear`` wipes on process 0 via an atomic
    rename-to-tombstone — no barrier, see its docstring for why a
    concurrent manager re-init on another process stays safe).

    Per-boundary cost: one extra program dispatch + a host fetch of
    U and V + the Orbax write (measured on the 8-device CPU mesh —
    see docs/perf.md).
    """
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    n_dev = prep.n_dev
    block_u, block_i = prep.block_u, prep.block_i
    if int(np.prod(mesh.devices.shape)) != n_dev:
        raise ValueError(
            f"layout was prepared for {n_dev} devices but the mesh has "
            f"{int(np.prod(mesh.devices.shape))}")

    from predictionio_tpu import ops
    from predictionio_tpu.models.als import _gram_precision

    # resolved per call (not inside the lru_cached builder) so an env
    # flip between calls is never shadowed by a stale cache entry
    platform = mesh.devices.flat[0].platform
    gram_mode = ops.resolve_gram_mode(platform)
    log_train_modes(platform, gram_mode, n_devices=n_dev)

    def compiled(n_iters: int):
        return _compiled_sharded(
            mesh, prep.geom_u, prep.geom_i,
            p.rank, n_iters, bool(p.implicit),
            bool(p.weighted_reg), bool(p.bf16_gather), _gram_precision(),
            gram_mode)

    # inputs are placed directly onto the mesh with their shard_map
    # layouts (cached per mesh) — never through the default backend
    # (which may be a different platform than the mesh's)
    u_bufs, i_bufs = prep.device_buffers(mesh)
    # per-device live bytes after placement: "everything on device 0"
    # shows here (this process's devices; None where the backend
    # reports no memory stats)
    log.info("sharded ALS placement: bytes in use per device: %s",
             [(d.memory_stats() or {}).get("bytes_in_use")
              for d in mesh.local_devices])

    # identical init to the single-device path, per-device permuted so
    # the resident factor order matches the bucketed layouts
    V0g = _pad_rows(init_factors(prep.n_items, p.rank, p.seed),
                    block_i * n_dev)
    V0p = np.concatenate([
        V0g[d * block_i:(d + 1) * block_i][prep.i_sides[d].perm]
        for d in range(n_dev)])

    def fetch(x):
        # multi-host: the result spans non-addressable devices — gather
        # the global value onto every host (replicated model output,
        # the torrent-broadcast analogue in reverse)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(x, tiled=True))
        return np.asarray(x)

    def unpermute(xp, sides, block, n):
        blocks = [xp[d * block:(d + 1) * block][sides[d].inv_perm]
                  for d in range(n_dev)]
        return np.concatenate(blocks)[:n]

    v_spec = NamedSharding(mesh, P("data", None))
    reg_a, alpha_a = np.float32(p.reg), np.float32(p.alpha)

    # -- resume (mirrors als_train_prepared's protocol) ---------------------
    start = 0
    U_done = None  # restored U, consumed only when start == iterations
    if checkpointer is not None and checkpointer.latest_step() is not None:
        from predictionio_tpu.utils.checkpoint import CheckpointGeometryError

        template = {"U": np.zeros((block_u * n_dev, p.rank), np.float32),
                    "V": np.zeros_like(V0p)}
        try:
            state, step = checkpointer.restore_latest_compatible(template)
            V0p = np.asarray(state["V"])
            U_done = np.asarray(state["U"])
            start = min(int(step), p.iterations)
        except CheckpointGeometryError:
            import warnings

            warnings.warn(
                "sharded ALS checkpoints are stale (geometry/layout "
                "change) — wiped; training restarts from scratch",
                RuntimeWarning)
            # every process reads the same files → every process
            # raises the same error → this is collective; clear()
            # itself is multiprocess-safe (process 0 wipes, all sync)
            checkpointer.clear()

    if start >= p.iterations and U_done is not None:
        # died between the final checkpoint and model persistence
        Uh, Vh = U_done, V0p
    elif checkpointer is None or checkpoint_every <= 0 or p.iterations == 0:
        # iterations==0 (U recovered from initial V) has no blocks to
        # checkpoint — run the same single-shot program either way
        V0 = jax.device_put(V0p, v_spec)
        U, V = compiled(p.iterations - start)(u_bufs, i_bufs, V0,
                                              reg_a, alpha_a)
        Uh, Vh = fetch(U), fetch(V)
    else:
        V = jax.device_put(V0p, v_spec)
        Uh = Vh = None
        it = start
        while it < p.iterations:
            n = min(checkpoint_every, p.iterations - it)
            U, V = compiled(n)(u_bufs, i_bufs, V, reg_a, alpha_a)
            it += n
            Uh, Vh = fetch(U), fetch(V)
            # collective: Orbax's save syncs all processes and elects
            # the writer itself — a process-0-only call deadlocks the
            # others at the internal barrier
            checkpointer.save(it, {"U": Uh, "V": Vh})
        assert Uh is not None  # start < iterations here, loop ran

    return (unpermute(Uh, prep.u_sides, block_u, prep.n_users),
            unpermute(Vh, prep.i_sides, block_i, prep.n_items))


def als_train_sharded(
    coo: RatingsCOO, p: ALSParams, mesh,
    checkpointer=None, checkpoint_every: int = 0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Train ALS over the mesh's ``data`` axis; returns full (U, V)."""
    n_dev = int(np.prod(mesh.devices.shape))
    if "data" not in mesh.axis_names:
        raise ValueError(f"mesh must have a 'data' axis, got {mesh.axis_names}")
    return als_train_sharded_prepared(als_prepare_sharded(coo, n_dev), p, mesh,
                                      checkpointer=checkpointer,
                                      checkpoint_every=checkpoint_every)
