"""Alternating Least Squares matrix factorization on TPU.

Replaces Spark MLlib's ALS (reference behavior: [U]
org.apache.spark.mllib.recommendation.ALS used by the recommendation /
similar-product / e-commerce templates; block-partitioned factor
matrices, shuffle-joined rating blocks, per-row normal-equation Cholesky
solves — SURVEY.md §2d P2). The TPU-first redesign:

- Ratings are **bucketed by entity** — entities sorted by rating
  count, each padded to a ladder width C (capped at 8K; heavier
  entities are segmented across rows), and same-width entities batched
  into dense ``(nb, C)`` blocks. This is the sparsity-to-MXU bridge:
  each entity's normal equations ``A_e = Σ v vᵀ`` are ONE batch
  element of a dense batched weighted Gram ``(C×k)ᵀdiag(w)(C×k)`` —
  systolic-array work with **no scatter anywhere** (TPU scatter-add of
  row partials measured ~40% of the iteration in the round-1
  padded-row design).
- The power-law HEAD goes denser still: entities with count ≥
  n_other/14 (see ``_DENSE_RATIO``) skip gathering entirely — their
  normal equations are plain GEMMs of dense per-entity weight rows
  against the other side's factor outer products (the ~280 heaviest
  ML-20M entities hold ~65% of padded slots, and their gathers
  measured ~70% of the Gram phase at the ~140 GB/s XLA row-gather
  ceiling).
- Buckets stream through ``lax.scan`` in fixed-size slabs, emitting
  ridged normal equations into ONE solve buffer; a single chunked scan
  solves everything with one instance of the **block-recursive batched
  Cholesky built from batched matmuls**
  (:mod:`predictionio_tpu.ops.cholesky`) — replacing MLlib's per-row
  LAPACK ``dppsv`` calls (~18× faster on TPU than XLA's sequential
  ``cholesky`` lowering at ML-20M batch sizes, and a single Cholesky
  graph instance keeps XLA compile bounded).
- The whole training run (iterations × two half-steps) is ONE jitted
  ``lax.scan``: no host round-trips. Layout construction
  (:func:`als_prepare`) is a separate host-side step — the analogue of
  MLlib's InBlock build — done once per dataset and reused.
- With a mesh (:mod:`predictionio_tpu.models.als_sharded`): entities are
  range-partitioned across devices, each device runs this same bucketed
  program on its block, and one ``all_gather`` per half-step replaces
  the reference's shuffle.

Supports explicit feedback and implicit feedback (Hu-Koren-Volinsky
confidence weighting, MLlib's ``trainImplicit`` analogue) and MLlib's
weighted-λ regularization (λ scaled by each entity's rating count).
"""

from __future__ import annotations

import functools
import logging
import os
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from predictionio_tpu import native
from predictionio_tpu.utils import tracing

log = logging.getLogger(__name__)


@dataclass
class RatingsCOO:
    """Host-side ratings in COO form with dense entity indices."""

    user_idx: np.ndarray  # int32 [nnz]
    item_idx: np.ndarray  # int32 [nnz]
    rating: np.ndarray    # float32 [nnz]
    n_users: int
    n_items: int

    @property
    def nnz(self) -> int:
        return int(self.user_idx.shape[0])


@dataclass
class ALSParams:
    rank: int = 10
    iterations: int = 10
    reg: float = 0.01          # MLlib's `lambda`
    implicit: bool = False     # MLlib trainImplicit
    alpha: float = 1.0         # implicit confidence scale
    weighted_reg: bool = True  # ALS-WR: λ·n_e scaling (MLlib behavior)
    seed: int = 0
    # opt-in: gather factors in bfloat16 (halves the dominant HBM
    # traffic — the gather measured ~140 GB/s effective and ~60% of
    # device time); the Gram einsum accumulates f32. Costs ~1e-2
    # relative factor error (measured) — fine for recommendation
    # ranking, off by default for reference-grade numerics.
    bf16_gather: bool = False


def init_factors(n: int, rank: int, seed: int) -> np.ndarray:
    """Deterministic host-side factor init shared by the single-device and
    sharded paths (so their iterates are bitwise-comparable)."""
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n, rank)) / np.sqrt(rank)).astype(np.float32)



# -- bucketed layout ----------------------------------------------------------
#
# Round 1's padded-row layout paid one sorted scatter-add of ~nnz/W row
# partials per half-step; TPU scatter measured ~140-200 ms per ML-20M
# half-step — comparable to all the matmul work combined. Bucketing
# entities by padded rating count instead makes each entity's normal
# equations ONE batch element of a dense batched Gram — no scatter
# anywhere. This is the "bucketed/padded rating blocks" design SURVEY.md
# §7 anticipated. Entities live in count-descending permuted order
# during training (so same-width entities are contiguous); factors are
# un-permuted once at the end.

_SLAB_ELEMS = 1 << 20   # slab_entities × width bound per scan step. The r5
                        # trace showed the warm train latency-bound (~8.8k
                        # device ops/iteration, HBM at 49 of 819 GB/s), so
                        # bigger slabs = fewer, larger dispatches: 2^20
                        # (~256 MB gather at k=64) measured 2.16 s vs 2.71 s
                        # device-side for the ML-20M train against the r2-r4
                        # 2^18 default (an A/B on the v5e, r5). Layout
                        # parity across slab sizes is tested
                        # (test_als.py::test_slab_size_parity).

# Allowed padded widths. Round 2 used every power of two up to the
# heaviest entity's count (8.4M!): 38 buckets across both sides, each
# inlining its own copy of the solve — 219k lines of StableHLO, 111 s
# of tracing + 291 s of XLA compile at ML-20M geometry — and the
# super-C_MAX buckets alone held ~25M padded slots (more than nnz).
# A ×4 ladder capped at 8 K bounds the program at ≤7 buckets per side;
# entities heavier than the cap are segmented across rows instead
# (see _bucket_side), which is also strictly less gather work.
_LADDER = (8, 32, 128, 512, 2048, 8192)
_C_MAX = _LADDER[-1]

# Solve-pass shape: normal equations from every bucket are written into
# one (N, k, k) device buffer and solved by a single lax.scan in chunks
# of this many systems — so the whole program contains exactly ONE
# instance of the block-recursive Cholesky graph. Solving inside each
# bucket body (round 2) inlined that graph 38× → 219k lines of HLO and
# 258 s of XLA compile. The buffer costs N·k²·4 bytes (2.7 GB at
# ML-20M, k=64); catalogs where it would exceed the cap below fall back
# to in-body solves (memory flat, compile slower, persistent cache
# amortizes).
_SOLVE_CHUNK = 4096
_SOLVE_BUF_MB = 4096

# Dense-head crossover. The heaviest entities dominate padded slots
# under a power law (ML-20M shape: the >8K-rating "seg" entities are
# ~280 of 165K yet hold ~65% of all padded slots, and their gathers
# measured ~70% of the whole Gram phase at ~140 GB/s effective — the
# XLA row-gather ceiling). For an entity with C rating slots the
# gather-path cost is ~C·256B at that ceiling, while a DENSE weight
# row over the whole other side costs ~n_other·k(k+1) MXU flops via
# one GEMM against the other side's factor outer products (no gather
# at all). Measured crossover on v5e: C ≳ n_other/14. Entities above
# it form the "dense head": per-entity (multiplicity, rating-sum)
# rows over the full other side, normal equations by plain GEMM.
# _DENSE_MIN_COUNT keeps tiny problems (tests, small apps) on the
# uniform bucket path.
_DENSE_RATIO = 1.0 / 14.0
_DENSE_MIN_COUNT = 256
# Cap on the dense head's total weight-row bytes (w_cnt + w_val, 8
# bytes per (entity, other) cell, held on host AND device). The head
# pays off because a power-law tail keeps it to a few hundred entities;
# a distribution with MANY just-over-threshold entities would otherwise
# grow it without bound (~2 GB/side at 20M nnz worst case — ADVICE r3).
# Entities over the cap spill to the seg/ladder bucket path, which is
# always correct, just gather-bound.
_DENSE_HEAD_MB = 2048


@dataclass
class _Bucket:
    """Entities sharing one padded width C, sliced into scan slabs.

    Two row↔entity regimes:
    - ``seg is None``: one row per entity (``counts`` is per-row,
      shaped (n_slabs, slab)).
    - ``seg`` set (the single heavy bucket, entities with more than
      ``_C_MAX`` ratings): each entity spans several width-C rows.
      Rows are entity-sorted, so a slab of S rows touches ≤ S
      CONSECUTIVE entities; ``seg`` is the (n_slabs, slab, slab)
      SLAB-LOCAL one-hot row→entity matrix (entity index relative to
      ``seg_off`` for that slab) that aggregates per-row partial Grams
      into per-entity normal equations with ONE batched matmul per slab
      (MXU work, no scatter). Slab-local keeps ``seg`` at R×slab floats
      — a dense (R, nb) matrix would grow quadratically with the number
      of heavy entities. ``counts`` is per-entity, shaped (nb,).
    """

    C: int
    nb: int        # real entity count
    slab: int
    n_slabs: int
    other_idx: np.ndarray  # (n_slabs, slab, C) int32 — PERMUTED other pos
    vals: np.ndarray       # (n_slabs, slab, C) f32
    mask: np.ndarray       # (n_slabs, slab, C) f32
    counts: np.ndarray     # see class docstring
    seg: Optional[np.ndarray] = None
    seg_off: Optional[np.ndarray] = None  # (n_slabs,) int32 first entity

    @property
    def geometry(self) -> Tuple[int, int, int, int, bool]:
        return (self.C, self.nb, self.slab, self.n_slabs,
                self.seg is not None)


@dataclass
class _DenseHead:
    """The heaviest entities (see ``_DENSE_RATIO``): per-entity dense
    weight rows over the FULL other side. ``w_cnt[e, o]`` is the
    multiplicity of the (e, o) pair (0 almost everywhere), ``w_val``
    the rating sum — together they express exactly the same normal
    equations as the bucketed slots, as two GEMMs with no gather."""

    nb: int
    n_other: int
    w_cnt: np.ndarray   # (nb, n_other) f32
    w_val: np.ndarray   # (nb, n_other) f32
    counts: np.ndarray  # (nb,) f32 — rating count (ridge weighting)

    @property
    def geometry(self) -> Tuple[int, int]:
        return (self.nb, self.n_other)


@dataclass
class _BucketSide:
    """One half-step orientation: self entities bucketed, other side
    referenced by permuted position. ``dense`` (optional) covers the
    heaviest entities — permuted positions [0, dense.nb) — with the
    remaining entities in ``buckets``."""

    n: int
    perm: np.ndarray       # position p → original entity id
    inv_perm: np.ndarray   # original entity id → position
    buckets: list
    dense: Optional[_DenseHead] = None
    #: which path the data took through :func:`_bucket_side`: how the
    #: interactions were ordered ("native" counting pass | numpy
    #: "radix"), the radix order's 16-bit passes (1 | 2; 0 where the
    #: native pass ran), and how the dense head was filled ("assign" |
    #: "bincount" | "none" without a head)
    order_path: str = "radix"
    radix_passes: int = 0
    dense_fill: str = "none"

    @property
    def geometry(self):
        return (self.n,
                self.dense.geometry if self.dense is not None else None,
                tuple(b.geometry for b in self.buckets))

    def arrays(self):
        """The side's host arrays in the ``(dense, buckets)`` structure
        ``_make_half``'s ``half`` takes as ``bufs_side``."""
        d = self.dense
        return (() if d is None else (d.w_cnt, d.w_val, d.counts), tuple(
            (b.other_idx, b.vals, b.mask, b.counts)
            + ((b.seg, b.seg_off) if b.seg is not None else ())
            for b in self.buckets))


def _lies_as(a: np.ndarray, dtype) -> bool:
    """Whether the native layout passes can read ``a`` where it lies: a
    C-contiguous column of ``dtype``."""
    return a.dtype == dtype and a.ndim == 1 and a.flags.c_contiguous


def _entity_counts(idx: np.ndarray, n: int) -> np.ndarray:
    """Interactions per entity, ``np.bincount(idx, minlength=n)``: one
    native pass over the column as it lies (``native/als_layout.cc``)
    where it is a C-contiguous int32 array and the library can be
    built — ``np.bincount`` first copies the column to int64, nnz-long
    fresh memory for a table of ``n`` counts."""
    lib = native.als_layout_library() if _lies_as(idx, np.int32) else None
    if lib is None:
        return np.bincount(idx, minlength=n)
    counts = np.zeros(n, np.int64)
    bad = lib.als_count(idx.shape[0], idx.ctypes.data, n, counts.ctypes.data)
    if bad >= 0:
        raise IndexError(f"interaction {bad}: id {idx[bad]} outside the "
                         f"side's {n} entities")
    return counts


def _perm_by_count_desc(counts: np.ndarray):
    perm = np.argsort(-counts, kind="stable").astype(np.int32)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm), dtype=np.int32)
    return perm, inv


def _merge_bounds(counts_sorted_list, n_other: int) -> tuple:
    """Common bucket boundaries for one or many count-desc-sorted count
    vectors: ``(nb_dense, (nb_seg, n_slabs_seg), ((width, nb), … desc))``.

    For the sharded path every device must run the SAME program, so
    boundaries are the elementwise max over the devices' natural
    boundaries. Placing a lighter entity in a wider bucket (or the
    dense head) is always safe (capacity ≥ count — see the argument in
    ``_bucket_side``), so max-merging never breaks a device, only pads
    it.
    """
    thresh = max(_DENSE_MIN_COUNT, int(_DENSE_RATIO * n_other))
    nb_dense = max(int((c >= thresh).sum()) for c in counts_sorted_list)
    # byte-cap the head (see _DENSE_HEAD_MB): counts are sorted
    # descending, so truncating keeps the heaviest — highest-payoff —
    # entities and spills the rest to the buckets below
    nb_dense = min(nb_dense, (_DENSE_HEAD_MB << 20) // max(1, 8 * n_other))
    nb_seg = max(int((c[nb_dense:] > _C_MAX).sum())
                 for c in counts_sorted_list)
    rows_cap = 0
    if nb_seg:
        for c in counts_sorted_list:
            seg_c = c[nb_dense:nb_dense + nb_seg]
            rows = int(((seg_c + _C_MAX - 1) // _C_MAX).sum())
            rows_cap = max(rows_cap, rows, 1)
    ladder = np.asarray(_LADDER, np.int64)
    nbs: dict = {}
    for c in counts_sorted_list:
        rest = c[nb_dense + nb_seg:]
        rest = rest[rest > 0]
        if rest.size:
            w, n = np.unique(ladder[np.searchsorted(ladder, rest)],
                             return_counts=True)
            for wi, ni in zip(w, n):
                nbs[int(wi)] = max(nbs.get(int(wi), 0), int(ni))
    regs = tuple(sorted(nbs.items(), reverse=True))
    return (nb_dense, (nb_seg, rows_cap), regs)


def _stable_order(inv_perm: np.ndarray, idx_self: np.ndarray):
    """``np.argsort(inv_perm[idx_self], kind="stable")`` — the
    interactions ordered by their entity's permuted position — as an
    LSD radix sort over 16-bit digits: numpy's stable argsort of a
    ``uint16`` array IS its radix sort (O(n)), where that of the
    ``int32`` positions is a merge sort. One digit where the positions
    fit it, else two (``o1[o2]``; each pass being stable, so is their
    composition). A digit is gathered from a table of ``len(inv_perm)``
    entries, so the positions themselves are never laid out.
    ``(order, passes)``."""
    # (the cast to uint16 keeps a position's low 16 bits)
    o1 = np.argsort(inv_perm.astype(np.uint16)[idx_self], kind="stable")
    if len(inv_perm) <= 1 << 16:
        return o1, 1
    hi = (inv_perm >> 16).astype(np.uint16)[idx_self][o1]
    return o1[np.argsort(hi, kind="stable")], 2


def _native_order(idx_self, idx_other, other_pos, vals, inv_perm, starts):
    """The ordered ``(o, v)`` of :func:`_bucket_side`'s step 1 from ONE
    stable counting-sort pass in native code
    (``native/als_layout.cc``): interaction i goes to slot
    ``cursor[inv_perm[idx_self[i]]]++``, the cursors starting at
    ``starts`` — COO order within an entity, no permutation built, and
    ``o``, ``v`` the only nnz-long memory touched for the first time.
    None where the library cannot be built or a column is not what it
    takes (C-contiguous, int32 ids and positions, float32 values): the
    caller then orders with numpy, and no nnz-long column is copied to
    fit."""
    nnz = idx_self.shape[0]
    takes = (_lies_as(idx_self, np.int32) and _lies_as(idx_other, np.int32)
             and _lies_as(vals, np.float32) and _lies_as(other_pos, np.int32)
             and _lies_as(inv_perm, np.int32)
             and idx_other.shape[0] == vals.shape[0] == nnz == starts[-1]
             and inv_perm.shape[0] == len(starts) - 1)
    lib = native.als_layout_library() if takes else None
    if lib is None:
        return None
    cursor = starts[:-1].copy()
    o, v = np.empty(nnz, np.int32), np.empty(nnz, np.float32)
    bad = lib.als_order_scatter(
        nnz, idx_self.ctypes.data, idx_other.ctypes.data,
        other_pos.ctypes.data, other_pos.shape[0], vals.ctypes.data,
        inv_perm.ctypes.data, inv_perm.shape[0], cursor.ctypes.data,
        o.ctypes.data, v.ctypes.data)
    if bad >= 0:
        raise IndexError(
            f"interaction {bad}: entity {idx_self[bad]} / other "
            f"{idx_other[bad]} outside the layout's {inv_perm.shape[0]} "
            f"x {other_pos.shape[0]} ids, or more interactions of the "
            "entity than its count")
    return o, v


def _fill_rows(rowlen: np.ndarray, C: int, o: np.ndarray, v: np.ndarray):
    """``(other_idx, vals, mask)`` of ``len(rowlen)`` rows of width
    ``C``: row r holds the next ``rowlen[r]`` interactions of ``o``,
    ``v`` as a prefix, zeros behind. Boolean assignment walks the
    prefix mask row-major, which IS the sorted order of ``o``, ``v`` —
    no slot's (row, column) is ever computed."""
    m = np.arange(C, dtype=np.int32) < rowlen[:, None]
    oi = np.zeros(m.shape, np.int32)
    vv = np.zeros(m.shape, np.float32)
    oi[m] = o
    vv[m] = v
    return oi, vv, m.astype(np.float32)


def _bucket_side(idx_self, idx_other, other_pos, vals, n_self, counts,
                 perm, inv_perm, n_other=None, bounds=None) -> _BucketSide:
    """Bucket one orientation. ``other_pos`` maps an other-side id (an
    entry of ``idx_other``) to its factor-row position;
    ``counts/perm/inv_perm`` come from :func:`_perm_by_count_desc` on
    this side's counts; ``n_other`` is the other side's factor-row count
    (the width of dense-head weight rows — the gathered factor matrix
    height).

    ``bounds`` forces common bucket boundaries (sharded path: the
    max-merge over all devices, so every device traces one program).
    Forced boundaries are safe: the entity at permuted position p has
    count ≤ every entity before it, and merged boundaries only ever
    move p into the dense head or a bucket at least as wide as its
    natural one — so capacity C ≥ count always holds.

    The algorithm is O(nnz) passes over 4-byte data (the arrays are
    ``np.array_equal`` to those of the comparison-sort builder kept as
    ``tests/als_layout_oracle.py``):

    1. the interactions are ordered by their entity's permuted position
       with a STABLE counting sort — stable, because a row's slot
       order is the COO's order of appearance and the Gram's float
       sums depend on it. The counts are known (``counts``), so the
       rows' ``starts`` come first and one native pass over the COO
       drops every interaction at its entity's cursor
       (:func:`_native_order`); where the native library cannot be
       built, or a column is not a C-contiguous int32 / float32 array,
       numpy's radix sort orders them instead (:func:`_stable_order`;
       one or two 16-bit passes, read from the entity count) and
       ``other_pos[idx_other[order]]``, ``vals[order]`` are gathered
       through the order. Both give the same ``o``, ``v``;
    2. the dense head is filled by assignment where no (entity, other)
       pair repeats — seen on the data: as many non-zero cells as
       interactions — and by two ``bincount``s where one does;
    3. every bucket is filled through its prefix mask
       (:func:`_fill_rows`) from the rows' lengths alone.

    ``order_path``, ``radix_passes`` and ``dense_fill`` of the result
    say which path the data took.

    Invariant the fused kernel rests on: in every bucket row — regular
    or segmented, natural or forced boundaries — the real slots are a
    PREFIX (a row is filled from its start; only an entity's last
    segment row is short), so ``mask.sum(1)`` is the row's real length
    and ``ops.gather_gram`` fetches just that many lines
    (tests/test_als.py holds it).
    """
    with tracing.span("als.prepare.order"):
        counts_perm = counts[perm].astype(np.int64)
        starts = np.zeros(n_self + 1, np.int64)
        np.cumsum(counts_perm, out=starts[1:])
        ordered = _native_order(idx_self, idx_other, other_pos, vals,
                                inv_perm, starts)
        if ordered is not None:
            (o, v), order_path, passes = ordered, "native", 0
        else:
            order, passes = _stable_order(inv_perm, idx_self)
            o, v = other_pos[idx_other[order]], vals[order]
            del order
            order_path = "radix"
        if n_other is None:
            n_other = int(o.max()) + 1 if o.size else 1

    if bounds is None:
        bounds = _merge_bounds([counts_perm], n_other)
    nb_dense, (nb_seg, rows_cap), regs = bounds

    # dense head: heaviest entities (permuted positions [0, nb_dense))
    # as dense weight rows — see _DENSE_RATIO
    dense, dense_fill = None, "none"
    if nb_dense:
        with tracing.span("als.prepare.dense"):
            real = min(nb_dense, n_self)
            hi = int(starts[real])
            # linearized (entity, other) cell of each interaction
            lin = np.repeat(np.arange(real, dtype=np.int64) * n_other,
                            counts_perm[:real])
            lin += o[:hi]
            # where every pair is distinct a cell receives ONE
            # interaction, so assigning it is exactly what summing it
            # gives (``+ 0.0``: a sum never returns -0.0)
            w_cnt = np.zeros((nb_dense, n_other), np.float32)
            w_cnt.reshape(-1)[lin] = 1.0
            if np.count_nonzero(w_cnt) == hi:
                dense_fill = "assign"
                w_val = np.zeros((nb_dense, n_other), np.float32)
                w_val.reshape(-1)[lin] = v[:hi] + np.float32(0.0)
            else:
                # a pair repeats (a user who rated an item twice): sum.
                # bincount, because np.add.at is an unbuffered scalar
                # scatter, ~50-100× slower over the millions of
                # interactions the dense head holds
                dense_fill = "bincount"
                size = nb_dense * n_other
                w_cnt = np.bincount(lin, minlength=size).astype(
                    np.float32).reshape(nb_dense, n_other)
                w_val = np.bincount(lin, weights=v[:hi],
                                    minlength=size).astype(
                    np.float32).reshape(nb_dense, n_other)
            cnts = np.zeros(nb_dense, np.float32)
            cnts[:real] = counts_perm[:real]
            dense = _DenseHead(nb_dense, n_other, w_cnt, w_val, cnts)
        # rebase the remainder so the seg/ladder code below sees a
        # self-contained problem over positions [nb_dense, n_self)
        o, v = o[hi:], v[hi:]
        counts_perm = counts_perm[nb_dense:]
        starts = starts[nb_dense:] - hi
        n_self_rest = max(n_self - nb_dense, 0)
    else:
        n_self_rest = n_self
    buckets = []

    with tracing.span("als.prepare.fill"):
        # heavy entities (count > _C_MAX): one SEGMENTED bucket — each
        # entity spans ceil(count/C) rows of width C; the one-hot
        # ``seg`` matrix aggregates row partials per entity inside the
        # compiled program. Entities are count-descending, so these are
        # the first positions after the dense head and the output
        # concatenation order is preserved.
        if nb_seg:
            C = _C_MAX
            cnts = counts_perm[:nb_seg]
            rows_per = (cnts + C - 1) // C  # forced-in light entities: 1 row
            row_starts = np.zeros(nb_seg + 1, np.int64)
            np.cumsum(rows_per, out=row_starts[1:])
            n_rows = int(row_starts[-1])
            # slab capped at the (merged) row count: padding a small
            # bucket to a full 64MB slab made every tiny block solve
            # tens of thousands of identity systems
            slab = max(1, min(_SLAB_ELEMS // C, rows_cap))
            n_slabs = -(-rows_cap // slab)
            assert n_rows <= n_slabs * slab
            R = n_slabs * slab
            # a row is full but an entity's last (and the slab's
            # padding rows, which hold nothing)
            rowlen = np.zeros(R, np.int32)
            rowlen[:n_rows] = C
            has = rows_per > 0
            rowlen[row_starts[1:][has] - 1] = (cnts - (rows_per - 1) * C)[has]
            hi = int(starts[nb_seg])
            oi, vv, mm = _fill_rows(rowlen, C, o[:hi], v[:hi])
            row_ent = np.repeat(np.arange(nb_seg), rows_per)
            # slab-local one-hot: entity index relative to the slab's
            # first entity (rows are entity-sorted → ≤ slab consecutive
            # entities)
            if n_rows:
                seg_off = row_ent[np.minimum(np.arange(n_slabs) * slab,
                                             n_rows - 1)].astype(np.int32)
                local = row_ent - seg_off[np.arange(n_rows) // slab]
                seg = np.zeros((R, slab), np.float32)
                seg[np.arange(n_rows), local] = 1.0  # pad rows stay all-zero
            else:  # a device with no ratings in the (forced) seg range
                seg_off = np.zeros(n_slabs, np.int32)
                seg = np.zeros((R, slab), np.float32)
            buckets.append(_Bucket(
                C, nb_seg, slab, n_slabs,
                oi.reshape(n_slabs, slab, C),
                vv.reshape(n_slabs, slab, C),
                mm.reshape(n_slabs, slab, C),
                cnts.astype(np.float32),
                seg=seg.reshape(n_slabs, slab, slab),
                seg_off=seg_off))

        # the rest: one row per entity, padded to the bucket width
        e = nb_seg
        for C, nb in regs:
            slab = max(1, min(_SLAB_ELEMS // C, nb))
            n_slabs = -(-nb // slab)
            nb_pad = n_slabs * slab
            # forced boundaries may extend past this device's entities
            e_end = min(e + nb, n_self_rest)
            lo, hi = int(starts[min(e, n_self_rest)]), int(starts[e_end])
            rowlen = np.zeros(nb_pad, np.int32)
            rowlen[: max(e_end - e, 0)] = counts_perm[e:e_end]
            oi, vv, mm = _fill_rows(rowlen, C, o[lo:hi], v[lo:hi])
            buckets.append(_Bucket(
                C, nb, slab, n_slabs,
                oi.reshape(n_slabs, slab, C),
                vv.reshape(n_slabs, slab, C),
                mm.reshape(n_slabs, slab, C),
                rowlen.astype(np.float32).reshape(n_slabs, slab)))
            e += nb
    return _BucketSide(n_self, perm, inv_perm, buckets, dense=dense,
                       order_path=order_path, radix_passes=passes,
                       dense_fill=dense_fill)


@dataclass
class ALSPrepared:
    """Host-side prepared training layout (the analogue of MLlib ALS's
    InBlock construction — built once per dataset, reused across train
    calls; `bench.py` times training only, per BASELINE.md's
    "excluding data prep" protocol)."""

    n_users: int
    n_items: int
    nnz: int
    u_side: _BucketSide
    i_side: _BucketSide
    _device_bufs: Optional[dict] = None
    #: bytes :meth:`device_buffers` has sent to a device so far (a
    #: cached call sends none) — what the ``als.upload`` span reports
    uploaded_bytes: int = 0

    @property
    def geometry(self):
        return (self.u_side.geometry, self.i_side.geometry)

    def kernel_rows(self, rank: int) -> dict:
        """What the fused gather→Gram kernel is handed per iteration
        of a rank-``rank`` train when the Gram mode is fused, counted
        over the buckets that ``ops.gram.kernel_takes_width`` sends to
        it — the predicate ``bucket_systems`` in ``_make_half`` routes
        by, the one place that does: real (unpadded) interactions,
        padded slots (the layout's padding, still streamed as index
        and weights), bucket rows, the factor lines the kernel fetches
        — by either route: of them ``kernel_resident_rows`` are read
        from a table the dispatch holds in VMEM
        (``ops.gram.table_is_resident``, the predicate the kernel
        branches on; ``gram_table_bytes_u/i``: the table a side's
        half-step gathers from, the OTHER side's factors as the kernel
        lays them out), the rest are line copies — and the DMA waits
        that retire the copies. ``real ÷ padded`` is the share of the
        slots that hold an interaction, ``real ÷ dma`` the share of
        the fetches that bring a row somebody rated, ``waits ÷ dma``
        what is left of one wait a line."""
        from predictionio_tpu.ops.gram import (dma_waits, kernel_takes_width,
                                               table_bytes, table_is_resident)

        real = padded = rows = waits = held = 0
        for side, n_other in ((self.u_side, self.n_items),
                              (self.i_side, self.n_users)):
            resident = table_is_resident(n_other, rank)
            for b in side.buckets:
                if kernel_takes_width(b.C):
                    # one mask slot per interaction = the entities'
                    # counts (exact in f64; no pass over the mask)
                    n = int(b.counts.sum(dtype=np.float64))
                    real += n
                    rows += b.n_slabs * b.slab
                    padded += b.n_slabs * b.slab * b.C
                    if resident:
                        held += n
                    else:
                        # by the function the kernel takes its group
                        # sizes from (a segmented entity counts as one
                        # long row)
                        waits += dma_waits(b.counts, b.C)
        # the kernel is given each row's real length and fetches exactly
        # that many lines (``_gather_gram_kernel``: no rounding of a
        # length) — a kernel that rounded lengths up would count the
        # rounded ones here
        return {"kernel_real_rows": real, "kernel_padded_rows": padded,
                "kernel_bucket_rows": rows, "kernel_dma_rows": real,
                "kernel_dma_waits": waits, "kernel_resident_rows": held,
                "gram_table_bytes_u": table_bytes(self.n_items, rank),
                "gram_table_bytes_i": table_bytes(self.n_users, rank)}

    def layout_paths(self) -> dict:
        """Which data-dependent path :func:`_bucket_side` took on each
        side (attributes of the ``als.prepare`` span)."""
        return {"order_path_u": self.u_side.order_path,
                "order_path_i": self.i_side.order_path,
                "radix_passes_u": self.u_side.radix_passes,
                "radix_passes_i": self.i_side.radix_passes,
                "dense_fill_u": self.u_side.dense_fill,
                "dense_fill_i": self.i_side.dense_fill}

    def device_buffers(self, device=None):
        """Bucket arrays as device arrays (cached per device across
        train calls — a reused prep may be trained on different pinned
        devices, e.g. a `pio eval` grid over 1-device meshes)."""
        import jax
        import jax.numpy as jnp

        if self._device_bufs is None:
            self._device_bufs = {}
        if device not in self._device_bufs:
            def put(a):
                self.uploaded_bytes += a.nbytes
                return (jnp.asarray(a) if device is None
                        else jax.device_put(a, device))

            self._device_bufs[device] = jax.tree.map(
                put, (self.u_side.arrays(), self.i_side.arrays()))
        return self._device_bufs[device]


def als_prepare(coo: RatingsCOO) -> ALSPrepared:
    """Build the bucketed layout for single-device training."""
    with tracing.span("als.prepare.order"):
        cnt_u = _entity_counts(coo.user_idx, coo.n_users)
        cnt_i = _entity_counts(coo.item_idx, coo.n_items)
        perm_u, inv_u = _perm_by_count_desc(cnt_u)
        perm_i, inv_i = _perm_by_count_desc(cnt_i)
    u_side = _bucket_side(coo.user_idx, coo.item_idx, inv_i, coo.rating,
                          coo.n_users, cnt_u, perm_u, inv_u,
                          n_other=coo.n_items)
    i_side = _bucket_side(coo.item_idx, coo.user_idx, inv_u, coo.rating,
                          coo.n_items, cnt_i, perm_i, inv_i,
                          n_other=coo.n_users)
    return ALSPrepared(coo.n_users, coo.n_items, coo.nnz, u_side, i_side)



def als_train(
    coo: RatingsCOO,
    params: ALSParams,
    mesh=None,
    checkpointer=None,
    checkpoint_every: int = 0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Train ALS; returns (U [n_users,k], V [n_items,k]) as numpy arrays.

    ``mesh`` (a jax.sharding.Mesh with a ``"data"`` axis) enables the
    sharded path; None runs single-device. ``checkpointer`` +
    ``checkpoint_every`` enable mid-train checkpoint/resume on BOTH
    paths: the single-device loop and the sharded trainer split their
    iteration scan at block boundaries and save the factors after each
    block (see :func:`als_train_prepared` /
    :func:`als_sharded.als_train_sharded_prepared`).
    """
    if mesh is not None and np.prod(mesh.devices.shape) > 1:
        from predictionio_tpu.models.als_sharded import als_train_sharded

        return als_train_sharded(coo, params, mesh,
                                 checkpointer=checkpointer,
                                 checkpoint_every=checkpoint_every)
    # a 1-device mesh still pins the platform: run the single-device path
    # on THAT device, not wherever the default backend happens to live
    device = mesh.devices.flat[0] if mesh is not None else None
    with tracing.span("als.prepare", nnz=int(coo.nnz)):
        prep = als_prepare(coo)
        tracing.add_attrs(**prep.kernel_rows(params.rank),
                          **prep.layout_paths())
    return als_train_prepared(prep, params, device=device,
                              checkpointer=checkpointer,
                              checkpoint_every=checkpoint_every)


def als_train_many(
    coo: RatingsCOO,
    params_list,
    mesh=None,
) -> list:
    """Train one (U, V) per params on the SAME ratings — the `pio eval`
    grid fan-out (SURVEY.md §2d P4; reference behavior: MLlib grids
    re-run ALS per candidate from scratch).

    Costs shared across the grid:
    - the bucketed host layout is prepared ONCE (``als_prepare`` /
      ``als_prepare_sharded``) and its device upload is cached per
      device/mesh (``device_buffers``);
    - candidates differing only in ``reg``/``alpha`` share ONE compiled
      executable — both enter the kernel as traced scalars — so the
      canonical regularization grid compiles the train program once.
      Distinct ``rank``/``iterations``/``implicit``/``weighted_reg``
      still compile per distinct value (they change program shape or
      structure), amortized by ``_compiled_bucketed``'s lru_cache and
      the persistent XLA cache.
    """
    params_list = list(params_list)
    if mesh is not None and np.prod(mesh.devices.shape) > 1:
        from predictionio_tpu.models.als_sharded import (
            als_prepare_sharded,
            als_train_sharded_prepared,
        )

        sprep = als_prepare_sharded(coo, int(np.prod(mesh.devices.shape)))
        return [als_train_sharded_prepared(sprep, p, mesh)
                for p in params_list]
    device = mesh.devices.flat[0] if mesh is not None else None
    prep = als_prepare(coo)
    return [als_train_prepared(prep, p, device=device)
            for p in params_list]


def _make_half(k: int, implicit: bool, weighted_reg: bool, pvary=None,
               bf16_gather: bool = False,
               precision: str = "high", gram_mode: str = "off"):
    """Build the half-step program shared by the single-device and
    sharded (shard_map) paths:
    ``half(F_other, bufs, geometry, reg, alpha)`` — one full re-solve
    of one side's factors from the other side's.

    ``reg`` and ``alpha`` are TRACED scalar inputs: they enter the
    kernel only as multiplies, so an eval grid over regularization (the
    canonical ALS grid) shares ONE compiled executable across
    candidates instead of paying a full XLA compile per reg value.
    ``implicit`` and ``weighted_reg`` stay Python-static — they change
    the program's structure, not its constants.

    ``precision`` selects the Gram-einsum MXU precision: "high"
    (default, 3-pass) or "highest" (6-pass) via ``PIO_ALS_PRECISION``
    — CPU CI ignores the precision argument entirely, so the knob
    exists to let an on-device run A/B the two modes when triaging a
    numerical regression (ADVICE r3).

    The walk over a side is written once, in ``half``: the dense head
    and then every bucket (``bucket_systems``) build ridged normal
    equations and hand them to ``finish``. While the solve buffer fits
    (``_SOLVE_BUF_MB``) ``finish`` passes ``(A, b)`` on, all parts are
    concatenated into one buffer and a single chunked scan solves the
    whole side with ONE instance of the block-recursive batched
    Cholesky (compile-time bound — see ``_SOLVE_CHUNK``); catalogs too
    large for the buffer solve in ``finish`` itself, inside each bucket
    body (memory flat in catalog size). No scatter anywhere in the
    program but the segmented bucket's few hundred blocks.

    ``pvary`` marks created constants as varying over the mesh axis
    when tracing inside ``shard_map`` (vma typing); identity otherwise.

    ``gram_mode`` selects the gather→Gram implementation (resolved by
    :func:`predictionio_tpu.ops.resolve_gram_mode` from
    ``PIO_PALLAS_GRAM`` and the platform the trace will run on):
    ``"off"`` is the XLA gather + packed einsum with its per-bucket
    slab ``lax.scan``s; ``"pallas"`` / ``"interpret"`` route every
    bucket of width ≥ 128 through the fused
    :func:`predictionio_tpu.ops.gather_gram` kernel — the slab scans
    flatten into ONE fat kernel dispatch per bucket, the seg merge
    becomes one einsum + one (tiny) scatter-add (narrower buckets stay
    on XLA — :func:`predictionio_tpu.ops.gram.kernel_takes_width`).
    The solve follows the Gram mode: under ``"pallas"`` it is the VMEM
    Cholesky kernel (the ~50-op XLA solve recursion would re-create
    the dispatch wall the Gram fusion removes), else the XLA recursion.
    """
    import jax
    import jax.numpy as jnp

    pv = pvary if pvary is not None else (lambda x: x)
    eye = jnp.eye(k, dtype=jnp.float32)
    prec = (jax.lax.Precision.HIGHEST if precision == "highest"
            else jax.lax.Precision.HIGH)
    fused = gram_mode in ("pallas", "interpret")
    interp = gram_mode == "interpret"

    from predictionio_tpu.ops import gram as ops_gram
    from predictionio_tpu.ops.cholesky import chol_solve_batched as _csb

    # ``jax.named_scope`` here and on the stages below: names for the
    # profiler's trace only (the op_name of every operation of a stage
    # starts with its scope); the programs compute what they computed
    chol_solve_batched = jax.named_scope("als.solve")(functools.partial(
        _csb, kernel=(_solve_mode(gram_mode) == "pallas")))

    # reg/alpha are bound per trace by ``half`` (traced scalars shared
    # by every helper below via this cell — threading them through five
    # helper signatures would obscure the kernel structure)
    _ra: dict = {}

    def weights(v_s, m_s):
        alpha = _ra["alpha"]
        if implicit:
            return (alpha * v_s) * m_s, (1.0 + alpha * v_s) * m_s
        return m_s, v_s * m_s

    @jax.named_scope("als.gram_xla")
    def row_grams(F_other, oi_s, v_s, m_s):
        """One slab's per-row normal-equation partials on the MXU.

        A and b are built by ONE packed einsum: H = [w_o·F | w_b] is a
        (slab, C, k+1) block, and F'H = [A | b]. Computing b separately
        ("nc,nck->nk") lowered to a VPU multiply-reduce that measured
        ~45 ms/iteration at ML-20M — pure overhead next to the A matmul
        the MXU was already doing; packed, it is one extra MXU column.

        HIGH (3-pass bf16 ≈ f32): normal equations need f32-grade MXU
        passes — single-pass bf16 Gram error is ~3e-1 vs 6e-5 (see
        ops/gram.py) and the Cholesky solve amplifies it. HIGHEST
        (6-pass) halves MXU throughput for precision ALS cannot use:
        measured iterate divergence HIGH-vs-HIGHEST after 10 iterations
        is ~1e-4 relative — f32 solve noise level, far inside the
        parity-test tolerances."""
        F = F_other[oi_s]                               # (slab, C, k)
        wo, wb = weights(v_s, m_s)
        if bf16_gather:
            # F_other arrives pre-cast to bf16 (one pass per half
            # step); weights round to bf16 and the MXU runs a single
            # pass with f32 accumulation
            H = jnp.concatenate(
                [(wo[..., None] * F).astype(jnp.bfloat16),
                 wb[..., None].astype(jnp.bfloat16)], axis=-1)
            return jnp.einsum("nck,ncl->nkl", F, H,
                              preferred_element_type=jnp.float32)
        H = jnp.concatenate([wo[..., None] * F, wb[..., None]], axis=-1)
        return jnp.einsum("nck,ncl->nkl", F, H,
                          precision=prec,
                          preferred_element_type=jnp.float32)

    def ridge(A, cnt_s, G):
        reg = _ra["reg"]
        if implicit:
            A = A + G[None, :, :]
        lam = reg * cnt_s if weighted_reg else reg * jnp.ones_like(cnt_s)
        lam = jnp.where(cnt_s > 0, jnp.maximum(lam, 1e-8), 1.0)
        return A + lam[:, None, None] * eye

    def fused_grams(F_g, oi2, v2, m2):
        """All of a bucket's rows through ONE fused gather→Gram kernel
        dispatch (``ops.gather_gram``): the weights are two cheap XLA
        elementwise ops streamed as kernel operands, the gather and the
        Gram run inside the kernel, and only the (R, k, k) / (R, k)
        normal-equation blocks come back — the gathered (R, C, k)
        factor block never exists in HBM."""
        wo, wb = weights(v2, m2)
        # the mask is a prefix of every row (``_bucket_side``), so its
        # row sum is the row's real length: the kernel fetches that
        # many lines and no padding (exact in f32: a row has ≤ 8192)
        lengths = m2.sum(axis=1).astype(jnp.int32)
        return ops_gram.gather_gram(F_g, oi2, wo, wb, lengths,
                                    interpret=interp)

    def bucket_systems(F_g, geom, buf, G, finish):
        """One bucket → ``finish(A_ridged, b)``, rows in bucket order
        (a segmented bucket's ``nb`` entities, a regular bucket's
        ``n_slabs · slab`` padded rows). The ONE place that routes a
        bucket between the fused kernel and XLA's gather + einsum —
        ``fused and kernel_takes_width(C)`` — and that flattens
        ``(n_slabs, slab, C)`` to the kernel's ``(R, C)``.

        Through the kernel a bucket is one dispatch over all its rows
        (the kernel streams (RB, C) row blocks through VMEM itself) and
        ``finish`` runs once; through XLA it is a ``lax.scan`` over the
        slabs with ``finish`` inside the body, so a caller that solves
        in ``finish`` never holds a narrow bucket's Grams for all rows.

        Segmented bucket: entities span rows; each slab aggregates its
        per-row partials into ≤ slab consecutive entities with one
        (slab, slab) × (slab, k·(k+1)) matmul (slab-local one-hot),
        added into the per-entity buffer at the slab's entity offset
        (over-allocated by one slab so the update never clamps) —
        through the kernel, one batched einsum and ONE scatter-add of
        the slab-local blocks (n_seg_rows ≈ hundreds of k×(k+1)
        blocks; the no-scatter rule is about ~nnz/W-row scatters)."""
        C, nb, slab, n_slabs, is_seg = geom
        oi, vv, mm, cnt = buf[:4]
        R = n_slabs * slab
        kernel = fused and ops_gram.kernel_takes_width(C)
        if kernel:
            A_r, b_r = fused_grams(F_g, oi.reshape(R, C),
                                   vv.reshape(R, C), mm.reshape(R, C))
        if is_seg:
            seg, seg_off = buf[4:]
            if kernel:
                Ab_r = jnp.concatenate([A_r, b_r[:, :, None]], axis=-1)
                Ab_l = jnp.einsum("nre,nrkm->nekm", seg,
                                  Ab_r.reshape(n_slabs, slab, k, k + 1),
                                  precision=prec,
                                  preferred_element_type=jnp.float32)
                ids = seg_off[:, None] + jnp.arange(slab, dtype=jnp.int32)
                Ab_e = pv(jnp.zeros((nb + slab, k, k + 1),
                                    jnp.float32)).at[ids].add(Ab_l)
            else:
                def seg_body(Ab_e, chunk):
                    oi_s, v_s, m_s, seg_s, off_s = chunk
                    Ab_r = row_grams(F_g, oi_s, v_s, m_s)  # (slab, k, k+1)
                    Ab_l = jnp.einsum("ne,nkm->ekm", seg_s, Ab_r,
                                      precision=prec,
                                      preferred_element_type=jnp.float32)
                    blk = jax.lax.dynamic_slice(Ab_e, (off_s, 0, 0),
                                                (slab, k, k + 1))
                    Ab_e = jax.lax.dynamic_update_slice(Ab_e, blk + Ab_l,
                                                        (off_s, 0, 0))
                    return Ab_e, None

                init = pv(jnp.zeros((nb + slab, k, k + 1), jnp.float32))
                Ab_e, _ = jax.lax.scan(seg_body, init,
                                       (oi, vv, mm, seg, seg_off))
            return finish(ridge(Ab_e[:nb, :, :k], cnt, G), Ab_e[:nb, :, k])
        if kernel:
            return finish(ridge(A_r, cnt.reshape(R), G), b_r)

        def body(_, chunk):
            oi_s, v_s, m_s, cnt_s = chunk
            Ab = row_grams(F_g, oi_s, v_s, m_s)
            return None, finish(ridge(Ab[..., :k], cnt_s, G), Ab[..., k])

        if n_slabs == 1:
            return body(None, (oi[0], vv[0], mm[0], cnt[0]))[1]
        _, out = jax.lax.scan(body, None, (oi, vv, mm, cnt))
        return jax.tree.map(lambda a: a.reshape((R,) + a.shape[2:]), out)

    @jax.named_scope("als.dense_head")
    def dense_equations(F_other, dbuf, G):
        """Dense head: normal equations for the heaviest entities as
        two GEMMs over the FULL other side — A rows against the factor
        outer products, b rows against the factors — replacing the
        gathered seg path that measured ~70% of the Gram phase at
        ML-20M (~280 entities holding ~65% of padded slots). No gather,
        no scan: pure MXU work."""
        w_cnt, w_val, cnt = dbuf
        if implicit:
            alpha = _ra["alpha"]
            wo_m, wb_m = alpha * w_val, w_cnt + alpha * w_val
        else:
            wo_m, wb_m = w_cnt, w_val
        n_other = F_other.shape[0]
        FF = (F_other[:, :, None] * F_other[:, None, :]).reshape(
            n_other, k * k)
        A = jnp.einsum("nc,cm->nm", wo_m, FF,
                       precision=prec,
                       preferred_element_type=jnp.float32
                       ).reshape(-1, k, k)
        b = jnp.einsum("nc,ck->nk", wb_m, F_other,
                       precision=prec,
                       preferred_element_type=jnp.float32)
        return ridge(A, cnt, G), b

    def solve_buffer(systems, chunk, n_chunks):
        """The materialised finish: every part's ridged ``(A, b)``
        concatenated into one solve buffer that a single chunked scan
        solves — ONE Cholesky instance in the program. The parts come
        out of the bucket scans as ``ys`` (not a carried buffer updated
        with dynamic_update_slice): the carry pattern measured +116 ms
        per ML-20M half-step in buffer copies."""
        N_pad = n_chunks * chunk
        A_parts = [A for A, _ in systems]
        b_parts = [b for _, b in systems]
        n_rows = sum(b.shape[0] for b in b_parts)
        if n_rows < N_pad:  # tail pad: identity systems, x = 0
            A_parts.append(pv(jnp.zeros((N_pad - n_rows, k, k),
                                        jnp.float32) + eye))
            b_parts.append(pv(jnp.zeros((N_pad - n_rows, k),
                                        jnp.float32)))
        A_all = jnp.concatenate(A_parts) if len(A_parts) > 1 else A_parts[0]
        b_all = jnp.concatenate(b_parts) if len(b_parts) > 1 else b_parts[0]
        if n_chunks == 1:
            return chol_solve_batched(A_all, b_all)
        _, xc = jax.lax.scan(
            lambda _, ab: (None, chol_solve_batched(*ab)), None,
            (A_all.reshape(n_chunks, chunk, k, k),
             b_all.reshape(n_chunks, chunk, k)))
        return xc.reshape(N_pad, k)

    def half(F_other, bufs_side, geometry, reg, alpha):
        # bind the traced scalars for every helper above; pv marks them
        # device-varying under shard_map (they arrive replicated)
        _ra["reg"] = pv(jnp.asarray(reg, jnp.float32))
        _ra["alpha"] = pv(jnp.asarray(alpha, jnp.float32))
        n_self, dense_geom, bucket_geoms = geometry
        dense_buf, bufs = bufs_side
        # bf16 gather mode: ONE cast pass per half-step; every bucket
        # gather then moves half the bytes (dense head and the implicit
        # Gram stay f32)
        F_g = (F_other.astype(jnp.bfloat16) if bf16_gather else F_other)
        G = None
        if implicit:
            with jax.named_scope("als.yty"):
                G = jnp.einsum("nk,nl->kl", F_other, F_other,
                               precision=prec,
                               preferred_element_type=jnp.float32)
        # the parts of a side, in output order: [dense head] + buckets,
        # each (real entities, rows it hands on): the dense head and
        # seg buckets emit nb exact rows, regular buckets their padded
        # slabs
        sizes = ([(dense_geom[0], dense_geom[0])]
                 if dense_geom is not None else []) + \
            [(nb, nb if is_seg else n_slabs * slab)
             for (C, nb, slab, n_slabs, is_seg) in bucket_geoms]
        nbs = [nb for nb, _ in sizes]
        spans = [span for _, span in sizes]
        # solve chunk shrinks for small sides (sharded per-device
        # blocks) so the floor isn't thousands of padded identity solves
        chunk = min(_SOLVE_CHUNK, max(256, -(-sum(spans) // 256) * 256))
        n_chunks = max(1, -(-sum(spans) // chunk))
        # two ways of finishing a part: hand its (A, b) on to the one
        # solve buffer while that fits, else solve it where it is built
        # (huge catalog: memory flat in catalog size; compiles one
        # Cholesky per bucket)
        materialised = n_chunks * chunk * k * k * 4 <= _SOLVE_BUF_MB << 20
        finish = ((lambda A, b: (A, b)) if materialised
                  else chol_solve_batched)
        parts = []
        if dense_geom is not None:
            parts.append(finish(*dense_equations(F_other, dense_buf, G)))
        for geom, buf in zip(bucket_geoms, bufs):
            parts.append(bucket_systems(F_g, geom, buf, G, finish))
        offs = [0] * len(parts)
        if materialised:  # every part's solution is a window of x_all
            parts = [solve_buffer(parts, chunk, n_chunks)] * len(parts)
            offs = [sum(spans[:i]) for i in range(len(spans))]
        outs = [x[off:off + nb] for x, off, nb in zip(parts, offs, nbs)]
        total = sum(nbs)
        if total < n_self:  # zero-rating tail entities → zero factors
            outs.append(pv(jnp.zeros((n_self - total, k), jnp.float32)))
        out = jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
        # forced (merged) boundaries can exceed n_self; extras are zeros
        return out[:n_self] if total > n_self else out

    return half


def _solve_mode(gram_mode: str) -> str:
    """The solve follows the Gram mode: the VMEM Cholesky kernel
    (``"pallas"``) exactly when the Gram is the compiled fused kernel,
    else the XLA recursion (``"xla"``) — the interpreter mode included,
    which exists to test the Gram routing on a CPU."""
    return "pallas" if gram_mode == "pallas" else "xla"


def log_train_modes(platform: str, gram_mode: str, n_devices: int) -> str:
    """Say which Gram and which solve implementation this train runs —
    the selection is by rule (``ops.resolve_gram_mode``, and the solve
    follows it: :func:`_solve_mode`), so it can be stated up front.
    Returns the solve mode."""
    solve = _solve_mode(gram_mode)
    log.info("ALS train: platform=%s devices=%d gram=%s solve=%s",
             platform, n_devices, gram_mode, solve)
    return solve


def _gram_precision() -> str:
    """Gram-einsum precision mode from ``PIO_ALS_PRECISION`` ("high"
    default; "highest" restores the 6-pass MXU mode for on-device
    numerical triage — see ``_make_half``)."""
    return os.environ.get("PIO_ALS_PRECISION", "high").lower()


@functools.lru_cache(maxsize=8)
def _compiled_bucketed(geom_u, geom_i, n_users: int, n_items: int,
                       rank: int, iterations: int,
                       implicit: bool, weighted_reg: bool,
                       platform: Optional[str] = None,
                       bf16_gather: bool = False,
                       precision: str = "high",
                       gram_mode: str = "off"):
    """Build + jit the full single-device training program for one
    problem geometry (two `_make_half` programs under one iteration
    scan). ``reg`` and ``alpha`` are traced inputs of the returned
    ``train(u_bufs, i_bufs, V0p, reg, alpha)``, so a `pio eval` grid
    over regularization/alpha shares ONE executable; candidates
    recompile only when rank/iterations (or the implicit/weighted_reg
    program structure) change. ``platform`` is not read — the Gram
    mode, resolved for the platform by the caller, decides everything
    the platform did; it keeps its place in the positional list that
    ``benchmark/compile_check.py`` calls (ROADMAP D1b)."""
    import jax
    import jax.numpy as jnp

    k = rank
    half = _make_half(k, bool(implicit), bool(weighted_reg),
                      bf16_gather=bf16_gather,
                      precision=precision, gram_mode=gram_mode)

    def train(u_bufs, i_bufs, V0p, reg, alpha):
        if iterations == 0:
            # U-recovery program: derive U from already-converged V (the
            # resume path when a run died between its final checkpoint
            # and model persistence)
            return half(V0p, u_bufs, geom_u, reg, alpha), V0p

        def step(carry, _):
            U, V = carry
            U = half(V, u_bufs, geom_u, reg, alpha)
            V = half(U, i_bufs, geom_i, reg, alpha)
            return (U, V), None

        U0 = jnp.zeros((n_users, k), jnp.float32)
        (U, V), _ = jax.lax.scan(step, (U0, V0p), None, length=iterations)
        return U, V

    return jax.jit(train)


@functools.lru_cache(maxsize=1)
def _unpermute_pack():
    import jax
    import jax.numpy as jnp

    @jax.named_scope("als.unpermute_pack")
    def f(U, V, inv_u, inv_v):
        return jnp.concatenate([jnp.take(U, inv_u, axis=0),
                                jnp.take(V, inv_v, axis=0)], axis=0)

    return jax.jit(f)


def als_train_prepared(prep: ALSPrepared, p: ALSParams, device=None,
                       checkpointer=None, checkpoint_every: int = 0,
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Train from a prepared layout; returns (U, V) in ORIGINAL entity
    order as numpy arrays.

    With ``checkpointer`` + ``checkpoint_every > 0`` the iteration loop
    runs in blocks of ``checkpoint_every`` iterations, saving the
    (permuted) V factors after each block — an interrupted train
    restarted with the same checkpointer resumes from the newest block
    and produces the same result as an uninterrupted run (V fully
    determines the next iteration; U is recomputed from V). This is the
    SURVEY §5 restart-from-checkpoint contract; the checkpoint cadence
    costs one extra dispatch + a host fetch of V per block.
    """
    import jax
    import jax.numpy as jnp

    def put(a):
        return jnp.asarray(a) if device is None else jax.device_put(a, device)

    platform = (device.platform if device is not None
                else jax.default_backend())
    # resolved HERE (not inside the lru_cached builder) so an env flip
    # between calls can't be shadowed by a stale cache entry — the mode
    # is part of the cache key
    from predictionio_tpu import ops

    gram_mode = ops.resolve_gram_mode(platform)
    solve_mode = log_train_modes(platform, gram_mode, n_devices=1)

    def compiled(n_iters: int):
        return _compiled_bucketed(
            prep.u_side.geometry, prep.i_side.geometry,
            prep.n_users, prep.n_items,
            p.rank, n_iters, bool(p.implicit),
            bool(p.weighted_reg), platform,
            bool(p.bf16_gather), _gram_precision(), gram_mode)

    def iterate(n_iters: int, V, then=None):
        """One block of iterations, timed up to the point where its
        factors are ready. ``then(U, V)`` is dispatched behind the block
        BEFORE the host waits, so the wait adds no synchronisation that
        was not there: the host would block on these values in its next
        fetch anyway."""
        with tracing.span("als.iterate", iterations=n_iters,
                          gram=gram_mode, solve=solve_mode):
            U, V = compiled(n_iters)(u_bufs, i_bufs, V, reg_a, alpha_a)
            queued = then(U, V) if then is not None else None
            jax.block_until_ready((U, V))
        return U, V, queued

    reg_a = np.float32(p.reg)
    alpha_a = np.float32(p.alpha)

    start = 0
    U0 = None  # restored U (only consumed when start == iterations)
    with tracing.span("als.init"):
        V0 = init_factors(prep.n_items, p.rank, p.seed)[prep.i_side.perm]
        if (checkpointer is not None
                and checkpointer.latest_step() is not None):
            from predictionio_tpu.utils.checkpoint import (
                CheckpointGeometryError)

            template = {"U": np.zeros((prep.n_users, p.rank), np.float32),
                        "V": np.zeros_like(V0)}
            try:
                state, step = checkpointer.restore_latest_compatible(
                    template)
                V0 = np.asarray(state["V"])
                U0 = np.asarray(state["U"])
                start = min(int(step), p.iterations)
            except CheckpointGeometryError:
                # CONFIRMED stale (different geometry/rank): fresh start,
                # and the dir must be WIPED, else the fresh run's lower
                # step numbers stay shadowed by the stale latest_step and
                # every future resume restores the bad checkpoint again.
                # Transient read errors propagate instead — wiping on
                # those would destroy valid checkpoints (ADVICE r3).
                import warnings

                warnings.warn(
                    "ALS checkpoints are stale (geometry/format change) — wiped; training restarts from scratch", RuntimeWarning)
                checkpointer.clear()

    # died between the final checkpoint and model persistence: the
    # train is already done, nothing to recompute
    done = start >= p.iterations and U0 is not None
    with tracing.span("als.upload") as sp:
        sent = prep.uploaded_bytes
        u_bufs, i_bufs = prep.device_buffers(device)
        small = [V0, prep.u_side.inv_perm, prep.i_side.inv_perm]
        V, inv_u, inv_v = (put(a) for a in small)
        U = None
        if done:
            small.append(U0)
            U = put(U0)
        sp.set_attr("bytes", int(prep.uploaded_bytes - sent
                                 + sum(a.nbytes for a in small)))

    def unpermute(U, V):
        # un-permute to original entity order ON DEVICE, U and V as ONE
        # packed array: each device→host fetch is a full round trip,
        # and the device does the fancy-index copy faster than the host
        # would
        return _unpermute_pack()(U, V, inv_u, inv_v)

    packed = None
    if not done and (checkpointer is None or checkpoint_every <= 0
                     or p.iterations == 0):  # its U-recovery program has
        # no blocks to checkpoint; without this, the block loop below
        # never runs and the not-None assert fires (r5 review)
        U, V, packed = iterate(p.iterations - start, V, then=unpermute)
    elif not done:
        it = start
        while it < p.iterations:
            n = min(checkpoint_every, p.iterations - it)
            U, V, _ = iterate(n, V)
            it += n
            with tracing.span("als.checkpoint", step=it) as sp:
                state = {"U": np.asarray(U), "V": np.asarray(V)}
                checkpointer.save(it, state)
                sp.set_attr("bytes",
                            int(state["U"].nbytes + state["V"].nbytes))
        assert U is not None  # start < iterations here, loop ran
    with tracing.span("als.fetch") as sp:
        if packed is None:
            packed = unpermute(U, V)
        packed = np.asarray(packed)
        sp.set_attr("bytes", int(packed.nbytes))
    return packed[:prep.n_users], packed[prep.n_users:]


@functools.lru_cache(maxsize=8)
def als_train_scored(geom_u, geom_i, n_users: int, n_items: int,
                     rank: int, iterations: int,
                     implicit: bool, weighted_reg: bool,
                     bf16_gather: bool = False,
                     precision: str = "high",
                     gram_mode: str = "off"):
    """Pure vmappable train+score half of the distributed sweep
    (core/sweep.py): ``one(hyper, u_bufs, i_bufs, V0p, uq, iq, rq,
    valid) -> (sq_err_sum, valid_count)`` with ``hyper = [reg, alpha]``
    a TRACED row of the stacked grid. The training body is EXACTLY
    :func:`_compiled_bucketed`'s (same ``_make_half`` statics, same
    iteration scan, same zero-U0 start), with the held-out fold scored
    on-device: ``uq``/``iq`` index PERMUTED factor rows (callers map
    through ``inv_perm`` on the host), ``valid`` masks cold pairs —
    matching NegRMSE's skip-empty-prediction convention — so a
    candidate with zero warm pairs returns count 0 (NaN downstream,
    ranks last, never poisons the batch)."""
    import jax
    import jax.numpy as jnp

    k = rank
    half = _make_half(k, bool(implicit), bool(weighted_reg),
                      bf16_gather=bf16_gather,
                      precision=precision, gram_mode=gram_mode)

    def one(hyper, u_bufs, i_bufs, V0p, uq, iq, rq, valid):
        reg, alpha = hyper[0], hyper[1]

        def step(carry, _):
            U, V = carry
            U = half(V, u_bufs, geom_u, reg, alpha)
            V = half(U, i_bufs, geom_i, reg, alpha)
            return (U, V), None

        U0 = jnp.zeros((n_users, k), jnp.float32)
        (U, V), _ = jax.lax.scan(step, (U0, V0p), None, length=iterations)
        pred = (jnp.take(U, uq, axis=0) * jnp.take(V, iq, axis=0)).sum(-1)
        err = jnp.where(valid, (pred - rq) ** 2, 0.0)
        return err.sum(), valid.astype(jnp.float32).sum()

    return one


def als_sweep_program(prep: ALSPrepared, p0: ALSParams,
                      users: np.ndarray, items: np.ndarray,
                      ratings: np.ndarray, valid: np.ndarray,
                      device=None):
    """Assemble the ``(geometry, build, data)`` triple core/sweep.py's
    SweepProgram wants for a bucket of ALS candidates sharing compile
    geometry (rank/iterations/implicit/weighted_reg/seed + the prepared
    layout). ``users``/``items`` are fold-local dense entity ids (cold
    pairs carry any in-range id with ``valid`` False); they are mapped
    to permuted factor positions HERE so the device program gathers
    directly. Hyper rows are ``[reg, alpha]``."""
    import jax

    platform = (device.platform if device is not None
                else jax.default_backend())
    from predictionio_tpu import ops

    gram_mode = ops.resolve_gram_mode(platform)
    precision = _gram_precision()
    geometry = ("als_scored", prep.u_side.geometry, prep.i_side.geometry,
                prep.n_users, prep.n_items, int(p0.rank),
                int(p0.iterations), bool(p0.implicit),
                bool(p0.weighted_reg), platform, bool(p0.bf16_gather),
                precision, gram_mode, int(p0.seed), len(users))
    u_bufs, i_bufs = prep.device_buffers(device)
    V0p = init_factors(prep.n_items, p0.rank, p0.seed)[prep.i_side.perm]
    uq = prep.u_side.inv_perm[np.asarray(users, np.int64)].astype(np.int32)
    iq = prep.i_side.inv_perm[np.asarray(items, np.int64)].astype(np.int32)
    data = (u_bufs, i_bufs, V0p.astype(np.float32), uq, iq,
            np.asarray(ratings, np.float32), np.asarray(valid, bool))

    def build():
        return als_train_scored(
            prep.u_side.geometry, prep.i_side.geometry,
            prep.n_users, prep.n_items, int(p0.rank), int(p0.iterations),
            bool(p0.implicit), bool(p0.weighted_reg),
            bool(p0.bf16_gather), precision, gram_mode)

    return geometry, build, data


# -- scoring ------------------------------------------------------------------


def predict_ratings(U: np.ndarray, V: np.ndarray, users: np.ndarray,
                    items: np.ndarray) -> np.ndarray:
    """r̂ for (user, item) pairs."""
    return np.einsum("nk,nk->n", U[users], V[items])


def recommend(
    U: np.ndarray, V: np.ndarray, user: int, num: int,
    exclude: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Top-``num`` items for one user → (item_indices, scores)."""
    scores = V @ U[user]
    if exclude is not None and exclude.size:
        scores = scores.copy()
        scores[exclude] = -np.inf
    num = min(num, scores.shape[0])
    top = np.argpartition(-scores, num - 1)[:num]
    top = top[np.argsort(-scores[top])]
    return top, scores[top]


def _gather_score_topk_impl(U, Vp, user_ids, rows_valid=None, *, k: int,
                            n_valid: int, pallas: bool, tile: int):
    import jax.numpy as jnp

    from predictionio_tpu import ops

    Q = U[user_ids]
    if pallas:
        vals, idx = ops.score_topk(Q, Vp, k, tile=tile, n_valid=n_valid,
                                   rows_valid=rows_valid)
    else:
        vals, idx = ops.score_topk_xla(Q, Vp, k, n_valid=n_valid,
                                       rows_valid=rows_valid)
    # pack (vals, idx) into ONE output array: each device→host fetch is
    # a full round trip, so a query must fetch exactly once. Item
    # indices are exact in f32 (< 2^24).
    return jnp.concatenate([vals, idx.astype(jnp.float32)], axis=-1)


@functools.lru_cache(maxsize=1)
def _gather_score_topk_jit():
    import jax

    return jax.jit(_gather_score_topk_impl,
                   static_argnames=("k", "n_valid", "pallas", "tile"))


def _gather_score_topk(U, Vp, user_ids, *, k: int, n_valid: int,
                       pallas: bool, tile: int, rows_valid=None):
    """The p50-critical serving program: gather + score + top-k as ONE
    compiled dispatch, ONE packed host fetch. Eager composition here
    costs a host↔device round trip per op, and a second output fetch
    would double the floor again."""
    import jax.numpy as jnp

    packed = np.asarray(_gather_score_topk_jit()(
        U, Vp, jnp.asarray(user_ids, jnp.int32), rows_valid, k=k,
        n_valid=n_valid, pallas=pallas, tile=tile))
    return packed[..., :k], packed[..., k:].astype(np.int32)


def _bucket_k(want: int) -> int:
    """Serving k bucketed to powers of two from 16 (bounds the set of
    compiled programs; shared by the hot path and the AOT warmup so
    they agree on which executables exist)."""
    k = 16
    while k < want:
        k *= 2
    return k


_SERVE_MIN_ITEMS = 2048


def serve_on_device(n_items: int) -> bool:
    """The device-vs-host serving policy shared by every scorer
    selector (:func:`maybe_resident_scorer` and the ANN twin
    ``ann.scorer.maybe_ann_scorer``): device-resident serving for
    production-size catalogs (≥ ``_SERVE_MIN_ITEMS`` items), host
    numpy below that, where a matvec beats a device dispatch and
    tests/demos stay free of compile time. ``PIO_ALS_SERVE``
    overrides: "host" forces the host path, "device" forces a
    scorer."""
    mode = os.environ.get("PIO_ALS_SERVE", "auto")
    if mode == "host":
        return False
    return mode != "auto" or n_items >= _SERVE_MIN_ITEMS


def maybe_resident_scorer(U, V, cached=None):
    """Serving-path policy shared by the ALS-family templates: a lazy
    device-resident :class:`ResidentScorer` when
    :func:`serve_on_device` says so, else None (→ host numpy scoring).
    Pass the previous return value as ``cached`` so the scorer is
    built once per model; a cached scorer is reused only if it was
    built from these exact U/V arrays (identity check) — a caller that
    retrains and swaps factors gets a fresh scorer, never stale
    scores.
    """
    if not serve_on_device(V.shape[0]):
        return None
    if cached is not None and cached.built_from(U, V):
        return cached
    return ResidentScorer(U, V)


def serve_topk_batch(scorer, user_ids, item_inv, queries, fallback,
                     per_query=None):
    """Serve a micro-batch of top-k queries in ONE device dispatch.

    The shared implementation behind the templates' ``batch_predict``
    (`pio deploy --batching`, batchpredict, evaluation — SURVEY §3.2
    continuous-batching contract): collect every top-k-shaped query,
    score them all through ``scorer.recommend_batch`` with a single
    padded ``k = max(num)``, slice per row. Queries ``per_query``
    flags (e.g. rating-prediction shapes) and unknown users fall back
    without touching the device; ``scorer=None`` (host-path catalogs,
    :func:`maybe_resident_scorer`) serves everything via ``fallback``.

    ``user_ids``: str id → row index mapping (``.get``);
    ``item_inv``: row index → item id; ``fallback``: per-query callable
    returning a response dict.

    AOT-bucket ``PAD`` sentinels (``server/aot.PAD``, appended by the
    MicroBatcher to fill a batch up to its bucket) are never served:
    their slots stay None and the batcher slices them off the fan-out;
    the device batch itself is re-padded to the scorer's bucket ladder
    with masked rows inside ``recommend_batch``.
    """
    from predictionio_tpu.server.aot import PAD

    if scorer is None:
        return [None if q is PAD else fallback(q) for q in queries]
    out = [None] * len(queries)
    rows = []  # (out index, user row, num)
    for i, q in enumerate(queries):
        if q is PAD:
            continue
        if per_query is not None and per_query(q):
            out[i] = fallback(q)
            continue
        uidx = user_ids.get(str(q["user"]))
        if uidx is None:
            out[i] = {"itemScores": []}
            continue
        rows.append((i, uidx, int(q.get("num", 10))))
    if rows:
        k = max(n for _, _, n in rows)
        res = scorer.recommend_batch(
            np.asarray([u for _, u, _ in rows], np.int32), k)
        for (i, _, n), (iv, vv) in zip(rows, res):
            out[i] = {"itemScores": [
                {"item": item_inv[int(j)], "score": float(s)}
                for j, s in zip(iv[:n], vv[:n])]}
    return out


class ResidentScorer:
    """Serving-time scorer with factors resident on device.

    The reference's serving path keeps the ``MatrixFactorizationModel``
    in JVM heap and scores per query ([U] MLlib
    ``recommendProducts`` — SURVEY.md §3.2). Here U and V live in HBM
    across requests; each query is one compiled score→top-k program
    (streaming Pallas kernel on TPU, dense XLA fallback elsewhere).
    Exclusions are handled by over-fetching a padded k (bucketed to
    limit recompiles) and filtering host-side.
    """

    _TILE = 2048  # item-tile width of the streaming kernel

    def built_from(self, U, V) -> bool:
        """True iff this scorer was constructed from exactly these
        host arrays (used by :func:`maybe_resident_scorer` to reuse
        across calls without ever serving stale factors)."""
        if self._source is None:
            return False
        return self._source[0]() is U and self._source[1]() is V

    def __init__(self, U: np.ndarray, V: np.ndarray):
        import jax
        import jax.numpy as jnp

        # weak identity of the host arrays this scorer was built from,
        # so maybe_resident_scorer can detect a factor swap after
        # retrain (weakref, not id(): a freed array's address can be
        # recycled by a new allocation)
        import weakref
        try:
            self._source = (weakref.ref(U), weakref.ref(V))
        except TypeError:  # non-weakref-able array-likes (e.g. lists)
            self._source = None
        self.n_users, self.rank = U.shape
        self.n_items = V.shape[0]
        if self.n_items >= 1 << 24:
            # packed single-fetch output carries indices in f32 (exact
            # integers only below 2^24)
            raise ValueError("ResidentScorer supports catalogs < 2^24 items")
        self._U = jax.device_put(jnp.asarray(U, jnp.float32))
        # ONE resident copy, padded once at load to the streaming
        # kernel's tile; both scoring paths mask the pad rows
        pad = -self.n_items % self._TILE
        Vp = np.concatenate([V, np.zeros((pad, self.rank), V.dtype)]) if pad else V
        self._V_padded = jax.device_put(jnp.asarray(Vp, jnp.float32))
        #: AOT-bucket serving state (server/aot): when a ladder is set
        #: (deploy-time warmup / --aot-buckets), batch sizes snap to it
        #: and warmed buckets dispatch a precompiled executable
        self.bucket_ladder = None
        self._aot: dict = {}   # (B, k) -> (compiled, pallas)

    # -- AOT bucket ladder (server/aot) ---------------------------------------

    def set_bucket_ladder(self, ladder) -> None:
        """Snap serving batch sizes to ``ladder`` (a
        ``server/aot.BucketLadder``) instead of the default
        power-of-two rule; warmed buckets then dispatch precompiled
        executables."""
        self.bucket_ladder = ladder

    def _pallas_for(self, B: int, k: int) -> bool:
        from predictionio_tpu import ops

        # The streaming kernel pays off once the (B, n_items) score
        # matrix is too big to live cheaply in HBM between the matmul
        # and the top_k; below that XLA's fused path wins (measured on
        # v5e: XLA 1.5ms vs Pallas 2.8ms at B=32, N=27k).
        # k > 1024 would unroll the kernel's selection loop too far —
        # XLA's top_k handles large k better.
        return (ops.use_pallas() and k <= 1024
                and B * self.n_items > 64_000_000)

    def _aot_key(self, B: int, k: int, pallas: bool) -> tuple:
        import jax

        # everything that selects a distinct XLA program — executables
        # are shared process-wide across same-geometry models, which is
        # what makes a same-geometry /reload compile-free
        return ("gather_score_topk", self.n_users, self.rank,
                int(self._V_padded.shape[0]), self.n_items, B, k,
                pallas, self._TILE, jax.default_backend())

    def _ensure_executable(self, B: int, k: int) -> bool:
        """AOT lower+compile the serving program for one (batch bucket,
        k) pair, via the process-wide executable cache. Returns True if
        this call cold-compiled (False = cache hit)."""
        import jax

        from predictionio_tpu.server.aot import EXECUTABLES

        pallas = self._pallas_for(B, k)
        key = self._aot_key(B, k, pallas)
        was_cold = EXECUTABLES.get(key) is None

        def build():
            sds = (
                jax.ShapeDtypeStruct((self.n_users, self.rank), np.float32),
                jax.ShapeDtypeStruct(tuple(self._V_padded.shape), np.float32),
                jax.ShapeDtypeStruct((B,), np.int32),
                jax.ShapeDtypeStruct((), np.int32),  # rows_valid
            )
            return _gather_score_topk_jit().lower(
                *sds, k=k, n_valid=self.n_items, pallas=pallas,
                tile=self._TILE).compile()

        self._aot[(B, k)] = (EXECUTABLES.get_or_compile(key, build), pallas)
        return was_cold

    def warm_buckets(self, ladder, ks=(16,)) -> dict:
        """Deploy-time warmup: compile (or adopt from the process-wide
        cache) one executable per (bucket, k); adopts ``ladder`` as
        this scorer's serving ladder. Returns
        ``{"targets", "compiled", "cached"}`` for warmup progress."""
        self.set_bucket_ladder(ladder)
        compiled = cached = 0
        for B in ladder:
            for k in ks:
                kk = min(_bucket_k(k), self.n_items)
                if self._ensure_executable(B, kk):
                    compiled += 1
                else:
                    cached += 1
        return {"targets": compiled + cached,
                "compiled": compiled, "cached": cached}

    def _topk(self, user_ids, k: int, rows: Optional[int] = None):
        """One serving dispatch at an (already bucket-padded) batch.
        ``rows`` = real row count (pad rows masked on device). Warmed
        buckets run the precompiled executable; anything else falls
        back to jit dispatch (counted — a fallback on the serving path
        means a warmup gap)."""
        import time

        from predictionio_tpu.server import aot
        from predictionio_tpu.utils import tracing

        B = len(user_ids)
        rows_valid = np.int32(B if rows is None else rows)
        entry = self._aot.get((B, k))
        path = "aot" if entry is not None else "jit"
        with tracing.span("serving.device", bucket=B, k=k, path=path):
            t0 = time.perf_counter()
            if entry is not None:
                prog, _pallas = entry
                packed = np.asarray(prog(
                    self._U, self._V_padded,
                    np.asarray(user_ids, np.int32), rows_valid))
                out = packed[..., :k], packed[..., k:].astype(np.int32)
            else:
                out = _gather_score_topk(
                    self._U, self._V_padded, user_ids, k=k,
                    n_valid=self.n_items, pallas=self._pallas_for(B, k),
                    tile=self._TILE, rows_valid=rows_valid)
            aot.record_device_latency(B, time.perf_counter() - t0, path,
                                      trace_exemplar=tracing.exemplar())
        return out

    def recommend_batch(
        self, user_ids: np.ndarray, num: int,
        exclude: Optional[list] = None,
    ) -> list:
        """Top-``num`` per user → list of (item_indices, scores) pairs.

        ``exclude[i]`` is an optional array of item indices to drop for
        user i (seen-item / constraint filtering, e-commerce template);
        ``exclude`` itself or any entry may be None/empty.
        """
        import jax.numpy as jnp

        if not exclude:
            exclude = [None] * len(user_ids)
        exclude = [np.asarray([] if e is None else e, np.int32)
                   for e in exclude]
        max_ex = max((e.size for e in exclude), default=0)
        # bucket k to powers of two (bounds recompiles); over-fetch for
        # exclusions but never more than the catalog
        want = min(num + max_ex, self.n_items)
        k = min(_bucket_k(want), self.n_items)
        # bucket the BATCH dimension too: the micro-batcher produces
        # every size from 1..max_batch, and an unpadded B would compile
        # a program per distinct size (measured: 172 ms p99 under 8
        # concurrent clients vs ~7 ms once warm — r4). With an AOT
        # ladder set (deploy warmup) batches snap to ITS buckets so
        # every dispatch hits a precompiled executable; pad rows reuse
        # user 0, are masked on device, and are sliced off after the
        # dispatch.
        B = len(user_ids)
        Bp = (self.bucket_ladder.snap(B)
              if self.bucket_ladder is not None else 0)
        if Bp < B:  # no ladder, or batch beyond its top bucket
            # (direct recommend_batch callers, e.g. pio batchpredict)
            Bp = 1
            while Bp < B:
                Bp *= 2
        ids = np.asarray(user_ids, np.int32)
        if Bp != B:
            ids = np.concatenate([ids, np.zeros(Bp - B, np.int32)])
        vals, idx = self._topk(ids, k, rows=B)
        vals, idx = np.asarray(vals)[:B], np.asarray(idx)[:B]
        out = []
        for row in range(len(user_ids)):
            iv, vv = idx[row], vals[row]
            if exclude[row].size:
                keep = ~np.isin(iv, exclude[row])
                iv, vv = iv[keep], vv[keep]
            out.append((iv[:num], vv[:num]))
        return out

    def recommend(self, user: int, num: int,
                  exclude: Optional[np.ndarray] = None):
        [(iv, vv)] = self.recommend_batch(
            np.asarray([user]), num,
            [np.asarray(exclude if exclude is not None else [], np.int32)])
        return iv, vv


def similar_items(
    V: np.ndarray, item_indices: np.ndarray, num: int,
    exclude_self: bool = True,
) -> Tuple[np.ndarray, np.ndarray]:
    """Top-``num`` items by cosine similarity to the given items' mean
    direction (similar-product template behavior)."""
    norms = np.linalg.norm(V, axis=1, keepdims=True)
    Vn = V / np.maximum(norms, 1e-12)
    q = Vn[item_indices].mean(axis=0)
    qn = q / max(np.linalg.norm(q), 1e-12)
    scores = Vn @ qn
    if exclude_self:
        scores = scores.copy()
        scores[item_indices] = -np.inf
    num = min(num, scores.shape[0])
    top = np.argpartition(-scores, num - 1)[:num]
    top = top[np.argsort(-scores[top])]
    return top, scores[top]
