"""Causal attention inside the segments of a packed sequence, tile by
tile — three Pallas kernels (forward, dq, dk/dv) under one
``jax.custom_vjp``.

A packed sequence holds many histories back to back
(``models/seq_backbone.pack_histories``); a query row sees the keys of
its OWN segment up to itself, i.e. the keys ``first[r] … r`` where
``first[r]`` is the first row of r's segment — or, under a WINDOW of
``W`` keys, ``max(first row of the segment, r − W + 1)``: the window
is part of ``first``, not a mask over a full walk, and the clipped
``first`` still never falls along a sequence. Two things follow:

- **Only the tiles a segment reaches are visited.** A block of ``bq``
  query rows needs the key tiles from the one holding the first key of
  its EARLIEST segment up to its diagonal tile; a key tile is needed
  by the query blocks from its diagonal one to the last whose earliest
  segment starts at or before the tile's last key. Segments are
  contiguous, so both are intervals (:func:`tile_intervals`); they
  reach the kernels as scalar-prefetched loop bounds, and a tile
  outside them is neither read nor multiplied.
- **A tile's scores live on the chip only.** The forward pass keeps a
  running maximum, a running sum and the output accumulator in float32
  (the online softmax) and leaves the per-row log-sum-exp; the backward
  kernels recompute a tile's probabilities from it. Nothing of size
  ``rows × keys`` is ever in HBM.

Keys and values may have FEWER heads than the queries (grouped-query
attention): with ``Hkv`` key-value heads, ``g = H ÷ Hkv`` adjacent
query heads read key-value head ``h ÷ g``. Forward and dq fetch that
head's keys and values (once for the group: the block index does not
change between its heads); dk/dv walks the group's ``g`` query heads
and sums their parts in its float32 accumulators. With ``Hkv = H``
the kernels are the ungrouped ones.

The kernels see the arrays head-major ([H, S, D]; where they are made
XLA lays them out so, and the transposes cost no copy). The operand
that a kernel walks over (k and v for forward and dq, q and the
output's cotangent for dk/dv) stays in VMEM for a whole head: one grid
step is one block of rows of one head, the walk over its tiles is a
loop INSIDE the kernel, so a sequence of many short segments costs no
grid steps for the tiles it skips. Where the rows that dk/dv walks over
(a key-value head's ``g`` query heads, with their cotangents) are more
than :data:`_WHOLE_HEAD_BYTES` — 28 heads over 4 at 16,384 slots — the
grid walks the QUERY heads instead, one head's rows in VMEM at a time,
and the group's float32 parts are summed outside the kernel.

Precision: operands as given (bfloat16 in training), scores, mask,
maximum, sum and accumulators float32, the probabilities enter the
second product in the operands' dtype. Rows of segment 0 (padding) see
no key: their output is finite and means nothing, their gradients are
zero, and no real row depends on them.

**The block rule** (:func:`block_attention`; block-diffusion training,
``models/sdar_moe``). A sequence goes through a layer TWICE in one
pass, a clean copy (rows ``0 … S − 1`` of every operand) and a noised
copy (rows ``S … 2S − 1``) with the same segments and positions; a
segment is cut into blocks of ``block`` rows from its first row (the
last may be partial). With i, j rows of one segment:

    clean query i  sees clean key j   ⇔  block(j) ≤ block(i)
    noised query i sees clean key j   ⇔  block(j) <  block(i)
    noised query i sees noised key j  ⇔  block(j) =  block(i)
    no clean query sees a noised key

so a row's keys are no longer ONE interval that ends at the row: a
clean row sees ``first[r] … last[r]`` (``last``: the end of its block,
up to ``block − 1`` rows PAST the diagonal), a noised row the clean
keys ``first[r] … start[r] − 1`` AND the noised keys ``start[r] …
last[r]`` (``start``: its block's first row) — two intervals of the
2·S-row operand (:func:`block_spans`). The same three kernels run it:
a row carries its two intervals instead of its first key, and a block
of query rows (a key tile, in dk/dv) TWO runs of tiles instead of one
(:func:`block_intervals`) — a clean block the clean tiles from its
earliest segment's first key to the one holding its last row's block
end; a noised block the clean tiles up to the one holding its last
row's block START, then the noised tile(s) holding its own blocks. A
clean key so collects gradient from clean AND noised queries, a noised
key from its own block only, and the walk stays proportional to the
visible pairs: n(n + 4) for a segment of n = 4m rows, never the
(2n)²/2 of a causal walk over the concatenation. With no block rule
the kernels are traced as before it existed: same operands, same tile
bounds, the same numbers bit for bit.

Compiled for a TPU, interpreted anywhere else — decided when the
program is LOWERED (``jax.lax.platform_dependent``), so a program
lowered for a described chip from a CPU process gets the kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: keys a tile holds at most (its rows are the caller's ``bq``). On the
#: v5e, 20 heads of 256 over 4,096 slots packed ~30 segments a
#: sequence: 512 rows × 512 keys and 256 × 256 take the same time
#: (wider tiles multiply faster, 99 against 55 TFLOP/s forward, and
#: visit more: 30 % against 47 % of the pairs inside them are real);
#: 128 keys cost a third more (PERF.md §6, PR 32)
KEY_TILE = 512
_LANES = 128
#: what a masked score is set to; finite, so a row whose first visited
#: tile holds none of its keys gives exp(0) there and is wiped by the
#: first real maximum
_MASKED = -1e30
_NT = (((1,), (1,)), ((), ()))   # a · bᵀ
_VMEM_LIMIT = 96 * 1024 * 1024
#: most bytes of whole-head operands dk/dv keeps in VMEM (each is
#: double-buffered by the pipeline): above it the grid walks query heads
_WHOLE_HEAD_BYTES = 24 * 1024 * 1024


# -- which tiles ---------------------------------------------------------------


def first_keys(seg, xp=jnp, window=None):
    """Per row of ``seg`` [..., S], the first key it sees: the first
    row of its segment (a maximal run of one id), or the row ``window
    − 1`` before it where that lies later; ``r + 1`` for a padding row
    (id 0), which so has no key at all. ``xp``: numpy on the host,
    jax.numpy in a program — the same lines count the tiles and steer
    the kernels."""
    S = seg.shape[-1]
    r = xp.arange(S, dtype=xp.int32)
    new = xp.concatenate([xp.ones_like(seg[..., :1], dtype=bool),
                          seg[..., 1:] != seg[..., :-1]], axis=-1)
    starts = xp.where(new, r, 0)
    # NOT jnp.maximum.accumulate: that is a scan of S sequential steps,
    # 8 ms a call on the chip where the whole forward kernel takes less
    first = (np.maximum.accumulate(starts, axis=-1) if xp is np
             else jax.lax.cummax(starts, axis=starts.ndim - 1))
    if window is not None and window < S:
        first = xp.maximum(first, r - (window - 1))
    return xp.where(seg > 0, first, r + 1).astype(xp.int32)


def tile_intervals(first, bq: int, bk: int, xp=jnp):
    """(lo [..., S/bq], hi [..., S/bk]) from :func:`first_keys`: query
    block i visits key tiles ``lo[i] … diagonal``, key tile j is
    visited by query blocks ``diagonal … hi[j]``. ``first`` never
    falls along a sequence, so ``lo`` does not either and the blocks
    that reach tile j are the first ``hi[j] + 1``."""
    S = first.shape[-1]
    lead = first.shape[:-1]
    diag = ((xp.arange(S // bq, dtype=xp.int32) + 1) * bq - 1) // bk
    lo = xp.minimum(first.reshape(lead + (S // bq, bq)).min(-1) // bk, diag)
    tiles = xp.arange(S // bk, dtype=xp.int32)
    hi = (lo[..., None, :] <= tiles[:, None]).sum(-1) - 1
    return lo.astype(xp.int32), hi.astype(xp.int32)


def tile_pairs(seg: np.ndarray, bq: int, bk: int, skip: bool = True,
               window=None) -> int:
    """(query, key) pairs inside the tiles the forward pass visits for
    the sequences ``seg`` [N, S] (``skip`` False: inside those a walk
    from the first tile to the diagonal would) — one head, counted on
    the host by the function that steers the kernels."""
    lo, _ = tile_intervals(first_keys(seg, np, window), bq, bk, np)
    diag = ((np.arange(seg.shape[-1] // bq) + 1) * bq - 1) // bk
    return int((diag - lo * skip + 1).sum()) * bq * bk


# -- which tiles, under the block rule ------------------------------------------


def last_keys(seg, xp=jnp):
    """Per row of ``seg`` [..., S], the last row of its segment; ``r``
    for a padding row."""
    S = seg.shape[-1]
    r = xp.arange(S, dtype=xp.int32)
    end = xp.concatenate([seg[..., 1:] != seg[..., :-1],
                          xp.ones_like(seg[..., :1], dtype=bool)], axis=-1)
    ends = xp.where(end, r, S - 1)
    last = (np.minimum.accumulate(ends[..., ::-1], axis=-1)[..., ::-1]
            if xp is np else jax.lax.cummin(ends, axis=ends.ndim - 1,
                                            reverse=True))
    return xp.where(seg > 0, last, r).astype(xp.int32)


def block_spans(seg, block: int, xp=jnp):
    """(first, start, last) per row of ``seg`` [..., S]: the first row
    of its segment, of its block (``block`` rows, counted from the
    segment's first) and the block's last row (the segment's where the
    block is partial). A padding row gets ``r + 1, r + 1, r``: every
    interval made of them is empty, and all three still never fall
    along a sequence."""
    r = xp.arange(seg.shape[-1], dtype=xp.int32)
    first = first_keys(seg, xp)
    start = first + (r - first) // block * block
    last = xp.minimum(start + block - 1, last_keys(seg, xp))
    real = seg > 0
    return (first, xp.where(real, start, r + 1).astype(xp.int32),
            xp.where(real, last, r).astype(xp.int32))


def stream_spans(first, start, last, xp=jnp):
    """The two key intervals ``a0 … a1``, ``b0 … b1`` of every row of
    BOTH streams ([..., 4, 2·S], in rows of the 2·S-row operands): a
    clean row the clean keys to its block's end and nothing else, a
    noised row the clean keys before its block and its block's noised
    keys."""
    S = first.shape[-1]
    never, none = xp.full_like(first, 2 * S), xp.full_like(first, -1)
    return xp.stack([
        xp.concatenate([first, first], -1),
        xp.concatenate([last, start - 1], -1),
        xp.concatenate([never, start + S], -1),
        xp.concatenate([none, last + S], -1)], -2)


def _reached(tlo, thi, tiles: int, xp):
    """Blocks ``i`` visit tiles ``tlo[i] … thi[i]``, neither falling
    along the sequence: tile j is visited by the blocks ``lo[j] …
    hi[j]`` (an empty run where ``lo > hi``)."""
    j = xp.arange(tiles, dtype=xp.int32)[:, None]
    return ((thi[..., None, :] < j).sum(-1).astype(xp.int32),
            (tlo[..., None, :] <= j).sum(-1).astype(xp.int32) - 1)


def block_intervals(first, start, last, bq: int, bk: int, xp=jnp):
    """The tile runs under the block rule, from :func:`block_spans`
    (S slots; ``nq = S/bq`` query blocks and ``T = S/bk`` key tiles a
    stream, the noised stream's numbered after the clean one's):

    - ``fwd`` [..., 4, 2·nq]: query block i visits key tiles ``fwd[0, i]
      … fwd[1, i]`` and then ``fwd[2, i] … fwd[3, i]`` — a clean block
      the clean tiles to its last row's block end (and an empty second
      run), a noised block the clean tiles to its last row's block
      start − 1, then the noised tiles of its own blocks. Every block
      visits at least one tile.
    - ``bwd`` [..., 4, 2·T]: key tile j is visited by the query blocks
      ``bwd[0, j] … bwd[1, j]`` and ``bwd[2, j] … bwd[3, j]`` — a clean
      tile by clean blocks and by noised ones, a noised tile by the
      noised blocks whose own blocks it holds."""
    S = first.shape[-1]
    lead = first.shape[:-1]
    nq, T = S // bq, S // bk

    def blk(a):
        return a.reshape(lead + (nq, bq))

    diag = ((xp.arange(nq, dtype=xp.int32) + 1) * bq - 1) // bk
    lo = xp.minimum(blk(first).min(-1) // bk, diag)
    hi_clean = blk(last).max(-1) // bk
    hi_before = (blk(start).max(-1) - 1) // bk
    lo_own = xp.minimum(blk(start).min(-1) // bk, diag)
    none = xp.zeros_like(lo)
    fwd = xp.stack([
        xp.concatenate([lo, lo], -1),
        xp.concatenate([hi_clean, hi_before], -1),
        xp.concatenate([none, lo_own + T], -1),
        xp.concatenate([none - 1, hi_clean + T], -1)], -2)
    cc = _reached(lo, hi_clean, T, xp)
    nc = _reached(lo, hi_before, T, xp)
    nn = _reached(lo_own, hi_clean, T, xp)
    none = xp.zeros_like(cc[0])
    bwd = xp.stack([
        xp.concatenate([cc[0], nn[0] + nq], -1),
        xp.concatenate([cc[1], nn[1] + nq], -1),
        xp.concatenate([nc[0] + nq, none], -1),
        xp.concatenate([nc[1] + nq, none - 1], -1)], -2)
    return fwd.astype(xp.int32), bwd.astype(xp.int32)


def block_tile_pairs(seg: np.ndarray, bq: int, bk: int, block: int) -> int:
    """(query, key) pairs inside the tiles the forward pass visits
    under the block rule for the sequences ``seg`` [N, S], both streams
    — one head, counted on the host by the function that steers the
    kernels."""
    fwd, _ = block_intervals(*block_spans(seg, block, np), bq, bk, np)
    runs = (np.maximum(fwd[..., 1, :] - fwd[..., 0, :] + 1, 0)
            + np.maximum(fwd[..., 3, :] - fwd[..., 2, :] + 1, 0))
    return int(runs.sum()) * bq * bk


def block_pairs(sizes, block: int) -> int:
    """(query, key) pairs the block rule leaves of segments of ``sizes``
    rows, both streams: a row of block b (``s_b`` rows, ``e_b`` rows of
    the segment up to its end) sees ``e_b`` keys as a clean row and
    ``(e_b − s_b) + s_b`` as a noised one — 2 Σ_b s_b e_b a segment,
    n(n + block) where ``block`` divides n."""
    n = np.asarray(sizes, np.int64)
    full, part = n // block, n % block
    return int((block * block * full * (full + 1) + 2 * part * n).sum())


# -- the kernels ---------------------------------------------------------------


def _scores(q, k, visible, at, scale):
    """One tile: float32 scores [bq, bk] and which of them are real
    (``visible`` of :func:`_visible`, on the tile's columns)."""
    s = jax.lax.dot_general(q, k, _NT,
                            preferred_element_type=jnp.float32) * scale
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return s, visible(col, at)


def _rows_of(block, bq):
    return block * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)


def _visible(span_ref, i, bq, two):
    """``visible(col, at)``: which keys ``at + col`` the block's query
    rows see. One interval that ends at the row (``span_ref``: every
    row's first key in all its lanes) — or, ``two``, the row's two
    intervals (lanes 0 … 3: ``a0, a1, b0, b1``)."""
    if two:
        a0, a1, b0, b1 = (span_ref[:, n:n + 1] for n in range(4))
        return lambda col, at: (((col >= a0 - at) & (col <= a1 - at))
                                | ((col >= b0 - at) & (col <= b1 - at)))
    first, rows = span_ref[:, :1], _rows_of(i, bq)
    return lambda col, at: (col >= first - at) & (col <= rows - at)


def _runs(bounds_ref, i, one, two):
    """The runs of tiles (of query blocks, in dk/dv) that grid step
    ``i`` walks, as ``fori_loop`` bounds: ``one()`` — the single run of
    the causal rule, from its prefetched bound — or, ``two``, the two
    runs of the block rule (``bounds_ref``: [4 · steps], a row of
    :func:`block_intervals` after another)."""
    if not two:
        return (one(),)
    n = bounds_ref.shape[0] // 4
    return ((bounds_ref[i], bounds_ref[n + i] + 1),
            (bounds_ref[2 * n + i], bounds_ref[3 * n + i] + 1))


def _fwd_kernel(lo_ref, q_ref, k_ref, v_ref, first_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, scale, bk, two=False):
    i = pl.program_id(1)
    bq = q_ref.shape[0]
    q, visible = q_ref[...], _visible(first_ref, i, bq, two)
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def tile(j, _):
        at = pl.multiple_of(j * bk, bk)
        s, real = _scores(q, k_ref[pl.ds(at, bk), :], visible, at, scale)
        s = jnp.where(real, s, _MASKED)
        m = jnp.maximum(m_ref[...], s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_ref[...] - m)
        p = jnp.exp(s - m)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[pl.ds(at, bk), :],
            preferred_element_type=jnp.float32)
        m_ref[...] = m
        return _

    for lo, hi in _runs(lo_ref, i, two=two, one=lambda: (
            lo_ref[i], ((i + 1) * bq - 1) // bk + 1)):
        jax.lax.fori_loop(lo, hi, tile, None)
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
    lse_ref[...] = jnp.broadcast_to(m_ref[...] + jnp.log(l_ref[...]),
                                    lse_ref.shape)


def _dq_kernel(lo_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               first_ref, dq_ref, acc_ref, *, scale, bk, two=False):
    """A block of query rows against the key tiles it reaches: the
    probabilities recomputed from the rows' log-sum-exp, the scores'
    cotangent, its product with the keys."""
    i = pl.program_id(1)
    bq = q_ref.shape[0]
    q, do, visible = q_ref[...], do_ref[...], _visible(first_ref, i, bq, two)
    lse, delta = lse_ref[:, :1], delta_ref[:, :1]
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def tile(j, _):
        at = pl.multiple_of(j * bk, bk)
        k = k_ref[pl.ds(at, bk), :]
        s, real = _scores(q, k, visible, at, scale)
        p = jnp.where(real, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v_ref[pl.ds(at, bk), :], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        acc_ref[...] += jnp.dot(ds.astype(k.dtype), k,
                                preferred_element_type=jnp.float32)
        return _

    for lo, hi in _runs(lo_ref, i, two=two, one=lambda: (
            lo_ref[i], ((i + 1) * bq - 1) // bk + 1)):
        jax.lax.fori_loop(lo, hi, tile, None)
    dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(hi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                first_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale, bq,
                group, two=False):
    """A key tile against the query blocks that reach it, of each of
    the ``group`` query heads that read this key-value head (their
    rows lie one head after another: head g's block i is block
    ``g · blocks + i``), TRANSPOSED: scores [keys, rows], so that both
    products into dk and dv take their left operand as it lies and
    nothing is turned; the rows' numbers come as rows ([blocks, 1, bq],
    a block by its index — ``two``: [4, blocks, 1, bq], the rows' two
    intervals)."""
    j = pl.program_id(1)
    bk = k_ref.shape[0]
    blocks = first_ref.shape[-3]
    k, v = k_ref[...], v_ref[...]
    keys = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
    dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
    dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    def visible(i):
        if two:
            return (((keys >= first_ref[0, i]) & (keys <= first_ref[1, i]))
                    | ((keys >= first_ref[2, i]) & (keys <= first_ref[3, i])))
        rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (1, bq), 1)
        return (keys >= first_ref[i]) & (keys <= rows)

    for g in range(group):
        def block(i, _, at=g * blocks):
            rs = pl.ds(pl.multiple_of((at + i) * bq, bq), bq)
            q, do = q_ref[rs, :], do_ref[rs, :]
            s = jax.lax.dot_general(
                k, q, _NT, preferred_element_type=jnp.float32) * scale
            p = jnp.where(visible(i), jnp.exp(s - lse_ref[at + i]), 0.0)
            dp = jax.lax.dot_general(v, do, _NT,
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[at + i]) * scale
            dv_acc[...] += jnp.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
            dk_acc[...] += jnp.dot(ds.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)
            return _

        for lo, hi in _runs(hi_ref, j, two=two, one=lambda: (
                j * bk // bq, hi_ref[j] + 1)):
            jax.lax.fori_loop(lo, hi, block, None)
    dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
    dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


# -- the calls -----------------------------------------------------------------


def _head(rows: int, width: int, whole: bool = False, group: int = 1):
    """``rows`` rows of the grid's head ([H, S, width]) — with
    ``group``, of the key-value head that ``group`` query heads share:
    the grid's block of them, or (``whole``) all — fetched once a
    head, walked by the kernel's own loop."""
    return pl.BlockSpec((None, rows, width),
                        lambda h, i, _: (h // group if group > 1 else h,
                                         0 if whole else i, 0))


def _first(rows: int):
    """The grid's block of every row's first key (one for all heads)."""
    return pl.BlockSpec((rows, _LANES), lambda h, i, _: (i, 0))


def _call(kernel, name, blocks, bounds, operands, in_specs, outs, out_specs,
          scratch, interpret):
    """One kernel over the grid (heads, blocks of rows), its loop
    bounds prefetched into SMEM."""
    return pl.pallas_call(
        kernel, name=name, out_shape=outs, interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(operands[0].shape[0], blocks),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(bounds, *operands)


def _by_platform(run, *args):
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(run, interpret=False),
        default=functools.partial(run, interpret=True))


def _forward(q, k, v, first, lo, bq, bk, scale, interpret, two=False):
    """``first`` [S] and ``lo``: every row's first key and the blocks'
    first tiles — or (``two``) ``first`` [4, S] the rows' two intervals
    and ``lo`` [4, S/bq] the blocks' two runs of tiles, S the rows of
    both streams."""
    H, S, D = q.shape
    Dv, g = v.shape[-1], H // k.shape[0]
    return _call(
        functools.partial(_fwd_kernel, scale=scale, bk=bk, two=two),
        "seq_attention_bd_fwd" if two else "seq_attention_fwd", S // bq,
        lo.reshape(-1), (q, k, v, _columns(first)),
        [_head(bq, D), _head(S, D, True, g), _head(S, Dv, True, g),
         _first(bq)],
        [jax.ShapeDtypeStruct((H, S, Dv), v.dtype),
         jax.ShapeDtypeStruct((H, S, _LANES), jnp.float32)],
        [_head(bq, Dv), _head(bq, _LANES)],
        [pltpu.VMEM((bq, 1), jnp.float32), pltpu.VMEM((bq, 1), jnp.float32),
         pltpu.VMEM((bq, Dv), jnp.float32)], interpret)


def _backward(q, k, v, do, lse, delta, first, lo, hi, bq, bk, scale,
              interpret, two=False):
    """``lse``, ``delta`` [H, S] and ``first`` [S] (``two``: [4, S], as
    in :func:`_forward`, and ``hi`` [4, S/bk]): dq reads a row's
    number as a column (its 128 lanes), dk/dv as part of a row. dk/dv's
    grid walks the KEY-VALUE heads: the ``g`` query heads of one are
    adjacent, so their rows are one head of ``g · S`` rows — unless
    those rows do not fit VMEM (:data:`_WHOLE_HEAD_BYTES`): then it
    walks the query heads, each giving its float32 part of dk and dv,
    and the group's parts are summed here."""
    H, S, D = q.shape
    Hkv, Dv = v.shape[0], v.shape[-1]
    g = H // Hkv
    split = g > 1 and g * S * (D + Dv) * q.dtype.itemsize > _WHOLE_HEAD_BYTES
    dq, = _call(
        functools.partial(_dq_kernel, scale=scale, bk=bk, two=two),
        "seq_attention_bd_dq" if two else "seq_attention_dq", S // bq,
        lo.reshape(-1),
        (q, k, v, do, _lanes(lse), _lanes(delta), _columns(first)),
        [_head(bq, D), _head(S, D, True, g), _head(S, Dv, True, g),
         _head(bq, Dv), _head(bq, _LANES), _head(bq, _LANES), _first(bq)],
        [jax.ShapeDtypeStruct(q.shape, q.dtype)], [_head(bq, D)],
        [pltpu.VMEM((bq, D), jnp.float32)], interpret)
    # the grid's heads: the key-value heads with their whole group, or
    # (split) the query heads, each reading key-value head h ÷ g
    heads, rows, per = (H, 1, g) if split else (Hkv, g, 1)
    as_rows = pl.BlockSpec((None, rows * S // bq, 1, bq),
                           lambda h, j, _: (h, 0, 0, 0))
    spans = first.shape[:-1] + (S // bq, 1, bq)
    dk, dv = _call(
        functools.partial(_dkv_kernel, scale=scale, bq=bq, group=rows,
                          two=two),
        "seq_attention_bd_dkv" if two else "seq_attention_dkv", S // bk,
        hi.reshape(-1),
        (q.reshape(heads, rows * S, D), k, v,
         do.reshape(heads, rows * S, Dv), lse.reshape(heads, -1, 1, bq),
         delta.reshape(heads, -1, 1, bq), first.reshape(spans)),
        [_head(rows * S, D, True), _head(bk, D, group=per),
         _head(bk, Dv, group=per), _head(rows * S, Dv, True), as_rows,
         as_rows, pl.BlockSpec(spans, lambda h, j, _: (0,) * len(spans))],
        [jax.ShapeDtypeStruct((heads, S, D),
                              jnp.float32 if split else k.dtype),
         jax.ShapeDtypeStruct((heads, S, Dv),
                              jnp.float32 if split else v.dtype)],
        [_head(bk, D), _head(bk, Dv)],
        [pltpu.VMEM((bk, D), jnp.float32), pltpu.VMEM((bk, Dv), jnp.float32)],
        interpret)
    if split:
        dk, dv = (a.reshape(Hkv, g, S, -1).sum(1).astype(like.dtype)
                  for a, like in ((dk, k), (dv, v)))
    return dq, dk, dv


def _columns(first):
    """The rows' numbers where forward and dq read them as columns:
    [S] → [S, 128], a row's first key in all its lanes; [4, S] →
    [S, 128], a row's two intervals in lanes 0 … 3."""
    if first.ndim == 1:
        return _lanes(first)
    return jnp.pad(first.T, ((0, 0), (0, _LANES - first.shape[0])))


def _lanes(x):
    """[..., S] → [..., S, 128]: a per-row number where a kernel can
    read it as a column."""
    return jnp.broadcast_to(x[..., None], x.shape + (_LANES,))


def _heads_first(x):
    """[S, H, D] ↔ [H, S, D]: the kernels' grid walks heads, and a
    head's rows are one block. XLA gives the arrays this layout where
    they are made; no copy comes of it."""
    return x.transpose(1, 0, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def segment_attention(q, k, v, seg, bq: int, bk: int, scale: float,
                      window=None):
    """softmax(scale · q kᵀ, causal AND inside one segment) v for ONE
    packed sequence: q [S, H, D], k [S, Hkv, D], v [S, Hkv, Dv] with
    ``Hkv`` dividing ``H`` (query head h reads key-value head
    h ÷ (H ÷ Hkv)), ``seg`` [S] int32 (0 = padding) → [S, H, Dv] in v's
    dtype. ``bq`` query rows and ``bk`` keys a tile; both divide S.
    ``window``: a row sees its newest ``window`` keys only (itself
    included); None, or one of S or more, is no window — the same
    program."""
    return _attend(q, k, v, seg, bq, bk, scale, window)[0]


def _attend(q, k, v, seg, bq, bk, scale, window=None):
    S = q.shape[0]
    if S % bq or S % bk:
        raise ValueError(f"tiles of {bq} rows × {bk} keys do not divide "
                         f"a sequence of {S}")
    if k.shape[1] != v.shape[1] or q.shape[1] % k.shape[1]:
        raise ValueError(f"{k.shape[1]} key and {v.shape[1]} value heads "
                         f"do not group {q.shape[1]} query heads")
    first = first_keys(seg, window=window)
    lo, _ = tile_intervals(first, bq, bk)
    out, lse = _by_platform(
        functools.partial(_forward, bq=bq, bk=bk, scale=scale),
        *map(_heads_first, (q, k, v)), first, lo)
    out, lse = _named(_heads_first(out), lse[..., 0])
    # one number a row is kept for the backward pass, not its 128 lanes
    return out, (q, k, v, out, lse, seg)


def _attend_bwd(bq, bk, scale, window, res, do):
    q, k, v, out, lse, seg = res
    first = first_keys(seg, window=window)
    lo, hi = tile_intervals(first, bq, bk)
    delta = (out.astype(jnp.float32) * do.astype(jnp.float32)).sum(-1).T
    grads = _by_platform(
        functools.partial(_backward, bq=bq, bk=bk, scale=scale),
        *map(_heads_first, (q, k, v, do)), lse, delta, first, lo, hi)
    return (*map(_heads_first, grads), None)


segment_attention.defvjp(_attend, _attend_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def block_attention(q, k, v, seg, bq: int, bk: int, scale: float,
                    block: int):
    """softmax(scale · q kᵀ, the block rule) v for BOTH streams of one
    packed sequence: q [2·S, H, D], k [2·S, Hkv, D], v [2·S, Hkv, Dv] —
    rows ``0 … S − 1`` the clean stream, ``S … 2·S − 1`` the noised one
    — and ``seg`` [S] int32 (0 = padding), the segments of either → [2·S,
    H, Dv] in v's dtype. A clean row sees the clean keys of its segment
    up to the end of its block of ``block`` rows, a noised row the clean
    keys before its block and the noised keys of its block. ``bq``
    query rows and ``bk`` keys a tile; both divide S. Operands of S
    rows are ONE stream under the clean stream's rule (serving: the
    rows to fill are MASK rows of the history itself)."""
    return _attend_blocks(q, k, v, seg, bq, bk, scale, block)[0]


def _steer_blocks(seg, bq, bk, block, both=True):
    """(the rows' intervals, the query blocks' runs of tiles, the key
    tiles' runs of query blocks) of both streams — or of the clean
    stream alone, which nothing of the noised one reaches but its own
    rows."""
    S = seg.shape[-1]
    spans = block_spans(seg, block)
    rows, fwd, bwd = (stream_spans(*spans),
                      *block_intervals(*spans, bq, bk))
    if both:
        return rows, fwd, bwd
    bwd = bwd[:, :S // bk]
    return (rows[:, :S], fwd[:, :S // bq],
            bwd.at[2:].set(jnp.array([[0], [-1]], jnp.int32)))


def _attend_blocks(q, k, v, seg, bq, bk, scale, block):
    S = seg.shape[0]
    if S % bq or S % bk:
        raise ValueError(f"tiles of {bq} rows × {bk} keys do not divide "
                         f"a sequence of {S}")
    if q.shape[0] not in (S, 2 * S) or {k.shape[0], v.shape[0]} != {
            q.shape[0]}:
        raise ValueError(f"one or two streams of {S} slots are {S} or "
                         f"{2 * S} rows, not {q.shape[0]}, {k.shape[0]} "
                         f"and {v.shape[0]}")
    if k.shape[1] != v.shape[1] or q.shape[1] % k.shape[1]:
        raise ValueError(f"{k.shape[1]} key and {v.shape[1]} value heads "
                         f"do not group {q.shape[1]} query heads")
    spans, fwd, _ = _steer_blocks(seg, bq, bk, block, q.shape[0] > S)
    out, lse = _by_platform(
        functools.partial(_forward, bq=bq, bk=bk, scale=scale, two=True),
        *map(_heads_first, (q, k, v)), spans, fwd)
    out, lse = _named(_heads_first(out), lse[..., 0])
    return out, (q, k, v, out, lse, seg)


def _attend_blocks_bwd(bq, bk, scale, block, res, do):
    q, k, v, out, lse, seg = res
    spans, fwd, bwd = _steer_blocks(seg, bq, bk, block,
                                    q.shape[0] > seg.shape[0])
    delta = (out.astype(jnp.float32) * do.astype(jnp.float32)).sum(-1).T
    grads = _by_platform(
        functools.partial(_backward, bq=bq, bk=bk, scale=scale, two=True),
        *map(_heads_first, (q, k, v, do)), lse, delta, spans, fwd, bwd)
    return (*map(_heads_first, grads), None)


block_attention.defvjp(_attend_blocks, _attend_blocks_bwd)


# -- what a caller's checkpoint may keep ---------------------------------------
# (below the kernels' call sites: a line that moves above them is a new
# cache key for every program that holds a kernel, PERF.md §6, PR 43)

#: the names either forward gives its output ([S, H, Dv] in v's dtype,
#: as it is handed back) and the rows' log-sum-exp ([H, S] float32) —
#: of the backward pass's residuals (q, k, v, out, lse, seg) the two
#: that only the forward KERNEL can make again. A caller that runs
#: attention under ``jax.checkpoint`` with the policy
#: ``save_only_these_names(*KEPT)`` keeps the two, and its backward
#: pass recomputes q, k and v but does not run the forward kernel a
#: second time; without a policy the names do nothing and the
#: checkpoint recomputes the kernel, as it does everything else.
KEPT = ("attn_out", "attn_lse")


def _named(out, lse):
    from jax.ad_checkpoint import checkpoint_name

    return tuple(checkpoint_name(x, name) for x, name in zip((out, lse), KEPT))
