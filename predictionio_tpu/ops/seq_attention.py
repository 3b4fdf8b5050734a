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


# -- the kernels ---------------------------------------------------------------


def _scores(q, k, first, rows, at, scale):
    """One tile: float32 scores [bq, bk] and which of them are real —
    key ``at + column`` lies in ``first … row`` of its query row."""
    s = jax.lax.dot_general(q, k, _NT,
                            preferred_element_type=jnp.float32) * scale
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return s, (col >= first - at) & (col <= rows - at)


def _rows_of(block, bq):
    return block * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)


def _fwd_kernel(lo_ref, q_ref, k_ref, v_ref, first_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, scale, bk):
    i = pl.program_id(1)
    bq = q_ref.shape[0]
    q, first, rows = q_ref[...], first_ref[:, :1], _rows_of(i, bq)
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def tile(j, _):
        at = pl.multiple_of(j * bk, bk)
        s, real = _scores(q, k_ref[pl.ds(at, bk), :], first, rows, at, scale)
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

    jax.lax.fori_loop(lo_ref[i], ((i + 1) * bq - 1) // bk + 1, tile, None)
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
    lse_ref[...] = jnp.broadcast_to(m_ref[...] + jnp.log(l_ref[...]),
                                    lse_ref.shape)


def _dq_kernel(lo_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               first_ref, dq_ref, acc_ref, *, scale, bk):
    """A block of query rows against the key tiles it reaches: the
    probabilities recomputed from the rows' log-sum-exp, the scores'
    cotangent, its product with the keys."""
    i = pl.program_id(1)
    bq = q_ref.shape[0]
    q, do, rows = q_ref[...], do_ref[...], _rows_of(i, bq)
    lse, delta, first = lse_ref[:, :1], delta_ref[:, :1], first_ref[:, :1]
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def tile(j, _):
        at = pl.multiple_of(j * bk, bk)
        k = k_ref[pl.ds(at, bk), :]
        s, real = _scores(q, k, first, rows, at, scale)
        p = jnp.where(real, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v_ref[pl.ds(at, bk), :], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        acc_ref[...] += jnp.dot(ds.astype(k.dtype), k,
                                preferred_element_type=jnp.float32)
        return _

    jax.lax.fori_loop(lo_ref[i], ((i + 1) * bq - 1) // bk + 1, tile, None)
    dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(hi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                first_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale, bq,
                group):
    """A key tile against the query blocks that reach it, of each of
    the ``group`` query heads that read this key-value head (their
    rows lie one head after another: head g's block i is block
    ``g · blocks + i``), TRANSPOSED: scores [keys, rows], so that both
    products into dk and dv take their left operand as it lies and
    nothing is turned; the rows' numbers come as rows ([blocks, 1, bq],
    a block by its index)."""
    j = pl.program_id(1)
    bk = k_ref.shape[0]
    blocks = first_ref.shape[0]
    k, v = k_ref[...], v_ref[...]
    keys = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
    dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
    dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    for g in range(group):
        def block(i, _, at=g * blocks):
            rs = pl.ds(pl.multiple_of((at + i) * bq, bq), bq)
            q, do = q_ref[rs, :], do_ref[rs, :]
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (1, bq), 1)
            s = jax.lax.dot_general(
                k, q, _NT, preferred_element_type=jnp.float32) * scale
            p = jnp.where((keys >= first_ref[i]) & (keys <= rows),
                          jnp.exp(s - lse_ref[at + i]), 0.0)
            dp = jax.lax.dot_general(v, do, _NT,
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[at + i]) * scale
            dv_acc[...] += jnp.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
            dk_acc[...] += jnp.dot(ds.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)
            return _

        jax.lax.fori_loop(j * bk // bq, hi_ref[j] + 1, block, None)
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


def _forward(q, k, v, first, lo, bq, bk, scale, interpret):
    H, S, D = q.shape
    Dv, g = v.shape[-1], H // k.shape[0]
    return _call(
        functools.partial(_fwd_kernel, scale=scale, bk=bk),
        "seq_attention_fwd", S // bq, lo, (q, k, v, _lanes(first)),
        [_head(bq, D), _head(S, D, True, g), _head(S, Dv, True, g),
         _first(bq)],
        [jax.ShapeDtypeStruct((H, S, Dv), v.dtype),
         jax.ShapeDtypeStruct((H, S, _LANES), jnp.float32)],
        [_head(bq, Dv), _head(bq, _LANES)],
        [pltpu.VMEM((bq, 1), jnp.float32), pltpu.VMEM((bq, 1), jnp.float32),
         pltpu.VMEM((bq, Dv), jnp.float32)], interpret)


def _backward(q, k, v, do, lse, delta, first, lo, hi, bq, bk, scale,
              interpret):
    """``lse``, ``delta`` [H, S] and ``first`` [S]: dq reads a row's
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
        functools.partial(_dq_kernel, scale=scale, bk=bk),
        "seq_attention_dq", S // bq, lo,
        (q, k, v, do, _lanes(lse), _lanes(delta), _lanes(first)),
        [_head(bq, D), _head(S, D, True, g), _head(S, Dv, True, g),
         _head(bq, Dv), _head(bq, _LANES), _head(bq, _LANES), _first(bq)],
        [jax.ShapeDtypeStruct(q.shape, q.dtype)], [_head(bq, D)],
        [pltpu.VMEM((bq, D), jnp.float32)], interpret)
    # the grid's heads: the key-value heads with their whole group, or
    # (split) the query heads, each reading key-value head h ÷ g
    heads, rows, per = (H, 1, g) if split else (Hkv, g, 1)
    as_rows = pl.BlockSpec((None, rows * S // bq, 1, bq),
                           lambda h, j, _: (h, 0, 0, 0))
    dk, dv = _call(
        functools.partial(_dkv_kernel, scale=scale, bq=bq, group=rows),
        "seq_attention_dkv", S // bk, hi,
        (q.reshape(heads, rows * S, D), k, v,
         do.reshape(heads, rows * S, Dv), lse.reshape(heads, -1, 1, bq),
         delta.reshape(heads, -1, 1, bq), first.reshape(-1, 1, bq)),
        [_head(rows * S, D, True), _head(bk, D, group=per),
         _head(bk, Dv, group=per), _head(rows * S, Dv, True), as_rows,
         as_rows, pl.BlockSpec((S // bq, 1, bq), lambda h, j, _: (0, 0, 0))],
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
    out = _heads_first(out)
    # one number a row is kept for the backward pass, not its 128 lanes
    return out, (q, k, v, out, lse[..., 0], seg)


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
