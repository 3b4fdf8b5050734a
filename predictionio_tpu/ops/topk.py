"""Streaming score→top-k over item tiles — the serving hot path kernel.

Recommendation serving scores a query batch against the full item-factor
matrix and keeps the top-k: ``scores = Q Vᵀ`` is (B, n_items) — at
ML-20M scale that is a 100+MB intermediate per batch that XLA would
materialize in HBM between the matmul and the top_k (reference serving
does the same dense score in JVM memory: [U] MLlib
``MatrixFactorizationModel.recommendProducts`` — SURVEY.md §3.2).

This kernel tiles the item axis: each grid step does one (B,d)×(d,T)
matmul on the MXU and folds the tile into a running (B, k) best-list in
VMEM scratch, so HBM traffic is just Q + V + the (B,k) result. The
running merge uses only max/min reductions (no sort/top_k primitive —
portable Mosaic).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -3.0e38  # finite "-inf" (python float so the kernel doesn't capture a traced constant)


def _mask_pad_rows(Q, rows_valid):
    """Zero query rows ≥ ``rows_valid`` (a TRACED scalar, so one
    executable serves every real batch size within a padded bucket).
    Zeroed rows produce all-zero scores — defined, finite outputs for
    the pad rows the caller slices off — and cannot perturb real rows
    (each batch row's score/top-k is row-independent), which is the
    padded-parity guarantee tests/test_aot_serving.py asserts
    bitwise."""
    row = jax.lax.broadcasted_iota(jnp.int32, (Q.shape[0], 1), 0)
    return jnp.where(row < rows_valid, Q, jnp.zeros_like(Q))


@functools.partial(jax.jit, static_argnames=("k", "n_valid"))
def score_topk_xla(Q, V, k: int, n_valid: int = 0, rows_valid=None):
    """XLA fallback: full (B, N) score matrix then lax.top_k.

    ``n_valid``: real row count when V carries tail padding (lets a
    caller share one padded resident copy with :func:`score_topk`).
    ``rows_valid``: optional traced scalar — real BATCH-row count when
    Q carries AOT-bucket padding; pad rows are masked (see
    :func:`_mask_pad_rows`).
    Jitted: the serving path must be ONE dispatch — eager ops each pay
    a host→device round trip.
    """
    if rows_valid is not None:
        Q = _mask_pad_rows(Q, rows_valid)
    scores = jnp.dot(Q, V.T, preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)
    if n_valid and n_valid < V.shape[0]:
        col = jnp.arange(V.shape[0])[None, :]
        scores = jnp.where(col < n_valid, scores, _NEG)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx.astype(jnp.int32)


def _topk_kernel(Q_ref, V_ref, vals_ref, idx_ref, best_v, best_i,
                 *, k: int, tile: int, n_items: int):
    step = pl.program_id(0)
    n_steps = pl.num_programs(0)

    @pl.when(step == 0)
    def _():
        best_v[:] = jnp.full_like(best_v, _NEG)
        best_i[:] = jnp.zeros_like(best_i)

    B = Q_ref.shape[0]
    scores = jax.lax.dot_general(              # (B, T) on the MXU
        Q_ref[:], V_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)   # f32 scores → stable ranking
    col = jax.lax.broadcasted_iota(jnp.int32, (B, tile), 1) + step * tile
    scores = jnp.where(col < n_items, scores, _NEG)  # mask tail padding

    cand_v = jnp.concatenate([best_v[:], scores], axis=1)        # (B, k+T)
    cand_i = jnp.concatenate([best_i[:], col], axis=1)
    pos = jax.lax.broadcasted_iota(jnp.int32, cand_v.shape, 1)
    BIG = jnp.int32(2**30)

    # k rounds of (max, first-argmax-by-min-position, knock out) — selection
    # via pure max/min reductions, k is small and static.
    for j in range(k):
        m = jnp.max(cand_v, axis=1)                               # (B,)
        hit = cand_v == m[:, None]
        p = jnp.min(jnp.where(hit, pos, BIG), axis=1)             # (B,)
        sel = pos == p[:, None]
        best_v[:, j] = m
        best_i[:, j] = jnp.sum(jnp.where(sel, cand_i, 0), axis=1)
        cand_v = jnp.where(sel, _NEG, cand_v)

    @pl.when(step == n_steps - 1)
    def _():
        vals_ref[:] = best_v[:]
        idx_ref[:] = best_i[:]


# -- PQ asymmetric-distance scan + re-rank (ann subsystem math) ---------------
#
# Pure traceable functions (no jit here): predictionio_tpu/ann/scorer.py
# fuses gather → ADC scan → shortlist → exact re-rank into ONE jitted
# serving program per AOT bucket; keeping the math in ops/ keeps the
# layering of the exact path (ops holds math, the caller owns residency
# and compilation).


#: columns per streamed ADC tile — the live score set is (B, _ADC_CHUNK)
#: f32 (8 MB at B=64), cache/VMEM-resident, independent of corpus size
_ADC_CHUNK = 32768


def _adc_lut(Q, codebooks):
    """(B, m, K) table of query-subvector · centroid inner products."""
    B = Q.shape[0]
    m, K, dsub = codebooks.shape
    return jnp.einsum("bmd,mkd->bmk", Q.reshape(B, m, dsub),
                      codebooks, preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)


def _adc_sum(lut, codesT):
    """Sum LUT entries along each item's code word → (B, n) scores.
    The m-loop is a static Python unroll (m is small); each step is
    one (B, K) table gather → (B, n) add."""
    scores = jnp.zeros((lut.shape[0], codesT.shape[1]), jnp.float32)
    for mi in range(codesT.shape[0]):
        scores = scores + jnp.take(lut[:, mi, :], codesT[mi], axis=1)
    return scores


def adc_scores(Q, codebooks, codesT):
    """Asymmetric-distance (inner-product) scores of queries against a
    product-quantized corpus, dense: (B, N).

    ``Q``: (B, d) float queries; ``codebooks``: (m, K, d/m) PQ
    centroids; ``codesT``: (m, N) uint8 code matrix (transposed so each
    subspace's codes are a contiguous gather). Materializes the full
    (B, N) score matrix — fine for parity tests and small corpora; the
    serving path uses :func:`adc_shortlist`, which streams.
    """
    return _adc_sum(_adc_lut(Q, codebooks), codesT)


def adc_shortlist(Q, codebooks, codesT, kprime: int,
                  chunk: int = _ADC_CHUNK, *, n_valid: int = 0,
                  col_offset=None):
    """Top-``kprime`` shortlist by ADC score → (vals, idx (B, k′) i32).

    Streams the corpus in ``chunk``-column tiles: each
    :func:`jax.lax.scan` step sums the m LUT gathers for one tile and
    keeps the tile-local top-k′; one final top-k′ over the
    (n_tiles · k′) tile winners merges them. The result is identical to
    a full-scan top-k (every global winner wins its own tile), but the
    (B, N) score matrix is never materialized — the live set is
    (B, chunk), so a 10M-item scan holds steady at megabytes where the
    dense scan needs gigabytes of HBM per batch.

    Sharded serving runs this per mesh shard on a contiguous column
    block of the global code matrix: ``col_offset`` (traced scalar ok —
    it is ``axis_index * local_n`` inside shard_map) is added to the
    returned indices so they are GLOBAL corpus rows, and ``n_valid``
    (static, global real item count) masks pad columns past the corpus
    tail. Defaults leave the single-device path byte-identical.
    """
    m = codesT.shape[0]
    N = codesT.shape[1]
    B = Q.shape[0]
    lut = _adc_lut(Q, codebooks)
    if N <= 2 * chunk or kprime > chunk:   # small corpus: one dense tile
        s = _adc_sum(lut, codesT)
        if n_valid or col_offset is not None:
            col = jnp.arange(N, dtype=jnp.int32)[None, :]
            if col_offset is not None:
                col = col + col_offset
            if n_valid:
                s = jnp.where(col < n_valid, s, _NEG)
        vals, idx = jax.lax.top_k(s, kprime)
        idx = idx.astype(jnp.int32)
        if col_offset is not None:
            idx = idx + col_offset
        return vals, idx
    n_tiles = -(-N // chunk)
    pad = n_tiles * chunk - N
    ct = codesT
    if pad:
        ct = jnp.concatenate([ct, jnp.zeros((m, pad), ct.dtype)], axis=1)
    ct = jnp.moveaxis(ct.reshape(m, n_tiles, chunk), 1, 0)  # (T, m, chunk)
    starts = jnp.arange(n_tiles, dtype=jnp.int32) * chunk
    if not n_valid:
        local_valid = N              # mask only the chunk-pad tail
    elif col_offset is None:
        local_valid = n_valid
    else:
        local_valid = n_valid - col_offset   # global bound, local columns

    def tile_step(carry, xs):
        codes, start = xs
        s = _adc_sum(lut, codes)                            # (B, chunk)
        col = start + jnp.arange(chunk, dtype=jnp.int32)
        s = jnp.where((col < local_valid)[None, :], s, _NEG)  # tail padding
        v, i = jax.lax.top_k(s, kprime)
        i = i + start
        if col_offset is not None:
            i = i + col_offset
        return carry, (v, i.astype(jnp.int32))

    _, (tv, ti) = jax.lax.scan(tile_step, 0, (ct, starts))
    tv = jnp.moveaxis(tv, 0, 1).reshape(B, n_tiles * kprime)
    ti = jnp.moveaxis(ti, 0, 1).reshape(B, n_tiles * kprime)
    vals, loc = jax.lax.top_k(tv, kprime)
    return vals, jnp.take_along_axis(ti, loc, axis=1)


def merge_shortlists(vals, idx, kprime: int):
    """Distributed top-k′ merge: (S, B, k′) per-shard shortlists (as
    produced by ``all_gather`` over the ``shards`` axis) → global
    (B, k′) (vals, idx).

    A small dense top-k over the (k′ · S) gathered candidates — every
    global winner won its own shard, so this equals a top-k′ over the
    full dense ADC scores. With S=1 the input is already sorted and
    ``lax.top_k`` (stable, lowest-index tie-break) returns it
    unchanged, which is what keeps the one-shard program bitwise equal
    to the single-device scorer.
    """
    S, B, kp = vals.shape
    v = jnp.moveaxis(vals, 0, 1).reshape(B, S * kp)
    i = jnp.moveaxis(idx, 0, 1).reshape(B, S * kp)
    mv, loc = jax.lax.top_k(v, kprime)
    return mv, jnp.take_along_axis(i, loc, axis=1)


def rerank_partial(Q, V_local, idx, col_offset):
    """This shard's contribution to the exact re-rank of a GLOBAL
    candidate list: scores the candidates whose corpus row lives in
    this shard's ``V_local`` block (rows [col_offset, col_offset +
    local_n)), zero elsewhere — a ``psum`` over the ``shards`` axis
    assembles the full exact scores without ever gathering V.

    Pure per-shard math (no collectives — the caller owns the mesh);
    out-of-shard rows clip to a valid local row and are masked to 0.0,
    so every shard does identical work (no divergent gathers).
    """
    local_n = V_local.shape[0]
    own = (idx >= col_offset) & (idx < col_offset + local_n)
    lrow = jnp.clip(idx - col_offset, 0, local_n - 1)
    Vs = V_local[lrow]                                      # (B, k', d)
    exact = jnp.einsum("bd,bqd->bq", Q, Vs,
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
    return jnp.where(own, exact, 0.0)


def rerank_topk(Q, V, shortlist_idx, k: int):
    """Exact re-rank of a per-row shortlist against float embeddings.

    Gathers only the (B, k′, d) shortlist rows of ``V`` — never the
    full corpus — scores them exactly, and returns the top-``k``
    (vals, idx) with ``idx`` mapped back to corpus row indices.
    """
    Vs = V[shortlist_idx]                                   # (B, k', d)
    exact = jnp.einsum("bd,bqd->bq", Q, Vs,
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
    vals, loc = jax.lax.top_k(exact, k)
    idx = jnp.take_along_axis(shortlist_idx, loc, axis=1)
    return vals, idx.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("k", "tile", "n_valid", "interpret"))
def score_topk(Q, V, k: int, *, tile: int = 512, n_valid: int = 0,
               rows_valid=None, interpret: bool = False):
    """(B,d),(N,d) → top-k (vals (B,k), idx (B,k)) of Q·Vᵀ, streamed.

    Pass a pre-padded V (rows a multiple of ``tile``) with ``n_valid``
    set to the real item count to avoid a per-call pad of the factor
    matrix on the serving hot path. ``rows_valid`` (traced scalar)
    masks AOT-bucket pad rows of Q before the kernel — same contract
    as :func:`score_topk_xla`.
    """
    if rows_valid is not None:
        Q = _mask_pad_rows(Q, rows_valid)
    B, d = Q.shape
    N = n_valid or V.shape[0]
    n_pad = -V.shape[0] % tile
    if n_pad:
        V = jnp.concatenate([V, jnp.zeros((n_pad, d), V.dtype)], axis=0)
    grid = (V.shape[0] // tile,)
    kern = functools.partial(_topk_kernel, k=k, tile=tile, n_items=N)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((B, k), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((B, k), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
        ),
        scratch_shapes=[
            pltpu.VMEM((B, k), jnp.float32),
            pltpu.VMEM((B, k), jnp.int32),
        ],
        name="score_topk",
        cost_estimate=pl.CostEstimate(
            flops=2 * B * d * V.shape[0] + 2 * B * k * V.shape[0],
            bytes_accessed=4 * (B * d + V.shape[0] * d + 2 * B * k),
            transcendentals=0,
        ),
        interpret=interpret,
    )(Q, V)
