"""Batched SPD solve as MXU matmuls — the ALS normal-equation solver.

MLlib solves each entity's k×k normal equations with one LAPACK
``dppsv`` call per row (reference behavior: [U] mllib ALS
NormalEquation / CholeskySolver — SURVEY.md §2d P2). The direct XLA
translation (``jnp.linalg.cholesky`` + two ``triangular_solve``) is
catastrophically slow on TPU for large batches of small matrices: both
ops lower to *sequential* column loops that leave the MXU idle
(measured 1.28 s for a (138k, 64, 64) batch on v5e — ~70% of the whole
ALS iteration).

This module reorganizes the same factorization so ~all FLOPs are
batched matmuls, which XLA tiles onto the MXU:

- ``L⁻¹`` is built by **recursive 2×2 blocking**::

      inv(chol([[A11,   ·],          [[L11⁻¹,        0],
                [A21, A22]]))    =    [-L22⁻¹L21L11⁻¹, L22⁻¹]]

  where ``L21 = A21·L11⁻ᵀ`` and ``L22⁻¹ = inv(chol(A22 − L21·L21ᵀ))``
  — every step a batched (h×h) matmul except the ≤8×8 leaves, which use
  an unrolled Cholesky–Banachiewicz + forward substitution vectorized
  over the batch (scalar ops on (n,) lanes, VPU work).
- The solve is then two batched matvecs: ``x = L⁻ᵀ(L⁻¹b)``.

Same flop count and numerical profile as LAPACK's blocked algorithm
(explicit triangular inverses are benign here: ALS systems carry a
``λ·n_e·I`` ridge, so condition numbers are modest); ~25× faster than
the sequential lowering at ALS scale.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_LEAF = 8  # unrolled base-case size


def _mm(a, b):
    """Batched matmul in full f32 precision.

    XLA's batched dot on TPU loops the (huge) batch dim with a fixed
    ~1–6 ms cost per op at these shapes, so for the small half-block
    contractions (h ≤ 32) and for matvecs a broadcast-multiply-reduce —
    pure fused VPU work, exact f32 — is 3–10× faster (measured on v5e:
    0.1/0.6/3.8 ms vs 1.2/2.8/5.5 ms per op at h=8/16/32, batch 65k).
    Larger contractions go to the MXU via einsum at HIGHEST precision
    (ALS solves are sensitive to Gram/solve precision — see ops/gram.py).
    """
    if a.shape[-1] <= 32 or b.shape[-1] == 1:
        return (a[..., :, :, None] * b[..., None, :, :]).sum(-2)
    return jnp.einsum("...ij,...jk->...ik", a, b,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _t(a):
    return jnp.swapaxes(a, -1, -2)


def _chol_inv_leaf(A):
    """(..., m, m) SPD with m ≤ _LEAF → L⁻¹, vectorized over the batch
    dims.

    Column-vectorized: m rank-1 downdates build L, then m forward-
    substitution rows build L⁻¹ — ~10 traced ops per column instead of
    the earlier fully-unrolled ~m³/3 scalar graph. Same flops, same
    numerics, but ~5× less HLO: with ~tens of inlined call sites in the
    ALS program the unrolled leaf dominated XLA compile time (258 s at
    ML-20M geometry).

    The matrix dims are moved to the FRONT so every step reads
    contiguous (batch,) lanes — (..., i, j) slices would re-read the
    strided (..., m, m) buffer (measured 13 ms vs <1 ms per leaf at
    batch 65k on v5e)."""
    m = A.shape[-1]
    At = jnp.moveaxis(A, (-2, -1), (0, 1))  # (m, m, *batch)
    bshape = (1,) * (At.ndim - 2)
    lane = jnp.arange(m).reshape((m,) + bshape)
    cols = []  # cols[j][i] = L[i, j], each (m, *batch)
    for j in range(m):
        # the ridge keeps diagonals strictly positive; the floor only
        # guards padded identity blocks from rounding
        d = jnp.sqrt(jnp.maximum(At[j, j], 1e-30))
        col = jnp.where(lane >= j, At[:, j] / d, 0.0)
        At = At - col[:, None] * col[None, :]
        cols.append(col)
    inv = []  # rows of L⁻¹, each (m, *batch)
    for i in range(m):
        s = jnp.where(lane == i, jnp.ones_like(cols[0]), 0.0)
        for p in range(i):
            s = s - cols[p][i] * inv[p]
        inv.append(jnp.where(lane <= i, s / cols[i][i], 0.0))
    out = jnp.stack(inv, axis=0)  # (i, j, *batch)
    return jnp.moveaxis(out, (0, 1), (-2, -1))


def _chol_inv(A):
    """(..., m, m) SPD, m a power of two ≥ _LEAF → L⁻¹ by 2×2 block
    recursion (batched MXU matmuls at every level)."""
    m = A.shape[-1]
    if m <= _LEAF:
        return _chol_inv_leaf(A)
    h = m // 2
    A11 = A[..., :h, :h]
    A21 = A[..., h:, :h]
    A22 = A[..., h:, h:]
    L11i = _chol_inv(A11)
    L21 = _mm(A21, _t(L11i))          # A21 · L11⁻ᵀ
    S = A22 - _mm(L21, _t(L21))       # Schur complement
    L22i = _chol_inv(S)
    B = -_mm(L22i, _mm(L21, L11i))
    zeros = jnp.zeros(A.shape[:-2] + (h, m - h), A.dtype)
    return jnp.concatenate([
        jnp.concatenate([L11i, zeros], axis=-1),
        jnp.concatenate([B, L22i], axis=-1),
    ], axis=-2)


@jax.jit
def _chol_solve(A, b):
    """jit-wrapped so tracing is cached per (batch, k) shape — callers
    like the ALS program may instantiate several solves, and re-tracing
    the recursive graph at every call site multiplies lowering time.
    (The ALS program additionally arranges to contain only ONE solve
    shape at all — see models/als.py ``_SOLVE_CHUNK``.)"""
    k = A.shape[-1]
    m = _LEAF
    while m < k:
        m *= 2
    if m != k:
        pad = m - k
        batch_pad = [(0, 0)] * (A.ndim - 2)
        A = jnp.pad(A, batch_pad + [(0, pad), (0, pad)])
        tail = jnp.concatenate(
            [jnp.zeros(k, A.dtype), jnp.ones(pad, A.dtype)])
        A = A + jnp.diag(tail)
        b = jnp.pad(b, batch_pad + [(0, pad)])
    Li = _chol_inv(A)
    y = _mm(Li, b[..., None])
    x = _mm(_t(Li), y)[..., 0]
    return x[..., :k]


def chol_solve_batched(A, b, kernel: bool = False):
    """Solve the batched SPD systems ``A x = b``.

    A: (..., k, k) SPD (symmetric positive definite — ALS adds a ridge),
    b: (..., k) → x: (..., k). Any k ≥ 1.

    The XLA block-recursive path (internally padded to a power of two
    with an identity block, which factors to itself and leaves the k×k
    solve untouched), or with ``kernel`` the Pallas VMEM-resident
    kernel (:func:`chol_solve_pallas`) for the batches that have its
    shape, (N ≥ 256, k, k). ``kernel`` is the caller's rule, not a
    preference: ``models/als.py`` sets it exactly when the Gram is the
    compiled fused kernel — a caller already in the fat-dispatch regime
    wants the ~50-op XLA solve recursion collapsed to one kernel per
    chunk, or the solve pass alone re-creates the dispatch wall the
    Gram fusion removed. Nothing is tried and caught: on a TPU a
    selected kernel compiles or the caller fails with the compiler's
    message.
    """
    A = jnp.asarray(A, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    if kernel and A.ndim == 3 and A.shape[0] >= 256:
        return chol_solve_pallas(A, b)
    return _chol_solve(A, b)


# -- Pallas VMEM-resident blocked solve ---------------------------------------
#
# The XLA recursion above is ~50 separate HLO ops per solve; between
# them every (batch, h, h) intermediate round-trips through HBM —
# measured ~80 ms/iteration at ML-20M (41 chunks × 4096 systems)
# against a ~3 ms read-the-operands-once roofline. This kernel holds a
# batch tile entirely in VMEM and runs a blocked (LAPACK-style,
# 8×8 blocks) Cholesky factor + forward/backward substitution with NO
# intermediate HBM traffic.
#
# Layout: batch lives on the LANE dimension — work arrays are
# (8, 8, bt) / (8, bt) with bt = 128, so every elementwise op runs on
# full (8, 128) f32 vregs (a (bt, 8, 8) layout would use 8 of 128
# lanes). The caller transposes A to (k, k, N) once in XLA (one
# efficient pass) and the grid walks lane-dim tiles.

_BT = 128  # batch tile = one f32 lane group


def _t_l(a):
    """Transpose of a lane-major block: (i, j, bt) → (j, i, bt)."""
    return jnp.swapaxes(a, 0, 1)


def _bmm_l(a, b):
    """(m, m, bt) @ (m, m, bt) batched over lanes: full-width VPU."""
    return (a[:, :, None, :] * b[None, :, :, :]).sum(axis=1)


def _bmv_l(L, y):
    """(m, m, bt) @ (m, bt) → (m, bt)."""
    return (L * y[None, :, :]).sum(axis=1)


def _leaf_inv_lanes(S):
    """L⁻¹ of an (m, m, bt) SPD block, m ≤ 8, batch on lanes — the
    lane-major twin of :func:`_chol_inv_leaf` (same math)."""
    m = S.shape[0]
    At = S
    lane = jnp.arange(m).reshape(m, 1)
    cols = []
    for j in range(m):
        d = jnp.sqrt(jnp.maximum(At[j, j], 1e-30))
        col = jnp.where(lane >= j, At[:, j] / d, 0.0)      # (m, bt)
        At = At - col[:, None, :] * col[None, :, :]
        cols.append(col)
    inv = []
    for i in range(m):
        s = jnp.where(lane == i, jnp.ones_like(cols[0]), 0.0)
        for p in range(i):
            s = s - cols[p][i] * inv[p]
        inv.append(jnp.where(lane <= i, s / cols[i][i], 0.0))
    return jnp.stack(inv, axis=0)                          # (m, m, bt)


def _solve_kernel(At_ref, bt_ref, x_ref, *, k: int):
    A = At_ref[...]            # (k, k, bt)
    b = bt_ref[...]            # (k, bt)
    m = k // _LEAF

    def blk(i, j):
        return A[_LEAF * i:_LEAF * (i + 1), _LEAF * j:_LEAF * (j + 1), :]

    # left-looking blocked factorization; only diagonal INVERSES and
    # off-diagonal L blocks are kept (VMEM-resident python dicts)
    L = {}
    Dinv = {}
    for j in range(m):
        S = blk(j, j)
        for p in range(j):
            S = S - _bmm_l(L[(j, p)], _t_l(L[(j, p)]))
        Dinv[j] = _leaf_inv_lanes(S)
        for i in range(j + 1, m):
            S2 = blk(i, j)
            for p in range(j):
                S2 = S2 - _bmm_l(L[(i, p)], _t_l(L[(j, p)]))
            L[(i, j)] = _bmm_l(S2, _t_l(Dinv[j]))

    # forward substitution: L y = b
    y = []
    for i in range(m):
        s = b[_LEAF * i:_LEAF * (i + 1), :]
        for p in range(i):
            s = s - _bmv_l(L[(i, p)], y[p])
        y.append(_bmv_l(Dinv[i], s))
    # backward substitution: Lᵀ x = y
    x = [None] * m
    for i in reversed(range(m)):
        s = y[i]
        for p in range(i + 1, m):
            s = s - _bmv_l(_t_l(L[(p, i)]), x[p])
        x[i] = _bmv_l(_t_l(Dinv[i]), s)
    x_ref[...] = jnp.concatenate(x, axis=0)                # (k, bt)


def chol_solve_pallas(A, b, interpret: bool = False):
    """Batched SPD solve as ONE Pallas kernel: A (N, k, k), b (N, k)
    → x (N, k). Pads k to a multiple of 8 (identity tail) and N to the
    lane tile. ``interpret=True`` runs the Mosaic interpreter (CPU
    tests)."""
    import functools

    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, k = b.shape
    kp = -(-max(k, 1) // _LEAF) * _LEAF
    if kp != k:
        batch_pad = [(0, 0)]
        A = jnp.pad(A, batch_pad + [(0, kp - k), (0, kp - k)])
        tail = jnp.concatenate(
            [jnp.zeros(k, A.dtype), jnp.ones(kp - k, A.dtype)])
        A = A + jnp.diag(tail)
        b = jnp.pad(b, batch_pad + [(0, kp - k)])
    Np = -(-max(N, 1) // _BT) * _BT
    if Np != N:
        pad = Np - N
        eye_tail = jnp.broadcast_to(jnp.eye(kp, dtype=A.dtype),
                                    (pad, kp, kp))
        A = jnp.concatenate([A, eye_tail]) if N else eye_tail
        b = jnp.concatenate([b, jnp.zeros((pad, kp), b.dtype)]) if N \
            else jnp.zeros((pad, kp), b.dtype)
    At = jnp.transpose(A, (1, 2, 0))   # (k, k, Np) — one XLA pass
    bt = jnp.transpose(b, (1, 0))      # (k, Np)

    xt = pl.pallas_call(
        functools.partial(_solve_kernel, k=kp),
        grid=(Np // _BT,),
        in_specs=[
            pl.BlockSpec((kp, kp, _BT), lambda i: (0, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((kp, _BT), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((kp, _BT), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((kp, Np), jnp.float32),
        name="chol_solve",
        cost_estimate=pl.CostEstimate(
            flops=int(Np * (2 * kp**3 / 3 + 4 * kp**2)),
            bytes_accessed=4 * (Np * kp * kp + 3 * Np * kp),
            transcendentals=Np * kp,   # the sqrt per column
        ),
        interpret=interpret,
    )(At, bt)
    return jnp.transpose(xt, (1, 0))[:N, :k]
