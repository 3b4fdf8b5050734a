"""Manifold-constrained hyper-connections: the residual path of a block
whose stream is ``n`` copies wide ("mHC: Manifold-Constrained
Hyper-Connections", arXiv:2512.24880, after "Hyper-Connections",
arXiv:2409.19606).

Per token the stream is X ∈ R^{n×d}. Around a sublayer F with its own
φ ∈ R^{nd×(2n+n²)}, b ∈ R^{2n+n²} and α ∈ R³:

    x̂ = vec(X) / rms(vec(X));  m = x̂ φ
    H_pre  = σ(α₁·m[:n] + b[:n])            read:   u = Σᵢ H_pre[i]·X[i]
    H_post = 2·σ(α₂·m[n:2n] + b[n:2n])      y = F(u)
    A = clamp(α₃·mat(m[2n:]) + mat(b[2n:]));  M⁽⁰⁾ = exp(A)
    ``iters`` times: every row ÷ (its sum + ε), then every column ÷
    (its sum + ε);  H_res = M
    write:  X[i] ← Σⱼ H_res[i, j]·X[j] + H_post[i]·y

The LAYOUT is the work. The stream is ``[n, T, d]``: the copies a
LEADING axis, so that a mix is n multiply-adds over ``[T, d]`` slabs —
never a batched product of depth n, whose 4 × 4 would lie in (8, 128)
tiles at 1/64 of their room. The coefficients are ``[…, T]``: the
TOKENS on the lane axis, the 2n + n² numbers of a token down the
sublanes, and every sum of the Sinkhorn chain n unrolled adds of
``[n, T]`` slices (no reduction: the whole chain is one elementwise
pass). φ is kept ``[n, d, 2n + n²]`` — row i·d + k of the paper's matrix
is ``phi[i, k]`` — so that m is n products summed, the stream never
reshaped to ``[T, n·d]`` (a transposition of all of it).

Everything is float32 but φ's product, whose operands are the caller's
matmul dtype with float32 accumulation; since x̂ has no gain, the
product is taken of X itself and scaled by 1/rms afterwards — the
normed stream is never made.
"""

from __future__ import annotations

from typing import Tuple


def _total(parts):
    """Σ of equal-shaped arrays as unrolled adds: slabs added one to the
    next, not a reduction over a short leading axis."""
    out = parts[0]
    for part in parts[1:]:
        out = out + part
    return out


def copies(x, n: int):
    """x [..., d] → the stream's start [n, ..., d]: n copies."""
    import jax.numpy as jnp

    return jnp.broadcast_to(x[None], (n,) + x.shape)


def fold(X):
    """The stream's end: Σᵢ X[i]."""
    return _total([X[i] for i in range(X.shape[0])])


def sinkhorn(M, iters: int, eps: float):
    """M [n, n, T] positive → ``iters`` times rows (axis 1 summed) then
    columns (axis 0 summed) divided by their sum + ``eps``; the sums are
    n unrolled adds of [n, T] slices."""
    n = M.shape[0]
    for _ in range(iters):
        rows = _total([M[:, j] for j in range(n)])
        M = M / (rows[:, None] + eps)
        cols = _total([M[i] for i in range(n)])
        M = M / (cols[None] + eps)
    return M


def ds_error(res):
    """The largest |row or column sum − 1| of H_res [n, n, T]: how far
    from doubly stochastic the chain left it (a float32 scalar)."""
    import jax.numpy as jnp

    return jnp.maximum(jnp.abs(res.sum(1) - 1.0).max(),
                       jnp.abs(res.sum(0) - 1.0).max())


def coefficients(X, phi, b, alpha, *, norm_eps: float, iters: int,
                 eps: float, clamp: Tuple[float, float], dtype):
    """X [n, T, d] float32, ``phi`` [n, d, 2n + n²], ``b`` [2n + n²],
    ``alpha`` [3] → (H_pre [n, T], H_post [n, T], H_res [n, n, T]),
    float32, the tokens on the last axis. ``dtype``: the operands of
    φ's product (float32 accumulation)."""
    import jax
    import jax.numpy as jnp

    n, T, d = X.shape
    square = _total([X[i] * X[i] for i in range(n)])
    inv = jax.lax.rsqrt(square.sum(-1) / (n * d) + norm_eps)        # [T]
    m = _total([jax.lax.dot_general(
        phi[i].astype(dtype), X[i].astype(dtype), (((0,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) for i in range(n)]) * inv  # [c, T]
    b = b.astype(jnp.float32)[:, None]
    pre = jax.nn.sigmoid(alpha[0] * m[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + b[n:2 * n])
    a = jnp.clip(alpha[2] * m[2 * n:] + b[2 * n:], *clamp)
    res = sinkhorn(jnp.exp(a).reshape(n, n, T), iters, eps)
    return pre, post, res


def read(X, pre):
    """u = Σᵢ H_pre[i]·X[i]: X [n, T, d], ``pre`` [n, T] → [T, d]."""
    return _total([pre[i][:, None] * X[i] for i in range(X.shape[0])])


def write(X, res, post, y):
    """X[i] ← Σⱼ H_res[i, j]·X[j] + H_post[i]·y: X [n, T, d], ``res``
    [n, n, T], ``post`` [n, T], y [T, d] → [n, T, d]; n + 1
    multiply-adds a copy, unrolled."""
    import jax.numpy as jnp

    n = X.shape[0]
    return jnp.stack([_total(
        [post[i][:, None] * y] + [res[i, j][:, None] * X[j]
                                  for j in range(n)]) for i in range(n)])
