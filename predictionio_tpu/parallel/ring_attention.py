"""Ring attention: sequence-parallel attention over a device mesh.

The reference has no sequence models (SURVEY.md §5 "long-context:
ABSENT"), but long-context is first-class in this framework: sequences
longer than one chip's HBM shard over the mesh's sequence axis, and
attention runs blockwise — each device keeps its query block resident
and the K/V blocks rotate around the ring (one ``ppermute`` per step,
riding ICI) while an online-softmax accumulator folds each block in.
Per-device memory is O(S_local·S_local) per step instead of O(S²), so
max sequence length scales linearly with device count.

The rotation/accumulation pattern follows the public blockwise ring
attention formulation (Liu et al., "Ring Attention with Blockwise
Transformers"); the online softmax is the standard streaming
max/denominator fold used by flash-style kernels.

Layout: ``[batch, seq, heads, head_dim]``, sharded on ``seq``. Causal
masking uses global positions reconstructed from each block's ring
origin, so results are exactly those of single-device causal attention.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def attention_reference(q, k, v, causal: bool = False,
                        scale: Optional[float] = None, k_mask=None):
    """Single-device softmax attention — the parity oracle for the ring
    path and the fallback when no mesh axis is available.

    q: [B, Sq, H, D]; k, v: [B, Sk, H, D] → [B, Sq, H, D].
    ``k_mask``: [B, Sk] bool, False = key position masked out (padding).
    Fully-masked query rows yield zeros, not NaN.
    """
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qi = jnp.arange(q.shape[1])[:, None]
        ki = jnp.arange(k.shape[1])[None, :]
        s = jnp.where((ki > qi)[None, None], -jnp.inf, s)
    if k_mask is not None:
        s = jnp.where(k_mask[:, None, None, :], s, -jnp.inf)
    m = s.max(axis=-1, keepdims=True)
    m = jnp.where(jnp.isneginf(m), 0.0, m)  # fully-masked rows → zeros
    p = jnp.exp(s - m)
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    denom = p.sum(axis=-1, keepdims=True)
    p = p / jnp.where(denom == 0.0, 1.0, denom)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _fold_block(carry, kv, q, q_pos, k_pos, scale, causal: bool,
                k_mask=None):
    """Online-softmax fold of one K/V block into (o, m, l).

    o: [B, Sq, H, D] unnormalized output, m: [B, H, Sq] running max,
    l: [B, H, Sq] running denominator. ``k_mask``: [B, Sk_block] bool.
    """
    o, m, l = carry
    k, v = kv
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        s = jnp.where((k_pos[None, :] > q_pos[:, None])[None, None],
                      -jnp.inf, s)
    if k_mask is not None:
        s = jnp.where(k_mask[:, None, None, :], s, -jnp.inf)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # fully-masked-so-far rows keep m = -inf; their rescale factor is 0
    alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_new))
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(jnp.isneginf(s), 0.0, p)  # masked entries contribute 0
    l = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    o = o * alpha.transpose(0, 2, 1)[..., None] + pv
    return (o, m_new, l)


@functools.partial(jax.jit, static_argnames=("axis", "causal", "mesh"))
def _ring_attention_sharded(q, k, v, k_mask, *, mesh, axis: str,
                            causal: bool):
    from jax.sharding import PartitionSpec as P

    from predictionio_tpu.parallel.mesh import pvary

    n_dev = mesh.shape[axis]
    scale = 1.0 / np.sqrt(q.shape[-1])

    def local(q_l, k_l, v_l, mask_l):
        B, Sq, H, D = q_l.shape
        sk_local = k_l.shape[1]  # K blocks stride by THEIR length, not Sq
        my = jax.lax.axis_index(axis)
        q_pos = my * Sq + jnp.arange(Sq)

        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

        def fold(t, o_m_l, k_c, v_c, mask_c):
            # at step t this device holds the block that ORIGINATED at
            # ring position (my - t) mod n_dev
            src = (my - t) % n_dev
            k_pos = src * sk_local + jnp.arange(sk_local)
            return _fold_block(o_m_l, (k_c, v_c), q_l, q_pos, k_pos,
                               scale, causal, mask_c)

        def step(t, carry):
            o_m_l, k_c, v_c, mask_c = carry
            o_m_l = fold(t, o_m_l, k_c, v_c, mask_c)
            k_c = jax.lax.ppermute(k_c, axis, perm)
            v_c = jax.lax.ppermute(v_c, axis, perm)
            mask_c = jax.lax.ppermute(mask_c, axis, perm)
            return (o_m_l, k_c, v_c, mask_c)

        o0 = pvary(jnp.zeros(q_l.shape, jnp.float32), axis)
        m0 = pvary(jnp.full((B, H, Sq), -jnp.inf, jnp.float32), axis)
        l0 = pvary(jnp.zeros((B, H, Sq), jnp.float32), axis)
        # n_dev-1 rotated steps; the last block folds OUTSIDE the loop so
        # its ppermute set (whose result would be discarded) never issues
        o_m_l, k_c, v_c, mask_c = jax.lax.fori_loop(
            0, n_dev - 1, step, ((o0, m0, l0), k_l, v_l, mask_l))
        o, m, l = fold(n_dev - 1, o_m_l, k_c, v_c, mask_c)
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked query rows → zeros
        return (o / l.transpose(0, 2, 1)[..., None]).astype(q_l.dtype)

    spec = P(None, axis, None, None)
    mspec = P(None, axis)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(spec, spec, spec, mspec),
                       out_specs=spec)
    if k_mask is None:
        k_mask = jnp.ones(k.shape[:2], bool)
    return fn(q, k, v, k_mask)


def ring_attention(q, k, v, mesh=None, axis: str = "data",
                   causal: bool = False, k_mask=None):
    """Sequence-parallel attention; exact (up to fp error) vs
    :func:`attention_reference`.

    q, k, v: [B, S, H, D] with S divisible by the mesh axis size;
    ``k_mask``: optional [B, Sk] bool key-padding mask (False = masked).
    ``mesh=None`` (or a 1-device axis) falls back to the local oracle.
    """
    if mesh is None:
        return attention_reference(q, k, v, causal=causal, k_mask=k_mask)
    if axis not in mesh.axis_names:
        raise ValueError(
            f"mesh has no axis {axis!r} (axes: {mesh.axis_names}); "
            "pass mesh=None for single-device attention")
    if mesh.shape[axis] == 1:
        return attention_reference(q, k, v, causal=causal, k_mask=k_mask)
    n_dev = mesh.shape[axis]
    if q.shape[1] % n_dev or k.shape[1] % n_dev:
        raise ValueError(
            f"seq len {q.shape[1]}/{k.shape[1]} not divisible by mesh "
            f"axis {axis!r} size {n_dev}")
    return _ring_attention_sharded(q, k, v, k_mask, mesh=mesh, axis=axis,
                                   causal=causal)
