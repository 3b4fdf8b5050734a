"""Ulysses-style sequence parallelism: all_to_all resharding.

The alternative to ring attention for long sequences (DeepSpeed-Ulysses
pattern, public): activations arrive sharded on the **sequence** axis;
one ``all_to_all`` reshards them to be sharded on the **heads** axis
with the full sequence local, standard attention runs per head group,
and a second ``all_to_all`` restores sequence sharding. Two collectives
per attention call, both riding ICI; requires ``heads %% n_dev == 0``.

Ring attention (``.ring_attention``) scales sequence length with device
count at O(block²) memory; Ulysses keeps full-sequence attention local
(better for short-ish sequences with many heads). Both are exact.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from predictionio_tpu.parallel.ring_attention import attention_reference


@functools.partial(jax.jit, static_argnames=("axis", "causal", "mesh"))
def _ulysses_sharded(q, k, v, k_mask, *, mesh, axis: str, causal: bool):
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n_dev = mesh.shape[axis]

    def local(q_l, k_l, v_l, mask_l):
        # [B, S/n, H, D] → all_to_all → [B, S, H/n, D]
        def seq_to_heads(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        def heads_to_seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        mask = jax.lax.all_gather(mask_l, axis, axis=1, tiled=True)
        o = attention_reference(seq_to_heads(q_l), seq_to_heads(k_l),
                                seq_to_heads(v_l), causal=causal,
                                k_mask=mask)
        return heads_to_seq(o)

    spec = P(None, axis, None, None)
    mspec = P(None, axis)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(spec, spec, spec, mspec), out_specs=spec)
    if k_mask is None:
        k_mask = jnp.ones(k.shape[:2], bool)
    return fn(q, k, v, k_mask)


def ulysses_attention(q, k, v, mesh=None, axis: str = "data",
                      causal: bool = False, k_mask=None):
    """Sequence-parallel attention via head-resharding.

    q, k, v: [B, S, H, D]; S and H must both divide by the mesh axis
    size; ``k_mask``: optional [B, Sk] bool key-padding mask.
    ``mesh=None`` (or a 1-device axis) falls back to the oracle.
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
    if q.shape[1] % n_dev or q.shape[2] % n_dev:
        raise ValueError(
            f"seq {q.shape[1]} and heads {q.shape[2]} must divide by mesh "
            f"axis {axis!r} size {n_dev}")
    return _ulysses_sharded(q, k, v, k_mask, mesh=mesh, axis=axis,
                            causal=causal)
