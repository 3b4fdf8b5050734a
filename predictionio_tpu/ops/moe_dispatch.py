"""Sparse-expert dispatch for ONE chip's share of an expert layer.

The layer is told which experts it holds (``held``: their global ids),
routes every token over ALL the router's experts, and computes the
part of the result that its own experts give. Nothing stands in for
the absent experts or their exchange.

    route      sigmoid scores + selection-only bias → top-k ids, gates
               (route_softmax: logits → top-k ids, a softmax over them)
    plan       (token, expert) pairs sorted by held expert; pairs of
               absent experts (and of padding tokens) sort behind them
    experts    gather → grouped gated unit (SiLU | ReLU | …) over the
               experts held → combine

No capacity and no dropped pair: the pair buffer holds every pair the
router can produce (tokens × k rows), and the grouped product visits
only the rows its groups cover, so the buffer's size costs memory
traffic, not FLOPs.

The grouped product is ``lax.ragged_dot``: XLA's TPU backend runs it
(and both of its gradients) as its own tiled grouped-matmul kernel
over the rows the groups cover — ``ragged-dot`` in a trace — and the
CPU backend has a plain lowering for the tests.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

#: blocks the sorted pair rows are worked through in
ROW_BLOCKS = 4


def route(scores, bias, k: int, scaling: float, normalize: bool = True):
    """``scores`` [T, E] float32 sigmoid affinities; ``bias`` [E] enters
    the SELECTION only (and takes no gradient: it is not differentiated
    and ``top_k``'s indices carry none). Returns ids [T, k] int32 and
    gates [T, k] float32 = scaling · s_e / Σ_selected s."""
    import jax
    import jax.numpy as jnp

    _, ids = jax.lax.top_k(scores + jax.lax.stop_gradient(bias)[None, :], k)
    picked = jnp.take_along_axis(scores, ids, axis=1)
    if normalize:
        picked = picked / (picked.sum(axis=1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), picked * scaling


def route_softmax(logits, k: int):
    """``logits`` [T, E] float32: the top-k experts of each token and
    gates = softmax over the SELECTED logits (they sum to 1; the
    unselected take no gradient). No bias, no scaling. Returns ids
    [T, k] int32 and gates [T, k] float32."""
    import jax
    import jax.numpy as jnp

    picked, ids = jax.lax.top_k(logits, k)
    return ids.astype(jnp.int32), jax.nn.softmax(picked, axis=-1)


class Plan(NamedTuple):
    order: object         # [M] int32: sorted row r holds pair order[r]
    inverse: object       # [M] int32: where pair (t, j) went, t*k+j → row
    group_sizes: object   # [H] int32: rows of each held expert, in order
    here: object          # [T, k] bool: the pair's expert is held here
    pairs_here: object    # () int32
    rows: object          # () int32: rows the groups cover (= pairs_here)


def plan(ids, held: Sequence[int], n_experts: int, valid=None) -> Plan:
    """Sort the (token, expert) pairs by held expert. ``valid`` [T]
    bool masks padding tokens out of every expert."""
    import jax.numpy as jnp
    import numpy as np

    T, k = ids.shape
    H = len(held)
    slot = np.full(n_experts, H, np.int32)
    slot[np.asarray(held, np.int64)] = np.arange(H, dtype=np.int32)
    local = jnp.asarray(slot)[ids]                      # [T, k] in 0..H
    if valid is not None:
        local = jnp.where(valid[:, None], local, H)
    # A stable COUNTING sort: the keys are 0..H, so a pair's place is
    # its key's start plus its rank among the pairs of that key (a
    # running count) — a comparison sort of 65,536 keys costs the
    # chip's compiler 17 s each time it appears in a program.
    flat = local.reshape(-1)
    onehot = (flat[:, None] == jnp.arange(H + 1, dtype=jnp.int32)[None, :]
              ).astype(jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot,
                               flat[:, None], axis=1)[:, 0]
    counts = onehot.sum(axis=0)
    inverse = ((jnp.cumsum(counts) - counts)[flat] + rank).astype(jnp.int32)
    order = jnp.zeros_like(inverse).at[inverse].set(
        jnp.arange(T * k, dtype=jnp.int32))
    sizes = counts[:H]
    here = local < H
    return Plan(order, inverse, sizes, here,
                here.sum().astype(jnp.int32), sizes.sum())


def _take_rows(x, index, back, keep):
    """``x[index]`` whose backward is a GATHER too. The sort is a
    permutation of the pairs, so row i of ``x`` went to the rows
    ``back[i]`` ([n, fan]) and its cotangent is their sum — no
    scatter-add over 65,536 rows. ``keep`` [n, fan] bool zeroes the
    cotangent of pairs whose rows lie behind the groups (there it is
    whatever the buffer held)."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def take(x, index, back, keep):
        return x[index]

    def fwd(x, index, back, keep):
        return x[index], (back, keep)

    def bwd(res, g):
        back, keep = res
        picked = jnp.where(keep[..., None], g[back], jnp.zeros((), g.dtype))
        return (picked.sum(axis=1, dtype=jnp.float32).astype(g.dtype),
                None, None, None)

    take.defvjp(fwd, bwd)
    return take(x, index, back, keep)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` [M, K] rows sorted by group, ``rhs`` [G, K, N]: row r of
    group g gives ``lhs[r] @ rhs[g]``, accumulated in float32, in
    ``lhs``'s dtype. Rows behind the last group are NOT defined."""
    import jax

    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)


def experts_swiglu(x, wg, wu, wd, gates, p: Plan, act=None):
    """The held experts' part of the layer's result: ``x`` [T, d]
    (matmul dtype), ``wg``/``wu`` [H, d, f], ``wd`` [H, f, d], ``gates``
    [T, k] float32. Returns [T, d] float32 — Σ over the token's pairs
    held here of gate · W_d^e(act(W_g^e x) ⊙ W_u^e x); ``act`` (float32
    → float32) is the caller's block's: SiLU where none is given."""
    import jax
    import jax.numpy as jnp

    act = act or jax.nn.silu

    T, k = gates.shape
    # Rows behind the groups are never defined — in the products and in
    # their cotangents alike: the two ends select them away (``here``,
    # ``covered``) inside passes that are made anyway, so that no masked
    # copy of a 65,536-row buffer is ever made.
    with jax.named_scope("seqrec.moe.dispatch"):
        rows = _take_rows(x, p.order // k, p.inverse.reshape(T, k), p.here)
    with jax.named_scope("seqrec.moe.experts"):
        # The sorted rows in ROW_BLOCKS blocks: the held experts' pairs
        # come first, so on average the first block holds them all (a
        # whole group per product) and the others find empty groups —
        # but the wide intermediates are a block's, not the buffer's.
        nb = ROW_BLOCKS if (T * k) % ROW_BLOCKS == 0 else 1
        mb = T * k // nb
        ends = jnp.cumsum(p.group_sizes)
        lo = (jnp.arange(nb, dtype=jnp.int32) * mb)[:, None]
        sizes = (jnp.clip(ends[None, :], lo, lo + mb)
                 - jnp.clip((ends - p.group_sizes)[None, :], lo, lo + mb))

        @jax.checkpoint
        def block(args):
            rows, sizes = args
            g = grouped_matmul(rows, wg, sizes)
            u = grouped_matmul(rows, wu, sizes)
            h = (act(g.astype(jnp.float32))
                 * u.astype(jnp.float32)).astype(rows.dtype)
            return grouped_matmul(h, wd, sizes)

        y = jax.lax.map(block, (rows.reshape(nb, mb, -1), sizes)).reshape(
            T * k, -1)
    with jax.named_scope("seqrec.moe.combine"):
        # the weighted sum is a product like the others: operands in
        # the matmul dtype, float32 accumulation
        # rows behind the groups hold whatever the buffer held, NaN
        # included: zeroed here, in the same pass as the weighting, or
        # the gates' cotangent (0 · NaN) would carry it into the router
        covered = (jnp.arange(T * k, dtype=jnp.int32) < p.rows)[:, None]
        w = jnp.take(gates.reshape(-1), p.order)[:, None]
        y = (jnp.where(covered, y, jnp.zeros((), y.dtype)).astype(
            jnp.float32) * w).astype(x.dtype)
        back = _take_rows(y, p.inverse, p.order[:, None],
                          jnp.ones((T * k, 1), bool))
        return jnp.where(p.here[..., None], back.reshape(T, k, -1),
                         jnp.zeros((), back.dtype)).sum(
                             axis=1, dtype=jnp.float32)
