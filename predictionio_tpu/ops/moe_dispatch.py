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
router can produce (tokens × k rows). The held experts' pairs are a
PREFIX of it (``Plan.rows`` rows), and every pass that is indexed by
sorted row — the gather of the tokens' rows, the gated units, the
weighting by the gates, and the backward pass of each — walks the
buffer in blocks of ``BLOCK_ROWS`` rows and stops at the block that
holds row ``rows − 1``: those passes cost by the pairs held here
(rounded up to a block), not by the buffer. What the buffer's size
still costs is the TOKEN side: a zero-fill of the buffer and one
gather of tokens × k rows from it, in each direction.

The grouped product is ``lax.ragged_dot``: XLA's TPU backend runs it
(and both of its gradients) as its own tiled grouped-matmul kernel
over the rows the groups cover — ``ragged-dot`` in a trace — and the
CPU backend has a plain lowering for the tests.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

#: Rows of the sorted pair buffer a block holds. A block that is run
#: reads the held experts' weights once more and adds its part to their
#: gradients' float32 sums (0.5 … 1 GB moved a block at the published
#: widths); a block's last rows are wasted, half of them on average.
#: One layer on the chip, forward and backward (PERF.md §6, PR 51):
#: 8,192 beats 4,096 by 4 … 11 % at three cells' shapes and loses 5 %
#: at the smallest buffer; 16,384 is no better anywhere.
BLOCK_ROWS = 8192


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


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` [M, K] rows sorted by group, ``rhs`` [G, K, N]: row r of
    group g gives ``lhs[r] @ rhs[g]``, accumulated in float32, in
    ``lhs``'s dtype. Rows behind the last group are NOT defined."""
    import jax

    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)


def row_blocks(m: int) -> Tuple[int, int]:
    """(blocks, rows a block) a sorted buffer of ``m`` rows is walked
    in: blocks of ``BLOCK_ROWS``, and ONE block where ``m`` is no
    multiple of it (serving's few tokens, the tests' toys)."""
    return (m // BLOCK_ROWS, BLOCK_ROWS) if m % BLOCK_ROWS == 0 else (1, m)


def blocks_run(p: Plan):
    """() int32: the blocks that hold a row of a held expert — what
    :func:`experts_swiglu` runs of ``row_blocks(len(p.order))[0]``."""
    mb = row_blocks(p.order.shape[0])[1]
    return (p.rows + mb - 1) // mb


def experts_swiglu(x, wg, wu, wd, gates, p: Plan, act=None):
    """The held experts' part of the layer's result: ``x`` [T, d]
    (matmul dtype), ``wg``/``wu`` [H, d, f], ``wd`` [H, f, d], ``gates``
    [T, k] float32. Returns [T, d] float32 — Σ over the token's pairs
    held here of gate · W_d^e(act(W_g^e x) ⊙ W_u^e x); ``act`` (float32
    → float32) is the caller's block's: SiLU where none is given.

    Forward and backward are each ONE loop over the first
    ``blocks_run(p)`` blocks of the sorted buffer and one token-side
    gather; the backward pass recomputes a block's gated units, so
    nothing wider than a block is kept, and every index is a GATHER's
    (the sort is a permutation: no scatter-add over the buffer)."""
    import jax
    import jax.numpy as jnp

    act = act or jax.nn.silu
    T, k = gates.shape
    mb = row_blocks(T * k)[1]

    def units(rows, wg, wu, wd, sizes):
        g = grouped_matmul(rows, wg, sizes)
        u = grouped_matmul(rows, wu, sizes)
        h = (act(g.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(rows.dtype)
        return grouped_matmul(h, wd, sizes)

    def block(b, x, gates, p):
        """Block ``b``'s pairs: their tokens, the held experts' rows
        inside it, which rows a group covers (rows behind the groups
        are never defined, in a product and in its cotangent alike),
        the tokens' rows of ``x`` and the pairs' gates."""
        lo = b * mb
        pair = jax.lax.dynamic_slice(p.order, (lo,), (mb,))
        token = pair // k
        ends = jnp.cumsum(p.group_sizes)
        sizes = (jnp.clip(ends, lo, lo + mb)
                 - jnp.clip(ends - p.group_sizes, lo, lo + mb))
        covered = (lo + jnp.arange(mb, dtype=jnp.int32) < p.rows)[:, None]
        with jax.named_scope("seqrec.moe.dispatch"):
            rows = x[token]
        with jax.named_scope("seqrec.moe.combine"):
            w = gates.reshape(-1)[pair][:, None]
        return lo, token, sizes, covered, rows, w

    def per_token(buf, p):
        """Σ over a token's pairs held here of its row of the sorted
        ``buf``, float32 (a pair of an absent expert went behind the
        groups, where the buffer holds whatever it held)."""
        return jnp.where(p.here[..., None], buf[p.inverse].reshape(T, k, -1),
                         jnp.zeros((), buf.dtype)).sum(axis=1,
                                                       dtype=jnp.float32)

    def forward(x, wg, wu, wd, gates, p):
        def body(b, y):
            lo, _, sizes, covered, rows, w = block(b, x, gates, p)
            with jax.named_scope("seqrec.moe.experts"):
                out = units(rows, wg, wu, wd, sizes)
            with jax.named_scope("seqrec.moe.combine"):
                # the weighted sum is a product like the others:
                # operands in the matmul dtype, float32 accumulation
                out = (jnp.where(covered, out, jnp.zeros((), out.dtype))
                       .astype(jnp.float32) * w).astype(x.dtype)
                return jax.lax.dynamic_update_slice(y, out, (lo, 0))

        with jax.named_scope("seqrec.moe.combine"):
            y = jnp.zeros((T * k, wd.shape[-1]), x.dtype)
        y = jax.lax.fori_loop(0, blocks_run(p), body, y)
        with jax.named_scope("seqrec.moe.combine"):
            return per_token(y, p)

    def backward(res, g):
        x, wg, wu, wd, gates, p = res
        g = g.astype(x.dtype)

        def body(b, carry):
            d_rows, d_w, d_weights = carry
            lo, token, sizes, covered, rows, w = block(b, x, gates, p)
            with jax.named_scope("seqrec.moe.experts"):
                out, pull = jax.vjp(
                    lambda *a: units(*a, sizes), rows, wg, wu, wd)
            with jax.named_scope("seqrec.moe.combine"):
                # junk behind the groups (NaN included) is zeroed in
                # the same pass as the weighting: 0 · NaN would carry it
                # into the router through the gates' cotangent
                out = jnp.where(covered, out, jnp.zeros((), out.dtype))
                g_out = g[token].astype(jnp.float32)
                d_w = jax.lax.dynamic_update_slice(
                    d_w, (g_out * out.astype(jnp.float32)).sum(axis=1),
                    (lo,))
                g_out = jnp.where(covered, (g_out * w).astype(out.dtype),
                                  jnp.zeros((), out.dtype))
            with jax.named_scope("seqrec.moe.experts"):
                part, *parts = pull(g_out)
                # a block's part of the weights' gradients is rounded
                # to the matmul dtype; the sum over blocks is float32
                d_weights = [a + b.astype(jnp.float32)
                             for a, b in zip(d_weights, parts)]
            with jax.named_scope("seqrec.moe.dispatch"):
                d_rows = jax.lax.dynamic_update_slice(d_rows, part, (lo, 0))
            return d_rows, d_w, d_weights

        with jax.named_scope("seqrec.moe.dispatch"):
            d_rows = jnp.zeros((T * k, x.shape[-1]), x.dtype)
        d_rows, d_w, d_weights = jax.lax.fori_loop(
            0, blocks_run(p), body,
            (d_rows, jnp.zeros(T * k, jnp.float32),
             [jnp.zeros(a.shape, jnp.float32) for a in (wg, wu, wd)]))
        with jax.named_scope("seqrec.moe.dispatch"):
            d_x = per_token(d_rows, p).astype(x.dtype)
        with jax.named_scope("seqrec.moe.combine"):
            d_gates = jnp.where(p.here, d_w[p.inverse].reshape(T, k), 0.0)
        return (d_x, *(a.astype(b.dtype) for a, b in zip(
            d_weights, (wg, wu, wd))), d_gates, None)

    run = jax.custom_vjp(forward)
    run.defvjp(lambda *a: (forward(*a), a), backward)
    return run(x, wg, wu, wd, gates, p)
