"""``ops/moe_dispatch.experts_swiglu`` against a dense all-experts
reference, values and every gradient, where the held experts' rows end
anywhere in the sorted pair buffer — and that the passes over the
buffer stop where those rows end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import moe_dispatch

T, K, E, D, F = 64, 2, 8, 16, 8     # a pair buffer of 128 rows
BLOCK = 32                          # … in four blocks


def poisoned(real):
    """``grouped_matmul`` as the chip runs it: rows behind the last
    group are never read and hold NaN afterwards — in the product and
    in ``lhs``'s cotangent alike."""
    def behind(a, sizes):
        return (jnp.arange(a.shape[0]) >= sizes.sum())[:, None]

    @jax.custom_vjp
    def product(lhs, rhs, sizes):
        return jnp.where(behind(lhs, sizes), jnp.nan,
                         real(jnp.where(behind(lhs, sizes), 0, lhs), rhs,
                              sizes))

    def fwd(lhs, rhs, sizes):
        return product(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        clean = lambda a: jnp.where(behind(a, sizes), 0, a)  # noqa: E731
        d_lhs, d_rhs = jax.vjp(lambda a, b: real(a, b, sizes), clean(lhs),
                               rhs)[1](clean(g))
        return jnp.where(behind(lhs, sizes), jnp.nan, d_lhs), d_rhs, None

    product.defvjp(fwd, bwd)
    return product


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _prefix_ids(rows):
    """ids [T, K] whose first ``rows`` pairs (token by token) go to the
    held experts 2 and 5 and the others to the absent 0 and 1."""
    i = np.arange(T * K)
    return jnp.asarray(np.where(i < rows, np.where(i % 2 == 0, 2, 5),
                                i % 2).reshape(T, K), jnp.int32)


def _random_ids(seed=1):
    scores = jax.random.uniform(jax.random.PRNGKey(seed), (T, E))
    return jax.lax.top_k(scores, K)[1].astype(jnp.int32)


#: name → (ids, held, rows of a block, valid tokens or None, rows the
#: groups cover, blocks run of blocks, junk behind the groups)
CASES = {
    "no_pair_held": (_prefix_ids(0), (2, 5), BLOCK, None, 0, (0, 4), False),
    "on_a_blocks_end": (_prefix_ids(64), (2, 5), BLOCK, None, 64, (2, 4),
                        False),
    "one_row_past_it": (_prefix_ids(65), (2, 5), BLOCK, None, 65, (3, 4),
                        False),
    "every_expert_held": (_random_ids(), tuple(range(E)), BLOCK, None, 128,
                          (4, 4), False),
    "no_multiple_of_the_block": (_random_ids(), (1, 4, 6), 48, None, None,
                                 (1, 1), False),
    "padding_tokens": (_prefix_ids(100), (2, 5), BLOCK, 40, 80, (3, 4),
                       False),
    "junk_behind_the_groups": (_prefix_ids(41), (2, 5), BLOCK, None, 41,
                               (2, 4), True),
    "junk_and_one_block": (_random_ids(2), (0, 3), 256, None, None, (1, 1),
                           True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_experts_equal_the_dense_sum_wherever_the_rows_end(case, monkeypatch):
    ids, held, block, n_valid, rows, blocks, junk = CASES[case]
    monkeypatch.setattr(moe_dispatch, "BLOCK_ROWS", block)
    if junk:
        monkeypatch.setattr(moe_dispatch, "grouped_matmul",
                            poisoned(moe_dispatch.grouped_matmul))
    valid = None if n_valid is None else jnp.arange(T) < n_valid
    key = jax.random.split(jax.random.PRNGKey(7), 6)
    H = len(held)
    x = jax.random.normal(key[0], (T, D))
    gates = jax.random.uniform(key[1], (T, K), minval=0.2)
    wg = 0.3 * jax.random.normal(key[2], (H, D, F))
    wu = 0.3 * jax.random.normal(key[3], (H, D, F))
    wd = 0.3 * jax.random.normal(key[4], (H, F, D))
    cot = jax.random.normal(key[5], (T, D))

    def sparse(x, wg, wu, wd, gates):
        p = moe_dispatch.plan(ids, held, E, valid)
        return moe_dispatch.experts_swiglu(x, wg, wu, wd, gates, p), p

    def dense(x, wg, wu, wd, gates):
        out = 0.0
        for j, e in enumerate(held):
            gate = jnp.where(ids == e, gates, 0.0).sum(1)
            if valid is not None:
                gate = jnp.where(valid, gate, 0.0)
            out = out + gate[:, None] * (
                (jax.nn.silu(x @ wg[j]) * (x @ wu[j])) @ wd[j])
        return out + jnp.zeros_like(x), None

    def both(fn):
        def against(*a):
            out, p = fn(*a)
            return (out * cot).sum(), (out, p)

        def run(*a):
            (_, (out, p)), grads = jax.value_and_grad(
                against, (0, 1, 2, 3, 4), has_aux=True)(*a)
            return out, grads, p
        return jax.jit(run)

    with jax.default_matmul_precision("highest"):
        out, got, p = both(sparse)(x, wg, wu, wd, gates)
        ref_out, want, _ = both(dense)(x, wg, wu, wd, gates)
    if rows is not None:
        assert int(p.rows) == rows == int(p.pairs_here)
    assert (int(moe_dispatch.blocks_run(p)),
            moe_dispatch.row_blocks(T * K)[0]) == blocks
    for g, w in zip((out, *got), (ref_out, *want)):
        assert bool(jnp.isfinite(g).all())
        if float(jnp.abs(w).max()) == 0.0:
            assert float(jnp.abs(g).max()) == 0.0
        else:
            assert _rel(g, w) < 1e-5


def _while_bodies(jaxpr):
    """(jaxprs outside every ``while``, bodies of the ``while`` loops)
    of a closed jaxpr, sub-jaxprs followed."""
    outside, bodies = [], []

    def walk(j, inside):
        (bodies if inside else outside).append(j)
        for eqn in j.eqns:
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, inside or eqn.primitive.name == "while")

    walk(jaxpr.jaxpr, False)
    return outside, bodies


def _count(jaxprs, name):
    return sum(eqn.primitive.name == name for j in jaxprs for eqn in j.eqns)


def test_the_grouped_products_sit_inside_the_bounded_loop(monkeypatch):
    """Forward and backward pass hold their grouped products INSIDE a
    loop whose trip count is computed from the plan's ``rows`` — none
    outside it, where it would walk the whole buffer — and the loop
    runs ``blocks_run`` times: counted where the products run."""
    monkeypatch.setattr(moe_dispatch, "BLOCK_ROWS", BLOCK)
    runs = []
    real = moe_dispatch.grouped_matmul

    def counted(lhs, rhs, sizes):
        jax.debug.callback(lambda: runs.append(1))
        return real(lhs, rhs, sizes)

    held = (2, 5)
    key = jax.random.split(jax.random.PRNGKey(8), 5)
    x = jax.random.normal(key[0], (T, D))
    gates = jax.random.uniform(key[1], (T, K))
    w = [0.3 * jax.random.normal(k, s) for k, s in zip(
        key[2:], [(2, D, F), (2, D, F), (2, F, D)])]

    def loss(x, gates, wg, wu, wd, ids):
        p = moe_dispatch.plan(ids, held, E)
        return (moe_dispatch.experts_swiglu(x, wg, wu, wd, gates, p)
                ** 2).sum()

    grad = jax.grad(loss, (0, 1, 2, 3, 4))
    for fn, products in ((loss, 3), (grad, 3 + 3 + 6)):
        outside, bodies = _while_bodies(
            jax.make_jaxpr(fn)(x, gates, *w, _prefix_ids(65)))
        assert _count(outside, "ragged_dot_general") == 0
        assert _count(bodies, "ragged_dot_general") == products
    monkeypatch.setattr(moe_dispatch, "grouped_matmul", counted)
    run = jax.jit(lambda *a: loss(*a))  # ``loss`` itself is traced above
    for rows, blocks in ((0, 0), (64, 2), (65, 3), (128, 4)):
        del runs[:]
        jax.block_until_ready(run(x, gates, *w, _prefix_ids(rows)))
        jax.effects_barrier()
        assert len(runs) == 3 * blocks
