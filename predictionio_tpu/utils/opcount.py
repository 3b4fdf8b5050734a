"""Chip-free device-dispatch counting for compiled programs.

The r5 VERDICT measured the warm ML-20M ALS train latency-bound at
~8.8k device ops per iteration (1.0% MFU, HBM at 49 of 819 GB/s) — the
cost was DISPATCH COUNT, not FLOPs. This module makes that number a
first-class, hardware-free metric: trace a program to its jaxpr
(``jax.make_jaxpr`` over ``ShapeDtypeStruct``s — no device buffers, no
backend execution) and count the primitive applications the device
would run, expanding control flow the way XLA does:

- ``scan``/``while`` body ops multiply by the trip count (a scan of
  100 slabs IS 100× its body's dispatches on device);
- ``pjit``/``closed_call``/``custom_*_call``/``remat`` recurse into
  their sub-jaxprs (inlined at compile time);
- ``cond`` takes the max over branches (one branch runs);
- a ``pallas_call`` is ONE op — that asymmetry is the whole point of
  the fused gather→Gram work.

The count is an upper-bound proxy (XLA fusion merges some elementwise
neighbors), but it is stable, cheap, and moves in lockstep with the
dispatch wall: `bench.py` emits it next to ``mfu_device`` and
`tests/test_als.py::TestFusedGram::test_dispatch_collapse_ratio` guards
the ≥10× collapse without hardware.
"""

from __future__ import annotations

# primitives that recurse into exactly one inner jaxpr
_CALL_PRIMS = ("pjit", "closed_call", "core_call", "xla_call", "remat",
               "remat2", "checkpoint", "custom_jvp_call",
               "custom_vjp_call", "custom_vjp_call_jaxpr",
               "shard_map", "jit")


def _inner_jaxprs(eqn):
    """Every ClosedJaxpr/Jaxpr hiding in an eqn's params."""
    import jax.extend.core as jcore

    out = []
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else [v]):
            if isinstance(j, jcore.ClosedJaxpr):
                out.append(j.jaxpr)
            elif isinstance(j, jcore.Jaxpr):
                out.append(j)
    return out


def count_jaxpr_ops(jaxpr) -> int:
    """Device-op estimate for a (Closed)Jaxpr — see module docstring."""
    import jax.extend.core as jcore

    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        inner = _inner_jaxprs(eqn)
        if name == "scan":
            body = count_jaxpr_ops(eqn.params["jaxpr"])
            total += body * int(eqn.params.get("length", 1))
        elif name == "while":
            # ≥1 trip: body + cond once (trip count is data-dependent;
            # ALS programs use scan for anything with known length)
            total += sum(count_jaxpr_ops(j) for j in inner)
        elif name == "cond":
            branches = eqn.params.get("branches", ())
            total += max((count_jaxpr_ops(b) for b in branches),
                         default=0)
        elif name in _CALL_PRIMS and inner:
            total += sum(count_jaxpr_ops(j) for j in inner)
        else:
            # pallas_call lands here: ONE device dispatch, params'
            # kernel jaxpr intentionally NOT recursed
            total += 1
    return total


def count_fn_ops(fn, *avals) -> int:
    """Trace ``fn`` over ShapeDtypeStructs and count device ops."""
    import jax

    return count_jaxpr_ops(jax.make_jaxpr(fn)(*avals))


def _struct_tree(tree):
    """numpy/array pytree → matching ShapeDtypeStruct pytree."""
    import jax
    import numpy as np

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
        if not isinstance(a, jax.ShapeDtypeStruct) else a, tree)


def _host_side_bufs(side):
    """``side.arrays()``: the structure ``ALSPrepared.device_buffers``
    uploads, as HOST numpy arrays (nothing touches a device). Kept
    under this name for ``benchmark/compile_check.py`` (ROADMAP D9)."""
    return side.arrays()


def als_iteration_ops(prep, params, gram_mode: str = "off") -> int:
    """Device ops for ONE ALS iteration (two half-steps) at ``prep``'s
    geometry under ``gram_mode`` — traced abstractly, so ``"pallas"``
    counts what the CHIP would dispatch, even from a chip-free host
    (the solve is the Pallas kernel too: ``als._solve_mode``).
    """
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models import als as als_mod

    p = params
    half = als_mod._make_half(
        p.rank, bool(p.implicit), bool(p.weighted_reg),
        bf16_gather=bool(p.bf16_gather),
        precision=als_mod._gram_precision(), gram_mode=gram_mode)
    geom_u, geom_i = prep.u_side.geometry, prep.i_side.geometry

    def step(u_bufs, i_bufs, U, V, reg, alpha):
        U = half(V, u_bufs, geom_u, reg, alpha)
        V = half(U, i_bufs, geom_i, reg, alpha)
        return U, V

    u_bufs = _struct_tree(_host_side_bufs(prep.u_side))
    i_bufs = _struct_tree(_host_side_bufs(prep.i_side))
    U = jax.ShapeDtypeStruct((prep.n_users, p.rank), jnp.float32)
    V = jax.ShapeDtypeStruct((prep.n_items, p.rank), jnp.float32)
    s = jax.ShapeDtypeStruct((), jnp.float32)

    return count_fn_ops(step, u_bufs, i_bufs, U, V, s, s)


def als_dispatch_report(prep, params) -> dict:
    """Baseline-vs-fused dispatch counts for one ALS iteration:
    ``{"xla": n, "fused": n, "ratio": xla/fused}`` — the chip-free
    evidence for the dispatch-collapse claim (ISSUE 17 acceptance)."""
    xla = als_iteration_ops(prep, params, "off")
    fused = als_iteration_ops(prep, params, "pallas")
    return {"device_ops_per_iter_xla": xla,
            "device_ops_per_iter": fused,
            "dispatch_collapse_ratio": xla / max(1, fused)}
