"""How many times a traced program calls each Pallas kernel."""

import collections

import jax


def kernel_calls(jaxpr) -> collections.Counter:
    """Kernel name → its ``pallas_call`` equations in ``jaxpr``, those
    inside its equations' own jaxprs too (a scan's body, a checkpoint's
    recomputation, BOTH branches of ``jax.lax.platform_dependent``: a
    call site of ``ops/seq_attention`` counts twice)."""
    calls = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls[eqn.params["name"]] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            calls += kernel_calls(sub)
    return calls
