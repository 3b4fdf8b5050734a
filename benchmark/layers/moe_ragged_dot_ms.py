"""Layer "kernels": device milliseconds of ONE traced train in XLA's
``ragged-dot`` kernels (``moe_dispatch.grouped_matmul``: the experts'
three products, forward, recomputation and both backward products) and
the group metadata made for them, found by the operations' OWN names:
the compiler gives them the ``tf_op`` path ``ragged-dot-none``, so they
lie under no ``seqrec.*`` scope — not under ``seqrec.moe.experts``, which
holds what runs between them. Absent where the trace has no such
operation."""

import scope_layers


def read(obs):
    return scope_layers.ragged_dot_seconds(obs) * 1e3 or None
