"""Layer "kernels": device milliseconds of ONE traced train that the held
experts cost, WHOLE: XLA's ``ragged-dot`` kernels (the three grouped
products of ``moe_dispatch.grouped_matmul`` and their group metadata,
found by the operations' own names — the compiler writes their path as
``ragged-dot-none``, under no scope; ``moe_ragged_dot_ms`` alone) plus
what runs BETWEEN them under the scope ``seqrec.moe.experts`` (the
activation, the gate's product, copies; ``scope_reduce``: the
operations' ``tf_op`` paths) — forward, recomputation and backward
(``seq_layers.seconds``). Before PR 49 it read the scope alone. Absent
where the trace names no such scope."""

import seq_layers


def read(obs):
    secs = seq_layers.seconds(obs, "moe_experts")
    return None if secs is None else secs * 1e3
