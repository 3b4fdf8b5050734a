"""Layer "kernels": the held experts' grouped products' share of their
roofline, in percent: the least time the chip could take for
``moe_pairs_here`` × 3 products of hidden × expert width, forward and
backward, and the experts' bytes (the ``experts`` part of the cell's
``roofline_*.needs``: bound by operations, or by the weights' bytes
where an expert sees few pairs) over ``moe_experts_ms``'s time — ALL the
seconds the experts cost, the ``ragged-dot`` kernels and what runs
between them (``seq_layers.seconds``). Before PR 49 the divisor left the
kernels out and the share read two to three times too high."""

import seq_layers


def read(obs):
    return seq_layers.roofline_pct(obs, "moe_experts", "experts")
