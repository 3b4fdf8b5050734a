"""Layer "kernels": the held experts' grouped products' share of their
roofline, in percent: the least time the chip could take for
``moe_pairs_here`` × 3 products of 2048 × 1536, forward and backward,
and the experts' bytes (``roofline_seq.needs``) over
``moe_experts_ms``'s time."""

import seq_layers


def read(obs):
    return seq_layers.roofline_pct(obs, "moe_experts", "experts")
