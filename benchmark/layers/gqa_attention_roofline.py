"""Layer "kernels": the grouped-query attention product's share of its
roofline, in percent: the least time the chip could take for the causal,
segment-masked scores and values of one train, forward and backward
(``roofline_lfm2.needs``: the (query, key) pairs the packing's segments
allow × 32 heads × 128, bound by operations) over ``gqa_attention_ms``'s
time."""

import roofline_lfm2


def read(obs):
    return roofline_lfm2.roofline_pct(obs, "gqa_attention", "attention")
