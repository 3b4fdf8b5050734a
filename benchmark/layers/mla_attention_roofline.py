"""Layer "kernels": the attention product's share of its roofline, in
percent: the least time the chip could take for the causal,
segment-masked scores and values of one train, forward and backward
(``roofline_seq.needs``: the (query, key) pairs the packing's segments
allow, against ``peaks.json``) over ``mla_attention_ms``'s time."""

import seq_layers


def read(obs):
    return seq_layers.roofline_pct(obs, "mla_attention", "attention")
