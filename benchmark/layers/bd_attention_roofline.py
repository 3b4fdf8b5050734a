"""Layer "kernels": block-rule attention's share of its roofline, in
percent: the least time the chip could take for the scores and values
of the pairs the four visibility lines LEAVE inside the packing's
segments, both streams, forward and backward, over all layers
(``roofline_sdar.needs``: ``attn_pairs_bd`` × layers × 32 heads × 2 ×
128, bound by operations) over ``bd_attention_ms``'s time."""

import roofline_sdar


def read(obs):
    return roofline_sdar.roofline_pct(obs, "bd_attention", "bd_attention")
