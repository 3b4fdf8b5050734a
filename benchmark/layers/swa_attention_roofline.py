"""Layer "kernels": the window layers' attention product's share of its
roofline, in percent: the least time the chip could take for the scores
and values of the pairs a window of 4,096 keys LEAVES inside the
packing's segments, forward and backward, over all window layers
(``roofline_smallthinker.needs``: ``attn_pairs_window`` × 28 heads × 128,
bound by operations) over ``swa_attention_ms``'s time."""

import roofline_smallthinker


def read(obs):
    return roofline_smallthinker.roofline_pct(obs, "swa_attention",
                                              "window_attention")
