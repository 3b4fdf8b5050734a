"""Layer "kernels": the short convolution's gates and taps' share of
their roofline, in percent: the least time the chip could take to move
B, C, u, y and their cotangents once (``roofline_lfm2.needs``: bound by
bytes, as matmul operands; recomputation not counted) over
``shortconv_ms``'s time."""

import roofline_lfm2


def read(obs):
    return roofline_lfm2.roofline_pct(obs, "shortconv", "shortconv")
