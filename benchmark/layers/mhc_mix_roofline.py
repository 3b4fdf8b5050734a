"""Layer "residual mixer": the mixes' share of their roofline, in percent:
the least time the chip could take to read the n-copy float32 stream once
for u, and to read and write it once for the write-back, around every
sublayer of every real token, forward and backward
(``roofline_xing4.needs``: ``mhc_mix``, bound by bytes — 1.118 MB a token
and layer) over ``mhc_mix_ms``'s time. What a fusion reads twice, or a
recomputation reads again, lowers it."""

import roofline_xing4


def read(obs):
    return roofline_xing4.roofline_pct(obs, "mhc_mix", "mhc_mix")
