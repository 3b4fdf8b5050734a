"""Layer "kernels": device milliseconds of ONE traced train under the
scope ``seqrec.conv`` outside its gates and taps: the operator's norm, W_in
and W_out (``scope_reduce``: the operations' ``tf_op`` paths), forward,
recomputation and backward. Absent where the trace names no such scope."""

import roofline_lfm2


def read(obs):
    secs = roofline_lfm2.seconds(obs, "shortconv_proj")
    return None if secs is None else secs * 1e3
