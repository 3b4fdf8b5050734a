"""Layer "kernels": device milliseconds of ONE traced train under the
scope ``seqrec.conv.mix``: the short convolution's two gate products and
its taps (``scope_reduce``: the operations' ``tf_op`` paths), forward,
recomputation and backward. Absent where the trace names no such scope."""

import roofline_lfm2


def read(obs):
    secs = roofline_lfm2.seconds(obs, "shortconv")
    return None if secs is None else secs * 1e3
