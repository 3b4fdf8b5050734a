"""Layer "kernels": device milliseconds of ONE traced train under the
scope ``seqrec.gdn`` outside its convolution and its scan: the linear
layers' norm, W_qkvz, W_ba, the norms of q and k, β, g, the gated output
norm and W_out (``scope_reduce``: the operations' ``tf_op`` paths),
forward, recomputation and backward. Absent where the trace names no
such scope."""

import roofline_qwen3next


def read(obs):
    secs = roofline_qwen3next.seconds(obs, "gdn_proj")
    return None if secs is None else secs * 1e3
