"""Layer "kernels": device milliseconds of ONE traced train under the
scope ``seqrec.gdn.conv``: the linear layers' 4-tap causal convolution
over q, k and v with its segment mask and SiLU (``scope_reduce``: the
operations' ``tf_op`` paths), forward, recomputation and backward.
Absent where the trace names no such scope."""

import roofline_qwen3next


def read(obs):
    secs = roofline_qwen3next.seconds(obs, "gdn_conv")
    return None if secs is None else secs * 1e3
