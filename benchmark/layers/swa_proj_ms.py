"""Layer "kernels": device milliseconds of ONE traced train under the
scope ``seqrec.swa`` outside its attention product: a window layer's
norm, projections, RoPE and W_o (``scope_reduce``: the operations'
``tf_op`` paths), forward, recomputation and backward. Absent where the
trace names no such scope."""

import roofline_smallthinker
import scope_layers


def read(obs):
    return scope_layers.milliseconds(
        obs, *roofline_smallthinker.SCOPES["swa_proj"])
