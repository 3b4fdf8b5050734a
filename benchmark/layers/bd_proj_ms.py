"""Layer "kernels": device milliseconds of ONE traced train under the
scope ``seqrec.bd`` outside its attention product: a layer's norm,
projections, per-head QK norm, RoPE and W_o over BOTH streams' rows
(``scope_reduce``: the operations' ``tf_op`` paths), forward,
recomputation and backward. Absent where the trace names no such
scope."""

import roofline_sdar
import scope_layers


def read(obs):
    return scope_layers.milliseconds(obs, *roofline_sdar.SCOPES["bd_proj"])
