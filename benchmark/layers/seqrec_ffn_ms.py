"""Layer "kernels": device milliseconds of ONE traced train under the scope ``seqrec.ffn``: the dense layer's SwiGLU and the shared experts
(``scope_reduce``: the operations' ``tf_op`` paths), forward, recomputation
and backward. Absent where the trace names no such scope."""

import seq_layers


def read(obs):
    secs = seq_layers.seconds(obs, "seqrec_ffn")
    return None if secs is None else secs * 1e3
