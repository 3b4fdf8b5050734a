"""Layer "kernels": device milliseconds of ONE traced train under the scope ``seqrec.mla.attention``: scores, softmax and values
(``scope_reduce``: the operations' ``tf_op`` paths), forward, recomputation
and backward. Absent where the trace names no such scope."""

import seq_layers


def read(obs):
    secs = seq_layers.seconds(obs, "mla_attention")
    return None if secs is None else secs * 1e3
