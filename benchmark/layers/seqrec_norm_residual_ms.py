"""Layer "kernels": device milliseconds of ONE traced train under the
scopes ``seqrec.norm`` and ``seqrec.residual`` — the RMS norm between a
layer's operator and its FFN or experts (with the experts' copy of the
normed rows in the matmul dtype), and the expert branch's residual add
(``scope_reduce``: the operations' ``tf_op`` paths), forward,
recomputation and backward. Absent where the trace names neither
scope."""

import scope_layers


def read(obs):
    return scope_layers.milliseconds(obs, "seqrec.norm", "seqrec.residual")
