"""Layer "kernels": device milliseconds of ONE traced train under the
scope ``seqrec.stack.cast`` — ``seq_backbone._cast_in_loop``: a scanned
layer's matrices cast to the matmul dtype inside the loop, forward and
recomputation, and their cotangents cast back (``scope_reduce``: the
operations' ``tf_op`` paths). Absent where the trace names no such
scope."""

import scope_layers


def read(obs):
    return scope_layers.milliseconds(obs, "seqrec.stack.cast")
