"""Layer "optimizer": device milliseconds of ONE traced train under the scope ``seqrec.optimizer``: gradient norms, clipping, Adam over every parameter, the router bias
(``scope_reduce``: the operations' ``tf_op`` paths), forward, recomputation
and backward. Absent where the trace names no such scope."""

import seq_layers


def read(obs):
    secs = seq_layers.seconds(obs, "seqrec_optimizer")
    return None if secs is None else secs * 1e3
