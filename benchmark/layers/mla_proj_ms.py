"""Layer "kernels": device milliseconds of ONE traced train under the scope ``seqrec.mla`` outside its attention product: the latent projections, norms and RoPE
(``scope_reduce``: the operations' ``tf_op`` paths), forward, recomputation
and backward. Absent where the trace names no such scope."""

import seq_layers


def read(obs):
    secs = seq_layers.seconds(obs, "mla_proj")
    return None if secs is None else secs * 1e3
