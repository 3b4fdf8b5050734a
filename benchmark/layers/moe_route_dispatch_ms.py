"""Layer "expert dispatch": device milliseconds of ONE traced train under the scopes ``seqrec.moe.route``, ``.dispatch`` and ``.combine``: router, top-k, sort, gather, weighted un-permute
(``scope_reduce``: the operations' ``tf_op`` paths), forward, recomputation
and backward. Absent where the trace names no such scope."""

import seq_layers


def read(obs):
    secs = seq_layers.seconds(obs, "moe_route_dispatch")
    return None if secs is None else secs * 1e3
