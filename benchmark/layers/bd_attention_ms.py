"""Layer "kernels": device milliseconds of ONE traced train under the
scope ``seqrec.bd.attention``: attention of both streams under the block
rule — the three kernels of ``ops/seq_attention.py`` walking, for a
block of clean rows, the clean key tiles to its blocks' end and, for a
block of noised rows, the clean tiles before its blocks and the noised
tiles of its own (``scope_reduce``: the operations' ``tf_op`` paths),
forward, recomputation and backward. Absent where the trace names no
such scope."""

import roofline_sdar
import scope_layers


def read(obs):
    return scope_layers.milliseconds(
        obs, *roofline_sdar.SCOPES["bd_attention"])
