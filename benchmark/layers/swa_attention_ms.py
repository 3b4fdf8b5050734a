"""Layer "kernels": device milliseconds of ONE traced train under the
scope ``seqrec.swa.attention``: the window layers' scores, softmax and
values — the three kernels of ``ops/seq_attention.py`` walking only the
key tiles inside the window (``scope_reduce``: the operations' ``tf_op``
paths), forward, recomputation and backward. Absent where the trace names
no such scope."""

import roofline_smallthinker
import scope_layers


def read(obs):
    return scope_layers.milliseconds(
        obs, *roofline_smallthinker.SCOPES["swa_attention"])
