"""Layer "kernels": device milliseconds of ONE traced train under the
scope ``seqrec.gdn.scan``: the linear layers' gated delta rule — its
chunks' triangular solves and the walk over them (``scope_reduce``: the
operations' ``tf_op`` paths), forward, recomputation and backward.
Absent where the trace names no such scope."""

import roofline_qwen3next


def read(obs):
    secs = roofline_qwen3next.seconds(obs, "gdn_scan")
    return None if secs is None else secs * 1e3
