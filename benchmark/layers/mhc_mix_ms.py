"""Layer "residual mixer": device milliseconds of ONE traced train under
the scopes ``seqrec.mhc.mix`` and ``seqrec.mhc``: the n-copy stream's read
u = Σᵢ H_pre[i]·X[i] and write-back X[i] ← Σⱼ H_res[i, j]·X[j] +
H_post[i]·y around every sublayer, the copies at the stack's start and the
fold at its end (``scope_reduce``: the operations' ``tf_op`` paths),
forward, recomputation and backward. Absent where the trace names no such
scope (a program without the mixer)."""

import roofline_xing4
import scope_layers


def read(obs):
    return scope_layers.milliseconds(obs, *roofline_xing4.SCOPES["mhc_mix"])
