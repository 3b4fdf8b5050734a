"""Layer "residual mixer": device milliseconds of ONE traced train under
the scope ``seqrec.mhc.coef``: a sublayer's coefficients — the norm of the
n·d-wide stream, φ's product (n·d × (2n + n²)), the two sigmoids, the clamp,
exp and the Sinkhorn chain's row and column divisions — forward,
recomputation and backward. Absent where the trace names no such scope."""

import roofline_xing4
import scope_layers


def read(obs):
    return scope_layers.milliseconds(obs, *roofline_xing4.SCOPES["mhc_coef"])
