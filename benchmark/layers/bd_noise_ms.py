"""Layer "objective": device milliseconds of ONE traced train under the
scope ``seqrec.bd.noise``: the step's draw (threefry, two uniforms a
slot), the noised stream's tokens and the rows' weights
(``scope_reduce``: the operations' ``tf_op`` paths). Absent where the
trace names no such scope."""

import roofline_sdar
import scope_layers


def read(obs):
    return scope_layers.milliseconds(obs, *roofline_sdar.SCOPES["bd_noise"])
