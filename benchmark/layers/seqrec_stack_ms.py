"""Layer "seqrec step": device milliseconds of ONE traced train under
the scope ``seqrec.stack`` outside every scope inside it — the scan over
a run of layers itself: a layer's weights sliced out of the stack, the
carried stream kept for the backward pass, the weights' gradients
written back into theirs (``scope_reduce``: the operations' ``tf_op``
paths), forward and backward. Absent where the trace names no such
scope."""

import scope_layers


def read(obs):
    return scope_layers.milliseconds(obs, "seqrec.stack")
