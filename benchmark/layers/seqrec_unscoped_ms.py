"""Layer "seqrec step": device milliseconds of ONE traced train that
nothing names — under no ``seqrec.*`` scope (``other``) or under
``seqrec.step`` alone, the step's body outside every narrower scope
(``scope_reduce``: the operations' ``tf_op`` paths) — less the
``ragged-dot`` kernels, which the compiler takes out of their scope and
``moe_ragged_dot_ms`` reads by name. A program without ``seqrec.step``
(the parent) reads its ``other``; absent only where the trace gave no
scopes at all."""

import scope_layers


def read(obs):
    found = obs.get("scopes")
    if not found:
        return None
    return (found.get("other", 0.0) + found.get("seqrec.step", 0.0)
            - scope_layers.ragged_dot_seconds(obs)) * 1e3
