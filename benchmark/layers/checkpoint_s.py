"""Layer "checkpoint": summed seconds of the program's
``als.checkpoint`` spans in the traced train, one per block of
iterations — the host fetch of U and V and ``checkpointer.save``, with
the chip idle."""

import spans


def read(obs):
    return spans.seconds_of(spans.tree_of(obs), "als.checkpoint")
