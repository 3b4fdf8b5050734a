"""Layer "packing": seconds of the program's ``seqrec.pack`` span in
the newest train — histories into sequences with segment ids."""

import spans


def read(obs):
    return spans.seconds_of(spans.tree_of(obs), "seqrec.pack")
