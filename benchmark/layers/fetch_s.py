"""Layer "fetch": seconds of the program's ``als.fetch`` span in the
traced train — the un-permute on the device and the packed
device→host fetch of U and V."""

import spans


def read(obs):
    return spans.seconds_of(spans.tree_of(obs), "als.fetch")
