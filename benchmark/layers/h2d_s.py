"""Layer "upload": seconds of the program's ``als.upload`` span in the
traced train — ``prep.device_buffers`` and the ``put`` of V0 and the
permutations: the host's side of the host→device copies (the calls
return when the runtime has taken the buffers; nothing waits for the
device)."""

import spans


def read(obs):
    return spans.seconds_of(spans.tree_of(obs), "als.upload")
