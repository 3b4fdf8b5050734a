"""Layer "save": seconds of the program's ``train.save`` span in the
traced train — ``model.serialize`` (``algo.save_model``: a pickled head
and the arrays' own buffers as parts, no npz, no zlib — PR 34) and
``model.put`` (the registry write, ``save_write_s`` + ``save_sync_s`` +
the hash's tail)."""

import spans


def read(obs):
    return spans.seconds_of(spans.tree_of(obs), "train.save")
