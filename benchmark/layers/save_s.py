"""Layer "save": seconds of the program's ``train.save`` span in the
traced train — ``model.serialize`` (``algo.save_model``:
``np.savez_compressed``) and ``model.put`` (the registry write)."""

import spans


def read(obs):
    return spans.seconds_of(spans.tree_of(obs), "train.save")
