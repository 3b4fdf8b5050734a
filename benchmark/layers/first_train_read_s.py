"""Layer "training read": seconds of the first ``train.run``'s
``train.read`` span — the cold read: the full scan of the event store
(``storage.scan`` with ``scan_cache`` = miss) and the snapshot's first
write, where ``read_training_s`` is the window's warm one. None on a
program that keeps no first verb."""

import setup_layers
import spans


def read(obs):
    return spans.seconds_of(setup_layers.first_tree(obs), "train.read")
