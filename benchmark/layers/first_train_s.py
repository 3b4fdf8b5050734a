"""Layer "set-up": length of the process's FIRST ``train.run`` — the
warm-up train of the run, the one cold verb (compile or cache load, the
full scan, the snapshot's first write). What ``setup_s`` holds besides
is the data's making, the import and the process's start. None on a
program that keeps no first verb (``tracing.first_verb``)."""

import setup_layers


def read(obs):
    return setup_layers.first_train_seconds(obs)
