"""Layer "save": seconds of the program's ``model.put.sync`` span in
the traced train — flush, fsync of the written file, the replace, fsync
of its directory: what makes the model durable under its name. None on
a program that opens no such span (the parent), and under a model store
that is not the local file system."""

import spans


def read(obs):
    return spans.seconds_of(spans.tree_of(obs), "model.put.sync")
