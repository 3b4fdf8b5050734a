"""Layer "save": seconds of the program's ``model.put.write`` span in
the traced train — ``LocalFSModelStore.put_parts`` copying the model's
parts into the file (the page cache) with the SHA-256 running beside it.
None on a program that opens no such span (the parent), and under a
model store that is not the local file system."""

import spans


def read(obs):
    return spans.seconds_of(spans.tree_of(obs), "model.put.write")
