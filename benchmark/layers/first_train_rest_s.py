"""Layer "set-up": ``first_train_s`` less the union of ALL its
``compile.*`` spans — a warm verb plus the cold costs no span names yet
(the cold read is ``first_train_read_s``). With the four compile
metrics it adds up to ``first_train_s`` unless a program compiled on
another thread. None where the program keeps no compile record."""

import setup_layers


def read(obs):
    compiling = setup_layers.compile_seconds(obs)
    if compiling is None:
        return None
    return setup_layers.first_train_seconds(obs) - compiling
