"""Layer "compile": union of the first ``train.run``'s
``compile.trace`` spans — Python tracing of every program the cold verb
jitted (``utils/compilecache.py``; a jit traced inside another is part
of the outer one). Happens BEFORE the persistent cache can be asked, so
a full cache does not save it. None where the program keeps no compile
record."""

import setup_layers


def read(obs):
    return setup_layers.compile_seconds(obs, ("compile.trace",))
