"""Layer "compile": union of the first ``train.run``'s
``compile.lower`` spans — jaxpr → MLIR module of every program the cold
verb jitted. The cache's key is made from the lowered module, so a full
cache does not save it. None where the program keeps no compile
record."""

import setup_layers


def read(obs):
    return setup_layers.compile_seconds(obs, ("compile.lower",))
