"""Layer "compile": union of the first ``train.run``'s
``compile.backend`` spans whose ``cache`` is ``hit`` — the persistent
cache's read, the executable's deserialisation and its load onto the
device. 0 with an empty cache. None where the program keeps no compile
record."""

import setup_layers


def read(obs):
    return setup_layers.compile_seconds(
        obs, ("compile.backend",), lambda a: a.get("cache") == "hit")
