"""Layer "compile": union of the first ``train.run``'s
``compile.backend`` spans whose ``cache`` is not ``hit`` — the backend's
compiler itself (``miss``: the cache was asked and had nothing; ``off``:
it was never asked). 0 with a full cache. None where the program keeps
no compile record."""

import setup_layers


def read(obs):
    return setup_layers.compile_seconds(
        obs, ("compile.backend",), lambda a: a.get("cache") != "hit")
