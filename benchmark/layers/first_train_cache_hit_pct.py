"""Layer "compile": 100 × the first ``train.run``'s root ``cache_hits``
÷ (``cache_hits`` + ``programs_compiled``) — of the programs that
reached the backend, the share the persistent cache answered. Says WHICH
set-up the run had (0: the machine's cache was empty; 100: full), so two
``setup_s`` readings are compared like with like. None where the program
keeps no compile record or nothing reached the backend."""

import setup_layers


def read(obs):
    sums = setup_layers.compile_sums(obs)
    if sums is None:
        return None
    reached = sums["cache_hits"] + sums["programs_compiled"]
    return 100.0 * sums["cache_hits"] / reached if reached else None
