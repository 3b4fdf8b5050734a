"""Layer "event ingest": Σ of the program's
``pio_ingest_seconds_total`` over its stages — every second spent in
``data/filestore.py append_jsonl`` during the run's import, by the
program's own clock (the benchmark's ``imported … in N s`` also holds
its NDJSON writer). None where the store was reused or the program has
no such series."""

import setup_layers


def read(obs):
    return setup_layers.ingest_seconds(obs)
