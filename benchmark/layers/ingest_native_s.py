"""Layer "event ingest": the ``native`` stage of
``pio_ingest_seconds_total`` alone — the C++ ``pel_append_jsonl`` calls
(parse, frame, append). ``ingest_append_s`` less this is Python around
the call (the status buffer's per-line loop, locks) and the fsync of a
durable store. None where the store was reused or the program has no
such series."""

import setup_layers


def read(obs):
    return setup_layers.ingest_seconds(obs, "native")
