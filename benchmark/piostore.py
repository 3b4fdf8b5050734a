"""The benchmark's PredictionIO home: event store (EVENTLOG, the C++
engine `pio import` uses), meta data and model registry under
``benchmark/.cache/<config>/pio``, filled from a seed.

The imported event log is kept with a manifest (seed, count, digest,
generator version). A later run of the same configuration and seed in
the same checkout verifies the manifest and reuses the log; everything
else in the home (instances, models, the scan snapshot) is made anew by
every run, so each run does the same work. One store is kept per
configuration: another seed replaces it.
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import threading
import time

from datagen import GENERATOR_VERSION, ndjson_block
from harness import CACHE, BenchFailure, say

APP = "Bench"
#: lines per ``append_jsonl`` call. Small on purpose: the program's call
#: scans its per-line status buffer with ``status.raw[i]``, which copies
#: the whole buffer for every line, so a call costs n² bytes of copying
#: — 1 M-line blocks imported at 30 k events/s on the chip's host, 4 k-line
#: blocks at ten times that (my chip run and CPU, PR 23; PERF.md §7)
BLOCK = 4096


def _home(config_name: str, tiny: bool) -> str:
    return os.path.join(CACHE, config_name + ("-tiny" if tiny else ""))


def open_home(config_name: str, tiny: bool, keep_events: bool) -> str:
    """Point the program's storage at this configuration's home, wiped
    but for the built C++ engine and (where asked) the event log."""
    home = os.path.join(_home(config_name, tiny), "pio")
    if os.path.isdir(home):
        for entry in os.listdir(home):
            if entry == "native" or (keep_events and entry == "eventlog"):
                continue
            path = os.path.join(home, entry)
            shutil.rmtree(path) if os.path.isdir(path) else os.remove(path)
    os.makedirs(home, exist_ok=True)
    os.environ["PIO_HOME"] = home
    os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "BENCHLOG"
    os.environ["PIO_STORAGE_SOURCES_BENCHLOG_TYPE"] = "EVENTLOG"
    return home


def ensure_events(config_name: str, tiny: bool, data, seed: int,
                  decimals: int) -> dict:
    """The seed's interactions in the event store, imported now or
    found as an earlier run left them. Returns what was done."""
    from predictionio_tpu import native
    from predictionio_tpu.storage import get_storage
    from predictionio_tpu.storage.registry import set_storage

    manifest_path = os.path.join(_home(config_name, tiny), "manifest.json")
    want = {"generator_version": GENERATOR_VERSION, "seed": seed,
            "count": data.nnz, "digest": data.digest()}
    try:
        with open(manifest_path) as f:
            reuse = json.load(f) == want
    except (OSError, ValueError):
        reuse = False
    if not reuse and os.path.exists(manifest_path):
        os.remove(manifest_path)
    open_home(config_name, tiny, keep_events=reuse)
    if native.eventlog_library() is None:   # built under $PIO_HOME/native
        raise BenchFailure("the native event-log engine did not build "
                           "(g++ missing?): no EVENTLOG store, no cell")
    set_storage(None)
    st = get_storage()
    app = st.meta.create_app(APP)
    app_id = getattr(app, "id", app)
    st.events.init_channel(app_id, None)
    t0 = time.perf_counter()
    if not reuse:
        _import(st, app_id, data, decimals)
    stats = st.events.creation_stats(app_id, None)
    have = stats[0] if stats else -1
    if have != data.nnz:
        raise BenchFailure(f"the event store holds {have} events, "
                           f"{data.nnz} were made")
    secs = time.perf_counter() - t0
    if reuse:
        say(f"store: manifest matches seed {seed} "
            f"({data.nnz:,} events, digest {want['digest']}): reused")
    else:
        with open(manifest_path, "w") as f:
            json.dump(want, f)
        say(f"store: imported {data.nnz:,} events in {secs:.1f} s "
            f"({data.nnz / secs / 1e3:.0f} k events/s, native parser)")
    return {"reused": reuse, "import_s": secs, "app_id": app_id}


def _import(st, app_id: int, data, decimals: int) -> None:
    """`pio import`'s native ingest (``append_jsonl``: parse, frame and
    append in C++), fed NDJSON blocks from memory while the next block
    is being written — the 2.8 GB file never exists."""
    blocks: queue.Queue = queue.Queue(maxsize=2)

    def produce() -> None:
        try:
            for a in range(0, data.nnz, BLOCK):
                b = min(a + BLOCK, data.nnz)
                blocks.put((ndjson_block(data.users[a:b], data.items[a:b],
                                         data.values[a:b], decimals),
                            b - a))
            blocks.put(None)
        except Exception as e:  # noqa: BLE001 — raised again by the reader
            blocks.put(e)

    writer = threading.Thread(target=produce, name="bench-ndjson")
    writer.start()
    try:
        while True:
            got = blocks.get()
            if got is None:
                break
            if isinstance(got, Exception):
                raise got
            blob, n_lines = got
            appended, fallback = st.events.append_jsonl(
                blob, n_lines, app_id, None)
            if appended != n_lines or fallback:
                raise BenchFailure(
                    f"the native parser took {appended} of {n_lines} "
                    f"lines ({len(fallback)} declined)")
    finally:
        while writer.is_alive():        # never leave the thread blocked
            try:
                blocks.get(timeout=0.1)
            except queue.Empty:
                pass
        writer.join()
