"""Shared plumbing for the profile_*.py harnesses: in-memory Storage
wiring and running an asyncio HTTP server (Event/Engine Server) on a
background thread with readiness polling and clean shutdown."""

from __future__ import annotations

import http.client
import threading
import time
from contextlib import contextmanager


def resolve_platform(platform: str):
    """Apply a profiler's ``--platform`` choice and fail fast.

    A named platform pins jax to it; "" leaves jax's own choice. Either
    way a harness that expects the chip ("" or ``tpu``) aborts when
    only the CPU answers, rather than record CPU numbers labeled as
    chip measurements."""
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    jax.devices()  # fail fast if the platform is unreachable
    if platform in ("", "tpu") and jax.default_backend() == "cpu":
        raise SystemExit(
            f"--platform {platform or 'default'} expects the chip but "
            "only the CPU backend is available — aborting rather than "
            "mislabeling CPU numbers")
    return jax


def force_host_devices(n: int):
    """Expose ``n`` virtual CPU devices for chip-free mesh runs
    (sharded ANN A/Bs, dryruns). XLA reads the flag at backend init,
    so this MUST run before the first ``import jax`` anywhere in the
    process — same discipline as ``__graft_entry__.dryrun_multichip``."""
    import os
    import re
    import sys

    if "jax" in sys.modules:
        raise SystemExit(
            "force_host_devices must run before jax is imported "
            "(XLA reads --xla_force_host_platform_device_count at "
            "backend init)")
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   flags).strip()
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={int(n)}".strip())


def make_memory_storage():
    """A fresh all-in-memory Storage installed as process default."""
    from predictionio_tpu.data.events import MemoryEventStore
    from predictionio_tpu.storage.meta import MetaStore
    from predictionio_tpu.storage.models import MemoryModelStore
    from predictionio_tpu.storage.registry import (Storage, StorageConfig,
                                                   set_storage)

    st = Storage(StorageConfig(metadata_type="MEMORY",
                               eventdata_type="MEMORY",
                               modeldata_type="MEMORY"))
    st._meta = MetaStore(":memory:")
    st._events = MemoryEventStore()
    st._models = MemoryModelStore()
    set_storage(st)
    return st


@contextmanager
def server_thread(server, port: int, timeout: float = 15.0):
    """Run an Event/Engine Server's asyncio loop on a daemon thread,
    wait for `GET /` to answer, yield, then shut it down."""
    loop_box = {}

    def run():
        import asyncio

        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        loop_box["loop"] = loop
        loop.run_until_complete(server.serve_forever())

    t = threading.Thread(target=run, daemon=True)
    t.start()
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=2)
            try:
                conn.request("GET", "/")
                conn.getresponse().read()
                break
            finally:
                conn.close()
        except OSError:
            time.sleep(0.2)
    else:
        raise TimeoutError("server did not come up")
    try:
        yield
    finally:
        loop_box["loop"].call_soon_threadsafe(server.http.request_shutdown)
        t.join(timeout=5)
