"""Native (C++) runtime components, built on demand with g++.

The reference's native layer lives in its dependencies (HBase client
transports, netty, netlib BLAS — SURVEY.md §2b); this package holds the
framework's own first-party native code. Libraries compile lazily on
first use into ``$PIO_HOME/native/`` keyed by a source hash, so a source
update or compiler change rebuilds automatically. Import failures (no
g++, sandboxed FS) degrade gracefully: callers fall back to the pure-
Python backends and say so.

- ``eventlog.cc`` — the append-only event log engine (``EVENTLOG``
  backend, import and the training scan).
- ``als_layout.cc`` — the order stage of the ALS layout as a stable
  counting sort: the entities' counts, then one pass over the COO that
  places every interaction at its entity's cursor (``models/als.py
  als_prepare``, ``_bucket_side`` step 1). Where it
  cannot be built, or a column is not a C-contiguous int32 / float32
  array, the layout runs its numpy radix order instead — the same
  arrays, bit for bit — and ``order_path_u/i`` on the ``als.prepare``
  span says which ran.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
_lock = threading.Lock()
_cache: dict = {}


class NativeBuildError(RuntimeError):
    pass


def _build_dir() -> str:
    from predictionio_tpu.storage.registry import pio_home

    d = os.path.join(pio_home(), "native")
    os.makedirs(d, exist_ok=True)
    return d


def load_library(name: str) -> ctypes.CDLL:
    """Compile (if needed) and dlopen ``<name>.cc`` from this package."""
    with _lock:
        if name in _cache:
            return _cache[name]
        src = os.path.join(_SRC_DIR, f"{name}.cc")
        with open(src, "rb") as f:
            source = f.read()
        tag = hashlib.sha256(source).hexdigest()[:16]
        so_path = os.path.join(_build_dir(), f"{name}-{tag}.so")
        if not os.path.exists(so_path):
            tmp = so_path + f".tmp{os.getpid()}"
            cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                   src, "-o", tmp]
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True,
                                      timeout=120)
            except (OSError, subprocess.TimeoutExpired) as e:
                raise NativeBuildError(f"g++ unavailable: {e}") from e
            if proc.returncode != 0:
                raise NativeBuildError(
                    f"g++ failed for {name}.cc:\n{proc.stderr[-2000:]}")
            os.replace(tmp, so_path)  # atomic vs concurrent builders
        lib = ctypes.CDLL(so_path)
        _cache[name] = lib
        return lib


def eventlog_library() -> Optional[ctypes.CDLL]:
    """The event-log engine, or None if it cannot be built here."""
    try:
        lib = load_library("eventlog")
    except NativeBuildError:
        return None
    lib.pel_open.restype = ctypes.c_void_p
    lib.pel_open.argtypes = [ctypes.c_char_p]
    lib.pel_open_ex.restype = ctypes.c_void_p
    lib.pel_open_ex.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.pel_info.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong),
        ctypes.POINTER(ctypes.c_longlong), ctypes.POINTER(ctypes.c_longlong),
        ctypes.POINTER(ctypes.c_longlong)]
    lib.pel_close.argtypes = [ctypes.c_void_p]
    lib.pel_append_batch.restype = ctypes.c_int
    lib.pel_append_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong, ctypes.c_int]
    lib.pel_sync.restype = ctypes.c_int
    lib.pel_sync.argtypes = [ctypes.c_void_p]
    lib.pel_delete.restype = ctypes.c_int
    lib.pel_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
    lib.pel_wipe.restype = ctypes.c_int
    lib.pel_wipe.argtypes = [ctypes.c_void_p]
    lib.pel_count.restype = ctypes.c_longlong
    lib.pel_count.argtypes = [ctypes.c_void_p]
    lib.pel_live_ids.restype = ctypes.c_longlong
    lib.pel_live_ids.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p)]
    # out-params are void* (payloads contain NUL bytes — read with
    # ctypes.string_at(ptr, length), never c_char_p auto-conversion)
    lib.pel_get.restype = ctypes.c_longlong
    lib.pel_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
                            ctypes.POINTER(ctypes.c_void_p)]
    lib.pel_find.restype = ctypes.c_longlong
    lib.pel_find.argtypes = [
        ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.c_int, ctypes.c_longlong,
        ctypes.POINTER(ctypes.c_void_p)]
    lib.pel_aggregate.restype = ctypes.c_longlong
    lib.pel_aggregate.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong,
        ctypes.c_longlong, ctypes.POINTER(ctypes.c_void_p)]
    lib.pel_append_jsonl.restype = ctypes.c_longlong
    lib.pel_append_jsonl.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_longlong,
        ctypes.c_longlong, ctypes.c_ulonglong, ctypes.c_char_p,
        ctypes.c_longlong, ctypes.c_char_p]
    lib.pel_export_jsonl.restype = ctypes.c_longlong
    lib.pel_export_jsonl.argtypes = [
        ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong,
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_longlong)]
    lib.pel_scan_columnar.restype = ctypes.c_longlong
    lib.pel_scan_columnar.argtypes = [
        ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong,
        ctypes.c_longlong, ctypes.c_longlong,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_void_p)]
    lib.pel_scan_columnar_ex.restype = ctypes.c_longlong
    lib.pel_scan_columnar_ex.argtypes = [
        ctypes.c_void_p, ctypes.c_longlong, ctypes.c_longlong,
        ctypes.c_longlong, ctypes.c_longlong,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_void_p)]
    lib.pel_creation_stats.restype = ctypes.c_longlong
    lib.pel_creation_stats.argtypes = [
        ctypes.c_void_p, ctypes.c_longlong,
        ctypes.POINTER(ctypes.c_longlong)]
    lib.pel_creation_bounds.restype = ctypes.c_longlong
    lib.pel_creation_bounds.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong),
        ctypes.POINTER(ctypes.c_longlong)]
    lib.pel_free.argtypes = [ctypes.c_void_p]
    return lib


def als_layout_library() -> Optional[ctypes.CDLL]:
    """The ALS layout's order pass, or None if it cannot be built here
    (no g++, no writable ``$PIO_HOME/native``)."""
    try:
        lib = load_library("als_layout")
    except (NativeBuildError, OSError):
        return None
    lib.als_count.restype = ctypes.c_int64
    lib.als_count.argtypes = [ctypes.c_int64, ctypes.c_void_p,
                              ctypes.c_int64, ctypes.c_void_p]
    lib.als_order_scatter.restype = ctypes.c_int64
    lib.als_order_scatter.argtypes = [
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p]
    return lib
