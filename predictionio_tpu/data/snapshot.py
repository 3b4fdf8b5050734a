"""Columnar snapshot cache: persisted ``ColumnarEvents`` + watermark.

The reference's training contract re-reads the FULL event history on
every ``pio train`` (PAPER.md §0: PEventStore → RDD per invocation).
For the steady-state retrain loop over a mostly-append-only log that
makes train startup O(total events) forever. This module is the disk
layer of the incremental scan cache that turns it into O(events since
last train):

- a **snapshot** is one ``ColumnarEvents`` (the arrays
  ``data/pipeline.columnar_from_rows`` builds) persisted as ONE file of
  raw columns (``snap_<key>.cols``, the format below) next to a small
  JSON **manifest**;
- the manifest carries a **watermark** — the maximum ``creationTime``
  (epoch µs) the snapshot covers, taken from the store BEFORE the
  building scan started — plus the live-event count at that watermark
  and the hash of the filter key;
- on the next train, ``data/store.py`` loads the snapshot, asks the
  backend to scan only ``creationTime > watermark`` (predicate pushed
  down into C++/SQL/doc-values), and concatenates the delta
  (:func:`data.pipeline.concat_columnar`).

File format (schema 3), laid out so that a load touches every byte
ONCE — with the SHA-256 that verifies it — and the train uses the
columns where they lie:

- ``b"PIOSNAP3"``, a little-endian u64 with the header's length, the
  header (JSON: ``n_rows`` and, per string table, its numpy dtype and
  length);
- the five columns (``entity_idx`` u32, ``target_idx`` u32,
  ``name_idx`` u16, ``values`` f64, ``times_us`` i64) and the three
  string tables (fixed-width unicode, as numpy lays a ``U`` array out)
  in that order, each section starting at the next multiple of 64 B,
  the gaps zero, nothing after the last;
- :func:`load_snapshot` maps the file read-only (or reads it once into
  one buffer where the file system refuses a map), digests the header
  and the eight sections where they lie — concurrently, ``hashlib``
  releases the GIL — and hands out the columns as read-only
  ``np.frombuffer`` views of that one buffer: no copy, aligned,
  C-contiguous. The mapping lives as long as the columns do and no
  longer; nothing loaded is kept between trains.

Invalidation rules (any failure falls back to a full rescan — the
cache can cost a rebuild, never correctness):

- manifest missing/unreadable, schema version bump (a schema-2
  ``.npz`` pair left by an older tree is a cold miss; the rebuild
  removes it), filter-key hash mismatch, the column file missing, or
  a row count disagreeing with the manifest;
- the column file foreign, truncated or otherwise not laid out as
  above, or a SHA-256 digest in the manifest (header, five
  columns, three tables) disagreeing with the file's bytes (bit rot):
  counted on ``pio_integrity_failed_total{artifact="snapshot"}`` and
  treated as a cold cache — a corrupt snapshot costs a rebuild, never
  a wrong training set and never a crash. Every digest is verified
  BEFORE any of the bytes is used;
- the live-event count at the old watermark no longer matches the
  manifest (events were deleted, or arrived bearing creationTimes at
  or below the watermark);
- the delta contains an event whose eventTime is ≤ the snapshot's
  maximum (out-of-order append: concatenation would not reproduce the
  (eventTime, creationTime, id) scan order);
- ``startTime``/``untilTime`` filters bypass the cache entirely (a
  time-windowed read is not the repeat-train shape).

Cache keys hash the full filter tuple PLUS a backend-provided
``cache_identity`` string (e.g. the sqlite path), so two stores that
happen to share an app id can never serve each other's snapshots.
Files live under ``<storage home>/scan_cache/`` (override with
``PIO_SCAN_CACHE_DIR``).
"""

from __future__ import annotations

import hashlib
import json
import mmap
import os
import struct
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.utils import faults, tracing
from predictionio_tpu.utils.atomic_write import atomic_file, atomic_write_text
from predictionio_tpu.utils.integrity import (
    INTEGRITY_FAILED,
    INTEGRITY_VERIFIED,
)

# v3: one file of raw 64-byte-aligned columns that a load maps and
# digests where they lie (v2: an ``.npz``, copied out member by
# member). The bump itself invalidates older snapshots (a cache miss,
# rebuilt on the next train).
SCHEMA_VERSION = 3

# watermark of an empty namespace: below every real creationTime, and
# matching the native scan's unbounded sentinel so `creation > W`
# selects everything and `creation <= W` selects nothing
EMPTY_WATERMARK = -(2**62)

_ARRAY_FIELDS = ("entity_idx", "target_idx", "name_idx", "values",
                 "times_us")
_TABLE_FIELDS = ("entity_ids", "target_ids", "names")
_DTYPES = {"entity_idx": "uint32", "target_idx": "uint32",
           "name_idx": "uint16", "values": "float64",
           "times_us": "int64"}

_MAGIC = b"PIOSNAP3"
_HEADER_LEN = struct.Struct("<Q")
_ALIGN = 64
#: the manifest's digest of magic + length + header JSON, beside the
#: eight sections' own
_HEADER = "header"


@dataclass
class SnapshotManifest:
    """The validity contract of one persisted snapshot."""

    schema: int
    filter_hash: str
    watermark_us: int
    pre_count: int  # live events with creationTime <= watermark_us
    n_rows: int     # rows in the columns (post-filter)
    created_at: float
    digests: Dict[str, str] = field(default_factory=dict)  # field -> sha256


def cache_dir(storage) -> str:
    """Snapshot directory for a Storage (env-overridable)."""
    override = os.environ.get("PIO_SCAN_CACHE_DIR")
    if override:
        return override
    return os.path.join(storage.config.home, "scan_cache")


def filter_fingerprint(
    identity: str,
    app_id: int,
    channel_id: Optional[int],
    entity_type: Optional[str],
    target_entity_type: Optional[str],
    event_names: Optional[Sequence[str]],
    value_key: Optional[str],
) -> str:
    """Hash of (store identity, namespace, scan filters) — the cache
    key. Hashed rather than embedded so arbitrary ids/filters can't
    produce unbounded or path-hostile filenames."""
    payload = json.dumps(
        {"identity": identity, "app": app_id, "channel": channel_id,
         "entity_type": entity_type,
         "target_entity_type": target_entity_type,
         "event_names": (list(event_names)
                         if event_names is not None else None),
         "value_key": value_key},
        sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(payload.encode("utf-8")).hexdigest()


def _paths(directory: str, fingerprint: str) -> Tuple[str, str]:
    base = os.path.join(directory, f"snap_{fingerprint}")
    return base + ".cols", base + ".json"


def legacy_path(directory: str, fingerprint: str) -> str:
    """Where a schema-2 tree kept this key's columns (an ``.npz``)."""
    return os.path.join(directory, f"snap_{fingerprint}.npz")


def _table_array(strings) -> np.ndarray:
    # numpy U-dtype: fixed-width unicode, readable where it lies
    if len(strings):
        return np.asarray(list(strings), dtype=np.str_)
    return np.empty(0, dtype="U1")


def _aligned(offset: int) -> int:
    return -(-offset // _ALIGN) * _ALIGN


def _digests(sections: Dict[str, memoryview]) -> Dict[str, str]:
    """SHA-256 of every section, read where it lies (no copy), the
    sections side by side: ``hashlib`` releases the GIL."""
    with ThreadPoolExecutor(max_workers=len(sections)) as pool:
        done = pool.map(lambda b: hashlib.sha256(b).hexdigest(),
                        sections.values())
        return dict(zip(sections, done))


def save_snapshot(
    directory: str,
    fingerprint: str,
    cols,
    watermark_us: int,
    pre_count: int,
) -> bool:
    """Persist ``cols`` + manifest atomically AND durably (fsync'd tmp
    file + rename + dir fsync via utils.atomic_write; the manifest
    lands LAST, so a manifest's presence implies a complete column
    file). Returns False instead of raising — a full disk or read-only
    cache dir must never fail the training read it rides on."""
    cols_path, man_path = _paths(directory, fingerprint)
    try:
        os.makedirs(directory, exist_ok=True)
        arrays = {k: np.ascontiguousarray(getattr(cols, k))
                  for k in _ARRAY_FIELDS}
        if any(a.dtype != np.dtype(_DTYPES[k]) or a.shape != (cols.n,)
               for k, a in arrays.items()):
            return False    # not the columns a load would hand back
        arrays.update((k, _table_array(getattr(cols, k)))
                      for k in _TABLE_FIELDS)
        head = json.dumps(
            {"n_rows": int(cols.n),
             "tables": {k: [arrays[k].dtype.str, int(arrays[k].shape[0])]
                        for k in _TABLE_FIELDS}},
            separators=(",", ":")).encode("utf-8")
        head = _MAGIC + _HEADER_LEN.pack(len(head)) + head
        sections = {_HEADER: memoryview(head)}
        sections.update((k, a.view(np.uint8).data)
                        for k, a in arrays.items())
        digests = _digests(sections)
        with atomic_file(cols_path, "wb") as f:
            at = 0
            for section in sections.values():
                f.write(bytes(_aligned(at) - at))
                f.write(section)
                at = _aligned(at) + section.nbytes
        ok = _write_manifest(man_path, fingerprint, watermark_us,
                             pre_count, cols.n, digests)
        try:    # an older tree's columns for this key: superseded now
            os.unlink(legacy_path(directory, fingerprint))
        except OSError:
            pass
        return ok
    except Exception:
        return False


def update_manifest(
    directory: str,
    fingerprint: str,
    watermark_us: int,
    pre_count: int,
    n_rows: int,
) -> bool:
    """Advance the watermark of an existing snapshot whose arrays are
    unchanged (an empty delta still moves the watermark forward, so
    later delta scans stay O(new events) instead of re-walking the
    whole post-watermark window). The digests carry over from the
    existing manifest — the column file did not change."""
    _cols, man_path = _paths(directory, fingerprint)
    try:
        with open(man_path, "r", encoding="utf-8") as f:
            digests = json.load(f).get("digests")
        if not isinstance(digests, dict):
            return False  # pre-integrity manifest: let it invalidate
        return _write_manifest(man_path, fingerprint, watermark_us,
                               pre_count, n_rows, digests)
    except Exception:
        return False


def _write_manifest(man_path: str, fingerprint: str, watermark_us: int,
                    pre_count: int, n_rows: int,
                    digests: Dict[str, str]) -> bool:
    doc = {"schema": SCHEMA_VERSION, "filter": fingerprint,
           "watermark_us": int(watermark_us), "pre_count": int(pre_count),
           "n_rows": int(n_rows), "created_at": time.time(),
           "digests": digests}
    atomic_write_text(man_path, json.dumps(doc, separators=(",", ":")))
    return True


def _open_buffer(path: str):
    """The file's bytes as ONE read-only buffer: a private mapping of
    its pages, or — where the file system refuses a map — one read."""
    with open(path, "rb") as f:
        try:
            return mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        except (OSError, ValueError):
            return f.read()


def _sections(buf) -> Tuple[int, Dict[str, Tuple[int, int, np.dtype]]]:
    """``(n_rows, {name: (offset, count, dtype)})`` of a schema-3
    buffer: its header and its eight sections, in file order. Raises on
    any buffer that is not laid out as :func:`save_snapshot` writes it:
    wrong magic, a section past the end, a gap that is not zeros, bytes
    after the last section."""
    view = memoryview(buf)
    fixed = len(_MAGIC) + _HEADER_LEN.size
    if bytes(view[:len(_MAGIC)]) != _MAGIC:
        raise ValueError("not a schema-3 snapshot")
    (head_len,) = _HEADER_LEN.unpack_from(view, len(_MAGIC))
    end = fixed + head_len
    if end > len(view):
        raise ValueError("truncated inside the header")
    head = json.loads(bytes(view[fixed:end]))
    n_rows = int(head["n_rows"])
    shapes = [(k, n_rows, np.dtype(_DTYPES[k])) for k in _ARRAY_FIELDS]
    for k in _TABLE_FIELDS:
        dtype, count = head["tables"][k]
        dtype = np.dtype(dtype)
        if dtype.kind != "U":
            raise ValueError(f"table {k} is not a string table")
        shapes.append((k, int(count), dtype))
    where = {_HEADER: (0, end, np.dtype(np.uint8))}
    for k, count, dtype in shapes:
        at = _aligned(end)
        if (count < 0 or at + count * dtype.itemsize > len(view)
                or any(view[end:at])):
            raise ValueError(f"section {k} is not where it belongs")
        where[k] = (at, count, dtype)
        end = at + count * dtype.itemsize
    if end != len(view):
        raise ValueError("bytes after the last section")
    return n_rows, where


def load_snapshot(directory: str, fingerprint: str):
    """Load and validate one snapshot.

    Returns ``(ColumnarEvents, SnapshotManifest)``, or None on ANY
    defect — missing files, unreadable JSON, schema/filter mismatch, a
    column file that is foreign, truncated or damaged, or lengths that
    disagree with the manifest. Callers treat None as a cold cache.

    The columns are READ-ONLY views of the one buffer the file was
    mapped (or read) into, verified before they are built; the span
    that is current (``storage.scan.load`` under a train) gets
    ``bytes`` (digested), ``copied_bytes`` (written into fresh memory
    to hold the columns: 0 when mapped), ``mapped`` and ``schema``."""
    from predictionio_tpu.data.pipeline import ColumnarEvents

    cols_path, man_path = _paths(directory, fingerprint)
    try:
        with open(man_path, "r", encoding="utf-8") as f:
            doc = json.load(f)
        if (doc.get("schema") != SCHEMA_VERSION
                or doc.get("filter") != fingerprint):
            return None
        digests = doc.get("digests")
        if not isinstance(digests, dict):
            return None
        man = SnapshotManifest(
            schema=int(doc["schema"]), filter_hash=doc["filter"],
            watermark_us=int(doc["watermark_us"]),
            pre_count=int(doc["pre_count"]), n_rows=int(doc["n_rows"]),
            created_at=float(doc.get("created_at", 0.0)),
            digests={str(k): str(v) for k, v in digests.items()})
        # byte-flip-on-read fault site, feeding the checks below (it
        # copies the buffer when armed, and only then)
        buf = faults.corrupt_bytes("data.corrupt.snapshot",
                                   _open_buffer(cols_path))
        mapped = not isinstance(buf, bytes)     # a read or the fault's copy
        try:
            n_rows, where = _sections(buf)
        except Exception:
            # valid manifest but a file that is not a snapshot's =
            # damage, not a cold cache
            INTEGRITY_FAILED.inc(("snapshot",))
            return None
        if n_rows != man.n_rows:
            return None
        # digest verification, before any byte is used: a flipped bit
        # anywhere in the file is a counted cache miss (rebuild), never
        # a wrong training set
        view = memoryview(buf)
        parts = {k: view[at:at + count * dtype.itemsize]
                 for k, (at, count, dtype) in where.items()}
        if any(man.digests.get(k) != d
               for k, d in _digests(parts).items()):
            INTEGRITY_FAILED.inc(("snapshot",))
            return None
        INTEGRITY_VERIFIED.inc(("snapshot",))
        tracing.add_attrs(
            schema=man.schema, mapped=int(mapped),
            bytes=sum(part.nbytes for part in parts.values()),
            copied_bytes=0 if mapped else len(buf))
        arrays = {k: np.frombuffer(buf, dtype=dtype, count=count, offset=at)
                  for k, (at, count, dtype) in where.items() if k != _HEADER}
        tables: Dict[str, List[str]] = {k: arrays[k].tolist()
                                        for k in _TABLE_FIELDS}
        # index columns must point inside their tables, or downstream
        # vectorized gathers would read garbage
        for idx_k, tab_k in (("entity_idx", "entity_ids"),
                             ("target_idx", "target_ids"),
                             ("name_idx", "names")):
            a = arrays[idx_k]
            if a.size and int(a.max()) >= len(tables[tab_k]):
                return None
        cols = ColumnarEvents(
            entity_idx=arrays["entity_idx"],
            target_idx=arrays["target_idx"],
            name_idx=arrays["name_idx"], values=arrays["values"],
            times_us=arrays["times_us"],
            entity_ids=tables["entity_ids"],
            target_ids=tables["target_ids"], names=tables["names"])
        return cols, man
    except Exception:
        return None
