"""Offline integrity scanner behind ``pio fsck``.

A pure-Python re-implementation of the eventlog on-disk contract (see
``native/eventlog.cc``'s header comment — the C++ side is the writer,
this side only ever reads), plus digest checks for the other two
persisted artifact classes (snapshot columns + manifest, model blobs +
sidecars). Deliberately NOT the engine:

- it runs without a compiler (the native engine needs g++ to build;
  an operator fscking a damaged volume may not have one);
- it never repairs implicitly — ``pel_open`` quarantines torn tails as
  a side effect of opening, this walks read-only unless ``repair=True``
  is requested explicitly;
- it hosts the ``data.corrupt.eventlog`` fault site, so checksum
  detection is testable without manufacturing real bit rot.

Verdicts per artifact: ``ok`` (all checks pass), ``corrupt`` (checksum
or structural mismatch in the body), ``torn`` (incomplete tail — a
crash mid-append), ``unchecksummed`` (pre-integrity artifact with no
digest to verify), ``repaired`` (was torn, tail quarantined and
truncated under ``--repair``), ``stale`` (a snapshot file of an older
schema that no train reads any more: not damage).

Repair policy mirrors what each artifact can afford:

- **eventlog**: copy the torn tail to ``<log>.quarantine-<offset>``
  (never destroy operator data, even garbage), then truncate to the
  last intact record boundary. Checksummed records are never touched.
- **snapshot**: delete the pair — it is a cache; the next train
  rebuilds it from the log.
- **model**: report only. A model blob is not rebuildable from
  anything here; the operator must retrain or restore.
"""

from __future__ import annotations

import hashlib
import json
import os
import struct
from typing import Dict, List, Optional

from predictionio_tpu.utils import faults
from predictionio_tpu.utils.atomic_write import fsync_dir
from predictionio_tpu.utils.integrity import DIGEST_SUFFIX

#: v2 file header (must match kMagic in eventlog.cc)
PEL_MAGIC = b"PELOGv2\n"

_U32 = struct.Struct("<I")

# CRC-32C (Castagnoli), reflected, table-driven — bit-for-bit the
# engine's crc32c(): crc32c(b"123456789") == 0xE3069283
_CRC_TABLE: List[int] = []
for _i in range(256):
    _c = _i
    for _ in range(8):
        _c = (_c >> 1) ^ 0x82F63B78 if _c & 1 else _c >> 1
    _CRC_TABLE.append(_c)


def crc32c(data: bytes, crc: int = 0) -> int:
    crc ^= 0xFFFFFFFF
    for b in data:
        crc = _CRC_TABLE[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF


def _payload_ok(kind: int, payload: bytes) -> bool:
    """Structural walk of a record payload — the only corruption
    signal a v1 (checksum-less) file offers, and a cheap cross-check
    on v2. Kind 0: two i64 timestamps then 9 length-prefixed strings
    consuming the payload exactly; kind 1: one length-prefixed id."""
    if kind == 0:
        pos = 16  # two i64 timestamps
        if len(payload) < pos:
            return False
        for _ in range(9):
            if pos + 4 > len(payload):
                return False
            (n,) = _U32.unpack_from(payload, pos)
            pos += 4 + n
            if pos > len(payload):
                return False
        return pos == len(payload)
    if kind == 1:
        if len(payload) < 4:
            return False
        (n,) = _U32.unpack_from(payload, 0)
        return 4 + n == len(payload)
    return False  # unknown kind byte


def scan_pel(path: str, repair: bool = False) -> Dict[str, object]:
    """Walk one ``.pel`` segment record-by-record.

    Returns a report dict: ``version``, ``records``, ``tombstones``,
    ``corrupt`` (+ ``corrupt_offsets``, capped), ``torn_offset`` (None
    when the tail is clean), ``valid_end`` (last intact record
    boundary), ``status``, and under ``repair`` the ``quarantine``
    sidecar path written before truncation.
    """
    report: Dict[str, object] = {
        "path": path, "version": 0, "records": 0, "tombstones": 0,
        "corrupt": 0, "corrupt_offsets": [], "torn_offset": None,
        "valid_end": 0, "quarantine": None, "status": "ok",
    }
    with open(path, "rb") as f:
        data = f.read()
    # byte-flip-on-read fault site (detection drill, not repair drill:
    # the flip lives in this read, not on disk)
    data = faults.corrupt_bytes("data.corrupt.eventlog", data)
    size = len(data)

    if data.startswith(PEL_MAGIC):
        version, off, trailer = 2, len(PEL_MAGIC), 4
    else:
        version, off, trailer = 1, 0, 0
    report["version"] = version
    torn: Optional[int] = None
    while off < size:
        if off + 5 > size:
            torn = off
            break
        rec_len = _U32.unpack_from(data, off)[0]
        kind = data[off + 4]
        plen = rec_len - 1
        if rec_len < 1 or off + 5 + plen + trailer > size:
            # implausible length or frame runs past EOF — cannot
            # resynchronise (no record markers), treat as torn tail
            torn = off
            break
        payload = data[off + 5:off + 5 + plen]
        bad = False
        if version == 2:
            stored = _U32.unpack_from(data, off + 5 + plen)[0]
            bad = crc32c(data[off:off + 5 + plen]) != stored
        if not bad:
            bad = not _payload_ok(kind, payload)
        if bad:
            report["corrupt"] += 1  # type: ignore[operator]
            offsets = report["corrupt_offsets"]
            if len(offsets) < 32:  # type: ignore[arg-type]
                offsets.append(off)  # type: ignore[union-attr]
        else:
            report["records"] += 1  # type: ignore[operator]
            if kind == 1:
                report["tombstones"] += 1  # type: ignore[operator]
        off += 5 + plen + trailer
    report["valid_end"] = torn if torn is not None else off

    if torn is not None:
        report["torn_offset"] = torn
        report["status"] = "torn"
        if repair:
            side = f"{path}.quarantine-{torn}"
            with open(side, "wb") as qf:
                qf.write(data[torn:])
                qf.flush()
                os.fsync(qf.fileno())
            with open(path, "r+b") as lf:
                lf.truncate(torn)
                lf.flush()
                os.fsync(lf.fileno())
            fsync_dir(os.path.dirname(os.path.abspath(path)))
            report["quarantine"] = side
            report["status"] = "repaired"
    elif report["corrupt"]:
        report["status"] = "corrupt"
    return report


def check_segment_dir(dir_path: str,
                      repair: bool = False) -> List[Dict[str, object]]:
    """Audit one ``.peld`` segment directory against its manifest.

    Sealed segments are immutable, so the rules differ from the active
    log: a torn tail here is CORRUPTION (never quarantined — only the
    active segment may legitimately tear in a crash); the manifest's
    sha256 must match the file when present (``None`` = not yet
    finalized → ``unchecksummed``); compaction sidecars must match
    their recorded digest. Cold segments whose frame file has shipped
    are reported as ``cold`` and content-checked on fetch instead.
    Under ``repair`` a bad compaction sidecar or live-id filter is
    deleted (both are caches; the raw frames remain authoritative) —
    frame-file corruption is report-only.
    """
    reports: List[Dict[str, object]] = []
    man_path = os.path.join(dir_path, "segments.json")
    try:
        with open(man_path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [{"path": man_path, "artifact": "segment",
                 "status": "corrupt", "detail": f"unreadable manifest: {e}"}]
    if doc.get("schema") != 1:
        return [{"path": man_path, "artifact": "segment",
                 "status": "corrupt",
                 "detail": f"unknown manifest schema {doc.get('schema')!r}"}]
    for d in doc.get("segments", []):
        path = os.path.join(dir_path, str(d.get("file")))
        r: Dict[str, object] = {
            "path": path, "artifact": "segment",
            "segment_id": d.get("id"), "state": d.get("state"),
            "records": d.get("records"), "status": "ok",
        }
        reports.append(r)
        if not os.path.exists(path):
            if d.get("state") == "cold":
                # frame file shipped to the cold tier; its digest is
                # enforced on fetch (ensure_local refuses mismatches)
                r["status"] = "cold"
            else:
                r["status"] = "corrupt"
                r["detail"] = "segment file missing"
        else:
            s = scan_pel(path, repair=False)
            r["version"] = s["version"]
            r["records"] = s["records"]
            r["corrupt_records"] = s["corrupt"]
            if s["torn_offset"] is not None:
                r["status"] = "corrupt"
                r["detail"] = (f"torn tail at {s['torn_offset']} in a "
                               "sealed (immutable) segment")
            elif s["corrupt"]:
                r["status"] = "corrupt"
            elif d.get("sha256"):
                with open(path, "rb") as f:
                    data = f.read()
                data = faults.corrupt_bytes("data.corrupt.segment", data)
                if hashlib.sha256(data).hexdigest() != d["sha256"]:
                    r["status"] = "corrupt"
                    r["detail"] = "content digest mismatch vs manifest"
            else:
                r["status"] = "unchecksummed"  # sealed, not yet finalized
        cols = d.get("cols")
        if cols and r["status"] in ("ok", "unchecksummed", "cold"):
            cp = os.path.join(dir_path, str(cols.get("file")))
            if not os.path.exists(cp):
                # the sidecar is a cache — scans fall back to frames
                r["cols_status"] = "missing"
            else:
                with open(cp, "rb") as f:
                    cdata = f.read()
                cdata = faults.corrupt_bytes("data.corrupt.segment", cdata)
                if hashlib.sha256(cdata).hexdigest() != cols.get("sha256"):
                    if repair:
                        try:
                            os.unlink(cp)
                        except OSError:
                            pass
                        fsync_dir(dir_path)
                        r["cols_status"] = "repaired"
                        r["status"] = "repaired"
                    else:
                        r["cols_status"] = "corrupt"
                        r["status"] = "corrupt"
                        r["detail"] = "compaction sidecar digest mismatch"
                else:
                    r["cols_status"] = "ok"
        idf = d.get("idf")
        if idf and r["status"] in ("ok", "unchecksummed", "cold"):
            # the live-id filter is a cache like the compaction
            # sidecar: the tombstone path falls back to fetching the
            # frames when it is missing, so repair may delete it
            ip = os.path.join(dir_path, str(idf.get("file")))
            if not os.path.exists(ip):
                r["idf_status"] = "missing"
            else:
                with open(ip, "rb") as f:
                    fdata = f.read()
                if hashlib.sha256(fdata).hexdigest() != idf.get("sha256"):
                    if repair:
                        try:
                            os.unlink(ip)
                        except OSError:
                            pass
                        fsync_dir(dir_path)
                        r["idf_status"] = "repaired"
                        r["status"] = "repaired"
                    else:
                        r["idf_status"] = "corrupt"
                        r["status"] = "corrupt"
                        r["detail"] = "id-filter digest mismatch"
                else:
                    r["idf_status"] = "ok"
    return reports


def check_snapshot(path: str, repair: bool = False) -> Dict[str, object]:
    """Verify one snapshot pair (``snap_<key>.cols`` + manifest)
    against its manifest digests. Uses
    ``data/snapshot.load_snapshot``'s own validation (same digest walk
    the training read runs), so fsck can never pass what a train would
    reject. Under ``repair`` a bad pair is deleted — it is a cache.

    A ``snap_<key>.npz`` is what a schema-2 tree left behind: ``stale``
    (no train reads it; the next one of that key rebuilds the snapshot
    and removes it), never damage. Under ``repair`` it is removed, with
    its manifest where that is still the older schema's."""
    from predictionio_tpu.data import snapshot as snap

    report: Dict[str, object] = {"path": path, "status": "ok"}
    directory = os.path.dirname(path)
    fingerprint, ext = os.path.splitext(
        os.path.basename(path)[len("snap_"):])
    man_path = os.path.join(directory, f"snap_{fingerprint}.json")
    try:
        with open(man_path, "r", encoding="utf-8") as f:
            manifest = json.load(f)
    except FileNotFoundError:
        manifest = None
    except (OSError, ValueError):
        manifest = {}
    doomed = [path, man_path]
    if ext == ".npz":
        report["status"] = "stale"
        report["detail"] = (f"schema-{snap.SCHEMA_VERSION - 1} snapshot "
                            "of an older tree")
        if (manifest or {}).get("schema") == snap.SCHEMA_VERSION:
            doomed = [path]     # the manifest is the rebuilt pair's
    elif manifest is None:
        report["status"] = "corrupt"
        report["detail"] = "manifest missing"
    elif not isinstance(manifest.get("digests"), dict):
        report["status"] = "unchecksummed"
    elif snap.load_snapshot(directory, fingerprint) is None:
        report["status"] = "corrupt"
    if repair and report["status"] != "ok":
        for p in doomed:
            try:
                os.unlink(p)
            except OSError:
                pass
        fsync_dir(directory)
        if report["status"] == "stale":
            report["detail"] = f"{report['detail']}; removed"
        else:
            report["status"] = "repaired"
    return report


def check_model(blob_path: str) -> Dict[str, object]:
    """Verify one model blob against its digest sidecar (report-only:
    a model is not rebuildable here)."""
    report: Dict[str, object] = {"path": blob_path, "status": "ok"}
    try:
        with open(blob_path, "rb") as f:
            blob = f.read()
    except OSError as e:
        report["status"] = "corrupt"
        report["detail"] = str(e)
        return report
    blob = faults.corrupt_bytes("data.corrupt.model", blob)
    try:
        with open(blob_path + DIGEST_SUFFIX, "r", encoding="ascii") as f:
            expected = f.read().strip()
    except OSError:
        report["status"] = "unchecksummed"
        return report
    if hashlib.sha256(blob).hexdigest() != expected:
        report["status"] = "corrupt"
    return report


def check_ann_index(blob_path: str) -> Dict[str, object]:
    """Verify one ANN retrieval index blob (``ann_index.bin``,
    predictionio_tpu/ann) against its sha256 sidecar AND its internal
    header payload digest (the blob is self-verifying, so an index
    embedded without a sidecar still gets a real verdict). Report-only:
    an index is rebuilt by re-running ``pio train``, not by fsck."""
    report: Dict[str, object] = {"path": blob_path, "status": "ok"}
    try:
        with open(blob_path, "rb") as f:
            blob = f.read()
    except OSError as e:
        report["status"] = "corrupt"
        report["detail"] = str(e)
        return report
    sidecar = None
    try:
        with open(blob_path + DIGEST_SUFFIX, "r", encoding="ascii") as f:
            sidecar = f.read().strip()
    except OSError:
        pass
    if sidecar is not None and hashlib.sha256(blob).hexdigest() != sidecar:
        report["status"] = "corrupt"
        report["detail"] = "blob digest mismatch vs sidecar"
        return report
    from predictionio_tpu.ann.index import PQIndex

    try:
        PQIndex.from_bytes(blob)
    except Exception as e:
        report["status"] = "corrupt"
        report["detail"] = f"index blob failed verification: {e}"
        return report
    if sidecar is None:
        report["status"] = "unchecksummed"
    return report


def check_model_registry(root: str,
                         repair: bool = False) -> List[Dict[str, object]]:
    """Audit the generation-aware model registry (``model_registry/``).

    Checks, per manifest generation: the blob dir + ``model.bin``
    exist, the sha256 sidecar exists and agrees with the manifest, and
    the blob content matches the recorded digest. Also surfaces
    **orphaned** ``gen-*`` dirs — dirs with no manifest entry, the
    signature of a trainer crash between blob write and manifest commit
    (the write order is deliberate: an orphan is harmless; a manifest
    entry pointing at nothing would not be).

    Repair policy: orphaned dirs are deleted (the crashed cycle never
    published, the next delta train re-registers); a missing or
    mismatched *sidecar* over an intact blob is rewritten from the
    manifest digest (the manifest is authoritative); blob corruption is
    report-only — like ``check_model``, a generation blob is not
    rebuildable here.
    """
    import shutil as _shutil

    from predictionio_tpu.storage.models import ModelRegistry

    reports: List[Dict[str, object]] = []
    man_path = os.path.join(root, ModelRegistry.MANIFEST)
    try:
        with open(man_path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except FileNotFoundError:
        return reports  # no registry at this home: nothing to audit
    except (OSError, ValueError) as e:
        return [{"path": man_path, "artifact": "model_registry",
                 "status": "corrupt", "detail": f"unreadable manifest: {e}"}]
    if doc.get("schema") != 1:
        return [{"path": man_path, "artifact": "model_registry",
                 "status": "corrupt",
                 "detail": f"unknown manifest schema {doc.get('schema')!r}"}]
    champ = doc.get("champion")
    if champ is not None and not any(
            e.get("gen") == champ for e in doc.get("generations", [])):
        reports.append({
            "path": man_path, "artifact": "model_registry",
            "status": "corrupt",
            "detail": f"champion generation {champ} has no manifest entry"})
    known = set()
    for entry in doc.get("generations", []):
        gen = entry.get("gen")
        known.add(gen)
        d = os.path.join(root, f"gen-{int(gen):06d}")
        blob_path = os.path.join(d, "model.bin")
        r: Dict[str, object] = {
            "path": blob_path, "artifact": "model_registry",
            "generation": gen, "gen_status": entry.get("status"),
            "status": "ok",
        }
        reports.append(r)
        try:
            with open(blob_path, "rb") as f:
                blob = f.read()
        except OSError as e:
            r["status"] = "corrupt"
            r["detail"] = f"generation blob missing: {e}"
            continue
        blob = faults.corrupt_bytes("data.corrupt.model", blob)
        expected = entry.get("sha256")
        if not expected:
            r["status"] = "unchecksummed"
            continue
        if hashlib.sha256(blob).hexdigest() != expected:
            r["status"] = "corrupt"
            r["detail"] = "blob digest mismatch vs manifest"
            continue
        side = blob_path + DIGEST_SUFFIX
        side_ok = False
        try:
            with open(side, "r", encoding="ascii") as f:
                side_ok = f.read().strip() == expected
        except OSError:
            pass
        if not side_ok:
            if repair:
                with open(side, "w", encoding="ascii") as f:
                    f.write(expected)
                    f.flush()
                    os.fsync(f.fileno())
                fsync_dir(d)
                r["status"] = "repaired"
                r["detail"] = "sidecar rewritten from manifest digest"
            else:
                r["status"] = "corrupt"
                r["detail"] = "sha256 sidecar missing or mismatched"
    gen_dir_re = ModelRegistry._GEN_DIR
    for name in sorted(os.listdir(root)):
        m = gen_dir_re.match(name)
        if not m or int(m.group(1)) in known:
            continue
        p = os.path.join(root, name)
        r = {"path": p, "artifact": "model_registry",
             "status": "corrupt", "detail": "orphaned generation dir "
             "(no manifest entry; crash between blob write and commit)"}
        if repair:
            _shutil.rmtree(p, ignore_errors=True)
            fsync_dir(root)
            r["status"] = "repaired"
            r["detail"] = "orphaned generation dir deleted"
        reports.append(r)
    return reports


def check_replica_state(home: str) -> Optional[Dict[str, object]]:
    """Follower cursor doc (``<home>/replica_state.json``, written by
    data/replication.py): must be well-formed JSON, and no cursor may
    claim more replicated bytes than the active file actually holds —
    an offset past EOF means the follower acked bytes it does not
    have, which is a replication bug, not a crash artifact. Absent
    file = not a follower = no-op (returns ``None``)."""
    path = os.path.join(home, "replica_state.json")
    if not os.path.exists(path):
        return None
    report: Dict[str, object] = {
        "path": path, "artifact": "replica", "status": "ok",
        "errors": [],
    }
    errors: List[str] = report["errors"]  # type: ignore[assignment]
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        cursors = doc.get("cursors")
        if cursors is None:
            cursors = {}
        if not isinstance(cursors, dict):
            raise ValueError(f"cursors is {type(cursors).__name__}")
    except (OSError, ValueError, AttributeError) as e:
        report["status"] = "corrupt"
        errors.append(f"unreadable replica state: {e}")
        return report
    for tag in sorted(cursors):
        cur = cursors[tag]
        try:
            offset = int(cur.get("offset", 0))
        except (AttributeError, TypeError, ValueError):
            report["status"] = "corrupt"
            errors.append(f"{tag}: malformed cursor {cur!r}")
            continue
        active = os.path.join(home, "eventlog", f"{tag}.pel")
        size = os.path.getsize(active) if os.path.exists(active) else 0
        if offset > size:
            report["status"] = "corrupt"
            errors.append(f"{tag}: cursor at byte {offset} but the "
                          f"active file holds {size}")
    return report


def fsck_home(home: str, repair: bool = False) -> Dict[str, object]:
    """Scan every persisted artifact under one storage home.

    Covers ``<home>/eventlog/*.pel`` (record walk), the snapshot cache
    (``PIO_SCAN_CACHE_DIR`` or ``<home>/scan_cache``),
    ``<home>/models/*/model.bin``, and the continuous-training model
    registry (``<home>/model_registry``: manifest ↔ dirs ↔ sidecars,
    orphaned candidate dirs). Also lists quarantine sidecars left
    by previous recoveries so the runbook's "inspect, then delete"
    step has an inventory to work from.
    """
    artifacts: List[Dict[str, object]] = []
    quarantines: List[str] = []

    rep_state = check_replica_state(home)
    if rep_state is not None:
        artifacts.append(rep_state)

    log_dir = os.path.join(home, "eventlog")
    if os.path.isdir(log_dir):
        for name in sorted(os.listdir(log_dir)):
            p = os.path.join(log_dir, name)
            if name.endswith(".pel"):
                # the ACTIVE segment: the one place a torn tail is a
                # legitimate crash artifact, so repair may quarantine
                r = scan_pel(p, repair=repair)
                r["artifact"] = "eventlog"
                artifacts.append(r)
            elif name.endswith(".peld") and os.path.isdir(p):
                artifacts.extend(check_segment_dir(p, repair=repair))
            elif ".quarantine-" in name:
                quarantines.append(p)

    snap_dir = os.environ.get("PIO_SCAN_CACHE_DIR") or os.path.join(
        home, "scan_cache")
    if os.path.isdir(snap_dir):
        for name in sorted(os.listdir(snap_dir)):
            if name.startswith("snap_") and name.endswith((".cols",
                                                            ".npz")):
                r = check_snapshot(os.path.join(snap_dir, name),
                                   repair=repair)
                r["artifact"] = "snapshot"
                artifacts.append(r)

    model_dir = os.path.join(home, "models")
    if os.path.isdir(model_dir):
        for inst in sorted(os.listdir(model_dir)):
            inst_dir = os.path.join(model_dir, inst)
            p = os.path.join(inst_dir, "model.bin")
            if os.path.exists(p):
                r = check_model(p)
                r["artifact"] = "model"
                r["instance"] = inst
                artifacts.append(r)
            # per-algorithm ANN index blobs beside the model blob
            # (<inst>/<algo>/ann_index.bin — predictionio_tpu/ann)
            if os.path.isdir(inst_dir):
                for algo in sorted(os.listdir(inst_dir)):
                    ip = os.path.join(inst_dir, algo, "ann_index.bin")
                    if os.path.exists(ip):
                        r = check_ann_index(ip)
                        r["artifact"] = "ann_index"
                        r["instance"] = inst
                        artifacts.append(r)

    reg_dir = os.path.join(home, "model_registry")
    if os.path.isdir(reg_dir):
        artifacts.extend(check_model_registry(reg_dir, repair=repair))

    statuses = [a["status"] for a in artifacts]
    report = {
        "home": home,
        "artifacts": artifacts,
        "quarantines": quarantines,
        "checked": len(artifacts),
        "clean": statuses.count("ok"),
        "corrupt": sum(1 for s in statuses if s in ("corrupt", "torn")),
        "repaired": statuses.count("repaired"),
        "unchecksummed": statuses.count("unchecksummed"),
        "cold": statuses.count("cold"),
        "stale": statuses.count("stale"),
    }
    return report
