"""End-to-end data integrity: eventlog record checksums + quarantine,
artifact digests, fault-injected bit rot, crash consistency, `pio fsck`."""

import datetime as dt
import json
import os
import signal
import struct
import subprocess
import sys
import time

import numpy as np
import pytest

from predictionio_tpu.data.event import Event
from predictionio_tpu.data.pel_integrity import (
    PEL_MAGIC,
    crc32c,
    fsck_home,
    scan_pel,
)
from predictionio_tpu.utils import faults
from predictionio_tpu.utils.atomic_write import (
    atomic_file,
    atomic_write_bytes,
    atomic_write_text,
)
from predictionio_tpu.utils.integrity import (
    INTEGRITY_FAILED,
    INTEGRITY_VERIFIED,
    IntegrityError,
)

APP = 1
_T = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)


def _events(n, start=0):
    return [Event(event="rate", entity_type="user", entity_id=str(start + i),
                  target_entity_type="item", target_entity_id=str(i % 3),
                  properties={"rating": float(i % 5)}, event_time=_T)
            for i in range(n)]


def _store(directory):
    from predictionio_tpu.data.filestore import NativeEventLogStore

    try:
        return NativeEventLogStore(str(directory))
    except RuntimeError as e:  # no g++ in this environment
        pytest.skip(str(e))


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.FAULTS.disarm()


def _counter(counter, artifact):
    return counter._values.get((artifact,), 0.0)


# -- CRC32C parity -------------------------------------------------------------


def test_crc32c_check_vector():
    # the canonical CRC-32C check value — proves polynomial, reflection,
    # and xorout all match the C++ table
    assert crc32c(b"123456789") == 0xE3069283


def test_python_scan_agrees_with_cpp_writer(tmp_path):
    st = _store(tmp_path / "log")
    st.insert_batch(_events(40), APP)
    st.close()
    rep = scan_pel(str(tmp_path / "log" / "events_1.pel"))
    assert rep["version"] == 2
    assert rep["records"] == 40
    assert rep["corrupt"] == 0
    assert rep["torn_offset"] is None


# -- v2 format + v1 compatibility ---------------------------------------------


def test_v2_file_has_header_and_round_trips(tmp_path):
    st = _store(tmp_path / "log")
    ids = st.insert_batch(_events(5), APP)
    st.close()
    path = tmp_path / "log" / "events_1.pel"
    assert path.read_bytes().startswith(PEL_MAGIC)
    s2 = _store(tmp_path / "log")
    assert [e.event_id for e in s2.find(APP)] == ids
    s2.close()


def test_v1_log_opens_under_v2_code(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_EVENTLOG_FORMAT", "1")
    st = _store(tmp_path / "log")
    ids = st.insert_batch(_events(5), APP)
    st.close()
    path = tmp_path / "log" / "events_1.pel"
    assert not path.read_bytes().startswith(PEL_MAGIC)
    assert scan_pel(str(path))["version"] == 1

    # default (v2-writing) code reads and appends the legacy file; the
    # on-disk format stays v1 — no mixed framing within one file
    monkeypatch.delenv("PIO_EVENTLOG_FORMAT")
    s2 = _store(tmp_path / "log")
    assert [e.event_id for e in s2.find(APP)] == ids
    more = s2.insert_batch(_events(3, start=100), APP)
    assert [e.event_id for e in s2.find(APP)] == ids + more
    s2.close()
    rep = scan_pel(str(path))
    assert rep["version"] == 1 and rep["records"] == 8


# -- corruption detection ------------------------------------------------------


def test_flipped_byte_record_never_served(tmp_path):
    st = _store(tmp_path / "log")
    ids = st.insert_batch(_events(10), APP)
    st.close()
    path = tmp_path / "log" / "events_1.pel"
    # flip one payload byte of the FIRST record: [u32 len][u8 kind] at
    # offset 8 (after the magic), payload starts at 13
    raw = bytearray(path.read_bytes())
    raw[20] ^= 0xFF
    path.write_bytes(bytes(raw))

    rep = scan_pel(str(path))
    assert rep["corrupt"] == 1 and rep["records"] == 9

    before = _counter(INTEGRITY_FAILED, "eventlog")
    s2 = _store(tmp_path / "log")
    got = [e.event_id for e in s2.find(APP)]
    assert got == ids[1:]  # the damaged record is dropped, not served
    assert s2.get(ids[0], APP) is None
    s2.close()
    assert _counter(INTEGRITY_FAILED, "eventlog") == before + 1


@pytest.mark.parametrize("fmt", ["1", "2"])
def test_torn_tail_quarantined_zero_record_loss(tmp_path, monkeypatch, fmt):
    monkeypatch.setenv("PIO_EVENTLOG_FORMAT", fmt)
    st = _store(tmp_path / "log")
    ids = st.insert_batch(_events(10), APP)
    st.close()
    path = tmp_path / "log" / "events_1.pel"
    raw = path.read_bytes()
    cut = len(raw) - 3  # mid-record: an interrupted append
    with open(path, "r+b") as f:
        f.truncate(cut)

    s2 = _store(tmp_path / "log")  # open runs recovery
    assert [e.event_id for e in s2.find(APP)] == ids[:9]
    s2.close()

    # every complete record survived; the torn bytes are preserved in
    # the sidecar, byte-for-byte, before the truncation
    rep = scan_pel(str(path))
    assert rep["records"] == 9 and rep["torn_offset"] is None
    sidecars = [p for p in os.listdir(tmp_path / "log")
                if ".quarantine-" in p]
    assert len(sidecars) == 1
    torn_off = int(sidecars[0].rsplit("-", 1)[1])
    side = (tmp_path / "log" / sidecars[0]).read_bytes()
    assert side == raw[torn_off:cut]


# -- crash consistency (SIGKILL) ----------------------------------------------


def _run_to_kill(tmp_path, code, ready_probe, timeout=30.0):
    """Start a writer subprocess, wait until ``ready_probe()`` says it
    made durable progress, SIGKILL it mid-write."""
    proc = subprocess.Popen(
        [sys.executable, "-c", code],
        cwd=str(tmp_path),
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.path.dirname(os.path.dirname(
                 os.path.abspath(__file__)))},
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)
    deadline = time.monotonic() + timeout
    try:
        while not ready_probe():
            if proc.poll() is not None:
                raise AssertionError(
                    "writer died early: " + proc.stderr.read().decode())
            if time.monotonic() > deadline:
                raise AssertionError("writer made no progress")
            time.sleep(0.02)
    finally:
        try:
            proc.send_signal(signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()


def test_sigkill_mid_append_recovers(tmp_path):
    _store(tmp_path / "probe").close()  # skip early when no g++
    log_dir = tmp_path / "log"
    code = """
import datetime as dt
from predictionio_tpu.data.filestore import NativeEventLogStore
from predictionio_tpu.data.event import Event
st = NativeEventLogStore("log")
t = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
i = 0
while True:
    st.insert_batch([Event(event="e", entity_type="u", entity_id=str(i + k),
                           properties={"p": "x" * 64}, event_time=t)
                     for k in range(50)], 1)
    i += 50
"""
    pel = log_dir / "events_1.pel"

    def progressed():
        return pel.exists() and pel.stat().st_size > 65536

    _run_to_kill(tmp_path, code, progressed)

    # reopen: recovery must yield a servable log — every record either
    # fully present or quarantined, never a crash or a half-parsed event
    s2 = _store(log_dir)
    events = list(s2.find(APP))
    assert len(events) > 0
    assert all(e.properties == {"p": "x" * 64} for e in events)
    s2.close()
    rep = scan_pel(str(pel))
    assert rep["corrupt"] == 0 and rep["torn_offset"] is None


def test_sigkill_mid_snapshot_never_yields_garbage(tmp_path, monkeypatch):
    snap_dir = tmp_path / "snaps"
    snap_dir.mkdir()
    code = """
import numpy as np
from predictionio_tpu.data.pipeline import ColumnarEvents
from predictionio_tpu.data.snapshot import save_snapshot
n = 50000
cols = ColumnarEvents(
    entity_idx=np.zeros(n, np.uint32), target_idx=np.zeros(n, np.uint32),
    name_idx=np.zeros(n, np.uint16), values=np.ones(n),
    times_us=np.arange(n, dtype=np.int64),
    entity_ids=["u"], target_ids=["i"], names=["rate"])
i = 0
while True:
    save_snapshot("snaps", "deadbeef", cols, i, n)
    i += 1
"""

    def progressed():
        return (snap_dir / "snap_deadbeef.json").exists()

    _run_to_kill(tmp_path, code, progressed)

    from predictionio_tpu.data.snapshot import load_snapshot

    # whatever instant the kill hit: load either validates fully or
    # reports a cold cache — never an exception, never partial arrays
    got = load_snapshot(str(snap_dir), "deadbeef")
    if got is not None:
        cols, man = got
        assert cols.n == man.n_rows == 50000


# -- snapshot digest verification ---------------------------------------------


def _make_cols(n=32):
    from predictionio_tpu.data.pipeline import ColumnarEvents

    return ColumnarEvents(
        entity_idx=np.arange(n, dtype=np.uint32) % 4,
        target_idx=np.arange(n, dtype=np.uint32) % 3,
        name_idx=np.zeros(n, np.uint16),
        values=np.linspace(0, 1, n),
        times_us=np.arange(n, dtype=np.int64),
        entity_ids=["u0", "u1", "u2", "u3"],
        target_ids=["i0", "i1", "i2"], names=["rate"])


def test_snapshot_bit_rot_is_counted_cache_miss(tmp_path):
    from predictionio_tpu.data.snapshot import load_snapshot, save_snapshot

    d = str(tmp_path)
    assert save_snapshot(d, "fp", _make_cols(), 100, 32)
    ok = load_snapshot(d, "fp")
    assert ok is not None and ok[0].n == 32

    before = _counter(INTEGRITY_FAILED, "snapshot")
    faults.FAULTS.arm("data.corrupt.snapshot")
    assert load_snapshot(d, "fp") is None  # rebuild, never wrong data
    assert _counter(INTEGRITY_FAILED, "snapshot") == before + 1
    faults.FAULTS.disarm()
    assert load_snapshot(d, "fp") is not None  # disk was never damaged


def test_snapshot_manifest_digest_tamper(tmp_path):
    from predictionio_tpu.data.snapshot import load_snapshot, save_snapshot

    d = str(tmp_path)
    assert save_snapshot(d, "fp", _make_cols(), 100, 32)
    man = tmp_path / "snap_fp.json"
    doc = json.loads(man.read_text())
    doc["digests"]["values"] = "0" * 64
    man.write_text(json.dumps(doc))
    assert load_snapshot(d, "fp") is None


# -- model digest sidecars -----------------------------------------------------


def test_model_blob_verified_and_corrupt_refused(tmp_path):
    from predictionio_tpu.storage.models import LocalFSModelStore

    ms = LocalFSModelStore(str(tmp_path))
    blob = os.urandom(4096)
    before = _counter(INTEGRITY_VERIFIED, "model")
    ms.put("inst1", blob)
    assert ms.get("inst1") == blob
    assert _counter(INTEGRITY_VERIFIED, "model") == before + 1

    faults.FAULTS.arm("data.corrupt.model")
    with pytest.raises(IntegrityError):
        ms.get("inst1")  # a corrupt candidate model is REFUSED
    faults.FAULTS.disarm()
    assert ms.get("inst1") == blob


def test_model_without_sidecar_is_legacy_accepted(tmp_path):
    from predictionio_tpu.storage.models import LocalFSModelStore

    ms = LocalFSModelStore(str(tmp_path))
    ms.put("inst1", b"old blob")
    os.unlink(tmp_path / "inst1" / "model.bin.sha256")
    assert ms.get("inst1") == b"old blob"  # pre-integrity data still loads
    home = tmp_path / "home"
    (home / "models").mkdir(parents=True)
    (home / "models" / "inst1").mkdir()
    (home / "models" / "inst1" / "model.bin").write_bytes(b"old blob")
    rep = fsck_home(str(home))
    assert rep["unchecksummed"] == 1 and rep["corrupt"] == 0


# -- durable atomic writes -----------------------------------------------------


def test_atomic_write_helpers(tmp_path):
    p = tmp_path / "f.bin"
    atomic_write_bytes(str(p), b"abc")
    assert p.read_bytes() == b"abc"
    atomic_write_text(str(p), "hello")
    assert p.read_text() == "hello"


def test_atomic_file_failure_leaves_old_content(tmp_path):
    p = tmp_path / "f.txt"
    p.write_text("old")
    with pytest.raises(RuntimeError):
        with atomic_file(str(p), "w", encoding="utf-8") as f:
            f.write("new half-writ")
            raise RuntimeError("simulated crash before replace")
    assert p.read_text() == "old"
    assert [x for x in os.listdir(tmp_path) if x.startswith(".atomic-")] == []


# -- fault injection contract --------------------------------------------------


def test_corrupt_bytes_disarmed_is_identity():
    data = b"payload"
    assert faults.corrupt_bytes("data.corrupt.model", data) is data


def test_corrupt_bytes_flips_exactly_one_middle_byte():
    faults.FAULTS.arm("data.corrupt.model")
    data = bytes(range(10))
    out = faults.corrupt_bytes("data.corrupt.model", data)
    assert out != data and len(out) == len(data)
    assert [i for i in range(10) if out[i] != data[i]] == [5]
    faults.FAULTS.disarm()


# -- pio fsck ------------------------------------------------------------------


def _fsck_cli(home, *extra):
    from predictionio_tpu.tools.cli import main

    try:
        main(["fsck", "--home", str(home), "--json", *extra])
    except SystemExit as e:
        return int(e.code or 0)
    return 0


def test_fsck_cli_clean_corrupt_repair_cycle(tmp_path, monkeypatch, capsys):
    monkeypatch.delenv("PIO_SCAN_CACHE_DIR", raising=False)
    home = tmp_path / "home"
    st = _store(home / "eventlog")
    st.insert_batch(_events(20), APP)
    st.close()

    assert _fsck_cli(home) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["checked"] == 1 and doc["clean"] == 1

    # tear the tail: fsck reports (exit 2), --repair quarantines (exit
    # 3), the rerun is clean again (exit 0) with the sidecar listed
    pel = home / "eventlog" / "events_1.pel"
    with open(pel, "r+b") as f:
        f.truncate(pel.stat().st_size - 3)
    assert _fsck_cli(home) == 2
    capsys.readouterr()
    assert _fsck_cli(home, "--repair") == 3
    doc = json.loads(capsys.readouterr().out)
    assert doc["repaired"] == 1
    assert _fsck_cli(home) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["clean"] == 1 and len(doc["quarantines"]) == 1


def test_fsck_detects_corruption_via_fault_site(tmp_path, monkeypatch):
    monkeypatch.delenv("PIO_SCAN_CACHE_DIR", raising=False)
    home = tmp_path / "home"
    st = _store(home / "eventlog")
    st.insert_batch(_events(10), APP)
    st.close()
    assert fsck_home(str(home))["corrupt"] == 0
    # the same scan through a byte-flipping read reports corruption —
    # the detection drill the runbook rehearses without real bit rot
    faults.FAULTS.arm("data.corrupt.eventlog")
    assert fsck_home(str(home))["corrupt"] == 1


def test_fsck_checks_the_column_file_and_names_a_flipped_byte(
        tmp_path, monkeypatch):
    from predictionio_tpu.data.snapshot import save_snapshot

    monkeypatch.delenv("PIO_SCAN_CACHE_DIR", raising=False)
    home = tmp_path / "home"
    d = home / "scan_cache"
    d.mkdir(parents=True)
    assert save_snapshot(str(d), "fp", _make_cols(), 100, 32)
    assert save_snapshot(str(d), "other", _make_cols(64), 100, 64)
    rep = fsck_home(str(home))
    assert (rep["checked"], rep["clean"], rep["corrupt"]) == (2, 2, 0)
    assert {os.path.basename(a["path"]): a["status"]
            for a in rep["artifacts"]} == {"snap_fp.cols": "ok",
                                           "snap_other.cols": "ok"}
    cols = d / "snap_fp.cols"
    raw = bytearray(cols.read_bytes())
    raw[-1] ^= 0x01                       # the last table's last byte
    cols.write_bytes(bytes(raw))
    rep = fsck_home(str(home))
    assert (rep["clean"], rep["corrupt"]) == (1, 1)
    (bad,) = [a for a in rep["artifacts"] if a["status"] == "corrupt"]
    assert bad["artifact"] == "snapshot" and bad["path"] == str(cols)


@pytest.mark.parametrize("rebuilt", [False, True])
def test_fsck_reports_a_schema2_npz_as_stale_not_damage(
        tmp_path, monkeypatch, rebuilt):
    """An ``.npz`` an older tree left is no train's input: ``stale``,
    exit clean; ``--repair`` removes it — and its manifest only while
    that is still the older schema's."""
    from predictionio_tpu.data.snapshot import save_snapshot

    monkeypatch.delenv("PIO_SCAN_CACHE_DIR", raising=False)
    home = tmp_path / "home"
    d = home / "scan_cache"
    d.mkdir(parents=True)
    if rebuilt:     # a v3 pair of the same key, and the npz not yet gone
        assert save_snapshot(str(d), "fp", _make_cols(), 100, 32)
    else:
        (d / "snap_fp.json").write_text(json.dumps(
            {"schema": 2, "filter": "fp", "digests": {}}))
    (d / "snap_fp.npz").write_bytes(b"PK\x03\x04 whatever it held")
    rep = fsck_home(str(home))
    assert rep["stale"] == 1 and rep["corrupt"] == 0
    assert rep["clean"] == (1 if rebuilt else 0)
    rep = fsck_home(str(home), repair=True)
    assert rep["stale"] == 1 and rep["repaired"] == 0
    assert not (d / "snap_fp.npz").exists()
    assert (d / "snap_fp.json").exists() == rebuilt
    rep = fsck_home(str(home))
    assert rep["stale"] == 0 and rep["checked"] == (1 if rebuilt else 0)


def test_fsck_repairs_corrupt_snapshot_by_deletion(tmp_path, monkeypatch):
    from predictionio_tpu.data.snapshot import save_snapshot

    monkeypatch.delenv("PIO_SCAN_CACHE_DIR", raising=False)
    home = tmp_path / "home"
    d = home / "scan_cache"
    d.mkdir(parents=True)
    assert save_snapshot(str(d), "fp", _make_cols(), 100, 32)
    npz = d / "snap_fp.cols"
    raw = bytearray(npz.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    npz.write_bytes(bytes(raw))
    rep = fsck_home(str(home))
    assert rep["corrupt"] == 1
    rep = fsck_home(str(home), repair=True)
    assert rep["repaired"] == 1
    assert not npz.exists()  # it is a cache: deleted, rebuilt next train
    assert fsck_home(str(home))["checked"] == 0
