"""chip_smoke.py's contract, as far as a CPU can show it.

On a platform other than a TPU the script still runs every phase (the
rehearsal) and then FAILS: non-zero exit and no result. The success
line's shape is checked by stubbing the platform HERE, never through an
option of the script.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(*argv, devices=1):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the child sees the CPU as it is, not conftest's 8 virtual devices
    env.pop("PIO_MESH_PLATFORM", None)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, SCRIPT, *argv], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=600)


def _no_result(proc):
    assert proc.returncode != 0, proc.stdout[-2000:]
    for line in proc.stdout.splitlines():
        assert '"ok": true' not in line and '"ok":true' not in line, line
    assert "verdict: FAILED" in proc.stdout.splitlines()[-1]


@pytest.mark.parametrize("argv", [("--tiny",), ()],
                         ids=["tiny", "no-arguments"])
def test_rehearsal_runs_every_phase_and_fails_off_tpu(argv):
    """As the driver runs it (no arguments) and as a builder rehearses
    it: off a TPU both run every phase at the tiny size, then fail."""
    proc = _run(*argv)
    out = proc.stdout
    assert ("rehearsing at the --tiny size" in out) == (not argv)
    assert "nnz=120,000 users=4,000" in out
    for phase in ("[smoke] device:", "platform is not a TPU",
                  "[smoke] compile cache:", "[smoke] import engine:",
                  "[smoke] data:", "$ pio app new", "$ pio import",
                  "[smoke] import:", "$ pio train", "[smoke] train 1:",
                  "gram=off solve=xla", "[smoke] train 2:",
                  "compiled nothing new", "train RMSE on the",
                  "[smoke] reference:", "[smoke] deploy: /health 200",
                  "AOT warm-up is ready", "[smoke] query:", "queries for known users: 200",
                  "unknown user: 200", "pio_aot_dispatch_total moved",
                  "nothing compiled on the query path",
                  "server stopped (thread alive: False)"):
        assert phase in out, (phase, out[-3000:], proc.stderr[-2000:])
    assert "FAILED:" not in out, out[-3000:]   # no phase failed …
    _no_result(proc)                           # … and still no result
    assert not os.path.exists(os.path.join(REPO, ".chip_smoke_home"))


def test_four_chip_rehearsal_on_virtual_devices():
    """--chips 4 runs only the sharded train and the single-device train
    it is compared with."""
    proc = _run("--tiny", "--chips", "4", devices=4)
    out = proc.stdout
    for phase in ("train sharded x4: ALS train: platform=cpu devices=4",
                  "bytes in use per device:",
                  "train single: ALS train: platform=cpu devices=1",
                  "[smoke] parity: train RMSE", "mean top-10 overlap >="):
        assert phase in out, (phase, out[-3000:], proc.stderr[-2000:])
    assert "[smoke] deploy:" not in out and "FAILED:" not in out
    _no_result(proc)


def test_four_chips_wanted_one_device_fails_instead_of_shrinking():
    proc = _run("--tiny", "--chips", "4", devices=1)
    assert "FAILED: 4 device(s) wanted, jax reports 1" in proc.stdout
    assert "$ pio train" not in proc.stdout
    _no_result(proc)


@pytest.fixture()
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_success_line_has_exactly_the_contract_keys(smoke, monkeypatch,
                                                    capsys):
    monkeypatch.setattr(smoke, "device_report", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert smoke.finish(True, smoke.device_report()) == 0
    last = capsys.readouterr().out.splitlines()[-1]
    doc = json.loads(last)
    assert set(doc) == {"ok", "device"} and doc["ok"] is True
    assert set(doc["device"]) == {"platform", "kind", "count"}
    assert last == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')


@pytest.mark.parametrize("ok,platform", [(False, "tpu"), (True, "cpu")])
def test_no_result_when_a_phase_failed_or_off_tpu(smoke, capsys, ok,
                                                  platform):
    rc = smoke.finish(ok, {"platform": platform, "kind": "x", "count": 1})
    assert rc != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_never_sets_a_platform():
    src = open(SCRIPT).read()
    assert "jax_platforms" not in src and "JAX_PLATFORMS" not in src
