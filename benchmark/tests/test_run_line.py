"""The command end to end at the rehearsal size: exit code, the shape
of the line it would print, and refusal without the program."""

import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def run(args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py")] + args,
        cwd=cwd, env=env, capture_output=True, text=True, timeout=900)


def rehearsal_line(stdout):
    tag = "[bench] rehearsal line: "
    return json.loads(next(ln for ln in stdout.splitlines()
                           if ln.startswith(tag))[len(tag):])


def test_tiny_run_prints_the_line_on_an_earlier_line_and_fails():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for trace in (0, 1):
        p = run(["--workload", "als-ml20m-train", "--seed", "5",
                 "--seconds", "2", "--trace", str(trace), "--tiny"])
        assert p.returncode == 1, p.stdout[-2000:] + p.stderr[-2000:]
        last = p.stdout.strip().splitlines()[-1]
        assert "no result" in last and not last.startswith("{")
        line = rehearsal_line(p.stdout)
        assert set(line) - {"breakdown"} == {
            "correct", "attempted", "failed", "metrics", "device", "checks"}
        assert line["correct"] is True and line["failed"] == 0
        # every comparison, its number beside its limit: the line's LAST
        # key, and the last lines on stderr
        assert list(line)[-1] == "checks" and line["checks"]
        assert all(c.startswith("check ok: ") for c in line["checks"])
        assert [ln[len("[bench] "):] for ln in p.stderr.splitlines()
                if ln.startswith("[bench] check ")] == line["checks"]
        assert p.stderr.strip().splitlines()[-1] == (
            "[bench] " + line["checks"][-1])
        assert {"platform", "kind", "count",
                "memory_peak_bytes"} <= set(line["device"])
        kind = "per_layer" if trace else "end_to_end"
        units = {m["name"]: m["unit"] for m in bench[kind]}
        assert line["metrics"]
        for name, m in line["metrics"].items():
            assert m["unit"] == units[name]
            assert isinstance(m["value"], float)
        if trace:
            assert {"busy_s", "window_s"} <= set(line["device"])
            assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        else:
            assert set(line["metrics"]) == {"train_updates_per_s",
                                            "setup_s"}
            assert line["attempted"] >= 2


def test_full_size_off_a_tpu_gives_no_result():
    p = run(["--workload", "als-ml20m-train", "--seed", "1",
             "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert "rehearsal line" not in p.stdout
    assert not p.stdout.strip().splitlines()[-1].startswith("{")


def test_no_result_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = run(["--workload", "als-ml20m-train", "--seed", "1",
             "--seconds", "1", "--trace", "0", "--tiny"], cwd=str(tmp_path))
    assert p.returncode != 0
    assert "rehearsal line" not in p.stdout
