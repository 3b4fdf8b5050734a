"""The harness's own arithmetic: which metrics a cell reports, and the
peak-memory rule."""

import json
import os
import time

import harness

ROOT = os.path.dirname(harness.BENCH)


def test_cell_finds_its_files_and_metrics_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.Cell(w["name"], 1, 1.0, False, True,
                            time.perf_counter())
        assert cell.config["name"] == w["config"]
        assert cell.traffic["generator"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            harness.load_module("layers", m["name"]).read({})
        # --tiny runs the sample's sizes and thresholds
        assert cell.shape()["n_users"] == cell.config["sample"]["n_users"]
        assert cell.settings()["correct"] == cell.config["sample"]["correct"]


def test_a_metric_with_workloads_is_reported_only_there():
    spec = {"name": "x", "workloads": ["a"]}
    assert harness.metric_applies(spec, "a")
    assert not harness.metric_applies(spec, "b")
    assert harness.metric_applies({"name": "y"}, "b")


def test_program_temporaries_are_read_from_the_compiler():
    import jax
    import jax.numpy as jnp

    seen = harness.ProgramTemporaries()

    @jax.jit
    def f(x):
        return ((x @ x.T) @ x).sum()

    f(jnp.ones((300, 200))).block_until_ready()
    # the (300, 300) product is a temporary of that program
    assert seen.largest >= 300 * 300 * 4
