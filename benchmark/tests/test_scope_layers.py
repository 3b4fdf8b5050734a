"""The readers of the train step's own scopes and of ``model.put``'s
children (PR 36) on hand-made observations: what each sums, and that a
program without the scope or span (the parent) gives None — never an
error — while ``seqrec_unscoped_ms`` reads such a program's ``other``
(``JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_scope_layers.py
-q``)."""

import json
import os

import pytest

import harness
import scope_reduce
import trace_reduce
from test_span_layers import span

#: a traced train of the CHANGE: every scope the step opens, seconds
CHANGE = {"other": 0.25, "seqrec.step": 0.05, "seqrec.stack": 0.5,
          "seqrec.stack.cast": 0.125, "seqrec.norm": 0.25,
          "seqrec.residual": 0.0625, "seqrec.conv": 2.0,
          "seqrec.moe.experts": 1.0}
#: the same train of the PARENT: no new scope, all of it under ``other``
PARENT = {"other": 1.2375, "seqrec.conv": 2.0, "seqrec.moe.experts": 1.0}


def trace(**op_seconds):
    return trace_reduce.TraceSummary(20.0, 15.0, 1, op_seconds=op_seconds)


def read(name, obs):
    return harness.load_module("layers", name).read(obs)


@pytest.mark.parametrize("name,want", [
    ("seqrec_unscoped_ms", 300.0),           # other + seqrec.step
    ("seqrec_stack_ms", 500.0),
    ("seqrec_cast_ms", 125.0),
    ("seqrec_norm_residual_ms", 312.5),      # seqrec.norm + .residual
])
def test_reader_sums_its_scopes(name, want):
    assert read(name, {"scopes": CHANGE}) == pytest.approx(want)


@pytest.mark.parametrize("name", ["seqrec_stack_ms", "seqrec_cast_ms",
                                  "seqrec_norm_residual_ms"])
def test_a_scope_absent_reads_none(name):
    assert read(name, {"scopes": PARENT}) is None
    assert read(name, {"scopes": {}}) is None
    assert read(name, {}) is None


def test_one_of_two_scopes_is_enough():
    assert read("seqrec_norm_residual_ms",
                {"scopes": {"seqrec.norm": 0.25}}) == pytest.approx(250.0)


def test_unscoped_reads_the_parents_other_and_none_only_without_scopes():
    assert read("seqrec_unscoped_ms",
                {"scopes": PARENT}) == pytest.approx(1237.5)
    # ``other`` absent: every operation lay under some scope
    assert read("seqrec_unscoped_ms",
                {"scopes": {"seqrec.conv": 2.0}}) == 0.0
    assert read("seqrec_unscoped_ms", {"scopes": {}}) is None
    assert read("seqrec_unscoped_ms", {}) is None


def test_ragged_dot_kernels_are_read_by_name_and_taken_off_unscoped():
    """The compiler writes the kernels' ``tf_op`` as ``ragged-dot-none``:
    they land under ``other``, one reader finds them by the operations'
    names and the unscoped remainder leaves them out."""
    assert scope_reduce.innermost_scope("ragged-dot-none") == "other"
    obs = {"scopes": dict(CHANGE, other=0.25 + 1.5),
           "trace": trace(**{"ragged-dot-none.27": 1.0,
                             "ragged-dot-none.3": 0.4375,
                             "ragged-dot-metadata.2": 0.0625,
                             "fusion.1034": 9.0})}
    assert read("moe_ragged_dot_ms", obs) == pytest.approx(1500.0)
    assert read("seqrec_unscoped_ms", obs) == pytest.approx(300.0)
    # a trace without such a kernel; no trace at all
    assert read("moe_ragged_dot_ms",
                {"scopes": CHANGE, "trace": trace(fusion=1.0)}) is None
    assert read("moe_ragged_dot_ms", {"scopes": CHANGE}) is None


def test_the_new_readings_and_the_old_scopes_add_up_to_the_device_time():
    obs = {"scopes": dict(CHANGE, other=0.25 + 1.5),
           "trace": trace(**{"ragged-dot-none.27": 1.5})}
    new = sum(read(n, obs) for n in (
        "seqrec_unscoped_ms", "seqrec_stack_ms", "seqrec_cast_ms",
        "seqrec_norm_residual_ms", "moe_ragged_dot_ms")) / 1e3
    old = CHANGE["seqrec.conv"] + CHANGE["seqrec.moe.experts"]
    assert new + old == pytest.approx(sum(obs["scopes"].values()))


# -- model.put's children ------------------------------------------------------


def tree(children=True):
    kids = [
        span("w", "p", "model.put.write", 701, 901, bytes=2_000, parts=3),
        span("y", "p", "model.put.sync", 901, 981),
        span("d", "p", "model.put.digest", 981, 996),
    ] if children else []
    return [
        span("r", None, "train.run", 0, 1000, status="COMPLETED"),
        span("f", "r", "train.fit", 0, 690),
        span("s", "r", "train.save", 690, 1000),
        span("z", "s", "model.serialize", 690, 700),
        span("p", "s", "model.put", 700, 1000, bytes=2_000, parts=3,
             streamed=1),
        *kids,
    ]


@pytest.mark.parametrize("name,want", [("save_write_s", 0.200),
                                       ("save_sync_s", 0.080)])
def test_save_reader_reads_its_span(name, want):
    assert read(name, {"spans": tree()}) == pytest.approx(want)


@pytest.mark.parametrize("name", ["save_write_s", "save_sync_s"])
def test_save_reader_gives_none_without_the_span(name):
    assert read(name, {"spans": tree(children=False)}) is None
    assert read(name, {"spans": []}) is None


def test_model_put_is_no_leaf_any_more_and_its_rest_is_untraced():
    """``host_untraced_s`` subtracts LEAF spans: with the children,
    what ``model.put`` does outside them (here 1 + 4 ms) is host time
    no span names; the save's own reader still reads the whole."""
    with_kids, leaf = {"spans": tree()}, {"spans": tree(children=False)}
    assert read("host_untraced_s", leaf) == pytest.approx(0.0)
    assert read("host_untraced_s", with_kids) == pytest.approx(0.005)
    assert read("save_s", with_kids) == read("save_s", leaf) == \
        pytest.approx(0.310)


# -- BENCHMARK.json -------------------------------------------------------------


def test_every_new_metric_is_declared_for_its_cell_and_has_a_reader():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    sequence_cells = [w["name"] for w in bench["workloads"]
                      if w["name"].startswith("seqrec-")]
    assert len(sequence_cells) == 5
    for name, layer, source in (
            ("seqrec_unscoped_ms", "seqrec step", "device_trace"),
            ("seqrec_stack_ms", "seqrec step", "device_trace"),
            ("seqrec_cast_ms", "kernels", "device_trace"),
            ("seqrec_norm_residual_ms", "kernels", "device_trace"),
            ("moe_ragged_dot_ms", "kernels", "device_trace"),
            ("save_write_s", "save", "program_span"),
            ("save_sync_s", "save", "program_span")):
        spec = by_name[name]        # ONE entry, every sequence cell listed
        assert spec["workloads"] == sequence_cells
        assert (spec["layer"], spec["source"], spec["better"],
                spec["moves"]) == (layer, source, "lower",
                                   "train_updates_per_s")
        assert callable(harness.load_module("layers", name).read)
