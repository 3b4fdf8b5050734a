"""The ten readers of a set-up's layers (PR 52; ``benchmark/layers/``
over ``benchmark/setup_layers.py``) on hand-made first-verb trees and a
hand-made registry — unions, the ``hit`` / not-``hit`` split, None on a
program without the record — and their ten ``BENCHMARK.json`` rows
(``JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_setup_layers.py
-q``)."""

import json
import os

import pytest

import harness
import setup_layers

MS = 1_000_000
OWN = ("first_train_s", "first_train_trace_s", "first_train_lower_s",
       "first_train_compile_s", "first_train_cache_load_s",
       "first_train_cache_hit_pct", "first_train_read_s",
       "first_train_rest_s", "ingest_append_s", "ingest_native_s")
SUMS = {"programs_traced": 3, "programs_lowered": 3, "programs_compiled": 2,
        "cache_hits": 1, "cache_misses": 1, "trace_s": 9.0, "lower_s": 6.0,
        "compile_s": 50.0, "cache_load_s": 4.0}


def span(sid, parent, name, start_ms, end_ms, **attrs):
    return {"spanId": sid, "parentId": parent, "name": name,
            "startNs": start_ms * MS, "endNs": end_ms * MS, "attrs": attrs}


def cold_tree(**root_attrs):
    """A first ``train.run`` of 100 s: a cold read, one program under
    ``seqrec.init`` (cache ``off``), two under ``seqrec.fit`` (``miss``,
    ``hit``), and a fourth on ANOTHER thread whose trace overlaps the
    miss's compile."""
    return [
        span("r", None, "train.run", 0, 100_000, status="COMPLETED",
             **root_attrs),
        span("rd", "r", "train.read", 1_000, 8_000),
        span("sc", "rd", "storage.scan", 1_000, 7_000,
             scan_cache="miss:cold"),
        span("in", "r", "seqrec.init", 8_000, 20_000),
        span("t0", "in", "compile.trace", 8_000, 9_000, program="init"),
        span("l0", "in", "compile.lower", 9_000, 10_000, program="jit_init"),
        span("b0", "in", "compile.backend", 10_000, 20_000,
             program="jit_init", cache="off", cache_read_s=0.0),
        span("f", "r", "seqrec.fit", 20_000, 95_000),
        span("t1", "f", "compile.trace", 20_000, 25_000, program="step"),
        span("l1", "f", "compile.lower", 25_000, 28_000, program="jit_step"),
        span("b1", "f", "compile.backend", 28_000, 68_000,
             program="jit_step", cache="miss", cache_read_s=0.0),
        span("t2", "f", "compile.trace", 68_000, 71_000, program="eval"),
        span("l2", "f", "compile.lower", 71_000, 73_000, program="jit_eval"),
        span("b2", "f", "compile.backend", 73_000, 77_000,
             program="jit_eval", cache="hit", cache_read_s=1.5),
        # another thread: 2 s of tracing inside b1's 40 s
        span("tx", "f", "compile.trace", 30_000, 32_000, program="aside"),
    ]


def read(name, obs):
    return harness.load_module("layers", name).read(obs)


@pytest.mark.parametrize("name,want", [
    ("first_train_s", 100.0),
    ("first_train_trace_s", 1.0 + 5.0 + 3.0 + 2.0),
    ("first_train_lower_s", 1.0 + 3.0 + 2.0),
    ("first_train_compile_s", 10.0 + 40.0),      # off and miss
    ("first_train_cache_load_s", 4.0),           # the hit alone
    ("first_train_cache_hit_pct", 100.0 / 3),
    ("first_train_read_s", 7.0),
    # 100 − the UNION of every compile.* span (8–20, 20–77): the other
    # thread's trace lies inside b1 and is not taken off twice
    ("first_train_rest_s", 100.0 - 12.0 - 57.0),
])
def test_reader_on_a_cold_tree(name, want):
    assert read(name, {"first_spans": cold_tree(**SUMS)}) == \
        pytest.approx(want)


def test_the_stages_of_one_thread_add_up_to_the_verb():
    one_thread = [s for s in cold_tree(**SUMS) if s["spanId"] != "tx"]
    obs = {"first_spans": one_thread}
    parts = [read(n, obs) for n in (
        "first_train_trace_s", "first_train_lower_s",
        "first_train_compile_s", "first_train_cache_load_s",
        "first_train_rest_s")]
    assert sum(parts) == pytest.approx(read("first_train_s", obs))
    # with the other thread's overlapping trace they are 2 s over
    over = {"first_spans": cold_tree(**SUMS)}
    assert sum(read(n, over) for n in (
        "first_train_trace_s", "first_train_lower_s",
        "first_train_compile_s", "first_train_cache_load_s",
        "first_train_rest_s")) == pytest.approx(102.0)


def test_a_full_and_an_empty_cache_read_zero_not_none():
    full = [dict(s, attrs=dict(s["attrs"], cache="hit"))
            if s["name"] == "compile.backend" else s
            for s in cold_tree(**dict(SUMS, cache_hits=3,
                                      programs_compiled=0))]
    assert read("first_train_compile_s", {"first_spans": full}) == 0.0
    assert read("first_train_cache_load_s", {"first_spans": full}) == 54.0
    assert read("first_train_cache_hit_pct", {"first_spans": full}) == 100.0
    empty = [s for s in cold_tree(**dict(SUMS, cache_hits=0))
             if s["spanId"] != "b2"]
    assert read("first_train_cache_load_s", {"first_spans": empty}) == 0.0
    assert read("first_train_cache_hit_pct", {"first_spans": empty}) == 0.0


@pytest.mark.parametrize("name", [n for n in OWN if n.startswith("first_")])
def test_none_without_the_record(name):
    """A root without the compile sums (a program that keeps a first
    verb and counts no compile) leaves the compile metrics out; no first
    verb at all leaves all eight out."""
    no_sums = {"first_spans": cold_tree()}
    if name in ("first_train_s", "first_train_read_s"):
        assert read(name, no_sums) is not None
    else:
        assert read(name, no_sums) is None
    assert read(name, {"first_spans": []}) is None


def test_nothing_reached_the_backend_has_no_hit_share():
    sums = dict(SUMS, cache_hits=0, programs_compiled=0)
    assert read("first_train_cache_hit_pct",
                {"first_spans": cold_tree(**sums)}) is None


def test_first_tree_falls_back_to_the_programs_first_verb():
    from predictionio_tpu.utils import tracing

    tracing.TRACER.reset()
    assert setup_layers.first_tree({}) is None
    assert read("first_train_s", {}) is None
    with tracing.verb("train.run", run=1):
        with tracing.span("train.read"):
            pass
    with tracing.verb("train.run", run=2):
        pass
    tree = setup_layers.first_tree({})
    assert tree[0]["attrs"] == {"run": 1} and len(tree) == 2
    assert read("first_train_s", {}) == \
        (tree[0]["endNs"] - tree[0]["startNs"]) / 1e9
    assert read("first_train_read_s", {}) > 0
    assert read("first_train_trace_s", {}) is None     # nothing compiled
    tracing.TRACER.reset()


def _registry(**stages):
    from predictionio_tpu.utils.metrics import Registry

    reg = Registry()
    reg.counter("pio_unrelated_total", "x").inc()
    if stages:
        c = reg.counter(setup_layers.INGEST, "x", ("stage",))
        for stage, secs in stages.items():
            c.inc((stage,), secs)
    return reg


def test_ingest_readers_on_a_hand_made_registry():
    obs = {"registry": _registry(native=40.0, sync=0.0, python=12.5)}
    assert read("ingest_append_s", obs) == 52.5
    assert read("ingest_native_s", obs) == 40.0


def test_ingest_is_none_where_nothing_was_ingested():
    # no such series (the parent), and a series that never moved (the
    # store was reused)
    assert read("ingest_append_s", {"registry": _registry()}) is None
    assert read("ingest_native_s", {"registry": _registry()}) is None
    idle = {"registry": _registry(native=0.0, sync=0.0, python=0.0)}
    assert read("ingest_append_s", idle) is None
    assert read("ingest_native_s", idle) is None


def test_the_ten_rows_of_the_table():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    harness.check_table(bench)
    cells = [w["name"] for w in bench["workloads"]]
    assert len(cells) == 8 and len(bench["per_layer"]) == 82
    rows = bench["per_layer"][-10:]
    assert [m["name"] for m in rows] == list(OWN)
    layer = {"first_train_s": "set-up", "first_train_rest_s": "set-up",
             "first_train_read_s": "training read",
             "ingest_append_s": "event ingest",
             "ingest_native_s": "event ingest"}
    for m in rows:
        counter = m["name"].startswith("ingest_") or \
            m["name"] == "first_train_cache_hit_pct"
        assert m == {
            "name": m["name"],
            "unit": "%" if m["name"].endswith("_pct") else "s",
            "better": "higher" if m["name"].endswith("_pct") else "lower",
            "source": "program_counter" if counter else "program_span",
            "layer": layer.get(m["name"], "compile"),
            "moves": "setup_s", "workloads": cells}
        harness.load_module("layers", m["name"])
    # they are the ONLY readers under setup_s, and every cell gets them
    assert [m["name"] for m in bench["per_layer"]
            if m["moves"] == "setup_s"] == list(OWN)
    import time

    for name in cells:
        cell = harness.Cell(name, 0, 1.0, True, False, time.perf_counter())
        assert [m["name"] for m in cell.per_layer][-10:] == list(OWN)
