"""The readers of the program's span tree (``benchmark/layers/`` over
``benchmark/spans.py``): each on a made-up tree, and on a tree (or a
program) that lacks its span — None, never an error."""

import pytest

import harness
import spans

MS = 1_000_000


def span(sid, parent, name, start_ms, end_ms, **attrs):
    return {"spanId": sid, "parentId": parent, "name": name,
            "startNs": start_ms * MS, "endNs": end_ms * MS,
            "startUs": 1_000_000 + start_ms * 1000, "attrs": attrs}


def tree(gram="pallas"):
    return [
        span("r", None, "train.run", 0, 1000, status="COMPLETED"),
        span("i", "r", "train.init", 0, 10),
        span("rd", "r", "train.read", 10, 210),
        span("sc", "rd", "storage.scan", 10, 110, scan_cache="hit"),
        span("ix", "rd", "train.read.index", 110, 200),
        span("p", "r", "train.prepare", 210, 211),
        span("f", "r", "train.fit", 211, 900),
        span("ap", "f", "als.prepare", 211, 511, nnz=100,
             kernel_real_rows=45, kernel_padded_rows=100,
             kernel_bucket_rows=4),
        span("up", "f", "als.upload", 511, 531, bytes=1 << 20),
        span("i1", "f", "als.iterate", 531, 681, iterations=5, gram=gram),
        span("c1", "f", "als.checkpoint", 681, 701, step=5),
        span("i2", "f", "als.iterate", 701, 851, iterations=5, gram=gram),
        span("c2", "f", "als.checkpoint", 851, 881, step=10),
        span("ft", "f", "als.fetch", 881, 899),
        span("s", "r", "train.save", 900, 990),
        span("ms", "s", "model.serialize", 900, 970),
        span("mp", "s", "model.put", 970, 990),
        span("fn", "r", "train.finish", 990, 1000),
    ]


def read(name, obs):
    return harness.load_module("layers", name).read(obs)


@pytest.mark.parametrize("name,want", [
    ("read_scan_s", 0.100),
    ("layout_in_train_s", 0.300),
    ("h2d_s", 0.020),
    ("checkpoint_s", 0.050),          # both blocks' spans, summed
    ("fetch_s", 0.018),
    ("save_s", 0.090),
    ("gram_real_rows_pct", 45.0),
    # 1000 − (10 + 100 + 90 + 1 + 300 + 20 + 300 + 50 + 18 + 90 + 10):
    # train.read 200–210 and train.fit 899–900 lie in no leaf
    ("host_untraced_s", 0.011),
])
def test_reader_on_a_made_up_tree(name, want):
    assert read(name, {"spans": tree()}) == pytest.approx(want)


@pytest.mark.parametrize("name,span_name", [
    ("read_scan_s", "storage.scan"),
    ("layout_in_train_s", "als.prepare"),
    ("h2d_s", "als.upload"),
    ("checkpoint_s", "als.checkpoint"),
    ("fetch_s", "als.fetch"),
    ("save_s", "train.save"),
    ("gram_real_rows_pct", "als.prepare"),
    ("host_untraced_s", "train.run"),
])
def test_reader_gives_none_where_its_span_is_missing(name, span_name):
    without = [s for s in tree() if s["name"] != span_name]
    assert read(name, {"spans": without}) is None
    assert read(name, {"spans": []}) is None


def test_real_rows_share_only_where_the_fused_gram_ran():
    assert read("gram_real_rows_pct", {"spans": tree(gram="off")}) is None
    assert read("gram_real_rows_pct",
                {"spans": tree(gram="interpret")}) == pytest.approx(45.0)
    no_kernel_bucket = tree()
    no_kernel_bucket[7]["attrs"].update(kernel_real_rows=0,
                                        kernel_padded_rows=0)
    assert read("gram_real_rows_pct", {"spans": no_kernel_bucket}) is None


def test_a_program_without_the_verb_record_gives_none(monkeypatch):
    """The parent commit of the PR that brought these readers has no
    ``tracing.last_verb``: every reader returns None and raises
    nothing."""
    from predictionio_tpu.utils import tracing

    monkeypatch.delattr(tracing, "last_verb", raising=False)
    assert spans.tree_of({}) is None
    for name in ("read_scan_s", "layout_in_train_s", "h2d_s", "checkpoint_s",
                 "fetch_s", "save_s", "gram_real_rows_pct",
                 "host_untraced_s"):
        assert read(name, {}) is None


def test_tree_of_takes_the_programs_newest_verb(monkeypatch):
    from predictionio_tpu.utils import tracing

    monkeypatch.setattr(tracing, "last_verb",
                        lambda root: tree() if root == "train.run" else None,
                        raising=False)
    assert read("fetch_s", {}) == pytest.approx(0.018)
    # what the generator puts in obs wins over the process-wide record
    assert read("fetch_s", {"spans": []}) is None


def test_chol_solve_ms_reads_the_named_kernel():
    import trace_reduce

    named = trace_reduce.TraceSummary(
        1.0, 0.5, 1, op_seconds={"chol_solve.3": 0.002, "chol_solve.4": 0.001,
                                 "gather_gram.1[8x128]": 0.4})
    assert read("chol_solve_ms", {"trace": named}) == pytest.approx(3.0)
    unnamed = trace_reduce.TraceSummary(
        1.0, 0.5, 1, op_seconds={"custom-call.7": 0.003})
    assert read("chol_solve_ms", {"trace": unnamed}) is None
    assert read("chol_solve_ms", {}) is None


def test_every_new_metric_has_its_reader_file():
    """BENCHMARK.json's per-layer entries are found BY NAME."""
    import json
    import os

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for spec in bench["per_layer"]:
        harness.load_module("layers", spec["name"])
