"""Train→persist→deploy round trip through the real workflow + the
recommendation template (the in-process core of the reference's
quickstart scenario; SURVEY.md §4 Tier 2)."""

import numpy as np
import pytest

from predictionio_tpu.core.workflow import prepare_deploy, run_train
from predictionio_tpu.data.event import Event

FACTORY = "predictionio_tpu.templates.recommendation.engine:engine_factory"


def seed_ratings(storage, app_name="TestApp", n_users=30, n_items=20, seed=0):
    app = storage.meta.create_app(app_name)
    storage.events.init_channel(app.id)
    rng = np.random.default_rng(seed)
    # block structure: even users like even items, odd users like odd items
    evs = []
    for u in range(n_users):
        for i in range(n_items):
            if rng.random() < 0.5:
                r = 5.0 if (u % 2) == (i % 2) else 1.0
                evs.append(Event(event="rate", entity_type="user", entity_id=str(u),
                                 target_entity_type="item", target_entity_id=str(i),
                                 properties={"rating": r}))
    # a few implicit buys
    evs.append(Event(event="buy", entity_type="user", entity_id="0",
                     target_entity_type="item", target_entity_id="0"))
    storage.events.insert_batch(evs, app.id)
    return app


VARIANT = {
    "id": "default",
    "engineFactory": FACTORY,
    "datasource": {"params": {"appName": "TestApp"}},
    "algorithms": [{"name": "als",
                    "params": {"rank": 8, "numIterations": 8, "lambda": 0.05}}],
}


class TestTrainDeploy:
    def test_round_trip(self, storage):
        seed_ratings(storage)
        instance_id = run_train(FACTORY, variant=VARIANT, storage=storage,
                                use_mesh=False)
        ei = storage.meta.get_engine_instance(instance_id)
        assert ei.status == "COMPLETED"
        assert ei.end_time is not None

        deployed = prepare_deploy(engine_factory=FACTORY, storage=storage)
        assert deployed.instance.id == instance_id
        res = deployed.query({"user": "0", "num": 5})
        assert len(res["itemScores"]) == 5
        items = [int(s["item"]) for s in res["itemScores"]]
        # user 0 (even) should prefer even items
        even = sum(1 for i in items if i % 2 == 0)
        assert even >= 4, f"expected even-item preference, got {items}"
        # scores sorted descending
        scores = [s["score"] for s in res["itemScores"]]
        assert scores == sorted(scores, reverse=True)

    def test_unknown_user_empty(self, storage):
        seed_ratings(storage)
        run_train(FACTORY, variant=VARIANT, storage=storage, use_mesh=False)
        deployed = prepare_deploy(engine_factory=FACTORY, storage=storage)
        assert deployed.query({"user": "zzz", "num": 3}) == {"itemScores": []}

    def test_latest_instance_wins(self, storage):
        seed_ratings(storage)
        run_train(FACTORY, variant=VARIANT, storage=storage, use_mesh=False)
        second = run_train(FACTORY, variant=VARIANT, storage=storage,
                           use_mesh=False)
        deployed = prepare_deploy(engine_factory=FACTORY, storage=storage)
        assert deployed.instance.id == second

    def test_train_failure_marks_failed(self, storage):
        storage.meta.create_app("TestApp")  # no events → DataSource raises
        with pytest.raises(ValueError):
            run_train(FACTORY, variant=VARIANT, storage=storage, use_mesh=False)
        eis = storage.meta.list_engine_instances()
        assert eis and eis[0].status == "FAILED"
        assert prepare_deploy_fails(storage)


def prepare_deploy_fails(storage):
    try:
        prepare_deploy(engine_factory=FACTORY, storage=storage)
    except ValueError:
        return True
    return False


class TestEvalWorkflow:
    def test_grid_search(self, storage):
        from predictionio_tpu.controller import (
            EngineParams,
            Evaluation,
            OptionAverageMetric,
        )
        from predictionio_tpu.core.workflow import run_evaluation
        from predictionio_tpu.templates.recommendation.engine import (
            ALSAlgorithmParams,
            DataSourceParams,
        )

        seed_ratings(storage)

        class RMSE(OptionAverageMetric):
            higher_is_better = False
            header = "SquaredError"

            def calculate_one_opt(self, q, p, a):
                scores = p.get("itemScores", [])
                if not scores or scores[0]["score"] is None:
                    return None
                return (scores[0]["score"] - a) ** 2

        class Ev(Evaluation):
            engine_factory = FACTORY
            metric = RMSE()

        dsp = DataSourceParams(app_name="TestApp", eval_k=2)
        candidates = [
            EngineParams(dsp, None,
                         [("als", ALSAlgorithmParams(rank=r, num_iterations=6,
                                                     lambda_=0.05))], None)
            for r in (2, 8)
        ]
        iid, result = run_evaluation(Ev(), candidates, storage=storage,
                                     use_mesh=False)
        vi = storage.meta.get_evaluation_instance(iid)
        assert vi.status == "EVALCOMPLETED"
        assert len(result.candidates) == 2
        assert result.best_score == min(s for _, s, _ in result.candidates)


# -- the train verb's span tree (utils/tracing.verb, PERF.md §3) ---------------

import contextlib
import importlib.util
import io
import os
import re
import signal

from predictionio_tpu.utils import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: span → its parent, for every span of a checkpointed ALS train off a
#: store with a columnar scan (the contract of ISSUE 24's table)
SPAN_PARENTS = {
    "train.init": "train.run",
    "train.read": "train.run",
    "storage.scan": "train.read",
    "storage.scan.probe": "storage.scan",
    "storage.scan.load": "storage.scan",
    "train.read.index": "train.read",
    "train.read.arrays": "train.read",
    "train.prepare": "train.run",
    "train.fit": "train.run",
    "als.index": "train.fit",
    "als.prepare": "train.fit",
    "als.prepare.order": "als.prepare",
    "als.prepare.fill": "als.prepare",
    "als.init": "train.fit",
    "als.upload": "train.fit",
    "als.iterate": "train.fit",
    "als.checkpoint": "train.fit",
    "als.fetch": "train.fit",
    "train.save": "train.run",
    "model.serialize": "train.save",
    "model.put": "train.save",
    "train.finish": "train.run",
}

COMPILE_SPANS = {"compile.trace", "compile.lower", "compile.backend"}

SPAN_ATTRS = {
    "train.run": ("engine_factory", "instance_id", "status"),
    "storage.scan": ("scan_cache", "records"),
    "train.read.index": ("kept", "n_entities", "n_targets", "densify_e",
                         "densify_t", "masked"),
    "als.index": ("nnz",),
    "als.prepare": ("nnz", "kernel_real_rows", "kernel_padded_rows",
                    "kernel_bucket_rows", "kernel_dma_rows",
                    "kernel_dma_waits", "kernel_resident_rows",
                    "gram_table_bytes_u", "gram_table_bytes_i",
                    "radix_passes_u", "radix_passes_i", "dense_fill_u",
                    "dense_fill_i", "order_path_u", "order_path_i"),
    "als.upload": ("bytes",),
    "als.iterate": ("iterations", "gram", "solve"),
    "als.checkpoint": ("step", "bytes"),
    "als.fetch": ("bytes",),
    "model.serialize": ("bytes",),
    "model.put": ("bytes", "parts", "streamed"),
}

SPAN_VARIANT = dict(VARIANT, algorithms=[{"name": "als", "params": {
    "rank": 8, "numIterations": 4, "lambda": 0.05, "checkpointEvery": 2}}])


@contextlib.contextmanager
def scan_storage(home):
    """Meta and models in memory, events in SQLite: a store with a
    columnar scan (so ``storage.scan`` runs) that needs no compiler."""
    from predictionio_tpu.storage.registry import (Storage, StorageConfig,
                                                   set_storage)

    st = Storage(StorageConfig(metadata_type="MEMORY",
                               modeldata_type="MEMORY",
                               eventdata_type="SQLITE", home=str(home)))
    set_storage(st)
    try:
        yield st
    finally:
        st.events.close()
        set_storage(None)


def _generator_phase_regex():
    """The benchmark generator's own regex for the phase line, read out
    of its source (importing it would import the whole benchmark)."""
    with open(os.path.join(REPO, "benchmark", "generators",
                           "train_jobs.py")) as f:
        m = re.search(r'^_PHASES = re\.compile\(r"(.*)"\)$', f.read(), re.M)
    assert m, "benchmark/generators/train_jobs.py lost its _PHASES regex"
    return re.compile(m.group(1))


@pytest.fixture(scope="class")
def traced_train(tmp_path_factory):
    """ONE verbose train with tracing disabled: its tree, its context
    and what it printed. A COLD one: whatever this process has jitted
    is dropped first, so the verb traces, lowers and compiles."""
    import jax

    import predictionio_tpu.core.workflow as wf

    tracing.TRACER.reset()
    jax.clear_caches()
    seen = {}
    build = wf._build_context

    def keeping(*a, **kw):
        seen["ctx"] = build(*a, **kw)
        return seen["ctx"]

    out = io.StringIO()
    with scan_storage(tmp_path_factory.mktemp("spans")) as st:
        seed_ratings(st)
        wf._build_context = keeping
        try:
            with contextlib.redirect_stdout(out):
                iid = run_train(FACTORY, variant=SPAN_VARIANT, storage=st,
                                use_mesh=False, verbose=1)
        finally:
            wf._build_context = build
        seen.update(iid=iid, tree=tracing.last_verb("train.run"),
                    lines=out.getvalue().splitlines(), storage=st)
        yield seen


def _by_name(tree, name):
    return [s for s in tree if s["name"] == name]


class TestTrainSpans:
    @pytest.mark.parametrize("name", sorted(SPAN_PARENTS))
    def test_span_is_recorded_under_its_parent(self, traced_train, name):
        tree = traced_train["tree"]
        ids = {s["spanId"]: s for s in tree}
        got = _by_name(tree, name)
        assert got, f"no {name} span in {[s['name'] for s in tree]}"
        for s in got:
            parent = ids[s["parentId"]]
            assert parent["name"] == SPAN_PARENTS[name]
            assert parent["startNs"] <= s["startNs"] <= s["endNs"] \
                <= parent["endNs"]

    @pytest.mark.parametrize("name", sorted(SPAN_ATTRS))
    def test_span_carries_its_counts(self, traced_train, name):
        for s in _by_name(traced_train["tree"], name):
            assert set(SPAN_ATTRS[name]) <= set(s.get("attrs") or {}), s

    def test_root_is_first_and_names_the_instance(self, traced_train):
        root = traced_train["tree"][0]
        assert root["name"] == "train.run" and root["parentId"] is None
        assert root["attrs"]["instance_id"] == traced_train["iid"]
        assert root["attrs"]["status"] == "COMPLETED"
        assert root["attrs"]["engine_factory"] == FACTORY

    def test_no_unknown_span(self, traced_train):
        names = {s["name"] for s in traced_train["tree"]}
        assert names == set(SPAN_PARENTS) | {"train.run"} | COMPILE_SPANS

    def test_cold_train_names_what_it_compiled(self, traced_train):
        """The stages of every program the cold verb jitted lie under
        the span that called it, a few a program, and add up."""
        tree = traced_train["tree"]
        ids = {s["spanId"]: s for s in tree}
        stages = [s for s in tree if s["name"] in COMPILE_SPANS]
        root = tree[0]["attrs"]
        assert len(stages) == (root["programs_traced"]
                               + root["programs_lowered"]
                               + root["programs_compiled"]
                               + root["cache_hits"])
        assert root["programs_compiled"] + root["cache_hits"] >= 1
        assert len(stages) <= 3 * root["programs_traced"]
        for s in stages:
            assert ids[s["parentId"]]["name"] in SPAN_PARENTS
            assert s["attrs"]["program"]
            if s["name"] == "compile.backend":
                assert s["attrs"]["cache"] in ("hit", "miss", "off")
        assert {ids[s["parentId"]]["name"] for s in stages
                if s["name"] == "compile.backend"} >= {"als.iterate"}
        stages.sort(key=lambda s: s["startNs"])
        for a, b in zip(stages, stages[1:]):
            assert a["endNs"] <= b["startNs"], (a, b)
        summed = sum(root[k] for k in ("trace_s", "lower_s", "compile_s",
                                       "cache_load_s"))
        assert summed == pytest.approx(
            sum(s["endNs"] - s["startNs"] for s in stages) / 1e9, abs=1e-6)

    def test_compile_line_is_printed_once_on_its_own_line(
            self, traced_train):
        holding = [ln for ln in traced_train["lines"] if "compile:" in ln]
        root = traced_train["tree"][0]["attrs"]
        assert len(holding) == 1
        assert re.fullmatch(
            rf"\[workflow {traced_train['iid']}\] compile: "
            rf"traced {root['programs_traced']} \([\d.]+ s\), "
            rf"lowered {root['programs_lowered']} \([\d.]+ s\), "
            rf"compiled {root['programs_compiled']} \([\d.]+ s\), "
            rf"cache answered {root['cache_hits']} \([\d.]+ s\)",
            holding[0])
        assert "train phases:" not in holding[0]
        assert not _generator_phase_regex().search(holding[0])

    def test_siblings_do_not_overlap(self, traced_train):
        kids = {}
        for s in traced_train["tree"]:
            kids.setdefault(s["parentId"], []).append(s)
        for group in kids.values():
            group.sort(key=lambda s: s["startNs"])
            for a, b in zip(group, group[1:]):
                assert a["endNs"] <= b["startNs"], (a["name"], b["name"])

    def test_one_iterate_and_checkpoint_per_block(self, traced_train):
        tree = traced_train["tree"]
        assert [s["attrs"]["iterations"]
                for s in _by_name(tree, "als.iterate")] == [2, 2]
        assert [s["attrs"]["step"]
                for s in _by_name(tree, "als.checkpoint")] == [2, 4]

    def test_timings_are_the_spans_durations(self, traced_train):
        tree, ctx = traced_train["tree"], traced_train["ctx"]
        assert list(ctx.timings) == ["read_training", "prepare", "train:als"]
        for key, name in (("read_training", "train.read"),
                          ("prepare", "train.prepare"),
                          ("train:als", "train.fit")):
            (s,) = _by_name(tree, name)
            assert ctx.timings[key] == (s["endNs"] - s["startNs"]) / 1e9

    def test_phase_line_still_parses_as_the_benchmark_parses_it(
            self, traced_train):
        regex = _generator_phase_regex()
        hits = [m for m in map(regex.search, traced_train["lines"]) if m]
        assert len(hits) == 1
        phases = {k: float(v.rstrip("s")) for k, v in
                  (kv.split("=") for kv in hits[0].group(1).split(", "))}
        assert list(phases) == ["read_training", "prepare", "train:als"]
        ctx = traced_train["ctx"]
        assert phases == {k: float(f"{v:.3f}")
                          for k, v in ctx.timings.items()}

    def test_phase_text_is_on_no_other_line(self, traced_train):
        holding = [ln for ln in traced_train["lines"]
                   if "train phases:" in ln]
        assert len(holding) == 1
        assert holding[0].startswith(f"[workflow {traced_train['iid']}] "
                                     "train phases: read_training=")

    def test_tree_is_printed_under_its_own_heading(self, traced_train):
        lines = traced_train["lines"]
        at = lines.index(f"[workflow {traced_train['iid']}] train spans:")
        assert lines[at + 1].startswith("train.run ")
        printed = [ln.split()[0] for ln in lines[at + 1:]]
        assert printed == [s["name"] for s in _ordered(traced_train["tree"])]

    def test_tracer_stayed_disabled_and_its_ring_empty(self, traced_train):
        assert not tracing.TRACER.enabled and not tracing.TRACER.active
        assert len(tracing.TRACER.ring) == 0
        assert tracing.span("outside.any.verb") is tracing.NOOP_SPAN

    def test_second_train_replaces_the_tree(self, traced_train):
        first = traced_train["tree"]
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            second_id = run_train(FACTORY, variant=SPAN_VARIANT,
                                  storage=traced_train["storage"],
                                  use_mesh=False)
        tree = tracing.last_verb("train.run")
        # the warm verb jits nothing: the parent's span names, the
        # parent's root, no compile line — and the cold record is kept
        assert {s["name"] for s in tree} == set(SPAN_PARENTS) | {"train.run"}
        assert set(tree[0]["attrs"]) == set(SPAN_ATTRS["train.run"])
        assert "compile:" not in out.getvalue()
        assert tracing.first_verb("train.run") == first
        assert tree[0]["attrs"]["instance_id"] == second_id != \
            first[0]["attrs"]["instance_id"]
        assert not {s["spanId"] for s in tree} & {s["spanId"] for s in first}
        (scan,) = _by_name(tree, "storage.scan")
        assert scan["attrs"]["scan_cache"] == "hit"
        (load,) = _by_name(tree, "storage.scan.load")
        assert {"mapped", "bytes", "copied_bytes", "schema"} <= set(
            load["attrs"])

    def test_benchmark_reads_the_load_span(self, traced_train,
                                           monkeypatch):
        """``benchmark/layers/snapshot_load_s.py`` (ISSUE 42) against a
        real tree; None — never an error — on a program without the
        span (the parent), and declared for the two ALS cells."""
        import json

        bench = os.path.join(REPO, "benchmark")
        monkeypatch.syspath_prepend(bench)
        spec = importlib.util.spec_from_file_location(
            "bench_layers_snapshot_load_s",
            os.path.join(bench, "layers", "snapshot_load_s.py"))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        tree = traced_train["tree"]
        (load,) = _by_name(tree, "storage.scan.load")
        (scan,) = _by_name(tree, "storage.scan")
        got = reader.read({"spans": tree})
        assert got == (load["endNs"] - load["startNs"]) / 1e9
        assert 0 < got <= (scan["endNs"] - scan["startNs"]) / 1e9
        older = [s for s in tree if not s["name"].startswith("storage.scan.")]
        assert reader.read({"spans": older}) is None
        assert reader.read({"spans": []}) is None
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            (entry,) = [m for m in json.load(f)["per_layer"]
                        if m["name"] == "snapshot_load_s"]
        assert entry == {
            "name": "snapshot_load_s", "unit": "s", "better": "lower",
            "source": "program_span", "layer": "training read",
            "moves": "train_updates_per_s",
            "workloads": ["als-ml20m-train", "ials-lastfm360k-train"]}

    def test_bare_als_train_leaves_the_tree_alone(self, traced_train):
        from predictionio_tpu.models.als import (ALSParams, RatingsCOO,
                                                 als_train)

        before = tracing.last_verb("train.run")
        rng = np.random.default_rng(0)
        coo = RatingsCOO(rng.integers(0, 20, 300).astype(np.int32),
                         rng.integers(0, 15, 300).astype(np.int32),
                         rng.random(300).astype(np.float32), 20, 15)
        als_train(coo, ALSParams(rank=4, iterations=1))
        assert tracing.last_verb("train.run") == before

    def test_failed_train_records_its_status(self, traced_train):
        st = traced_train["storage"]
        st.meta.create_app("Empty")
        variant = dict(SPAN_VARIANT,
                       datasource={"params": {"appName": "Empty"}})
        with pytest.raises(ValueError):
            run_train(FACTORY, variant=variant, storage=st, use_mesh=False)
        tree = tracing.last_verb("train.run")
        assert tree[0]["attrs"]["status"] == "FAILED"
        assert tree[0]["status"] == "error"
        assert not tracing.TRACER.active


@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
def test_train_from_a_snapshot_hit_is_the_cold_trains_bit_for_bit(
        tmp_path, implicit):
    """The second train reads its columns as views of the mapped
    snapshot (ISSUE 42) — verified, not copied — and everything behind
    the read stays on the path it took from a cold scan: the same
    index passes, the same layout path, the same factors to the bit."""
    from predictionio_tpu import native

    variant = dict(VARIANT, algorithms=[{"name": "als", "params": {
        "rank": 8, "numIterations": 3, "lambda": 0.05,
        "implicitPrefs": implicit, "alpha": 1.0, "seed": 7}}])
    trees, models = [], []
    with scan_storage(tmp_path) as st:
        seed_ratings(st)
        for _ in range(2):
            iid = run_train(FACTORY, variant=variant, storage=st,
                            use_mesh=False)
            trees.append(tracing.last_verb("train.run"))
            models.append(prepare_deploy(instance_id=iid,
                                         storage=st).models[0])
    cold, hit = trees
    assert _by_name(cold, "storage.scan")[0]["attrs"]["scan_cache"] \
        == "miss:cold"
    (scan,) = _by_name(hit, "storage.scan")
    assert scan["attrs"]["scan_cache"] == "hit"
    (load,) = _by_name(hit, "storage.scan.load")
    probes = _by_name(hit, "storage.scan.probe")
    assert len(probes) == 2         # the store's count; the old
    #                                 watermark's count + the delta scan
    assert {s["parentId"] for s in (load, *probes)} == {scan["spanId"]}
    assert probes[0]["endNs"] <= load["startNs"] <= load["endNs"] \
        <= probes[1]["startNs"]
    attrs = load["attrs"]
    assert attrs["mapped"] == 1 and attrs["copied_bytes"] == 0
    assert attrs["schema"] == 3 and attrs["bytes"] > 0
    assert not _by_name(cold, "storage.scan.load")[0].get("attrs")
    order = "native" if native.als_layout_library() is not None else "radix"
    for tree in trees:
        (index,) = _by_name(tree, "train.read.index")
        assert (index["attrs"]["densify_e"], index["attrs"]["densify_t"],
                index["attrs"]["masked"]) == ("identity", "identity", 0)
        (prep,) = _by_name(tree, "als.prepare")
        assert prep["attrs"]["order_path_u"] == order
        assert prep["attrs"]["order_path_i"] == order
    a, b = models
    assert a.U.tobytes() == b.U.tobytes() and a.V.tobytes() == b.V.tobytes()
    assert list(a.user_ids) == list(b.user_ids)
    assert list(a.item_ids) == list(b.item_ids)


def _ordered(tree):
    """Depth-first, siblings in start order: the order the tree prints."""
    kids = {}
    for s in tree:
        kids.setdefault(s["parentId"], []).append(s)
    out = []

    def walk(s):
        out.append(s)
        for k in sorted(kids.get(s["spanId"], []),
                        key=lambda s: s["startNs"]):
            walk(k)

    walk(tree[0])
    return out


@contextlib.contextmanager
def _time_limit(seconds: int):
    def expired(signum, frame):
        raise TimeoutError(f"no end within {seconds} s")

    old = signal.signal(signal.SIGALRM, expired)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def test_verb_spans_land_in_the_profilers_trace(tmp_path):
    """Under a profiler session every verb span is an event of the
    trace's host plane, named ``pio:<span>`` — on the clock of the
    device operations; a cold verb's compile stages among them."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.clear_caches()
    with _time_limit(240):
        with scan_storage(tmp_path / "home") as st:
            seed_ratings(st)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path / "trace"),
                                     profiler_options=opts)
            try:
                run_train(FACTORY, variant=SPAN_VARIANT, storage=st,
                          use_mesh=False)
            finally:
                jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "trace" / "plugins" / "profile"
                                / "*" / "*.xplane.pb"))
        events = {ev.name: ev
                  for plane in ProfileData.from_file(path).planes
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("pio:")}
    tree = tracing.last_verb("train.run")
    assert {"pio:" + s["name"] for s in tree} == set(events)
    assert {"pio:" + name for name in COMPILE_SPANS} <= set(events)
    for name in ("train.run", "als.prepare"):
        (s,) = _by_name(tree, name)
        ev = events["pio:" + name]
        assert abs(ev.duration_ns - (s["endNs"] - s["startNs"])) < 1e6


@pytest.mark.parametrize("route", ["copied", "resident"])
def test_program_kernel_rows_equal_the_benchmarks_roofline_mirror(
        monkeypatch, route):
    """``benchmark/roofline.py`` mirrors the rule by which ``_make_half``
    hands a bucket to ``gather_gram``; the program counts by the rule
    itself (``ALSPrepared.kernel_rows``). Held equal on a layout with
    kernel-width buckets on both sides, whichever way the kernel
    fetches these small tables' lines (by the rule they are resident;
    ``copied`` moves the rule's constant under them)."""
    from predictionio_tpu.models.als import RatingsCOO, als_prepare
    from predictionio_tpu.ops import gram

    if route == "copied":
        monkeypatch.setattr(gram, "_RESIDENT_TABLE_BYTES", 0)

    spec = importlib.util.spec_from_file_location(
        "bench_roofline", os.path.join(REPO, "benchmark", "roofline.py"))
    roofline = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(roofline)

    rng = np.random.default_rng(5)
    n_users, n_items = 400, 900
    deg = np.minimum(rng.zipf(1.3, n_users) + 3, n_items)
    users = np.repeat(np.arange(n_users), deg)
    items = np.concatenate([rng.choice(n_items, d, replace=False)
                            for d in deg])
    coo = RatingsCOO(users.astype(np.int32), items.astype(np.int32),
                     rng.random(users.size).astype(np.float32),
                     n_users, n_items)
    prep = als_prepare(coo)
    got = prep.kernel_rows(8)
    need = roofline.gather_gram_need(prep, rank=8, iterations=1)
    assert got["kernel_padded_rows"] > 0, "no kernel-width bucket: no test"
    assert {k: got[k] for k in ("kernel_real_rows", "kernel_padded_rows",
                                "kernel_bucket_rows")} == {
        "kernel_real_rows": need["real_rows"],
        "kernel_padded_rows": need["padded_rows"],
        "kernel_bucket_rows": need["bucket_rows"]}
    # the copies the kernel starts (no mirror in the benchmark): never
    # fewer than the interactions, never more than the slots
    assert (got["kernel_real_rows"] <= got["kernel_dma_rows"]
            <= got["kernel_padded_rows"])
    if route == "copied":
        # a wait retires a group of copies (PR 37): at least one a row
        # that holds anything, far fewer than one a copy
        assert 0 < got["kernel_dma_waits"] < got["kernel_dma_rows"]
        assert got["kernel_resident_rows"] == 0
    else:   # a line read from the table the dispatch holds: no wait
        assert got["kernel_dma_waits"] == 0
        assert got["kernel_resident_rows"] == got["kernel_real_rows"]
    assert (got["gram_table_bytes_u"], got["gram_table_bytes_i"]) == (
        gram.table_bytes(n_items, 8), gram.table_bytes(n_users, 8))
