"""The ``qwen3_next`` cell's readers on hand-made observations,
``roofline_qwen3next`` against a hand count at the published shapes, the
configuration against the catalog row, what the generator changes on
the module it loads, and the ``--tiny`` rehearsal (``JAX_PLATFORMS=cpu
python3 -m pytest benchmark/tests/test_qwen3next_layers.py -q``)."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import harness
import roofline_qwen3next
import scope_reduce

from predictionio_tpu.models import qwen3_next as qn

CELL = "seqrec-qwen3next-80b-a3b-train"
#: the catalog row's ``config`` (model-configs guide,
#: ``architectures.jsonl``: Qwen3-Next-80B-A3B-Instruct), key by key
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}
OWN = ("gdn_scan_ms", "gdn_scan_roofline", "gdn_conv_ms", "gdn_proj_ms",
       "gdn_boundary_chunks_pct")
EVENTS, STEPS = 262_144, 16


def _reader(name):
    return harness.load_module("layers", name)


def _config():
    with open(os.path.join(harness.BENCH, "configs",
                           "seqrec-qwen3next-80b-a3b-ep16.json")) as f:
        return json.load(f)


def _cfg():
    gen = harness.load_module("generators", "qwen3next_train_jobs")
    conf = _config()
    return qn.Qwen3NextConfig.from_architecture(
        gen.shared.architecture(conf, conf))


@pytest.mark.parametrize("path,scope", [
    ("jit(train)/while/body/seqrec.gdn/seqrec.gdn.scan/while/body/"
     "dot_general:", "seqrec.gdn.scan"),
    ("jit(train)/transpose(jvp(seqrec.gdn))/seqrec.gdn.conv/mul:",
     "seqrec.gdn.conv"),
    ("jit(train)/transpose(jvp(seqrec.gdn))/dot_general:", "seqrec.gdn"),
])
def test_the_new_scopes_are_names_the_reducer_reads(path, scope):
    assert scope_reduce.innermost_scope(path) == scope


def test_the_configuration_holds_the_catalog_rows_keys():
    conf = _config()
    for key, value in CATALOG.items():
        if key in REDUCED:
            assert conf[key] == REDUCED[key], key
            assert conf["published"][key] == value, key
        else:
            assert conf[key] == value, key
    assert sorted(conf["published"]) == sorted(REDUCED)
    for key in ("deployment", "bytes", "assumed", "expert_load", "precision"):
        assert conf[key], key
    assert "625,667,136" in conf["bytes"] and "10.01 GB" in conf["bytes"]
    assert conf["job"] == {
        "ep_size": 16, "ep_rank": 0, "seq_len": 16384, "seqs_per_step": 1,
        "gdn_chunk": 64, "clip_norm": 1.0, "init_std": 0.02,
        "matmul_dtype": "bfloat16", "attn_block": 512, "token_chunk": 4096}
    assert conf["train"] == {"epochs": 1, "lr": 1e-4, "steps": 16}
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == conf["name"])
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    assert entry["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
        "config.json")
    assert len(bench["configs"]) == len(bench["workloads"]) == 7
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        conf["name"], "qwen3next_train_back_to_back", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    # five of its own, and the cell LISTED by the 24 every sequence cell
    # reports and by five more whose scopes it opens (the whole set is
    # pinned in test_benchmark_table.py; no copies under a suffix)
    assert sorted(m["name"] for m in mine
                  if m["workloads"] == [CELL]) == sorted(OWN)
    assert all(m["workloads"][-1] == CELL for m in mine)
    assert len(mine) == 5 + 24 + 5 and len(bench["per_layer"]) == 68
    names = {m["name"] for m in mine}
    assert {"seqrec_step_mfu_pct", "seqrec_ffn_ms", "attn_tile_real_pct",
            "gqa_attention_ms", "gqa_attention_roofline", "gqa_proj_ms",
            "hbm_peak_GB", "programs_compiled",
            "moe_experts_roofline"} <= names
    for m in mine:          # every reader is a file that is there
        _reader(m["name"])


def test_the_architecture_is_what_the_backbone_knows_of_the_file():
    cfg = _cfg()
    assert qn.n_params(cfg) == 625_667_136
    assert (cfg.ep_size, cfg.router_experts, cfg.held) == (
        16, 512, tuple(range(32)))
    assert cfg.seqs_per_step * cfg.seq_len == 16384
    assert (cfg.chunk, cfg.runs) == (64, (("linear", 3), ("full", 1)))
    assert _config()["n_items"] == cfg.vocab_size - 2   # PAD, items, a spare


def test_the_traffic_law_is_the_issues():
    """256 histories of 64 … 16,384 events, 262,144 in all (16 sequences
    of 16,384 slots, none of them padding), one at the cap, the
    quantiles the file says."""
    import datagen

    conf = _config()
    lengths = datagen.degree_sequence(
        conf["n_users"], conf["n_events"],
        conf["data"]["user_degree_quantiles"])
    assert (lengths.min(), lengths.max(), int(lengths.sum())) == (
        64, 16384, 262144 == 16 * 16384 and 262144)
    assert int((lengths == 16384).sum()) == 1
    assert np.quantile(lengths, [0.25, 0.5, 0.75, 0.9]).tolist() == [
        260.75, 348.5, 841.75, 2232.0]
    assert int(np.quantile(lengths, 0.97)) == 6452
    assert "260.75 / 348.5 / 841.75" in conf["data"]["what"]
    assert "degree_tables_from" not in conf["data"]
    with open(os.path.join(harness.BENCH, "configs",
                           "seqrec-smallthinker-21b-ep8.json")) as f:
        other = json.load(f)["data"]
    for key in ("item_degree_quantiles", "follow_share", "successors"):
        assert conf["data"][key] == other[key], key
    # the full layer's pairs: 28 % of a dense causal walk over sixteen
    # 16,384-slot sequences
    pairs = int((lengths * (lengths + 1) // 2).sum())
    assert pairs / (16 * 16384 * 16385 // 2) == pytest.approx(0.28, abs=0.01)


def test_needs_are_a_hand_count_at_the_published_shapes():
    cfg = _cfg()
    macs = roofline_qwen3next.per_token_macs(cfg)
    assert macs["gdn_proj"] == 3 * (2048 * 12288 + 2048 * 64 + 4096 * 2048)
    assert macs["gdn_conv"] == 3 * 4 * 8192
    assert macs["attn_proj"] == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    assert macs["router"] == 4 * 2048 * 512
    assert macs["shared"] == 4 * (3 * 2048 * 512 + 2048)
    assert macs["head"] == 2048 * 18992
    pairs = 600_000_000
    here = 4 * EVENTS * 10 // 16
    need = roofline_qwen3next.needs(
        cfg, {"steps": STEPS, "moe_pairs_here": here},
        {"sequences": 16, "real_tokens": EVENTS, "attn_pairs": pairs})
    # the recurrence: 7 x 128 x 128 a row, value head and linear layer
    # forward = 60.1 GFLOP a layer and step; twice that backward
    scan_fwd = 7 * 128 * 128 * 32 * 3 * EVENTS
    assert scan_fwd / (3 * STEPS) == pytest.approx(60.1e9, rel=1e-3)
    assert need["gdn_scan"]["flops"] == 3 * scan_fwd
    # q, k (16 x 128 each), v, o (32 x 128 each) as operands, g and beta
    # as float32: 24,832 B a row and layer = 0.41 GB a layer and step
    row = 2 * (2 * 2048 + 2 * 4096) + 4 * 2 * 32
    assert row == 24_832 and row * 16384 == pytest.approx(0.407e9, rel=1e-3)
    assert need["gdn_scan"]["bytes"] == 3 * 3 * EVENTS * row
    attn = pairs * 16 * 2 * 256
    assert need["attention"]["flops"] == 6 * attn
    assert need["attention"]["bytes"] == 3 * EVENTS * 2 * 256 * 2 * 18
    experts = here * 3 * 2048 * 512
    assert need["experts"]["flops"] == 6 * experts
    dense = EVENTS * sum(macs.values())
    assert need["train_flops"] == 3 * (2 * (dense + experts + attn)
                                       + scan_fwd)
    # ≈ 330 TFLOP a verb, ≈ 29 of them the full layer's pairs
    assert need["train_flops"] == pytest.approx(330e12, rel=0.05)
    assert need["attention"]["flops"] == pytest.approx(29.5e12, rel=0.01)
    # the scan is bound by its bytes, and far from either peak
    peaks = harness.peaks_for("TPU v5 lite")
    import roofline
    least, bound = roofline.least_seconds(need["gdn_scan"], peaks)
    assert bound == "bytes" and least == pytest.approx(0.0715, rel=0.01)


def _obs(scopes):
    return {"scopes": scopes,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "need": roofline_qwen3next.needs(
                _cfg(), {"steps": STEPS, "moe_pairs_here": 655_360},
                {"sequences": 16, "real_tokens": EVENTS,
                 "attn_pairs": 600_000_000})}


def _tree(pack=None, fit=None):
    return [{"name": "train.run", "spanId": 1, "parentId": None,
             "startNs": 0, "endNs": 10, "attrs": {}},
            {"name": "seqrec.pack", "spanId": 2, "parentId": 1,
             "startNs": 1, "endNs": 2, "attrs": pack or {}},
            {"name": "seqrec.fit", "spanId": 3, "parentId": 1,
             "startNs": 3, "endNs": 4, "attrs": fit or {}}]


def test_the_readers_sum_their_scopes_and_share_their_roofline():
    obs = _obs({"seqrec.gdn.scan": 2.0, "seqrec.gdn.conv": 0.25,
                "seqrec.gdn": 1.5, "seqrec.gqa.attention": 0.5,
                "other": 1.0})
    assert _reader("gdn_scan_ms").read(obs) == 2000.0
    assert _reader("gdn_conv_ms").read(obs) == 250.0
    assert _reader("gdn_proj_ms").read(obs) == 1500.0
    share = _reader("gdn_scan_roofline").read(obs)
    assert share == pytest.approx(
        100 * (9 * EVENTS * 24_832 / 819e9) / 2.0)
    assert 0 < share <= 100
    # the full layer through the readers that were there
    assert _reader("gqa_attention_ms").read(obs) == 500.0
    assert 0 < _reader("gqa_attention_roofline").read(obs) <= 100


def test_the_counter_reader_divides_the_spans_counters():
    obs = {"spans": _tree(pack={"gdn_chunk": 64, "gdn_chunks": 4096,
                                "gdn_boundary_chunks": 240})}
    assert _reader("gdn_boundary_chunks_pct").read(obs) == pytest.approx(
        100 * 240 / 4096)


@pytest.mark.parametrize("name", OWN[:4])
@pytest.mark.parametrize("obs", [
    {}, {"scopes": {}},
    {"scopes": {"seqrec.gqa": 1.0, "seqrec.gqa.attention": 1.0,
                "other": 2.0}},
    {"trace": types.SimpleNamespace(busy_s=0.0)}],
    ids=["nothing", "no_scopes", "another_programs_scopes", "no_device"])
def test_a_program_without_the_scopes_leaves_the_metric_out(name, obs):
    """What the parent with these files laid over it gives: None, no
    error."""
    assert _reader(name).read(obs) is None


@pytest.mark.parametrize("obs", [
    {"spans": []},
    {"spans": _tree(pack={"attn_pairs": 1000, "attn_tile_pairs": 1250},
                    fit={"steps": 16, "moe_pairs": 5})}],
    ids=["no_spans", "spans_without_the_chunks"])
def test_a_program_without_the_counters_leaves_the_metric_out(obs):
    assert _reader("gdn_boundary_chunks_pct").read(obs) is None


def test_a_need_without_the_scan_gives_no_share():
    """The reader on another backbone's needs (``roofline_lfm2``'s have
    no ``gdn_scan``): None, not a KeyError."""
    obs = _obs({"seqrec.gdn.scan": 1.0})
    del obs["need"]["gdn_scan"]
    assert _reader("gdn_scan_roofline").read(obs) is None


def test_the_generator_changes_only_what_the_recurrence_adds():
    gen = harness.load_module("generators", "qwen3next_train_jobs")
    other = harness.load_module("generators", "lfm2_train_jobs")
    assert gen.shared.ROOFLINES["qwen3_next"] == "roofline_qwen3next"
    assert "qwen3_next" not in other.ROOFLINES
    assert gen.run is gen.shared.run
    assert gen.shared.TARGETS["loss"] == "tgt1"       # the next item
    assert gen.shared.Reference.__module__ == other.Reference.__module__
    assert gen.shared.shared.first_batches is gen.first_batches
    assert other.shared.first_batches is not gen.first_batches
    # two checks more, around the loaded module's own
    assert gen.shared.check_reference is gen.check_reference
    assert gen._check.__code__ == other.check_reference.__code__
    assert gen.shared.shared.compare_logits is gen.compare_logits
    assert gen._compare.__code__ == other.shared.compare_logits.__code__
    for name in ("run", "reference_first_step", "reference_logits",
                 "_sequence"):
        assert getattr(gen.shared, name).__code__ == getattr(
            other, name).__code__, name
    with open(os.path.join(harness.BENCH, "traffic",
                           "qwen3next_train_back_to_back.json")) as f:
        traffic = json.load(f)
    assert (traffic["generator"], traffic["min_complete"]) == (
        "qwen3next_train_jobs", 2)
    # the model FIRST: a tree without the backbone fails before any data
    with open(os.path.join(harness.BENCH, "generators",
                           "qwen3next_train_jobs.py")) as f:
        code = [ln for ln in f.read().split('"""', 2)[2].splitlines()
                if ln and not ln.startswith(("#", "from __future__"))]
    assert code[:3] == [
        "import numpy as np",
        "from predictionio_tpu.models import seq_backbone",
        'seq_backbone.backbone("qwen3_next")']


def test_the_early_rows_median_reads_the_first_rows_of_each_segment():
    gen = harness.load_module("generators", "qwen3next_train_jobs")
    seg = np.array([[1] * 10 + [2] * 5 + [0]], np.int32)
    pos = np.array([list(range(10)) + list(range(5)) + [0]], np.int32)
    gen._last.update(early=1, batches=types.SimpleNamespace(seg=seg, pos=pos))
    rng = np.random.default_rng(0)
    want = rng.normal(size=(1, 16, 7)).astype(np.float32)
    got = want.copy()
    got[0, [0, 10]] += 0.5               # only the segments' first rows
    out = gen.compare_logits(got, want)
    base = gen._compare(got, want)
    assert {k: out[k] for k in base} == base
    assert base["token_median"] == 0.0   # 2 of 16 tokens: the median sleeps
    scale = np.sqrt(np.mean(np.square(want, dtype=np.float64).sum(-1)))
    assert out["early_median"] == pytest.approx(0.5 * np.sqrt(7) / scale,
                                                rel=1e-5)
    got[0, 15] += 9.0                    # a padding row is no first row
    assert gen.compare_logits(got, want)["early_median"] == pytest.approx(
        out["early_median"])


def test_the_program_is_held_to_a_share_of_the_lower_precisions_median(
        monkeypatch):
    """``check_lower``: the program's median over that of the
    all-bfloat16 reference's logits against the float32 ones kept by
    ``compare_logits`` — on stand-ins for the model and the
    reference."""
    import checks
    from predictionio_tpu.core import workflow

    gen = harness.load_module("generators", "qwen3next_train_jobs")
    rng = np.random.default_rng(1)
    want = rng.normal(size=(1, 16, 7)).astype(np.float32)
    seg = np.ones((1, 16), np.int32)
    gen._last.update(early=1, batches=types.SimpleNamespace(
        seg=seg, pos=np.arange(16, dtype=np.int32)[None]))
    program = gen.compare_logits(want + 0.01, want)["token_median"]
    made = []
    monkeypatch.setattr(workflow, "prepare_deploy", lambda **kw: (
        types.SimpleNamespace(models=[types.SimpleNamespace(
            device_params=lambda: "weights")])))
    monkeypatch.setattr(gen.shared, "Reference",
                        lambda backbone, cfg, dtype: made.append(dtype))
    monkeypatch.setattr(
        gen.shared, "reference_logits",
        lambda ref, model, packed, n: [want[:n] + 0.02])
    config = {"engine_factory": "x", "reference": {
        "sequences_compared": 1, "logits_vs_lower_max": 0.75}}
    verdict = checks.Verdict()
    share = gen.check_lower(verdict, config, None, None, None, program)
    assert share == pytest.approx(0.5, rel=1e-4) and verdict.ok
    assert [str(np.dtype(d)) for d in made] == ["bfloat16"]
    assert "want" not in gen._last          # the float32 logits let go
    gen.compare_logits(want + 0.01, want)
    verdict = checks.Verdict()
    gen.check_lower(verdict, config, None, None, None, 1.6 * program)
    assert not verdict.ok                   # 0.8 of the lower precision's


def test_the_scan_check_sees_a_missing_reset():
    """``check_scan`` at a small size on the CPU: the program's scan
    passes a limit that the same scan over ONE segment (no reset)
    breaks."""
    import checks
    import jax.numpy as jnp

    from predictionio_tpu.ops import gated_delta

    gen = harness.load_module("generators", "qwen3next_train_jobs")
    cfg = qn.Qwen3NextConfig.from_architecture(dict(
        hidden_size=64, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16, num_experts=8,
        num_experts_per_tok=2, vocab_size=50, seq_len=512, gdn_chunk=16,
        attn_block=32, token_chunk=64))
    seg = np.repeat(np.arange(1, 9), 64).astype(np.int32)
    tol = {"scan_g_max": 0.004, "scan_row_worst_max": 0.02}
    ops = gen.scan_operands(cfg, 7, tol["scan_g_max"])
    assert ops["q"].shape == (512, 2, 16) and ops["v"].shape == (512, 4, 16)
    assert (ops["g"] <= 0).all() and ops["g"].min() >= -0.004
    verdict = checks.Verdict()
    good = gen.check_scan(verdict, tol, cfg, 7, seg)
    assert verdict.ok and good < 0.01
    # no reset: the scan handed ONE segment
    real = gated_delta.gated_delta_rule
    try:
        gated_delta.gated_delta_rule = lambda q, k, v, g, b, s, c: real(
            q, k, v, g, b, jnp.ones_like(s), c)
        verdict = checks.Verdict()
        leak = gen.check_scan(verdict, tol, cfg, 7, seg)
        assert not verdict.ok and leak > 20 * good
    finally:
        gated_delta.gated_delta_rule = real


def test_the_configurations_limits_name_what_the_generator_checks():
    conf = _config()
    for tol in (conf["reference"], conf["sample"]["reference"]):
        assert {"loss_abs_max", "grad_norm_rel_max",
                "logits_token_median_max", "logits_vs_lower_max",
                "logits_early_median_max",
                "early_positions", "scan_g_max", "scan_row_worst_max",
                "sequences_compared"} <= set(tol)
    assert (conf["reference"]["scan_row_worst_max"]
            < conf["sample"]["reference"]["scan_row_worst_max"])


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(harness.BENCH, "reference",
                           "qwen3_next_jnp.py")) as f:
        lines = [ln for ln in f.read().splitlines()
                 if ln.startswith(("import ", "from "))]
    assert lines and not any("predictionio_tpu" in ln for ln in lines)


def test_the_tiny_rehearsal_is_correct():
    """Every phase at the configuration's ``sample`` size on the CPU:
    a ``rehearsal line:`` with ``"correct": true``, then a non-zero
    exit (no chip, no result)."""
    run = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"),
         "--workload", CELL, "--seed", "3000000007", "--seconds", "1",
         "--trace", "1", "--tiny"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    line = next(ln for ln in run.stdout.splitlines()
                if "rehearsal line:" in ln)
    result = json.loads(line.split("rehearsal line:", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    assert run.returncode != 0
    assert 0 < result["metrics"]["gdn_boundary_chunks_pct"]["value"] <= 100
