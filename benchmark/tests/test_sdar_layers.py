"""The ``sdar_moe`` cell's readers on hand-made observations,
``roofline_sdar`` against ISSUE 40's arithmetic, the configuration
against the catalog row, what the generator changes on the module it
loads, and the ``--tiny`` rehearsal (``JAX_PLATFORMS=cpu python3 -m
pytest benchmark/tests/test_sdar_layers.py -q``)."""

import json
import os
import subprocess
import sys
import types

import pytest

import harness
import roofline_sdar
import scope_reduce

from predictionio_tpu.models import sdar_moe as sd

CELL = "seqrec-sdar-30b-a3b-train"
#: the catalog row's ``config`` (model-configs guide,
#: ``architectures.jsonl``: SDAR-30B-A3B-Chat), key by key
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 18992}
#: one 8,192-row segment under the block rule: n(n + 4)
FULL = 8192 * 8196


def _reader(name):
    return harness.load_module("layers", name)


def _config():
    with open(os.path.join(harness.BENCH, "configs",
                           "seqrec-sdar-30b-a3b-ep8.json")) as f:
        return json.load(f)


def _cfg():
    gen = harness.load_module("generators", "sdar_train_jobs")
    conf = _config()
    return sd.SdarConfig.from_architecture(
        gen.shared.architecture(conf, conf))


@pytest.mark.parametrize("path,scope", [
    ("jit(train)/while/body/seqrec.bd/seqrec.bd.attention/pallas_call:",
     "seqrec.bd.attention"),
    ("jit(train)/transpose(jvp(seqrec.bd))/dot_general:", "seqrec.bd"),
    ("jit(train)/seqrec.step/seqrec.bd.noise/threefry2x32:",
     "seqrec.bd.noise"),
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
    assert "456,346,624" in conf["bytes"] and "7.30 GB" in conf["bytes"]
    assert "block length 4" in conf["assumed"][0]
    assert "schedule" in conf["assumed"][0]
    assert conf["job"] == {
        "ep_size": 8, "ep_rank": 0, "block_length": 4, "noise_eps": 1e-3,
        "seq_len": 8192, "seqs_per_step": 1, "clip_norm": 1.0,
        "init_std": 0.02, "matmul_dtype": "bfloat16", "attn_block": 512,
        "token_chunk": 4096}
    assert conf["train"] == {"epochs": 1, "lr": 1e-4, "steps": 32}
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == conf["name"])
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    assert entry["source"] == (
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
        "config.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        conf["name"], "sdar_train_back_to_back", 1)
    assert len(cell["why"]) <= 200
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    # six of its own, and the cell LISTED by the 24 every sequence cell
    # reports (no copies under a suffix: 128 metrics at most; the whole
    # set is pinned in test_benchmark_table.py)
    assert sum(m["workloads"] == [CELL] for m in mine) == 6
    assert len(mine) == 6 + 24 and len(bench["per_layer"]) <= 128
    names = {m["name"] for m in mine}
    assert not any("." in n for n in names)
    assert "moe_experts_roofline" in names
    assert not any(n.startswith(("seqrec_ffn_ms", "gqa_",
                                 "attn_tile_real_pct")) for n in names)
    for m in mine:          # every reader is a file that is there
        _reader(m["name"])


def test_the_architecture_is_what_the_backbone_knows_of_the_file():
    cfg = _cfg()
    assert sd.n_params(cfg) == 456_346_624
    assert (cfg.ep_size, cfg.router_experts, cfg.held) == (
        8, 128, tuple(range(16)))
    assert cfg.seqs_per_step * cfg.seq_len == 8192
    assert (cfg.block_length, cfg.noise_eps, cfg.mask_id) == (
        4, 1e-3, 18991)
    conf = _config()
    assert conf["n_items"] == cfg.mask_id - 1      # PAD, items, MASK


def test_the_traffic_law_is_the_issues():
    """64 histories of 512 … 8,192 events, 262,144 in all (32 sequences
    of 8,192 slots, none of them padding), 17 at the cap, the quartiles
    the file says."""
    import datagen
    import numpy as np

    conf = _config()
    lengths = datagen.degree_sequence(
        conf["n_users"], conf["n_events"],
        conf["data"]["user_degree_quantiles"])
    assert (lengths.min(), lengths.max(), int(lengths.sum())) == (
        512, 8192, 262144 == 32 * 8192 and 262144)
    assert int((lengths == 8192).sum()) == 17
    assert list(np.quantile(lengths, [0.25, 0.5, 0.75])) == [
        1084.5, 3209.5, 8192.0]
    assert "1,084.5 / 3,209.5 / 8,192" in conf["data"]["what"]
    assert "degree_tables_from" not in conf["data"]
    with open(os.path.join(harness.BENCH, "configs",
                           "als-ml20m-r64.json")) as f:
        assert conf["data"]["item_degree_quantiles"] == json.load(f)[
            "item_degree_quantiles"]
    # the block rule leaves 2.0 x the causal pairs of these histories,
    # not the 4 x of a causal walk over both streams
    from predictionio_tpu.ops.seq_attention import block_pairs
    causal = int((lengths * (lengths + 1) // 2).sum())
    assert block_pairs(lengths, 4) / causal == pytest.approx(2.0, abs=2e-3)


def test_needs_are_the_issues_arithmetic():
    from predictionio_tpu.ops.seq_attention import block_pairs

    cfg = _cfg()
    macs = roofline_sdar.per_row_macs(cfg)
    assert macs["attn_proj"] == 4 * 18_874_368
    assert macs["router"] == 4 * 262_144
    assert block_pairs([8192], 4) == FULL == 67_141_632
    steps, events = 32, 262_144
    masked = events // 2
    fit = {"steps": steps, "bd_masked": masked,
           "moe_pairs_here": 4 * 2 * events * 8 // 8}
    pack = {"sequences": 32, "real_tokens": events,
            "attn_pairs_bd": 32 * FULL}
    need = roofline_sdar.needs(cfg, fit, pack)
    assert need["bd_attention"]["flops"] == 6 * 32 * FULL * 4 * 32 * 2 * 128
    assert need["experts"]["flops"] == 6 * fit["moe_pairs_here"] \
        * 3 * 2048 * 768
    # both streams' rows through projections, router and held experts:
    # 16,384 x 4 x (18.87 + 0.26 + 4.72 M) = 1.56 T multiply-adds a
    # step; the head on the masked rows only
    per_step = (2 * events * (macs["attn_proj"] + macs["router"])
                + fit["moe_pairs_here"] * 3 * 2048 * 768) / steps
    assert per_step == pytest.approx(1.56e12, rel=5e-3)
    head = masked * 2048 * 18992
    assert need["train_flops"] == 6 * (
        per_step * steps + head + 32 * FULL * 4 * 32 * 256)
    assert need["bd_attention"]["bytes"] == (
        4 * 3 * 2 * events * 2 * 128 * 2 * 36)


def _obs(scopes):
    return {"scopes": scopes,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "need": roofline_sdar.needs(
                _cfg(), {"steps": 32, "bd_masked": 131_072,
                         "moe_pairs_here": 2_097_152},
                {"sequences": 32, "real_tokens": 262_144,
                 "attn_pairs_bd": 8 * FULL})}


def _tree(pack=None, fit=None):
    return [{"name": "train.run", "spanId": 1, "parentId": None,
             "startNs": 0, "endNs": 10, "attrs": {}},
            {"name": "seqrec.pack", "spanId": 2, "parentId": 1,
             "startNs": 1, "endNs": 2, "attrs": pack or {}},
            {"name": "seqrec.fit", "spanId": 3, "parentId": 1,
             "startNs": 3, "endNs": 4, "attrs": fit or {}}]


def test_the_readers_sum_their_scopes_and_share_their_roofline():
    obs = _obs({"seqrec.bd.attention": 4.0, "seqrec.bd": 1.5,
                "seqrec.bd.noise": 0.002, "other": 1.0})
    assert _reader("bd_attention_ms").read(obs) == 4000.0
    assert _reader("bd_proj_ms").read(obs) == 1500.0
    assert _reader("bd_noise_ms").read(obs) == 2.0
    share = _reader("bd_attention_roofline").read(obs)
    assert share == pytest.approx(
        100 * (6 * 8 * FULL * 4 * 32 * 256 / 197e12) / 4.0)
    assert 0 < share <= 100


def test_the_counter_readers_divide_the_spans_counters():
    obs = {"spans": _tree(pack={"attn_pairs_bd": 800,
                                "attn_tile_pairs_bd": 1000,
                                "attn_pairs": 400, "attn_tile_pairs": 500},
                          fit={"bd_masked": 505, "bd_real": 1000})}
    assert _reader("bd_tile_real_pct").read(obs) == 80.0
    assert _reader("bd_masked_pct").read(obs) == 50.5


@pytest.mark.parametrize("name", [
    "bd_attention_ms", "bd_attention_roofline", "bd_proj_ms",
    "bd_noise_ms"])
@pytest.mark.parametrize("obs", [
    {}, {"scopes": {}},
    {"scopes": {"seqrec.gqa": 1.0, "seqrec.gqa.attention": 1.0,
                "other": 2.0}},
    {"trace": types.SimpleNamespace(busy_s=0.0)}],
    ids=["nothing", "no_scopes", "another_programs_scopes", "no_device"])
def test_a_program_without_the_scopes_leaves_the_metric_out(name, obs):
    """What a program without the block rule gives: None, no error."""
    assert _reader(name).read(obs) is None


@pytest.mark.parametrize("name", ["bd_tile_real_pct", "bd_masked_pct"])
@pytest.mark.parametrize("obs", [
    {"spans": []},
    {"spans": _tree(pack={"attn_pairs": 1000, "attn_tile_pairs": 1250},
                    fit={"steps": 16, "moe_pairs": 5})}],
    ids=["no_spans", "spans_without_the_block_rule"])
def test_a_program_without_the_counters_leaves_the_metric_out(name, obs):
    assert _reader(name).read(obs) is None


def test_a_need_without_the_block_part_gives_no_share():
    """The reader on another backbone's needs (``roofline_lfm2``'s have
    no ``bd_attention``): None, not a KeyError."""
    obs = _obs({"seqrec.bd.attention": 1.0})
    del obs["need"]["bd_attention"]
    assert _reader("bd_attention_roofline").read(obs) is None


def test_the_generator_changes_only_what_the_objective_changes():
    gen = harness.load_module("generators", "sdar_train_jobs")
    other = harness.load_module("generators", "lfm2_train_jobs")
    assert gen.shared.ROOFLINES["sdar_moe"] == "roofline_sdar"
    assert gen.run is gen.shared.run
    # the divisor: every real event; the loss: the weighted one; the
    # batches: with the first step's noise — on the LOADED module only
    assert gen.shared.TARGETS["loss"] == "seg"
    assert other.TARGETS["loss"] == "tgt1"
    assert gen.shared.Reference is gen.Reference
    assert gen.Reference.__mro__[1].__name__ == "Reference"
    assert gen.Reference.__mro__[1].__module__ == other.Reference.__module__
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
                           "sdar_train_back_to_back.json")) as f:
        traffic = json.load(f)
    assert (traffic["generator"], traffic["min_complete"]) == (
        "sdar_train_jobs", 2)


def test_the_early_rows_median_reads_the_first_rows_of_each_segment():
    """``compare_logits`` as the generator wraps it: the loaded
    module's numbers unchanged, and the median over the tokens among
    the first ``early_positions`` rows of their segment beside them."""
    import numpy as np

    gen = harness.load_module("generators", "sdar_train_jobs")
    seg = np.array([[1] * 10 + [2] * 5 + [0]], np.int32)
    pos = np.array([list(range(10)) + list(range(5)) + [0]], np.int32)
    gen._last.update(early=3, batches=types.SimpleNamespace(seg=seg, pos=pos))
    rng = np.random.default_rng(0)
    want = rng.normal(size=(1, 16, 7)).astype(np.float32)
    got = want.copy()
    early = [0, 1, 2, 10, 11, 12]
    got[0, early] += 0.5                 # only the early rows differ
    out = gen.compare_logits(got, want)
    base = gen._compare(got, want)
    assert {k: out[k] for k in base} == base
    assert base["token_median"] == 0.0   # 6 of 16 tokens: the median sleeps
    scale = np.sqrt(np.mean(np.square(want, dtype=np.float64).sum(-1)))
    assert out["early_median"] == pytest.approx(0.5 * np.sqrt(7) / scale,
                                                rel=1e-5)
    got[0, 15] += 9.0                    # a padding row is no early row
    assert gen.compare_logits(got, want)["early_median"] == pytest.approx(
        out["early_median"])


def test_the_configurations_limits_name_what_the_generator_checks():
    conf = _config()
    for tol in (conf["reference"], conf["sample"]["reference"]):
        assert {"loss_abs_max", "grad_norm_rel_max",
                "logits_token_median_max", "logits_early_median_max",
                "early_positions", "weight_mean_abs_max",
                "sequences_compared"} <= set(tol)
    assert conf["reference"]["early_positions"] == 64
    assert (conf["reference"]["logits_early_median_max"]
            < conf["sample"]["reference"]["logits_early_median_max"])


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(harness.BENCH, "reference",
                           "sdar_moe_jnp.py")) as f:
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
    assert 0 < result["metrics"]["bd_tile_real_pct"]["value"] <= 100
    assert 20 < result["metrics"]["bd_masked_pct"]["value"] < 80
