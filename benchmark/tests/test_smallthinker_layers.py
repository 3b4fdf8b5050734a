"""The ``smallthinker`` cell's readers on hand-made observations,
``roofline_smallthinker`` against ISSUE 38's arithmetic, the
configuration against the catalog row, and the ``--tiny`` rehearsal
(``JAX_PLATFORMS=cpu python3 -m pytest
benchmark/tests/test_smallthinker_layers.py -q``)."""

import json
import os
import subprocess
import sys
import types

import pytest

import harness
import roofline_smallthinker
import scope_reduce

from predictionio_tpu.models import smallthinker as st

CELL = "seqrec-smallthinker-21b-train"
#: the catalog row's ``config`` (model-configs guide,
#: ``architectures.jsonl``: SmallThinker-21BA3B-Instruct), key by key
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 4, "rope_layout": [0, 1, 1, 1],
           "sliding_window_layout": [0, 1, 1, 1],
           "moe_num_primary_experts": 8, "vocab_size": 18992}


def _reader(name):
    return harness.load_module("layers", name)


def _config():
    with open(os.path.join(harness.BENCH, "configs",
                           "seqrec-smallthinker-21b-ep8.json")) as f:
        return json.load(f)


def _cfg():
    gen = harness.load_module("generators", "smallthinker_train_jobs")
    conf = _config()
    return st.SmallThinkerConfig.from_architecture(
        gen.shared.architecture(conf, conf))


@pytest.mark.parametrize("path,scope", [
    ("jit(train)/while/body/seqrec.swa/seqrec.swa.attention/pallas_call:",
     "seqrec.swa.attention"),
    ("jit(train)/transpose(jvp(seqrec.swa))/dot_general:", "seqrec.swa"),
    ("jit(train)/while/seqrec.gqa/seqrec.gqa.attention/pallas_call:",
     "seqrec.gqa.attention"),
    ("jit(train)/seqrec.stack/while/body/seqrec.moe.route/top_k:",
     "seqrec.moe.route"),
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
    assert "370,547,200" in conf["bytes"] and "5.93 GB" in conf["bytes"]
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == conf["name"])
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    assert entry["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        conf["name"], "smallthinker_train_back_to_back", 1)
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    # five of its own, the 24 every sequence cell reports, and the four
    # of the global layers (the whole set: test_benchmark_table.py)
    assert sum(m["workloads"] == [CELL] for m in mine) == 5
    assert len(mine) == 5 + 24 + 4
    names = {m["name"] for m in mine}
    assert not any("." in n for n in names)
    assert "moe_experts_roofline" in names
    assert "seqrec_ffn_ms" not in names
    for m in mine:          # every reader is a file that is there
        _reader(m["name"])


def test_the_architecture_is_what_the_backbone_knows_of_the_file():
    cfg = _cfg()
    assert st.n_params(cfg) == 370_547_200
    assert (cfg.ep_size, cfg.router_experts, cfg.held) == (
        8, 64, tuple(range(8)))
    assert cfg.seqs_per_step * cfg.seq_len == 32768
    assert cfg.runs == (("global", 1), ("window", 3))
    assert cfg.window == 4096


def test_the_traffic_law_is_the_issues():
    """64 histories of 1,024 … 16,384 events, 524,288 in all, 17 at
    the cap; the window keeps about half the causal pairs."""
    import datagen

    conf = _config()
    lengths = datagen.degree_sequence(
        conf["n_users"], conf["n_events"],
        conf["data"]["user_degree_quantiles"])
    assert (lengths.min(), lengths.max(), int(lengths.sum())) == (
        1024, 16384, 524288)
    assert int((lengths == 16384).sum()) == 17
    assert "degree_tables_from" not in conf["data"]
    from predictionio_tpu.models.seq_backbone import window_pairs
    kept, bound = window_pairs(lengths, 4096)
    pairs = int((lengths * (lengths + 1) // 2).sum())
    assert 100 * kept / pairs == pytest.approx(50.5, abs=0.1)
    assert bound / lengths.sum() == pytest.approx(0.59, abs=0.005)


def test_needs_are_the_issues_arithmetic():
    cfg = _cfg()
    macs = roofline_smallthinker.per_token_macs(cfg)
    assert macs["attn_proj"] == 4 * 20_971_520
    assert macs["router"] == 4 * 163_840
    assert macs["head"] == 48_619_520
    # ONE full-length history a sequence: 134.2 M causal pairs of which
    # a window of 4,096 keeps 58.7 M
    full, kept = 16384 * 16385 // 2, 4096 * 4097 // 2 + 12288 * 4096
    assert full == pytest.approx(134.2e6, rel=1e-3)
    assert kept == pytest.approx(58.7e6, rel=1e-3)
    from predictionio_tpu.models.seq_backbone import window_pairs
    assert window_pairs([16384], 4096) == (kept, 12288)
    assert window_pairs([8192], 4096)[0] == pytest.approx(25.2e6, rel=2e-3)
    assert window_pairs([4096, 100], 4096) == (
        4096 * 4097 // 2 + 5050, 0)
    fit = {"steps": 16, "moe_pairs_here": 16 * 4 * 32768 * 6 // 8}
    pack = {"sequences": 32, "real_tokens": 524_288,
            "attn_pairs": 32 * full, "attn_pairs_window": 32 * kept}
    need = roofline_smallthinker.needs(cfg, fit, pack)
    assert need["attention"]["flops"] == 6 * 32 * full * 28 * 2 * 128
    assert need["window_attention"]["flops"] == (
        6 * 3 * 32 * kept * 28 * 2 * 128)
    assert need["experts"]["flops"] == 6 * fit["moe_pairs_here"] \
        * 3 * 2560 * 768
    # per token of a full-length history: (8,192 + 3 x 3,583) keys x 28
    # x 128 x 2 = 136 M multiply-adds of attention against 102 M in
    # projections and held experts and 48.6 M in the head
    attn = (need["attention"]["flops"]
            + need["window_attention"]["flops"]) / 6 / 524_288
    assert attn == pytest.approx(136e6, rel=5e-3)
    rest = sum(macs.values()) + 4 * 0.75 * 5_898_240
    assert rest - macs["head"] == pytest.approx(102e6, rel=5e-3)
    assert need["train_flops"] == pytest.approx(
        6 * 524_288 * (attn + rest), rel=1e-9)
    # window layers move q, k, v and the output of three layers, the
    # global ones of one
    assert need["window_attention"]["bytes"] == 3 * need["attention"]["bytes"]


def _obs(scopes):
    full, kept = 16384 * 16385 // 2, 4096 * 4097 // 2 + 12288 * 4096
    return {"scopes": scopes,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "need": roofline_smallthinker.needs(
                _cfg(), {"steps": 16, "moe_pairs_here": 1_572_864},
                {"sequences": 32, "real_tokens": 524_288,
                 "attn_pairs": 8 * full, "attn_pairs_window": 8 * kept})}


def _pack_tree(**attrs):
    return [{"name": "train.run", "spanId": 1, "parentId": None,
             "startNs": 0, "endNs": 10, "attrs": {}},
            {"name": "seqrec.pack", "spanId": 2, "parentId": 1,
             "startNs": 1, "endNs": 2, "attrs": attrs}]


def test_the_readers_sum_their_scopes_and_share_their_rooflines():
    obs = _obs({"seqrec.swa.attention": 2.0, "seqrec.swa": 1.5,
                "seqrec.gqa.attention": 1.0, "seqrec.gqa": 0.25,
                "other": 1.0})
    assert _reader("swa_attention_ms").read(obs) == 2000.0
    assert _reader("swa_proj_ms").read(obs) == 1500.0
    assert _reader("gqa_attention_ms").read(obs) == 1000.0
    assert _reader("gqa_proj_ms").read(obs) == 250.0
    kept = 4096 * 4097 // 2 + 12288 * 4096
    assert _reader("swa_attention_roofline").read(obs) == pytest.approx(
        100 * (6 * 3 * 8 * kept * 28 * 256 / 197e12) / 2.0)
    # the global layers' share reads the part named "attention", as in
    # the lfm2_moe cell
    assert _reader("gqa_attention_roofline").read(obs) == pytest.approx(
        100 * (6 * 8 * (16384 * 16385 // 2) * 28 * 256 / 197e12) / 1.0)
    for name in ("swa_attention_roofline", "gqa_attention_roofline"):
        assert 0 < _reader(name).read(obs) <= 100


def test_the_pair_readers_divide_the_pack_spans_counters():
    obs = {"spans": _pack_tree(attn_pairs=1000, attn_pairs_window=505,
                               attn_tile_pairs=1250,
                               attn_tile_pairs_window=700)}
    assert _reader("swa_window_pairs_pct").read(obs) == 50.5
    assert _reader("swa_tile_real_pct").read(obs) == pytest.approx(
        100 * 505 / 700)
    assert _reader("attn_tile_real_pct").read(obs) == 80.0


@pytest.mark.parametrize("name", [
    "swa_attention_ms", "swa_attention_roofline", "swa_proj_ms"])
@pytest.mark.parametrize("obs", [
    {}, {"scopes": {}},
    {"scopes": {"seqrec.gqa": 1.0, "seqrec.gqa.attention": 1.0,
                "other": 2.0}},
    {"trace": types.SimpleNamespace(busy_s=0.0)}],
    ids=["nothing", "no_scopes", "another_programs_scopes", "no_device"])
def test_a_program_without_the_scopes_leaves_the_metric_out(name, obs):
    """What a program without the window layers gives: None, no error."""
    assert _reader(name).read(obs) is None


@pytest.mark.parametrize("name", ["swa_window_pairs_pct",
                                  "swa_tile_real_pct"])
@pytest.mark.parametrize("obs", [
    {"spans": []}, {"spans": _pack_tree(attn_pairs=1000,
                                        attn_tile_pairs=1250)}],
    ids=["no_pack_span", "a_pack_span_without_the_window"])
def test_a_program_without_the_counters_leaves_the_metric_out(name, obs):
    assert _reader(name).read(obs) is None


def test_a_need_without_the_window_part_gives_no_share():
    """The reader on another backbone's needs (``roofline_lfm2``'s have
    no ``window_attention``): None, not a KeyError."""
    obs = _obs({"seqrec.swa.attention": 1.0})
    del obs["need"]["window_attention"]
    assert _reader("swa_attention_roofline").read(obs) is None


def test_the_generator_enters_the_needs_and_copies_nothing():
    gen = harness.load_module("generators", "smallthinker_train_jobs")
    assert gen.shared.ROOFLINES["smallthinker"] == "roofline_smallthinker"
    assert gen.run is gen.shared.run
    with open(os.path.join(harness.BENCH, "traffic",
                           "smallthinker_train_back_to_back.json")) as f:
        traffic = json.load(f)
    assert (traffic["generator"], traffic["min_complete"]) == (
        "smallthinker_train_jobs", 2)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(harness.BENCH, "reference",
                           "smallthinker_jnp.py")) as f:
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
    assert 0 < result["metrics"]["swa_window_pairs_pct"]["value"] < 100
