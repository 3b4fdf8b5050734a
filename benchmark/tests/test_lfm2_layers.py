"""The ``lfm2_moe`` cell's readers on hand-made observations,
``roofline_lfm2`` against ISSUE 33's arithmetic, and the generator's
``architecture`` (``JAX_PLATFORMS=cpu python3 -m pytest
benchmark/tests/test_lfm2_layers.py -q``)."""

import json
import os
import types

import pytest

import harness
import roofline_lfm2
import scope_reduce

from predictionio_tpu.models import lfm2_moe as lfm


def _reader(name):
    return harness.load_module("layers", name)


def _config():
    with open(os.path.join(harness.BENCH, "configs",
                           "seqrec-lfm2-8b-a1b-ep4.json")) as f:
        return json.load(f)


def _cfg():
    gen = harness.load_module("generators", "lfm2_train_jobs")
    conf = _config()
    return lfm.Lfm2Config.from_architecture(gen.architecture(conf, conf))


@pytest.mark.parametrize("path,scope", [
    ("jit(train)/while/body/seqrec.conv/seqrec.conv.mix/mul:",
     "seqrec.conv.mix"),
    ("jit(train)/transpose(jvp(seqrec.conv))/dot_general:", "seqrec.conv"),
    ("jit(train)/while/seqrec.gqa/seqrec.gqa.attention/pallas_call:",
     "seqrec.gqa.attention"),
    ("jit(train)/transpose(jvp(seqrec.gqa))/rsqrt:", "seqrec.gqa"),
])
def test_the_new_scopes_are_names_the_reducer_reads(path, scope):
    assert scope_reduce.innermost_scope(path) == scope


def test_the_configuration_holds_the_catalog_rows_keys():
    conf = _config()
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True}
    for key, value in published.items():
        assert conf[key] == value, key
    reduced = {"num_hidden_layers": (5, 24), "num_dense_layers": (1, 2),
               "num_experts": (8, 32), "vocab_size": (16384, 65536)}
    for key, (here, there) in reduced.items():
        assert (conf[key], conf["published"][key]) == (here, there), key
    # the five kept layers are published layers 1..5 (from 0)
    assert conf["layer_types"] == conf["published"]["layer_types"][1:6]
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == conf["name"])
    assert sorted(entry["reduced"]) == sorted(
        list(reduced) + ["layer_types"])


def test_the_architecture_is_what_the_backbone_knows_of_the_file():
    cfg = _cfg()
    assert lfm.n_params(cfg) == 507_820_160
    assert (cfg.ep_size, cfg.router_experts, cfg.held) == (
        4, 32, tuple(range(8)))
    assert cfg.seqs_per_step * cfg.seq_len == 32768
    assert cfg.runs == (("conv", True, 1), ("full_attention", False, 1),
                        ("conv", False, 3))


def test_needs_are_the_issues_arithmetic():
    cfg = _cfg()
    macs = roofline_lfm2.per_token_macs(cfg)
    assert macs["conv_proj"] == 4 * 16_777_216          # 4 × 16.78 M
    assert macs["gqa_proj"] == 10_485_760               # 10.49 M
    assert macs["dense_ffn"] == 44_040_192
    assert macs["head"] == 33_554_432
    assert macs["router"] == 4 * 65_536
    fit = {"steps": 16, "moe_pairs_here": 16 * 4 * 32768}   # one a token
    pack = {"sequences": 128, "real_tokens": 524_288,
            "attn_pairs": 172_000_000}
    need = roofline_lfm2.needs(cfg, fit, pack)
    per_token = sum(macs.values()) + 4 * 11_010_048
    assert per_token == pytest.approx(199.5e6, rel=2e-3)
    attention = 6 * 172e6 * 32 * 128
    assert need["attention"]["flops"] == pytest.approx(attention)
    assert need["train_flops"] == pytest.approx(
        6 * per_token * 524_288 + attention)
    assert need["train_flops"] == pytest.approx(632e12, rel=5e-3)
    # B, C, u, y and their cotangents: 11 rows of 2048 bfloat16 a token
    # and conv layer
    assert need["shortconv"]["bytes"] == 11 * 2048 * 2 * 524_288 * 4
    assert need["experts"]["flops"] == 6 * fit["moe_pairs_here"] \
        * 3 * 2048 * 1792


def _obs(scopes):
    return {"scopes": scopes,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "need": roofline_lfm2.needs(
                _cfg(), {"steps": 16, "moe_pairs_here": 2_097_152},
                {"sequences": 128, "real_tokens": 524_288,
                 "attn_pairs": 172_000_000})}


def test_the_readers_sum_their_scopes_and_share_their_rooflines():
    obs = _obs({"seqrec.conv.mix": 0.5, "seqrec.conv": 3.0,
                "seqrec.gqa.attention": 0.25, "seqrec.gqa": 0.75,
                "other": 1.0})
    assert _reader("shortconv_ms").read(obs) == 500.0
    assert _reader("shortconv_proj_ms").read(obs) == 3000.0
    assert _reader("gqa_attention_ms").read(obs) == 250.0
    assert _reader("gqa_proj_ms").read(obs) == 750.0
    least = 11 * 2048 * 2 * 524_288 * 4 / 819e9
    assert _reader("shortconv_roofline").read(obs) == pytest.approx(
        100 * least / 0.5)
    assert _reader("gqa_attention_roofline").read(obs) == pytest.approx(
        100 * (6 * 172e6 * 32 * 128 / 197e12) / 0.25)
    for name in ("shortconv_roofline", "gqa_attention_roofline"):
        assert 0 < _reader(name).read(obs) <= 100


@pytest.mark.parametrize("name", [
    "shortconv_ms", "shortconv_roofline", "shortconv_proj_ms",
    "gqa_attention_ms", "gqa_attention_roofline", "gqa_proj_ms"])
@pytest.mark.parametrize("obs", [
    {}, {"scopes": {}}, {"scopes": {"seqrec.mla": 1.0, "other": 2.0}},
    {"trace": types.SimpleNamespace(busy_s=0.0)}],
    ids=["nothing", "no_scopes", "another_programs_scopes", "no_device"])
def test_a_program_without_the_scopes_leaves_the_metric_out(name, obs):
    """What a parent without this PR's program gives: None, no error."""
    assert _reader(name).read(obs) is None
