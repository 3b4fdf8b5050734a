"""The ``xing4_0`` cell's readers on hand-made observations,
``roofline_xing4`` against a hand count at the published shapes, the
configuration against the catalog row, what the generator changes on
the module it loads, and the ``--tiny`` rehearsal (``JAX_PLATFORMS=cpu
python3 -m pytest benchmark/tests/test_xing4_layers.py -q``)."""

import json
import os
import subprocess
import sys
import types

import pytest

import harness
import roofline
import roofline_seq
import roofline_xing4
import scope_reduce

from predictionio_tpu.models import xing4_0 as xg

CELL = "seqrec-xing4-29b-a4b-train"
#: the catalog row's ``config`` (model-configs guide,
#: ``architectures.jsonl``: Xing4.0-29B-A4B), key by key
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 8, "vocab_size": 16384,
           "num_nextn_predict_layers": 0}
OWN = ("mhc_mix_ms", "mhc_mix_roofline", "mhc_coef_ms", "mhc_ds_err")
#: the readers that were there and list the cell (29)
LISTED = {
    "read_training_s", "programs_compiled", "device_idle_pct", "hbm_peak_GB",
    "read_scan_s", "save_s", "host_untraced_s", "seqrec_device_s",
    "seqrec_step_mfu_pct", "mla_attention_ms", "mla_attention_roofline",
    "moe_route_dispatch_ms", "moe_experts_ms", "moe_experts_roofline",
    "seqrec_head_loss_ms", "seqrec_optimizer_ms", "mla_proj_ms",
    "seqrec_ffn_ms", "moe_load_max_over_mean", "seq_pack_real_pct",
    "seqrec_pack_s", "attn_tile_real_pct", "seqrec_unscoped_ms",
    "seqrec_stack_ms", "seqrec_cast_ms", "seqrec_norm_residual_ms",
    "moe_ragged_dot_ms", "save_write_s", "save_sync_s"}
EVENTS, STEPS = 131_072, 32


def _reader(name):
    return harness.load_module("layers", name)


def _config():
    with open(os.path.join(harness.BENCH, "configs",
                           "seqrec-xing4-29b-a4b-ep8.json")) as f:
        return json.load(f)


def _cfg():
    gen = harness.load_module("generators", "xing4_train_jobs")
    conf = _config()
    return xg.XingConfig.from_architecture(
        gen.shared.architecture(conf, conf))


@pytest.mark.parametrize("path,scope", [
    ("jit(train)/while/body/seqrec.stack/while/body/checkpoint/"
     "seqrec.mhc.coef/div:", "seqrec.mhc.coef"),
    ("jit(train)/transpose(jvp(seqrec.stack))/while/body/seqrec.mhc.mix/"
     "mul:", "seqrec.mhc.mix"),
    ("jit(train)/seqrec.step/jvp(seqrec.stack)/seqrec.mhc/add:",
     "seqrec.mhc"),
    ("jit(train)/seqrec.stack/seqrec.mhc.mix/seqrec.mla/dot_general:",
     "seqrec.mla"),
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
    for key in ("deployment", "bytes", "assumed", "precision", "what"):
        assert conf[key] and "TBD" not in str(conf[key]), key
    assert "759,346,190" in conf["bytes"] and "12.15 GB" in conf["bytes"]
    assert conf["job"] == {
        "ep_size": 8, "ep_rank": 0, "seq_len": 4096, "seqs_per_step": 1,
        "bias_update_rate": 0.001, "mtp_loss_weight": 0.3, "clip_norm": 1.0,
        "init_std": 0.02, "matmul_dtype": "bfloat16", "attn_block": 512,
        "token_chunk": 4096}
    assert conf["train"] == {"epochs": 1, "lr": 1e-4, "steps": STEPS}
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    harness.check_table(bench)
    entry = bench["configs"][-1]
    assert entry["name"] == conf["name"]
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    assert entry["source"] == (
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
        "config.json")
    assert len(bench["configs"]) == len(bench["workloads"]) == 8
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, conf["name"], "xing4_train_back_to_back", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert not any(w["chips"] == 4 for w in bench["workloads"])
    # four of its own, LAST in the table, and the cell appended to the
    # readers that were there and find something to read in it
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(OWN)
    assert len(bench["per_layer"]) == 72
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert {m["name"] for m in mine} == set(OWN) | LISTED
    for m in mine:
        assert m["workloads"][-1] == CELL and m["moves"] == (
            "train_updates_per_s")
        _reader(m["name"])          # every reader is a file that is there
    for m in bench["per_layer"][-4:]:
        assert m["workloads"] == [CELL] and m["layer"] == "residual mixer"
    # what the cell does NOT list: another backbone's operators
    assert not any(m["name"].startswith(("gqa_", "swa_", "bd_", "gdn_",
                                         "shortconv", "als_", "gram_"))
                   for m in mine)


def test_the_architecture_is_what_the_backbone_knows_of_the_file():
    cfg = _cfg()
    assert xg.BACKBONE.n_params(cfg) == 759_346_190
    assert (cfg.ep_size, cfg.router_experts, cfg.held) == (
        8, 64, tuple(range(8)))
    assert cfg.seqs_per_step * cfg.seq_len == 4096
    assert cfg.heads == ("loss",) and cfg.yarn == CATALOG["rope_scaling"]
    conf = _config()
    assert conf["n_items"] == cfg.vocab_size - 2     # PAD, items, a spare
    assert conf["n_events"] == EVENTS == STEPS * 4096


def test_the_traffic_law_is_the_glm_cells():
    """ml-20m's degree tables, ``follow_share`` 0.6, 4 successors: the
    GLM cell's law on half its events and users; 131,072 events fill 32
    sequences of 4,096 slots."""
    import datagen

    conf = _config()
    with open(os.path.join(harness.BENCH, "configs",
                           "seqrec-glm47flash-ep8.json")) as f:
        glm = json.load(f)
    assert conf["data"] == glm["data"]
    assert (conf["n_events"], conf["n_users"]) == (
        glm["n_events"] // 2, glm["n_users"] // 2)
    tables = harness.load_json("configs", conf["data"]["degree_tables_from"])
    lengths = datagen.degree_sequence(conf["n_users"], conf["n_events"],
                                      tables["user_degree_quantiles"])
    assert int(lengths.sum()) == EVENTS and lengths.min() >= 2
    assert lengths.mean() == pytest.approx(144, abs=1)


def test_needs_are_a_hand_count_at_the_published_shapes():
    cfg = _cfg()
    one = roofline_xing4.mixer_per_token(cfg)
    # a sublayer forward: the stream read for u, read and written for the
    # write-back (with y): 3 x 4 x 3584 x 4 + 3584 x 4 B
    assert one["bytes"] == 3 * 4 * 3584 * 4 + 3584 * 4 == 186_368
    assert one["flops"] == 2 * 24 * 3584
    assert one["coef_flops"] == 2 * 4 * 3584 * 24
    fit = {"steps": STEPS, "moe_pairs_here": 4 * EVENTS * 4 // 8,
           "mhc_sublayers": 10}
    pack = {"sequences": 32, "real_tokens": EVENTS,
            "attn_pairs": 40_000_000}
    need = roofline_xing4.needs(cfg, fit, pack)
    base = roofline_seq.needs(cfg, fit, pack)
    # 1.118 MB a token and layer, forward and backward
    per_layer = need["mhc_mix"]["bytes"] / (EVENTS * 5)
    assert per_layer == 3 * 2 * 186_368 == 1_118_208
    assert need["mhc_mix"]["flops"] == 3 * EVENTS * 10 * 2 * 24 * 3584
    assert need["attention"] == base["attention"]
    assert need["experts"] == base["experts"]
    assert need["train_flops"] - base["train_flops"] == (
        3 * EVENTS * 10 * (2 * 24 * 3584 + 2 * 4 * 3584 * 24))
    # no MTP term at 0 modules: the head once, five layers' projections
    macs = roofline_seq.per_token_macs(cfg)
    assert macs["mtp_proj"] == 0 and macs["heads"] == 3584 * 16384
    assert macs["mla_proj"] == 5 * (3584 * 768 + 768 * 32 * 192 + 3584 * 576
                                    + 512 * 32 * 256 + 32 * 128 * 3584)
    # ≈ 0.27 GFLOP of products a token and layer, 2.2 a token in all
    assert need["train_flops"] / EVENTS == pytest.approx(2.3e9, rel=0.05)
    # the mixer is bound by its bytes: 733 GB a verb, 0.895 s at 819 GB/s
    peaks = harness.peaks_for("TPU v5 lite")
    least, bound = roofline.least_seconds(need["mhc_mix"], peaks)
    assert bound == "bytes" and least == pytest.approx(0.895, rel=0.01)


def _obs(scopes):
    return {"scopes": scopes,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "need": roofline_xing4.needs(
                _cfg(), {"steps": STEPS, "moe_pairs_here": 262_144,
                         "mhc_sublayers": 10},
                {"sequences": 32, "real_tokens": EVENTS,
                 "attn_pairs": 40_000_000})}


def _tree(fit=None):
    return [{"name": "train.run", "spanId": 1, "parentId": None,
             "startNs": 0, "endNs": 10, "attrs": {}},
            {"name": "seqrec.fit", "spanId": 3, "parentId": 1,
             "startNs": 3, "endNs": 4, "attrs": fit or {}}]


def test_the_readers_sum_their_scopes_and_share_their_roofline():
    obs = _obs({"seqrec.mhc.mix": 2.0, "seqrec.mhc": 0.25,
                "seqrec.mhc.coef": 0.5, "seqrec.mla": 1.5,
                "seqrec.mla.attention": 0.5, "other": 1.0})
    assert _reader("mhc_mix_ms").read(obs) == 2250.0
    assert _reader("mhc_coef_ms").read(obs) == 500.0
    share = _reader("mhc_mix_roofline").read(obs)
    assert share == pytest.approx(
        100 * (EVENTS * 5 * 1_118_208 / 819e9) / 2.25)
    assert 0 < share <= 100
    # latent attention through the readers that were there
    assert _reader("mla_attention_ms").read(obs) == 500.0
    assert _reader("mla_proj_ms").read(obs) == 1500.0
    assert 0 < _reader("mla_attention_roofline").read(obs) <= 100


def test_the_counter_reader_reads_the_fit_spans_counter():
    obs = {"spans": _tree(fit={"mhc_ds_err": 0.00125, "mhc_streams": 4})}
    assert _reader("mhc_ds_err").read(obs) == 0.00125


@pytest.mark.parametrize("name", OWN[:3])
@pytest.mark.parametrize("obs", [
    {}, {"scopes": {}},
    {"scopes": {"seqrec.mla": 1.0, "seqrec.mla.attention": 1.0,
                "other": 2.0}},
    {"trace": types.SimpleNamespace(busy_s=0.0)}],
    ids=["nothing", "no_scopes", "another_programs_scopes", "no_device"])
def test_a_program_without_the_scopes_leaves_the_metric_out(name, obs):
    """What the parent with these files laid over it gives: None, no
    error."""
    assert _reader(name).read(obs) is None


@pytest.mark.parametrize("obs", [
    {"spans": []}, {"spans": _tree(fit={"steps": 16, "moe_pairs": 5})}],
    ids=["no_spans", "spans_without_the_counter"])
def test_a_program_without_the_counter_leaves_the_metric_out(obs):
    assert _reader("mhc_ds_err").read(obs) is None


def test_a_need_without_the_mixer_gives_no_share():
    """The reader on another backbone's needs (``roofline_seq``'s have
    no ``mhc_mix``): None, not a KeyError."""
    obs = _obs({"seqrec.mhc.mix": 1.0})
    del obs["need"]["mhc_mix"]
    assert _reader("mhc_mix_roofline").read(obs) is None


def test_the_generator_changes_only_the_heads_and_two_checks():
    import checks

    gen = harness.load_module("generators", "xing4_train_jobs")
    other = harness.load_module("generators", "lfm2_train_jobs")
    assert gen.shared.ROOFLINES["xing4_0"] == "roofline_xing4"
    assert "xing4_0" not in other.ROOFLINES
    assert gen.run is gen.shared.run
    assert gen.shared.backbone_of is gen.backbone_of
    assert gen.shared.check_reference is gen.check_reference
    assert gen._check.__code__ == other.check_reference.__code__
    for name in ("run", "reference_first_step", "reference_logits",
                 "_sequence", "architecture"):
        assert getattr(gen.shared, name).__code__ == getattr(
            other, name).__code__, name
    assert gen.shared.Reference.__module__ == other.Reference.__module__
    # the heads THIS configuration trains
    conf = _config()
    assert gen.backbone_of(conf).heads == ("loss",)
    assert gen.backbone_of(dict(conf, num_nextn_predict_layers=1)).heads == (
        "loss", "mtp_loss")
    assert gen.backbone_of(conf).train_program is xg.BACKBONE.train_program
    # the check: under the limit, over it, and a program without it
    seen, mixed = [], []
    gen._check = lambda *a: seen.append(a) or {}
    real_mixer, gen.check_mixer = gen.check_mixer, (
        lambda verdict, tol, cfg, seed: mixed.append(seed) or 0.0)
    try:
        for err, ok in ((1e-4, True), (0.5, False), (None, False)):
            verdict = checks.Verdict()
            fit = {} if err is None else {"mhc_ds_err": err}
            out = gen.check_reference(
                verdict, {"correct": {"mhc_ds_err_max": 0.01},
                          "reference": {}}, None, None, 7, None, fit)
            assert verdict.ok is ok and out["mhc_ds_err"] == err
            assert out["mixer_rel_rms"] == 0.0
    finally:
        gen._check, gen.check_mixer = other.check_reference, real_mixer
    assert len(seen) == 3 and mixed == [7, 7, 7]
    with open(os.path.join(harness.BENCH, "traffic",
                           "xing4_train_back_to_back.json")) as f:
        traffic = json.load(f)
    assert (traffic["generator"], traffic["min_complete"]) == (
        "xing4_train_jobs", 2)
    # the model FIRST: a tree without the backbone fails before any data
    with open(os.path.join(harness.BENCH, "generators",
                           "xing4_train_jobs.py")) as f:
        code = [ln for ln in f.read().split('"""', 2)[2].splitlines()
                if ln and not ln.startswith(("#", "from __future__"))]
    assert code[:2] == [
        "from predictionio_tpu.models import seq_backbone",
        'seq_backbone.backbone("xing4_0")']


def _small():
    return xg.XingConfig.from_architecture(dict(
        hidden_size=256, seq_len=128, attn_block=64, token_chunk=128,
        n_routed_experts=8, ep_size=8, vocab_size=256, num_hidden_layers=2,
        first_k_dense_replace=1, num_nextn_predict_layers=0))


def test_the_mixer_check_passes_the_program_and_sees_a_lower_precision():
    """``check_mixer`` at a small size on the CPU: the program's
    operator reads under a limit that the reference computed in bfloat16
    throughout breaks."""
    import checks
    import jax.numpy as jnp

    gen = harness.load_module("generators", "xing4_train_jobs")
    cfg = _small()
    ops = gen.mixer_operands(cfg, 7)
    assert ops["X"].shape == (4, 128, 256) and ops["phi"].shape == (
        4, 256, 24)
    assert (gen.mixer_operands(cfg, 7)["b"] == ops["b"]).all()
    assert (gen.mixer_operands(cfg, 8)["b"] != ops["b"]).any()
    tol = {"mixer_rel_rms_max": 1e-3}
    verdict = checks.Verdict()
    good = gen.check_mixer(verdict, tol, cfg, 7)
    assert verdict.ok and good < 5e-4
    x = {k: jnp.asarray(v) for k, v in ops.items()}
    want = gen.mixer_reference(cfg)(x)
    low = gen.mixer_rel_rms(gen.mixer_reference(cfg, jnp.bfloat16)(x), want)
    assert low > 2 * tol["mixer_rel_rms_max"] > 4 * good


@pytest.mark.parametrize("fault,least", [("res_transposed", 0.03),
                                         ("post_unscaled", 0.2)])
def test_the_mixer_check_sees_a_wrong_equation(monkeypatch, fault, least):
    """What (a) and (b) are nearly blind to where the coefficients
    start: the mixer's own check reads a transposed H_res, or an H_post
    without its 2, tens of times over its limit — planted in the
    reference here, and in the PROGRAM's operator too."""
    import checks
    import jax.numpy as jnp

    from predictionio_tpu.ops import hyper_connections as hc
    from reference import xing4_0_jnp as ref

    gen = harness.load_module("generators", "xing4_train_jobs")
    cfg = _small()
    x = {k: jnp.asarray(v) for k, v in gen.mixer_operands(cfg, 7).items()}
    want = gen.mixer_reference(cfg)(x)
    monkeypatch.setattr(ref, "FAULT", fault)
    assert gen.mixer_rel_rms(gen.mixer_reference(cfg)(x), want) > least
    monkeypatch.setattr(ref, "FAULT", None)
    # the same fault in the program: the check fails
    real = hc.coefficients

    def wrong(*a, **kw):
        pre, post, res = real(*a, **kw)
        return ((pre, post, res.transpose(1, 0, 2))
                if fault == "res_transposed" else (pre, post / 2, res))

    monkeypatch.setattr(hc, "coefficients", wrong)
    verdict = checks.Verdict()
    read = gen.check_mixer(verdict, {"mixer_rel_rms_max": 1e-3}, cfg, 7)
    assert not verdict.ok and read > least


def test_the_configurations_limits_name_what_the_generator_checks():
    conf = _config()
    for tol in (conf["reference"], conf["sample"]["reference"]):
        assert {"loss_abs_max", "grad_norm_rel_max",
                "logits_token_median_max", "mixer_rel_rms_max",
                "sequences_compared"} <= set(tol)
    for cor in (conf["correct"], conf["sample"]["correct"]):
        assert {"loss_drop_min", "mhc_ds_err_max", "why"} <= set(cor)
    assert (conf["correct"]["mhc_ds_err_max"]
            < conf["sample"]["correct"]["mhc_ds_err_max"])
    assert (conf["reference"]["mixer_rel_rms_max"]
            < conf["sample"]["reference"]["mixer_rel_rms_max"])


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(harness.BENCH, "reference",
                           "xing4_0_jnp.py")) as f:
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
    assert "mhc_ds_err" in result["metrics"]
    assert run.returncode != 0
