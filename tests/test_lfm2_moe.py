"""The ``lfm2_moe`` backbone of the ``sequentialrec`` template against
its plain reference (``benchmark/reference/lfm2_moe_jnp.py``), on seeded
random weights at a preset of hidden 64, 4 query heads over 2 key-value
heads, 8 experts top-2, 64-position sequences and four layers of three
kinds (conv + dense, attention + experts, 2 × conv + experts)."""

import os
import pickle
import struct
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import lfm2_moe_jnp as ref  # noqa: E402

from predictionio_tpu.models import lfm2_moe as lfm  # noqa: E402
from predictionio_tpu.models import seq_backbone  # noqa: E402

ARCH = dict(
    model_type="lfm2_moe", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_attention_heads=4, num_key_value_heads=2,
    conv_L_cache=3, conv_bias=False, use_expert_bias=True,
    layer_types=["conv", "full_attention", "conv", "conv"],
    num_hidden_layers=4, num_dense_layers=1, num_experts=8, ep_size=1,
    num_experts_per_tok=2, vocab_size=50, seq_len=64, seqs_per_step=2,
    attn_block=32, token_chunk=64, init_std=0.2)

#: the comparison's limits with bfloat16 operands (what the cell's
#: configuration states), at the configuration's init_std of 0.02: the
#: stated precision reads 0.0037 (the logits, rms(diff)/rms), bfloat16
#: EVERYWHERE 0.0138 — the limit between the two. The loss hardly
#: moves with the precision at this size (9e-5 stated, 7e-5 lower): its
#: limit only catches a wrong loss, ten times the reading
BF16_LOGITS_REL_RMS = 0.008
BF16_LOSS_ABS = 1e-3


def _config(**over):
    return lfm.Lfm2Config.from_architecture(dict(ARCH, **over))


def _histories(seed=0, n=12, top=50):
    rng = np.random.default_rng(seed)
    return ([rng.integers(1, top, rng.integers(3, 40)) for _ in range(n)]
            + [rng.integers(1, top, 100)])


def _setup(c, seed=3):
    packed = seq_backbone.pack_histories(_histories(), c.seq_len,
                                         c.seqs_per_step, seed=1)
    params, bias = lfm.BACKBONE.init_state(c, seed)
    # a bias that matters: selection differs from the plain top-k
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(1), bias.shape)
    batch = {k: jnp.asarray(getattr(packed, k)[:c.seqs_per_step])
             for k in lfm.BATCH_KEYS}
    return packed, params, bias, batch


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _logits(params, bias, batch, c):
    """The program's head, through its own jitted entry point."""
    return lfm.BACKBONE.sequence_logits({"params": params, "bias": bias}, batch, c)[0]


def _ref_logits(params, bias, batch, c, **kw):
    @jax.jit
    def run(params, bias, batch):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(lambda s: ref.forward(
                params, bias, s, dict(c.__dict__), c.held, **kw)[0])(batch)

    return run(params, bias, batch)


def _named(tree):
    return dict((lfm._path_name(p), g) for p, g in
                jax.tree_util.tree_flatten_with_path(tree)[0])


# -- 1. the system against the reference -------------------------------------


@pytest.fixture(scope="module")
def exact():
    """The system with float32 operands and the reference, once."""
    c = _config(matmul_dtype="float32")
    packed, params, bias, batch = _setup(c)
    (loss, rec), grads = jax.jit(lambda p, b, bt: jax.value_and_grad(
        lfm.loss_fn, has_aux=True)(p, b, bt, c))(params, bias, batch)
    (rloss, loads), rgrads = jax.jit(
        lambda w, b, bt: ref.loss_and_grads(w, b, bt, dict(c.__dict__)))(
            params, bias, batch)
    return dict(c=c, params=params, bias=bias, batch=batch, loss=loss,
                rec=rec, grads=grads, rloss=rloss, loads=loads,
                rgrads=rgrads)


def test_the_stack_is_one_body_a_run_of_equal_layers():
    c = _config()
    assert c.runs == (("conv", True, 1), ("full_attention", False, 1),
                      ("conv", False, 2))
    shapes = lfm.param_shapes(c)
    assert [r["op_norm"][0] for r in shapes["runs"]] == [1, 1, 2]
    assert "head" not in shapes                   # the embedding is the head
    # the published stack: 2 dense conv layers, then 22 expert layers
    whole = lfm.Lfm2Config.from_architecture(dict(
        layer_types=["conv", "conv", "full_attention", "conv", "conv",
                     "conv", "full_attention"], num_hidden_layers=7))
    assert whole.runs == (("conv", True, 2), ("full_attention", False, 1),
                          ("conv", False, 3), ("full_attention", False, 1))


def test_parameter_count_of_the_benchmarks_share():
    """ISSUE 33's arithmetic: 507,820,160 parameters."""
    c = lfm.Lfm2Config.from_architecture(dict(
        layer_types=["conv", "full_attention", "conv", "conv", "conv"],
        num_hidden_layers=5, num_dense_layers=1, num_experts=8, ep_size=4,
        vocab_size=16384))
    assert lfm.BACKBONE.n_params(c) == 507_820_160
    assert c.held == tuple(range(8)) and c.router_experts == 32


def test_logits_match_reference(exact):
    c = exact["c"]
    got = _logits(exact["params"], exact["bias"], exact["batch"], c)
    want = _ref_logits(exact["params"], exact["bias"], exact["batch"], c)
    assert _rel(got, want) < 1e-5


def test_loss_matches_reference(exact):
    assert abs(float(exact["loss"]) - float(exact["rloss"])) < 1e-5
    assert abs(float(exact["rec"]["loss"]) - float(exact["rloss"])) < 1e-5
    assert exact["rec"]["moe"]["load"].shape == (3, 8)   # expert layers
    np.testing.assert_array_equal(np.asarray(exact["rec"]["moe"]["load"]),
                                  np.asarray(exact["loads"]))


_LEAVES = [lfm._path_name(p) for p, _ in jax.tree_util.tree_flatten_with_path(
    lfm.param_shapes(_config()), is_leaf=seq_backbone._is_shape)[0]]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_leaf_matches_reference(exact, leaf):
    got, want = _named(exact["grads"]), _named(exact["rgrads"])
    assert got[leaf].shape == want[leaf].shape
    assert float(jnp.linalg.norm(want[leaf])) > 0
    assert _rel(got[leaf], want[leaf]) < 2e-5


def test_every_leaf_has_a_group_and_the_groups_are_the_parts():
    assert lfm.BACKBONE.grad_groups(_config()) == (
        "attn", "conv", "embed", "experts", "ffn", "norms", "router")
    assert {lfm.group_of(leaf) for leaf in _LEAVES} == set(
        lfm.BACKBONE.grad_groups(_config()))


def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses(exact):
    """E enters as the embedding and, transposed, as the head: handed
    over as two arrays, their two gradients add up to the one of the
    tied table."""
    c, params = exact["c"], exact["params"]

    def untied(embed, head, params, bias, batch):
        x, _ = lfm._stack(dict(params, embed=embed), bias, batch, c)
        n = jnp.maximum((batch["tgt1"] > 0).sum(), 1)
        return lfm._chunked_ce(lambda x: lfm._head_logits(
            dict(params, embed=head), x, c), x, batch["tgt1"], c) / n

    as_embedding, as_head = jax.jit(jax.grad(untied, (0, 1)))(
        params["embed"], params["embed"], params, exact["bias"],
        exact["batch"])
    assert float(jnp.linalg.norm(as_embedding)) > 0
    assert float(jnp.linalg.norm(as_head)) > 0
    assert _rel(as_embedding + as_head, exact["grads"]["embed"]) < 1e-5


def _ref_loss(params, bias, batch, c, **kw):
    @jax.jit
    def run(params, bias, batch):
        with jax.default_matmul_precision("highest"):
            return ref.loss(params, bias, batch, dict(c.__dict__), **kw)[0]

    return float(run(params, bias, batch))


@pytest.fixture(scope="module")
def stated():
    """The configuration's own init_std and operand dtype, with the
    reference's float32 logits and loss."""
    c = _config(init_std=0.02)
    _, params, bias, batch = _setup(c)
    return dict(c=c, params=params, bias=bias, batch=batch,
                want=_ref_logits(params, bias, batch, c),
                rloss=_ref_loss(params, bias, batch, c))


def test_stated_precision_within_its_limits(stated):
    """bfloat16 operands, float32 accumulation, float32 taps and gate
    products: inside the limits that the lower precision below breaks."""
    c = stated["c"]
    got = _logits(stated["params"], stated["bias"], stated["batch"], c)
    assert _rel(got, stated["want"]) < BF16_LOGITS_REL_RMS
    loss, _ = jax.jit(lambda p, b, bt: lfm.loss_fn(p, b, bt, c))(
        stated["params"], stated["bias"], stated["batch"])
    assert abs(float(loss) - stated["rloss"]) < BF16_LOSS_ABS


def test_lower_precision_fails(stated):
    """The reference computed in bfloat16 THROUGHOUT (router scores,
    softmax, norms, taps, gates, accumulation — the nearest precision
    below the stated one) breaks the limits the stated precision
    keeps."""
    low = _ref_logits(stated["params"], stated["bias"], stated["batch"],
                      stated["c"], dtype=jnp.bfloat16)
    assert _rel(low, stated["want"]) > BF16_LOGITS_REL_RMS


# -- 2. the shares add up ----------------------------------------------------


def test_four_shares_of_eight_experts_add_up_to_the_whole_layer():
    """The expert layer run 4 times, each told it holds a different
    quarter of 32 experts: the sum is the uncut reference's output for
    the whole layer (no shared expert to count once)."""
    whole = _config(matmul_dtype="float32", num_experts=32,
                    num_experts_per_tok=4)
    params, bias = lfm.BACKBONE.init_state(whole, 5)
    w = jax.tree.map(lambda a: a[0], params["runs"][1])
    x = jax.random.normal(jax.random.PRNGKey(2), (128, whole.hidden_size))
    valid = jnp.ones(128, bool)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(w, x, valid.astype(x.dtype), bias[0], None,
                          dict(whole.__dict__))
    total, pairs = 0.0, 0
    for rank in range(4):
        share = _config(matmul_dtype="float32", num_experts=8, ep_size=4,
                        ep_rank=rank, num_experts_per_tok=4)
        assert share.router_experts == 32
        mine = dict(w, experts=jax.tree.map(
            lambda a: a[8 * rank:8 * rank + 8], w["experts"]))
        part, stats = jax.jit(lambda w, x, b, share=share: lfm._moe(
            w, x, valid, b, share))(mine, x, bias[0])
        assert int(stats["dropped"]) == 0
        pairs += int(stats["pairs_here"])
        total = total + part
    assert pairs == 128 * 4
    assert _rel(total, want) < 1e-5


# -- 3. routing --------------------------------------------------------------


def test_no_pair_dropped_under_a_skewed_router():
    """A selection bias that sends every token to experts 0 and 1: the
    layer keeps every pair and reports the skew."""
    c = _config(matmul_dtype="float32")
    params, _ = lfm.BACKBONE.init_state(c, 5)
    w = jax.tree.map(lambda a: a[0], params["runs"][1])
    x = jax.random.normal(jax.random.PRNGKey(3), (128, c.hidden_size))
    bias = jnp.zeros(c.router_experts).at[:2].set(10.0)
    out, stats = jax.jit(lambda w, x, b: lfm._moe(
        w, x, jnp.ones(128, bool), b, c))(w, x, bias)
    assert int(stats["dropped"]) == 0
    assert int(stats["pairs_here"]) == int(stats["pairs"]) == 128 * 2
    np.testing.assert_array_equal(np.asarray(stats["load"]),
                                  [128, 128, 0, 0, 0, 0, 0, 0])
    assert float(stats["load_max_over_mean"]) == pytest.approx(4.0)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(w, x, jnp.ones(128), bias, None,
                          dict(c.__dict__))
    assert _rel(out, want) < 1e-5


def test_router_bias_takes_no_gradient_and_moves_by_the_rule(exact):
    c = exact["c"]
    g = jax.jit(jax.grad(lambda b: lfm.loss_fn(
        exact["params"], b, exact["batch"], c)[0]))(exact["bias"])
    assert float(jnp.abs(g).max()) == 0.0
    from predictionio_tpu.models.seq_rec import _make_tx

    program = lfm.BACKBONE.train_program(c, 1)
    opt = _make_tx().init(exact["params"])
    copy = jax.tree.map(jnp.array, (exact["params"], opt, exact["bias"]))
    data = {k: v[None] for k, v in exact["batch"].items()}
    (_, _, new_bias), rec = program(copy, data)
    want = ref.bias_update(exact["bias"], exact["loads"],
                           c.bias_update_rate)
    np.testing.assert_allclose(np.asarray(new_bias), np.asarray(want),
                               atol=1e-7)
    assert set(rec) >= {"loss", "grad_norm", "group_norms", "moe_pairs"}
    assert "mtp_loss" not in rec
    assert int(rec["moe_dropped_pairs"][0]) == 0
    assert int(rec["moe_pairs_here"][0]) == int(rec["moe_pairs"][0])


# -- 4. packing meets the convolution ----------------------------------------


def _packed_and_alone(c):
    a, b = _histories(3, n=2)[:2]
    a, b = a[:30], b[:25]
    both = seq_backbone.pack_histories([a, b], 64, 1, seed=0)
    assert both.counters["sequences"] == 1
    alone = seq_backbone.pack_histories([b], 64, 1, seed=0)
    seg_of_b = both.seg[0][np.flatnonzero(both.tokens[0] == b[0])[0]]
    assert seg_of_b == 2          # b lies BEHIND a: its taps could cross
    return both, alone, both.seg[0] == seg_of_b, b.size


def _both_logits(params, bias, c, both, alone):
    def logits(packed):
        batch = {k: jnp.asarray(getattr(packed, k)) for k in lfm.BATCH_KEYS}
        return np.asarray(_logits(params, bias, batch, c)[0])

    return logits(both), logits(alone)


@pytest.mark.parametrize("layer_types", [
    ["conv", "full_attention", "conv", "conv"], ["conv"] * 4,
    ["full_attention"] * 4], ids=["both_kinds", "conv_only", "attn_only"])
def test_a_history_reads_the_same_packed_or_alone(layer_types):
    """Neither attention nor a convolution's taps cross a segment's
    start: the logits of a history inside a packed sequence are those
    of the history alone — through both layer kinds, and each alone."""
    c = _config(matmul_dtype="float32", seqs_per_step=1,
                layer_types=layer_types)
    params, bias = lfm.BACKBONE.init_state(c, 7)
    both, alone, inside, n = _packed_and_alone(c)
    packed, single = _both_logits(params, bias, c, both, alone)
    np.testing.assert_allclose(packed[inside], single[:n], atol=2e-5)


def test_a_tap_that_crossed_a_segments_start_would_show(monkeypatch):
    """The same comparison with the taps' mask taken away (every row
    is told it lies deep inside its segment): the first rows of the
    second history now read the first one's last rows, and differ."""
    c = _config(matmul_dtype="float32", seqs_per_step=1)
    params, bias = lfm.BACKBONE.init_state(c, 7)
    both, alone, inside, n = _packed_and_alone(c)
    mix = lfm._conv_mix
    monkeypatch.setattr(lfm, "_conv_mix", lambda b, cc, u, taps, pos: mix(
        b, cc, u, taps, pos + taps.shape[0]))
    lfm.BACKBONE.logits_program.cache_clear()
    try:
        packed, single = _both_logits(params, bias, c, both, alone)
    finally:
        lfm.BACKBONE.logits_program.cache_clear()
    assert np.abs(packed[inside][:2] - single[:2]).max() > 1e-3


def test_conv_masked_taps_equals_the_count_by_hand():
    hist = _histories() + [[5], [7, 9]]     # one row is no history
    packed = seq_backbone.pack_histories(hist, 64, 2, seed=1)
    sizes = [n for row in packed.seg for n in np.bincount(row[row > 0])[1:]]
    assert 1 not in sizes or sizes.count(1) < len(sizes)
    # 3 a segment of two rows or more (2 at its first row, 1 at its
    # second), 2 a segment of one row
    by_hand = sum(3 if n >= 2 else 2 for n in sizes)
    assert lfm.conv_masked_taps(packed.pos, packed.seg, 3) == by_hand
    # and what the operator really zeroes: with unit taps and gates the
    # convolution of ones counts each row's live taps
    ones = jnp.ones(packed.tokens.shape + (1,))
    live = lfm._conv_mix(ones, ones, ones, jnp.ones((3, 1)),
                         jnp.asarray(packed.pos))[..., 0]
    real = packed.seg > 0
    assert int((3 - np.asarray(live))[real].sum()) == by_hand


# -- 5. the architecture object ----------------------------------------------


@pytest.mark.parametrize("over, match", [
    (dict(conv_bias=True), "conv_bias"),
    (dict(use_expert_bias=False), "use_expert_bias"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings"),
    (dict(n_shared_experts=1), "unknown architecture keys"),
    (dict(layer_types=["conv"] * 3), "3 layer_types for 4 layers"),
    (dict(layer_types=["conv", "mamba", "conv", "conv"]), "mamba"),
    (dict(num_key_value_heads=3), "key-value heads"),
    (dict(num_dense_layers=4), "leaves no expert layer"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_the_block_cannot_honour_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        _config(**over)


def test_the_table_names_both_backbones_and_refuses_others():
    assert seq_backbone.backbone("lfm2_moe") is lfm.BACKBONE
    assert seq_backbone.backbone(None).model_type == "glm4_moe_lite"
    assert lfm.BACKBONE.heads == ("loss",)
    assert "tgt2" not in lfm.BACKBONE.batch_keys
    with pytest.raises(ValueError, match="implemented are"):
        seq_backbone.backbone("mamba2")


# -- 6. through the template -------------------------------------------------

FACTORY = "predictionio_tpu.templates.sequentialrec.engine:engine_factory"


def _variant(epochs):
    return {"id": "default", "engineFactory": FACTORY,
            "datasource": {"params": {"appName": "LfmApp"}},
            "algorithms": [{"name": "seqrec", "params": {
                "epochs": epochs, "lr": 0.003, "seed": 5,
                "architecture": dict(ARCH, vocab_size=16, init_std=0.02,
                                     matmul_dtype="float32")}}]}


@pytest.fixture()
def lfm_app(storage):
    import datetime as dt

    from predictionio_tpu.data.event import Event

    app = storage.meta.create_app("LfmApp", "")
    storage.events.init_channel(app.id)
    t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
    for u in range(20):
        for t in range(14):
            storage.events.insert(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"i{(u + t) % 8}",
                event_time=t0 + dt.timedelta(minutes=t)), app.id)
    return app


def test_train_deploy_predict_returns_the_references_top_items(storage,
                                                               lfm_app):
    from predictionio_tpu.core.workflow import prepare_deploy, run_train
    from predictionio_tpu.utils import tracing

    iid = run_train(FACTORY, variant=_variant(2), storage=storage,
                    use_mesh=False)
    tree = tracing.last_verb("train.run")
    spans = {s["name"]: s.get("attrs") or {} for s in tree}
    assert {"seqrec.index", "seqrec.pack", "seqrec.init", "seqrec.fit",
            "seqrec.fetch", "model.serialize"} <= set(spans)
    # 20 histories of 14 rows: 3 taps each, one epoch and conv layer
    assert spans["seqrec.pack"]["conv_masked_taps"] == 20 * 3
    fit = spans["seqrec.fit"]
    assert (fit["backbone"], fit["conv_layers"], fit["attn_layers"]) == (
        "lfm2_moe", 3, 1)
    assert fit["moe_dropped_pairs"] == 0 and fit["losses_finite"]
    assert set(fit["grad_norms_first"]) == set(lfm.BACKBONE.grad_groups(_config()))
    assert not any(k.startswith("mtp") for k in fit)
    deployed = prepare_deploy(engine_factory=FACTORY, storage=storage,
                              instance_id=iid)
    model = deployed.models[0]
    assert model.model_type == "lfm2_moe"
    assert isinstance(model.hp, lfm.Lfm2Config)
    history = ["i0", "i1", "i2", "i3", "i4"]
    got = deployed.query({"history": history, "num": 3})["itemScores"]
    ids = jnp.asarray([model.item_ids[i] + 1 for i in history], jnp.int32)
    seq = {"tokens": ids, "seg": jnp.ones_like(ids),
           "pos": jnp.arange(ids.size, dtype=jnp.int32)}
    with jax.default_matmul_precision("highest"):
        logits, _ = ref.forward(model.params["params"],
                                model.params["bias"], seq,
                                dict(model.hp.__dict__), model.hp.held)
    scores = np.asarray(logits[-1])[1:len(model.item_ids) + 1]
    top = np.argsort(-scores)[:3]
    inv = model.item_ids.inverse()
    assert [s["item"] for s in got] == [inv[int(i)] for i in top]
    np.testing.assert_allclose([s["score"] for s in got], scores[top],
                               rtol=1e-4, atol=1e-5)


def test_a_saved_model_names_its_backbone_and_an_older_one_is_glm(
        storage, lfm_app):
    """The blob's head records ``model_type``; one saved before the
    backbones had a table (no such key) loads as ``glm4_moe_lite``."""
    from predictionio_tpu.models import glm4_moe_lite as glm
    from predictionio_tpu.templates.sequentialrec import engine as eng

    magic = eng._RAW_MAGIC

    def head_of(parts):
        blob = b"".join(parts)
        (n,) = struct.unpack("<Q", blob[len(magic):len(magic) + 8])
        at = len(magic) + 8
        return pickle.loads(blob[at:at + n]), blob[at + n:]

    algo = eng.SeqRecAlgorithm(eng.SeqRecAlgorithmParams())
    from predictionio_tpu.utils.bimap import BiMap

    for module, config, want in (
            (lfm, _config(vocab_size=16), "lfm2_moe"),
            (glm, glm.GlmConfig(hidden_size=64, intermediate_size=128,
                                moe_intermediate_size=32,
                                num_attention_heads=2, q_lora_rank=24,
                                kv_lora_rank=16, qk_nope_head_dim=8,
                                qk_rope_head_dim=8, v_head_dim=16,
                                n_routed_experts=2, num_experts_per_tok=2,
                                num_hidden_layers=2,
                                vocab_size=16), "glm4_moe_lite")):
        params, bias = module.BACKBONE.init_state(config, 1)
        model = eng.SeqRecModel(
            jax.device_get({"params": params, "bias": bias}),
            BiMap.string_int(f"i{i}" for i in range(8)), "LfmApp", config,
            algo.params, np.zeros(0, np.float32), config.model_type)
        parts = algo.save_model(model, None)
        head, arrays = head_of(parts)
        assert head["model_type"] == want
        assert algo.load_model(b"".join(parts), None).model_type == want
        if want == "glm4_moe_lite":
            del head["model_type"]                # as saved before PR 33
            old = pickle.dumps(head)
            loaded = algo.load_model(b"".join(
                [magic, struct.pack("<Q", len(old)), old, arrays]), None)
            assert loaded.model_type == "glm4_moe_lite"
            assert len(loaded.next_items(["i1", "i2"], 3)) == 3


def test_a_train_killed_after_an_epoch_resumes_to_the_same_parameters(
        tmp_path, monkeypatch):
    from predictionio_tpu.utils.checkpoint import TrainCheckpointer

    c = _config(matmul_dtype="float32", vocab_size=16, init_std=0.02)
    hist = [list((np.arange(14) + u) % 8 + 1) for u in range(20)]
    straight, losses = lfm.BACKBONE.train(hist, c, 2, 0.003, 5)
    steps = len(losses) // 2

    saves = []
    real_save = TrainCheckpointer.save

    def save_then_die(self, step, state):
        real_save(self, step, state)
        saves.append(step)
        self.close()
        raise KeyboardInterrupt("killed after the checkpoint")

    ckdir = str(tmp_path / "ck")
    monkeypatch.setattr(TrainCheckpointer, "save", save_then_die)
    with pytest.raises(KeyboardInterrupt):
        lfm.BACKBONE.train(hist, c, 2, 0.003, 5, checkpoint_dir=ckdir)
    monkeypatch.setattr(TrainCheckpointer, "save", real_save)
    assert saves == [1]           # between the blocks, never after the last
    resumed, rest = lfm.BACKBONE.train(hist, c, 2, 0.003, 5,
                                   checkpoint_dir=ckdir)
    assert len(rest) == steps     # only the second epoch ran
    assert TrainCheckpointer(ckdir).latest_step() == 1
    for a, b in zip(jax.tree.leaves(straight), jax.tree.leaves(resumed)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
