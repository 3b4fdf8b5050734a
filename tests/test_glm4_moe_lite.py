"""The ``glm4_moe_lite`` backbone of the ``sequentialrec`` template
against its plain reference (``benchmark/reference/
glm4_moe_lite_jnp.py``), on seeded random weights at a preset of hidden
64, 2 heads, 8 experts top-2 and 64-position sequences."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import glm4_moe_lite_jnp as ref  # noqa: E402

from predictionio_tpu.models import glm4_moe_lite as glm  # noqa: E402
from predictionio_tpu.ops import moe_dispatch  # noqa: E402
from tests.test_moe_dispatch import poisoned  # noqa: E402

ARCH = dict(
    model_type="glm4_moe_lite", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_attention_heads=2, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=8, ep_size=1, num_experts_per_tok=2,
    num_hidden_layers=3, vocab_size=50, seq_len=64, seqs_per_step=2,
    attn_block=32, token_chunk=64, init_std=0.2)

#: the comparison's limits with bfloat16 operands (what the cell's
#: configuration states), at the configuration's init_std of 0.02: the
#: stated precision reads 0.0029 / 0.0036 (the two heads' logits,
#: rms(diff)/rms) and 7e-6 (loss); bfloat16 EVERYWHERE reads 0.026 /
#: 0.017 and 1e-4 — each limit between its two readings
BF16_LOGITS_REL_RMS = 0.008
BF16_LOSS_ABS = 3e-5


def _config(**over):
    return glm.GlmConfig.from_architecture(dict(ARCH, **over))


def _histories(seed=0, n=12, top=50):
    rng = np.random.default_rng(seed)
    return ([rng.integers(1, top, rng.integers(3, 40)) for _ in range(n)]
            + [rng.integers(1, top, 100)])


def _setup(c, seed=3):
    packed = glm.pack_histories(_histories(), c.seq_len, c.seqs_per_step,
                                seed=1)
    params, bias = glm.BACKBONE.init_state(c, seed)
    # a bias that matters: selection differs from the plain top-k
    bias = jax.tree.map(
        lambda b: 0.05 * jax.random.normal(jax.random.PRNGKey(1), b.shape),
        bias)
    batch = {k: jnp.asarray(getattr(packed, k)[:c.seqs_per_step])
             for k in glm.BATCH_KEYS}
    return packed, params, bias, batch


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _logits(params, bias, batch, c):
    """The program's two heads, through its own jitted entry point."""
    return glm.BACKBONE.sequence_logits({"params": params, "bias": bias}, batch, c)


def _ref_logits(params, bias, batch, c, **kw):
    @jax.jit
    def run(params, bias, batch):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(lambda s: ref.forward(
                params, bias, s, dict(c.__dict__), c.held, **kw)[:2])(batch)

    return run(params, bias, batch)


# -- 1. the system against the reference -------------------------------------


@pytest.fixture(scope="module")
def exact():
    """The system with float32 operands and the reference, once."""
    c = _config(matmul_dtype="float32")
    packed, params, bias, batch = _setup(c)
    (loss, rec), grads = jax.jit(lambda p, b, bt: jax.value_and_grad(
        glm.loss_fn, has_aux=True)(p, b, bt, c))(params, bias, batch)
    (rloss, (ce, ce_mtp, loads)), rgrads = jax.jit(
        lambda w, b, bt: ref.loss_and_grads(w, b, bt, dict(c.__dict__)))(
            params, bias, batch)
    return dict(c=c, params=params, bias=bias, batch=batch, loss=loss,
                rec=rec, grads=grads, rloss=rloss, ce=ce, ce_mtp=ce_mtp,
                loads=loads, rgrads=rgrads)


@pytest.mark.parametrize("head", [0, 1], ids=["next_item", "mtp"])
def test_logits_match_reference(exact, head):
    c = exact["c"]
    got = _logits(exact["params"], exact["bias"], exact["batch"], c)
    want = _ref_logits(exact["params"], exact["bias"], exact["batch"], c)
    assert _rel(got[head], want[head]) < 1e-5


def test_loss_matches_reference(exact):
    assert abs(float(exact["loss"]) - float(exact["rloss"])) < 1e-5
    assert abs(float(exact["rec"]["loss"]) - float(exact["ce"])) < 1e-5
    assert abs(float(exact["rec"]["mtp_loss"])
               - float(exact["ce_mtp"])) < 1e-5
    np.testing.assert_array_equal(np.asarray(exact["rec"]["moe"]["load"]),
                                  np.asarray(exact["loads"]))


_LEAVES = [glm._path_name(p) for p, _ in jax.tree_util.tree_flatten_with_path(
    glm.param_shapes(_config()), is_leaf=glm._is_shape)[0]]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_leaf_matches_reference(exact, leaf):
    got = dict((glm._path_name(p), g) for p, g in
               jax.tree_util.tree_flatten_with_path(exact["grads"])[0])
    want = dict((glm._path_name(p), g) for p, g in
                jax.tree_util.tree_flatten_with_path(exact["rgrads"])[0])
    assert got[leaf].shape == want[leaf].shape
    assert float(jnp.linalg.norm(want[leaf])) > 0
    assert _rel(got[leaf], want[leaf]) < 2e-5


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
    want = _ref_logits(params, bias, batch, c)
    rloss = _ref_loss(params, bias, batch, c)
    return dict(c=c, params=params, bias=bias, batch=batch, want=want,
                rloss=rloss)


def test_stated_precision_within_its_limits(stated):
    """bfloat16 operands, float32 accumulation: inside the limits that
    the lower precision of test 5 breaks."""
    c = stated["c"]
    got = _logits(stated["params"], stated["bias"], stated["batch"], c)
    assert max(_rel(g, w) for g, w in zip(got, stated["want"])
               ) < BF16_LOGITS_REL_RMS
    loss, _ = jax.jit(lambda p, b, bt: glm.loss_fn(p, b, bt, c))(
        stated["params"], stated["bias"], stated["batch"])
    assert abs(float(loss) - stated["rloss"]) < BF16_LOSS_ABS


# -- 2. the shares add up ----------------------------------------------------


def test_expert_shares_add_up_to_the_whole_layer():
    """The MoE layer run 8 times, each told it holds a different eighth
    of the experts, the shared expert counted once: the sum is the
    uncut reference's output for the whole layer."""
    whole = _config(matmul_dtype="float32")
    params, bias = glm.BACKBONE.init_state(whole, 5)
    w = jax.tree.map(lambda a: a[0], params["moe"])
    x = jax.random.normal(jax.random.PRNGKey(2), (128, whole.hidden_size))
    valid = jnp.ones(128, bool)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(w, x, valid.astype(x.dtype), bias[0], None,
                          dict(whole.__dict__))
        shared = ref.swiglu(w["shared"], x)
    total = -7 * shared          # eight shares each add the shared expert
    for rank in range(8):
        share = _config(matmul_dtype="float32", n_routed_experts=1,
                        ep_size=8, ep_rank=rank)
        mine = dict(w, experts=jax.tree.map(lambda a: a[rank:rank + 1],
                                            w["experts"]))
        part, stats = jax.jit(lambda w, x, b, share=share: glm._moe(
            w, x, valid, b, share))(mine, x, bias[0])
        assert int(stats["dropped"]) == 0
        total = total + part
    assert _rel(total, want) < 1e-5


# -- 3. routing --------------------------------------------------------------


def _skewed(c, T=128):
    """Every token to experts 0 and 1: router columns 0, 1 dominate."""
    x = jax.random.normal(jax.random.PRNGKey(3), (T, c.hidden_size))
    scores = jnp.full((T, c.router_experts), 0.1).at[:, :2].set(0.9)
    ids, gates = moe_dispatch.route(scores, jnp.zeros(c.router_experts),
                                    c.num_experts_per_tok, 1.8)
    return x, ids, gates


def test_no_pair_dropped_under_a_skewed_router():
    c = _config(matmul_dtype="float32")
    x, ids, gates = _skewed(c)
    p = moe_dispatch.plan(ids, c.held, c.router_experts)
    assert int(p.pairs_here) == 128 * 2 == int(p.rows)
    np.testing.assert_array_equal(np.asarray(p.group_sizes),
                                  [128, 128, 0, 0, 0, 0, 0, 0])


@pytest.mark.parametrize("held", [tuple(range(8)), (0, 1), (2, 5, 7), (1,)])
def test_grouped_product_equals_masked_dense(held):
    c = _config(matmul_dtype="float32")
    key = jax.random.split(jax.random.PRNGKey(4), 5)
    T, d, f = 128, c.hidden_size, c.moe_intermediate_size
    x = jax.random.normal(key[0], (T, d))
    scores = jax.nn.sigmoid(jax.random.normal(key[1], (T, 8)))
    wg = 0.1 * jax.random.normal(key[2], (len(held), d, f))
    wu = 0.1 * jax.random.normal(key[3], (len(held), d, f))
    wd = 0.1 * jax.random.normal(key[4], (len(held), f, d))
    ids, gates = moe_dispatch.route(scores, jnp.zeros(8), 2, 1.8)

    def sparse(x, wg, wu, wd):
        p = moe_dispatch.plan(ids, held, 8)
        return moe_dispatch.experts_swiglu(x, wg, wu, wd, gates, p)

    def dense(x, wg, wu, wd):
        out = 0.0
        for j, e in enumerate(held):
            gate = jnp.where(ids == e, gates, 0.0).sum(1)[:, None]
            out = out + gate * ((jax.nn.silu(x @ wg[j]) * (x @ wu[j]))
                                @ wd[j])
        return out

    def both(fn):
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            lambda *a: (fn(*a) ** 2).sum(), (0, 1, 2, 3))(*a)))

    with jax.default_matmul_precision("highest"):
        out, got = both(sparse)(x, wg, wu, wd)
        ref_out, want = both(dense)(x, wg, wu, wd)
    assert _rel(out, ref_out) < 1e-5
    for g, w in zip(got, want):
        assert _rel(g, w) < 1e-5


def test_rows_behind_the_groups_may_hold_anything(monkeypatch):
    """What the buffer held behind the groups (NaN here) reaches no
    result and no gradient — the router's included, through the gates."""
    T, d, f, held = 128, 64, 32, (2, 5, 7)
    key = jax.random.split(jax.random.PRNGKey(6), 5)
    x = jax.random.normal(key[0], (T, d))
    logits = jax.random.normal(key[1], (T, 8))
    w = [0.1 * jax.random.normal(k, shape) for k, shape in zip(
        key[2:], [(3, d, f), (3, d, f), (3, f, d)])]

    def layer(x, logits, wg, wu, wd):
        ids, gates = moe_dispatch.route(jax.nn.sigmoid(logits),
                                        jnp.zeros(8), 2, 1.8)
        p = moe_dispatch.plan(ids, held, 8)
        return moe_dispatch.experts_swiglu(x, wg, wu, wd, gates, p)

    def run():
        return jax.jit(jax.value_and_grad(
            lambda *a: (layer(*a) ** 2).sum(), (0, 1, 2, 3, 4)))(x, logits,
                                                                  *w)

    want = run()
    monkeypatch.setattr(moe_dispatch, "grouped_matmul",
                        poisoned(moe_dispatch.grouped_matmul))
    got = run()
    for g, wnt in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.isfinite(g).all())
        assert _rel(g, wnt) < 1e-5


def test_padding_tokens_reach_no_expert():
    c = _config(matmul_dtype="float32")
    _, ids, _ = _skewed(c)
    valid = jnp.arange(128) < 100
    p = moe_dispatch.plan(ids, c.held, c.router_experts, valid)
    assert int(p.pairs_here) == 200


def test_router_bias_takes_no_gradient_and_moves_by_the_rule(exact):
    c = exact["c"]
    g = jax.jit(jax.grad(lambda b: glm.loss_fn(
        exact["params"], b, exact["batch"], c)[0]))(exact["bias"])
    assert all(float(jnp.abs(x).max()) == 0.0 for x in jax.tree.leaves(g))
    # one train step: b moves by γ·sign(mean load − load), every expert
    from predictionio_tpu.models.seq_rec import _make_tx

    program = glm.BACKBONE.train_program(c, 1)
    opt = _make_tx().init(exact["params"])
    copy = jax.tree.map(jnp.array, (exact["params"], opt, exact["bias"]))
    data = {k: v[None] for k, v in exact["batch"].items()}
    (_, _, new_bias), rec = program(copy, data)
    want = ref.bias_update(exact["bias"], exact["loads"],
                           c.bias_update_rate)
    np.testing.assert_allclose(np.asarray(new_bias), np.asarray(want),
                               atol=1e-7)
    assert int(rec["moe_dropped_pairs"][0]) == 0
    assert int(rec["moe_pairs_here"][0]) == int(rec["moe_pairs"][0])


# -- 4. packing --------------------------------------------------------------


def test_packing_counters_equal_the_count_by_hand():
    hist = _histories()
    packed = glm.pack_histories(hist, 64, 2, seed=1)
    real = sum(len(h) for h in hist)
    n_seq = -(-real // 64)
    n_seq += n_seq % 2
    assert packed.counters["real_tokens"] == real == int(
        (packed.tokens > 0).sum())
    assert packed.counters["slots"] == n_seq * 64 == packed.tokens.size
    assert packed.counters["histories"] == len(hist)
    assert packed.counters["split"] >= 1          # the 100-item history
    assert packed.counters["attn_pairs"] == sum(
        n * (n + 1) // 2 for row in packed.seg for n in
        np.bincount(row[row > 0])[1:])
    # every history's items are all there, in order inside each segment
    assert sorted(packed.tokens[packed.tokens > 0].tolist()) == sorted(
        int(i) for h in hist for i in h)


def test_positions_restart_and_targets_stop_at_a_segments_end():
    packed = glm.pack_histories(_histories(), 64, 2, seed=1)
    for tok, seg, pos, t1, t2 in zip(packed.tokens, packed.seg, packed.pos,
                                     packed.tgt1, packed.tgt2):
        for s in np.unique(seg[seg > 0]):
            at = np.flatnonzero(seg == s)
            assert (np.diff(at) == 1).all()
            np.testing.assert_array_equal(pos[at], np.arange(at.size))
            np.testing.assert_array_equal(t1[at][:-1], tok[at][1:])
            assert t1[at][-1] == 0
            np.testing.assert_array_equal(t2[at][:-2], tok[at][2:])
            assert (t2[at][-2:] == 0).all()
        assert (t1[seg == 0] == 0).all() and (t2[seg == 0] == 0).all()


def test_a_history_longer_than_the_sequence_is_split():
    long = np.arange(1, 151) % 49 + 1
    packed = glm.pack_histories([long], 64, 1, seed=0)
    assert packed.counters["split"] == 1
    assert packed.counters["sequences"] == 3
    assert sorted(np.bincount(packed.seg[packed.seg > 0].ravel() * 0
                              + np.repeat(np.arange(3), 64)[
                                  (packed.seg > 0).ravel()]).tolist()) == [
        22, 64, 64]


@pytest.mark.parametrize("head", [0, 1], ids=["next_item", "mtp"])
def test_a_history_reads_the_same_packed_or_alone(head):
    """Segments never attend across users: the logits of a history
    inside a packed sequence are those of the history alone."""
    c = _config(matmul_dtype="float32", seqs_per_step=1)
    params, bias = glm.BACKBONE.init_state(c, 7)
    a, b = _histories(3, n=2)[:2]
    a, b = a[:30], b[:25]
    both = glm.pack_histories([a, b], 64, 1, seed=0)
    assert both.counters["sequences"] == 1
    alone = glm.pack_histories([b], 64, 1, seed=0)

    def logits(packed):
        batch = {k: jnp.asarray(getattr(packed, k)) for k in glm.BATCH_KEYS}
        return np.asarray(_logits(params, bias, batch, c)[head][0])

    seg_of_b = both.seg[0][np.flatnonzero(both.tokens[0] == b[0])[0]]
    inside = logits(both)[both.seg[0] == seg_of_b]
    np.testing.assert_allclose(inside, logits(alone)[:b.size], atol=2e-5)


# -- 5. the limits catch a lower precision -----------------------------------


def test_lower_precision_fails(stated):
    """The reference computed in bfloat16 THROUGHOUT (router scores,
    softmax, norms, accumulation — the nearest precision below the
    stated one) breaks the limits that the stated precision keeps."""
    c = stated["c"]
    low = _ref_logits(stated["params"], stated["bias"], stated["batch"], c,
                      dtype=jnp.bfloat16)
    assert min(_rel(g, w) for g, w in zip(low, stated["want"])
               ) > BF16_LOGITS_REL_RMS
    loss = _ref_loss(stated["params"], stated["bias"], stated["batch"], c,
                     dtype=jnp.bfloat16)
    assert abs(loss - stated["rloss"]) > BF16_LOSS_ABS


# -- 6. through the template -------------------------------------------------

FACTORY = "predictionio_tpu.templates.sequentialrec.engine:engine_factory"


def _variant(epochs):
    return {"id": "default", "engineFactory": FACTORY,
            "datasource": {"params": {"appName": "GlmApp"}},
            "algorithms": [{"name": "seqrec", "params": {
                "epochs": epochs, "lr": 0.003, "seed": 5,
                "architecture": dict(ARCH, vocab_size=16, init_std=0.02,
                                     matmul_dtype="float32")}}]}


@pytest.fixture()
def glm_app(storage):
    import datetime as dt

    from predictionio_tpu.data.event import Event

    app = storage.meta.create_app("GlmApp", "")
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
                                                               glm_app):
    from predictionio_tpu.core.workflow import prepare_deploy, run_train
    from predictionio_tpu.utils import tracing

    iid = run_train(FACTORY, variant=_variant(2), storage=storage,
                    use_mesh=False)
    names = {s["name"] for s in tracing.last_verb("train.run")}
    assert {"seqrec.index", "seqrec.pack", "seqrec.init", "seqrec.fit",
            "seqrec.fetch", "model.serialize"} <= names
    deployed = prepare_deploy(engine_factory=FACTORY, storage=storage,
                              instance_id=iid)
    model = deployed.models[0]
    history = ["i0", "i1", "i2", "i3", "i4"]
    got = deployed.query({"history": history, "num": 3})["itemScores"]
    ids = jnp.asarray([model.item_ids[i] + 1 for i in history], jnp.int32)
    seq = {"tokens": ids, "seg": jnp.ones_like(ids),
           "pos": jnp.arange(ids.size, dtype=jnp.int32),
           "tgt1": jnp.zeros_like(ids)}
    with jax.default_matmul_precision("highest"):
        logits, _, _ = ref.forward(model.params["params"],
                                   model.params["bias"], seq,
                                   dict(model.hp.__dict__), model.hp.held)
    scores = np.asarray(logits[-1])[1:len(model.item_ids) + 1]
    top = np.argsort(-scores)[:3]
    inv = model.item_ids.inverse()
    assert [s["item"] for s in got] == [inv[int(i)] for i in top]
    np.testing.assert_allclose([s["score"] for s in got], scores[top],
                               rtol=1e-4, atol=1e-5)


def test_a_train_killed_after_an_epoch_resumes_to_the_same_parameters(
        tmp_path, monkeypatch):
    from predictionio_tpu.utils.checkpoint import TrainCheckpointer

    c = _config(matmul_dtype="float32", vocab_size=16, init_std=0.02)
    hist = [list((np.arange(14) + u) % 8 + 1) for u in range(20)]
    straight, losses = glm.BACKBONE.train(hist, c, 2, 0.003, 5)
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
        glm.BACKBONE.train(hist, c, 2, 0.003, 5, checkpoint_dir=ckdir)
    monkeypatch.setattr(TrainCheckpointer, "save", real_save)
    assert saves == [1]           # between the blocks, never after the last
    resumed, rest = glm.BACKBONE.train(hist, c, 2, 0.003, 5, checkpoint_dir=ckdir)
    assert len(rest) == steps     # only the second epoch ran
    assert TrainCheckpointer(ckdir).latest_step() == 1
    for a, b in zip(jax.tree.leaves(straight), jax.tree.leaves(resumed)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
