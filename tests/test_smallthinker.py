"""The ``smallthinker`` backbone of the ``sequentialrec`` template against
its plain reference (``benchmark/reference/smallthinker_jnp.py``), on
seeded random weights at a preset of hidden 64, 4 query heads over 2
key-value heads of 16, 8 ReGLU experts top-3, 64-position sequences, a
window of 24 keys and one period of four layers (1 × global, 3 ×
window)."""

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

from reference import smallthinker_jnp as ref  # noqa: E402

from predictionio_tpu.models import seq_backbone  # noqa: E402
from predictionio_tpu.models import smallthinker as st  # noqa: E402
from predictionio_tpu.ops import moe_dispatch, seq_attention  # noqa: E402
from tests.kernel_calls import kernel_calls  # noqa: E402
from tests.test_chip_compile import _smallthinker_cell_config  # noqa: E402

ARCH = dict(
    model_type="smallthinker", hidden_size=64, head_dim=16,
    num_attention_heads=4, num_key_value_heads=2, moe_ffn_hidden_size=32,
    moe_num_primary_experts=8, ep_size=1, moe_num_active_primary_experts=3,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    num_hidden_layers=4, sliding_window_layout=[0, 1, 1, 1],
    rope_layout=[0, 1, 1, 1], sliding_window_size=24, rope_theta=1.5e6,
    tie_word_embeddings=False, vocab_size=50, seq_len=64, seqs_per_step=2,
    attn_block=32, token_chunk=64, init_std=0.2)

#: the comparison's limits with bfloat16 operands (what the cell's
#: configuration states), at the configuration's init_std of 0.02: the
#: stated precision reads 0.0072 (the logits, rms(diff)/rms; 0.0050 …
#: 0.0072 over four seeds), bfloat16 EVERYWHERE 0.0111 (0.0077 …
#: 0.0111; always 1.3 … 1.8 times the stated one's on the same seed) —
#: the limit between the two of the seed the test uses. The loss hardly
#: moves with the precision at this size (6e-5): its limit only catches
#: a wrong loss
BF16_LOGITS_REL_RMS = 0.009
BF16_LOSS_ABS = 1e-3


def _config(**over):
    return st.SmallThinkerConfig.from_architecture(dict(ARCH, **over))


def _histories(seed=0, n=12, top=50):
    """Short histories and one of 100 events: longer than a sequence,
    so cut into a 64-row piece and a 36-row one, both longer than the
    window of 24."""
    rng = np.random.default_rng(seed)
    return ([rng.integers(1, top, rng.integers(3, 40)) for _ in range(n)]
            + [rng.integers(1, top, 100)])


def _setup(c, seed=3):
    packed = seq_backbone.pack_histories(_histories(), c.seq_len,
                                         c.seqs_per_step, seed=1,
                                         window=c.window)
    params, bias = st.BACKBONE.init_state(c, seed)
    batch = {k: jnp.asarray(getattr(packed, k)[:c.seqs_per_step])
             for k in st.BATCH_KEYS}
    return packed, params, bias, batch


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _logits(params, bias, batch, c):
    """The program's head, through its own jitted entry point."""
    return st.BACKBONE.sequence_logits({"params": params, "bias": bias}, batch, c)[0]


def _ref_logits(params, bias, batch, c, **kw):
    @jax.jit
    def run(params, bias, batch):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(lambda s: ref.forward(
                params, bias, s, dict(c.__dict__), c.held, **kw)[0])(batch)

    return run(params, bias, batch)


def _named(tree):
    return dict((seq_backbone._path_name(p), g) for p, g in
                jax.tree_util.tree_flatten_with_path(tree)[0])


# -- 1. the system against the reference -------------------------------------


@pytest.fixture(scope="module")
def exact():
    """The system with float32 operands and the reference, once."""
    c = _config(matmul_dtype="float32")
    packed, params, bias, batch = _setup(c)
    # the batch holds what the window is for: rows a window cuts
    assert (np.asarray(batch["pos"]) >= c.sliding_window_size).any()
    (loss, rec), grads = jax.jit(lambda p, b, bt: jax.value_and_grad(
        st.loss_fn, has_aux=True)(p, b, bt, c))(params, bias, batch)
    (rloss, loads), rgrads = jax.jit(
        lambda w, b, bt: ref.loss_and_grads(w, b, bt, dict(c.__dict__)))(
            params, bias, batch)
    return dict(c=c, params=params, bias=bias, batch=batch, loss=loss,
                rec=rec, grads=grads, rloss=rloss, loads=loads,
                rgrads=rgrads)


def test_the_stack_is_one_body_a_run_of_one_kind():
    c = _config()
    assert c.runs == (("global", 1), ("window", 3))
    shapes = st.param_shapes(c)
    assert [r["attn_norm"][0] for r in shapes["runs"]] == [1, 3]
    assert shapes["head"] == (64, 50) and shapes["embed"] == (50, 64)
    whole = _config(num_hidden_layers=52,
                    sliding_window_layout=[0, 1, 1, 1] * 13,
                    rope_layout=[0, 1, 1, 1] * 13)
    assert whole.runs == (("global", 1), ("window", 3)) * 13
    assert _config(sliding_window_layout=[0] * 4,
                   rope_layout=[0] * 4).window is None


def test_parameter_count_of_the_benchmarks_share():
    """ISSUE 38's arithmetic: 370,547,200 parameters."""
    c = st.SmallThinkerConfig.from_architecture(dict(
        moe_num_primary_experts=8, ep_size=8, vocab_size=18992))
    assert st.BACKBONE.n_params(c) == 370_547_200
    assert c.held == tuple(range(8)) and c.router_experts == 64
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, c.moe_ffn_hidden_size, c.num_experts_per_tok,
            c.sliding_window_size, c.rope_theta, c.rms_norm_eps) == (
                2560, 28, 4, 128, 768, 6, 4096, 1.5e6, 1e-6)


def test_logits_match_reference(exact):
    c = exact["c"]
    got = _logits(exact["params"], exact["bias"], exact["batch"], c)
    want = _ref_logits(exact["params"], exact["bias"], exact["batch"], c)
    assert _rel(got, want) < 1e-5


def test_loss_matches_reference(exact):
    assert abs(float(exact["loss"]) - float(exact["rloss"])) < 1e-5
    assert abs(float(exact["rec"]["loss"]) - float(exact["rloss"])) < 1e-5
    assert exact["rec"]["moe"]["load"].shape == (4, 8)   # every layer
    np.testing.assert_array_equal(np.asarray(exact["rec"]["moe"]["load"]),
                                  np.asarray(exact["loads"]))


_LEAVES = [seq_backbone._path_name(p) for p, _ in jax.tree_util.tree_flatten_with_path(
    st.param_shapes(_config()), is_leaf=seq_backbone._is_shape)[0]]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_leaf_matches_reference(exact, leaf):
    got, want = _named(exact["grads"]), _named(exact["rgrads"])
    assert got[leaf].shape == want[leaf].shape
    assert float(jnp.linalg.norm(want[leaf])) > 0
    assert _rel(got[leaf], want[leaf]) < 2e-5


def test_every_leaf_has_a_group_and_the_groups_are_the_parts(exact):
    groups = st.BACKBONE.grad_groups(_config())
    assert groups == ("attn", "embed", "experts", "head", "norms", "router")
    assert {st.group_of(leaf) for leaf in _LEAVES} == set(groups)
    got = jax.jit(st.group_squares)(exact["grads"])
    want = jax.jit(st.group_squares)(exact["rgrads"])
    for g in groups:
        assert float(want[g]) > 0
        assert abs(float(got[g]) ** 0.5 / float(want[g]) ** 0.5 - 1) < 2e-5


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
    """bfloat16 operands, float32 accumulation, float32 router, softmax,
    norms and RoPE: inside the limits that the lower precision below
    breaks."""
    c = stated["c"]
    got = _logits(stated["params"], stated["bias"], stated["batch"], c)
    assert _rel(got, stated["want"]) < BF16_LOGITS_REL_RMS
    loss, _ = jax.jit(lambda p, b, bt: st.loss_fn(p, b, bt, c))(
        stated["params"], stated["bias"], stated["batch"])
    assert abs(float(loss) - stated["rloss"]) < BF16_LOSS_ABS


def test_lower_precision_fails(stated):
    """The reference computed in bfloat16 THROUGHOUT (router logits,
    softmax, norms, RoPE, accumulation — the nearest precision below
    the stated one) breaks the limit the stated precision keeps."""
    low = _ref_logits(stated["params"], stated["bias"], stated["batch"],
                      stated["c"], dtype=jnp.bfloat16)
    assert _rel(low, stated["want"]) > BF16_LOGITS_REL_RMS


# -- 2. the router: where it reads, how it gates ------------------------------


def _one_layer(c, seed=5):
    params, _ = st.BACKBONE.init_state(c, seed)
    w = jax.tree.map(lambda a: a[0], params["runs"][1])     # a window layer
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(1, 64, c.hidden_size)), jnp.float32)
    seg = jnp.asarray(np.r_[np.full(40, 1), np.full(20, 2), np.zeros(4)],
                      jnp.int32)[None]
    pos = jnp.asarray(np.r_[np.arange(40), np.arange(20), np.zeros(4)],
                      jnp.int32)[None]
    return w, x, seg, pos


def _ref_layer(w, x, seg, pos, c, windowed=True):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda w, x: ref.layer(
            w, x, seg[0], pos[0], c.held, dict(c.__dict__), windowed))(
                w, x[0])


@pytest.mark.parametrize("fed", ["post_attention", "normed_input"])
def test_a_router_fed_the_wrong_rows_would_show(monkeypatch, fed):
    """The layer against the reference's layer — equal as written; with
    the router fed the post-attention rows (where the other backbones
    route) or the NORMED input, the selection and the result differ."""
    c = _config(matmul_dtype="float32")
    w, x, seg, pos = _one_layer(c)
    # gains that are not all one: a norm then turns the rows, and the
    # selection moves with them
    w = dict(w, attn_norm=jnp.asarray(np.random.default_rng(8).uniform(
        0.2, 3.0, c.hidden_size), jnp.float32))
    want, want_load = _ref_layer(w, x, seg, pos, c)
    real = np.asarray(seg[0]) > 0
    got, stats = jax.jit(lambda w, x: st._layer(w, x, seg, pos, c,
                                                "window"))(w, x)
    assert _rel(got[0][real], want[real]) < 1e-5
    np.testing.assert_array_equal(np.asarray(stats["load"]),
                                  np.asarray(want_load))

    route = seq_backbone._route

    def wrong_rows(router, rows, valid, bias, c, softmax=False):
        B, S, d = x.shape
        if fed == "normed_input":
            rows = st._rms(rows, w["attn_norm"], c.rms_norm_eps)
        else:
            h = x + st._attend(w["attn"], st._rms(
                x, w["attn_norm"], c.rms_norm_eps), seg, pos, c, "window")
            rows = h.reshape(B * S, d)
        return route(router, rows, valid, bias, c, softmax)

    monkeypatch.setattr(st, "_route", wrong_rows)
    bad, bad_stats = jax.jit(lambda w, x: st._layer(w, x, seg, pos, c,
                                                    "window"))(w, x)
    assert _rel(bad[0][real], want[real]) > 1e-2
    assert (np.asarray(bad_stats["load"]) != np.asarray(want_load)).any()


def test_gates_are_a_softmax_over_the_selected_and_sum_to_one():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(40, 16)) * 3, jnp.float32)
    ids, gates = moe_dispatch.route_softmax(logits, 5)
    assert ids.dtype == jnp.int32 and ids.shape == gates.shape == (40, 5)
    np.testing.assert_allclose(np.asarray(gates.sum(1)), 1.0, atol=1e-6)
    order = np.argsort(-np.asarray(logits), axis=1)[:, :5]
    np.testing.assert_array_equal(np.sort(np.asarray(ids), 1),
                                  np.sort(order, 1))
    picked = np.take_along_axis(np.asarray(logits), np.asarray(ids), 1)
    want = np.exp(picked - picked.max(1, keepdims=True))
    np.testing.assert_allclose(np.asarray(gates),
                               want / want.sum(1, keepdims=True), atol=1e-6)


def test_the_gates_carry_the_softmaxs_gradient_to_the_router():
    """∂(Σ c·gate)/∂logits: softmax's Jacobian on the selected, zero on
    the unselected — against the dense form by ``jax.grad``."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(12, 8)), jnp.float32)
    coef = jnp.asarray(rng.normal(size=(12, 3)), jnp.float32)
    got = jax.grad(lambda lg: (moe_dispatch.route_softmax(lg, 3)[1]
                               * coef).sum())(logits)
    ids, gates = moe_dispatch.route_softmax(logits, 3)

    def dense(lg):
        mask = jax.nn.one_hot(ids, 8).sum(1) > 0
        p = jax.nn.softmax(jnp.where(mask, lg, -jnp.inf), axis=-1)
        return (jnp.take_along_axis(p, ids, 1) * coef).sum()

    want = jax.grad(dense)(logits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    unselected = np.asarray(jax.nn.one_hot(ids, 8).sum(1) == 0)
    assert not np.asarray(got)[unselected].any()
    # each row's gradient sums to zero: the gates sum to one
    np.testing.assert_allclose(np.asarray(got).sum(1), 0.0, atol=1e-6)


def test_the_experts_are_relu_gated_units():
    """The same dispatch with ReLU against the plain ReGLU of every
    expert on every token — and it is NOT the SiLU unit's result."""
    c = _config(matmul_dtype="float32")
    w, x, seg, _ = _one_layer(c)
    rows = x[0]
    valid = seg[0] > 0
    gates, plan, _ = seq_backbone._route(w["router"], rows, valid, None, c,
                                         softmax=True)
    got = jax.jit(lambda w, m: seq_backbone._experts(
        w, m, gates, plan, c, act=jax.nn.relu))(w, rows)
    silu = jax.jit(lambda w, m: seq_backbone._experts(
        w, m, gates, plan, c))(w, rows)
    with jax.default_matmul_precision("highest"):
        gate, _ = ref.route(w["router"], rows, valid.astype(rows.dtype), 3)
        want = ref.experts(w["experts"], rows, gate, c.held)
    real = np.asarray(valid)
    assert _rel(got[real], want[real]) < 1e-5
    assert _rel(silu[real], want[real]) > 1e-2
    assert not np.asarray(got)[~real].any()


# -- 3. the shares add up ----------------------------------------------------


def test_eight_shares_of_eight_experts_add_up_to_the_whole_layer():
    """The layer run 8 times, each told it holds a different eighth of
    64 experts (``ep_rank`` 0 … 7): the sum of the experts' parts, with
    what every chip computes alike (the input, attention and its
    residual) counted once, is the uncut reference's layer."""
    whole = _config(matmul_dtype="float32", moe_num_primary_experts=64,
                    moe_num_active_primary_experts=6)
    w, x, seg, pos = _one_layer(whole)
    with jax.default_matmul_precision("highest"):
        want, load = jax.jit(lambda w, x: ref.layer(
            w, x, seg[0], pos[0], None, dict(whole.__dict__), True))(w, x[0])
        attended = x[0] + ref.attend(
            w["attn"], ref.rms_norm(x[0], w["attn_norm"], 1e-6), seg[0],
            pos[0], dict(whole.__dict__), True)
    total, pairs = attended, 0
    for rank in range(8):
        share = _config(matmul_dtype="float32", moe_num_primary_experts=8,
                        ep_size=8, ep_rank=rank,
                        moe_num_active_primary_experts=6)
        assert share.router_experts == 64
        assert share.held == tuple(range(8 * rank, 8 * rank + 8))
        mine = dict(w, experts=jax.tree.map(
            lambda a: a[8 * rank:8 * rank + 8], w["experts"]))
        out, stats = jax.jit(lambda w, x, share=share: st._layer(
            w, x, seg, pos, share, "window"))(mine, x)
        assert int(stats["dropped"]) == 0
        np.testing.assert_array_equal(np.asarray(stats["load"]),
                                      np.asarray(load))
        pairs += int(stats["pairs_here"])
        total = total + (out[0] - attended)
    real = np.asarray(seg[0]) > 0
    assert pairs == 60 * 6
    assert _rel(total[real], want[real]) < 1e-5


def test_no_pair_dropped_under_a_skewed_router():
    """A router column so large that every token picks expert 0: the
    layer keeps every pair and reports the skew."""
    c = _config(matmul_dtype="float32")
    w, x, seg, _ = _one_layer(c)
    rows = jnp.abs(x[0]) + 0.1
    router = w["router"].at[:, 0].set(10.0)
    _, plan, stats = jax.jit(lambda r, m: seq_backbone._route(
        r, m, seg[0] > 0, None, c, softmax=True))(router, rows)
    assert int(stats["dropped"]) == 0
    assert int(stats["pairs_here"]) == int(stats["pairs"]) == 60 * 3
    assert int(stats["load"][0]) == 60
    assert float(stats["load_max_over_mean"]) == pytest.approx(60 / 22.5)


def test_the_step_has_no_router_bias_to_move(exact):
    """The train step every backbone shares carries a router bias: here
    it is zero, takes no gradient and never moves."""
    from predictionio_tpu.models.seq_rec import _make_tx

    c = exact["c"]
    assert c.bias_update_rate == 0.0
    assert not np.asarray(exact["bias"]).any()
    program = st.BACKBONE.train_program(c, 1)
    opt = _make_tx().init(exact["params"])
    copy = jax.tree.map(jnp.array, (exact["params"], opt, exact["bias"]))
    data = {k: v[None] for k, v in exact["batch"].items()}
    (_, _, new_bias), rec = program(copy, data)
    assert not np.asarray(new_bias).any()
    assert float(rec["router_bias_absmax"][0]) == 0.0
    assert int(rec["moe_dropped_pairs"][0]) == 0
    assert int(rec["moe_pairs_here"][0]) == int(rec["moe_pairs"][0])
    assert float(rec["loss"][0]) == pytest.approx(float(exact["loss"]),
                                                  abs=1e-6)


# -- 4. layer kinds: positions and the window ----------------------------------


def test_a_global_layer_has_no_positions_and_a_window_layer_has():
    """Shifting every position by a constant leaves a window layer's
    result (RoPE is relative) and a global layer's (it reads none)
    alone; SCALING them moves the window layer only."""
    c = _config(matmul_dtype="float32")
    w, x, seg, pos = _one_layer(c)
    real = np.asarray(seg[0]) > 0

    def run(kind, pos):
        return np.asarray(jax.jit(lambda w, x: st._layer(
            w, x, seg, pos, c, kind)[0])(w, x))[0][real]

    for kind in st.KINDS:
        np.testing.assert_allclose(run(kind, pos + 7), run(kind, pos),
                                   atol=2e-5)
    np.testing.assert_array_equal(run("global", pos * 3), run("global", pos))
    assert np.abs(run("window", pos * 3) - run("window", pos)).max() > 1e-3


def test_the_window_binds_in_the_window_layers_only():
    """A token 30 rows back in a 40-row segment, changed: a window layer
    (24 keys) leaves the segment's last row alone, a global layer does
    not."""
    c = _config(matmul_dtype="float32")
    w, x, seg, pos = _one_layer(c)
    moved = x.at[0, 9].add(1.0)                       # row 39 − 30

    def last_row(kind, x):
        return np.asarray(jax.jit(lambda w, x: st._layer(
            w, x, seg, pos, c, kind)[0])(w, x))[0, 39]

    np.testing.assert_array_equal(last_row("window", moved),
                                  last_row("window", x))
    assert np.abs(last_row("global", moved) - last_row("global", x)).max() \
        > 1e-5


def test_a_history_reads_the_same_packed_or_alone():
    """Neither kind of layer crosses a segment's start: the logits of a
    history inside a packed sequence are those of the history alone."""
    c = _config(matmul_dtype="float32", seqs_per_step=1)
    params, bias = st.BACKBONE.init_state(c, 7)
    a, b = _histories(3, n=2)[:2]
    a, b = a[:30], b[:29]
    both = seq_backbone.pack_histories([a, b], 64, 1, seed=0)
    alone = seq_backbone.pack_histories([b], 64, 1, seed=0)
    inside = both.seg[0] == both.seg[0][np.flatnonzero(
        both.tokens[0] == b[0])[-1]]
    assert inside.sum() == b.size and inside[-1] == 0 and not inside[0]

    def logits(packed):
        batch = {k: jnp.asarray(getattr(packed, k)) for k in st.BATCH_KEYS}
        return np.asarray(_logits(params, bias, batch, c)[0])

    np.testing.assert_allclose(logits(both)[inside], logits(alone)[:b.size],
                               atol=2e-5)


def test_what_the_global_turn_keeps_changes_no_number_of_a_step(
        exact, monkeypatch):
    """The GLOBAL run's turn keeps the attention kernel's output and
    log-sum-exp (``seq_attention.KEPT``), the WINDOW run's turn is a
    plain ``jax.checkpoint``; with the policy taken away here the step
    runs the global layer's forward kernel a second time and reads the
    same loss and records bit for bit, and the same gradients to the
    last places of a float32."""
    c, args = exact["c"], (exact["params"], exact["bias"], exact["batch"])
    step = lambda: jax.jit(lambda p, b, bt: jax.value_and_grad(  # noqa: E731
        st.loss_fn, has_aux=True)(p, b, bt, c))
    calls = kernel_calls(jax.make_jaxpr(step())(*args).jaxpr)
    # which run keeps: a stack of global layers alone holds ONE forward
    # call site (both branches of the platform's choice), a stack of
    # window layers alone two
    for layout, sites in (([0] * 4, 1), ([1] * 4, 2)):
        one = _config(matmul_dtype="float32", sliding_window_layout=layout,
                      rope_layout=layout)
        params, bias = jax.eval_shape(lambda: st.BACKBONE.init_state(one, 0))
        assert kernel_calls(jax.make_jaxpr(jax.grad(
            lambda p, b, bt: st.loss_fn(p, b, bt, one)[0]))(
                params, bias, args[2]).jaxpr)["seq_attention_fwd"] == (
                    2 * sites)
    asked = []
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: asked.append(names))
    plain_calls = kernel_calls(jax.make_jaxpr(step())(*args).jaxpr)
    (plain_loss, plain_rec), plain_grads = step()(*args)
    assert asked == [seq_attention.KEPT] * 2
    # two scanned bodies: forward, dq and dk/dv once each (both
    # branches of the platform's choice), and the WINDOW body's
    # recomputed forward; the plain step has the global body's too
    assert calls == {"seq_attention_fwd": 6, "seq_attention_dq": 4,
                     "seq_attention_dkv": 4}
    assert plain_calls - calls == {"seq_attention_fwd": 2}
    assert float(exact["loss"]) == float(plain_loss)
    jax.tree.map(np.testing.assert_array_equal, exact["rec"], plain_rec)
    plain_grads = _named(plain_grads)
    for name, g in _named(exact["grads"]).items():
        g, want = np.asarray(g), np.asarray(plain_grads[name])
        assert want.any(), name
        assert np.abs(g - want).max() <= 1e-6 * np.abs(want).max(), name


def test_the_bytes_the_global_turns_keep():
    """Per GLOBAL layer and sequence: rows × heads × (the head width
    in the products' dtype + one float32); a window layer keeps none."""
    # 1 global layer × 2 sequences × 64 rows × 4 heads × (16 × 2 B + 4 B)
    assert st.BACKBONE.fit_attrs(_config())["attn_kept_bytes"] == (
        2 * 64 * 4 * (32 + 4))
    assert st.attn_kept_bytes(_config(
        num_hidden_layers=8, sliding_window_layout=[0, 1, 1, 1] * 2,
        rope_layout=[0, 1, 1, 1] * 2)) == 2 * 2 * 64 * 4 * (32 + 4)
    assert st.attn_kept_bytes(_config(
        sliding_window_layout=[1] * 4, rope_layout=[1] * 4)) == 0
    # the cell: 1 global layer × 2 × 16,384 rows × 28 heads of 128
    c = _smallthinker_cell_config()
    assert (c.runs, c.seqs_per_step * c.seq_len, c.num_attention_heads,
            c.head_dim) == ((("global", 1), ("window", 3)), 32768, 28, 128)
    assert st.attn_kept_bytes(c) == 234_881_024 + 3_670_016 == 238_551_040


# -- 5. the architecture object ----------------------------------------------


@pytest.mark.parametrize("over, match", [
    (dict(moe_primary_router_apply_softmax=False),
     "moe_primary_router_apply_softmax"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
    (dict(n_shared_experts=1), "unknown architecture keys"),
    (dict(sliding_window_layout=[0, 1, 1]), "3 entries"),
    (dict(rope_layout=[1, 1, 1, 1]), "rope_layout differs"),
    (dict(sliding_window_layout=[0, 2, 1, 1], rope_layout=[0, 2, 1, 1]),
     "other than 0 and 1"),
    (dict(num_key_value_heads=3), "key-value heads"),
    (dict(moe_num_active_primary_experts=9), "top-9"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_the_block_cannot_honour_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        _config(**over)


def test_the_table_names_the_backbone():
    assert seq_backbone.backbone("smallthinker") is st.BACKBONE
    assert st.BACKBONE.heads == ("loss",)
    assert "tgt2" not in st.BACKBONE.batch_keys
    assert {"model_name", "max_position_embeddings", "norm_topk_prob",
            "rope_scaling"} <= st.SmallThinkerConfig.known_keys()
    with pytest.raises(ValueError, match="implemented are.*smallthinker"):
        seq_backbone.backbone("mamba2")


# -- 6. through the template -------------------------------------------------

FACTORY = "predictionio_tpu.templates.sequentialrec.engine:engine_factory"


def _variant(epochs):
    return {"id": "default", "engineFactory": FACTORY,
            "datasource": {"params": {"appName": "StApp"}},
            "algorithms": [{"name": "seqrec", "params": {
                "epochs": epochs, "lr": 0.003, "seed": 5,
                "architecture": dict(ARCH, vocab_size=16, init_std=0.02,
                                     seq_len=32, attn_block=16,
                                     token_chunk=32, sliding_window_size=8,
                                     matmul_dtype="float32")}}]}


@pytest.fixture()
def st_app(storage):
    import datetime as dt

    from predictionio_tpu.data.event import Event

    app = storage.meta.create_app("StApp", "")
    storage.events.init_channel(app.id)
    t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
    for u in range(20):
        for t in range(16):           # longer than the window of 8
            storage.events.insert(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"i{(u + t) % 8}",
                event_time=t0 + dt.timedelta(minutes=t)), app.id)
    return app


def test_train_deploy_predict_returns_the_references_top_items(storage,
                                                               st_app):
    from predictionio_tpu.core.workflow import prepare_deploy, run_train
    from predictionio_tpu.utils import tracing

    iid = run_train(FACTORY, variant=_variant(2), storage=storage,
                    use_mesh=False)
    tree = tracing.last_verb("train.run")
    spans = {s["name"]: s.get("attrs") or {} for s in tree}
    assert {"seqrec.index", "seqrec.pack", "seqrec.init", "seqrec.fit",
            "seqrec.fetch", "model.serialize"} <= set(spans)
    pack = spans["seqrec.pack"]
    # 20 histories of 16 rows (two a sequence, none cut) under a window
    # of 8: rows 8 … 15 are bound
    assert (pack["sequences"], pack["split"]) == (10, 0)
    assert pack["attn_pairs"] == 20 * 16 * 17 // 2
    assert pack["window_bound_tokens"] == 20 * 8
    assert pack["attn_pairs_window"] == 20 * (8 * 9 // 2 + 8 * 8)
    assert pack["attn_tile_pairs_window"] <= pack["attn_tile_pairs"]
    fit = spans["seqrec.fit"]
    assert (fit["backbone"], fit["window_layers"], fit["global_layers"]) == (
        "smallthinker", 3, 1)
    # 1 global layer × 2 sequences a step × 32 rows × 4 heads × (16
    # float32 + the log-sum-exp)
    assert fit["attn_kept_bytes"] == 2 * 32 * 4 * (64 + 4)
    assert fit["moe_dropped_pairs"] == 0 and fit["losses_finite"]
    assert fit["router_bias_absmax"] == 0.0
    assert set(fit["grad_norms_first"]) == set(st.BACKBONE.grad_groups(_config()))
    deployed = prepare_deploy(engine_factory=FACTORY, storage=storage,
                              instance_id=iid)
    model = deployed.models[0]
    assert model.model_type == "smallthinker"
    assert isinstance(model.hp, st.SmallThinkerConfig)
    # a history longer than the window (8), and one longer than seq_len
    # (32), which keeps its newest 32
    for history in ([f"i{t % 8}" for t in range(12)],
                    [f"i{(3 * t) % 8}" for t in range(40)]):
        got = deployed.query({"history": history, "num": 3})["itemScores"]
        ids = jnp.asarray([model.item_ids[i] + 1 for i in history][-32:],
                          jnp.int32)
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


def test_a_train_killed_after_an_epoch_resumes_to_the_same_loss(
        tmp_path, monkeypatch):
    from predictionio_tpu.utils.checkpoint import TrainCheckpointer

    c = _config(matmul_dtype="float32", vocab_size=16, init_std=0.02)
    hist = [list((np.arange(30) + u) % 8 + 1) for u in range(20)]
    straight, losses = st.BACKBONE.train(hist, c, 2, 0.003, 5)
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
        st.BACKBONE.train(hist, c, 2, 0.003, 5, checkpoint_dir=ckdir)
    monkeypatch.setattr(TrainCheckpointer, "save", real_save)
    assert saves == [1]           # between the blocks, never after the last
    resumed, rest = st.BACKBONE.train(hist, c, 2, 0.003, 5,
                                          checkpoint_dir=ckdir)
    assert len(rest) == steps     # only the second epoch ran
    np.testing.assert_allclose(rest, losses[steps:], rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(straight), jax.tree.leaves(resumed)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
