"""The ``xing4_0`` backbone of the ``sequentialrec`` template against its
plain reference (``benchmark/reference/xing4_0_jnp.py``), on seeded
random weights at a preset of hidden 64, a stream of 4 copies, 2 heads
of 8 + 8 query/key and 16 value dims, 8 experts top-2 + 1 shared,
64-position sequences, one dense layer and two expert layers — with one
MTP module and with none."""

import math
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

from reference import xing4_0_jnp as ref  # noqa: E402

from predictionio_tpu.models import glm4_moe_lite as glm  # noqa: E402
from predictionio_tpu.models import seq_backbone  # noqa: E402
from predictionio_tpu.models import xing4_0 as xg  # noqa: E402
from predictionio_tpu.ops import hyper_connections as hc  # noqa: E402

ARCH = dict(
    model_type="xing4_0", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_attention_heads=2, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=8, ep_size=1, num_experts_per_tok=2,
    num_hidden_layers=3, first_k_dense_replace=1, vocab_size=50, seq_len=64,
    seqs_per_step=2, attn_block=32, token_chunk=64, init_std=0.2)

#: the comparison's limits with bfloat16 operands (what the cell's
#: configuration states) at its init_std of 0.02, on this seed: the
#: stated precision reads 0.0020 (the logits, rms(diff)/rms) and 4e-5
#: (loss); bfloat16 EVERYWHERE reads 0.0104 and 2.5e-4 — each limit
#: between its two readings
BF16_LOGITS_REL_RMS = 0.005
BF16_LOSS_ABS = 1.5e-4
#: ``mhc_ds_err``'s limit at this size: 20 iterations on seeded exp(A),
#: A ~ normal(0, 1), leave 2e-5; one leaves 0.5
DS_ERR_MAX = 1e-3


def _config(**over):
    return xg.XingConfig.from_architecture(dict(ARCH, **over))


def _histories(seed=0, n=12, top=50):
    rng = np.random.default_rng(seed)
    return ([rng.integers(1, top, rng.integers(3, 40)) for _ in range(n)]
            + [rng.integers(1, top, 100)])


def _setup(c, seed=3):
    packed = seq_backbone.pack_histories(_histories(), c.seq_len,
                                         c.seqs_per_step, seed=1)
    params, bias = xg.BACKBONE.init_state(c, seed)
    # a bias that matters: selection differs from the plain top-k
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(1), bias.shape)
    # coefficients that matter: a read, a write and a mixer FAR from
    # where they start, different in every layer and copy
    params = jax.tree_util.tree_map_with_path(
        lambda p, a: (a + 0.7 * jax.random.normal(
            jax.random.PRNGKey(len(str(p))), a.shape)
            if seq_backbone._path_name(p).endswith((".b", ".alpha"))
            else a), params)
    batch = {k: jnp.asarray(getattr(packed, k)[:c.seqs_per_step])
             for k in xg.BACKBONE.batch_keys}
    return packed, params, bias, batch


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _logits(params, bias, batch, c):
    """The program's heads, through its own jitted entry point."""
    return xg.BACKBONE.sequence_logits({"params": params, "bias": bias},
                                       batch, c)


def _ref_logits(params, bias, batch, c, **kw):
    @jax.jit
    def run(params, bias, batch):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(lambda s: ref.forward(
                params, bias, s, dict(c.__dict__), c.held, **kw)[:-1])(batch)

    return run(params, bias, batch)


def _named(tree):
    return dict((seq_backbone._path_name(p), g) for p, g in
                jax.tree_util.tree_flatten_with_path(tree)[0])


# -- 1. the system against the reference, with the MTP module and without ----


def _exact(modules):
    c = _config(matmul_dtype="float32", num_nextn_predict_layers=modules)
    _, params, bias, batch = _setup(c)
    (loss, rec), grads = jax.jit(lambda p, b, bt: jax.value_and_grad(
        xg.loss_fn, has_aux=True)(p, b, bt, c))(params, bias, batch)
    (rloss, (ces, loads)), rgrads = jax.jit(
        lambda w, b, bt: ref.loss_and_grads(w, b, bt, dict(c.__dict__)))(
            params, bias, batch)
    return dict(c=c, params=params, bias=bias, batch=batch, loss=loss,
                rec=rec, grads=grads, rloss=rloss, ces=ces, loads=loads,
                rgrads=rgrads)


@pytest.fixture(scope="module")
def exact():
    """The system with float32 operands and the reference, once a
    number of MTP modules."""
    return {m: _exact(m) for m in (0, 1)}


@pytest.mark.parametrize("modules,head", [(0, 0), (1, 0), (1, 1)],
                         ids=["no_mtp-next_item", "mtp-next_item", "mtp-mtp"])
def test_logits_match_reference(exact, modules, head):
    e = exact[modules]
    got = _logits(e["params"], e["bias"], e["batch"], e["c"])
    want = _ref_logits(e["params"], e["bias"], e["batch"], e["c"])
    assert len(got) == len(want) == len(e["c"].heads) == 1 + modules
    assert _rel(got[head], want[head]) < 2e-5


@pytest.mark.parametrize("modules", [0, 1])
def test_loss_matches_reference(exact, modules):
    e = exact[modules]
    assert abs(float(e["loss"]) - float(e["rloss"])) < 1e-5
    assert abs(float(e["rec"]["loss"]) - float(e["ces"][0])) < 1e-5
    assert ("mtp_loss" in e["rec"]) == bool(modules)
    if modules:
        assert abs(float(e["rec"]["mtp_loss"]) - float(e["ces"][1])) < 1e-5
    assert e["rec"]["moe"]["load"].shape == (2 + modules, 8)
    np.testing.assert_array_equal(np.asarray(e["rec"]["moe"]["load"]),
                                  np.asarray(e["loads"]))
    assert 0 < float(e["rec"]["mhc_ds_err"]) < 0.1


def _leaves(modules):
    return [seq_backbone._path_name(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(
                xg.param_shapes(_config(num_nextn_predict_layers=modules)),
                is_leaf=seq_backbone._is_shape)[0]]


@pytest.mark.parametrize("modules,leaf",
                         [(m, leaf) for m in (0, 1) for leaf in _leaves(m)])
def test_every_gradient_leaf_matches_reference(exact, modules, leaf):
    got = _named(exact[modules]["grads"])
    want = _named(exact[modules]["rgrads"])
    assert got[leaf].shape == want[leaf].shape
    assert float(jnp.linalg.norm(want[leaf])) > 0
    assert _rel(got[leaf], want[leaf]) < 1e-4


@pytest.mark.parametrize("modules", [0, 1])
def test_every_leaf_has_a_group_and_the_groups_match(exact, modules):
    c = exact[modules]["c"]
    groups = xg.BACKBONE.grad_groups(c)
    own = {"dense.hc_attn", "dense.hc_ffn", "moe.hc_attn", "moe.hc_ffn"}
    assert own <= set(groups)
    assert ({"mtp.hc_attn", "mtp.hc_ffn", "mtp.proj"} <= set(groups)) == bool(
        modules)
    got = jax.jit(xg.group_squares)(exact[modules]["grads"])
    want = jax.jit(xg.group_squares)(exact[modules]["rgrads"])
    assert set(got) == set(groups)
    for g in groups:
        assert float(want[g]) > 0
        assert abs(float(got[g]) ** 0.5 / float(want[g]) ** 0.5 - 1) < 1e-4


# -- 2. the parameters, the config and where the coefficients start -----------


def test_parameter_count_of_the_benchmarks_share():
    """ISSUE 50's arithmetic, to the parameter: 759,346,190."""
    c = xg.XingConfig.from_architecture(dict(
        n_routed_experts=8, ep_size=8, vocab_size=16384, num_hidden_layers=5,
        first_k_dense_replace=1, num_nextn_predict_layers=0))
    d, hcw = 3584, 2 * (4 * 3584 * 24 + 24 + 3)
    attn = (d * 768 + 768 + 768 * 32 * 192 + d * 576 + 512
            + 512 * 32 * 256 + 32 * 128 * d)
    assert attn == 28_411_136
    dense = attn + 3 * d * 9216 + 2 * d + hcw
    moe = attn + d * 64 + 9 * 3 * d * 1024 + 2 * d + hcw
    assert xg.BACKBONE.n_params(c) == (dense + 4 * moe + 2 * 16384 * d + d
                                       ) == 759_346_190
    assert c.held == tuple(range(8)) and c.router_experts == 64
    assert c.heads == ("loss",) and "mtp" not in xg.param_shapes(c)
    whole = xg.XingConfig()
    assert whole.heads == ("loss", "mtp_loss")
    assert (whole.num_hidden_layers, whole.first_k_dense_replace,
            whole.hc_mult, whole.hc_sinkhorn_iters, whole.hc_eps,
            whole.mhc_h_res_clamp_min, whole.mhc_h_res_clamp_max) == (
                40, 2, 4, 20, 1e-6, -30.0, 30.0)
    assert xg.fit_attrs(c) == {
        "mhc_streams": 4, "mhc_sublayers": 10,
        "mhc_kept_bytes": 5 * 4 * 4096 * 3584 * 4}


def test_the_config_takes_the_published_file_and_refuses_what_it_cannot():
    import json

    with open(os.path.join(BENCH, "configs",
                           "seqrec-xing4-29b-a4b-ep8.json")) as f:
        conf = json.load(f)
    arch = {k: v for k, v in conf.items() if k in xg.XingConfig.known_keys()}
    c = xg.XingConfig.from_architecture(dict(arch, **conf["job"]))
    assert hash(c) == hash(xg.XingConfig.from_architecture(
        dict(arch, **conf["job"])))
    assert c.yarn == conf["rope_scaling"] and c.ep_size == 8
    for bad in (dict(rope_scaling=None),
                dict(rope_scaling=dict(conf["rope_scaling"], type="linear")),
                dict(rope_scaling=dict(conf["rope_scaling"], mscale=0.5)),
                dict(num_nextn_predict_layers=2), dict(hc_mult=1),
                dict(scoring_func="softmax"), dict(num_key_value_heads=4)):
        with pytest.raises(ValueError):
            xg.XingConfig.from_architecture(dict(arch, **bad))
    # GLM's own checks are as they were
    with pytest.raises(ValueError, match="exactly one MTP module"):
        glm.GlmConfig.from_architecture(dict(num_nextn_predict_layers=0))
    with pytest.raises(ValueError, match="rope_scaling"):
        glm.GlmConfig.from_architecture(dict(rope_scaling={"type": "yarn"}))


def test_the_coefficients_start_where_the_file_says():
    """α 0.01; b around the b₀ of H_pre 1/n, H_post 1 and a mixer of
    0.87 on and 0.043 off the diagonal, spread 0.5; φ normal."""
    c = _config()
    params, _ = xg.BACKBONE.init_state(c, 11)
    n = c.hc_mult
    b = np.concatenate([np.asarray(params[s][k]["b"]).reshape(-1, c.hc_width)
                        for s in ("dense", "moe")
                        for k in ("hc_attn", "hc_ffn")])
    np.testing.assert_array_equal(np.asarray(params["moe"]["hc_ffn"]["alpha"]),
                                  np.full((3, 3), 0.01, np.float32))
    start = np.concatenate([np.full(n, -math.log(n - 1)), np.zeros(n),
                            np.where(np.eye(n, dtype=bool), 0.0,
                                     xg.RES_OFF_INIT).reshape(-1)])
    assert abs((b - start).mean()) < 0.15
    assert 0.3 < (b - start).std() < 0.7
    assert abs(float(params["dense"]["hc_attn"]["phi"].std()) - 0.2) < 0.02
    at = jnp.asarray(start, jnp.float32)[:, None]
    res = hc.sinkhorn(jnp.exp(at[2 * n:]).reshape(n, n, 1), 20, 1e-6)[..., 0]
    np.testing.assert_allclose(np.diag(res), 0.870, atol=2e-3)
    np.testing.assert_allclose(res[0, 1], 0.0433, atol=1e-3)
    assert float(jax.nn.sigmoid(at[0, 0])) == pytest.approx(0.25)


# -- 3. the mixer: the stream's ends, the chain, the mixes ---------------------


def test_the_stream_starts_as_copies_and_ends_as_their_sum():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 5, 8)),
                    jnp.float32)
    X = hc.copies(x, 4)
    assert X.shape == (4, 2, 5, 8)
    for i in range(4):
        np.testing.assert_array_equal(np.asarray(X[i]), np.asarray(x))
    np.testing.assert_allclose(np.asarray(hc.fold(X)), 4 * np.asarray(x))
    Y = jnp.asarray(np.random.default_rng(1).normal(size=(4, 7, 8)),
                    jnp.float32)
    np.testing.assert_allclose(np.asarray(hc.fold(Y)), np.asarray(Y).sum(0),
                               rtol=1e-6)


def test_twenty_iterations_make_the_mixer_doubly_stochastic_and_one_does_not():
    A = np.random.default_rng(2).normal(size=(4, 4, 300)).astype(np.float32)
    M = jnp.exp(jnp.asarray(A))
    late, early = hc.sinkhorn(M, 20, 1e-6), hc.sinkhorn(M, 1, 1e-6)
    assert float(hc.ds_error(late)) < DS_ERR_MAX < 0.1 < float(
        hc.ds_error(early))
    np.testing.assert_allclose(np.asarray(late.sum(0)), 1.0, atol=DS_ERR_MAX)
    np.testing.assert_allclose(np.asarray(late.sum(1)), 1.0, atol=DS_ERR_MAX)
    # the chain as the reference writes it, token-major
    want = ref.sinkhorn(jnp.exp(jnp.asarray(A.transpose(2, 0, 1))), 20, 1e-6)
    np.testing.assert_allclose(np.asarray(late).transpose(2, 0, 1),
                               np.asarray(want), rtol=2e-5)
    # rows THEN columns: the last division leaves the columns exact
    assert float(jnp.abs(late.sum(0) - 1).max()) < 1e-5


def test_coefficients_and_mixes_match_the_reference_token_for_token():
    c = _config(matmul_dtype="float32")
    rng = np.random.default_rng(4)
    n, T, d = c.hc_mult, 48, c.hidden_size
    X = jnp.asarray(rng.normal(size=(n, T, d)), jnp.float32)
    w = {"phi": jnp.asarray(0.2 * rng.normal(size=(n, d, c.hc_width)),
                            jnp.float32),
         "b": jnp.asarray(rng.normal(size=c.hc_width), jnp.float32),
         "alpha": jnp.asarray([0.3, -0.4, 0.5], jnp.float32)}
    y = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        pre, post, res = hc.coefficients(
            X, w["phi"], w["b"], w["alpha"], norm_eps=c.rms_norm_eps,
            iters=c.hc_sinkhorn_iters, eps=c.hc_eps,
            clamp=(c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max),
            dtype=jnp.float32)
        Xt = X.transpose(1, 0, 2)
        rpre, rpost, rres = ref.coefficients(w, Xt, dict(c.__dict__))
        want = ref.hyper(w, Xt, lambda u: y, dict(c.__dict__))
    np.testing.assert_allclose(np.asarray(pre.T), np.asarray(rpre), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(post.T), np.asarray(rpost),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(res.transpose(2, 0, 1)),
                               np.asarray(rres), rtol=1e-4, atol=1e-7)
    got = hc.write(X, res, post, y)
    np.testing.assert_allclose(np.asarray(got.transpose(1, 0, 2)),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(hc.read(X, pre)),
        np.einsum("it,itd->td", np.asarray(pre), np.asarray(X)), rtol=1e-5,
        atol=1e-6)


def test_the_clamp_comes_before_exp():
    """A logit of ±1e4 is clamped to ±30 and the chain stays finite."""
    c = _config(matmul_dtype="float32")
    n, d = c.hc_mult, c.hidden_size
    X = jnp.ones((n, 3, d), jnp.float32)
    b = jnp.zeros(c.hc_width).at[2 * n].set(1e4).at[2 * n + 1].set(-1e4)
    _, _, res = hc.coefficients(
        X, jnp.zeros((n, d, c.hc_width)), b, jnp.ones(3),
        norm_eps=1e-6, iters=20, eps=1e-6, clamp=(-30.0, 30.0),
        dtype=jnp.float32)
    assert bool(jnp.isfinite(res).all())
    assert float(res[0, 0, 0]) > 0.9 and float(res[0, 1, 0]) < 1e-12


@pytest.mark.parametrize("fault", ["res_transposed", "post_unscaled",
                                   "plain_scale"])
def test_a_planted_fault_moves_the_references_logits(exact, monkeypatch,
                                                     fault):
    """What the chip's probe plants (``xing4_precision_probe.py``): each
    of the three wrong equations reads far from the program."""
    e = exact[0]
    got = _logits(e["params"], e["bias"], e["batch"], e["c"])[0]
    monkeypatch.setattr(ref, "FAULT", fault)
    wrong = _ref_logits(e["params"], e["bias"], e["batch"], e["c"])[0]
    assert _rel(got, wrong) > 0.02


# -- 4. YaRN ------------------------------------------------------------------


def test_yarn_frequencies_against_a_table_written_by_hand():
    """dim 64, θ 1e4, factor 64 over 4,096 positions, β 32 / 1: the
    ramp runs from dim 10 (32 turns fit at 64·ln(4096/64π)/(2 ln 1e4) =
    10.47, floored) to dim 23 (1 turn at 22.51, ceiled): below 10 the
    plain θ^(−i/32), from 23 on that ÷ 64, between them the blend."""
    f = np.asarray(xg.XingConfig().rope_freqs)
    plain = 1e4 ** (-np.arange(32) / 32.0)
    assert f.shape == (32,)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(f[23:], plain[23:] / 64, rtol=1e-12)
    for i, share in ((11, 1 / 13), (16, 6 / 13), (22, 12 / 13)):
        np.testing.assert_allclose(
            f[i], plain[i] * (1 - share) + plain[i] / 64 * share, rtol=1e-12)
    # by hand: i = 16 is 1e4^(−1/2) = 0.01; 0.01·(7/13) + 0.01/64·(6/13)
    assert f[16] == pytest.approx(0.0054567308, rel=1e-6)
    assert f[0] == 1.0 and f[31] == pytest.approx(1e4 ** (-31 / 32) / 64)
    # the reference writes the same table on its own
    np.testing.assert_allclose(
        ref.yarn_freqs(64, 1e4, dict(xg._YARN)), f, rtol=1e-6)
    # and the softmax scale carries mscale² = (0.1 ln 64 + 1)²
    assert xg.XingConfig().softmax_scale == pytest.approx(
        (0.1 * math.log(64) + 1) ** 2 / math.sqrt(192))
    assert glm.GlmConfig().softmax_scale == 1 / np.sqrt(256)
    assert glm.GlmConfig().rope_freqs is None


def test_rope_takes_frequencies_and_theta_alone_is_as_it_was():
    x = jnp.asarray(np.random.default_rng(5).normal(size=(6, 8)), jnp.float32)
    pos = jnp.arange(6)
    plain = seq_backbone._rope(x, pos, 1e4)
    same = seq_backbone._rope(x, pos, 1e4, [1e4 ** (-i / 4) for i in range(4)])
    np.testing.assert_allclose(np.asarray(plain), np.asarray(same),
                               rtol=1e-5, atol=1e-6)
    slow = seq_backbone._rope(x, pos, 1e4, [0.0] * 4)
    np.testing.assert_array_equal(np.asarray(slow), np.asarray(x))


# -- 5. the shares add up ------------------------------------------------------


def test_expert_shares_add_up_to_the_whole_layer():
    """The expert sublayer's F run 8 times, each told it holds a
    different eighth of the experts, the shared expert — what every
    chip computes alike — counted once: the sum is the uncut
    reference's output for the whole layer; and through the write-back
    (linear in y) the streams add up likewise, H_res·X counted once."""
    whole = _config(matmul_dtype="float32")
    _, params, bias, _ = _setup(whole, seed=5)
    w = jax.tree.map(lambda a: a[0], params["moe"])
    n, T, d = whole.hc_mult, 128, whole.hidden_size
    X = jax.random.normal(jax.random.PRNGKey(2), (n, T, d))
    valid = jnp.ones(T, bool)
    cfg = dict(whole.__dict__)
    with jax.default_matmul_precision("highest"):
        Xt = X.transpose(1, 0, 2)
        pre, post, res = ref.coefficients(w["hc_ffn"], Xt, cfg)
        h = ref.rms_norm(jnp.einsum("si,sid->sd", pre, Xt), w["ffn_norm"],
                         whole.rms_norm_eps)
        want_y, _ = ref.moe(w, h, valid.astype(h.dtype), bias[0], None, cfg)
        shared = ref.swiglu(w["shared"], h)
        mixed = jnp.einsum("sij,sjd->sid", res, Xt)
        want_X = mixed + post[:, :, None] * want_y[:, None]
    total_y = -7 * shared
    total_X = -7 * (mixed + post[:, :, None] * shared[:, None])
    for rank in range(8):
        share = _config(matmul_dtype="float32", n_routed_experts=1,
                        ep_size=8, ep_rank=rank, seq_len=T, token_chunk=T)
        mine = dict(w, experts=jax.tree.map(lambda a: a[rank:rank + 1],
                                            w["experts"]))
        part, stats = jax.jit(lambda w, h, b, share=share: seq_backbone._moe(
            w, h, valid, b, share))(mine, h, bias[0])
        assert int(stats["dropped"]) == 0
        total_y = total_y + part

        def ffn_only(w, X, b, share=share):
            # the layer's second half as the program runs it
            def ffn(u):
                return seq_backbone._moe(
                    w, seq_backbone._rms(u, w["ffn_norm"],
                                         share.rms_norm_eps), valid, b, share)

            return xg._hyper(w["hc_ffn"], X, ffn, share)[0]

        total_X = total_X + jax.jit(ffn_only)(mine, X, bias[0]).transpose(
            1, 0, 2)
    assert _rel(total_y, want_y) < 1e-5
    assert _rel(total_X, want_X) < 1e-5


# -- 6. packing, precision -----------------------------------------------------


@pytest.mark.parametrize("modules", [0, 1])
def test_a_history_reads_the_same_packed_or_alone(modules):
    """Segments never attend across users, and the mixer is per token:
    the logits of a history inside a packed sequence are those of the
    history alone."""
    c = _config(matmul_dtype="float32", seqs_per_step=1,
                num_nextn_predict_layers=modules)
    params, bias = xg.BACKBONE.init_state(c, 7)
    a, b = _histories(3, n=2)[:2]
    a, b = a[:30], b[:25]
    both = seq_backbone.pack_histories([a, b], 64, 1, seed=0)
    alone = seq_backbone.pack_histories([b], 64, 1, seed=0)

    def logits(packed):
        batch = {k: jnp.asarray(getattr(packed, k))
                 for k in xg.BACKBONE.batch_keys}
        return np.asarray(_logits(params, bias, batch, c)[-1][0])

    seg_of_b = both.seg[0][np.flatnonzero(both.tokens[0] == b[0])[0]]
    inside = logits(both)[both.seg[0] == seg_of_b]
    np.testing.assert_allclose(inside, logits(alone)[:b.size], atol=3e-5)


@pytest.fixture(scope="module")
def stated():
    """The configuration's own init_std and operand dtype, with the
    reference's float32 logits and loss."""
    c = _config(init_std=0.02, num_nextn_predict_layers=0)
    _, params, bias, batch = _setup(c)
    with jax.default_matmul_precision("highest"):
        rloss = float(jax.jit(lambda w, b, bt: ref.loss(
            w, b, bt, dict(c.__dict__))[0])(params, bias, batch))
    return dict(c=c, params=params, bias=bias, batch=batch, rloss=rloss,
                want=_ref_logits(params, bias, batch, c)[0])


def test_stated_precision_within_its_limits(stated):
    c = stated["c"]
    got = _logits(stated["params"], stated["bias"], stated["batch"], c)[0]
    assert _rel(got, stated["want"]) < BF16_LOGITS_REL_RMS
    loss, _ = jax.jit(lambda p, b, bt: xg.loss_fn(p, b, bt, c))(
        stated["params"], stated["bias"], stated["batch"])
    assert abs(float(loss) - stated["rloss"]) < BF16_LOSS_ABS


def test_lower_precision_fails(stated):
    """The reference in bfloat16 THROUGHOUT — stream, coefficients,
    Sinkhorn chain, router, softmax, norms — breaks the limit the stated
    precision keeps."""
    low = _ref_logits(stated["params"], stated["bias"], stated["batch"],
                      stated["c"], dtype=jnp.bfloat16)[0]
    assert _rel(low, stated["want"]) > BF16_LOGITS_REL_RMS


# -- 7. through the template ---------------------------------------------------

FACTORY = "predictionio_tpu.templates.sequentialrec.engine:engine_factory"


def _variant(epochs, modules):
    return {"id": "default", "engineFactory": FACTORY,
            "datasource": {"params": {"appName": "XingApp"}},
            "algorithms": [{"name": "seqrec", "params": {
                "epochs": epochs, "lr": 0.003, "seed": 5,
                "architecture": dict(ARCH, vocab_size=16, init_std=0.02,
                                     matmul_dtype="float32",
                                     num_nextn_predict_layers=modules)}}]}


@pytest.fixture()
def xing_app(storage):
    import datetime as dt

    from predictionio_tpu.data.event import Event

    app = storage.meta.create_app("XingApp", "")
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


@pytest.mark.parametrize("modules", [0, 1])
def test_train_deploy_predict_returns_the_references_top_items(
        storage, xing_app, modules):
    from predictionio_tpu.core.workflow import prepare_deploy, run_train
    from predictionio_tpu.utils import tracing

    iid = run_train(FACTORY, variant=_variant(2, modules), storage=storage,
                    use_mesh=False)
    verb = tracing.last_verb("train.run")
    assert {"seqrec.index", "seqrec.pack", "seqrec.init", "seqrec.fit",
            "seqrec.fetch", "model.serialize"} <= {s["name"] for s in verb}
    fit = next(s for s in verb if s["name"] == "seqrec.fit")["attrs"]
    assert fit["backbone"] == "xing4_0" and fit["mhc_streams"] == 4
    assert fit["mhc_sublayers"] == 2 * (3 + modules)
    assert fit["mhc_kept_bytes"] == (3 + modules) * 4 * 2 * 64 * 64 * 4
    assert 0 < fit["mhc_ds_err"] < 0.05
    assert ("mtp_loss_first" in fit) == bool(modules)
    deployed = prepare_deploy(engine_factory=FACTORY, storage=storage,
                              instance_id=iid)
    model = deployed.models[0]
    assert model.model_type == "xing4_0"
    history = ["i0", "i1", "i2", "i3", "i4"]
    got = deployed.query({"history": history, "num": 3})["itemScores"]
    ids = jnp.asarray([model.item_ids[i] + 1 for i in history], jnp.int32)
    seq = {"tokens": ids, "seg": jnp.ones_like(ids),
           "pos": jnp.arange(ids.size, dtype=jnp.int32),
           "tgt1": jnp.zeros_like(ids)}
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(model.params["params"], model.params["bias"],
                             seq, dict(model.hp.__dict__), model.hp.held)[0]
    scores = np.asarray(logits[-1])[1:len(model.item_ids) + 1]
    top = np.argsort(-scores)[:3]
    inv = model.item_ids.inverse()
    assert [s["item"] for s in got] == [inv[int(i)] for i in top]
    np.testing.assert_allclose([s["score"] for s in got], scores[top],
                               rtol=1e-4, atol=1e-5)
