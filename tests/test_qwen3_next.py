"""The ``qwen3_next`` backbone of the ``sequentialrec`` template against
its plain reference (``benchmark/reference/qwen3_next_jnp.py``), on
seeded random weights at a preset of hidden 64, one period of four
layers (3 × Gated DeltaNet with 2 key heads and 4 value heads of 16,
chunks of 16 rows; 1 × gated attention, 4 query heads over 2 key-value
heads of 32, RoPE on 8 dims), 8 experts top-3 beside a gated shared
expert, 64-position sequences."""

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

from reference import qwen3_next_jnp as ref  # noqa: E402

from predictionio_tpu.models import qwen3_next as qn  # noqa: E402
from predictionio_tpu.models import seq_backbone  # noqa: E402
from predictionio_tpu.ops import gated_delta  # noqa: E402

ARCH = dict(
    model_type="qwen3_next", hidden_size=64, num_hidden_layers=4,
    full_attention_interval=4, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    partial_rotary_factor=0.25, rope_theta=1e7, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts=8, ep_size=1,
    num_experts_per_tok=3, norm_topk_prob=True, tie_word_embeddings=False,
    vocab_size=50, seq_len=64, seqs_per_step=2, gdn_chunk=16, attn_block=32,
    token_chunk=32, init_std=0.05)

#: the comparison's limits with bfloat16 operands around a float32
#: recurrence (what the cell's configuration states) at the
#: configuration's init_std of 0.02: the stated precision reads 0.0032
#: (the logits, rms(diff)/rms), bfloat16 EVERYWHERE 0.0123 on the seed
#: the test uses. The loss hardly moves with the precision at this size
#: (2e-5): its limit only catches a wrong loss
BF16_LOGITS_REL_RMS = 0.006
BF16_LOSS_ABS = 1e-3


def _config(**over):
    return qn.Qwen3NextConfig.from_architecture(dict(ARCH, **over))


def _histories(seed=0, n=12, top=50):
    """Short histories — most shorter than a chunk of 16, some longer —
    and one of 100 events: longer than a sequence, so cut in two."""
    rng = np.random.default_rng(seed)
    return ([rng.integers(1, top, rng.integers(3, 40)) for _ in range(n)]
            + [rng.integers(1, top, 100)])


def _perturbed(params, seed=4):
    """The seeded weights with every norm's w, ``A_log`` and ``dt_bias``
    moved off their starts (0, 0 … 2.8, 1): a gain that is all ones
    hides a missing ``1 +``."""
    rng = np.random.default_rng(seed)

    def move(path, a):
        leaf = seq_backbone._path_name(path).split(".")[-1]
        if leaf.endswith("norm") or leaf in ("A_log", "dt_bias"):
            return a + jnp.asarray(rng.uniform(-0.3, 0.3, a.shape),
                                   jnp.float32)
        return a

    return jax.tree_util.tree_map_with_path(move, params)


def _setup(c, seed=3):
    packed = seq_backbone.pack_histories(_histories(), c.seq_len,
                                         c.seqs_per_step, seed=1,
                                         chunk=c.chunk)
    params, bias = qn.BACKBONE.init_state(c, seed)
    batch = {k: jnp.asarray(getattr(packed, k)[:c.seqs_per_step])
             for k in qn.BATCH_KEYS}
    return packed, _perturbed(params), bias, batch


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _logits(params, bias, batch, c):
    """The program's head, through its own jitted entry point."""
    return qn.BACKBONE.sequence_logits({"params": params, "bias": bias},
                                       batch, c)[0]


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
    # the batch holds what the reset is for: starts inside chunks
    assert packed.counters["gdn_boundary_chunks"] > 0
    (loss, rec), grads = jax.jit(lambda p, b, bt: jax.value_and_grad(
        qn.loss_fn, has_aux=True)(p, b, bt, c))(params, bias, batch)
    (rloss, loads), rgrads = jax.jit(
        lambda w, b, bt: ref.loss_and_grads(w, b, bt, dict(c.__dict__)))(
            params, bias, batch)
    return dict(c=c, params=params, bias=bias, batch=batch, loss=loss,
                rec=rec, grads=grads, rloss=rloss, loads=loads,
                rgrads=rgrads)


def test_the_stack_is_one_body_a_run_of_one_kind():
    c = _config()
    assert c.kinds == ("linear", "linear", "linear", "full")
    assert c.runs == (("linear", 3), ("full", 1)) and c.chunk == 16
    shapes = qn.param_shapes(c)
    assert [r["op_norm"][0] for r in shapes["runs"]] == [3, 1]
    assert "gdn" in shapes["runs"][0] and "attn" in shapes["runs"][1]
    assert shapes["head"] == (64, 50) and shapes["embed"] == (50, 64)
    whole = _config(num_hidden_layers=48)
    assert whole.runs == (("linear", 3), ("full", 1)) * 12
    assert _config(full_attention_interval=1).chunk is None


def test_parameter_count_of_the_benchmarks_share():
    """ISSUE 45's arithmetic at the published widths (shapes only):
    625,667,136 parameters."""
    c = qn.Qwen3NextConfig.from_architecture(dict(
        num_experts=32, ep_size=16, vocab_size=18992))
    layer = {kind: seq_backbone.count_params(qn._layer_shapes(c, kind))
             for kind in qn.KINDS}
    assert seq_backbone.count_params(
        qn._layer_shapes(c, "linear")["gdn"]) == 33_718_464
    assert seq_backbone.count_params(
        qn._layer_shapes(c, "full")["attn"]) == 27_263_488
    assert layer == {"linear": 138_582_208, "full": 132_127_232}
    assert qn.BACKBONE.n_params(c) == 625_667_136
    assert c.held == tuple(range(32)) and c.router_experts == 512
    assert (c.hidden_size, c.linear_num_key_heads, c.linear_num_value_heads,
            c.linear_key_head_dim, c.linear_value_head_dim,
            c.linear_conv_kernel_dim, c.num_attention_heads,
            c.num_key_value_heads, c.head_dim, c.rotary_dim,
            c.moe_intermediate_size, c.shared_expert_intermediate_size,
            c.num_experts_per_tok, c.rope_theta, c.rms_norm_eps) == (
                2048, 16, 32, 128, 128, 4, 16, 2, 256, 64, 512, 512, 10, 1e7,
                1e-6)


def test_the_seeded_start_is_the_familys():
    """Zero-centred norms start at w = 0, the recurrence's output norm
    at 1, ``dt_bias`` at 1, ``A_log`` at the log of U(0, 16); every
    matrix normal(0, init_std), the PAD row zero."""
    c = _config()
    params, bias = qn.BACKBONE.init_state(c, 11)
    named = _named(params)
    for name, a in named.items():
        leaf, a = name.split(".")[-1], np.asarray(a)
        if leaf == "out_norm":
            assert (a == 1).all(), name
        elif leaf.endswith("norm"):
            assert not a.any(), name
        elif leaf == "dt_bias":
            assert (a == 1).all(), name
        elif leaf == "A_log":
            assert a.shape == (3, 4) and (np.exp(a) < 16).all()
            assert np.unique(a).size == a.size
        else:
            assert 0.5 * c.init_std < a.std() < 1.5 * c.init_std, name
    assert not np.asarray(params["embed"][0]).any()
    assert bias.shape == (4, 8) and not np.asarray(bias).any()


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


_LEAVES = [seq_backbone._path_name(p) for p, _ in
           jax.tree_util.tree_flatten_with_path(
               qn.param_shapes(_config()), is_leaf=seq_backbone._is_shape)[0]]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_leaf_matches_reference(exact, leaf):
    got, want = _named(exact["grads"]), _named(exact["rgrads"])
    assert got[leaf].shape == want[leaf].shape
    assert float(jnp.linalg.norm(want[leaf])) > 0
    assert _rel(got[leaf], want[leaf]) < 3e-5


def test_every_leaf_has_a_group_and_the_groups_are_the_parts(exact):
    groups = qn.BACKBONE.grad_groups(_config())
    assert groups == ("attn", "embed", "experts", "gdn", "head", "norms",
                      "router", "shared")
    assert {qn.group_of(leaf) for leaf in _LEAVES} == set(groups)
    assert qn.group_of("runs.0.shared_gate") == "shared"
    got = jax.jit(qn.group_squares)(exact["grads"])
    want = jax.jit(qn.group_squares)(exact["rgrads"])
    for g in groups:
        assert float(want[g]) > 0
        assert abs(float(got[g]) ** 0.5 / float(want[g]) ** 0.5 - 1) < 3e-5


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
    """bfloat16 operands, float32 accumulation, float32 state, decays,
    router, softmax, norms and RoPE: inside the limits that the lower
    precision below breaks."""
    c = stated["c"]
    got = _logits(stated["params"], stated["bias"], stated["batch"], c)
    assert _rel(got, stated["want"]) < BF16_LOGITS_REL_RMS
    loss, _ = jax.jit(lambda p, b, bt: qn.loss_fn(p, b, bt, c))(
        stated["params"], stated["bias"], stated["batch"])
    assert abs(float(loss) - stated["rloss"]) < BF16_LOSS_ABS


def test_lower_precision_fails(stated):
    """The reference computed in bfloat16 THROUGHOUT (state, decays,
    router, softmax, norms, RoPE, accumulation — the nearest precision
    below the stated one) breaks the limit the stated precision keeps."""
    low = _ref_logits(stated["params"], stated["bias"], stated["batch"],
                      stated["c"], dtype=jnp.bfloat16)
    assert _rel(low, stated["want"]) > BF16_LOGITS_REL_RMS


# -- 2. the layers, one at a time ---------------------------------------------


def _one_layer(c, kind, seed=5):
    params, _ = qn.BACKBONE.init_state(c, seed)
    run = params["runs"][qn.KINDS.index(kind)]
    w = jax.tree.map(lambda a: a[0], _perturbed({"runs": [run]})["runs"][0])
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(1, 64, c.hidden_size)), jnp.float32)
    seg = jnp.asarray(np.r_[np.full(27, 1), np.full(1, 2), np.full(32, 3),
                            np.zeros(4)], jnp.int32)[None]
    pos = jnp.asarray(np.r_[np.arange(27), np.arange(1), np.arange(32),
                            np.zeros(4)], jnp.int32)[None]
    return w, x, seg, pos


def _ref_layer(w, x, seg, pos, c, held="own"):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda w, x: ref.layer(
            w, x, seg[0], pos[0], c.held if held == "own" else held,
            dict(c.__dict__)))(w, x[0])


@pytest.mark.parametrize("kind", qn.KINDS)
def test_a_layer_is_the_references_layer(kind):
    """Segments of 27, 1 and 32 rows in chunks of 16: starts inside a
    chunk, a one-row segment, padding behind."""
    c = _config(matmul_dtype="float32")
    w, x, seg, pos = _one_layer(c, kind)
    want, load = _ref_layer(w, x, seg, pos, c)
    got, stats = jax.jit(lambda w, x: qn._layer(w, x, seg, pos, c, kind))(
        w, x)
    real = np.asarray(seg[0]) > 0
    assert _rel(got[0][real], want[real]) < 1e-5
    np.testing.assert_array_equal(np.asarray(stats["load"]),
                                  np.asarray(load))
    assert int(stats["dropped"]) == 0
    assert int(stats["pairs"]) == 60 * 3


def test_the_convolution_stops_at_a_segments_start():
    """The three rows before a segment's first changed: a tap that
    reached them would move the segment's first three rows."""
    c = _config(matmul_dtype="float32")
    w, x, seg, pos = _one_layer(c, "linear")
    moved = x.at[0, 25:28].add(1.0)            # segment 1's last, segment 2
    run = jax.jit(lambda w, x: qn._layer(w, x, seg, pos, c, "linear")[0])
    np.testing.assert_allclose(np.asarray(run(w, moved))[0, 28:60],
                               np.asarray(run(w, x))[0, 28:60], atol=1e-5)
    unmasked = jax.jit(lambda w, x: qn._layer(
        w, x, seg, jnp.arange(64)[None], c, "linear")[0])
    assert np.abs(np.asarray(unmasked(w, moved))[0, 28:31]
                  - np.asarray(unmasked(w, x))[0, 28:31]).max() > 1e-3


def test_rope_turns_the_first_quarter_of_a_head_only():
    """Positions scaled: the full layer moves (RoPE is on), shifted by
    a constant it does not (RoPE is relative); a linear layer reads
    positions for its taps' reach only."""
    c = _config(matmul_dtype="float32")
    assert c.rotary_dim == 8
    w, x, seg, pos = _one_layer(c, "full")
    real = np.asarray(seg[0]) > 0

    layer = jax.jit(lambda w, x, pos: qn._layer(w, x, seg, pos, c,
                                                "full")[0])

    def run(pos):
        return np.asarray(layer(w, x, pos))[0][real]

    np.testing.assert_allclose(run(pos + 7), run(pos), atol=2e-5)
    assert np.abs(run(pos * 3) - run(pos)).max() > 1e-4
    a = jnp.asarray(np.random.default_rng(0).normal(size=(5, 2, 32)),
                    jnp.float32)
    turned = jnp.concatenate([seq_backbone._rope(
        a[..., :8], jnp.arange(5)[:, None], 1e7), a[..., 8:]], -1)
    np.testing.assert_array_equal(np.asarray(turned[..., 8:]),
                                  np.asarray(a[..., 8:]))
    np.testing.assert_array_equal(np.asarray(turned[0]), np.asarray(a[0]))
    assert np.abs(np.asarray(turned - a)[1:, :, :8]).max() > 1e-3


def test_the_shared_expert_sits_behind_its_sigmoid_gate():
    """``_experts`` with a layer's ``shared_gate``: the routed part plus
    σ(m · w_s) · shared(m); without the key (GLM's layer) the shared
    expert whole."""
    c = _config(matmul_dtype="float32")
    w, x, seg, _ = _one_layer(c, "full")
    rows, valid = x[0], seg[0] > 0
    gates, plan, _ = seq_backbone._route(w["router"], rows, valid, None, c,
                                         softmax=True)
    run = jax.jit(lambda w, m: seq_backbone._experts(w, m, gates, plan, c))
    routed = run({k: v for k, v in w.items()
                  if k not in ("shared", "shared_gate")}, rows)
    gated = run(w, rows)
    whole = run({k: v for k, v in w.items() if k != "shared_gate"}, rows)
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(w["shared"], rows)
        gate = jax.nn.sigmoid(rows @ w["shared_gate"])[:, None]
    real = np.asarray(valid)
    assert _rel((gated - routed)[real], (gate * shared)[real]) < 1e-5
    assert _rel((whole - routed)[real], shared[real]) < 1e-5
    assert _rel(gated[real], whole[real]) > 1e-2


# -- 3. the shares add up ----------------------------------------------------


@pytest.mark.parametrize("kind", qn.KINDS)
def test_four_shares_of_four_experts_add_up_to_the_whole_layer(kind):
    """The layer run 4 times, each told it holds a different quarter
    of 16 experts (``ep_rank`` 0 … 3): the sum of the ROUTED parts,
    with what every chip computes alike (the input, the mixer and its
    residual, the gated shared expert) counted once, is the uncut
    reference's layer."""
    over = dict(matmul_dtype="float32", num_experts_per_tok=10)
    whole = _config(num_experts=16, **over)
    w, x, seg, pos = _one_layer(whole, kind)
    cfg = dict(whole.__dict__)
    with jax.default_matmul_precision("highest"):
        want, load = jax.jit(lambda w, x: ref.layer(
            w, x, seg[0], pos[0], None, cfg))(w, x[0])
        a = ref.rms_norm(x[0], 1.0 + w["op_norm"], 1e-6)
        mixed = x[0] + (ref.gated_delta_net(w["gdn"], a, seg[0], cfg)
                        if kind == "linear" else
                        ref.gated_attention(w["attn"], a, seg[0], pos[0],
                                            cfg))
        m = ref.rms_norm(mixed, 1.0 + w["ffn_norm"], 1e-6)
        once = mixed + jax.nn.sigmoid(m @ w["shared_gate"])[:, None] * (
            ref.swiglu(w["shared"], m))
    total, pairs = once, 0
    for rank in range(4):
        share = _config(num_experts=4, ep_size=4, ep_rank=rank, **over)
        assert share.router_experts == 16
        assert share.held == tuple(range(4 * rank, 4 * rank + 4))
        mine = dict(w, experts=jax.tree.map(
            lambda a: a[4 * rank:4 * rank + 4], w["experts"]))
        out, stats = jax.jit(lambda w, x, share=share: qn._layer(
            w, x, seg, pos, share, kind))(mine, x)
        assert int(stats["dropped"]) == 0
        np.testing.assert_array_equal(np.asarray(stats["load"]),
                                      np.asarray(load))
        pairs += int(stats["pairs_here"])
        total = total + (out[0] - once)
    real = np.asarray(seg[0]) > 0
    assert pairs == 60 * 10
    assert _rel(total[real], want[real]) < 1e-5


def test_the_expert_half_in_chunks_is_the_expert_half_whole():
    """``token_chunk`` rows at a time (the cell: four chunks of 4,096)
    or all 64 at once: the same result, the loads and pairs summed —
    and a pair buffer a chunk, each of them one block here."""
    c = _config(matmul_dtype="float32")
    w, x, seg, _ = _one_layer(c, "full")
    m, valid = x[0], seg[0] > 0
    y4, s4 = jax.jit(lambda w, m: qn._moe(w, m, valid, _config(
        matmul_dtype="float32", token_chunk=16)))(w, m)
    y1, s1 = jax.jit(lambda w, m: qn._moe(w, m, valid, _config(
        matmul_dtype="float32", token_chunk=64)))(w, m)
    np.testing.assert_allclose(np.asarray(y4), np.asarray(y1), atol=1e-5)
    blocks = {k: (int(s4.pop(k)), int(s1.pop(k)))
              for k in ("blocks", "blocks_run")}
    assert blocks == {"blocks": (4, 1), "blocks_run": (4, 1)}
    for k in s1:
        np.testing.assert_allclose(np.asarray(s4[k]), np.asarray(s1[k]),
                                   rtol=1e-6)
    assert int(s4["pairs"]) == 60 * 3 and int(s4["dropped"]) == 0


# -- 4. packing ---------------------------------------------------------------


def test_a_history_reads_the_same_packed_or_alone():
    """Neither kind of layer crosses a segment's start — state, taps,
    keys: the logits of a history inside a packed sequence are those of
    the history alone."""
    c = _config(matmul_dtype="float32", seqs_per_step=1)
    params, bias = qn.BACKBONE.init_state(c, 7)
    params = _perturbed(params)
    a, b = _histories(3, n=2)[:2]
    a, b = a[:30], b[:29]
    both = seq_backbone.pack_histories([a, b], 64, 1, seed=0)
    alone = seq_backbone.pack_histories([b], 64, 1, seed=0)
    inside = both.seg[0] == both.seg[0][np.flatnonzero(
        both.tokens[0] == b[0])[-1]]
    assert inside.sum() == b.size and inside[-1] == 0 and not inside[0]

    def logits(packed):
        batch = {k: jnp.asarray(getattr(packed, k)) for k in qn.BATCH_KEYS}
        return np.asarray(_logits(params, bias, batch, c)[0])

    np.testing.assert_allclose(logits(both)[inside], logits(alone)[:b.size],
                               atol=2e-5)


def test_the_packing_counts_the_chunks_a_segment_starts_in():
    """Segments of 20, 12, 32 and 30, 2 rows in two 64-slot sequences,
    chunks of 16: starts at rows 20 (inside chunk 1), 32 (a chunk's
    first row: nothing inside it) and 30 (inside chunk 1 of the other);
    padding's start is no segment's."""
    hist = [np.arange(1, n + 1) for n in (32, 30, 20, 12, 2)]
    packed = seq_backbone.pack_histories(hist, 64, 1, seed=0, chunk=16)
    assert packed.counters["gdn_chunk"] == 16
    assert packed.counters["gdn_chunks"] == 2 * 4
    sizes = sorted(np.bincount(s[s > 0]).tolist()[1:] for s in packed.seg)
    assert sizes == [[30, 20, 12, 2], [32]] or sum(map(len, sizes)) == 5
    want = 0
    for s in packed.seg:
        start = np.flatnonzero((s[1:] != s[:-1]) & (s[1:] > 0)) + 1
        want += len({int(r) // 16 for r in start if r % 16})
    assert packed.counters["gdn_boundary_chunks"] == want > 0
    assert "gdn_chunks" not in seq_backbone.pack_histories(
        hist, 64, 1, seed=0).counters


def test_next_item_scores_is_the_last_row_of_sequence_logits():
    c = _config(matmul_dtype="float32", seqs_per_step=1)
    params, bias = qn.BACKBONE.init_state(c, 9)
    model = {"params": _perturbed(params), "bias": bias}
    for n in (5, 16, 40, 90):              # buckets of 16, 16, 64 and a cut
        history = list(np.random.default_rng(n).integers(1, 50, n))
        scores = qn.BACKBONE.next_item_scores(model, history, c)
        kept = history[-c.seq_len:]
        packed = seq_backbone.pack_histories([kept + [1]], 64, 1, seed=0)
        batch = {k: jnp.asarray(getattr(packed, k)) for k in qn.BATCH_KEYS}
        want = np.asarray(_logits(model["params"], bias, batch, c))[
            0, len(kept) - 1]
        assert scores[0] == -np.inf
        np.testing.assert_allclose(scores[1:], want[1:], atol=2e-5)


# -- 5. the architecture object ----------------------------------------------


def test_what_the_turn_keeps_changes_no_number_of_a_step(monkeypatch):
    """A layer turn's checkpoint keeps the recurrence's output and the
    states that enter its blocks (``gated_delta.KEPT``); under a plain
    ``jax.checkpoint(turn)`` — the policy taken away here — the step
    walks the recurrence forward once more and reads the same loss and
    routing records bit for bit, and the same gradients to the last
    places of a float32 (the recomputed forward is another fusion:
    4e-7 of a leaf's largest entry here)."""
    c = _config(matmul_dtype="float32")
    _, params, bias, batch = _setup(c)

    def step():
        return jax.jit(lambda p, b, bt: jax.value_and_grad(
            qn.loss_fn, has_aux=True)(p, b, bt, c))(params, bias, batch)

    (loss, rec), grads = step()
    asked = []
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: asked.append(names))
    (plain_loss, plain_rec), plain_grads = step()
    # the stack asked for its policy and got None: a plain turn
    assert asked == [gated_delta.KEPT]
    assert float(loss) == float(plain_loss)
    assert {"load", "pairs", "pairs_here", "dropped"} <= set(rec["moe"])
    jax.tree.map(np.testing.assert_array_equal, rec, plain_rec)
    plain_grads = _named(plain_grads)
    for name, g in _named(grads).items():
        g, want = np.asarray(g), np.asarray(plain_grads[name])
        assert want.any(), name
        assert np.abs(g - want).max() <= 5e-6 * np.abs(want).max(), name


@pytest.mark.parametrize("over, match", [
    (dict(decoder_sparse_step=2), "decoder_sparse_step"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
    (dict(mlp_only_layers=[0]), "mlp_only_layers"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(n_shared_experts=1), "unknown architecture keys"),
    (dict(linear_num_value_heads=3), "value heads"),
    (dict(num_key_value_heads=3), "key-value heads"),
    (dict(partial_rotary_factor=0.1), "rotates 3 dims"),
    (dict(num_experts_per_tok=9), "top-9"),
    (dict(gdn_chunk=48), "chunks of 48"),
    (dict(full_attention_interval=0), "full_attention_interval"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_the_block_cannot_honour_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        _config(**over)


def test_the_table_names_the_backbone_and_the_config_round_trips():
    import pickle

    assert seq_backbone.backbone("qwen3_next") is qn.BACKBONE
    assert qn.BACKBONE.heads == ("loss",)
    assert "tgt2" not in qn.BACKBONE.batch_keys
    assert {"intermediate_size", "max_position_embeddings", "hidden_act",
            "use_sliding_window", "decoder_sparse_step", "mlp_only_layers",
            "rope_scaling"} <= qn.Qwen3NextConfig.known_keys()
    c = _config()
    assert pickle.loads(pickle.dumps(c)) == c
    assert qn.Qwen3NextConfig.from_architecture(
        {k: list(v) if isinstance(v, tuple) else v
         for k, v in c.__dict__.items()}) == c
    assert (c.window, c.block_length, c.mask_id, c.chunk) == (None, None,
                                                              None, 16)


def test_the_benchmarks_configuration_is_the_published_one():
    """``benchmark/configs/seqrec-qwen3next-80b-a3b-ep16.json``: every
    published key as the catalog row has it but the three reduced, and
    its ``bytes`` = ``n_params``."""
    import json

    with open(os.path.join(
            BENCH, "configs", "seqrec-qwen3next-80b-a3b-ep16.json")) as f:
        conf = json.load(f)
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    reduced = {"num_hidden_layers": 4, "num_experts": 32,
               "vocab_size": 18992}
    for key, value in published.items():
        assert conf[key] == reduced.get(key, value), key
    assert conf["published"] == {k: published[k] for k in reduced}
    arch = {k: v for k, v in conf.items()
            if k in qn.Qwen3NextConfig.known_keys()}
    c = qn.Qwen3NextConfig.from_architecture(dict(arch, **conf["job"]))
    assert (c.ep_size, c.seq_len, c.seqs_per_step, c.gdn_chunk,
            c.token_chunk) == (16, 16384, 1, 64, 4096)
    assert f"{qn.BACKBONE.n_params(c):,} parameters" in conf["bytes"]
    assert qn.BACKBONE.n_params(c) * 16 == 10_010_674_176
    # three linear layers keep the rule's output [16,384 × 32 × 128]
    # and the 8 states [32 × 128 × 128] that enter its blocks, float32
    fit = qn.BACKBONE.fit_attrs(c)
    assert fit["gdn_kept_bytes"] == 3 * 4 * (
        67_108_864 + 4_194_304) == 855_638_016
    # chunks of 64 rows, a 128 × 128 state: the walk runs in its kernels
    # — per step three layers' 256 chunks forward twice, in reverse once
    assert (fit["gdn_walk"], fit["gdn_walk_chunk_steps"]) == (
        "kernel", 3 * 3 * 256)


# -- 6. through the template -------------------------------------------------

FACTORY = "predictionio_tpu.templates.sequentialrec.engine:engine_factory"


def _variant(epochs):
    return {"id": "default", "engineFactory": FACTORY,
            "datasource": {"params": {"appName": "QnApp"}},
            "algorithms": [{"name": "seqrec", "params": {
                "epochs": epochs, "lr": 0.003, "seed": 5,
                "architecture": dict(ARCH, vocab_size=16, init_std=0.02,
                                     seq_len=32, attn_block=16,
                                     token_chunk=32, gdn_chunk=8,
                                     matmul_dtype="float32")}}]}


@pytest.fixture()
def qn_app(storage):
    import datetime as dt

    from predictionio_tpu.data.event import Event

    app = storage.meta.create_app("QnApp", "")
    storage.events.init_channel(app.id)
    t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
    for u in range(20):
        for t in range(10):           # longer than a chunk of 8
            storage.events.insert(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"i{(u + t) % 8}",
                event_time=t0 + dt.timedelta(minutes=t)), app.id)
    return app


def test_train_deploy_predict_returns_the_references_top_items(storage,
                                                               qn_app):
    from predictionio_tpu.core.workflow import prepare_deploy, run_train
    from predictionio_tpu.utils import tracing

    iid = run_train(FACTORY, variant=_variant(2), storage=storage,
                    use_mesh=False)
    tree = tracing.last_verb("train.run")
    spans = {s["name"]: s.get("attrs") or {} for s in tree}
    assert {"seqrec.index", "seqrec.pack", "seqrec.init", "seqrec.fit",
            "seqrec.fetch", "model.serialize"} <= set(spans)
    pack = spans["seqrec.pack"]
    # 20 histories of 10 rows, three a 32-slot sequence (7 sequences, an
    # eighth of padding for the step of two): starts at rows 10 and 20,
    # inside the second and the third chunk of 8 — 6 × 2 + 1
    assert (pack["sequences"], pack["split"]) == (8, 0)
    assert (pack["gdn_chunk"], pack["gdn_chunks"],
            pack["gdn_boundary_chunks"]) == (8, 32, 13)
    # a history loses 3 + 2 + 1 taps of a 4-tap convolution
    assert pack["conv_masked_taps"] == 20 * 6
    fit = spans["seqrec.fit"]
    assert (fit["backbone"], fit["linear_layers"], fit["full_layers"]) == (
        "qwen3_next", 3, 1)
    # 3 linear layers × 2 sequences a step × float32 × (32 rows × 4
    # heads × 16 + one block's entering state 4 × 16 × 16)
    assert fit["gdn_kept_bytes"] == 3 * 2 * 4 * (32 * 4 * 16 + 4 * 16 * 16)
    # a 16 × 16 state is no whole tile: the walk stays a scan — 3 layers
    # × 2 sequences × 3 walks × 4 chunks of 8 rows
    assert (fit["gdn_walk"], fit["gdn_walk_chunk_steps"]) == ("scan", 72)
    assert fit["moe_dropped_pairs"] == 0 and fit["losses_finite"]
    assert fit["router_bias_absmax"] == 0.0
    assert set(fit["grad_norms_first"]) == set(
        qn.BACKBONE.grad_groups(_config()))
    deployed = prepare_deploy(engine_factory=FACTORY, storage=storage,
                              instance_id=iid)
    model = deployed.models[0]
    assert model.model_type == "qwen3_next"
    assert isinstance(model.hp, qn.Qwen3NextConfig)
    # a history longer than a chunk (8), and one longer than seq_len
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
