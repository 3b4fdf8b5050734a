"""The gated delta rule as a chunked scan (``ops/gated_delta.py``)
against the row-by-row recurrence of the plain reference
(``benchmark/reference/qwen3_next_jnp.delta_rule``): chunk length and
packing change nothing beyond float32 rounding."""

import os
import re
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

from predictionio_tpu.ops import gated_delta  # noqa: E402

S, HK, H, DK, DV = 128, 2, 4, 16, 8
NAMES = ("q", "k", "v", "g", "beta")


def _segments(*lengths):
    seg, at = np.zeros(S, np.int32), 0
    for j, n in enumerate(lengths):
        seg[at:at + n] = j + 1
        at += n
    assert at <= S
    return seg


#: starts inside chunks of 16 and of 64, at a chunk's first row (64)
#: and at its last (63, 127), a one-row segment (row 0; rows 63, 127),
#: padding behind (none in the first)
PACKINGS = {
    "starts_everywhere": _segments(1, 16, 46, 1, 36, 27, 1),
    "one_segment": _segments(128),
    "padding_tail": _segments(40, 23, 30),
    "many_short": _segments(*([5, 9, 2, 17, 3, 11, 7] * 2)),
}


def _operands(seed=0, g_scale=3.0):
    rng = np.random.default_rng(seed)

    def unit(shape):
        x = rng.normal(size=shape)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    return tuple(jnp.asarray(a, jnp.float32) for a in (
        unit((S, HK, DK)), unit((S, HK, DK)), rng.normal(size=(S, H, DV)),
        -rng.uniform(0, g_scale, (S, H)), rng.uniform(0, 1, (S, H))))


def _chunked(seg, chunk):
    seg = jnp.asarray(seg)
    return lambda q, k, v, g, beta: gated_delta.gated_delta_rule(
        q[None], k[None], v[None], g[None], beta[None], seg[None],
        chunk)[0]


def _rows(seg):
    seg = jnp.asarray(seg)

    def run(q, k, v, g, beta):
        with jax.default_matmul_precision("highest"):
            return ref.delta_rule(jnp.repeat(q, H // HK, axis=1),
                                  jnp.repeat(k, H // HK, axis=1), v, g, beta,
                                  seg)

    return run


def _value_and_grads(rule, ops, weight):
    return jax.jit(jax.value_and_grad(
        lambda *ops: (rule(*ops) * weight).sum(), range(5)))(*ops)


def _keeping(rule):
    """The rule as a layer turn runs it: inside a ``jax.checkpoint``
    whose policy keeps what the rule's forward names."""
    return jax.checkpoint(
        rule, policy=jax.checkpoint_policies.save_only_these_names(
            *gated_delta.KEPT))


def _assert_same(got, want):
    """A value and its gradients equal to another's, bit for bit."""
    assert float(got[0]) == float(want[0])
    for name, a, b in zip(NAMES, got[1], want[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)


def _weight(seg, seed=9):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(S, H, DV))
                       * (seg > 0)[:, None, None], jnp.float32)


# -- 1. chunked = row by row ---------------------------------------------------


@pytest.mark.parametrize("packing, chunk", [
    ("starts_everywhere", 16), ("starts_everywhere", 64),
    ("starts_everywhere", S), ("padding_tail", 16), ("one_segment", 64)])
def test_chunked_equals_the_recurrence_output_and_every_gradient(packing,
                                                                 chunk):
    seg, ops = PACKINGS[packing], _operands()
    out = jax.jit(_chunked(seg, chunk))(*ops)
    want = jax.jit(_rows(seg))(*ops)
    assert out.shape == (S, H, DV) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    w = _weight(seg)
    got = _value_and_grads(_chunked(seg, chunk), ops, w)
    exp = _value_and_grads(_rows(seg), ops, w)
    np.testing.assert_allclose(float(got[0]), float(exp[0]), rtol=1e-4,
                               atol=1e-4)
    for name, a, b in zip(NAMES, got[1], exp[1]):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= 5e-5 * max(np.abs(b).max(), 1.0), name


@pytest.mark.parametrize("packing, chunk", [("starts_everywhere", 16),
                                            ("padding_tail", 64)])
def test_chunked_under_a_checkpoint_that_keeps_its_names(packing, chunk):
    """The rule inside ``jax.checkpoint(…, policy=save_only_these_names(
    "gdn_out", "gdn_states"))``: the backward pass reads the kept
    output and entering states in place of a second forward walk —
    the same output and the same gradients, bit for bit."""
    seg, ops, w = PACKINGS[packing], _operands(), _weight(PACKINGS[packing])
    rule = _chunked(seg, chunk)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(_keeping(rule))(*ops)),
        np.asarray(jax.jit(rule)(*ops)))
    _assert_same(_value_and_grads(_keeping(rule), ops, w),
                 _value_and_grads(rule, ops, w))


def test_the_chunk_taken_divides_the_sequence():
    """A chunk that does not divide S: the most that does is taken (a
    serving bucket of 16 or 32 rows under the configuration's 64)."""
    seg, ops = PACKINGS["starts_everywhere"], _operands()
    np.testing.assert_allclose(
        np.asarray(jax.jit(_chunked(seg, 48))(*ops)),       # gcd 16
        np.asarray(jax.jit(_chunked(seg, 16))(*ops)), atol=1e-6)


def test_blocks_of_rows_are_checkpoints_not_another_result(monkeypatch):
    """The walk's blocks (2,048 rows at the cell's size: what the
    backward pass recomputes at a time) cut at 32 rows here: four
    blocks, the state and the run handed from one to the next — the
    same output and gradients."""
    seg, ops = PACKINGS["starts_everywhere"], _operands()
    w = _weight(seg)
    whole = _value_and_grads(_chunked(seg, 16), ops, w)
    monkeypatch.setattr(gated_delta, "BLOCK_ROWS", 32)
    cut = _value_and_grads(_chunked(seg, 16), ops, w)
    np.testing.assert_allclose(float(cut[0]), float(whole[0]), rtol=1e-5)
    for a, b in zip(cut[1], whole[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-5)


def test_blocks_under_a_checkpoint_that_keeps_their_entering_states(
        monkeypatch):
    """Four blocks of 32 rows inside a checkpoint that keeps the rule's
    names: the four entering states are what its backward walk starts
    each block from — the cut rule's own output and gradients, bit for
    bit."""
    seg, ops = PACKINGS["starts_everywhere"], _operands()
    w = _weight(seg)
    monkeypatch.setattr(gated_delta, "BLOCK_ROWS", 32)
    assert gated_delta.block_rows(16, S) == (16, 32)
    _assert_same(_value_and_grads(_keeping(_chunked(seg, 16)), ops, w),
                 _value_and_grads(_chunked(seg, 16), ops, w))


# -- 1b. what a layer turn keeps of the rule -----------------------------------


def _layer_gradient(policy):
    """The gradient program of two scanned layer turns (a projection a
    side of the rule), each turn one ``jax.checkpoint`` under
    ``policy``, and its operands."""
    seg = jnp.asarray(PACKINGS["starts_everywhere"])[None]
    rng = np.random.default_rng(2)
    d = H * DV
    weights = {name: jnp.asarray(rng.normal(size=(2, d, n)) * 0.2,
                                 jnp.float32)
               for name, n in (("q", HK * DK), ("k", HK * DK), ("v", d),
                               ("g", H), ("beta", H), ("out", d))}
    x = jnp.asarray(rng.normal(size=(1, S, d)), jnp.float32)

    def turn(x, w):
        def unit(a):
            a = a.reshape(1, S, HK, DK)
            return a * jax.lax.rsqrt((a * a).sum(-1, keepdims=True) + 1e-6)

        o = gated_delta.gated_delta_rule(
            unit(x @ w["q"]), unit(x @ w["k"]),
            (x @ w["v"]).reshape(1, S, H, DV), -jax.nn.softplus(x @ w["g"]),
            jax.nn.sigmoid(x @ w["beta"]), seg, 16)
        return x + jnp.tanh(o.reshape(1, S, d)) @ w["out"], None

    def loss(weights, x):
        return (jax.lax.scan(jax.checkpoint(turn, policy=policy), x,
                             weights)[0] ** 2).mean()

    return jax.jit(jax.value_and_grad(loss, (0, 1))), (weights, x)


def _scans(jaxpr):
    """The ``scan`` equations of a jaxpr, those inside its equations'
    own jaxprs too."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "scan"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _scans(sub)
    return n


def test_a_turn_that_keeps_the_names_walks_forward_once_less(monkeypatch):
    """A checkpointed layer turn's gradient program with and without
    the policy: with it the backward pass holds no second forward of
    the rule — one scan over blocks and one over a block's chunks fewer
    (and the map over sequences around them), in the jaxpr and as loops
    of the compiled program — and reads the same numbers."""
    monkeypatch.setattr(gated_delta, "BLOCK_ROWS", 32)
    names = jax.checkpoint_policies.save_only_these_names(*gated_delta.KEPT)
    counts, values = {}, {}
    for key, policy in (("plain", None), ("kept", names)):
        program, operands = _layer_gradient(policy)
        text = program.lower(*operands).compile().as_text()
        counts[key] = (_scans(jax.make_jaxpr(program)(*operands).jaxpr),
                       len(re.findall(r"(?m)^\s*(?:ROOT )?\S+ = .* while\(",
                                      text)))
        values[key] = program(*operands)
    # sequences, blocks, chunks: a forward walk of the rule is 3 scans
    assert counts["plain"][0] - counts["kept"][0] == 3
    # … and two loops once the one-sequence map is unrolled
    assert counts["plain"][1] - counts["kept"][1] == 2
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), values["kept"], values["plain"])


# -- 2. packed = each segment alone --------------------------------------------


@pytest.mark.parametrize("chunk", [64])
def test_a_packed_segment_gets_what_it_gets_alone(chunk):
    """Each segment of the packing moved to the FRONT of a sequence of
    its own: the same rows, whatever stood before it in the packing and
    wherever in a chunk it started."""
    seg, ops = PACKINGS["starts_everywhere"], _operands()
    packed = np.asarray(jax.jit(_chunked(seg, chunk))(*ops))
    for j in range(1, seg.max() + 1):
        rows = np.flatnonzero(seg == j)
        alone_seg = _segments(rows.size)
        moved = tuple(jnp.zeros_like(a).at[:rows.size].set(a[rows])
                      for a in ops)
        alone = np.asarray(jax.jit(_chunked(alone_seg, chunk))(*moved))
        np.testing.assert_allclose(packed[rows], alone[:rows.size],
                                   atol=2e-5, rtol=2e-5)


def test_no_row_reads_across_a_segments_start():
    """Everything before a segment changed: its rows read as before
    (to the rounding of a cumulative sum that starts at the chunk's
    first row), and no gradient of them reaches the rows before — also
    where the segment starts in the middle of a chunk."""
    seg, ops = PACKINGS["starts_everywhere"], _operands()
    start = int(np.flatnonzero(seg == 6)[0])
    assert start % 64 and start % 16                        # mid-chunk
    before = np.arange(S) < start
    bumped = tuple(jnp.where(before.reshape((S,) + (1,) * (a.ndim - 1)),
                             a * 0.5 - 0.25, a) for a in ops)
    for chunk in (16, 64):
        a = np.asarray(jax.jit(_chunked(seg, chunk))(*ops))
        b = np.asarray(jax.jit(_chunked(seg, chunk))(*bumped))
        np.testing.assert_allclose(a[start:], b[start:], atol=1e-5)
        assert np.abs(a[:start] - b[:start]).max() > 1e-3
        w = _weight(seg) * jnp.asarray(~before, jnp.float32)[:, None, None]
        for name, g in zip(NAMES, _value_and_grads(_chunked(seg, chunk),
                                                   ops, w)[1]):
            g = np.asarray(g)
            # (a decay's cotangent comes back through the chunk's
            # cumulative sum: equal terms of both signs, summed in two
            # orders)
            assert np.abs(g[:start]).max() <= (
                1e-6 * np.abs(g).max() if name == "g" else 0.0), name
            assert g[start:].any(), name


def test_a_state_that_is_not_reset_would_show():
    """The packing as ONE segment — what a scan without resets
    computes: the rows behind a start differ."""
    seg, ops = PACKINGS["starts_everywhere"], _operands(g_scale=0.1)
    reset = np.asarray(jax.jit(_chunked(seg, 16))(*ops))
    carried = np.asarray(jax.jit(_chunked(_segments(S), 16))(*ops))
    first = np.flatnonzero(seg == 1)
    np.testing.assert_allclose(reset[first], carried[first], atol=1e-6)
    assert np.abs(reset - carried).max() > 1e-2


def test_padding_rows_give_and_take_no_gradient():
    seg, ops = PACKINGS["padding_tail"], _operands()
    pad = seg == 0
    assert pad.sum() == 35
    grads = _value_and_grads(_chunked(seg, 16), ops, _weight(seg))[1]
    for name, g in zip(NAMES, grads):
        g = np.asarray(g)
        assert np.isfinite(g).all() and not g[pad].any(), name
        assert g[~pad].any(), name


# -- 3. decays -----------------------------------------------------------------


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_decay_of_minus_21_a_row_stays_finite(chunk):
    """A = 16 and softplus ≈ 1.3: g = −21 a row, −1,344 over a chunk of
    64: e^{−γ} is no float32, the differences' exponentials are."""
    seg = PACKINGS["starts_everywhere"]
    q, k, v, _, beta = _operands()
    g = jnp.full((S, H), -21.0).at[:, 1].set(-1e-3).at[:, 2].set(-5.0)
    ops = (q, k, v, g, beta)
    out = np.asarray(jax.jit(_chunked(seg, chunk))(*ops))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, np.asarray(jax.jit(_rows(seg))(*ops)),
                               atol=2e-5, rtol=2e-5)
    got = _value_and_grads(_chunked(seg, chunk), ops, _weight(seg))[1]
    exp = _value_and_grads(_rows(seg), ops, _weight(seg))[1]
    for name, a, b in zip(NAMES, got, exp):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= 5e-5 * max(np.abs(b).max(), 1.0), name


# -- 4. operands ---------------------------------------------------------------


def test_operands_of_any_dtype_give_a_float32_result():
    """The layer hands over float32; bfloat16 operands (a caller that
    has them) are taken up into float32 before any product."""
    seg, ops = PACKINGS["one_segment"], _operands(g_scale=0.01)
    low = tuple(a.astype(jnp.bfloat16) for a in ops)
    got = jax.jit(_chunked(seg, 16))(*low)
    assert got.dtype == jnp.float32
    want = jax.jit(_chunked(seg, 16))(*(a.astype(jnp.float32) for a in low))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_integers_get_no_cotangent_and_low_floats_their_own():
    """``seg`` gets no cotangent, nor does an operand handed over as
    integers (a write strength of 0 / 1); bfloat16 operands get
    bfloat16 cotangents of a float32 result — the values a float32
    copy of them gets, rounded."""
    seg = jnp.asarray(PACKINGS["starts_everywhere"])
    q, k, v, g, beta = _operands(g_scale=0.1)
    w = _weight(np.asarray(seg))

    def loss(q, k, v, g, beta, seg):
        out = gated_delta.gated_delta_rule(
            q[None], k[None], v[None], g[None], beta[None], seg[None], 16)[0]
        assert out.dtype == jnp.float32
        return (out * w).sum()

    grad = jax.jit(jax.grad(loss, range(6), allow_int=True))
    ones = jnp.ones((S, H), jnp.int32)
    whole = grad(q, k, v, g, ones, seg)
    assert whole[4].dtype == whole[5].dtype == jax.dtypes.float0
    assert whole[4].shape == (S, H) and whole[5].shape == (S,)
    want = grad(q, k, v, g, ones.astype(jnp.float32), seg)
    for name, a, b in zip(NAMES[:4], whole, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    low = tuple(a.astype(jnp.bfloat16) for a in (q, k, v, g, beta))
    got = grad(*low, seg)
    want = grad(*(a.astype(jnp.float32) for a in low), seg)
    for name, a, b in zip(NAMES, got, want):
        assert a.dtype == jnp.bfloat16 and b.dtype == jnp.float32, name
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.bfloat16).astype(jnp.float32)), name)


def test_value_heads_read_the_key_head_of_their_group():
    """4 value heads over 2 key heads = the same with every key head
    handed over twice."""
    seg, (q, k, v, g, beta) = PACKINGS["many_short"], _operands()
    seg1 = jnp.asarray(seg)[None]
    grouped = gated_delta.gated_delta_rule(
        q[None], k[None], v[None], g[None], beta[None], seg1, 16)
    alone = gated_delta.gated_delta_rule(
        jnp.repeat(q, 2, axis=1)[None], jnp.repeat(k, 2, axis=1)[None],
        v[None], g[None], beta[None], seg1, 16)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(alone),
                               atol=1e-6)


def test_runs_of_counts_maximal_runs_of_one_id():
    seg = np.asarray([3, 3, 4, 4, 4, 1, 0, 0], np.int32)
    np.testing.assert_array_equal(gated_delta.runs_of(seg, np),
                                  [1, 1, 2, 2, 2, 3, 4, 4])


@pytest.mark.parametrize("C", [8, 16, 64])
def test_the_unit_lower_solve_is_the_triangular_solve(C):
    """Diagonal blocks of 16 rows inverted by doubling, the block rows
    by substitution: (I + L)⁻¹ rhs to float32 rounding — also where
    every entry of L is ½ (one item played over and over: equal keys),
    whose powers over a whole chunk of 64 would leave float32."""
    from jax.scipy.linalg import solve_triangular

    rng = np.random.default_rng(C)
    for lower in (np.tril(rng.normal(size=(3, 2, C, C)) * 0.4, -1),
                  np.tril(np.full((3, 2, C, C), 0.5), -1)):
        rhs = rng.normal(size=(3, 2, C, 5))
        lower, rhs = (jnp.asarray(a, jnp.float32) for a in (lower, rhs))
        want = np.asarray(solve_triangular(lower + jnp.eye(C), rhs,
                                           lower=True), np.float64)
        got = np.asarray(jax.jit(gated_delta.solve_unit_lower)(lower, rhs))
        assert np.abs(got - want).max() <= 2e-5 * max(np.abs(want).max(), 1)


@pytest.mark.parametrize("C", [8, 16, 64])
def test_the_unit_lower_inverse_is_the_inverse(C):
    """What the solve multiplies its right-hand side by: (I + L)⁻¹,
    unit lower triangular itself, from the 16-row blocks — also where
    every entry of L is ½."""
    rng = np.random.default_rng(C + 1)
    for lower in (np.tril(rng.normal(size=(2, 3, C, C)) * 0.4, -1),
                  np.tril(np.full((2, 3, C, C), 0.5), -1)):
        got = np.asarray(jax.jit(gated_delta.unit_lower_inverse)(
            jnp.asarray(lower, jnp.float32)), np.float64)
        np.testing.assert_array_equal(np.triu(got, 1), 0)
        np.testing.assert_array_equal(np.diagonal(got, 0, -2, -1), 1)
        eye = got @ (np.eye(C) + lower)
        assert np.abs(eye - np.eye(C)).max() <= 2e-5 * np.abs(got).max()


# -- 7. the walk over a block's chunks: kernels and scan, one rule -------------

WALK_NAMES = ("state", "u", "wq", "p", "k_out", "keep")


def _walk_operands(H, Hk, M, starts, run_before, monkeypatch, C=64, D=128):
    """What :func:`gated_delta._block` hands its walk at kernel-sized
    shapes (chunks of ``C`` rows, a ``D`` × ``D`` state a head): ``M``
    chunks whose segments start at the rows ``starts``, the block
    entered in run ``run_before`` by a state that is not zero."""
    rng = np.random.default_rng(H * 100 + M)
    R = M * C

    def unit(shape):
        x = rng.normal(size=shape)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    run = 1 + np.isin(np.arange(R), starts).cumsum()
    rows = tuple(jnp.asarray(a, jnp.float32) for a in (
        unit((R, Hk, D)), unit((R, Hk, D)), rng.normal(size=(R, H, D)),
        -rng.uniform(0, 0.1, (R, H)), rng.uniform(0, 1, (R, H))))
    state = jnp.asarray(rng.normal(size=(H, D, D)) * 0.1, jnp.float32)
    caught = []
    with monkeypatch.context() as patch:
        patch.setattr(gated_delta, "_walk", lambda *operands: (
            caught.append(operands), (operands[0], operands[1]))[1])
        gated_delta._block((state, jnp.int32(run_before)),
                           (*rows, jnp.asarray(run, jnp.int32)), C)
    return caught[0]


@pytest.mark.parametrize("H, Hk, M, starts, run_before", [
    (2, 2, 1, (20,), 1), (2, 1, 4, (64,), 1), (2, 2, 4, (0,), 0),
    (8, 4, 1, (), 1), (8, 4, 4, (0, 70, 128, 191), 0)],
    ids=["H2-M1-inside_a_chunk", "H2-M4-a_chunks_first_row",
         "H2-M4-the_blocks_first_row", "H8k4-M1-one_segment",
         "H8k4-M4-starts_of_every_kind"])
def test_the_walks_kernels_are_the_plain_scan(H, Hk, M, starts, run_before,
                                              monkeypatch):
    """``_walk`` at kernel-sized shapes — the Pallas kernels,
    interpreted on the CPU, behind their own ``custom_vjp`` — against
    the plain scan differentiated by JAX: the chunks' output, the state
    that leaves, and the cotangent of every operand and of the state
    that enters."""
    operands = _walk_operands(H, Hk, M, starts, run_before, monkeypatch)
    assert gated_delta.walk_form(64, 128, 128) == "kernel"
    assert operands[1].shape == (M, H, 64, 128)
    assert np.abs(np.asarray(operands[0])).max() > 0
    rng = np.random.default_rng(7)
    weights = [jnp.asarray(rng.normal(size=shape), jnp.float32)
               for shape in ((H, 128, 128), (M, H, 64, 128))]

    def graded(walk):
        return jax.jit(lambda *a: jax.vjp(walk, *a)[1](tuple(weights))
                       + tuple(walk(*a)))(*operands)

    got = graded(gated_delta._walk)
    want = graded(gated_delta._scan_walk)
    for name, a, b in zip(WALK_NAMES + ("left", "out"), got, want):
        a, b = np.asarray(a), np.asarray(b)
        # a segment that starts at the block's first row reads nothing
        # of the state that enters: that state's cotangent is zero
        wiped = name == "state" and 0 in starts
        assert a.shape == b.shape and (np.abs(b).max() > 0) != wiped, name
        assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max(), name


def _kernel_calls(jaxpr, found=None):
    """The names of a jaxpr's ``pallas_call`` equations, those inside
    its equations' own jaxprs too. A site of the walk holds its kernel
    TWICE: compiled (a TPU) and interpreted (anywhere else), one taken
    when the program is lowered."""
    import collections

    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, found)
    return found


def _rule_gradient(dk, dv, chunk, policy="none"):
    """The jaxpr of the rule's gradient on one 128-row sequence of two
    heads — under a ``jax.checkpoint`` with ``policy``, if any."""
    rows = tuple(jnp.ones(shape, jnp.float32) for shape in (
        (1, S, 1, dk), (1, S, 1, dk), (1, S, 2, dv), (1, S, 2), (1, S, 2)))
    seg = jnp.ones((1, S), jnp.int32)

    def rule(*rows):
        return gated_delta.gated_delta_rule(*rows, seg, chunk)

    if policy != "none":
        rule = jax.checkpoint(rule, policy=policy)
    return jax.make_jaxpr(jax.grad(lambda *rows: rule(*rows).sum(),
                                   range(5)))(*rows).jaxpr


def test_the_shapes_alone_choose_the_walks_form():
    """The toy shapes of this file's other tests (states of 16 × 8,
    chunks of 8, 16, 64) walk under ``lax.scan`` and hold no kernel; the
    benchmark cell's (chunks of 64 rows, a 128 × 128 state) walk in the
    kernels — forward at the rule's two forward sites, in reverse once."""
    for chunk in (8, 16, 64, S):
        assert gated_delta.walk_form(chunk, DK, DV) == "scan"
    assert gated_delta.walk_form(64, 128, 128) == "kernel"
    for C, dk, dv in ((60, 128, 128), (64, 64, 128), (64, 128, 192)):
        assert gated_delta.walk_form(C, dk, dv) == "scan"
    assert not _kernel_calls(_rule_gradient(DK, DV, 16))
    assert _kernel_calls(_rule_gradient(128, 128, 64)) == {
        "gdn_walk_fwd": 2 * 2, "gdn_walk_bwd": 2}


def test_a_turn_that_keeps_the_names_still_walks_forward_twice():
    """The rule inside a checkpoint at kernel-sized shapes: a plain
    turn runs the forward kernel at three sites (the pass, the turn's
    recomputation, block by block inside the rule's backward), one
    that keeps ``KEPT`` at two — and the reverse kernel at one either
    way."""
    kept = jax.checkpoint_policies.save_only_these_names(*gated_delta.KEPT)
    assert _kernel_calls(_rule_gradient(128, 128, 64, None)) == {
        "gdn_walk_fwd": 2 * 3, "gdn_walk_bwd": 2}
    assert _kernel_calls(_rule_gradient(128, 128, 64, kept)) == {
        "gdn_walk_fwd": 2 * 2, "gdn_walk_bwd": 2}
