"""ops/seq_attention: the tiled walk over a packed sequence against the
dense masked softmax (forward and gradients, the kernels in interpret
mode), the tiles it skips, and the count of those it visits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import glm4_moe_lite as glm
from predictionio_tpu.ops import seq_attention as sa
from tests.kernel_calls import kernel_calls

H, D, DV = 2, 16, 8


def _dense(q, k, v, seg, scale):
    """The plain form: every score of the sequence, masked (same
    segment, causal, not padding), one softmax; a key-value head
    repeated for each query head of its group (in float32, so that a
    group's cotangents are summed before they are rounded, not after)."""
    if k.shape[1] != q.shape[1]:
        k, v = (jnp.repeat(a.astype(jnp.float32), q.shape[1] // a.shape[1],
                           axis=1) for a in (k, v))
    r = jnp.arange(q.shape[0])
    mask = ((seg[:, None] == seg[None, :]) & (r[:, None] >= r[None, :])
            & (seg[:, None] > 0))
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _segments(*lengths, S):
    """Segments 1, 2, … of these lengths from slot 0, padding behind."""
    seg = np.zeros(S, np.int32)
    at = 0
    for j, n in enumerate(lengths):
        seg[at:at + n] = j + 1
        at += n
    assert at <= S
    return seg


#: name → (segment ids, query rows a tile, keys a tile[, head widths
#: [, query heads, key-value heads]])
PACKINGS = {
    "grouped_4_of_4": (_segments(20, 70, 30, S=128), 32, 64, 64, 64, 4, 4),
    "grouped_8_over_2": (_segments(50, 90, 40, 60, S=256), 32, 128, 64, 64,
                         8, 2),
    "grouped_32_over_8": (_segments(20, 70, 38, S=128), 32, 32, 64, 64,
                          32, 8),
    "one_segment": (_segments(128, S=128), 32, 32),
    "many_short": (_segments(*([5, 9, 2, 17, 3, 11, 7] * 4), S=256), 32, 32),
    "crosses_three_tiles": (_segments(20, 70, 38, S=128), 32, 32),
    "ends_on_tile_edges": (_segments(32, 64, 32, S=128), 32, 32),
    "padding_tail": (_segments(40, 30, S=128), 32, 32),
    "one_tile": (_segments(12, 20, S=32), 32, 32),
    "wide_key_tiles": (_segments(50, 90, 40, 60, S=256), 32, 128),
    "narrow_key_tiles": (_segments(50, 90, 40, 60, S=256), 64, 16),
    "lane_wide_heads": (_segments(20, 70, 30, S=128), 32, 64, 128, 128),
    "lane_wide_heads_one_tile": (_segments(12, 20, S=32), 32, 32, 256, 128),
}


def _operands(S, dtype, D=D, DV=DV, H=H, Hkv=None):
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(size=(S, h, D)), dtype)
            for h in (H, Hkv or H))
    return q, k, jnp.asarray(rng.normal(size=(S, Hkv or H, DV)), dtype)


def _value_and_grads(attend, q, k, v, seg):
    """Σ w · attend(q, k, v) over the REAL rows and its gradients."""
    w = jnp.asarray(np.random.default_rng(9).normal(
        size=q.shape[:2] + v.shape[2:]) * (seg > 0)[:, None, None],
        jnp.float32)
    return jax.jit(jax.value_and_grad(
        lambda q, k, v: (attend(q, k, v).astype(jnp.float32) * w).sum(),
        (0, 1, 2)))(q, k, v)


def _tiled(seg, bq, bk):
    return lambda q, k, v: sa.segment_attention(
        q, k, v, jnp.asarray(seg), bq, bk, q.shape[-1] ** -0.5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("packing", sorted(PACKINGS))
def test_tiled_equals_dense_forward_and_gradients(packing, dtype):
    seg, bq, bk, *widths = PACKINGS[packing]
    q, k, v = _operands(len(seg), dtype, *widths)
    real = seg > 0
    out = _tiled(seg, bq, bk)(q, k, v)
    assert out.dtype == v.dtype and bool(jnp.isfinite(out).all())
    want = _dense(q, k, v, jnp.asarray(seg), q.shape[-1] ** -0.5)
    # float32: the same sums in another order; bfloat16: the
    # probabilities are rounded before they are normalised, not after
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32)[real],
                               np.asarray(want)[real], atol=tol, rtol=tol)
    got = _value_and_grads(_tiled(seg, bq, bk), q, k, v, seg)
    ref = _value_and_grads(
        lambda q, k, v: _dense(q, k, v, jnp.asarray(seg), q.shape[-1] ** -0.5), q, k, v, seg)
    for name, a, b in zip("qkv", got[1], ref[1]):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.dtype == b.dtype and np.isfinite(a).all()
        scale = np.sqrt((b ** 2).mean())
        assert np.abs(a - b).max() <= 2.5 * tol * max(scale, 1.0), name
        # a padding row has no key and is nobody's key
        assert not a[~real].any(), name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_groups_of_eight_over_a_head_of_256(dtype):
    """``qwen3_next``'s full layer: 16 query heads over 2 key-value
    heads of width 256, through the same kernels — output and all three
    gradients against the dense form. In bfloat16 a key-value head's
    cotangent sums eight query heads' parts: compared to one unit in
    the last place of the largest."""
    seg, bq, bk = _segments(20, 70, 38, S=128), 32, 32
    q, k, v = _operands(128, dtype, 256, 256, 16, 2)
    real = seg > 0
    dense = lambda q, k, v: _dense(  # noqa: E731
        q, k, v, jnp.asarray(seg), 256 ** -0.5)
    out = _tiled(seg, bq, bk)(q, k, v)
    assert out.shape == (128, 16, 256) and out.dtype == v.dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32)[real],
                               np.asarray(dense(q, k, v))[real], atol=tol,
                               rtol=tol)
    got = _value_and_grads(_tiled(seg, bq, bk), q, k, v, seg)
    ref = _value_and_grads(dense, q, k, v, seg)
    ulp = 2e-5 if dtype == jnp.float32 else 2.0 ** -7
    for name, a, b in zip("qkv", got[1], ref[1]):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape and np.isfinite(a).all()
        assert np.abs(a - b).max() <= ulp * max(np.abs(b).max(), 1.0), name
        assert not a[~real].any(), name


def test_one_key_value_head_a_query_head_is_the_ungrouped_kernel():
    """Grouped against ungrouped, bit for bit: 8 query heads over 2
    key-value heads, and the same with each key-value head handed over
    four times as heads of its own (``Hkv = H``: the grid's head IS
    the key-value head, the kernels every caller had before there were
    groups). Forward and dq do the same sums in the same order; dk/dv
    sums a group inside the kernel and is compared to rounding."""
    seg, bq, bk, D_, DV_, H_, Hkv_ = PACKINGS["grouped_8_over_2"]
    q, k, v = _operands(len(seg), jnp.float32, D_, DV_, H_, Hkv_)
    rep = lambda a: jnp.repeat(a, H_ // Hkv_, axis=1)  # noqa: E731
    grouped = _value_and_grads(_tiled(seg, bq, bk), q, k, v, seg)
    alone = _value_and_grads(_tiled(seg, bq, bk), q, rep(k), rep(v), seg)
    np.testing.assert_array_equal(
        np.asarray(_tiled(seg, bq, bk)(q, k, v)),
        np.asarray(_tiled(seg, bq, bk)(q, rep(k), rep(v))))
    np.testing.assert_array_equal(np.asarray(grouped[1][0]),
                                  np.asarray(alone[1][0]))
    for a, b in zip(grouped[1][1:], alone[1][1:]):
        summed = np.asarray(b).reshape(len(seg), Hkv_, H_ // Hkv_, -1).sum(2)
        np.testing.assert_allclose(np.asarray(a), summed, atol=2e-5,
                                   rtol=2e-5)


def test_key_value_heads_must_divide_the_query_heads():
    q, k, v = _operands(64, jnp.float32, H=4, Hkv=3)
    with pytest.raises(ValueError, match="do not group"):
        sa.segment_attention(q, k, v, jnp.ones(64, jnp.int32), 32, 32, 1.0)


def test_a_skipped_tile_is_never_read():
    """Two tile-aligned segments, the first one's keys and values NaN:
    the second's outputs and gradients are those of the clean run — a
    masked product would have given 0 · NaN."""
    seg, bq, bk = _segments(64, 64, S=128), 32, 32
    q, k, v = _operands(128, jnp.float32)
    second = seg == 2
    rows = jnp.asarray(second)[:, None, None]
    poison = jnp.where(jnp.asarray(seg == 1)[:, None, None], jnp.nan, 0.0)

    def run(k, v):
        out, grads = jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.where(
                rows, _tiled(seg, bq, bk)(q, k, v), 0.0).sum(),
            (0, 1, 2)))(q, k, v)
        return [np.asarray(out)] + [np.asarray(g)[second] for g in grads]

    clean, dirty = run(k, v), run(k + poison, v + poison)
    for a, b in zip(clean, dirty):
        assert np.isfinite(b).all()
        np.testing.assert_array_equal(a, b)


def test_intervals_and_tile_pairs_by_hand():
    """Three sequences of 8 slots, tiles of 2 rows × 2 keys (or 4 × 2):
    which tiles each block visits, counted by hand."""
    seg = np.array([[1, 1, 1, 1, 1, 1, 1, 1],      # one history
                    [1, 1, 2, 2, 2, 3, 3, 0],      # 2 + 3 + 2, a PAD
                    [1, 2, 2, 0, 0, 0, 0, 0]],     # mostly padding
                   np.int32)
    first = sa.first_keys(seg, np)
    np.testing.assert_array_equal(first, [[0, 0, 0, 0, 0, 0, 0, 0],
                                          [0, 0, 2, 2, 2, 5, 5, 8],
                                          [0, 1, 1, 4, 5, 6, 7, 8]])
    np.testing.assert_array_equal(first, sa.first_keys(jnp.asarray(seg)))
    lo, hi = sa.tile_intervals(first, 2, 2, np)
    # block i of rows 2i, 2i+1 starts at the tile of its earliest first
    # key; tile j ends at the last block that starts at or before it
    np.testing.assert_array_equal(lo, [[0, 0, 0, 0], [0, 1, 1, 2],
                                       [0, 0, 2, 3]])
    np.testing.assert_array_equal(hi, [[3, 3, 3, 3], [0, 2, 3, 3],
                                       [1, 1, 2, 3]])
    for got, want in zip(sa.tile_intervals(jnp.asarray(first), 2, 2),
                         (lo, hi)):
        np.testing.assert_array_equal(got, want)
    # tiles visited: 1+2+3+4, 1+1+2+2, 1+2+1+1 = 21 of 4 pairs each;
    # to the diagonal alone 10 tiles a sequence
    assert sa.tile_pairs(seg, 2, 2) == 84
    assert sa.tile_pairs(seg, 2, 2, skip=False) == 120
    # blocks of 4 rows: tiles 0-1 and 0-3; 0-1 and 1-3; 0-1 and 2-3
    lo4, hi4 = sa.tile_intervals(first, 4, 2, np)
    np.testing.assert_array_equal(lo4, [[0, 0], [0, 1], [0, 2]])
    np.testing.assert_array_equal(hi4, [[1, 1, 1, 1], [0, 1, 1, 1],
                                        [0, 0, 1, 1]])
    assert sa.tile_pairs(seg, 4, 2) == (6 + 5 + 4) * 8
    assert sa.tile_pairs(seg, 4, 2, skip=False) == 3 * 6 * 8


def test_every_real_pair_lies_in_a_visited_tile():
    """The packer's own sequences: each real (query, key) pair is inside
    the forward interval of its block and the backward interval of its
    tile, and the two walks visit the same tiles."""
    rng = np.random.default_rng(4)
    packed = glm.pack_histories(
        [rng.integers(1, 50, n) for n in rng.integers(2, 90, 40)], 128, 1)
    for bq, bk in ((32, 32), (32, 16), (16, 64)):
        first = sa.first_keys(packed.seg, np)
        lo, hi = sa.tile_intervals(first, bq, bk, np)
        tiles = 0
        for s in range(packed.seg.shape[0]):
            fwd = {(i, j) for i in range(128 // bq)
                   for j in range(lo[s, i], ((i + 1) * bq - 1) // bk + 1)}
            bwd = {(i, j) for j in range(128 // bk)
                   for i in range(j * bk // bq, hi[s, j] + 1)}
            assert fwd == bwd
            tiles += len(fwd)
            real = np.flatnonzero(packed.seg[s] > 0)
            assert all((r // bq, key // bk) in fwd
                       for r in real for key in (first[s, r], r))
        assert sa.tile_pairs(packed.seg, bq, bk) == tiles * bq * bk


def test_tiles_must_divide_the_sequence():
    q, k, v = _operands(96, jnp.float32)
    with pytest.raises(ValueError, match="do not divide"):
        sa.segment_attention(q, k, v, jnp.ones(96, jnp.int32), 64, 32, 1.0)


def test_the_model_counts_its_tiles():
    """The GLM backbone's train records the pairs inside the tiles its attention
    visits beside the real ones, by the tiles ``_attention`` uses."""
    from predictionio_tpu.utils import tracing

    c = glm.GlmConfig(
        hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_attention_heads=2, q_lora_rank=16, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        n_routed_experts=4, num_experts_per_tok=2, num_hidden_layers=2,
        vocab_size=40, seq_len=64, seqs_per_step=1, attn_block=16,
        token_chunk=64)
    rng = np.random.default_rng(2)
    hist = [rng.integers(1, 40, n) for n in (30, 20, 9, 5)]
    with tracing.verb("train.run"):
        glm.BACKBONE.train(hist, c, 1, 1e-3, 0)
    attrs = next(s["attrs"] for s in tracing.last_verb("train.run")
                 if s["name"] == "seqrec.pack")
    assert glm._attn_tiles(c, 64) == (16, 64)
    # one sequence of 64 slots, key tiles as wide as the sequence: four
    # blocks of 16 rows, each visiting the one tile
    assert attrs["attn_tile_pairs"] == 4 * 16 * 64
    # today's block walk to the diagonal: 16 × (16 + 32 + 48 + 64)
    assert attrs["attn_dense_pairs"] == 16 * 160
    assert attrs["attn_pairs"] == sum(n * (n + 1) // 2
                                      for n in (30, 20, 9, 5))


# -- the window ----------------------------------------------------------------


def _dense_window(q, k, v, seg, scale, window):
    """:func:`_dense` with one more term in the mask: a key fewer than
    ``window`` rows behind its query."""
    if k.shape[1] != q.shape[1]:
        k, v = (jnp.repeat(a.astype(jnp.float32), q.shape[1] // a.shape[1],
                           axis=1) for a in (k, v))
    r = jnp.arange(q.shape[0])
    mask = ((seg[:, None] == seg[None, :]) & (r[:, None] >= r[None, :])
            & (seg[:, None] > 0) & (r[:, None] - r[None, :] < window))
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _windowed(seg, bq, bk, window):
    return lambda q, k, v: sa.segment_attention(
        q, k, v, jnp.asarray(seg), bq, bk, q.shape[-1] ** -0.5, window)


#: name → (segment ids, query rows a tile, keys a tile, window, query
#: heads, key-value heads): segments longer than, equal to and shorter
#: than the window, windows that start mid-tile and on a tile's edge
WINDOWS = {
    "longer_equal_shorter": (_segments(100, 40, 20, 60, S=256), 32, 32, 40,
                             4, 2),
    "starts_mid_tile": (_segments(150, 90, S=256), 32, 32, 37, 2, 2),
    "on_tile_edges": (_segments(128, 128, S=256), 32, 32, 64, 2, 1),
    "one_long_segment": (_segments(256, S=256), 32, 64, 48, 8, 2),
    "wider_than_a_block": (_segments(200, 30, S=256), 16, 32, 70, 4, 4),
    "window_of_one": (_segments(50, 60, S=128), 32, 32, 1, 2, 2),
    "padding_tail": (_segments(90, S=128), 32, 32, 33, 4, 2),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(WINDOWS))
def test_windowed_equals_dense_forward_and_gradients(name, dtype):
    seg, bq, bk, window, H_, Hkv_ = WINDOWS[name]
    q, k, v = _operands(len(seg), dtype, H=H_, Hkv=Hkv_)
    real = seg > 0
    want_fn = lambda q, k, v: _dense_window(  # noqa: E731
        q, k, v, jnp.asarray(seg), q.shape[-1] ** -0.5, window)
    out = _windowed(seg, bq, bk, window)(q, k, v)
    assert out.dtype == v.dtype and bool(jnp.isfinite(out).all())
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32)[real],
                               np.asarray(want_fn(q, k, v))[real],
                               atol=tol, rtol=tol)
    got = _value_and_grads(_windowed(seg, bq, bk, window), q, k, v, seg)
    ref = _value_and_grads(want_fn, q, k, v, seg)
    for which, a, b in zip("qkv", got[1], ref[1]):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all()
        scale = np.sqrt((b ** 2).mean())
        assert np.abs(a - b).max() <= 2.5 * tol * max(scale, 1.0), which
        assert not a[~real].any(), which


#: the accepted cells' head shapes: (query heads, key-value heads, D, Dv)
HEAD_SHAPES = {"glm_latent": (2, 2, 256, 256), "lfm2_grouped": (8, 2, 64, 64)}


@pytest.mark.parametrize("window", [None, 128, 1000],
                         ids=["none", "window_is_S", "window_over_S"])
@pytest.mark.parametrize("shape", sorted(HEAD_SHAPES))
def test_no_window_is_todays_program_bit_for_bit(shape, window):
    """``window=None`` and a window of S or more: the same intervals,
    the same first keys, the same output and gradients as the call
    without the argument — bit for bit, and the same lowered text."""
    H_, Hkv_, D_, Dv_ = HEAD_SHAPES[shape]
    seg = _segments(20, 70, 30, S=128)
    q, k, v = _operands(128, jnp.bfloat16, D_, Dv_, H_, Hkv_)
    today = _value_and_grads(_tiled(seg, 32, 64), q, k, v, seg)
    got = _value_and_grads(_windowed(seg, 32, 64, window), q, k, v, seg)
    np.testing.assert_array_equal(np.asarray(today[0]), np.asarray(got[0]))
    for a, b in zip(today[1], got[1]):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    lowered = [jax.jit(f).lower(q, k, v).as_text() for f in (
        _tiled(seg, 32, 64), _windowed(seg, 32, 64, window))]
    assert lowered[0] == lowered[1]


def _brute_tiles(seg, bq, bk, window):
    """Per sequence, the (block, tile) pairs that hold a real (query,
    key) pair or lie between one and the diagonal — by the mask's own
    definition, pair by pair."""
    S = seg.shape[-1]
    r = np.arange(S)
    out = []
    for row in seg:
        mask = ((row[:, None] == row[None, :]) & (row[:, None] > 0)
                & (r[:, None] >= r[None, :]))
        if window is not None:
            mask &= r[:, None] - r[None, :] < window
        tiles = set()
        for i in range(S // bq):
            diag = ((i + 1) * bq - 1) // bk
            block = mask[i * bq:(i + 1) * bq]
            keys = np.flatnonzero(block.any(0))
            lo = keys.min() // bk if keys.size else diag
            tiles |= {(i, j) for j in range(min(lo, diag), diag + 1)}
        out.append(tiles)
    return out


@pytest.mark.parametrize("window", [None, 1, 7, 16, 33, 64, 200])
def test_window_intervals_and_tile_pairs_against_brute_force(window):
    """Random packings: the forward interval of every block and the
    backward interval of every tile are what the mask says, pair by
    pair; ``first`` never falls; ``tile_pairs`` counts those tiles."""
    rng = np.random.default_rng(0 if window is None else window)
    packed = glm.pack_histories(
        [rng.integers(1, 50, n) for n in rng.integers(2, 120, 30)], 128, 1)
    for bq, bk in ((32, 32), (32, 16), (16, 64)):
        first = sa.first_keys(packed.seg, np, window)
        np.testing.assert_array_equal(
            first, sa.first_keys(jnp.asarray(packed.seg), window=window))
        real = packed.seg > 0
        assert (np.diff(first, axis=-1) >= 0).all()
        lo, hi = sa.tile_intervals(first, bq, bk, np)
        want = _brute_tiles(packed.seg, bq, bk, window)
        total = 0
        for s, tiles in enumerate(want):
            fwd = {(i, j) for i in range(128 // bq)
                   for j in range(lo[s, i], ((i + 1) * bq - 1) // bk + 1)}
            bwd = {(i, j) for j in range(128 // bk)
                   for i in range(j * bk // bq, hi[s, j] + 1)}
            assert fwd == bwd == tiles
            total += len(tiles)
        assert sa.tile_pairs(packed.seg, bq, bk, window=window) == (
            total * bq * bk)
        if window is not None:
            r = np.arange(128)
            assert (first[real] >= (r[None, :] - window + 1).repeat(
                len(first), 0)[real]).all()


def test_the_window_skips_the_tiles_it_hides():
    """One long segment, the keys more than a window behind every row of
    the last block NaN: that block's outputs and its queries' gradients
    are those of the clean run — the tiles are not read, not read and
    masked. (Earlier blocks do read them, so the keys' gradients are
    not compared.)"""
    S, bq, bk, window = 256, 32, 32, 40
    seg = _segments(S, S=S)
    q, k, v = _operands(S, jnp.float32)
    last = np.arange(S) >= S - bq
    hidden = np.arange(S) < (S - bq - window + 1) // bk * bk
    rows = jnp.asarray(last)[:, None, None]
    poison = jnp.where(jnp.asarray(hidden)[:, None, None], jnp.nan, 0.0)

    def run(k, v):
        out, dq = jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.where(
                rows, _windowed(seg, bq, bk, window)(q, k, v), 0.0).sum()))(
                    q, k, v)
        return [np.asarray(out), np.asarray(dq)[last]]

    for a, b in zip(run(k, v), run(k + poison, v + poison)):
        assert np.isfinite(b).all()
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("heads", [(16, 8, 8, 2), (256, 256, 16, 2)],
                         ids=["8_over_2_of_16", "16_over_2_of_256"])
@pytest.mark.parametrize("window", [None, 40], ids=["global", "window"])
def test_dkv_by_query_head_equals_dkv_by_group(monkeypatch, window, heads):
    """Where a key-value head's query rows do not fit VMEM the dk/dv
    grid walks the query heads and the group's parts are summed outside
    the kernel: the same gradients as the grouped walk, to rounding —
    the walk ``qwen3_next``'s full layer takes at 16,384 rows (8 query
    heads × 16,384 × (256 + 256) × 2 B = 134 MB a key-value head)."""
    assert 8 * 16384 * (256 + 256) * 2 > sa._WHOLE_HEAD_BYTES
    seg, bq, bk = _segments(100, 40, 20, 60, S=256), 32, 32
    q, k, v = _operands(256, jnp.float32, *heads)
    grouped = _value_and_grads(_windowed(seg, bq, bk, window), q, k, v, seg)
    monkeypatch.setattr(sa, "_WHOLE_HEAD_BYTES", 0)
    split = _value_and_grads(_windowed(seg, bq, bk, window), q, k, v, seg)
    np.testing.assert_array_equal(np.asarray(grouped[1][0]),
                                  np.asarray(split[1][0]))
    for a, b in zip(grouped[1][1:], split[1][1:]):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-5)


def test_window_counters_against_brute_force():
    """``pack_histories(…, window=W)``: the pairs the window leaves and
    the rows it binds, against the mask counted pair by pair."""
    from predictionio_tpu.models import seq_backbone

    rng = np.random.default_rng(5)
    hist = [rng.integers(1, 50, n) for n in (3, 17, 40, 64, 100, 150, 31)]
    for window in (1, 16, 40, 64, 500):
        packed = seq_backbone.pack_histories(hist, 64, 2, seed=3,
                                             window=window)
        r = np.arange(64)
        pairs = bound = 0
        for row in packed.seg:
            mask = ((row[:, None] == row[None, :]) & (row[:, None] > 0)
                    & (r[:, None] >= r[None, :]))
            kept = mask & (r[:, None] - r[None, :] < window)
            pairs += int(kept.sum())
            bound += int((kept.sum(1) < mask.sum(1)).sum())
        c = packed.counters
        assert c["attn_pairs_window"] == pairs
        assert c["window_bound_tokens"] == bound
        assert c["attn_pairs"] == int(sum(
            (row[:, None] == row[None, :]).__and__(row[:, None] > 0).__and__(
                r[:, None] >= r[None, :]).sum() for row in packed.seg))
        assert (c["attn_pairs_window"] == c["attn_pairs"]) == (bound == 0)
    assert "attn_pairs_window" not in seq_backbone.pack_histories(
        hist, 64, 2, seed=3).counters


# -- the block rule: two streams, a row's keys decided by blocks ---------------


def _blocks_of(seg, block):
    """A slot's block inside its segment, from the segment's first row
    — brute force: a loop over the slots."""
    out, at = np.zeros(len(seg), np.int64), 0
    for r in range(len(seg)):
        if r and seg[r] != seg[r - 1]:
            at = r
        out[r] = (r - at) // block
    return out


def _bd_mask(seg, block):
    """The four visibility lines over both streams' 2·S rows (clean
    rows, then noised), brute force."""
    seg = np.asarray(seg)
    blk = _blocks_of(seg, block)
    same = (seg[:, None] == seg[None, :]) & (seg[:, None] > 0)
    clean_clean = same & (blk[None, :] <= blk[:, None])
    noised_clean = same & (blk[None, :] < blk[:, None])
    noised_noised = same & (blk[None, :] == blk[:, None])
    clean_noised = np.zeros_like(same)
    return np.block([[clean_clean, clean_noised],
                     [noised_clean, noised_noised]])


def _bd_dense(q, k, v, mask, scale):
    if k.shape[1] != q.shape[1]:
        k, v = (jnp.repeat(a.astype(jnp.float32), q.shape[1] // a.shape[1],
                           axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _cut_by_the_packing():
    """What ``pack_histories`` makes of a history longer than a
    sequence and three short ones: the long one cut into a full
    sequence and a remainder that shares the second sequence."""
    packed = glm.pack_histories(
        [np.arange(1, 151) % 40 + 1, np.arange(1, 23), np.arange(1, 4),
         np.arange(1, 10)], 128, seed=0)
    assert packed.counters["split"] >= 1
    return packed.seg


#: name → (segment ids, query rows a tile, keys a tile, query heads,
#: key-value heads, block length)
BD_PACKINGS = {
    "shorter_than_a_block": (_segments(3, 1, 2, 33, 64, 7, S=128), 32, 32,
                             2, 2, 4),
    "partial_last_block": (_segments(21, 70, 30, S=128), 32, 64, 4, 4, 4),
    "cut_by_the_packing_0": (_cut_by_the_packing()[0], 32, 32, 2, 2, 4),
    "cut_by_the_packing_1": (_cut_by_the_packing()[1], 32, 32, 2, 2, 4),
    "a_block_starts_a_tile": (_segments(32, 64, 32, S=128), 32, 32, 2, 2, 4),
    "a_block_ends_a_tile": (_segments(28, 36, 64, S=128), 32, 32, 2, 2, 4),
    "blocks_straddle_tiles": (_segments(31, 33, 40, S=128), 32, 32, 2, 1, 4),
    "one_segment": (_segments(128, S=128), 32, 32, 2, 2, 4),
    "grouped_8_over_2": (_segments(50, 90, 40, 60, S=256), 32, 128, 8, 2, 4),
    "many_short_narrow_keys": (
        _segments(*([5, 9, 2, 17, 3, 11, 7] * 4), S=256), 64, 16, 2, 2, 4),
    "blocks_of_8": (_segments(50, 90, S=256), 32, 128, 4, 2, 8),
}


def _bd_case(name, dtype=jnp.float32):
    seg, bq, bk, H_, Hkv_, block = BD_PACKINGS[name]
    seg = np.asarray(seg)
    q, k, v = _operands(2 * len(seg), dtype, D, D, H_, Hkv_)
    tiled = lambda q, k, v: sa.block_attention(  # noqa: E731
        q, k, v, jnp.asarray(seg), bq, bk, D ** -0.5, block)
    mask = _bd_mask(seg, block)
    dense = lambda q, k, v: _bd_dense(q, k, v, mask, D ** -0.5)  # noqa: E731
    return seg, np.tile(seg, 2), q, k, v, tiled, dense


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("packing", sorted(BD_PACKINGS))
def test_block_rule_equals_the_dense_mask_forward(packing, dtype):
    _, seg2, q, k, v, tiled, dense = _bd_case(packing, dtype)
    out = tiled(q, k, v)
    assert out.dtype == v.dtype and bool(jnp.isfinite(out).all())
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32)[seg2 > 0],
                               np.asarray(dense(q, k, v))[seg2 > 0],
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("packing", sorted(BD_PACKINGS))
def test_block_rule_equals_the_dense_mask_all_three_gradients(packing):
    """dq, dk and dv: a clean key collects from clean AND noised
    queries, a noised key from its own block only."""
    _, seg2, q, k, v, tiled, dense = _bd_case(packing)
    got = _value_and_grads(tiled, q, k, v, seg2)
    ref = _value_and_grads(dense, q, k, v, seg2)
    np.testing.assert_allclose(float(got[0]), float(ref[0]), rtol=1e-5)
    for name, a, b in zip("qkv", got[1], ref[1]):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all()
        assert np.abs(a - b).max() <= 5e-5 * max(
            np.sqrt((b ** 2).mean()), 1.0), name
        assert not a[seg2 == 0].any(), name


@pytest.mark.parametrize("packing", ["partial_last_block",
                                     "blocks_straddle_tiles",
                                     "shorter_than_a_block"])
def test_a_noised_row_never_sees_its_own_blocks_clean_keys(packing):
    """The leak that would let a MASK row copy its answer: the clean
    keys and values of a block changed — every noised row of that block
    and of the blocks before it reads as before, to the bit; the rows
    of later blocks do not."""
    seg, _, q, k, v, tiled, _ = _bd_case(packing)
    S, blk = len(seg), _blocks_of(seg, BD_PACKINGS[packing][5])
    rows = np.flatnonzero((seg == seg.max()) & (blk == 1))
    assert rows.size                       # the last segment's second block
    bump = np.zeros(2 * S, bool)
    bump[rows] = True                      # clean rows only
    k2 = jnp.where(bump[:, None, None], k + 1.0, k)
    v2 = jnp.where(bump[:, None, None], v - 2.0, v)
    a, b = np.asarray(tiled(q, k, v)), np.asarray(tiled(q, k2, v2))
    earlier = np.flatnonzero((seg == seg.max()) & (blk <= 1)) + S
    np.testing.assert_array_equal(a[earlier], b[earlier])
    later = np.flatnonzero((seg == seg.max()) & (blk > 1)) + S
    if later.size:
        assert np.abs(a[later] - b[later]).max() > 1e-3
    # the block's own CLEAN rows see those keys: the rule, not a hole
    assert np.abs(a[rows] - b[rows]).max() > 1e-3


@pytest.mark.parametrize("packing", ["partial_last_block",
                                     "blocks_straddle_tiles"])
def test_a_clean_row_never_sees_a_noised_key(packing):
    """Every noised key and value changed: the clean stream reads as
    before, to the bit, forward — and no gradient reaches a noised key
    from a clean query."""
    seg, seg2, q, k, v, tiled, _ = _bd_case(packing)
    S = len(seg)
    noised = np.arange(2 * S) >= S
    k2 = jnp.where(noised[:, None, None], k * -3.0 + 1.0, k)
    v2 = jnp.where(noised[:, None, None], v + 5.0, v)
    np.testing.assert_array_equal(np.asarray(tiled(q, k, v))[:S],
                                  np.asarray(tiled(q, k2, v2))[:S])
    w = jnp.asarray((~noised & (seg2 > 0))[:, None, None], jnp.float32)
    dk, dv = jax.grad(lambda k, v: (tiled(q, k, v) * w).sum(), (0, 1))(k, v)
    assert not np.asarray(dk)[S:].any() and not np.asarray(dv)[S:].any()
    assert np.asarray(dk)[:S].any()


def test_one_stream_is_the_clean_streams_rule():
    """Operands of S rows (serving): a row sees its segment's keys up
    to the END of its block — the clean rows of the two-stream call, to
    the bit."""
    seg, _, q, k, v, tiled, _ = _bd_case("partial_last_block")
    _, bq, bk, _, _, block = BD_PACKINGS["partial_last_block"]
    S = len(seg)
    one = sa.block_attention(q[:S], k[:S], v[:S], jnp.asarray(seg), bq, bk,
                             D ** -0.5, block)
    np.testing.assert_array_equal(np.asarray(one),
                                  np.asarray(tiled(q, k, v))[:S])
    with pytest.raises(ValueError, match="one or two streams"):
        sa.block_attention(q[:S + bq], k[:S + bq], v[:S + bq],
                           jnp.asarray(seg), bq, bk, 1.0, block)


#: name → (query heads, key-value heads, head width, value width,
#: window) of the three accepted backbones' attention, and the digest
#: of (output, dq, dk, dv) on seeded bfloat16 operands as the kernels
#: gave it BEFORE they knew a block rule (commit c7e5d9b, this JAX,
#: interpret mode)
ACCEPTED = {
    "glm4_moe_lite": (4, 4, 48, 32, None, "3f0df40fc8626d11"),
    "lfm2_moe": (8, 2, 16, 16, None, "a72c483dd54e1778"),
    "smallthinker": (14, 2, 32, 32, 24, "6c90dd6571007fa0"),
}


@pytest.mark.parametrize("shape", sorted(ACCEPTED))
def test_no_block_rule_is_the_result_of_before_bit_for_bit(shape):
    import hashlib

    H_, Hkv_, D_, Dv_, window, want = ACCEPTED[shape]
    seg = _segments(20, 70, 30, S=128)
    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.normal(size=(128, h, w)), jnp.bfloat16)
               for h, w in ((H_, D_), (Hkv_, D_), (Hkv_, Dv_)))
    w = jnp.asarray(rng.normal(size=(128, H_, Dv_))
                    * (seg > 0)[:, None, None], jnp.float32)
    attend = lambda q, k, v: sa.segment_attention(  # noqa: E731
        q, k, v, jnp.asarray(seg), 32, 64, D_ ** -0.5, window)
    out = jax.jit(attend)(q, k, v)
    grads = jax.jit(jax.grad(
        lambda q, k, v: (attend(q, k, v).astype(jnp.float32) * w).sum(),
        (0, 1, 2)))(q, k, v)
    h = hashlib.sha256()
    for a in (out, *grads):
        h.update(np.asarray(a.astype(jnp.float32)).tobytes())
    assert h.hexdigest()[:16] == want


def _random_packing(rng, S):
    lengths, left = [], S - int(rng.integers(0, S // 4))
    while left > 0:
        n = int(min(left, rng.choice([1, 2, 3, 4, 5, 7, 8, 13, 31, 64, 90])))
        lengths.append(n)
        left -= n
    return _segments(*lengths, S=S)


@pytest.mark.parametrize("seed", range(6))
def test_block_intervals_and_tile_pairs_against_brute_force(seed):
    """On a random packing: every visible pair lies in a visited tile;
    a tile is visited only where its run says so; the key tiles' runs
    of query blocks are the query blocks' runs of tiles, transposed;
    ``block_tile_pairs`` counts exactly the visited tiles."""
    rng = np.random.default_rng(seed)
    S, bq, bk, block = 256, int(rng.choice([32, 64])), int(
        rng.choice([16, 32, 128])), int(rng.choice([2, 4, 8]))
    seg = _random_packing(rng, S)
    mask = _bd_mask(seg, block)
    spans = sa.block_spans(seg, block, np)
    fwd, bwd = sa.block_intervals(*spans, bq, bk, np)
    nq, T = 2 * S // bq, 2 * S // bk
    visited = np.zeros((nq, T), bool)
    for i in range(nq):
        for lo, hi in ((fwd[0, i], fwd[1, i]), (fwd[2, i], fwd[3, i])):
            assert 0 <= lo and hi < T
            visited[i, lo:hi + 1] = True
    assert visited.any(1).all()            # no block divides by zero
    tiles = mask.reshape(nq, bq, T, bk).any((1, 3))
    assert not (tiles & ~visited).any()
    back = np.zeros((nq, T), bool)
    for j in range(T):
        for lo, hi in ((bwd[0, j], bwd[1, j]), (bwd[2, j], bwd[3, j])):
            back[max(lo, 0):hi + 1, j] = True
    np.testing.assert_array_equal(back, visited)
    assert sa.block_tile_pairs(seg[None], bq, bk, block) == int(
        visited.sum()) * bq * bk
    # the rows' two intervals ARE the mask
    a0, a1, b0, b1 = sa.stream_spans(*spans, np)
    col = np.arange(2 * S)[None, :]
    np.testing.assert_array_equal(
        ((col >= a0[:, None]) & (col <= a1[:, None]))
        | ((col >= b0[:, None]) & (col <= b1[:, None])), mask)
    # and the walk follows the pairs: never the causal walk over 2·S
    causal = sum(((i + 1) * bq - 1) // bk + 1 for i in range(nq)) * bq * bk
    assert sa.block_tile_pairs(seg[None], bq, bk, block) < causal


@pytest.mark.parametrize("block", [2, 4, 8])
def test_block_pairs_against_brute_force_and_the_closed_form(block):
    rng = np.random.default_rng(block)
    seg = _random_packing(rng, 256)
    sizes = np.bincount(seg)[1:]
    assert sa.block_pairs(sizes, block) == int(_bd_mask(seg, block).sum())
    for n in (block, 8 * block, 8192):
        assert sa.block_pairs([n], block) == n * (n + block)
    assert sa.block_pairs([8192], 4) == 67_141_632


# -- what a caller's checkpoint keeps ------------------------------------------


def _kept_case(name):
    """(attend(q, k, v), operands of TWO sequences, the forward
    kernel's name): the global and the window form, grouped heads, and
    the block rule on both streams."""
    if name == "block_rule":
        seg, _, q, k, v, tiled, _ = _bd_case("grouped_8_over_2")
        fwd = "seq_attention_bd_fwd"
    else:
        seg, bq, bk, window, H_, Hkv_ = {
            "global": (_segments(20, 70, 30, S=128), 32, 64, None, 4, 4),
            "window": WINDOWS["longer_equal_shorter"],
            "grouped_8_over_2": (PACKINGS["grouped_8_over_2"][0], 32, 128,
                                 None, 8, 2),
            "grouped_window": WINDOWS["one_long_segment"],
        }[name]
        q, k, v = _operands(len(seg), jnp.float32, 64, 64, H_, Hkv_)
        tiled, fwd = _windowed(seg, bq, bk, window), "seq_attention_fwd"
    rng = np.random.default_rng(4)
    return tiled, tuple(jnp.stack([a, jnp.asarray(
        rng.normal(size=a.shape), a.dtype)]) for a in (q, k, v)), fwd


@pytest.mark.parametrize("mapped", [False, True],
                         ids=["one_sequence", "under_lax_map"])
@pytest.mark.parametrize("name", ["global", "window", "grouped_8_over_2",
                                  "grouped_window", "block_rule"])
def test_a_checkpoint_that_keeps_the_names_runs_the_forward_once(name,
                                                                 mapped):
    """Attention between two products — a layer turn — under
    ``jax.checkpoint(turn, policy=save_only_these_names(*KEPT))``
    against a plain ``jax.checkpoint(turn)``: the gradient program
    holds HALF the forward kernel's calls (one call site where the
    plain turn's recomputation adds a second; ``platform_dependent``
    shows a site once a branch) and as many of dq and dk/dv, and its
    value and gradients are the plain turn's bit for bit — alone, and
    under ``jax.lax.map`` over sequences, how the backbones call it."""
    attend, (q, k, v), fwd = _kept_case(name)
    if not mapped:
        q, k, v = q[0], k[0], v[0]

    def turn(q, k, v):
        one = lambda a: jnp.tanh(attend(a[0] * 0.5, a[1], a[2] * 2.0))  # noqa: E731,E501
        out = jax.lax.map(one, (q, k, v)) if mapped else one((q, k, v))
        return (out ** 2).sum()

    plain = jax.value_and_grad(jax.checkpoint(turn), (0, 1, 2))
    kept = jax.value_and_grad(jax.checkpoint(
        turn, policy=jax.checkpoint_policies.save_only_these_names(
            *sa.KEPT)), (0, 1, 2))
    calls = {key: kernel_calls(jax.make_jaxpr(f)(q, k, v).jaxpr)
             for key, f in (("plain", plain), ("kept", kept))}
    assert calls["plain"][fwd] == 4 and calls["kept"][fwd] == 2
    assert calls["plain"] - calls["kept"] == {fwd: 2}
    assert set(calls["kept"].values()) == {2} and len(calls["kept"]) == 3
    got, want = jax.jit(kept)(q, k, v), jax.jit(plain)(q, k, v)
    assert float(got[0]) == float(want[0])
    for which, a, b in zip("qkv", got[1], want[1]):
        assert np.asarray(b).any(), which
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), which)


@pytest.mark.parametrize("name", ["global", "block_rule"])
def test_the_names_alone_keep_nothing(name):
    """Without a policy the names do nothing: a plain checkpoint's
    gradient program runs the forward kernel twice, no checkpoint at
    all once — and what is named is the output as it is handed back
    and one float32 a row and head."""
    attend, (q, k, v), fwd = _kept_case(name)
    q, k, v = q[0], k[0], v[0]
    loss = lambda q, k, v: (attend(q, k, v) ** 2).sum()  # noqa: E731
    assert kernel_calls(jax.make_jaxpr(jax.grad(loss))(
        q, k, v).jaxpr)[fwd] == 2
    jaxpr = jax.make_jaxpr(jax.grad(jax.checkpoint(loss)))(q, k, v).jaxpr
    assert kernel_calls(jaxpr)[fwd] == 4

    def named(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "name":
                out[eqn.params["name"]] = eqn.outvars[0].aval
            for sub in jax.core.jaxprs_in_params(eqn.params):
                named(sub, out)
        return out

    avals = named(jaxpr, {})
    assert tuple(avals) == sa.KEPT == ("attn_out", "attn_lse")
    S, H_ = q.shape[:2]
    assert (avals["attn_out"].shape, avals["attn_out"].dtype) == (
        (S, H_, v.shape[-1]), v.dtype)
    assert (avals["attn_lse"].shape, avals["attn_lse"].dtype) == (
        (H_, S), jnp.float32)
