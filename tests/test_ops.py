"""Pallas kernels (interpret mode on CPU) vs numpy/XLA references."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops import (
    score_topk, score_topk_xla, segment_count, segment_mean, segment_sum,
)


class TestScoreTopK:
    def _check(self, B, N, d, k, tile=64, seed=0):
        rng = np.random.default_rng(seed)
        Q = rng.standard_normal((B, d)).astype(np.float32)
        V = rng.standard_normal((N, d)).astype(np.float32)
        vals, idx = score_topk(jnp.asarray(Q), jnp.asarray(V), k,
                               tile=tile, interpret=True)
        scores = Q @ V.T
        ref_idx = np.argsort(-scores, axis=1)[:, :k]
        ref_vals = np.take_along_axis(scores, ref_idx, axis=1)
        np.testing.assert_allclose(np.asarray(vals), ref_vals,
                                   rtol=1e-4, atol=1e-4)
        # indices must produce the same scores (ties may permute)
        got = np.take_along_axis(scores, np.asarray(idx), axis=1)
        np.testing.assert_allclose(got, ref_vals, rtol=1e-4, atol=1e-4)

    def test_exact_tile_multiple(self):
        self._check(B=4, N=256, d=16, k=10, tile=64)

    def test_padding_tail(self):
        self._check(B=3, N=200, d=8, k=7, tile=64, seed=1)

    def test_single_tile(self):
        self._check(B=2, N=40, d=4, k=5, tile=64, seed=2)

    def test_xla_fallback(self):
        rng = np.random.default_rng(3)
        Q = rng.standard_normal((2, 8)).astype(np.float32)
        V = rng.standard_normal((50, 8)).astype(np.float32)
        vals, idx = score_topk_xla(jnp.asarray(Q), jnp.asarray(V), 5)
        scores = Q @ V.T
        ref = np.sort(scores, axis=1)[:, ::-1][:, :5]
        np.testing.assert_allclose(np.asarray(vals), ref, rtol=1e-5)


class TestSegmentOps:
    def test_segment_sum(self):
        data = jnp.asarray(np.arange(12, dtype=np.float32).reshape(6, 2))
        ids = jnp.asarray([0, 0, 2, 2, 2, 1])
        out = np.asarray(segment_sum(data, ids, 4))
        assert out.shape == (4, 2)
        np.testing.assert_allclose(out[0], [2.0, 4.0])
        np.testing.assert_allclose(out[3], [0.0, 0.0])

    def test_segment_count_and_mean(self):
        ids = jnp.asarray([1, 1, 1, 0])
        assert np.asarray(segment_count(ids, 3)).tolist() == [1, 3, 0]
        data = jnp.asarray([[2.0], [4.0], [6.0], [10.0]])
        m = np.asarray(segment_mean(data, ids, 3))
        np.testing.assert_allclose(m[:, 0], [10.0, 4.0, 0.0])


class TestResidentScorer:
    def test_matches_numpy_recommend(self):
        from predictionio_tpu.models.als import ResidentScorer, recommend

        rng = np.random.default_rng(0)
        U = rng.standard_normal((20, 6)).astype(np.float32)
        V = rng.standard_normal((100, 6)).astype(np.float32)
        sc = ResidentScorer(U, V)
        for user in (0, 7, 19):
            iv, vv = sc.recommend(user, 5)
            ri, rv = recommend(U, V, user, 5)
            np.testing.assert_array_equal(iv, ri)
            np.testing.assert_allclose(vv, rv, rtol=1e-5)

    def test_exclusions(self):
        from predictionio_tpu.models.als import ResidentScorer, recommend

        rng = np.random.default_rng(1)
        U = rng.standard_normal((5, 4)).astype(np.float32)
        V = rng.standard_normal((30, 4)).astype(np.float32)
        sc = ResidentScorer(U, V)
        excl = np.asarray([3, 11, 29], np.int32)
        iv, vv = sc.recommend(2, 6, exclude=excl)
        ri, rv = recommend(U, V, 2, 6, exclude=excl)
        np.testing.assert_array_equal(iv, ri)
        assert not set(iv.tolist()) & set(excl.tolist())

    def test_exclude_edge_cases(self):
        from predictionio_tpu.models.als import ResidentScorer

        rng = np.random.default_rng(2)
        U = rng.standard_normal((4, 4)).astype(np.float32)
        V = rng.standard_normal((20, 4)).astype(np.float32)
        sc = ResidentScorer(U, V)
        ids = np.asarray([0, 1])
        for ex in (None, [], [None, None], [None, np.asarray([1, 2])]):
            out = sc.recommend_batch(ids, 3, exclude=ex)
            assert len(out) == 2 and all(len(iv) == 3 for iv, _ in out)
        # over-fetch larger than the catalog must clamp, not explode
        big = [np.arange(18, dtype=np.int32), np.asarray([], np.int32)]
        out = sc.recommend_batch(ids, 5, exclude=big)
        assert len(out[0][0]) == 2  # 20 items - 18 excluded


class TestCholSolve:
    """Block-recursive batched SPD solve vs dense oracle."""

    def _spd(self, n, k, seed=0, ridge=0.5):
        rng = np.random.default_rng(seed)
        G = rng.standard_normal((n, k, 2 * k)).astype(np.float32)
        A = G @ G.transpose(0, 2, 1) + ridge * np.eye(k, dtype=np.float32)
        b = rng.standard_normal((n, k)).astype(np.float32)
        return A, b

    @pytest.mark.parametrize("k", [1, 3, 8, 10, 16, 64])
    def test_matches_numpy_solve(self, k):
        from predictionio_tpu.ops.cholesky import chol_solve_batched

        A, b = self._spd(64, k, seed=k)
        x = np.asarray(chol_solve_batched(jnp.asarray(A), jnp.asarray(b)))
        x_ref = np.linalg.solve(A, b[..., None])[..., 0]
        np.testing.assert_allclose(x, x_ref, rtol=2e-4, atol=2e-4)

    def test_identity_padding_blocks_are_inert(self):
        # k=10 pads to 16 with an identity block; the answer must not move
        from predictionio_tpu.ops.cholesky import chol_solve_batched

        A, b = self._spd(8, 10, seed=3)
        x = np.asarray(chol_solve_batched(jnp.asarray(A), jnp.asarray(b)))
        assert x.shape == (8, 10)
        np.testing.assert_allclose(
            A @ x[..., None], b[..., None], rtol=1e-3, atol=1e-3)

    def test_ill_scaled_ridge_systems(self):
        # ALS-like: A = Gram + lambda*n_e*I with wildly varying scales
        from predictionio_tpu.ops.cholesky import chol_solve_batched

        rng = np.random.default_rng(9)
        k, n = 8, 32
        scale = 10.0 ** rng.uniform(-2, 4, n).astype(np.float32)
        G = rng.standard_normal((n, k, k)).astype(np.float32)
        A = (G @ G.transpose(0, 2, 1)) * scale[:, None, None]
        A += (0.05 * scale)[:, None, None] * np.eye(k, dtype=np.float32)
        b = rng.standard_normal((n, k)).astype(np.float32)
        x = np.asarray(chol_solve_batched(jnp.asarray(A), jnp.asarray(b)))
        x_ref = np.linalg.solve(A, b[..., None])[..., 0]
        np.testing.assert_allclose(x, x_ref, rtol=5e-3, atol=5e-4)


class TestCholSolvePallas:
    """The VMEM-resident blocked solve kernel, via the Mosaic
    interpreter (CPU CI) — must match numpy and the XLA recursion."""

    def _spd(self, n, k, seed=0, ridge=0.5):
        rng = np.random.default_rng(seed)
        G = rng.standard_normal((n, k, 2 * k)).astype(np.float32)
        A = G @ G.transpose(0, 2, 1) + ridge * np.eye(k, dtype=np.float32)
        b = rng.standard_normal((n, k)).astype(np.float32)
        return A, b

    @pytest.mark.parametrize("k", [8, 16, 64])
    def test_matches_numpy(self, k):
        from predictionio_tpu.ops.cholesky import chol_solve_pallas

        A, b = self._spd(64, k, seed=k)
        x = np.asarray(chol_solve_pallas(jnp.asarray(A), jnp.asarray(b),
                                         interpret=True))
        x_ref = np.linalg.solve(A, b[..., None])[..., 0]
        np.testing.assert_allclose(x, x_ref, rtol=2e-4, atol=2e-4)

    def test_odd_k_and_batch_padding(self):
        # k=10 pads to 16; N=37 pads to the 128-lane tile — padded
        # identity systems must not perturb the real ones
        from predictionio_tpu.ops.cholesky import chol_solve_pallas

        A, b = self._spd(37, 10, seed=3)
        x = np.asarray(chol_solve_pallas(jnp.asarray(A), jnp.asarray(b),
                                         interpret=True))
        assert x.shape == (37, 10)
        np.testing.assert_allclose(
            A @ x[..., None], b[..., None], rtol=1e-3, atol=1e-3)

    def test_matches_xla_recursion(self):
        from predictionio_tpu.ops.cholesky import (_chol_solve,
                                                   chol_solve_pallas)

        A, b = self._spd(130, 64, seed=7)
        xp = np.asarray(chol_solve_pallas(jnp.asarray(A), jnp.asarray(b),
                                          interpret=True))
        xr = np.asarray(_chol_solve(jnp.asarray(A), jnp.asarray(b)))
        np.testing.assert_allclose(xp, xr, rtol=2e-4, atol=2e-4)


class TestTPULowering:
    """Every Pallas kernel must LOWER for the TPU platform (Pallas →
    Mosaic MLIR) — runs on CPU CI via jax.export, catching
    unsupported-op regressions without a chip. Lowering is NOT the
    chip's verdict: Mosaic's codegen happens at XLA compile time, and
    tests/test_chip_compile.py runs that compiler for a described v5e
    (it refused a gather_gram that lowered fine here)."""

    def _lowers(self, fn, *avals):
        import jax

        txt = jax.export.export(jax.jit(fn), platforms=["tpu"])(
            *avals).mlir_module()
        assert "tpu_custom_call" in txt, txt[:300]

    def test_chol_solve_pallas(self):
        import jax
        from predictionio_tpu.ops.cholesky import chol_solve_pallas

        self._lowers(chol_solve_pallas,
                     jax.ShapeDtypeStruct((512, 64, 64), jnp.float32),
                     jax.ShapeDtypeStruct((512, 64), jnp.float32))

    def test_score_topk(self):
        import functools

        import jax
        from predictionio_tpu.ops.topk import score_topk

        self._lowers(functools.partial(score_topk, k=16, tile=512,
                                       n_valid=2000),
                     jax.ShapeDtypeStruct((8, 64), jnp.float32),
                     jax.ShapeDtypeStruct((2048, 64), jnp.float32))

    def test_gather_gram(self):
        import jax
        from predictionio_tpu.ops.gram import gather_gram

        txt = jax.export.export(jax.jit(gather_gram), platforms=["tpu"])(
            jax.ShapeDtypeStruct((26744, 64), jnp.float32),
            jax.ShapeDtypeStruct((300, 512), jnp.int32),
            jax.ShapeDtypeStruct((300, 512), jnp.float32),
            jax.ShapeDtypeStruct((300, 512), jnp.float32),
            jax.ShapeDtypeStruct((300,), jnp.int32)).mlir_module()
        assert "tpu_custom_call" in txt, txt[:300]


def _parent_gather_gram_kernel(idx_hbm, len_ref, idx_ref, wo_ref, wb_ref,
                               F_hbm, A_ref, b_ref, idx_smem, f_tile, accA,
                               accB, sem_idx, sem_row, *, RB, C, T, kp, G):
    """The kernel body as it was before PR 37 (PR 25's): ONE tile
    buffer, a tile's copies started, then retired one wait a copy, then
    multiplied; every program waits for its own index block. Kept here
    as the reference the pipelined kernel must equal bit for bit."""
    i = pl.program_id(0)
    L = f_tile.shape[1]
    cp = pltpu.make_async_copy(
        idx_hbm.at[pl.ds(i * RB, RB), :], idx_smem, sem_idx)
    cp.start()
    cp.wait()
    tile_row = jax.lax.broadcasted_iota(jnp.int32, (T, L), 0)
    lane_slot = jax.lax.broadcasted_iota(jnp.int32, (T, L), 1) // kp
    len_at = (i * RB) % (8 * 128)
    len_line, len_lane = len_at // 128, len_at % 128
    for r in range(RB):
        accA[...] = jnp.zeros((L, L), jnp.float32)
        accB[...] = jnp.zeros((1, L), jnp.float32)
        n = len_ref[len_line, len_lane + r]

        def tile_body(t, _):
            live = jnp.minimum(n - t * T, T)

            def issue(j, _):
                row = idx_smem[r, t * T + j]
                pltpu.make_async_copy(
                    F_hbm.at[pl.ds(row // G, 1), :],
                    f_tile.at[pl.ds(j, 1), :],
                    sem_row).start()
                return 0

            jax.lax.fori_loop(0, live, issue, 0)

            def drain(j, _):
                pltpu.make_async_copy(
                    F_hbm.at[pl.ds(0, 1), :],
                    f_tile.at[pl.ds(0, 1), :],
                    sem_row).wait()
                return 0

            jax.lax.fori_loop(0, live, drain, 0)
            keep = tile_row < live
            if G > 1:
                slot = idx_ref[r, pl.ds(t * T, T)] % G
                keep &= lane_slot == slot[:, None]
            F = jnp.where(keep, f_tile[...], 0.0)
            wo = wo_ref[r, pl.ds(t * T, T)]
            wb = wb_ref[r, pl.ds(t * T, T)]
            accA[...] += jax.lax.dot_general(
                F * wo[:, None], F, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            accB[...] += jnp.sum(F * wb[:, None], axis=0, keepdims=True)
            return 0

        jax.lax.fori_loop(0, (n + T - 1) // T, tile_body, 0)
        A = accA[0:kp, 0:kp]
        b = accB[:, 0:kp]
        for g in range(1, G):
            A = A + accA[g * kp:(g + 1) * kp, g * kp:(g + 1) * kp]
            b = b + accB[:, g * kp:(g + 1) * kp]
        A_ref[r] = A
        b_ref[r] = b[0]


def _parent_gather_gram(F_other, idx, wo, wb, lengths):
    """``gather_gram`` around the parent's kernel body, interpreter."""
    from predictionio_tpu.ops.gram import _line_width, _tile

    (R, C), (N, k) = idx.shape, F_other.shape
    T, (kp, G), RB = _tile(C), _line_width(k), 8
    L = kp * G
    Np, Rp = -(-N // G) * G, -(-R // RB) * RB
    F = jnp.pad(F_other.astype(jnp.float32),
                [(0, Np - N), (0, kp - k)]).reshape(Np // G, L)
    idx, wo, wb = (jnp.pad(a, [(0, Rp - R), (0, 0)]) for a in (idx, wo, wb))
    Rl = -(-R // 1024) * 1024
    lengths = jnp.pad(jnp.clip(lengths.astype(jnp.int32), 0, C),
                      (0, Rl - R)).reshape(Rl // 128, 128)
    row_block = pl.BlockSpec((RB, C), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    A, b = pl.pallas_call(
        functools.partial(_parent_gather_gram_kernel, RB=RB, C=C, T=T,
                          kp=kp, G=G),
        grid=(Rp // RB,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((8, 128), lambda i: (i // (1024 // RB), 0),
                         memory_space=pltpu.SMEM),
            row_block, row_block, row_block,
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec((RB, kp, kp), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((RB, kp), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(jax.ShapeDtypeStruct((Rp, kp, kp), jnp.float32),
                   jax.ShapeDtypeStruct((Rp, kp), jnp.float32)),
        scratch_shapes=[
            pltpu.SMEM((RB, C), jnp.int32),
            pltpu.VMEM((T, L), jnp.float32),
            pltpu.VMEM((L, L), jnp.float32),
            pltpu.VMEM((1, L), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=True,
    )(idx, lengths, idx, wo, wb, F)
    return A[:R, :k, :k], b[:R, :k]


class TestGatherGram:
    """Fused gather→weighted-Gram kernel (ISSUE 17) vs the XLA
    gather+einsum reference, interpret mode — every bucket width the
    ALS ladder produces, plus the padding/degenerate geometries.

    Every case that runs the kernel runs on BOTH routes (PR 41): the
    lines read from a table the dispatch holds in VMEM — what
    ``table_is_resident`` gives these small tables — and the lines
    copied one by one, which a test gets by moving the rule's constant
    under its table (``route``); no argument of the kernel chooses."""

    @pytest.fixture(params=["copied", "resident"])
    def route(self, request, monkeypatch):
        from predictionio_tpu.ops import gram

        if request.param == "copied":
            monkeypatch.setattr(gram, "_RESIDENT_TABLE_BYTES", 0)
        return request.param

    @staticmethod
    def _scratch(*avals):
        """(memory space, shape) of each scratch operand of the
        ``gather_gram`` dispatch traced for these operand shapes."""
        from predictionio_tpu.ops.gram import gather_gram

        # a fresh function: a trace cached under another value of the
        # rule's constant (``route``) must not answer
        jaxpr = jax.make_jaxpr(lambda *a: gather_gram(*a))(*avals)
        (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        n = eqn.params["grid_mapping"].num_scratch_operands
        return [(str(v.aval.memory_space), tuple(v.aval.shape))
                for v in eqn.params["jaxpr"].invars[-n:]]

    @classmethod
    def _route_traced(cls, n_other, k, R=16, C=128):
        """Which route the dispatch for these shapes was built for,
        read off its scratch operands: the table of lines in VMEM
        (resident) or one more DMA semaphore (copied)."""
        from predictionio_tpu.ops.gram import _line_width, _table_lines

        sds = jax.ShapeDtypeStruct
        scratch = cls._scratch(
            sds((n_other, k), jnp.float32), sds((R, C), jnp.int32),
            sds((R, C), jnp.float32), sds((R, C), jnp.float32),
            sds((R,), jnp.int32))
        kp, G = _line_width(k)
        table = ("vmem", (_table_lines(n_other, k), kp * G))
        assert len(scratch) == 6
        if scratch[-1] == table:
            return "resident"
        assert scratch[-1] == ("semaphore_mem", ()) and table not in scratch
        return "copied"

    def _data(self, R, C, k, n_other=999, seed=0, dtype=np.float32):
        rng = np.random.default_rng(seed)
        F = rng.standard_normal((n_other, k)).astype(dtype)
        idx = rng.integers(0, n_other, (R, C)).astype(np.int32)
        wo = rng.uniform(0, 2, (R, C)).astype(np.float32)
        wb = rng.uniform(0, 2, (R, C)).astype(np.float32)
        # sprinkle masked-out columns (weight 0) like real PAD entries
        wo[rng.uniform(size=(R, C)) < 0.2] = 0.0
        wb[wo == 0.0] = 0.0
        return F, idx, wo, wb

    def _ref(self, F, idx, wo, wb):
        G = F[idx].astype(np.float64)  # exact-order-free reference
        A = np.einsum("rc,rck,rcl->rkl", wo.astype(np.float64), G, G)
        b = np.einsum("rc,rck->rk", wb.astype(np.float64), G)
        return A, b

    def _check(self, route, R, C, k, **kw):
        from predictionio_tpu.ops.gram import gather_gram

        F, idx, wo, wb = self._data(R, C, k, **kw)
        assert self._route_traced(F.shape[0], k, R, C) == route
        A, b = gather_gram(jnp.asarray(F), jnp.asarray(idx),
                           jnp.asarray(wo), jnp.asarray(wb),
                           jnp.full((R,), C, jnp.int32), interpret=True)
        An, bn = self._ref(F, idx, wo, wb)
        assert A.shape == (R, k, k) and b.shape == (R, k)
        # f32 accumulation error grows with the C-length reduction;
        # the f64 reference is order-free so scale atol with sqrt(C)
        tol = dict(rtol=1e-4, atol=2e-5 * np.sqrt(C))
        np.testing.assert_allclose(np.asarray(A), An, **tol)
        np.testing.assert_allclose(np.asarray(b), bn, **tol)

    @pytest.mark.parametrize("C", [8, 32, 128, 512, 2048, 8192])
    def test_every_ladder_width(self, route, C):
        # R=16 divides the RB=8 row block exactly — no pad rows
        self._check(route, 16, C, 13)

    @pytest.mark.parametrize("C", [8, 512])
    def test_pad_rows(self, route, C):
        # R=3 forces padding up to the RB=8 row block; the padded
        # rows must not leak into the first R outputs
        self._check(route, 3, C, 13)

    @pytest.mark.parametrize("k", [64, 128])
    def test_rank_fills_a_line(self, route, k):
        """Rank 64 is two rows to a line (G = 2, the benchmark's);
        rank 128 one (G = 1): no slot mask, no diagonal blocks to
        fold, a line IS the row."""
        from predictionio_tpu.ops.gram import _line_width

        assert _line_width(k) == (k, 128 // k)
        self._check(route, 16, 256, k, n_other=301)

    def test_bf16_factors(self, route):
        from predictionio_tpu.ops.gram import gather_gram

        F, idx, wo, wb = self._data(16, 32, 8, dtype=np.float32)
        every = jnp.full((16,), 32, jnp.int32)
        A32, b32 = gather_gram(jnp.asarray(F), jnp.asarray(idx),
                               jnp.asarray(wo), jnp.asarray(wb), every,
                               interpret=True)
        A16, b16 = gather_gram(jnp.asarray(F, jnp.bfloat16),
                               jnp.asarray(idx), jnp.asarray(wo),
                               jnp.asarray(wb), every, interpret=True)
        assert A16.dtype == jnp.float32  # accumulation stays f32
        # bf16 carries an 8-bit mantissa: products of two quantized
        # values drift ~1%, so judge by absolute error at this scale
        np.testing.assert_allclose(np.asarray(A16), np.asarray(A32),
                                   rtol=5e-2, atol=1e-1)
        np.testing.assert_allclose(np.asarray(b16), np.asarray(b32),
                                   rtol=5e-2, atol=1e-1)

    def _ragged(self, C, lengths, k=13, seed=0):
        """Rows whose slots past ``lengths`` carry zero weight and
        point at factor row 0, which is poisoned with inf: a kernel
        that fetched one of them would return NaN (0 × inf)."""
        F, idx, wo, wb = self._data(len(lengths), C, k, seed=seed)
        F[0] = np.inf
        idx = np.maximum(idx, 1)
        pad = np.arange(C)[None, :] >= np.asarray(lengths)[:, None]
        idx[pad] = 0
        wo[pad] = 0.0
        wb[pad] = 0.0
        return F, idx, wo, wb

    def _ref_real(self, F, idx, wo, wb):
        # the reference sums the real slots only (F[0] is never one)
        F = F.copy()
        F[0] = 0.0
        return self._ref(F, idx, wo, wb)

    @pytest.mark.parametrize("C", [128, 512, 2048, 8192])
    def test_ragged_lengths(self, route, C):
        """Every row length around the tile edges (T = min(C, 256))
        and the row's ends, against the float64 reference."""
        from predictionio_tpu.ops.gram import gather_gram

        T = min(C, 256)
        lengths = sorted({0, 1, T - 1, T, min(T + 1, C), C - 1, C})
        F, idx, wo, wb = self._ragged(C, lengths)
        A, b = gather_gram(jnp.asarray(F), jnp.asarray(idx),
                           jnp.asarray(wo), jnp.asarray(wb),
                           jnp.asarray(lengths, jnp.int32), interpret=True)
        An, bn = self._ref_real(F, idx, wo, wb)
        tol = dict(rtol=1e-4, atol=2e-5 * np.sqrt(C))
        np.testing.assert_allclose(np.asarray(A), An, **tol)
        np.testing.assert_allclose(np.asarray(b), bn, **tol)
        # the length-0 row copied nothing and is exactly zero
        assert not np.asarray(A[0]).any() and not np.asarray(b[0]).any()

    def test_stale_tile_rows_are_masked(self, route):
        """Row 0 fills the line tile with inf; row 1 of the same block
        fetches 3 lines over it and must not see what row 0 left."""
        from predictionio_tpu.ops.gram import gather_gram

        C = 128
        F, idx, wo, wb = self._ragged(C, [C, 3])
        idx[0] = 0      # row 0 really gathers the inf row, every slot
        wo[0] = wb[0] = 1.0
        A, b = gather_gram(jnp.asarray(F), jnp.asarray(idx),
                           jnp.asarray(wo), jnp.asarray(wb),
                           jnp.asarray([C, 3], jnp.int32), interpret=True)
        assert not np.isfinite(np.asarray(A[0])).any()
        An, bn = self._ref_real(F, idx[1:], wo[1:], wb[1:])
        np.testing.assert_allclose(np.asarray(A[1]), An[0], rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(b[1]), bn[0], rtol=1e-4,
                                   atol=1e-4)

    @staticmethod
    def _edge_lengths(C):
        """Row lengths around every edge of the pipeline, 16 rows = two
        programs of 8: nothing, one copy, the issue loop's burst (U),
        a power of two of the wait ladder, the tile (T), the row."""
        from predictionio_tpu.ops.gram import _ISSUE_UNROLL as U, _tile

        T = _tile(C)
        return [min(n, C) for n in (
            C, 0, 1, U - 1, U, U + 1, 63, 64, 65, T - 1, T, T + 1,
            C - 1, 2 * T - 1, 3, C)]

    @staticmethod
    def _pipeline_case(name):
        """(C, lengths, rows whose every slot gathers the inf row)."""
        lengths_of = TestGatherGram._edge_lengths
        if name.startswith("edges-"):
            C = int(name.split("-")[1])
            return C, lengths_of(C), ()
        if name.startswith("inf-"):
            # a row's tiles land in the two tile buffers in turn. Rows
            # that gather the inf row leave inf behind: at C = 512 in
            # both buffers (two tiles a row), at C = 128 in the one
            # buffer a one-tile row uses. Every short row after them
            # lands on a buffer whose rows past its own still hold it;
            # the last row's second tile meets what row 4's left in
            # the OTHER buffer
            C = int(name.split("-")[1])
            one_tile = C == 128
            return (C, [C, C if one_tile else 3, 3, 5, C, 7, 0, 2 * C // 3],
                    (0, 1, 4) if one_tile else (0, 4))
        if name == "empty-between-full":
            return 512, [512, 0, 512, 0, 0, 300, 0, 512], ()
        if name == "ragged-row-count":
            # R = 11: the last program holds three rows and five of
            # padding; the index block of program 1 was fetched ahead
            return 512, lengths_of(512)[:11], ()
        raise AssertionError(name)

    @pytest.mark.parametrize("case", [
        "edges-128", "edges-512", "edges-2048", "edges-8192",
        "inf-128", "inf-512", "empty-between-full", "ragged-row-count"])
    def test_pipeline_equals_the_parents_kernel_bit_for_bit(self, route,
                                                            case):
        """The unrolled issue loop, group waits, two tile buffers in
        turn and the index block fetched a program ahead change HOW a
        line is fetched and WHERE a tile lands — and so does a line
        read from the resident table —, not what is summed in what
        order: on either route A and b carry the bits of the parent's
        kernel body (above), NaNs included, so the two routes equal
        each other bit for bit."""
        from predictionio_tpu.ops.gram import gather_gram

        C, lengths, inf_rows = self._pipeline_case(case)
        F, idx, wo, wb = self._ragged(C, lengths, seed=len(case))
        assert self._route_traced(F.shape[0], 13, len(lengths), C) == route
        for r in inf_rows:   # really gather the inf row, every slot
            idx[r, :lengths[r]] = 0
        args = (jnp.asarray(F), jnp.asarray(idx), jnp.asarray(wo),
                jnp.asarray(wb), jnp.asarray(lengths, jnp.int32))
        A, b = (np.asarray(a) for a in gather_gram(*args, interpret=True))
        Ap, bp = (np.asarray(a) for a in _parent_gather_gram(*args))
        assert A.shape == Ap.shape and b.shape == bp.shape
        assert np.array_equal(A.view(np.uint32), Ap.view(np.uint32))
        assert np.array_equal(b.view(np.uint32), bp.view(np.uint32))
        clean = [r for r in range(len(lengths)) if r not in inf_rows]
        assert np.isfinite(A[clean]).all() and np.isfinite(b[clean]).all()
        for r in inf_rows:
            assert not np.isfinite(A[r]).any()
        for r in clean:
            if lengths[r] == 0:   # copied nothing: exactly zero
                assert not A[r].any() and not b[r].any()

    def test_pipeline_holds_when_copies_land_as_late_as_they_may(self,
                                                                 route):
        """``interpret=True`` lands a copy the moment it is started, so
        it cannot see a tile multiplied before its copies were waited
        for, or a buffer refilled while it is read. The TPU interpreter
        can: it keeps real semaphores, runs a DMA only when a wait
        needs it (``on_wait``) and checks every access for races — the
        group waits' amounts must add up, or it hangs or misreads. On
        the resident route the one copy that fills the table must have
        landed before the first line is read."""
        from jax._src.pallas.mosaic.interpret import (
            interpret_pallas_call as tpu_interpreter)
        from predictionio_tpu.ops.gram import gather_gram

        C = 512   # two programs: the second's index block comes early
        lengths = [C, 0, 1, 9, 257, 3, 0, 300, 64, 5]
        F, idx, wo, wb = self._ragged(C, lengths, seed=2)
        args = (jnp.asarray(F), jnp.asarray(idx), jnp.asarray(wo),
                jnp.asarray(wb), jnp.asarray(lengths, jnp.int32))
        A, b = gather_gram(*args, interpret=True)
        Al, bl = gather_gram(*args, interpret=pltpu.InterpretParams(
            detect_races=True, dma_execution_mode="on_wait"))
        assert not tpu_interpreter.races.races_found
        np.testing.assert_array_equal(np.asarray(Al), np.asarray(A))
        np.testing.assert_array_equal(np.asarray(bl), np.asarray(b))

    def test_dma_waits_counts_the_ladders_waits(self):
        """``dma_waits`` (the host's count behind ``kernel_dma_waits``)
        against a walk of the kernel's own loop: per tile one wait for
        each ladder size that is a set bit of the tile's copies."""
        from predictionio_tpu.ops.gram import _tile, _wait_sizes, dma_waits

        for C in (128, 512, 8192, 96):
            T = _tile(C)
            assert sum(_wait_sizes(T)) >= T and _wait_sizes(T)[-1] == 1
            rng = np.random.default_rng(C)
            lengths = np.concatenate([[0, 1, T - 1, T, C],
                                      rng.integers(0, C + 1, 50)])
            walked = 0
            for n in lengths:
                for t in range(-(-int(n) // T)):
                    live, left = min(int(n) - t * T, T), 0
                    for g in _wait_sizes(T):
                        if live & g:
                            walked, left = walked + 1, left + g
                    assert left == live   # the waits retire every copy
            assert dma_waits(lengths, C) == walked
        # a full tile is ONE wait; a segmented entity is one long row
        assert dma_waits([256, 512], 512) == 3
        assert dma_waits([8192 * 3 + 5], 8192) == 96 + 2

    def test_table_one_line_over_the_rule_is_copied(self):
        """The rule is the table's bytes as the kernel lays it out
        against ONE constant: the largest table that fits is read from
        VMEM, one more line of factors and the dispatch copies its
        lines — seen in the scratch operands of the traced dispatch,
        at the benchmark's rank (two rows a line) and at rank 128."""
        from predictionio_tpu.ops import gram

        for k, G in ((64, 2), (128, 1)):
            most = gram._RESIDENT_TABLE_BYTES // 512 * G   # rows
            assert gram.table_is_resident(most, k)
            assert not gram.table_is_resident(most + 1, k)
            assert (gram.table_bytes(most + 1, k)
                    - gram.table_bytes(most, k)) == 512
            assert self._route_traced(most, k) == "resident"
            assert self._route_traced(most + 1, k) == "copied"
        # the benchmark's four tables (PERF.md §6 PR 41)
        for n in (26_744, 138_493, 270_000, 294_015):
            assert gram.table_is_resident(n, 64)

    def test_empty_rows(self, route):
        from predictionio_tpu.ops.gram import gather_gram

        F = jnp.zeros((10, 5), jnp.float32)
        A, b = gather_gram(F, jnp.zeros((0, 8), jnp.int32),
                           jnp.zeros((0, 8), jnp.float32),
                           jnp.zeros((0, 8), jnp.float32),
                           jnp.zeros((0,), jnp.int32), interpret=True)
        assert A.shape == (0, 5, 5) and b.shape == (0, 5)

    def test_xla_reference_matches_numpy(self):
        from predictionio_tpu.ops.gram import gather_gram_xla

        F, idx, wo, wb = self._data(7, 32, 5, seed=3)
        A, b = gather_gram_xla(jnp.asarray(F), jnp.asarray(idx),
                               jnp.asarray(wo), jnp.asarray(wb))
        An, bn = self._ref(F, idx, wo, wb)
        np.testing.assert_allclose(np.asarray(A), An, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(b), bn, rtol=1e-5, atol=1e-5)

    def test_resolve_gram_mode_env(self, monkeypatch):
        from predictionio_tpu.ops import gram as g

        monkeypatch.setenv("PIO_PALLAS_GRAM", "0")
        assert g.resolve_gram_mode("tpu") == "off"
        monkeypatch.setenv("PIO_PALLAS_GRAM", "interpret")
        assert g.resolve_gram_mode("cpu") == "interpret"
        assert g.resolve_gram_mode("tpu") == "interpret"
        # auto (the default) is the kernel on a TPU and never off it
        for auto in ("auto", None):
            if auto is None:
                monkeypatch.delenv("PIO_PALLAS_GRAM")
            else:
                monkeypatch.setenv("PIO_PALLAS_GRAM", auto)
            assert g.resolve_gram_mode("tpu") == "pallas"
            assert g.resolve_gram_mode("cpu") == "off"
