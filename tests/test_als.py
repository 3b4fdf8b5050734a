"""ALS numerics: convergence, implicit feedback, and single↔sharded
parity on the 8-device CPU mesh (ICI-collective semantics in CI,
SURVEY.md §4)."""

import numpy as np
import pytest

from predictionio_tpu.models.als import (
    ALSParams,
    RatingsCOO,
    als_train,
    predict_ratings,
    recommend,
    similar_items,
)


@pytest.fixture(scope="module")
def synthetic():
    rng = np.random.default_rng(0)
    n_u, n_i, k_true = 100, 70, 5
    U = rng.normal(size=(n_u, k_true))
    V = rng.normal(size=(n_i, k_true))
    R = U @ V.T
    mask = rng.random((n_u, n_i)) < 0.3
    uu, ii = np.nonzero(mask)
    coo = RatingsCOO(uu.astype(np.int32), ii.astype(np.int32),
                     R[uu, ii].astype(np.float32), n_u, n_i)
    return coo, R, mask


class TestSingleDevice:
    def test_convergence(self, synthetic):
        coo, R, mask = synthetic
        U, V = als_train(coo, ALSParams(rank=8, iterations=12, reg=0.05))
        pred = predict_ratings(U, V, coo.user_idx, coo.item_idx)
        rmse = float(np.sqrt(np.mean((pred - coo.rating) ** 2)))
        assert rmse < 0.3, rmse
        # held-out generalization beats predicting the mean
        huu, hii = np.nonzero(~mask)
        hrmse = float(np.sqrt(np.mean(
            (predict_ratings(U, V, huu, hii) - R[huu, hii]) ** 2)))
        assert hrmse < R.std()

    def test_implicit_finite_and_ranks_positives_high(self, synthetic):
        coo, R, _ = synthetic
        pos = RatingsCOO(coo.user_idx, coo.item_idx,
                         np.abs(coo.rating), coo.n_users, coo.n_items)
        U, V = als_train(pos, ALSParams(rank=8, iterations=8, reg=0.05,
                                        implicit=True, alpha=2.0))
        assert np.isfinite(U).all() and np.isfinite(V).all()
        scores = U @ V.T
        observed = scores[coo.user_idx, coo.item_idx].mean()
        assert observed > scores.mean()  # observed pairs score higher

    def test_zero_degree_entities_stay_finite(self):
        # user 3 and item 4 have no ratings at all
        coo = RatingsCOO(np.array([0, 1, 2], np.int32),
                         np.array([0, 1, 2], np.int32),
                         np.array([1.0, 2.0, 3.0], np.float32), 5, 6)
        U, V = als_train(coo, ALSParams(rank=4, iterations=3, reg=0.1))
        assert np.isfinite(U).all() and np.isfinite(V).all()
        assert np.allclose(U[3], 0) and np.allclose(V[4], 0)

    def test_recommend_and_similar(self, synthetic):
        coo, _, _ = synthetic
        U, V = als_train(coo, ALSParams(rank=8, iterations=6, reg=0.05))
        top, scores = recommend(U, V, 0, 7)
        assert len(top) == 7 and list(scores) == sorted(scores, reverse=True)
        top2, _ = recommend(U, V, 0, 7, exclude=np.array([top[0]]))
        assert top[0] not in top2
        sim, sscores = similar_items(V, np.array([3]), 5)
        assert 3 not in sim and len(sim) == 5


def _ref_half(idx_self, idx_other, vals, n_self, F, p):
    """Dense per-entity normal-equation solve (the math the bucketed
    MXU program must reproduce), in float64."""
    k = p.rank
    F = F.astype(np.float64)
    X = np.zeros((n_self, k), np.float64)
    for e in range(n_self):
        sel = idx_self == e
        n_e = int(sel.sum())
        if n_e == 0:
            continue
        Fe = F[idx_other[sel]]
        lam = p.reg * n_e if p.weighted_reg else p.reg
        A = Fe.T @ Fe + max(lam, 1e-8) * np.eye(k)
        X[e] = np.linalg.solve(A, Fe.T @ vals[sel].astype(np.float64))
    return X


def _ref_als(coo, p):
    from predictionio_tpu.models.als import init_factors

    V = init_factors(coo.n_items, p.rank, p.seed).astype(np.float64)
    U = np.zeros((coo.n_users, p.rank), np.float64)
    for _ in range(p.iterations):
        U = _ref_half(coo.user_idx, coo.item_idx, coo.rating,
                      coo.n_users, V, p)
        V = _ref_half(coo.item_idx, coo.user_idx, coo.rating,
                      coo.n_items, U, p)
    return U, V


def _layout_cases():
    """name → (knobs to monkeypatch on models/als.py, COO builder
    (given the monkeypatch), devices of the sharded layout or 0, what
    the new builder must report about the paths it took where numpy's
    radix sort orders the interactions — under the native counting pass
    ``radix_passes_*`` read 0)."""

    def coo_of(uu, ii, rr, n_u, n_i, dtype=np.int32):
        return RatingsCOO(np.asarray(uu).astype(dtype),
                          np.asarray(ii).astype(dtype),
                          np.asarray(rr, np.float32), n_u, n_i)

    def power_law(seed=41, n_u=2000, n_i=400, nnz=60000, dedupe=True,
                  dtype=np.int32):
        rng = np.random.default_rng(seed)
        uu = rng.zipf(1.3, nnz) % n_u
        ii = rng.zipf(1.2, nnz) % n_i
        if dedupe:
            keep = np.sort(np.unique(uu * n_i + ii, return_index=True)[1])
            uu, ii = uu[keep], ii[keep]
        return coo_of(uu, ii, rng.uniform(1, 5, len(uu)), n_u, n_i, dtype)

    def many_entities():
        # every one of 70,000 users rated: permuted positions pass
        # 65,536, so the order needs its second 16-bit digit
        rng = np.random.default_rng(42)
        n_u, n_i = 70_000, 300
        uu = np.concatenate([rng.permutation(n_u),
                             rng.zipf(1.5, 20_000) % n_u])
        ii = rng.integers(0, n_i, len(uu))
        return coo_of(uu, ii, rng.uniform(1, 5, len(uu)), n_u, n_i)

    def short_last_row():
        # counts 8·r + {1, 7, 0}: an entity's last segment row is
        # short, or (a multiple of the width) full
        deg = [8 * 5 + 1, 8 * 3 + 7, 8 * 4, 8 + 3, 7, 2, 1, 0, 3]
        rng = np.random.default_rng(43)
        uu = np.repeat(np.arange(len(deg)), deg)
        ii = np.concatenate([rng.choice(50, d, replace=False) for d in deg])
        mix = rng.permutation(len(uu))
        return coo_of(uu[mix], ii[mix], rng.uniform(1, 5, len(uu)),
                      len(deg), 50)

    def dense(repeat):
        rng = np.random.default_rng(44)
        n_u, n_i = 40, 25
        deg = np.minimum(rng.zipf(1.3, n_u) + 1, n_i)
        deg[:4] = (25, 20, 11, 6)
        uu = np.repeat(np.arange(n_u), deg)
        ii = np.concatenate([rng.choice(n_i, d, replace=False)
                             for d in deg])
        rr = rng.uniform(1, 5, len(uu))
        rr[3] = -0.0            # a sum gives +0.0: so must the assignment
        if repeat:              # user 1 rated item ii[30] twice
            uu, ii, rr = (np.append(uu, 1), np.append(ii, ii[30]),
                          np.append(rr, 2.5))
        mix = rng.permutation(len(uu))
        return coo_of(uu[mix], ii[mix], rr[mix], n_u, n_i)

    def no_interactions():
        # ids 50..119 and items 30..39 never appear
        rng = np.random.default_rng(45)
        return coo_of(rng.integers(0, 50, 600), rng.integers(0, 30, 600),
                      rng.uniform(1, 5, 600), 120, 40)

    def skewed_devices():
        # user 0 owns the segmented range: seven of eight devices have
        # nothing in the forced seg bucket
        rng = np.random.default_rng(11)
        n_u, n_i = 33, 17
        uu = np.concatenate([np.zeros(16, np.int64),
                             rng.integers(1, n_u, 120)])
        ii = np.concatenate([np.arange(16) % n_i,
                             rng.integers(0, n_i, 120)])
        keep = np.sort(np.unique(uu * n_i + ii, return_index=True)[1])
        return coo_of(uu[keep], ii[keep], rng.uniform(1, 5, len(keep)),
                      n_u, n_i)

    small = dict(_LADDER=(2, 8), _C_MAX=8)
    head = dict(_DENSE_MIN_COUNT=6)
    empty = np.zeros(0, np.int32)
    return {
        "default_ladder_power_law": (
            {}, lambda mp: power_law(), 0, dict(radix_passes_u=1, dense_fill_u="assign",
                                   dense_fill_i="assign")),
        "two_radix_passes": (
            {}, lambda mp: many_entities(), 0, dict(radix_passes_u=2, radix_passes_i=1)),
        "segmented_short_last_row": (
            small, lambda mp: short_last_row(), 0, dict(dense_fill_u="none")),
        "dense_head_distinct_pairs": (
            head, lambda mp: dense(False), 0, dict(dense_fill_u="assign")),
        "dense_head_repeated_pair": (
            head, lambda mp: dense(True), 0, dict(dense_fill_u="bincount")),
        "dense_head_power_law_repeats": (
            {}, lambda mp: power_law(dedupe=False), 0,
            dict(dense_fill_u="bincount")),
        "entities_without_interactions": (
            {}, lambda mp: no_interactions(), 0, {}),
        "empty_coo": (
            {}, lambda mp: coo_of(empty, empty, empty, 5, 6), 0,
            dict(dense_fill_u="none", dense_fill_i="none")),
        "int64_indices": (
            small, lambda mp: power_law(46, 60, 40, 900, dtype=np.int64),
            0, {}),
        "repeats_past_65536_entities": (
            {}, lambda mp: power_law(48, 70_000, 300, 120_000,
                                     dedupe=False), 0,
            dict(radix_passes_u=2, radix_passes_i=1)),
        "dense_seg_regular": (
            {}, lambda mp: _wide_layout(mp), 0,
            dict(radix_passes_u=1, dense_fill_u="assign")),
        "sharded_skewed_devices": (
            small, lambda mp: skewed_devices(), 8, {}),
        "sharded_dense_seg_regular": (
            {}, lambda mp: _wide_layout(mp), 4, {}),
        "sharded_uneven_empty_device": (
            head, lambda mp: power_law(47, 9, 21, 300, dedupe=False), 8,
            {}),
    }


class TestBucketedLayout:
    def test_segmented_heavy_bucket_matches_dense_reference(self, monkeypatch):
        """Shrink the width ladder so the heavy (segmented, one-hot
        aggregated) bucket path runs on a small dataset, and check the
        whole program against a dense float64 reference."""
        import predictionio_tpu.models.als as als_mod

        monkeypatch.setattr(als_mod, "_LADDER", (2, 8))
        monkeypatch.setattr(als_mod, "_C_MAX", 8)
        rng = np.random.default_rng(5)
        n_u, n_i, nnz = 40, 25, 600
        uu = (rng.zipf(1.3, nnz) % n_u).astype(np.int32)
        ii = (rng.zipf(1.3, nnz) % n_i).astype(np.int32)
        # dedupe (user, item) pairs so counts are exact
        keep = np.unique(uu.astype(np.int64) * n_i + ii, return_index=True)[1]
        uu, ii = uu[keep], ii[keep]
        rr = rng.uniform(1, 5, len(uu)).astype(np.float32)
        coo = RatingsCOO(uu, ii, rr, n_u, n_i)

        prep = als_mod.als_prepare(coo)
        assert any(b.seg is not None for b in prep.u_side.buckets), \
            "test dataset must exercise the segmented bucket"
        assert any(b.seg is None for b in prep.u_side.buckets)

        p = ALSParams(rank=4, iterations=2, reg=0.1, seed=2)
        U, V = als_mod.als_train_prepared(prep, p)
        Ur, Vr = _ref_als(coo, p)
        np.testing.assert_allclose(U, Ur, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(V, Vr, rtol=2e-3, atol=2e-3)

    def test_dense_head_matches_dense_reference(self, monkeypatch):
        """Lower the dense-head threshold so the heaviest entities run
        through the dense-weight GEMM path on a small dataset, and
        check the whole program against the float64 reference."""
        import predictionio_tpu.models.als as als_mod

        monkeypatch.setattr(als_mod, "_DENSE_MIN_COUNT", 6)
        rng = np.random.default_rng(9)
        n_u, n_i, nnz = 40, 25, 500
        uu = (rng.zipf(1.3, nnz) % n_u).astype(np.int32)
        ii = rng.integers(0, n_i, nnz).astype(np.int32)
        rr = rng.uniform(1, 5, nnz).astype(np.float32)  # duplicates kept
        coo = RatingsCOO(uu, ii, rr, n_u, n_i)

        prep = als_mod.als_prepare(coo)
        assert prep.u_side.dense is not None and prep.u_side.dense.nb > 0
        assert prep.u_side.buckets, "light entities must stay bucketed"

        p = ALSParams(rank=4, iterations=2, reg=0.1, seed=2)
        U, V = als_mod.als_train_prepared(prep, p)
        Ur, Vr = _ref_als(coo, p)
        np.testing.assert_allclose(U, Ur, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(V, Vr, rtol=2e-3, atol=2e-3)

    def test_dense_head_byte_cap_spills_to_buckets(self, monkeypatch):
        """``_DENSE_HEAD_MB`` caps the head's weight-row bytes; the
        spilled entities run through the bucket path with identical
        results (ADVICE r3: unbounded head risks host/device OOM)."""
        import predictionio_tpu.models.als as als_mod

        rng = np.random.default_rng(9)
        n_u, n_i, nnz = 40, 25, 500
        uu = (rng.zipf(1.3, nnz) % n_u).astype(np.int32)
        ii = rng.integers(0, n_i, nnz).astype(np.int32)
        rr = rng.uniform(1, 5, nnz).astype(np.float32)
        coo = RatingsCOO(uu, ii, rr, n_u, n_i)
        p = ALSParams(rank=4, iterations=2, reg=0.1, seed=2)

        monkeypatch.setattr(als_mod, "_DENSE_MIN_COUNT", 6)
        prep_full = als_mod.als_prepare(coo)
        assert prep_full.u_side.dense is not None
        full_nb = prep_full.u_side.dense.nb
        assert full_nb > 1
        U_full, V_full = als_mod.als_train_prepared(prep_full, p)

        # MB granularity can't isolate single rows on a tiny catalog, so
        # cap to zero: every head entity must spill to the buckets
        monkeypatch.setattr(als_mod, "_DENSE_HEAD_MB", 0)
        prep_capped = als_mod.als_prepare(coo)
        side = prep_capped.u_side
        assert side.dense is None or side.dense.nb == 0
        U_cap, V_cap = als_mod.als_train_prepared(prep_capped, p)
        np.testing.assert_allclose(U_cap, U_full, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(V_cap, V_full, rtol=1e-4, atol=1e-5)

    def test_dense_head_equivalent_to_bucketed_implicit(self, monkeypatch):
        """Implicit feedback: the dense-head program must produce the
        same factors as the pure bucketed layout on identical data."""
        import predictionio_tpu.models.als as als_mod

        rng = np.random.default_rng(10)
        n_u, n_i, nnz = 30, 20, 400
        uu = (rng.zipf(1.3, nnz) % n_u).astype(np.int32)
        ii = rng.integers(0, n_i, nnz).astype(np.int32)
        rr = rng.uniform(0.5, 3, nnz).astype(np.float32)
        coo = RatingsCOO(uu, ii, rr, n_u, n_i)
        p = ALSParams(rank=4, iterations=3, reg=0.1, implicit=True,
                      alpha=2.0, seed=2)

        U0, V0 = als_mod.als_train_prepared(als_mod.als_prepare(coo), p)
        monkeypatch.setattr(als_mod, "_DENSE_MIN_COUNT", 6)
        prep = als_mod.als_prepare(coo)
        assert prep.u_side.dense is not None and prep.u_side.dense.nb > 0
        U1, V1 = als_mod.als_train_prepared(prep, p)
        np.testing.assert_allclose(U0, U1, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(V0, V1, rtol=1e-4, atol=1e-5)

    def test_bf16_gather_mode_close_to_f32(self):
        """Opt-in bf16 gathers: same data, loose agreement with the
        f32 path and equivalent reconstruction quality."""
        rng = np.random.default_rng(13)
        n_u, n_i, k_true = 80, 60, 4
        Ut = rng.normal(size=(n_u, k_true))
        Vt = rng.normal(size=(n_i, k_true))
        mask = rng.random((n_u, n_i)) < 0.3
        uu, ii = np.nonzero(mask)
        coo = RatingsCOO(uu.astype(np.int32), ii.astype(np.int32),
                         (Ut @ Vt.T)[uu, ii].astype(np.float32), n_u, n_i)
        p32 = ALSParams(rank=6, iterations=6, reg=0.05, seed=2)
        p16 = ALSParams(rank=6, iterations=6, reg=0.05, seed=2,
                        bf16_gather=True)
        U32, V32 = als_train(coo, p32)
        U16, V16 = als_train(coo, p16)
        r32 = predict_ratings(U32, V32, coo.user_idx, coo.item_idx)
        r16 = predict_ratings(U16, V16, coo.user_idx, coo.item_idx)
        rmse32 = float(np.sqrt(np.mean((r32 - coo.rating) ** 2)))
        rmse16 = float(np.sqrt(np.mean((r16 - coo.rating) ** 2)))
        assert rmse16 < rmse32 + 0.05, (rmse16, rmse32)
        # factors agree to bf16-accumulation noise
        np.testing.assert_allclose(U16, U32, rtol=0.15, atol=0.1)

    @staticmethod
    def _both_finishes(coo, p, gram, monkeypatch):
        """Train with the one solve buffer, then with a buffer nothing
        fits (every part solved where it is built), same Gram mode."""
        import predictionio_tpu.models.als as als_mod

        monkeypatch.setenv("PIO_PALLAS_GRAM",
                           {"off": "0", "interpret": "interpret"}[gram])
        als_mod._compiled_bucketed.cache_clear()
        U_m, V_m = als_mod.als_train(coo, p)
        monkeypatch.setattr(als_mod, "_SOLVE_BUF_MB", 0)
        als_mod._compiled_bucketed.cache_clear()
        try:
            U_f, V_f = als_mod.als_train(coo, p)
        finally:
            als_mod._compiled_bucketed.cache_clear()
        return (U_m, V_m), (U_f, V_f)

    @pytest.mark.parametrize("implicit", [False, True],
                             ids=["explicit", "implicit"])
    @pytest.mark.parametrize("gram", ["off", "interpret"])
    def test_in_body_solve_fallback_matches_materialized(
            self, monkeypatch, gram, implicit):
        """The huge-catalog fallback (solve inside each bucket body,
        taken when the solve buffer would exceed ``_SOLVE_BUF_MB``)
        must produce the same factors as the materialized path — with
        XLA's Gram and with the fused kernel (a width-128 bucket on the
        user side), explicit and implicit: in-body × kernel × implicit
        is the path of the Last.fm configuration."""
        import predictionio_tpu.models.als as als_mod

        rng = np.random.default_rng(7)
        n_u, n_i = 60, 150
        deg = np.minimum(rng.zipf(1.4, n_u) + 2, 30)
        deg[:2] = (140, 120)                   # the dense head
        deg[2:8] = (90, 70, 60, 50, 40, 33)    # a width-128 bucket
        uu = np.repeat(np.arange(n_u), deg).astype(np.int32)
        ii = np.concatenate([rng.choice(n_i, d, replace=False)
                             for d in deg]).astype(np.int32)
        rr = rng.uniform(1, 5, len(uu)).astype(np.float32)
        coo = RatingsCOO(uu, ii, rr, n_u, n_i)
        p = ALSParams(rank=4, iterations=3, reg=0.1, seed=2,
                      implicit=implicit, alpha=2.0)
        # include a dense head so the fallback's dense branch is covered
        monkeypatch.setattr(als_mod, "_DENSE_MIN_COUNT", 100)
        prep = als_mod.als_prepare(coo)
        assert prep.u_side.dense is not None and prep.u_side.dense.nb == 2
        assert {b.C for b in prep.u_side.buckets} >= {8, 128}
        (U_m, V_m), (U_f, V_f) = self._both_finishes(coo, p, gram,
                                                     monkeypatch)
        np.testing.assert_allclose(U_f, U_m, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(V_f, V_m, rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("gram", ["off", "interpret"])
    def test_in_body_solve_fallback_seg_and_dense(self, monkeypatch, gram):
        """The same with every kind of part in one side: a dense head,
        a segmented bucket the kernel takes, and regular buckets on
        both sides of the kernel's width rule."""
        import predictionio_tpu.models.als as als_mod

        coo = _wide_layout(monkeypatch)
        prep = als_mod.als_prepare(coo)
        assert prep.u_side.dense is not None
        assert any(b.seg is not None for b in prep.u_side.buckets)
        p = ALSParams(rank=4, iterations=2, reg=0.1, seed=2)
        (U_m, V_m), (U_f, V_f) = self._both_finishes(coo, p, gram,
                                                     monkeypatch)
        np.testing.assert_allclose(U_f, U_m, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(V_f, V_m, rtol=1e-4, atol=1e-5)
        Ur, Vr = _ref_als(coo, p)
        np.testing.assert_allclose(U_f, Ur, rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("gram", ["off", "interpret"])
    def test_slab_size_parity(self, monkeypatch, gram):
        """The slab size (``_SLAB_ELEMS``, 2^20 after the r5 v5e A/B)
        only re-batches rows into scan steps — or, through the kernel,
        into the groups the segmented bucket's rows are aggregated by;
        training results must be invariant to it. Tiny slabs force
        multi-slab buckets on a small dataset, regular AND segmented,
        of widths on both sides of the kernel's rule."""
        import predictionio_tpu.models.als as als_mod

        monkeypatch.setenv("PIO_PALLAS_GRAM",
                           {"off": "0", "interpret": "interpret"}[gram])
        coo = _wide_layout(monkeypatch)
        p = ALSParams(rank=4, iterations=2, reg=0.1, seed=2)

        results = []
        for slab_elems in (256, 1024, 1 << 20):
            monkeypatch.setattr(als_mod, "_SLAB_ELEMS", slab_elems)
            prep = als_mod.als_prepare(coo)
            if slab_elems == 256:  # smallest: must actually multi-slab
                multi = [b for b in prep.u_side.buckets if b.n_slabs > 1]
                assert any(b.seg is not None for b in multi)
                assert any(b.seg is None for b in multi)
            results.append(als_mod.als_train_prepared(prep, p))
        als_mod._compiled_bucketed.cache_clear()
        # slab grouping changes f32 accumulation order in the seg
        # aggregation → tiny drift; a layout bug would be order-1 off
        (U0, V0), *rest = results
        for U, V in rest:
            np.testing.assert_allclose(U, U0, rtol=5e-4, atol=1e-5)
            np.testing.assert_allclose(V, V0, rtol=5e-4, atol=1e-5)

    @pytest.mark.parametrize("gram_mode", ["pallas", "off", "interpret"])
    def test_solve_is_the_kernel_exactly_under_pallas(self, monkeypatch,
                                                      gram_mode):
        """The solve follows the Gram mode and nothing else: the VMEM
        Cholesky kernel when the Gram is the compiled fused kernel, the
        XLA recursion under ``off`` and ``interpret`` — in the one
        solve buffer and in the in-body finish (a ≥ 256-row bucket),
        and the train's ``gram=… solve=…`` line says the same."""
        import jax
        import jax.numpy as jnp
        import predictionio_tpu.models.als as als_mod
        from predictionio_tpu.ops import cholesky as chol_mod

        rng = np.random.default_rng(5)
        n_u, n_i = 300, 40
        uu = np.repeat(np.arange(n_u), 3).astype(np.int32)
        ii = rng.integers(0, n_i, len(uu)).astype(np.int32)
        coo = RatingsCOO(uu, ii, np.ones(len(uu), np.float32), n_u, n_i)
        prep = als_mod.als_prepare(coo)
        assert [b.nb for b in prep.u_side.buckets] == [300]

        kernel_batches = []

        def spy(A, b, interpret=False):
            kernel_batches.append(A.shape[0])
            return jnp.zeros(b.shape, jnp.float32)

        monkeypatch.setattr(chol_mod, "chol_solve_pallas", spy)
        half = als_mod._make_half(4, False, True, gram_mode=gram_mode)
        bufs = prep.u_side.arrays()
        F = jax.ShapeDtypeStruct((n_i, 4), jnp.float32)
        want = gram_mode == "pallas"
        for buf_mb in (als_mod._SOLVE_BUF_MB, 0):  # one buffer, in-body
            monkeypatch.setattr(als_mod, "_SOLVE_BUF_MB", buf_mb)
            del kernel_batches[:]
            out = jax.eval_shape(
                lambda F, bufs: half(F, bufs, prep.u_side.geometry,
                                     0.1, 1.0), F, bufs)
            assert out.shape == (n_u, 4)
            assert bool(kernel_batches) == want, (buf_mb, kernel_batches)
            assert all(n >= 256 for n in kernel_batches)
        assert als_mod.log_train_modes("tpu", gram_mode, 1) == (
            "pallas" if want else "xla")

    def test_default_ladder_matches_dense_reference(self):
        rng = np.random.default_rng(6)
        n_u, n_i = 30, 20
        uu = rng.integers(0, n_u, 350).astype(np.int32)
        ii = rng.integers(0, n_i, 350).astype(np.int32)
        keep = np.unique(uu.astype(np.int64) * n_i + ii, return_index=True)[1]
        uu, ii = uu[keep], ii[keep]
        rr = rng.uniform(1, 5, len(uu)).astype(np.float32)
        coo = RatingsCOO(uu, ii, rr, n_u, n_i)
        p = ALSParams(rank=4, iterations=2, reg=0.1, seed=2)
        U, V = als_train(coo, p)
        Ur, Vr = _ref_als(coo, p)
        np.testing.assert_allclose(U, Ur, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(V, Vr, rtol=2e-3, atol=2e-3)

    @staticmethod
    def _same_array(what, new, old):
        assert (new is None) == (old is None), what
        if new is not None:
            assert new.dtype == old.dtype and new.shape == old.shape, what
            # bit for bit: array_equal would let -0.0 pass for 0.0
            assert new.tobytes() == old.tobytes(), what

    def _same_side(self, name, new, old):
        assert new.geometry == old.geometry, name
        self._same_array(f"{name}.perm", new.perm, old.perm)
        self._same_array(f"{name}.inv_perm", new.inv_perm, old.inv_perm)
        assert (new.dense is None) == (old.dense is None), name
        if new.dense is not None:
            for f in ("w_cnt", "w_val", "counts"):
                self._same_array(f"{name}.dense.{f}", getattr(new.dense, f),
                                 getattr(old.dense, f))
        for j, (b, ob) in enumerate(zip(new.buckets, old.buckets)):
            for f in ("other_idx", "vals", "mask", "counts", "seg",
                      "seg_off"):
                self._same_array(f"{name}.buckets[{j}] (C={b.C}).{f}",
                                 getattr(b, f), getattr(ob, f))

    @pytest.mark.parametrize("order", ["native", "radix"])
    @pytest.mark.parametrize("case", list(_layout_cases()))
    def test_layout_equals_oracle(self, monkeypatch, case, order):
        """ISSUE 28: the O(nnz) builder (prefix-mask fill, dense head
        by assignment) against the builder it replaced
        (``tests/als_layout_oracle.py``, the parent's body): every
        array of both sides equal bit for bit — ISSUE 39: whether the
        interactions were ordered by the native counting pass or, where
        that library cannot be built, by numpy's radix sort."""
        import predictionio_tpu.models.als as als_mod
        import predictionio_tpu.models.als_sharded as sh_mod
        from tests.als_layout_oracle import bucket_side_oracle

        knobs, build, n_dev, paths = _layout_cases()[case]
        if order == "radix":
            _no_native_build(monkeypatch)
        # the one case whose columns the native pass does not take
        took = "radix" if case == "int64_indices" else order
        for k, val in knobs.items():
            monkeypatch.setattr(als_mod, k, val)
        coo = build(monkeypatch)

        def prepare():
            if n_dev:
                p = sh_mod.als_prepare_sharded(coo, n_dev)
                return p, p.u_sides + p.i_sides
            p = als_mod.als_prepare(coo)
            return p, [p.u_side, p.i_side]

        prep, new = prepare()

        def oracle(idx_self, idx_other, other_pos, vals, *rest, **kw):
            return bucket_side_oracle(idx_self, other_pos[idx_other], vals,
                                      *rest, **kw)

        monkeypatch.setattr(als_mod, "_bucket_side", oracle)
        monkeypatch.setattr(sh_mod, "_bucket_side", oracle)
        _, old = prepare()
        assert len(new) == len(old) == 2 * max(n_dev, 1)
        for j, (a, b) in enumerate(zip(new, old)):
            self._same_side(f"side {j}", a, b)
        if case == "segmented_short_last_row":
            assert new[0].buckets[0].seg is not None
        if case == "sharded_skewed_devices":
            seg = [s.buckets[0] for s in prep.u_sides]
            assert all(b.seg is not None for b in seg)
            assert sum(1 for b in seg if not b.mask.any()) >= 1
        assert {s.order_path for s in new} == {took}
        if not n_dev:
            want = dict(paths, order_path_u=took, order_path_i=took)
            if took == "native":
                want.update({k: 0 for k in want if k.startswith("radix_")})
            got = prep.layout_paths()
            assert {k: got[k] for k in want} == want

    @pytest.mark.parametrize("side", ["u", "i"])
    @pytest.mark.parametrize("case", [
        "default_ladder_power_law", "repeats_past_65536_entities",
        "dense_head_repeated_pair", "entities_without_interactions",
        "empty_coo"])
    def test_native_order_equals_radix_order(self, monkeypatch, case, side):
        """The order stage alone: ``o``, ``v`` of the native counting
        pass are those of the radix order and its gathers — a repeated
        (entity, other) pair keeps its COO order, an entity without
        interactions moves no cursor, an empty side gives empty
        arrays."""
        import predictionio_tpu.models.als as als_mod

        knobs, build, _, _ = _layout_cases()[case]
        for k, val in knobs.items():
            monkeypatch.setattr(als_mod, k, val)
        coo = build(monkeypatch)
        idx_self, idx_other, n_self, n_other = (
            (coo.user_idx, coo.item_idx, coo.n_users, coo.n_items)
            if side == "u" else
            (coo.item_idx, coo.user_idx, coo.n_items, coo.n_users))
        _, inv, starts = _order_inputs(
            np.bincount(idx_self, minlength=n_self))
        _, other_pos, _ = _order_inputs(
            np.bincount(idx_other, minlength=n_other))
        o, v = als_mod._native_order(idx_self, idx_other, other_pos,
                                     coo.rating, inv, starts)
        order, _ = als_mod._stable_order(inv, idx_self)
        self._same_array("o", o, other_pos[idx_other[order]])
        self._same_array("v", v, coo.rating[order])

    @pytest.mark.parametrize("column", [
        "idx_self_strided", "idx_other_int64", "vals_float64",
        "other_pos_int64", "vals_strided"])
    def test_native_order_leaves_other_columns_to_numpy(self, monkeypatch,
                                                        column):
        """A column the native pass does not take as it lies in memory
        — strided, or of another width — is not copied to fit: the
        library is never asked for and the radix path orders the side,
        to the arrays the native pass gives on the column's copy."""
        import predictionio_tpu.models.als as als_mod
        from predictionio_tpu import native

        rng = np.random.default_rng(50)
        n_u, n_i, nnz = 50, 30, 400
        cols = dict(
            idx_self=rng.integers(0, n_u, nnz).astype(np.int32),
            idx_other=rng.integers(0, n_i, nnz).astype(np.int32),
            vals=rng.uniform(1, 5, nnz).astype(np.float32),
            other_pos=rng.permutation(n_i).astype(np.int32))
        counts = np.bincount(cols["idx_self"], minlength=n_u)
        perm, inv = als_mod._perm_by_count_desc(counts)

        def side(c):
            return als_mod._bucket_side(
                c["idx_self"], c["idx_other"], c["other_pos"], c["vals"],
                n_u, counts, perm, inv, n_other=n_i)

        want = side(cols)
        assert want.order_path == "native"
        name, _, how = column.rpartition("_")
        if how == "strided":
            cols[name] = np.repeat(cols[name], 2)[::2]
            assert not cols[name].flags.c_contiguous
        else:
            cols[name] = cols[name].astype(how)
        # never asked for: calling None raises
        monkeypatch.setattr(native, "als_layout_library", None)
        got = side(cols)
        assert got.order_path == "radix" and got.radix_passes == 1
        self._same_side(column, got, want)

    def test_native_order_declines_counts_that_are_not_the_columns(self):
        """``starts`` that do not end at the number of interactions are
        no layout of these columns: the pass is not run on them (it
        would leave slots unwritten)."""
        import predictionio_tpu.models.als as als_mod

        idx = np.array([0, 1, 1, 2], np.int32)
        _, inv, starts = _order_inputs(np.array([1, 1, 1]))  # 1 has two
        assert als_mod._native_order(
            idx, idx, np.arange(3, dtype=np.int32),
            np.ones(4, np.float32), inv, starts) is None

    @pytest.mark.parametrize("column", ["int32", "int32_empty", "int64",
                                        "int32_strided", "int32_nobuild"])
    def test_entity_counts_are_bincount(self, monkeypatch, column):
        """The counts the order starts from: the native pass over an
        int32 column as it lies, ``np.bincount`` for any other column
        or machine — the same int64 table, entities without
        interactions included."""
        import predictionio_tpu.models.als as als_mod
        from predictionio_tpu import native

        kind, _, how = column.partition("_")
        idx = np.random.default_rng(51).integers(
            0, 90, 0 if how == "empty" else 5000).astype(kind)
        if how == "strided":
            idx = np.repeat(idx, 2)[::2]
        if how == "nobuild":
            _no_native_build(monkeypatch)
        elif column not in ("int32", "int32_empty"):
            # never asked for: calling None raises
            monkeypatch.setattr(native, "als_layout_library", None)
        got = als_mod._entity_counts(idx, 100)
        self._same_array(column, got, np.bincount(idx, minlength=100))

    @pytest.mark.parametrize("bad", ["idx_self", "idx_other"])
    def test_native_order_refuses_an_id_outside_the_layout(self, bad):
        """numpy's gathers raise on an id past the table; so does the
        native pass, before it reads or writes outside an array."""
        import predictionio_tpu.models.als as als_mod

        cols = dict(idx_self=np.array([0, 1, 1, 2], np.int32),
                    idx_other=np.array([1, 0, 2, 1], np.int32))
        cols[bad] = cols[bad].copy()
        cols[bad][2] = 7
        _, inv, starts = _order_inputs(np.array([1, 2, 1]))
        if bad == "idx_self":
            with pytest.raises(IndexError, match="interaction 2: id 7"):
                als_mod._entity_counts(cols[bad], 3)
        with pytest.raises(IndexError, match="interaction 2"):
            als_mod._native_order(
                cols["idx_self"], cols["idx_other"],
                np.arange(3, dtype=np.int32), np.ones(4, np.float32),
                inv, starts)

    @pytest.mark.parametrize("implicit", [False, True])
    def test_train_is_bit_identical_under_both_orders(self, monkeypatch,
                                                      implicit):
        """The factors of a small train on the native order's layout
        and on the radix order's: equal bit for bit."""
        import predictionio_tpu.models.als as als_mod

        _wide_layout(monkeypatch)     # its knobs: a head, segments, a ladder
        coo = _layout_cases()["dense_head_power_law_repeats"][1](monkeypatch)
        p = ALSParams(rank=8, iterations=3, reg=0.05, seed=4,
                      implicit=implicit)
        native_prep = als_mod.als_prepare(coo)
        U, V = als_mod.als_train_prepared(native_prep, p)
        _no_native_build(monkeypatch)
        radix_prep = als_mod.als_prepare(coo)
        Ur, Vr = als_mod.als_train_prepared(radix_prep, p)
        assert native_prep.layout_paths()["order_path_u"] == "native"
        assert radix_prep.layout_paths()["order_path_i"] == "radix"
        assert native_prep.geometry == radix_prep.geometry
        u = native_prep.u_side
        assert u.dense is not None and u.buckets[0].seg is not None \
            and any(b.seg is None for b in u.buckets)
        assert native_prep.kernel_rows(8) == radix_prep.kernel_rows(8)
        self._same_array("U", U, Ur)
        self._same_array("V", V, Vr)

    def test_prepare_spans_only_inside_a_verb(self, monkeypatch):
        """``als.prepare`` has children since ISSUE 28 — ``order``,
        ``dense`` (a side with a head), ``fill`` — and carries the
        paths the data took; a direct ``als_prepare`` outside a verb
        (the benchmark's) records nothing."""
        import predictionio_tpu.models.als as als_mod
        from predictionio_tpu.utils import tracing

        monkeypatch.setattr(als_mod, "_DENSE_MIN_COUNT", 6)
        _, build, _, _ = _layout_cases()["dense_head_repeated_pair"]
        coo = build(monkeypatch)
        before = tracing.last_verb("layout.test")
        prep = als_mod.als_prepare(coo)
        assert tracing.last_verb("layout.test") is before
        assert prep.u_side.dense_fill == "bincount"
        with tracing.verb("layout.test"):
            als_mod.als_train(coo, ALSParams(rank=4, iterations=1))
        tree = tracing.last_verb("layout.test")
        (parent,) = [s for s in tree if s["name"] == "als.prepare"]
        kids = [s for s in tree if s["parentId"] == parent["spanId"]]
        # the perms, then per side its order, its head if it has one,
        # its buckets
        want = ["order"] + [
            step for side in (prep.u_side, prep.i_side)
            for step in ("order", "dense", "fill")
            if step != "dense" or side.dense is not None]
        assert [s["name"] for s in kids] == [
            "als.prepare." + step for step in want]
        for a, b in zip(kids, kids[1:]):
            assert parent["startNs"] <= a["startNs"] <= a["endNs"] \
                <= b["startNs"] <= b["endNs"] <= parent["endNs"]
        paths = prep.layout_paths()
        assert {k: parent["attrs"][k] for k in paths} == paths


def _no_native_build(monkeypatch):
    """A machine without g++: every native build fails, as
    ``native.load_library`` reports it."""
    from predictionio_tpu import native

    def refuse(name):
        raise native.NativeBuildError(f"g++ unavailable: {name}")

    monkeypatch.setattr(native, "load_library", refuse)


def _order_inputs(counts):
    """``(perm, inv_perm, starts)`` as ``als_prepare`` and
    ``_bucket_side`` make them from a side's counts."""
    import predictionio_tpu.models.als as als_mod

    perm, inv = als_mod._perm_by_count_desc(counts)
    starts = np.zeros(len(counts) + 1, np.int64)
    np.cumsum(counts[perm], out=starts[1:])
    return perm, inv, starts


def _zipf_coo(seed, n_u, n_i, nnz):
    rng = np.random.default_rng(seed)
    uu = (rng.zipf(1.3, nnz) % n_u).astype(np.int32)
    ii = (rng.zipf(1.3, nnz) % n_i).astype(np.int32)
    keep = np.unique(uu.astype(np.int64) * n_i + ii, return_index=True)[1]
    uu, ii = uu[keep], ii[keep]
    rr = rng.uniform(1, 5, len(uu)).astype(np.float32)
    return RatingsCOO(uu, ii, rr, n_u, n_i)


def _wide_layout(monkeypatch):
    """A layout with a dense head, a segmented bucket and regular
    buckets the kernel takes (width 128), on both sides."""
    import predictionio_tpu.models.als as als_mod

    monkeypatch.setattr(als_mod, "_LADDER", (8, 128))
    monkeypatch.setattr(als_mod, "_C_MAX", 128)
    monkeypatch.setattr(als_mod, "_DENSE_MIN_COUNT", 300)
    monkeypatch.setattr(als_mod, "_DENSE_RATIO", 0.75)
    rng = np.random.default_rng(31)
    n_u, n_i = 90, 400
    deg = np.minimum(rng.zipf(1.25, n_u) + 2, n_i)
    deg[:3] = (390, 350, 330)          # the dense head
    deg[3:9] = (290, 260, 200, 170, 140, 129)   # segmented rows
    uu = np.repeat(np.arange(n_u), deg).astype(np.int32)
    ii = np.concatenate([rng.choice(n_i, d, replace=False)
                         for d in deg]).astype(np.int32)
    rr = rng.uniform(1, 5, len(uu)).astype(np.float32)
    return RatingsCOO(uu, ii, rr, n_u, n_i)


class TestFusedGram:
    """ISSUE 17: whole-train parity of the fused gather→Gram Pallas
    path (Mosaic interpreter on CPU) against the XLA gather+einsum
    path, plus the dispatch-collapse regression guard."""

    def _train_both(self, coo, p, monkeypatch):
        import predictionio_tpu.models.als as als_mod

        monkeypatch.setenv("PIO_PALLAS_GRAM", "0")
        Ux, Vx = als_mod.als_train(coo, p)
        monkeypatch.setenv("PIO_PALLAS_GRAM", "interpret")
        Uf, Vf = als_mod.als_train(coo, p)
        return (Ux, Vx), (Uf, Vf)

    def test_train_parity_explicit(self, monkeypatch):
        coo = _zipf_coo(21, 60, 40, 900)
        p = ALSParams(rank=8, iterations=2, reg=0.1, seed=2)
        (Ux, Vx), (Uf, Vf) = self._train_both(coo, p, monkeypatch)
        np.testing.assert_allclose(Uf, Ux, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(Vf, Vx, rtol=1e-4, atol=1e-4)

    def test_train_parity_implicit(self, monkeypatch):
        coo = _zipf_coo(22, 50, 30, 700)
        p = ALSParams(rank=8, iterations=2, reg=0.1, seed=2,
                      implicit=True, alpha=2.0)
        (Ux, Vx), (Uf, Vf) = self._train_both(coo, p, monkeypatch)
        np.testing.assert_allclose(Uf, Ux, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(Vf, Vx, rtol=1e-4, atol=1e-4)

    def test_train_parity_seg_and_dense(self, monkeypatch):
        """Shrink the ladder + dense threshold so ONE program runs all
        three aggregation paths (regular buckets, segmented heavy
        bucket, dense head) — each must match with the kernel on."""
        import predictionio_tpu.models.als as als_mod

        monkeypatch.setattr(als_mod, "_LADDER", (2, 8))
        monkeypatch.setattr(als_mod, "_C_MAX", 8)
        monkeypatch.setattr(als_mod, "_DENSE_MIN_COUNT", 10)
        coo = _zipf_coo(23, 40, 25, 700)
        prep = als_mod.als_prepare(coo)
        assert any(b.seg is not None for b in prep.u_side.buckets)
        p = ALSParams(rank=4, iterations=2, reg=0.1, seed=2)
        (Ux, Vx), (Uf, Vf) = self._train_both(coo, p, monkeypatch)
        np.testing.assert_allclose(Uf, Ux, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(Vf, Vx, rtol=1e-4, atol=1e-4)
        # and against the dense float64 reference, same bar as XLA
        Ur, Vr = _ref_als(coo, p)
        np.testing.assert_allclose(Uf, Ur, rtol=2e-3, atol=2e-3)

    @staticmethod
    def _kernel_buckets(sides):
        from predictionio_tpu.ops.gram import kernel_takes_width

        return [b for s in sides for b in s.buckets
                if kernel_takes_width(b.C)]

    @pytest.mark.parametrize("sharded", [False, True],
                             ids=["single", "sharded_forced_bounds"])
    def test_real_slots_are_a_prefix_of_every_bucket_row(self, monkeypatch,
                                                         sharded):
        """What the kernel's lengths rest on (``_bucket_side``): padding
        only at a row's end, so ``mask.sum(1)`` is the real length —
        under natural bounds and under the sharded path's forced ones."""
        import predictionio_tpu.models.als as als_mod
        from predictionio_tpu.models.als_sharded import als_prepare_sharded

        coo = _wide_layout(monkeypatch)
        if sharded:
            prep = als_prepare_sharded(coo, 4)
            sides = prep.u_sides + prep.i_sides
        else:
            prep = als_mod.als_prepare(coo)
            sides = [prep.u_side, prep.i_side]
            assert prep.u_side.dense is not None
        buckets = self._kernel_buckets(sides)
        assert any(b.seg is not None for b in buckets)
        assert any(b.seg is None for b in buckets)
        for b in buckets:
            m = b.mask.reshape(-1, b.C)
            assert (m[:, :-1] >= m[:, 1:]).all()
            # every interaction of the bucket sits in some row's prefix
            assert m.sum() == b.counts.sum()

    @pytest.mark.parametrize("held", ["both", "users_only", "none"])
    def test_kernel_is_given_the_mask_row_sums(self, monkeypatch, held):
        """Each half-step hands ``gather_gram`` one length per bucket
        row, equal to that row's mask sum, and ``kernel_dma_rows`` is
        their total: the kernel fetches one line per real slot — from
        the table it holds where ``table_is_resident`` says so
        (``kernel_resident_rows``: the rule's constant is moved here so
        that both tables, the users' alone — the smaller, which the
        ITEM half-step gathers — or neither fit), else by a copy, and
        only copies are waited for."""
        import jax.numpy as jnp
        from predictionio_tpu.ops import gram as gram_mod
        import predictionio_tpu.models.als as als_mod

        coo = _wide_layout(monkeypatch)
        # tiles of 8 slots: these tables of 25 and 6 lines are laid
        # out in at least a tile's worth, and must differ
        monkeypatch.setattr(gram_mod, "_GATHER_TILE", 8)
        bytes_u, bytes_i = (gram_mod.table_bytes(n, 4)
                            for n in (coo.n_items, coo.n_users))
        assert bytes_u > bytes_i
        monkeypatch.setattr(
            gram_mod, "_RESIDENT_TABLE_BYTES",
            {"both": bytes_u, "users_only": bytes_u - 1,
             "none": bytes_i - 1}[held])
        prep = als_mod.als_prepare(coo)
        given = []
        orig = gram_mod.gather_gram

        def spy(F, idx, wo, wb, lengths=None, **kw):
            given.append(np.asarray(lengths))
            return orig(F, idx, wo, wb, lengths, **kw)

        monkeypatch.setattr(gram_mod, "gather_gram", spy)
        half = als_mod._make_half(4, False, True, gram_mode="interpret")
        u_bufs, i_bufs = prep.device_buffers()
        rng = np.random.default_rng(0)
        for side, bufs, n_other in ((prep.u_side, u_bufs, coo.n_items),
                                    (prep.i_side, i_bufs, coo.n_users)):
            F = jnp.asarray(rng.standard_normal((n_other, 4)), jnp.float32)
            half(F, bufs, side.geometry, 0.1, 1.0)   # eager: no jit
        buckets = self._kernel_buckets([prep.u_side, prep.i_side])
        assert len(given) == len(buckets) > 2
        for lengths, b in zip(given, buckets):
            np.testing.assert_array_equal(
                lengths, b.mask.reshape(-1, b.C).sum(1).astype(np.int32))
        rows = prep.kernel_rows(4)
        assert rows["kernel_dma_rows"] == sum(int(g.sum()) for g in given)
        assert (rows["gram_table_bytes_u"], rows["gram_table_bytes_i"]) \
            == (bytes_u, bytes_i)
        n_u = len(self._kernel_buckets([prep.u_side]))
        copied = {"both": [], "users_only": list(zip(given, buckets))[:n_u],
                  "none": list(zip(given, buckets))}[held]
        assert rows["kernel_resident_rows"] == (
            rows["kernel_dma_rows"] - sum(int(g.sum()) for g, _ in copied))
        assert (held == "both") == (
            rows["kernel_resident_rows"] == rows["kernel_real_rows"])
        assert (held == "none") == (rows["kernel_resident_rows"] == 0)
        # the waits are counted from the entities' counts; the kernel
        # makes them for the ROW lengths it is given — the same number,
        # a segmented entity's rows being cut at whole tiles — and for
        # copied lines only
        assert rows["kernel_dma_waits"] == sum(
            gram_mod.dma_waits(g, b.C) for g, b in copied)
        # no slot that holds an interaction is skipped
        assert rows["kernel_dma_rows"] >= rows["kernel_real_rows"] > 0

    def test_kernel_actually_traced(self, monkeypatch):
        """Guard against the silent-skip failure mode: a geometry where
        everything lands in the dense head never calls the kernel and
        'parity' is vacuous. Assert the fused train traces it."""
        from predictionio_tpu.ops import gram as gram_mod
        import predictionio_tpu.models.als as als_mod

        calls = []
        orig = gram_mod.gather_gram
        monkeypatch.setattr(gram_mod, "gather_gram",
                            lambda *a, **k: calls.append(1) or orig(*a, **k))
        monkeypatch.setenv("PIO_PALLAS_GRAM", "interpret")
        coo = _zipf_coo(24, 45, 35, 600)
        # rank 12 is unique in this file → fresh _compiled_bucketed
        # entry, so tracing (and the counter) actually runs
        als_mod.als_train(coo, ALSParams(rank=12, iterations=1, reg=0.1,
                                         seed=2))
        assert calls, "fused train never reached gather_gram"

    def test_off_flag_restores_xla_program(self, monkeypatch):
        """PIO_PALLAS_GRAM=0 must produce a program with zero
        pallas_call Gram dispatches (byte-identical XLA path)."""
        from predictionio_tpu.ops import gram as gram_mod
        import predictionio_tpu.models.als as als_mod

        calls = []
        orig = gram_mod.gather_gram
        monkeypatch.setattr(gram_mod, "gather_gram",
                            lambda *a, **k: calls.append(1) or orig(*a, **k))
        monkeypatch.setenv("PIO_PALLAS_GRAM", "0")
        coo = _zipf_coo(25, 45, 35, 600)
        als_mod.als_train(coo, ALSParams(rank=12, iterations=1, reg=0.1,
                                         seed=3))
        assert not calls, "gram kernel traced with PIO_PALLAS_GRAM=0"

    def test_dispatch_collapse_ratio(self):
        """The ISSUE-17 acceptance floor, chip-free: the fused TPU
        program must dispatch ≥10× fewer device ops per iteration than
        the XLA path on a representative multi-bucket geometry."""
        from predictionio_tpu.models.als import als_prepare
        from predictionio_tpu.utils import opcount

        # the ratio is geometry-dependent (fixed solve/dense overhead
        # amortizes over slab count): toy shapes sit near 8x, this
        # 250k-nnz zipf shape gives ~16x, the 500k bench shape ~100x
        coo = _zipf_coo(26, 20000, 4000, 250_000)
        prep = als_prepare(coo)
        assert len(prep.u_side.buckets) >= 3  # representative ladder
        p = ALSParams(rank=16, iterations=1, reg=0.1, seed=2)
        rep = opcount.als_dispatch_report(prep, p)
        assert rep["dispatch_collapse_ratio"] >= 10, rep

    @pytest.mark.slow
    def test_ml100k_scale_parity(self, monkeypatch):
        """Trained-factors parity at ML-100k scale (the acceptance
        geometry): 100k zipf ratings over 943×1682, default ladder."""
        coo = _zipf_coo(27, 943, 1682, 100_000)
        p = ALSParams(rank=16, iterations=2, reg=0.05, seed=2)
        (Ux, Vx), (Uf, Vf) = self._train_both(coo, p, monkeypatch)
        np.testing.assert_allclose(Uf, Ux, rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(Vf, Vx, rtol=5e-4, atol=5e-4)


class TestShardedParity:
    def test_explicit_matches_single(self, synthetic, cpu_mesh):
        coo, _, _ = synthetic
        p = ALSParams(rank=8, iterations=8, reg=0.05, seed=3)
        U1, V1 = als_train(coo, p, mesh=None)
        U8, V8 = als_train(coo, p, mesh=cpu_mesh)
        r1 = predict_ratings(U1, V1, coo.user_idx, coo.item_idx)
        r8 = predict_ratings(U8, V8, coo.user_idx, coo.item_idx)
        # same math, different init/order → near-identical predictions
        assert float(np.sqrt(np.mean((r1 - r8) ** 2))) < 0.15
        assert np.corrcoef(r1, r8)[0, 1] > 0.99

    def test_implicit_matches_single(self, synthetic, cpu_mesh):
        coo, _, _ = synthetic
        pos = RatingsCOO(coo.user_idx, coo.item_idx,
                         np.abs(coo.rating), coo.n_users, coo.n_items)
        p = ALSParams(rank=8, iterations=6, reg=0.05, implicit=True,
                      alpha=2.0, seed=3)
        Ua, Va = als_train(pos, p, mesh=None)
        Ub, Vb = als_train(pos, p, mesh=cpu_mesh)
        ra = (Ua @ Va.T)[pos.user_idx, pos.item_idx]
        rb = (Ub @ Vb.T)[pos.user_idx, pos.item_idx]
        assert np.corrcoef(ra, rb)[0, 1] > 0.99

    def test_sharded_matches_dense_reference(self, cpu_mesh):
        """The sharded bucketed kernel against the dense float64
        reference — same tolerance as the single-device path, so the
        mesh port cannot silently drift from the math."""
        rng = np.random.default_rng(9)
        n_u, n_i = 41, 26  # not divisible by 8
        uu = rng.integers(0, n_u, 400).astype(np.int32)
        ii = rng.integers(0, n_i, 400).astype(np.int32)
        keep = np.unique(uu.astype(np.int64) * n_i + ii, return_index=True)[1]
        uu, ii = uu[keep], ii[keep]
        rr = rng.uniform(1, 5, len(uu)).astype(np.float32)
        coo = RatingsCOO(uu, ii, rr, n_u, n_i)
        p = ALSParams(rank=4, iterations=2, reg=0.1, seed=2)
        from predictionio_tpu.models.als_sharded import als_train_sharded

        U, V = als_train_sharded(coo, p, cpu_mesh)
        Ur, Vr = _ref_als(coo, p)
        np.testing.assert_allclose(U, Ur, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(V, Vr, rtol=2e-3, atol=2e-3)

    def test_sharded_seg_bucket_skewed_devices(self, cpu_mesh, monkeypatch):
        """Merged-bounds path: a heavy-tailed dataset where devices have
        very different heavy-entity counts (one user owns most ratings)
        must still give every device one program and correct factors."""
        import predictionio_tpu.models.als as als_mod

        monkeypatch.setattr(als_mod, "_LADDER", (2, 8))
        monkeypatch.setattr(als_mod, "_C_MAX", 8)
        rng = np.random.default_rng(11)
        n_u, n_i = 33, 17
        # user 0 rates almost everything (heavy, lands on device 0);
        # the rest are sparse
        uu = np.concatenate([np.zeros(16, np.int32),
                             rng.integers(1, n_u, 120).astype(np.int32)])
        ii = np.concatenate([np.arange(16, dtype=np.int32) % n_i,
                             rng.integers(0, n_i, 120).astype(np.int32)])
        keep = np.unique(uu.astype(np.int64) * n_i + ii, return_index=True)[1]
        uu, ii = uu[keep], ii[keep]
        rr = rng.uniform(1, 5, len(uu)).astype(np.float32)
        coo = RatingsCOO(uu, ii, rr, n_u, n_i)

        from predictionio_tpu.models.als_sharded import (als_prepare_sharded,
                                                         als_train_sharded)

        prep = als_prepare_sharded(coo, 8)
        assert any(b.seg is not None for b in prep.u_sides[0].buckets)
        geoms = {s.geometry for s in prep.u_sides}
        assert len(geoms) == 1, "all devices must share one geometry"

        p = ALSParams(rank=4, iterations=2, reg=0.1, seed=2)
        U, V = als_train_sharded(coo, p, cpu_mesh)
        Ur, Vr = _ref_als(coo, p)
        np.testing.assert_allclose(U, Ur, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(V, Vr, rtol=2e-3, atol=2e-3)

    def test_sharded_dense_head_matches_reference(self, cpu_mesh,
                                                  monkeypatch):
        """Dense head under shard_map: per-device dense rows over the
        gathered (padded global) other side, max-merged nb_dense."""
        import predictionio_tpu.models.als as als_mod

        monkeypatch.setattr(als_mod, "_DENSE_MIN_COUNT", 6)
        rng = np.random.default_rng(12)
        n_u, n_i, nnz = 33, 17, 400
        uu = (rng.zipf(1.3, nnz) % n_u).astype(np.int32)
        ii = rng.integers(0, n_i, nnz).astype(np.int32)
        rr = rng.uniform(1, 5, nnz).astype(np.float32)
        coo = RatingsCOO(uu, ii, rr, n_u, n_i)

        from predictionio_tpu.models.als_sharded import (als_prepare_sharded,
                                                         als_train_sharded)

        prep = als_prepare_sharded(coo, 8)
        assert prep.u_sides[0].dense is not None
        assert prep.u_sides[0].dense.nb > 0
        assert len({s.geometry for s in prep.u_sides}) == 1

        p = ALSParams(rank=4, iterations=2, reg=0.1, seed=2)
        U, V = als_train_sharded(coo, p, cpu_mesh)
        Ur, Vr = _ref_als(coo, p)
        np.testing.assert_allclose(U, Ur, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(V, Vr, rtol=2e-3, atol=2e-3)

    def test_sharded_fused_gram_parity(self, cpu_mesh, monkeypatch):
        """Fused gather→Gram under shard_map (interpret mode): the
        unchecked-replication wrapper the kernel needs must not change
        the factors vs the XLA sharded path."""
        from predictionio_tpu.models.als_sharded import als_train_sharded

        coo = _zipf_coo(28, 41, 26, 500)
        p = ALSParams(rank=4, iterations=2, reg=0.1, seed=2)
        monkeypatch.setenv("PIO_PALLAS_GRAM", "0")
        Ux, Vx = als_train_sharded(coo, p, cpu_mesh)
        monkeypatch.setenv("PIO_PALLAS_GRAM", "interpret")
        Uf, Vf = als_train_sharded(coo, p, cpu_mesh)
        np.testing.assert_allclose(Uf, Ux, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(Vf, Vx, rtol=1e-4, atol=1e-4)

    def test_uneven_sizes(self, cpu_mesh):
        # sizes deliberately not divisible by 8
        rng = np.random.default_rng(1)
        n_u, n_i = 37, 23
        uu = rng.integers(0, n_u, 300).astype(np.int32)
        ii = rng.integers(0, n_i, 300).astype(np.int32)
        rr = rng.uniform(1, 5, 300).astype(np.float32)
        coo = RatingsCOO(uu, ii, rr, n_u, n_i)
        U, V = als_train(coo, ALSParams(rank=4, iterations=3, reg=0.1),
                         mesh=cpu_mesh)
        assert U.shape == (37, 4) and V.shape == (23, 4)
        assert np.isfinite(U).all() and np.isfinite(V).all()


class TestResidentScorerPolicy:
    """r4 advisor: maybe_resident_scorer must never serve a cached
    scorer built from different factor arrays (stale scores after a
    retrain/swap)."""

    def test_cache_reused_and_invalidated_on_swap(self, monkeypatch):
        from predictionio_tpu.models.als import maybe_resident_scorer

        monkeypatch.setenv("PIO_ALS_SERVE", "device")
        rng = np.random.default_rng(0)
        U1 = rng.normal(size=(6, 4)).astype(np.float32)
        V1 = rng.normal(size=(8, 4)).astype(np.float32)
        s1 = maybe_resident_scorer(U1, V1)
        assert maybe_resident_scorer(U1, V1, s1) is s1  # same arrays → reuse
        V2 = rng.normal(size=(8, 4)).astype(np.float32)
        s2 = maybe_resident_scorer(U1, V2, s1)  # retrain swapped V
        assert s2 is not s1
        assert maybe_resident_scorer(U1, V2, s2) is s2


class TestALSGrid:
    """VERDICT r3 #2: an eval grid over reg/alpha must share ONE
    compiled executable (reg/alpha are traced scalars)."""

    def _coo(self):
        rng = np.random.default_rng(7)
        n_u, n_i, nnz = 50, 30, 600
        return RatingsCOO(rng.integers(0, n_u, nnz).astype(np.int32),
                          rng.integers(0, n_i, nnz).astype(np.int32),
                          rng.uniform(1, 5, nnz).astype(np.float32),
                          n_u, n_i)

    def test_reg_grid_builds_one_program(self, monkeypatch):
        import predictionio_tpu.models.als as als_mod

        coo = self._coo()
        builds = {"n": 0}
        orig = als_mod._make_half

        def counting(*a, **k):
            builds["n"] += 1
            return orig(*a, **k)

        monkeypatch.setattr(als_mod, "_make_half", counting)
        als_mod._compiled_bucketed.cache_clear()

        grid = [ALSParams(rank=4, iterations=3, reg=r, seed=2)
                for r in (0.01, 0.05, 0.1, 0.5, 1.0)]
        results = als_mod.als_train_many(coo, grid)
        assert builds["n"] == 1, \
            f"5 reg candidates built {builds['n']} programs, expected 1"
        assert len(results) == 5
        # each candidate matches its individually-trained counterpart
        for p, (U, V) in zip(grid, results):
            U1, V1 = als_mod.als_train_prepared(als_mod.als_prepare(coo), p)
            np.testing.assert_allclose(U, U1, rtol=1e-5, atol=1e-6)
        # distinct reg values genuinely differ (the scalars really trace)
        assert not np.allclose(results[0][0], results[-1][0])

    def test_alpha_implicit_grid_shares_program(self, monkeypatch):
        import predictionio_tpu.models.als as als_mod

        coo = self._coo()
        builds = {"n": 0}
        orig = als_mod._make_half

        def counting(*a, **k):
            builds["n"] += 1
            return orig(*a, **k)

        monkeypatch.setattr(als_mod, "_make_half", counting)
        als_mod._compiled_bucketed.cache_clear()

        grid = [ALSParams(rank=4, iterations=3, reg=0.1, implicit=True,
                          alpha=a, seed=2) for a in (0.5, 1.0, 2.0, 4.0)]
        results = als_mod.als_train_many(coo, grid)
        assert builds["n"] == 1
        assert not np.allclose(results[0][0], results[-1][0])

    def test_rank_change_rebuilds(self, monkeypatch):
        import predictionio_tpu.models.als as als_mod

        coo = self._coo()
        builds = {"n": 0}
        orig = als_mod._make_half

        def counting(*a, **k):
            builds["n"] += 1
            return orig(*a, **k)

        monkeypatch.setattr(als_mod, "_make_half", counting)
        als_mod._compiled_bucketed.cache_clear()
        grid = [ALSParams(rank=4, iterations=3, reg=0.1, seed=2),
                ALSParams(rank=8, iterations=3, reg=0.1, seed=2)]
        als_mod.als_train_many(coo, grid)
        assert builds["n"] == 2  # rank changes program shape

    def test_sharded_reg_grid_builds_one_program(self, cpu_mesh,
                                                 monkeypatch):
        import predictionio_tpu.models.als as als_mod
        import predictionio_tpu.models.als_sharded as sh_mod

        coo = self._coo()
        builds = {"n": 0}
        orig = als_mod._make_half

        def counting(*a, **k):
            builds["n"] += 1
            return orig(*a, **k)

        # _compiled_sharded resolves _make_half from the als module at
        # call time via its import — patch where it's looked up
        monkeypatch.setattr(sh_mod, "_make_half", counting)
        sh_mod._compiled_sharded.cache_clear()

        grid = [ALSParams(rank=4, iterations=2, reg=r, seed=2)
                for r in (0.01, 0.1, 1.0)]
        results = als_mod.als_train_many(coo, grid, mesh=cpu_mesh)
        assert builds["n"] == 1, \
            f"3 sharded reg candidates built {builds['n']} programs"
        # parity with the single-device grid
        single = als_mod.als_train_many(coo, grid)
        for (U_s, _), (U_1, _) in zip(results, single):
            np.testing.assert_allclose(U_s, U_1, rtol=2e-4, atol=2e-5)


class TestMeshTraining:
    def test_workflow_train_with_mesh(self, storage):
        """use_mesh=True end-to-end: the full train workflow on the CPU mesh."""
        from predictionio_tpu.core.workflow import prepare_deploy, run_train
        from tests.test_workflow import FACTORY, VARIANT, seed_ratings

        seed_ratings(storage)
        run_train(FACTORY, variant=VARIANT, storage=storage, use_mesh=True)
        deployed = prepare_deploy(engine_factory=FACTORY, storage=storage)
        res = deployed.query({"user": "0", "num": 5})
        assert len(res["itemScores"]) == 5
