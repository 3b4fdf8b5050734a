"""AOT-bucketed serving executables (server/aot + the serving stack).

The PR 7 contract: at deploy time the serving program is lowered and
compiled for a ladder of padded batch buckets, so after warmup NO query
at any batch size ≤ max_batch triggers an XLA compile on the hot path;
a /reload of a same-geometry candidate swaps with zero compiles; and
padded execution is bitwise-identical to unpadded for every real row.
"""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from predictionio_tpu.server.aot import (
    EXECUTABLES,
    PAD,
    AOTWarmup,
    BucketLadder,
    ExecutableCache,
    is_pad,
    strip_pads,
)


def run(coro):
    return asyncio.run(coro)


# -- bucket ladder ------------------------------------------------------------


class TestBucketLadder:
    def test_geometric_always_includes_max(self):
        assert list(BucketLadder.geometric(64)) == [1, 2, 4, 8, 16, 32, 64]
        # a non-power-of-two max still terminates the ladder exactly
        assert list(BucketLadder.geometric(48)) == [1, 2, 4, 8, 16, 32, 48]
        assert list(BucketLadder.geometric(1)) == [1]

    def test_parse_auto_and_explicit(self):
        assert list(BucketLadder.parse("auto", 8)) == [1, 2, 4, 8]
        assert list(BucketLadder.parse(None, 4)) == [1, 2, 4]
        lad = BucketLadder.parse("1,4,16", 999)
        assert list(lad) == [1, 4, 16]
        # an explicit ladder defines its own max batch
        assert lad.max_batch == 16

    def test_parse_rejects_garbage(self):
        with pytest.raises(ValueError, match="aot-buckets"):
            BucketLadder.parse("1,two,3", 8)
        with pytest.raises(ValueError):
            BucketLadder([0])

    def test_snap(self):
        lad = BucketLadder([1, 4, 16])
        assert lad.snap(1) == 1
        assert lad.snap(2) == 4
        assert lad.snap(4) == 4
        assert lad.snap(5) == 16
        assert lad.snap(16) == 16       # max_batch boundary: no padding
        assert lad.snap(99) == 16       # beyond the top: defensive floor

    def test_dedup_and_sort(self):
        assert list(BucketLadder([8, 1, 8, 2])) == [1, 2, 8]


# -- PAD mechanics ------------------------------------------------------------


class TestPadSentinel:
    def test_identity_and_strip(self):
        assert is_pad(PAD) and not is_pad({"user": "1"})
        real, pos = strip_pads([{"u": 1}, PAD, {"u": 2}, PAD])
        assert real == [{"u": 1}, {"u": 2}] and pos == [0, 2]

    def test_batch_query_passes_pads_through(self, storage):
        """PAD slots are never supplemented, predicted (for per-query
        algorithms), or served — and the result list keeps arity."""
        from predictionio_tpu.core.workflow import DeployedEngine

        class Algo:
            def batch_predict(self, model, qs):
                # default per-query algorithm: must never see a PAD
                assert not any(is_pad(q) for q in qs)
                return [q["u"] * 10 for q in qs]

        class Serving:
            def supplement(self, q):
                assert not is_pad(q)
                return q

            def serve(self, q, preds):
                assert not is_pad(q)
                return preds[0]

        eng = DeployedEngine(
            engine=None, engine_params=None,
            algorithms=[("a", Algo())], models=[None],
            serving=Serving(), instance=None)
        out = eng.batch_query([{"u": 1}, PAD, {"u": 3}, PAD])
        assert out[0] == 10 and out[2] == 30
        assert is_pad(out[1]) and is_pad(out[3])

    def test_batch_query_inline_pads_for_padding_algos(self, storage):
        from predictionio_tpu.core.workflow import DeployedEngine

        seen = []

        class Algo:
            accepts_padding = True

            def batch_predict(self, model, qs):
                seen.append(len(qs))  # gets the PADDED batch inline
                return [None if is_pad(q) else q["u"] for q in qs]

        class Serving:
            def supplement(self, q):
                return q

            def serve(self, q, preds):
                return preds[0]

        eng = DeployedEngine(
            engine=None, engine_params=None,
            algorithms=[("a", Algo())], models=[None],
            serving=Serving(), instance=None)
        out = eng.batch_query([{"u": 7}, PAD])
        assert seen == [2] and out[0] == 7 and is_pad(out[1])


# -- executable cache ---------------------------------------------------------


class TestExecutableCache:
    def test_compile_once_then_hits(self):
        cache = ExecutableCache()
        built = []

        def build():
            built.append(1)
            return "prog"

        assert cache.get(("k",)) is None
        assert cache.get_or_compile(("k",), build) == "prog"
        assert cache.get_or_compile(("k",), build) == "prog"
        assert built == [1]
        assert cache.counts() == {"compile": 1, "hit": 1}
        assert len(cache) == 1
        cache.clear()
        assert cache.get(("k",)) is None


# -- ResidentScorer: warmup + padded parity -----------------------------------


@pytest.fixture()
def device_serving(monkeypatch):
    monkeypatch.setenv("PIO_ALS_SERVE", "device")


def _factors(n_users=300, n_items=2500, rank=12, seed=0):
    rng = np.random.default_rng(seed)
    U = (rng.standard_normal((n_users, rank)) / np.sqrt(rank)).astype(
        np.float32)
    V = (rng.standard_normal((n_items, rank)) / np.sqrt(rank)).astype(
        np.float32)
    return U, V


class TestScorerWarmup:
    def test_warm_buckets_compiles_the_ladder(self, device_serving):
        from predictionio_tpu.models.als import ResidentScorer

        U, V = _factors(seed=1)
        sc = ResidentScorer(U, V)
        ladder = BucketLadder([1, 2, 4])
        stats = sc.warm_buckets(ladder, ks=(10,))
        assert stats["targets"] == 3
        assert stats["compiled"] + stats["cached"] == 3
        assert sc.bucket_ladder is ladder
        # every (bucket, bucketed-k) now dispatches precompiled
        assert set(sc._aot) == {(1, 16), (2, 16), (4, 16)}

    def test_same_geometry_scorer_warms_from_cache(self, device_serving):
        """The /reload story: a fresh model with identical geometry
        must be pure executable-cache hits — zero compiles."""
        from predictionio_tpu.models.als import ResidentScorer

        U, V = _factors(seed=2)
        ladder = BucketLadder([1, 2, 4])
        ResidentScorer(U, V).warm_buckets(ladder, ks=(10,))
        U2, V2 = _factors(seed=3)  # new values, same geometry
        stats = ResidentScorer(U2, V2).warm_buckets(ladder, ks=(10,))
        assert stats == {"targets": 3, "compiled": 0, "cached": 3}

    def test_zero_compiles_at_every_batch_after_warmup(
            self, device_serving):
        """Acceptance: once warm, a query batch at ANY size ≤ max_batch
        dispatches a precompiled executable — no jit fallback, no
        executable-cache compile."""
        from predictionio_tpu.models.als import ResidentScorer
        from predictionio_tpu.server import aot

        U, V = _factors(seed=4)
        sc = ResidentScorer(U, V)
        ladder = BucketLadder.geometric(8)
        sc.warm_buckets(ladder, ks=(10,))

        def jit_dispatches():
            return sum(v for k, v in aot._DISPATCHES._values.items()
                       if k[1] == "jit")

        compiles0 = EXECUTABLES.counts().get("compile", 0)
        jit0 = jit_dispatches()
        for b in range(1, ladder.max_batch + 1):
            res = sc.recommend_batch(
                np.arange(b, dtype=np.int32), 10)
            assert len(res) == b
        assert EXECUTABLES.counts().get("compile", 0) == compiles0
        assert jit_dispatches() == jit0

    def test_unwarmed_shape_counts_a_jit_fallback(self, device_serving):
        from predictionio_tpu.models.als import ResidentScorer
        from predictionio_tpu.server import aot

        U, V = _factors(seed=5)
        sc = ResidentScorer(U, V)  # no ladder, nothing warmed

        def jit_dispatches():
            return sum(v for k, v in aot._DISPATCHES._values.items()
                       if k[1] == "jit")

        jit0 = jit_dispatches()
        sc.recommend_batch(np.asarray([1, 2, 3], np.int32), 10)
        assert jit_dispatches() == jit0 + 1


class TestPaddedParity:
    """Padded results must be BITWISE identical to unpadded execution
    for every real row — across the whole ladder, including batch 1 and
    the exact max_batch boundary (satellite 3)."""

    def test_als_parity_across_all_buckets(self, device_serving):
        from predictionio_tpu.models.als import ResidentScorer

        U, V = _factors(seed=6)
        ladder = BucketLadder.geometric(8)
        warm = ResidentScorer(U, V)
        warm.warm_buckets(ladder, ks=(10,))
        plain = ResidentScorer(U, V)  # no ladder → unpadded jit path
        rng = np.random.default_rng(7)
        # every real size 1..max_batch: covers batch 1, in-bucket sizes
        # that get padded, and the max_batch boundary (no padding)
        for b in range(1, ladder.max_batch + 1):
            ids = rng.integers(0, U.shape[0], size=b).astype(np.int32)
            got = warm.recommend_batch(ids, 10)
            want = plain.recommend_batch(ids, 10)
            for (gi, gv), (wi, wv) in zip(got, want):
                np.testing.assert_array_equal(gi, wi)
                np.testing.assert_array_equal(gv, wv)

    def test_als_parity_with_exclusions(self, device_serving):
        from predictionio_tpu.models.als import ResidentScorer

        U, V = _factors(seed=8)
        warm = ResidentScorer(U, V)
        warm.warm_buckets(BucketLadder([1, 4]), ks=(10,))
        plain = ResidentScorer(U, V)
        ids = np.asarray([5, 9], np.int32)   # pads 2 → 4
        excl = [np.asarray([0, 1, 2], np.int32), None]
        got = warm.recommend_batch(ids, 5, excl)
        want = plain.recommend_batch(ids, 5, excl)
        for (gi, gv), (wi, wv) in zip(got, want):
            np.testing.assert_array_equal(gi, wi)
            np.testing.assert_array_equal(gv, wv)

    def test_two_tower_parity_across_buckets(self, device_serving):
        """Two-tower retrieval rides the same resident program; its
        algorithm-level batch_predict must give identical itemScores
        through a padded batch."""
        from predictionio_tpu.templates.twotower.engine import (
            TwoTowerAlgorithm,
            TwoTowerModel,
        )
        from predictionio_tpu.utils.bimap import BiMap

        rng = np.random.default_rng(9)
        n_users, n_items, dim = 120, 2200, 8
        ue = rng.standard_normal((n_users, dim)).astype(np.float32)
        ie = rng.standard_normal((n_items, dim)).astype(np.float32)
        model = TwoTowerModel(
            None, ie, BiMap({str(i): i for i in range(n_users)}),
            BiMap({str(i): i for i in range(n_items)}), None,
            user_embeds=ue)
        algo = TwoTowerAlgorithm(None)
        ladder = BucketLadder([1, 2, 4])
        stats = algo.aot_warm(model, ladder, ks=(10,))
        assert stats["targets"] == 3

        queries = [{"user": str(u), "num": 10} for u in (3, 44, 97)]
        # padded batch (3 real + 1 PAD → bucket 4) vs each query alone
        # at bucket 1 — both warmed shapes
        padded = algo.batch_predict(model, queries + [PAD])
        assert padded[3] is None
        for q, got in zip(queries, padded[:3]):
            [want] = algo.batch_predict(model, [q])
            assert got == want

    def test_host_fallback_unaffected(self, monkeypatch):
        """PIO_ALS_SERVE=host: no scorer, PADs still skipped."""
        monkeypatch.setenv("PIO_ALS_SERVE", "host")
        from predictionio_tpu.models.als import serve_topk_batch

        out = serve_topk_batch(None, {}, {}, [{"user": "1"}, PAD],
                               fallback=lambda q: "fb")
        assert out == ["fb", None]


# -- deploy-time warmup orchestration -----------------------------------------


class _WarmableAlgo:
    def __init__(self, scorer):
        self._scorer = scorer

    def aot_warm(self, model, ladder, ks):
        return self._scorer.warm_buckets(ladder, ks)


class _FakeDeployed:
    def __init__(self, algos_models):
        self.algorithms = [(f"a{i}", a) for i, (a, _) in
                           enumerate(algos_models)]
        self.models = [m for _, m in algos_models]


class TestAOTWarmup:
    def test_background_warmup_reaches_ready(self, device_serving):
        from predictionio_tpu.models.als import ResidentScorer

        U, V = _factors(seed=10)
        sc = ResidentScorer(U, V)
        w = AOTWarmup(BucketLadder([1, 2]), ks=(10,))
        assert w.state == "idle"
        w.start(_FakeDeployed([(_WarmableAlgo(sc), sc)]))
        assert w.wait(60) and w.ready
        prog = w.progress()
        assert prog["state"] == "ready"
        assert prog["compiled"] + prog["cached"] == prog["targets"] == 2

    def test_warmup_failure_is_surfaced_not_raised(self):
        class Boom:
            def aot_warm(self, model, ladder, ks):
                raise RuntimeError("no device")

        w = AOTWarmup(BucketLadder([1]), ks=(10,))
        w.start(_FakeDeployed([(Boom(), None)]))
        assert w.wait(60)
        assert w.state == "failed" and not w.ready
        assert "no device" in w.progress()["error"]

    def test_algorithms_without_hook_warm_instantly(self):
        class Plain:
            pass

        w = AOTWarmup(BucketLadder([1, 2, 4]), ks=(10,))
        w.start(_FakeDeployed([(Plain(), None)]))
        assert w.wait(60) and w.ready
        assert w.progress()["targets"] == 0


# -- MicroBatcher under a bucket ladder ---------------------------------------


class TestMicroBatcherLadder:
    def test_batch_padded_to_bucket_and_sliced(self):
        from predictionio_tpu.server.batching import MicroBatcher

        shapes = []

        def fn(qs):
            shapes.append((len(qs), sum(1 for q in qs if is_pad(q))))
            return [None if is_pad(q) else q * 2 for q in qs]

        async def main():
            mb = MicroBatcher(fn, max_batch=8, max_wait_ms=20.0,
                              ladder=BucketLadder([1, 4, 8]))
            outs = await asyncio.gather(*(mb.submit(i) for i in range(3)))
            mb.stop()
            return outs

        assert run(main()) == [0, 2, 4]
        # every dispatch landed exactly on a bucket, and exactly the
        # 3 real queries flowed through (the rest were PAD fill)
        for padded, _ in shapes:
            assert padded in (1, 4, 8)
        assert sum(padded - pads for padded, pads in shapes) == 3

    def test_exact_bucket_not_padded(self):
        from predictionio_tpu.server.batching import MicroBatcher

        seen = []

        def fn(qs):
            seen.append(list(qs))
            return [q for q in qs]

        async def main():
            mb = MicroBatcher(fn, max_batch=4, max_wait_ms=0.0,
                              ladder=BucketLadder([1, 4]))
            return await mb.submit("x")

        assert run(main()) == "x"
        assert seen == [["x"]]  # bucket 1: no PAD appended

    def test_stop_then_serve_again_with_ladder(self):
        """Satellite 2 regression: stop() under bucket state must leave
        the batcher fully restartable — padding included."""
        from predictionio_tpu.server.batching import MicroBatcher

        calls = []

        def fn(qs):
            calls.append(len(qs))
            return [None if is_pad(q) else q + 1 for q in qs]

        async def main():
            mb = MicroBatcher(fn, max_batch=4, max_wait_ms=0.0,
                              ladder=BucketLadder([2, 4]))
            a = await mb.submit(1)
            mb.stop()
            b = await mb.submit(2)  # restarts worker + executor
            mb.stop()
            return a, b

        assert run(main()) == (2, 3)
        assert calls == [2, 2]  # both singles padded to bucket 2

    def test_stop_fails_undispatched_queries(self):
        from predictionio_tpu.server.batching import MicroBatcher

        async def main():
            mb = MicroBatcher(lambda qs: qs, max_batch=4)
            fut = asyncio.get_running_loop().create_future()
            await mb._queue.put(("orphan", fut, None))
            mb.stop()
            return fut

        fut = None

        async def outer():
            nonlocal fut
            fut = await main()
            with pytest.raises(RuntimeError, match="stopped"):
                fut.result()

        run(outer())

    def test_counters_mirrored_to_prometheus(self):
        from predictionio_tpu.server import batching
        from predictionio_tpu.server.batching import MicroBatcher

        def fn(qs):
            return [q for q in qs]

        sub0 = batching._SUBMITTED._values.get((), 0)
        bat0 = batching._BATCHES._values.get((), 0)

        async def main():
            mb = MicroBatcher(fn, max_batch=4)
            await asyncio.gather(*(mb.submit(i) for i in range(3)))
            mb.stop()
            return mb

        mb = run(main())
        assert mb.submitted == 3
        assert batching._SUBMITTED._values.get((), 0) - sub0 == 3
        assert batching._BATCHES._values.get((), 0) - bat0 == mb.batches

    def test_isolation_still_works_through_padding(self):
        """A poison query fails alone; its padded siblings succeed."""
        from predictionio_tpu.server.batching import MicroBatcher

        def fn(qs):
            out = []
            for q in qs:
                if is_pad(q):
                    out.append(None)
                elif q == "bad":
                    raise ValueError("poison")
                else:
                    out.append(q.upper())
            return out

        async def main():
            mb = MicroBatcher(fn, max_batch=8, max_wait_ms=20.0,
                              ladder=BucketLadder([1, 4, 8]))
            res = await asyncio.gather(
                mb.submit("a"), mb.submit("bad"), mb.submit("c"),
                return_exceptions=True)
            mb.stop()
            return res, mb.isolations

        res, isolations = run(main())
        ok = [r for r in res if isinstance(r, str)]
        bad = [r for r in res if isinstance(r, ValueError)]
        if isolations:  # queries coalesced into one (failing) batch
            assert sorted(ok) == ["A", "C"] and len(bad) == 1
        else:  # scheduling kept them separate; bad failed alone
            assert len(bad) == 1 and sorted(ok) == ["A", "C"]


# -- engine server: /health warmup + compile-free /reload ---------------------


def _fabricate(storage, n_users=200, n_items=2500, rank=8):
    """A synthetic COMPLETED ALS instance, the way pio train would
    persist one (profile_serving.py pattern)."""
    import json as _json

    from predictionio_tpu.core.workflow import frame_models
    from predictionio_tpu.data.event import utcnow
    from predictionio_tpu.storage.meta import EngineInstance
    from predictionio_tpu.templates.recommendation.engine import (
        ALSAlgorithm,
        ALSAlgorithmParams,
        ALSModel,
    )
    from predictionio_tpu.utils.bimap import BiMap

    U, V = _factors(n_users, n_items, rank, seed=11)
    model = ALSModel(U, V, BiMap({str(i): i for i in range(n_users)}),
                     BiMap({str(i): i for i in range(n_items)}))
    algo = ALSAlgorithm(ALSAlgorithmParams(rank=rank))
    blob = algo.save_model(model, None)
    factory = ("predictionio_tpu.templates.recommendation.engine:"
               "engine_factory")
    ei = EngineInstance(
        id="aot-test", status="COMPLETED",
        start_time=utcnow(), end_time=utcnow(),
        engine_factory=factory, engine_variant="", batch="",
        env={}, mesh_conf={},
        data_source_params=_json.dumps({"appName": "AOTApp"}),
        preparator_params="{}",
        algorithms_params=_json.dumps(
            [{"name": "als", "params": {"rank": rank}}]),
        serving_params="{}")
    storage.meta.insert_engine_instance(ei)
    storage.models.put_parts(ei.id, frame_models([blob]))
    return factory


class TestEngineServerAOT:
    def test_health_not_ready_until_warm_then_ok(self, storage,
                                                 device_serving):
        import json as _json

        from predictionio_tpu.server.engine_server import EngineServer

        factory = _fabricate(storage)
        server = EngineServer(engine_factory=factory, storage=storage,
                              batching=True, batch_max=4,
                              aot_buckets="auto")
        assert server._warmup is not None
        # deterministic view of the warming window: a server whose
        # warmup has not finished must answer 503 not-ready
        if not server._warmup.wait(0):
            resp = run(server._health(None))
            body = _json.loads(resp.body)
            if body["warmup"]["state"] in ("idle", "warming"):
                assert resp.status == 503
                assert body["status"] == "not-ready"
        assert server._warmup.wait(120) and server._warmup.ready
        resp = run(server._health(None))
        body = _json.loads(resp.body)
        assert resp.status == 200 and body["status"] == "ok"
        assert body["warmup"]["state"] == "ready"
        assert body["warmup"]["targets"] > 0

    def test_reload_same_geometry_causes_zero_compiles(self, storage,
                                                       device_serving):
        import json as _json

        from predictionio_tpu.server.engine_server import EngineServer

        from predictionio_tpu.server import aot

        factory = _fabricate(storage)
        server = EngineServer(engine_factory=factory, storage=storage,
                              batching=True, batch_max=4,
                              aot_buckets="auto")
        assert server._warmup.wait(120) and server._warmup.ready

        def jit_dispatches():
            return sum(v for k, v in aot._DISPATCHES._values.items()
                       if k[1] == "jit")

        # one asyncio.run: the batcher's queue/worker bind to the loop
        async def main():
            pred = await server._batcher.submit({"user": "3", "num": 5})
            assert pred["itemScores"]
            server._last_good_query = {"user": "3", "num": 5}

            compiles0 = EXECUTABLES.counts().get("compile", 0)
            resp = await server._reload(None)
            assert resp.status == 200
            assert _json.loads(resp.body)["reloadGeneration"] == 1
            # same geometry → the candidate's entire ladder came from
            # the process-wide executable cache: the swap compiled
            # NOTHING
            assert EXECUTABLES.counts().get("compile", 0) == compiles0
            assert server._warmup.ready
            # and the first post-swap query dispatches precompiled
            jit0 = jit_dispatches()
            pred = await server._batcher.submit({"user": "5", "num": 5})
            assert pred["itemScores"]
            assert jit_dispatches() == jit0
            server._batcher.stop()

        run(main())

    def test_explicit_ladder_caps_batch_max(self, storage, device_serving):
        from predictionio_tpu.server.engine_server import EngineServer

        factory = _fabricate(storage)
        server = EngineServer(engine_factory=factory, storage=storage,
                              batching=True, batch_max=64,
                              aot_buckets="1,2")
        assert server._batcher.max_batch == 2
        assert list(server._warmup.ladder) == [1, 2]
        assert server._warmup.wait(120)

    def test_no_aot_flag_means_no_warmup(self, storage):
        from predictionio_tpu.server.engine_server import EngineServer

        factory = _fabricate(storage)
        server = EngineServer(engine_factory=factory, storage=storage)
        assert server._warmup is None
        resp = run(server._health(None))
        assert resp.status == 200


@pytest.mark.slow
class TestFullLadderSweep:
    """Compile sweep across the full default ladder at a production-ish
    shape — minutes of XLA wall time, excluded from tier-1."""

    def test_geometric_64_ladder_compiles_and_serves(self, device_serving):
        from predictionio_tpu.models.als import ResidentScorer
        from predictionio_tpu.server import aot

        U, V = _factors(n_users=2000, n_items=27000, rank=32, seed=12)
        sc = ResidentScorer(U, V)
        ladder = BucketLadder.geometric(64)
        stats = sc.warm_buckets(ladder, ks=(10,))
        assert stats["targets"] == len(ladder)

        def jit_dispatches():
            return sum(v for k, v in aot._DISPATCHES._values.items()
                       if k[1] == "jit")

        jit0 = jit_dispatches()
        rng = np.random.default_rng(13)
        for b in range(1, 65):
            ids = rng.integers(0, 2000, size=b).astype(np.int32)
            res = sc.recommend_batch(ids, 10)
            assert len(res) == b
        assert jit_dispatches() == jit0
