"""Mid-train checkpoint/resume (Orbax) — SURVEY.md §5 recovery model."""

from __future__ import annotations

import os

import numpy as np
import pytest

from predictionio_tpu.utils.checkpoint import TrainCheckpointer


class TestCheckpointer:
    def test_round_trip_and_latest(self, tmp_path):
        state = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
                 "opt": {"mu": np.zeros(3), "count": np.asarray(4)}}
        with TrainCheckpointer(str(tmp_path / "ck")) as ck:
            assert ck.latest_step() is None
            ck.save(1, state)
            state2 = {**state, "w": state["w"] * 2}
            ck.save(2, state2)
            assert ck.latest_step() == 2
            got = ck.restore(template=state)
            np.testing.assert_array_equal(got["w"], state2["w"])
            got1 = ck.restore(step=1, template=state)
            np.testing.assert_array_equal(got1["w"], state["w"])

    def test_keep_policy(self, tmp_path):
        with TrainCheckpointer(str(tmp_path / "ck"), keep=2) as ck:
            for s in (1, 2, 3, 4):
                ck.save(s, {"x": np.asarray([s])})
            assert ck.latest_step() == 4
            with pytest.raises(Exception):
                ck.restore(step=1, template={"x": np.asarray([0])})

    def test_restore_empty_raises(self, tmp_path):
        with TrainCheckpointer(str(tmp_path / "ck")) as ck:
            with pytest.raises(FileNotFoundError):
                ck.restore()


def _train_killed_after(step, train):
    """Run ``train()`` and kill it right after its checkpoint of epoch
    ``step`` is on disk — what a crash between two blocks of epochs
    leaves (a trainer writes no checkpoint after its LAST block: the
    model save follows it at once)."""
    from unittest import mock

    from predictionio_tpu.utils.checkpoint import TrainCheckpointer

    real = TrainCheckpointer.save

    def save(self, s, state):
        real(self, s, state)
        if s == step:
            self.close()
            raise KeyboardInterrupt(f"killed after epoch {step}")

    with mock.patch.object(TrainCheckpointer, "save", save):
        with pytest.raises(KeyboardInterrupt):
            train()



class TestRestoreLatestCompatible:
    """ADVICE r3 (medium): transient restore failures must not wipe the
    checkpoint dir — only confirmed geometry mismatch may."""

    def test_picks_newest_matching(self, tmp_path):
        with TrainCheckpointer(str(tmp_path / "ck")) as ck:
            ck.save(1, {"x": np.asarray([1.0], np.float32)})
            ck.save(2, {"x": np.asarray([2.0], np.float32)})
            state, step = ck.restore_latest_compatible(
                {"x": np.zeros(1, np.float32)})
            assert step == 2
            np.testing.assert_array_equal(state["x"], [2.0])

    def test_all_mismatched_raises_geometry_error(self, tmp_path):
        from predictionio_tpu.utils.checkpoint import CheckpointGeometryError

        with TrainCheckpointer(str(tmp_path / "ck")) as ck:
            ck.save(1, {"x": np.zeros((3, 3), np.float32)})
            ck.save(2, {"x": np.zeros((3, 3), np.float32)})
            with pytest.raises(CheckpointGeometryError):
                ck.restore_latest_compatible({"x": np.zeros(1, np.float32)})

    def test_truncated_newest_falls_back_to_previous(self, tmp_path):
        """A save truncated by the crash being recovered from must fall
        back to the previous good step, not force a retrain."""
        d = str(tmp_path / "ck")
        with TrainCheckpointer(d) as ck:
            ck.save(1, {"x": np.asarray([1.0], np.float32)})
            ck.save(2, {"x": np.asarray([2.0], np.float32)})
        # simulate the torn newest save: truncate every payload file
        # under step 2 (structure intact, bytes gone)
        for root, _dirs, files in os.walk(os.path.join(d, "2")):
            for f in files:
                open(os.path.join(root, f), "wb").close()
        with TrainCheckpointer(d) as ck:
            state, step = ck.restore_latest_compatible(
                {"x": np.zeros(1, np.float32)})
            assert step == 1
            np.testing.assert_array_equal(state["x"], [1.0])

    def test_fallback_prunes_torn_step_so_saves_persist(self, tmp_path):
        """r4 review: after falling back past a torn newest step, the
        torn step dir must be pruned — Orbax save() silently no-ops on
        an existing step dir, so progress at that step would otherwise
        never persist and every resume would lose the same work."""
        d = str(tmp_path / "ck")
        with TrainCheckpointer(d) as ck:
            ck.save(1, {"x": np.asarray([1.0], np.float32)})
            ck.save(2, {"x": np.asarray([2.0], np.float32)})
        for root, _dirs, files in os.walk(os.path.join(d, "2")):
            for f in files:
                open(os.path.join(root, f), "wb").close()
        with TrainCheckpointer(d) as ck:
            state, step = ck.restore_latest_compatible(
                {"x": np.zeros(1, np.float32)})
            assert step == 1
            # the resumed run re-reaches step 2: the save must LAND
            ck.save(2, {"x": np.asarray([22.0], np.float32)})
        with TrainCheckpointer(d) as ck:
            state, step = ck.restore_latest_compatible(
                {"x": np.zeros(1, np.float32)})
            assert step == 2
            np.testing.assert_array_equal(state["x"], [22.0])

    def test_transiently_unreadable_newer_step_not_pruned(self, tmp_path,
                                                          monkeypatch):
        """r4 review: a newer step skipped on a TRANSIENT metadata
        error must survive the fallback — deleting it would destroy a
        valid checkpoint (only proven-torn/stale steps are pruned)."""
        import orbax.checkpoint as ocp

        d = str(tmp_path / "ck")
        with TrainCheckpointer(d) as ck:
            ck.save(1, {"x": np.asarray([1.0], np.float32)})
            ck.save(2, {"x": np.asarray([2.0], np.float32)})

        orig = ocp.StandardCheckpointer.metadata

        def flaky(self, path, *a, **k):
            if "/2/" in str(path) or str(path).endswith("2/default"):
                raise OSError("NFS hiccup")
            return orig(self, path, *a, **k)

        monkeypatch.setattr(ocp.StandardCheckpointer, "metadata", flaky)
        with TrainCheckpointer(d) as ck:
            state, step = ck.restore_latest_compatible(
                {"x": np.zeros(1, np.float32)})
            assert step == 1  # fell back past the flaky step
        monkeypatch.undo()
        # step 2 survived: the next (healthy) resume restores it
        with TrainCheckpointer(d) as ck:
            state, step = ck.restore_latest_compatible(
                {"x": np.zeros(1, np.float32)})
            assert step == 2
            np.testing.assert_array_equal(state["x"], [2.0])

    def test_permuted_shapes_rejected_positionally(self, tmp_path):
        """r4 review: a checkpoint whose leaf shapes are a PERMUTATION
        of the template's (e.g. swapped tower embeddings) must raise
        CheckpointGeometryError, not restore swapped state."""
        from predictionio_tpu.utils.checkpoint import CheckpointGeometryError

        d = str(tmp_path / "ck")
        with TrainCheckpointer(d) as ck:
            ck.save(1, {"a": np.zeros((128, 4), np.float32),
                        "b": np.zeros((64, 4), np.float32)})
        with TrainCheckpointer(d) as ck:
            with pytest.raises(CheckpointGeometryError):
                ck.restore_latest_compatible(
                    {"a": np.zeros((64, 4), np.float32),
                     "b": np.zeros((128, 4), np.float32)})

    def test_permuted_newer_step_pruned_after_fallback(self, tmp_path):
        """r4 review: a newer step that restores cleanly but with
        PERMUTED shapes is confirmed stale — after falling back it must
        be pruned so the resumed run's save at that step lands."""
        d = str(tmp_path / "ck")
        good = {"a": np.ones((4, 2), np.float32),
                "b": np.ones((8, 2), np.float32)}
        swapped = {"a": np.ones((8, 2), np.float32),
                   "b": np.ones((4, 2), np.float32)}
        with TrainCheckpointer(d) as ck:
            ck.save(1, good)
            ck.save(2, swapped)  # stale geometry, same shape multiset
        with TrainCheckpointer(d) as ck:
            state, step = ck.restore_latest_compatible(good)
            assert step == 1
            ck.save(2, {"a": good["a"] * 2, "b": good["b"]})  # must land
        with TrainCheckpointer(d) as ck:
            state, step = ck.restore_latest_compatible(good)
            assert step == 2
            np.testing.assert_array_equal(state["a"], good["a"] * 2)

    def test_transient_error_propagates_and_preserves_dir(self, tmp_path,
                                                          monkeypatch):
        """An IO hiccup on EVERY read must surface the error and leave
        the checkpoints on disk (no silent full retrain)."""
        d = str(tmp_path / "ck")
        with TrainCheckpointer(d) as ck:
            ck.save(1, {"x": np.asarray([1.0], np.float32)})
        with TrainCheckpointer(d) as ck:
            monkeypatch.setattr(
                TrainCheckpointer, "restore",
                lambda self, *a, **k: (_ for _ in ()).throw(
                    OSError("disk glitch")))
            with pytest.raises(OSError, match="disk glitch"):
                ck.restore_latest_compatible({"x": np.zeros(1, np.float32)})
        monkeypatch.undo()
        # the valid checkpoint survived and restores on the next attempt
        with TrainCheckpointer(d) as ck:
            state, step = ck.restore_latest_compatible(
                {"x": np.zeros(1, np.float32)})
            assert step == 1

    def test_seq_rec_transient_error_does_not_wipe(self, tmp_path,
                                                   monkeypatch):
        """End-to-end: a transient restore failure inside seq_rec_train
        surfaces instead of wiping + retraining (ADVICE r3 medium)."""
        import predictionio_tpu.utils.checkpoint as ckpt_mod
        from predictionio_tpu.models.seq_rec import (
            SeqRecParams,
            seq_rec_train,
        )

        rng = np.random.default_rng(2)
        seqs = [list(rng.integers(1, 21, rng.integers(3, 12)))
                for _ in range(30)]
        base = dict(hidden=16, num_blocks=1, num_heads=2, seq_len=8,
                    batch_size=16, lr=1e-3, seed=4)
        ckdir = str(tmp_path / "ck")
        _train_killed_after(2, lambda: seq_rec_train(seqs, 20, SeqRecParams(
            **base, epochs=4, checkpoint_dir=ckdir)))

        monkeypatch.setattr(
            ckpt_mod.TrainCheckpointer, "restore",
            lambda self, *a, **k: (_ for _ in ()).throw(
                OSError("disk glitch")))
        with pytest.raises(OSError, match="disk glitch"):
            seq_rec_train(seqs, 20, SeqRecParams(
                **base, epochs=4, checkpoint_dir=ckdir))
        monkeypatch.undo()
        # checkpoints intact: the retry resumes from epoch 2
        _, losses = seq_rec_train(seqs, 20, SeqRecParams(
            **base, epochs=4, checkpoint_dir=ckdir))
        assert len(losses) == 2


class TestTwoTowerResume:
    def _pairs(self, n=256, n_users=40, n_items=30, seed=0):
        rng = np.random.default_rng(seed)
        return (rng.integers(0, n_users, n).astype(np.int32),
                rng.integers(0, n_items, n).astype(np.int32),
                n_users, n_items)

    def test_resume_matches_straight_run(self, tmp_path):
        from predictionio_tpu.models.two_tower import (
            TwoTowerParams,
            two_tower_train,
        )

        u, i, nu, ni = self._pairs()
        base = dict(embed_dim=16, hidden=[32], out_dim=16, batch_size=64,
                    learning_rate=0.01, seed=3)

        straight = two_tower_train(
            u, i, nu, ni, TwoTowerParams(**base, epochs=4))

        ckdir = str(tmp_path / "ck")
        # "crash" after 2 epochs, then restart asking for 4
        two_tower_train(u, i, nu, ni, TwoTowerParams(
            **base, epochs=2, checkpoint_dir=ckdir))
        resumed = two_tower_train(u, i, nu, ni, TwoTowerParams(
            **base, epochs=4, checkpoint_dir=ckdir))

        for a, b in zip(__import__("jax").tree.leaves(straight),
                        __import__("jax").tree.leaves(resumed)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


    def test_resume_honors_new_learning_rate(self, tmp_path):
        """r4: lr lives in the optimizer state now — a restart that
        changes learning_rate must train at the NEW rate, not the
        checkpointed one. lr=0 on resume ⇒ params must not move."""
        from predictionio_tpu.models.two_tower import (
            TwoTowerParams,
            two_tower_train,
        )

        u, i, nu, ni = self._pairs()
        base = dict(embed_dim=16, hidden=[32], out_dim=16, batch_size=64,
                    seed=3)
        ckdir = str(tmp_path / "ck")
        frozen = two_tower_train(u, i, nu, ni, TwoTowerParams(
            **base, epochs=2, learning_rate=0.01, checkpoint_dir=ckdir))
        resumed = two_tower_train(u, i, nu, ni, TwoTowerParams(
            **base, epochs=4, learning_rate=0.0, checkpoint_dir=ckdir))
        import jax

        for a, b in zip(jax.tree.leaves(frozen), jax.tree.leaves(resumed)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)


class TestSeqRecResume:
    def _seqs(self, n_users=30, n_items=20, seed=2):
        rng = np.random.default_rng(seed)
        return [list(rng.integers(1, n_items + 1,
                                  rng.integers(3, 12)))
                for _ in range(n_users)], n_items

    def test_resume_matches_straight_run(self, tmp_path):
        from predictionio_tpu.models.seq_rec import (
            SeqRecParams,
            seq_rec_train,
        )

        seqs, n_items = self._seqs()
        base = dict(hidden=16, num_blocks=1, num_heads=2, seq_len=8,
                    batch_size=16, lr=1e-3, seed=4)

        straight, _ = seq_rec_train(seqs, n_items,
                                    SeqRecParams(**base, epochs=4))

        ckdir = str(tmp_path / "ck")
        # crash after 2 epochs of 4, then restart
        _train_killed_after(2, lambda: seq_rec_train(
            seqs, n_items, SeqRecParams(**base, epochs=4,
                                        checkpoint_dir=ckdir,
                                        checkpoint_every=1)))
        resumed, losses = seq_rec_train(seqs, n_items, SeqRecParams(
            **base, epochs=4, checkpoint_dir=ckdir, checkpoint_every=1))

        assert len(losses) == 2  # only the remaining epochs ran
        import jax

        for a, b in zip(jax.tree.leaves(straight), jax.tree.leaves(resumed)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_stale_checkpoint_wiped_so_resume_recovers(self, tmp_path):
        """A checkpoint from an incompatible geometry must not shadow
        the fresh run's saves: after one run past the stale dir,
        resume must work from the NEW checkpoints."""
        from predictionio_tpu.models.seq_rec import (
            SeqRecParams,
            seq_rec_train,
        )

        seqs, n_items = self._seqs()
        ckdir = str(tmp_path / "ck")
        # stale: bigger geometry, killed after saving steps 1..3
        _train_killed_after(3, lambda: seq_rec_train(
            seqs, n_items, SeqRecParams(
                hidden=32, num_blocks=1, num_heads=2, seq_len=8,
                batch_size=16, epochs=5, seed=4, checkpoint_dir=ckdir)))
        # new geometry: restore fails → dir wiped → the fresh run saves
        # 1..2 before it is killed too
        base = dict(hidden=16, num_blocks=1, num_heads=2, seq_len=8,
                    batch_size=16, lr=1e-3, seed=4)
        with pytest.warns(RuntimeWarning, match="stale"):
            _train_killed_after(2, lambda: seq_rec_train(
                seqs, n_items, SeqRecParams(**base, epochs=4,
                                            checkpoint_dir=ckdir)))
        # resume must pick up the NEW step-2 checkpoint, not the stale
        # step-3 one (which would silently retrain from scratch)
        resumed, losses = seq_rec_train(seqs, n_items, SeqRecParams(
            **base, epochs=4, checkpoint_dir=ckdir))
        assert len(losses) == 2  # epochs 3..4 only
        straight, _ = seq_rec_train(seqs, n_items,
                                    SeqRecParams(**base, epochs=4))
        import jax

        for a, b in zip(jax.tree.leaves(straight), jax.tree.leaves(resumed)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_resume_honors_new_learning_rate(self, tmp_path):
        """r4: lr rides in the optimizer state — a restart with lr=0
        must not move the checkpointed params (mirrors the two_tower
        test; this is the seq_rec side of the same code path)."""
        from predictionio_tpu.models.seq_rec import (
            SeqRecParams,
            seq_rec_train,
        )

        seqs, n_items = self._seqs()
        base = dict(hidden=16, num_blocks=1, num_heads=2, seq_len=8,
                    batch_size=16, seed=4)
        ckdir = str(tmp_path / "ck")
        frozen, _ = seq_rec_train(seqs, n_items, SeqRecParams(
            **base, lr=1e-3, epochs=2))
        _train_killed_after(2, lambda: seq_rec_train(
            seqs, n_items, SeqRecParams(**base, lr=1e-3, epochs=4,
                                        checkpoint_dir=ckdir)))
        resumed, _ = seq_rec_train(seqs, n_items, SeqRecParams(
            **base, lr=0.0, epochs=4, checkpoint_dir=ckdir))
        import jax

        for a, b in zip(jax.tree.leaves(frozen), jax.tree.leaves(resumed)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)

    def test_completed_run_writes_no_checkpoint_after_its_last_block(
            self, tmp_path):
        """Checkpoints lie BETWEEN blocks of epochs: the model save
        follows the last block at once and the workflow deletes the
        directory on completion, so a write there would be a fetch of
        params + optimizer state for nothing. A rerun on the kept
        directory trains the last block again, to the same model."""
        from predictionio_tpu.models.seq_rec import (
            SeqRecParams,
            seq_rec_train,
        )
        from predictionio_tpu.utils.checkpoint import TrainCheckpointer

        seqs, n_items = self._seqs()
        base = dict(hidden=16, num_blocks=1, num_heads=2, seq_len=8,
                    batch_size=16, lr=1e-3, seed=4)
        ckdir = str(tmp_path / "ck")
        done, _ = seq_rec_train(seqs, n_items, SeqRecParams(
            **base, epochs=3, checkpoint_dir=ckdir))
        assert TrainCheckpointer(ckdir).latest_step() == 2
        again, losses = seq_rec_train(seqs, n_items, SeqRecParams(
            **base, epochs=3, checkpoint_dir=ckdir))
        assert losses.size == 1  # the last block only
        import jax

        for a, b in zip(jax.tree.leaves(done), jax.tree.leaves(again)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


class TestALSResume:
    """Block-wise ALS checkpointing: interrupted + resumed == straight."""

    def _coo(self):
        from predictionio_tpu.models.als import RatingsCOO

        rng = np.random.default_rng(5)
        n_u, n_i, nnz = 40, 25, 400
        return RatingsCOO(rng.integers(0, n_u, nnz).astype(np.int32),
                          rng.integers(0, n_i, nnz).astype(np.int32),
                          rng.uniform(1, 5, nnz).astype(np.float32),
                          n_u, n_i)

    def test_resume_matches_straight_run(self, tmp_path):
        from predictionio_tpu.models.als import (ALSParams, als_prepare,
                                                 als_train_prepared)

        coo = self._coo()
        prep = als_prepare(coo)
        p8 = ALSParams(rank=4, iterations=8, reg=0.1, seed=2)
        U_ref, V_ref = als_train_prepared(prep, p8)

        # "crash" after 4 of 8 iterations (two 2-iteration blocks saved)
        with TrainCheckpointer(str(tmp_path / "als")) as ck:
            als_train_prepared(prep, ALSParams(rank=4, iterations=4,
                                               reg=0.1, seed=2),
                               checkpointer=ck, checkpoint_every=2)
            assert ck.latest_step() == 4
        # restart: restores step 4, runs the remaining 4
        with TrainCheckpointer(str(tmp_path / "als")) as ck:
            U, V = als_train_prepared(prep, p8, checkpointer=ck,
                                      checkpoint_every=2)
            assert ck.latest_step() == 8
        np.testing.assert_allclose(U, U_ref, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(V, V_ref, rtol=2e-4, atol=2e-5)

    def test_resume_after_final_checkpoint_recovers_u(self, tmp_path):
        # death AFTER the last save but BEFORE persistence: the resume
        # run must not re-train, just recover U from the stored V
        from predictionio_tpu.models.als import (ALSParams, als_prepare,
                                                 als_train_prepared)

        coo = self._coo()
        prep = als_prepare(coo)
        p = ALSParams(rank=4, iterations=4, reg=0.1, seed=2)
        with TrainCheckpointer(str(tmp_path / "als")) as ck:
            U_ref, V_ref = als_train_prepared(prep, p, checkpointer=ck,
                                              checkpoint_every=2)
        with TrainCheckpointer(str(tmp_path / "als")) as ck:
            U, V = als_train_prepared(prep, p, checkpointer=ck,
                                      checkpoint_every=2)
        np.testing.assert_allclose(V, V_ref, rtol=1e-6)
        np.testing.assert_allclose(U, U_ref, rtol=2e-4, atol=2e-5)

    def test_stale_checkpoint_falls_back_to_fresh(self, tmp_path):
        from predictionio_tpu.models.als import (ALSParams, als_prepare,
                                                 als_train_prepared)

        coo = self._coo()
        prep = als_prepare(coo)
        with TrainCheckpointer(str(tmp_path / "als")) as ck:
            ck.save(3, {"V": np.zeros((7, 9), np.float32)})  # wrong shape
        p = ALSParams(rank=4, iterations=3, reg=0.1, seed=2)
        U_ref, V_ref = als_train_prepared(prep, p)
        with TrainCheckpointer(str(tmp_path / "als")) as ck:
            U, V = als_train_prepared(prep, p, checkpointer=ck)
        np.testing.assert_allclose(U, U_ref, rtol=1e-6)


class TestShardedALSResume:
    """VERDICT r4 #2: the MULTI-CHIP trainer — exactly the deployment
    whose failure unit is the whole slice — must checkpoint mid-train.
    The fused iteration scan splits at block boundaries; a killed run
    resumes from the newest block with iterate parity."""

    def _coo(self):
        from predictionio_tpu.models.als import RatingsCOO

        rng = np.random.default_rng(11)
        n_u, n_i, nnz = 48, 30, 500
        return RatingsCOO(rng.integers(0, n_u, nnz).astype(np.int32),
                          rng.integers(0, n_i, nnz).astype(np.int32),
                          rng.uniform(1, 5, nnz).astype(np.float32),
                          n_u, n_i)

    def test_resume_matches_straight_run(self, tmp_path, cpu_mesh):
        from predictionio_tpu.models.als import ALSParams
        from predictionio_tpu.models.als_sharded import (
            als_prepare_sharded, als_train_sharded_prepared)

        coo = self._coo()
        n_dev = int(np.prod(cpu_mesh.devices.shape))
        prep = als_prepare_sharded(coo, n_dev)
        p8 = ALSParams(rank=4, iterations=8, reg=0.1, seed=2)
        U_ref, V_ref = als_train_sharded_prepared(prep, p8, cpu_mesh)

        # "crash" after 4 of 8 iterations (two 2-iteration blocks saved)
        with TrainCheckpointer(str(tmp_path / "als")) as ck:
            als_train_sharded_prepared(
                prep, ALSParams(rank=4, iterations=4, reg=0.1, seed=2),
                cpu_mesh, checkpointer=ck, checkpoint_every=2)
            assert ck.latest_step() == 4
        # restart: restores step 4, runs the remaining 4
        with TrainCheckpointer(str(tmp_path / "als")) as ck:
            U, V = als_train_sharded_prepared(
                prep, p8, cpu_mesh, checkpointer=ck, checkpoint_every=2)
            assert ck.latest_step() == 8
        np.testing.assert_allclose(U, U_ref, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(V, V_ref, rtol=2e-4, atol=2e-5)

    def test_resume_after_final_checkpoint_no_retrain(self, tmp_path,
                                                     cpu_mesh,
                                                     monkeypatch):
        # death AFTER the last save but BEFORE persistence: the resume
        # must restore, not re-run any training block
        import predictionio_tpu.models.als_sharded as sh
        from predictionio_tpu.models.als import ALSParams

        coo = self._coo()
        n_dev = int(np.prod(cpu_mesh.devices.shape))
        prep = sh.als_prepare_sharded(coo, n_dev)
        p = ALSParams(rank=4, iterations=4, reg=0.1, seed=2)
        with TrainCheckpointer(str(tmp_path / "als")) as ck:
            U_ref, V_ref = sh.als_train_sharded_prepared(
                prep, p, cpu_mesh, checkpointer=ck, checkpoint_every=2)

        calls = {"n": 0}
        orig = sh._compiled_sharded

        def counting(*a, **k):
            calls["n"] += 1
            return orig(*a, **k)

        monkeypatch.setattr(sh, "_compiled_sharded", counting)
        with TrainCheckpointer(str(tmp_path / "als")) as ck:
            U, V = sh.als_train_sharded_prepared(
                prep, p, cpu_mesh, checkpointer=ck, checkpoint_every=2)
        assert calls["n"] == 0, "fully-checkpointed run must not retrain"
        np.testing.assert_allclose(U, U_ref, rtol=1e-6)
        np.testing.assert_allclose(V, V_ref, rtol=1e-6)

    def test_stale_layout_falls_back_to_fresh(self, tmp_path, cpu_mesh):
        from predictionio_tpu.models.als import ALSParams
        from predictionio_tpu.models.als_sharded import (
            als_prepare_sharded, als_train_sharded_prepared)

        coo = self._coo()
        n_dev = int(np.prod(cpu_mesh.devices.shape))
        prep = als_prepare_sharded(coo, n_dev)
        with TrainCheckpointer(str(tmp_path / "als")) as ck:
            ck.save(3, {"U": np.zeros((5, 3), np.float32),
                        "V": np.zeros((7, 9), np.float32)})  # wrong layout
        p = ALSParams(rank=4, iterations=3, reg=0.1, seed=2)
        U_ref, _ = als_train_sharded_prepared(prep, p, cpu_mesh)
        with TrainCheckpointer(str(tmp_path / "als")) as ck:
            with pytest.warns(RuntimeWarning, match="stale"):
                U, _ = als_train_sharded_prepared(
                    prep, p, cpu_mesh, checkpointer=ck, checkpoint_every=2)
        np.testing.assert_allclose(U, U_ref, rtol=1e-6)


class TestWorkflowResume:
    """run_train --resume: the kill-and-resume contract end to end."""

    def _variant(self):
        from tests.test_workflow import FACTORY

        return {
            "id": "ckpt",
            "engineFactory": FACTORY,
            "datasource": {"params": {"appName": "TestApp"}},
            "algorithms": [{"name": "als",
                            "params": {"rank": 4, "numIterations": 6,
                                       "lambda": 0.05,
                                       "checkpointEvery": 2}}],
        }

    def test_kill_and_resume(self, storage, tmp_path, monkeypatch):
        import predictionio_tpu.utils.checkpoint as ckpt_mod
        from predictionio_tpu.core.workflow import prepare_deploy, run_train
        from tests.test_workflow import FACTORY, seed_ratings

        storage.config.home = str(tmp_path)  # checkpoints under tmp
        seed_ratings(storage)
        variant = self._variant()

        # clean reference run
        run_train(FACTORY, variant=variant, storage=storage, use_mesh=False)
        ref = prepare_deploy(engine_factory=FACTORY,
                             storage=storage).query({"user": "0", "num": 5})

        # interrupted run: die right after the step-4 checkpoint lands
        orig_save = ckpt_mod.TrainCheckpointer.save
        saves = {"n": 0}

        def flaky_save(self, step, state):
            orig_save(self, step, state)
            saves["n"] += 1
            if saves["n"] == 2:
                raise RuntimeError("simulated preemption")

        monkeypatch.setattr(ckpt_mod.TrainCheckpointer, "save", flaky_save)
        with pytest.raises(RuntimeError):
            run_train(FACTORY, variant=variant, storage=storage,
                      use_mesh=False)
        assert storage.meta.list_engine_instances()[0].status == "FAILED"

        # resume: only the remaining block runs (one more save, step 6)
        saves2 = {"n": 0}

        def counting_save(self, step, state):
            orig_save(self, step, state)
            saves2["n"] += 1

        monkeypatch.setattr(ckpt_mod.TrainCheckpointer, "save", counting_save)
        run_train(FACTORY, variant=variant, storage=storage, use_mesh=False,
                  resume=True)
        assert saves2["n"] == 1, "resume must continue, not retrain"

        res = prepare_deploy(engine_factory=FACTORY,
                             storage=storage).query({"user": "0", "num": 5})
        assert [s["item"] for s in res["itemScores"]] == \
            [s["item"] for s in ref["itemScores"]]
        np.testing.assert_allclose(
            [s["score"] for s in res["itemScores"]],
            [s["score"] for s in ref["itemScores"]], rtol=2e-4)

    def test_completed_run_clears_checkpoints(self, storage, tmp_path):
        import os

        from predictionio_tpu.core.workflow import _ckpt_root, run_train
        from tests.test_workflow import FACTORY, seed_ratings

        storage.config.home = str(tmp_path)
        seed_ratings(storage)
        run_train(FACTORY, variant=self._variant(), storage=storage,
                  use_mesh=False)
        assert not os.path.exists(_ckpt_root(storage, FACTORY, "ckpt"))
