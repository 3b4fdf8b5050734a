"""The numpy references against hand cases, and the checks that decide
``correct`` shown to pass on the program and to FAIL on a train that
drops one ALS iteration."""

import json
import os

import numpy as np
import pytest

import checks
from reference.als_numpy import (mean_percentile_rank, numpy_als, rmse)

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_explicit_half_step_by_hand():
    # user 0 rated items 0 and 1; user 1 rated item 1
    users, items = np.array([0, 0, 1]), np.array([0, 1, 1])
    r = np.array([4.0, 2.0, 5.0], np.float32)
    V0 = np.array([[1.0, 0.0], [0.5, 2.0]], np.float32)
    U, V = numpy_als(users, items, r, 2, 2, V0, 1, reg=0.1)
    A0 = V0.T.astype(np.float64) @ V0 + 0.1 * 2 * np.eye(2)
    want0 = np.linalg.solve(A0, V0.T.astype(np.float64) @ r[:2])
    v1 = V0[1].astype(np.float64)
    want1 = np.linalg.solve(np.outer(v1, v1) + 0.1 * np.eye(2), 5.0 * v1)
    assert np.allclose(U, [want0, want1], atol=1e-5)
    # and the item half from that U: item 0 has the one rating of user 0
    u0 = U[0].astype(np.float64)
    assert np.allclose(V[0], np.linalg.solve(
        np.outer(u0, u0) + 0.1 * np.eye(2), 4.0 * u0), atol=1e-4)


def test_implicit_half_step_by_hand():
    users, items = np.array([0, 1]), np.array([0, 0])
    plays = np.array([3.0, 1.0], np.float32)
    V0 = np.array([[1.0, 1.0], [2.0, 0.0]], np.float32)
    U, _ = numpy_als(users, items, plays, 2, 2, V0, 1, reg=0.5,
                     implicit=True, alpha=2.0)
    G = V0.T.astype(np.float64) @ V0
    y = V0[0].astype(np.float64)
    for u, r in ((0, 3.0), (1, 1.0)):
        A = G + 2.0 * r * np.outer(y, y) + 0.5 * np.eye(2)
        assert np.allclose(U[u], np.linalg.solve(A, (1 + 2.0 * r) * y),
                           atol=1e-5)


def test_rmse_and_percentile_rank_by_hand():
    U = np.array([[1.0, 0.0]], np.float32)
    V = np.array([[3.0, 0.0], [2.0, 0.0], [1.0, 0.0], [0.0, 0.0]],
                 np.float32)
    assert rmse(U, V, np.array([0, 0]), np.array([0, 1]),
                np.array([3.0, 4.0], np.float32)) == pytest.approx(2 ** 0.5)
    # item 0 is top (0 of 4 above it), item 2 has two above it
    assert mean_percentile_rank(U, V, np.array([0, 0]),
                                np.array([0, 2])) == pytest.approx(25.0)


@pytest.mark.parametrize("name", ["als-ml20m-r64", "ials-lastfm360k-r64"])
def test_reference_check_passes_and_catches_a_dropped_iteration(name):
    import dataclasses

    from predictionio_tpu.models.als import als_train

    c = config(name)
    good = checks.Verdict()
    out = checks.check_reference(good, c, seed=11)
    assert good.ok and out["pred_rel_rms"] < c["reference"][
        "pred_rel_rms_max"] / 10

    def one_short(coo, params):
        return als_train(coo, dataclasses.replace(
            params, iterations=params.iterations - 1))

    bad = checks.Verdict()
    checks.check_reference(bad, c, seed=11, train=one_short)
    assert not bad.ok


def test_heldout_check_fails_on_a_model_that_learned_nothing():
    import datagen

    c = config("als-ml20m-r64")
    d = datagen.Interactions(c["sample"], c["values"], 0.05, 2)
    rng = np.random.default_rng(0)
    U = rng.standard_normal((d.n_users, 8)).astype(np.float32)
    V = rng.standard_normal((d.n_items, 8)).astype(np.float32)
    v = checks.Verdict()
    checks.check_heldout(v, c, U, V, d.held_users, d.held_items,
                         d.held_values, 3.5)
    assert not v.ok
    # while the planted model itself passes
    v = checks.Verdict()
    P = np.concatenate([d.P * c["values"]["signal_std"],
                        np.full((d.n_users, 1), 3.5, np.float32)], axis=1)
    Q = np.concatenate([d.Q, np.ones((d.n_items, 1), np.float32)], axis=1)
    checks.check_heldout(v, c, P, Q, d.held_users, d.held_items,
                         d.held_values, 3.5)
    assert v.ok
