"""The comparisons that decide ``correct`` in the train cells."""

from __future__ import annotations

import time

import numpy as np

from harness import say


#: every check this process made, in order, each with the number it
#: compared beside its limit: ``run.py`` prints them as the run's last
#: lines on stderr and puts them last in the result's line
RECORD: list = []


class Verdict:
    """Collects the checks of one run; all must hold."""

    def __init__(self) -> None:
        self.ok = True

    def check(self, cond: bool, what: str) -> bool:
        told = ("check ok: " if cond else "check FAILED: ") + what
        say(told)
        RECORD.append(told)
        self.ok = self.ok and bool(cond)
        return bool(cond)


def dense_ids(bimap, prefix: str, n: int) -> np.ndarray:
    """The generator's integer ids → the model's dense indices (the
    event store numbers ids in the order it first sees them); -1 where
    the model does not know the id."""
    table = np.full(n, -1, np.int64)
    for key, idx in bimap.to_dict().items():
        if key[0] != prefix:
            raise ValueError(f"id {key!r} is not one of the generator's")
        table[int(key[1:])] = idx
    return table


def check_heldout(verdict: Verdict, config: dict, U, V, users, items,
                  values, train_mean: float) -> dict:
    """The trained model on pairs it never saw, against the threshold
    the configuration file gives with its reason."""
    from reference.als_numpy import mean_percentile_rank, rmse

    out = {}
    finite = bool(np.isfinite(U).all() and np.isfinite(V).all())
    verdict.check(finite, "the factors are finite")
    if not finite:
        return out
    bounds = config["correct"]
    if config["implicit"]:
        n = min(len(users), int(bounds["heldout_pairs_ranked"]))
        out["heldout_mpr"] = mean_percentile_rank(U, V, users[:n], items[:n])
        verdict.check(
            out["heldout_mpr"] <= bounds["heldout_mpr_max"],
            f"mean percentile rank of {n:,} held-out pairs "
            f"{out['heldout_mpr']:.2f} <= {bounds['heldout_mpr_max']} "
            "(50 = random)")
    else:
        out["heldout_rmse"] = rmse(U, V, users, items, values)
        out["mean_rmse"] = float(np.sqrt(np.mean(
            (values - train_mean) ** 2)))
        verdict.check(
            out["heldout_rmse"] <= bounds["heldout_rmse_max"],
            f"RMSE on {len(users):,} held-out pairs "
            f"{out['heldout_rmse']:.4f} <= {bounds['heldout_rmse_max']} "
            f"(predicting the mean: {out['mean_rmse']:.4f})")
    return out


def check_reference(verdict: Verdict, config: dict, seed: int,
                    train=None) -> dict:
    """The program's ALS (``models.als.als_train``, the call every
    template trains through) against the float32 numpy ALS of the same
    equations, on the configuration's seeded ``sample``, same rank,
    iterations, λ and α. ``train(coo, params) -> (U, V)`` replaces the
    program's call in the tests that show the check can fail."""
    from datagen import Interactions
    from predictionio_tpu.models.als import (ALSParams, RatingsCOO,
                                             als_train, init_factors)
    from reference.als_numpy import numpy_als, rmse

    sample = Interactions(config["sample"], config["values"], 0.01, seed)
    params = ALSParams(rank=config["rank"], iterations=config["iterations"],
                       reg=config["lambda"], implicit=config["implicit"],
                       alpha=config["alpha"], seed=seed)
    coo = RatingsCOO(sample.users, sample.items, sample.values,
                     sample.n_users, sample.n_items)
    t0 = time.perf_counter()
    U, V = (train or als_train)(coo, params)
    t_dev = time.perf_counter() - t0
    t0 = time.perf_counter()
    Ur, Vr = numpy_als(sample.users, sample.items, sample.values,
                       sample.n_users, sample.n_items,
                       init_factors(sample.n_items, config["rank"], seed),
                       config["iterations"], config["lambda"],
                       implicit=config["implicit"], alpha=config["alpha"])
    t_ref = time.perf_counter() - t0
    U, V = np.asarray(U), np.asarray(V)
    finite = bool(np.isfinite(U).all() and np.isfinite(V).all())
    verdict.check(finite, "reference sample: the factors are finite")
    if not finite:
        return {}
    tol = config["reference"]
    p_dev = np.einsum("nk,nk->n", U[sample.users], V[sample.items])
    p_ref = np.einsum("nk,nk->n", Ur[sample.users], Vr[sample.items])
    scale = float(np.sqrt(np.mean(p_ref ** 2)))
    d_pred = float(np.sqrt(np.mean((p_dev - p_ref) ** 2))) / scale
    out = {"pred_rel_rms": d_pred, "device_s": t_dev, "numpy_s": t_ref}
    say(f"reference: sample {sample.n_users}x{sample.n_items}, "
        f"{sample.nnz:,} interactions, rank {config['rank']}, "
        f"{config['iterations']} iterations: rms(program − numpy) over "
        f"rms(numpy) of the predictions on the sample's pairs = "
        f"{d_pred:.2e} (program {t_dev:.1f} s incl. compile, numpy "
        f"{t_ref:.1f} s)")
    verdict.check(d_pred <= tol["pred_rel_rms_max"],
                  f"relative rms prediction difference <= "
                  f"{tol['pred_rel_rms_max']}")
    if not config["implicit"]:
        r_dev = rmse(U, V, sample.users, sample.items, sample.values)
        r_ref = rmse(Ur, Vr, sample.users, sample.items, sample.values)
        out["rmse_diff"] = abs(r_dev - r_ref)
        verdict.check(out["rmse_diff"] <= tol["rmse_diff_max"],
                      f"|RMSE program {r_dev:.5f} − RMSE numpy "
                      f"{r_ref:.5f}| <= {tol['rmse_diff_max']}")
    return out
