"""Generator statistics against the configuration's targets.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``
(the full-size cases take about half a minute together).
"""

import json
import os

import numpy as np
import pytest

import datagen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def quantile_of(table, q):
    return next(d for tq, d in table if tq == q)


@pytest.mark.parametrize("name", ["als-ml20m-r64", "ials-lastfm360k-r64"])
def test_degree_sequences_match_the_source(name):
    c = config(name)
    for n, table in ((c["n_users"], c["user_degree_quantiles"]),
                     (c["n_items"], c["item_degree_quantiles"])):
        d = datagen.degree_sequence(n, c["n_interactions"], table)
        assert d.sum() == c["n_interactions"]
        # (the cut configuration thins every artist's listeners, so its
        # heaviest artist stays under the published maximum)
        assert d.min() == table[0][1]
        assert 0.7 * table[-1][1] <= d.max() <= table[-1][1]
        # the scaling that fits the total moves the median by little
        median = quantile_of(table, 0.5)
        assert abs(np.median(d) - median) <= max(1, 0.06 * median)


@pytest.fixture(scope="module", params=["als-ml20m-r64",
                                        "ials-lastfm360k-r64"])
def full(request):
    c = config(request.param)
    return c, [datagen.Interactions(c, c["values"], c["heldout_share"], s)
               for s in (1, 2)]


def test_full_size_counts_degrees_and_no_repeated_pair(full):
    c, (a, _) = full
    assert a.nnz == c["n_interactions"]
    du = np.bincount(a.users, minlength=c["n_users"])
    di = np.bincount(a.items, minlength=c["n_items"])
    assert (du == a.deg_u).all() and (di == a.deg_i).all()
    assert du.min() == c["user_degree_quantiles"][0][1]
    assert du.max() == c["user_degree_quantiles"][-1][1]
    assert di.max() <= c["item_degree_quantiles"][-1][1]
    keys = a.users.astype(np.int64) * c["n_items"] + a.items
    assert len(np.unique(keys)) == a.nnz
    # held-out pairs were never imported, and none twice
    held = a.held_users.astype(np.int64) * c["n_items"] + a.held_items
    assert len(np.unique(held)) == len(held) > 0.9 * 0.01 * a.nnz
    assert not np.isin(held, keys).any()


def test_two_seeds_same_geometry_other_pairs(full):
    from predictionio_tpu.models.als import RatingsCOO, als_prepare

    c, (a, b) = full
    assert (np.sort(a.deg_u) == np.sort(b.deg_u)).all()
    assert (np.sort(a.deg_i) == np.sort(b.deg_i)).all()
    assert a.digest() != b.digest()
    geom = [als_prepare(RatingsCOO(d.users, d.items, d.values, d.n_users,
                                   d.n_items)).geometry for d in (a, b)]
    assert geom[0] == geom[1]


def test_values_carry_the_planted_signal(full):
    c, (a, _) = full
    if c["values"]["kind"] == "half_stars":
        assert set(np.unique(a.values * 2)) <= set(range(1, 11))
        s = datagen._signal(a.P, a.Q, a.users[:200000], a.items[:200000])
        assert np.corrcoef(s, a.values[:200000])[0, 1] > 0.7
    else:
        assert a.values.min() >= 1 and (a.values == np.rint(a.values)).all()
        assert abs(np.median(a.values) - c["values"]["median"]) <= 3


def test_same_seed_same_data():
    c = config("als-ml20m-r64")
    a, b = (datagen.Interactions(c["sample"], c["values"], 0.01, 9)
            for _ in range(2))
    assert a.digest() == b.digest()
    assert (a.held_users == b.held_users).all()


def test_infeasible_degrees_are_refused():
    # one user would have to hold the only item twice
    with pytest.raises(ValueError):
        datagen.pair_stubs(np.array([2, 1]), np.array([3]),
                           np.random.default_rng(0))


def test_ndjson_lines_parse_back():
    rng = np.random.default_rng(0)
    u = rng.integers(0, 10 ** 6, 500)
    i = rng.integers(0, 10 ** 6, 500)
    for values, decimals in ((rng.integers(1, 11, 500) / 2, 1),
                             (rng.integers(1, 99999, 500).astype(float), 0)):
        blob = datagen.ndjson_block(u, i, values, decimals)
        got = sorted((e["entityId"], e["targetEntityId"],
                      e["properties"]["rating"])
                     for e in map(json.loads, blob.decode().splitlines()))
        want = sorted((datagen.user_id(a), datagen.item_id(b), float(v))
                      for a, b, v in zip(u, i, values))
        assert got == want
