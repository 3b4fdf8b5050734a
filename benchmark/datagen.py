"""Seeded data for the benchmark's cells: degree-matched interaction
graphs, ratings with a planted low-rank signal, and the NDJSON wire
lines `pio import` reads.

Both sides' DEGREE SEQUENCES are a function of the configuration file
alone (its counts and quantile tables, fitted to the source's published
statistics); the seed decides which id gets which degree, who is paired
with whom, the ratings and the held-out pairs. So every seed yields the
same bucket geometry in `als_prepare`, hence the same programs.
"""

from __future__ import annotations

import numpy as np

GENERATOR_VERSION = 1
#: rank of the planted signal (the served rank is the configuration's)
PLANTED_RANK = 16


# -- degree sequences ---------------------------------------------------------


def degree_sequence(n: int, total: int, table) -> np.ndarray:
    """``n`` integer degrees, ascending, summing to ``total`` exactly.

    ``table`` is ``[[q, degree], ...]`` from q=0 (the minimum) to q=1
    (the maximum): log-degree is interpolated linearly in logit(q)
    between the points, the ends sitting at the outermost of n evenly
    spaced quantiles. The excess over the minimum is then scaled (and
    clipped at the maximum) until the sum is ``total``, and the
    fractions are rounded by largest remainder.
    """
    tq = np.asarray([p[0] for p in table], np.float64)
    td = np.asarray([p[1] for p in table], np.float64)
    if (np.diff(tq) <= 0).any() or (np.diff(td) < 0).any():
        raise ValueError("quantile table must ascend")
    dmin, dmax = td[0], td[-1]
    if not n * dmin <= total <= n * dmax:
        raise ValueError(f"total {total} outside [{n * dmin}, {n * dmax}]")
    q = (np.arange(n) + 0.5) / n
    tq = np.clip(tq, q[0], q[-1])

    def logit(p):
        return np.log(p) - np.log1p(-p)

    d = np.exp(np.interp(logit(q), logit(tq), np.log(td)))

    def scaled(s: float) -> np.ndarray:
        return np.clip(dmin + s * (d - dmin), dmin, dmax)

    lo, hi = 0.0, 1.0
    while scaled(hi).sum() < total:
        hi *= 2.0
    for _ in range(80):
        mid = 0.5 * (lo + hi)
        if scaled(mid).sum() < total:
            lo = mid
        else:
            hi = mid
    real = scaled(hi)
    out = np.floor(real).astype(np.int64)
    short = int(total - out.sum())
    if short < 0:   # floor overshot only through float error at the cap
        raise AssertionError("degree rounding overshot")
    room = np.flatnonzero(out < dmax)
    frac = (real - out)[room]
    bump = room[np.argsort(-frac, kind="stable")[:short]]
    out[bump] += 1
    out.sort()
    assert out.sum() == total and out[0] >= dmin and out[-1] <= dmax
    return out


# -- pairing ------------------------------------------------------------------

#: rounds of at least this many users are shuffled afresh
_SHUFFLED_ROUND_MIN = 1024


def pair_stubs(deg_u: np.ndarray, deg_i: np.ndarray,
               rng: np.random.Generator):
    """(users, items): int32 arrays of ``deg_u.sum()`` DISTINCT pairs in
    which id ``u`` occurs exactly ``deg_u[u]`` times and ``i`` exactly
    ``deg_i[i]`` times — a configuration-model pairing laid out so that
    no pair can repeat, with the few candidates for a repeat repaired by
    swapping, never dropping.

    User stubs are dealt in ROUNDS: round r holds one stub of every user
    with more than r interactions, in an order drawn afresh for each
    round. Item stubs are laid along the rounds item by item, heaviest
    first. Within a round every user is distinct, so an item can meet a
    user twice only where its run of stubs crosses from one round into
    the next; there the users it already holds are swapped, inside the
    next round, for users further along. What comes out is nested the
    way rating data are (nearly everyone holds one of the few heaviest
    items; only heavy raters reach the long tail) and random inside
    each band of items.

    Raises if an item is heavier than the rounds it lands in can serve
    (a property of the two degree sequences, not of the seed).
    """
    deg_u = np.asarray(deg_u, np.int64)
    deg_i = np.asarray(deg_i, np.int64)
    nnz = int(deg_u.sum())
    if nnz != int(deg_i.sum()):
        raise ValueError("the two sides' degrees must have one sum")
    by_deg = np.argsort(-deg_u, kind="stable").astype(np.int32)
    d_desc = deg_u[by_deg]
    max_d = int(d_desc[0])
    # size[r]: users with more than r interactions
    size = len(d_desc) - np.searchsorted(d_desc[::-1], np.arange(max_d),
                                         side="right")
    start = np.concatenate([[0], np.cumsum(size)])
    users = np.empty(nnz, np.int32)
    steady = None
    for r in range(max_d):
        if size[r] >= _SHUFFLED_ROUND_MIN:
            users[start[r]:start[r + 1]] = rng.permutation(by_deg[:size[r]])
            continue
        # small rounds keep ONE drawn order: a user's stubs in rounds r
        # and r+1 then lie at least size[r+1] apart, so an item no
        # heavier than that cannot meet a user twice — where a fresh
        # order per round would leave too few users to swap with
        if steady is None:
            steady = rng.permutation(by_deg[:size[r]])
        steady = steady[deg_u[steady] > r]
        users[start[r]:start[r + 1]] = steady
    heavy_first = np.argsort(-deg_i, kind="stable").astype(np.int32)
    items = np.repeat(heavy_first, deg_i[heavy_first])
    item_end = np.cumsum(deg_i[heavy_first])
    for r in range(max_d - 1):
        b = int(start[r + 1])               # first position of round r+1
        if items[b - 1] != items[b]:
            continue                        # no item crosses here
        rank = int(np.searchsorted(item_end, b, side="right"))
        lo = max(int(item_end[rank] - deg_i[heavy_first[rank]]),
                 int(start[r]))
        hi, nxt = int(item_end[rank]), int(start[r + 2])
        if hi > nxt:
            raise ValueError(
                f"item of degree {int(deg_i[heavy_first[rank]])} spans "
                f"round {r + 1} of {int(size[r + 1])} users: no simple "
                "graph in this layout")
        held = users[lo:b]
        clash = b + np.flatnonzero(np.isin(users[b:hi], held))
        if not len(clash):
            continue
        free = hi + np.flatnonzero(~np.isin(users[hi:nxt], held))
        if len(free) < len(clash):
            raise ValueError(
                f"round {r + 1} has too few users to repair the item "
                "that crosses into it")
        free = free[:len(clash)]
        users[clash], users[free] = users[free], users[clash]
    return users, items


# -- values -------------------------------------------------------------------


def planted_factors(n_users: int, n_items: int, rng: np.random.Generator):
    """Unit-variance rank-``PLANTED_RANK`` signal: P[u] · Q[i] ~ N(0, 1)."""
    scale = PLANTED_RANK ** -0.25
    P = rng.standard_normal((n_users, PLANTED_RANK), np.float32) * scale
    Q = rng.standard_normal((n_items, PLANTED_RANK), np.float32) * scale
    return P, Q


def _signal(P, Q, users, items) -> np.ndarray:
    out = np.empty(len(users), np.float32)
    for a in range(0, len(users), 2_000_000):
        b = a + 2_000_000
        out[a:b] = np.einsum("nk,nk->n", P[users[a:b]], Q[items[a:b]])
    return out


def values_for(spec: dict, P, Q, users, items,
               rng: np.random.Generator) -> np.ndarray:
    """The interaction values on the source's scale: the planted signal
    plus noise, rounded as the source rounds.

    ``half_stars``: mean + signal_std·s + noise, to the nearest half star
    inside [min, max]. ``plays``: log-normal play counts,
    round(median · exp(signal_std·s + noise)) ≥ 1.
    """
    s = _signal(P, Q, users, items)
    noise = rng.standard_normal(len(users), np.float32) * spec["noise_std"]
    if spec["kind"] == "half_stars":
        v = spec["mean"] + spec["signal_std"] * s + noise
        return np.clip(np.rint(v * 2) / 2, spec["min"],
                       spec["max"]).astype(np.float32)
    if spec["kind"] == "plays":
        v = spec["median"] * np.exp(spec["signal_std"] * s + noise)
        return np.clip(np.rint(v), 1, spec["max"]).astype(np.float32)
    raise ValueError(f"unknown value kind {spec['kind']!r}")


# -- one data set -------------------------------------------------------------


class Interactions:
    """What one seed makes of one shape: the pairs that are imported,
    their values, and held-out pairs that never are."""

    def __init__(self, shape: dict, values: dict, heldout_share: float,
                 seed: int) -> None:
        rng = np.random.default_rng([GENERATOR_VERSION, seed])
        n_u, n_i = shape["n_users"], shape["n_items"]
        nnz = shape["n_interactions"]
        self.n_users, self.n_items = n_u, n_i
        deg_u = degree_sequence(n_u, nnz, shape["user_degree_quantiles"])
        deg_i = degree_sequence(n_i, nnz, shape["item_degree_quantiles"])
        # the seed decides which id gets which degree
        self.deg_u = deg_u[rng.permutation(n_u)]
        self.deg_i = deg_i[rng.permutation(n_i)]
        self.users, self.items = pair_stubs(self.deg_u, self.deg_i, rng)
        # the wire order is shuffled: the store numbers ids as it first
        # sees them, and the layout above would hand it the heavy first
        order = rng.permutation(nnz)
        self.users, self.items = self.users[order], self.items[order]
        self.P, self.Q = planted_factors(n_u, n_i, rng)
        self.values = values_for(values, self.P, self.Q, self.users,
                                 self.items, rng)
        # held out: pairs drawn like the data (both ends by degree) that
        # are NOT among the imported ones, valued by the same law
        want = int(round(heldout_share * nnz))
        keys = np.sort(self.users.astype(np.int64) * n_i + self.items)
        if (keys[1:] == keys[:-1]).any():
            raise AssertionError("the pairing repeated a pair")
        hu = self.users[rng.integers(0, nnz, 2 * want)]
        hi = self.items[rng.integers(0, nnz, 2 * want)]
        hk = hu.astype(np.int64) * n_i + hi
        pos = np.minimum(np.searchsorted(keys, hk), nnz - 1)
        _, first = np.unique(hk, return_index=True)
        new = np.zeros(len(hk), bool)
        new[first] = True
        new &= keys[pos] != hk
        self.held_users = hu[new][:want]
        self.held_items = hi[new][:want]
        self.held_values = values_for(values, self.P, self.Q,
                                      self.held_users, self.held_items, rng)

    @property
    def nnz(self) -> int:
        return len(self.users)

    def digest(self) -> str:
        import hashlib

        h = hashlib.blake2b(digest_size=16)
        for a in (self.users, self.items, self.values):
            h.update(np.ascontiguousarray(a).data)
        return h.hexdigest()


# -- the wire format ----------------------------------------------------------

ID_DIGITS = 6


def user_id(u: int) -> str:
    return f"u{u:0{ID_DIGITS}d}"


def item_id(i: int) -> str:
    return f"i{i:0{ID_DIGITS}d}"


def ndjson_block(users, items, values, decimals: int) -> bytes:
    """One "rate" event per interaction, as `pio import` reads them.
    Lines whose value has the same number of digits have the same
    width, so each such group is filled in bulk as a byte matrix (ids
    are strings: zero padding is legal; the value is a JSON number and
    is written without padding)."""
    head = b'{"event":"rate","entityType":"user","entityId":"u'
    mid = b'","targetEntityType":"item","targetEntityId":"i'
    tail = b'","properties":{"rating":'
    end = b'}}\n'
    u = users.astype(np.int64)
    i = items.astype(np.int64)
    scaled = np.rint(values.astype(np.float64) * 10 ** decimals).astype(
        np.int64)
    whole = scaled // 10 ** decimals
    n_digits = np.ones(len(whole), np.int64)
    for d in range(1, 12):
        n_digits += whole >= 10 ** d
    out = []
    for w in np.unique(n_digits):
        sel = np.flatnonzero(n_digits == w)
        value_width = int(w) + (1 + decimals if decimals else 0)
        template = np.frombuffer(
            head + b"0" * ID_DIGITS + mid + b"0" * ID_DIGITS + tail
            + b"0" * value_width + end, np.uint8)
        u0 = len(head)
        i0 = u0 + ID_DIGITS + len(mid)
        r0 = i0 + ID_DIGITS + len(tail)
        block = np.tile(template, (len(sel), 1))
        for d in range(ID_DIGITS):
            block[:, u0 + ID_DIGITS - 1 - d] = 48 + (u[sel] // 10 ** d) % 10
            block[:, i0 + ID_DIGITS - 1 - d] = 48 + (i[sel] // 10 ** d) % 10
        for d in range(int(w)):
            block[:, r0 + int(w) - 1 - d] = 48 + (whole[sel] // 10 ** d) % 10
        if decimals:
            block[:, r0 + int(w)] = ord(".")
            for d in range(decimals):
                block[:, r0 + int(w) + decimals - d] = (
                    48 + (scaled[sel] // 10 ** d) % 10)
        out.append(block.tobytes())
    return b"".join(out)
