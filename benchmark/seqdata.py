"""Item histories for the sequence cells, from the configuration and a
seed, and their import into the benchmark's PredictionIO home.

Lengths: ``datagen.degree_sequence`` over the user degree quantiles of
the ALS configuration named in ``data.degree_tables_from`` (ml-20m:
20 … 9,254 ratings a user, mean 144), scaled to ``n_events`` exactly.
Items: the ``n_items`` most-rated of that configuration's item degree
sequence, drawn in proportion to their degree. Order: each item has
``successors`` seeded successor items; an event follows one of its
predecessor's successors with probability ``follow_share``, else it is
drawn by popularity — a planted next-item signal. ``eventTime`` rises
by one second an event inside a history; events are imported in time
order, so the store's order is the history's.
"""

from __future__ import annotations

import hashlib
import json
import os
import time

import numpy as np

import piostore
from datagen import GENERATOR_VERSION, degree_sequence, item_id, user_id
from harness import BenchFailure, load_json, say

EVENT = "view"
T0 = 1767225600            # 2026-01-01T00:00:00Z


class Histories:
    def __init__(self, shape: dict, seed: int) -> None:
        """``shape``: the configuration (or its ``sample``): ``n_users``,
        ``n_items``, ``n_events`` and the law's parameters under
        ``data``."""
        spec = dict(shape["data"], n_events=shape["n_events"])
        tables = (load_json("configs", spec["degree_tables_from"])
                  if "degree_tables_from" in spec else spec)
        rng = np.random.default_rng([int(seed), 31])
        self.n_users, self.n_items = shape["n_users"], shape["n_items"]
        lengths = degree_sequence(self.n_users, spec["n_events"],
                                  tables["user_degree_quantiles"])
        self.lengths = lengths[rng.permutation(self.n_users)]
        n_source = tables.get("n_items", self.n_items)
        degree = degree_sequence(
            n_source, max(tables.get("n_interactions", 0), n_source * 2),
            tables["item_degree_quantiles"])[-self.n_items:]
        self.popularity = degree / degree.sum()
        k = spec["successors"]
        #: successor table: item → k items, drawn by popularity
        self.successors = rng.choice(self.n_items, (self.n_items, k),
                                     p=self.popularity)
        n, longest = int(self.lengths.sum()), int(self.lengths.max())
        # position-major: every user's event t before any event t + 1,
        # which is the order of the eventTimes
        alive = np.argsort(-self.lengths, kind="stable")
        counts = np.searchsorted(-self.lengths[alive],
                                 -np.arange(1, longest + 1), side="right")
        users, items, pos = [], [], []
        prev = np.zeros(self.n_users, np.int64)
        for t in range(longest):
            who = alive[:counts[t]]
            drawn = rng.choice(self.n_items, who.size, p=self.popularity)
            if t:
                follow = rng.random(who.size) < spec["follow_share"]
                nxt = self.successors[prev[who],
                                      rng.integers(k, size=who.size)]
                drawn = np.where(follow, nxt, drawn)
            prev[who] = drawn
            users.append(who)
            items.append(drawn)
            pos.append(np.full(who.size, t))
        self.users = np.concatenate(users).astype(np.int32)
        self.items = np.concatenate(items).astype(np.int32)
        self.pos = np.concatenate(pos).astype(np.int32)
        assert self.users.size == n

    @property
    def nnz(self) -> int:
        return int(self.users.size)

    def digest(self) -> str:
        h = hashlib.sha256()
        for a in (self.users, self.items, self.pos):
            h.update(np.ascontiguousarray(a).tobytes())
        return h.hexdigest()[:16]

    def histories(self) -> list:
        """Every user's items, oldest first (generator numbering): the
        import order is position-major, so a stable sort by user is
        each history in order."""
        order = np.argsort(self.users, kind="stable")
        return np.split(self.items[order],
                        np.cumsum(np.bincount(self.users,
                                              minlength=self.n_users))[:-1])

    def popularity_entropy(self) -> float:
        p = self.popularity
        return float(-(p * np.log(p)).sum())


def ndjson(h: Histories, a: int, b: int) -> bytes:
    stamps = (T0 + h.pos[a:b]).astype("datetime64[s]").astype(str)
    return "".join(
        f'{{"event":"{EVENT}","entityType":"user","entityId":"{user_id(u)}"'
        f',"targetEntityType":"item","targetEntityId":"{item_id(i)}"'
        f',"eventTime":"{t}.000Z"}}\n'
        for u, i, t in zip(h.users[a:b].tolist(), h.items[a:b].tolist(),
                           stamps)).encode()


def ensure_events(config_name: str, tiny: bool, h: Histories,
                  seed: int) -> dict:
    """The seed's events in the event store, imported now through the
    native parser (``append_jsonl``, what ``pio import`` uses) or found
    as an earlier run of this seed left them (``piostore``'s manifest
    rule)."""
    from predictionio_tpu import native
    from predictionio_tpu.storage import get_storage
    from predictionio_tpu.storage.registry import set_storage

    manifest = os.path.join(piostore._home(config_name, tiny),
                            "manifest.json")
    want = {"generator_version": GENERATOR_VERSION, "seed": seed,
            "count": h.nnz, "digest": h.digest()}
    try:
        with open(manifest) as f:
            reuse = json.load(f) == want
    except (OSError, ValueError):
        reuse = False
    if not reuse and os.path.exists(manifest):
        os.remove(manifest)
    piostore.open_home(config_name, tiny, keep_events=reuse)
    if native.eventlog_library() is None:
        raise BenchFailure("the native event-log engine did not build "
                           "(g++ missing?): no EVENTLOG store, no cell")
    set_storage(None)
    st = get_storage()
    app = st.meta.create_app(piostore.APP)
    app_id = getattr(app, "id", app)
    st.events.init_channel(app_id, None)
    t0 = time.perf_counter()
    if not reuse:
        for a in range(0, h.nnz, piostore.BLOCK):
            b = min(a + piostore.BLOCK, h.nnz)
            done, declined = st.events.append_jsonl(ndjson(h, a, b), b - a,
                                                    app_id, None)
            if done != b - a or declined:
                raise BenchFailure(f"the native parser took {done} of "
                                   f"{b - a} lines")
    stats = st.events.creation_stats(app_id, None)
    have = stats[0] if stats else -1
    if have != h.nnz:
        raise BenchFailure(f"the event store holds {have} events, "
                           f"{h.nnz} were made")
    secs = time.perf_counter() - t0
    if not reuse:
        with open(manifest, "w") as f:
            json.dump(want, f)
    say(f"store: {h.nnz:,} events "
        + (f"reused (manifest matches seed {seed})" if reuse
           else f"imported in {secs:.1f} s"))
    return {"reused": reuse, "import_s": secs}
