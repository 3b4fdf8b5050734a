"""The program's own record of its newest ``pio train`` verb
(``predictionio_tpu.utils.tracing.last_verb("train.run")``: one dict
per span — ``name``, ``spanId``, ``parentId``, ``startNs`` / ``endNs``
on ``perf_counter_ns``, ``startUs`` wall-clock, ``attrs``) and the
arithmetic the layer readers share. Nothing here times anything: the
numbers are the program's.

A program that keeps no such record (a checkout from before the verb
record) gives None everywhere, and the readers leave their metric out.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import trace_reduce

ROOT = "train.run"


def tree_of(obs: Dict[str, Any]) -> Optional[List[dict]]:
    """The span tree the readers read: ``obs["spans"]`` where a
    generator (or a test) put one, else the newest finished
    ``train.run`` of this process — in a ``--trace 1`` run the ONE
    traced train (the reference check calls ``als_train`` without a verb
    root and leaves it alone)."""
    if obs.get("spans") is not None:
        return obs["spans"]
    try:
        from predictionio_tpu.utils import tracing
    except ImportError:
        return None
    last_verb = getattr(tracing, "last_verb", None)
    return last_verb(ROOT) if last_verb is not None else None


def named(tree: Optional[List[dict]], name: str) -> List[dict]:
    return [s for s in tree or () if s.get("name") == name]


def seconds_of(tree: Optional[List[dict]], name: str) -> Optional[float]:
    """Summed length of the spans called ``name``; None where there is
    none (a block loop leaves one ``als.checkpoint`` per block)."""
    hit = named(tree, name)
    if not hit:
        return None
    return sum(s["endNs"] - s["startNs"] for s in hit) / 1e9


def attr_of(tree: Optional[List[dict]], name: str, key: str) -> Any:
    """Attribute ``key`` of the first span called ``name``, or None."""
    for s in named(tree, name):
        return (s.get("attrs") or {}).get(key)
    return None


def leaves(tree: List[dict]) -> List[dict]:
    parents = {s.get("parentId") for s in tree}
    return [s for s in tree if s.get("spanId") not in parents]


def untraced_seconds(tree: Optional[List[dict]]) -> Optional[float]:
    """The root's length minus the union of its leaf spans: host time
    of the verb that no named span accounts for."""
    root = next(iter(named(tree, ROOT)), None)
    if root is None:
        return None
    r0, r1 = root["startNs"], root["endNs"]
    inside = [(max(s["startNs"], r0), min(s["endNs"], r1))
              for s in leaves(tree) if s is not root]
    covered, _gaps = trace_reduce.union_seconds(
        [(a, b) for a, b in inside if b > a])
    return (r1 - r0 - covered) / 1e9
