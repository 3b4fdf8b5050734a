"""What the readers of a set-up's layers share (PR 52): the program's
record of the FIRST ``train.run`` of this process
(``predictionio_tpu.utils.tracing.first_verb``: the warm-up train of a
run — the one cold verb, whose tree holds the ``compile.*`` spans of
``utils/compilecache.py``, the full scan and the snapshot's first write)
and the import's own counters (``utils.metrics.REGISTRY``,
``pio_ingest_seconds_total{stage}``). The set-up of a ``--trace 1`` run
is any run's, so the readers run there like all the others. Nothing
here times anything: the numbers are the program's.

A program that keeps no such record (a checkout from before PR 52) gives
None everywhere, and the readers leave their metric out.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import spans
import trace_reduce

STAGES = ("compile.trace", "compile.lower", "compile.backend")
INGEST = "pio_ingest_seconds_total"


def first_tree(obs: Dict[str, Any]) -> Optional[List[dict]]:
    """``obs["first_spans"]`` where a test put one, else the program's
    first finished ``train.run``."""
    if obs.get("first_spans") is not None:
        return obs["first_spans"]
    try:
        from predictionio_tpu.utils import tracing
    except ImportError:
        return None
    first_verb = getattr(tracing, "first_verb", None)
    return first_verb(spans.ROOT) if first_verb is not None else None


def root_of(tree: Optional[List[dict]]) -> Optional[dict]:
    return next(iter(spans.named(tree, spans.ROOT)), None)


def compile_sums(obs: Dict[str, Any]) -> Optional[dict]:
    """The first verb's root attributes where the program left its
    compile sums there (``programs_traced`` …); None on a program that
    does not count them."""
    attrs = (root_of(first_tree(obs)) or {}).get("attrs") or {}
    return attrs if "programs_traced" in attrs else None


def first_train_seconds(obs: Dict[str, Any]) -> Optional[float]:
    root = root_of(first_tree(obs))
    return None if root is None else (root["endNs"] - root["startNs"]) / 1e9


def compile_seconds(obs: Dict[str, Any], names=STAGES,
                    keep: Callable[[dict], bool] = lambda attrs: True
                    ) -> Optional[float]:
    """Union of the first verb's spans called one of ``names`` whose
    attributes ``keep`` accepts; 0.0 where none is (a full cache leaves
    no miss), None where the program keeps no compile record."""
    if compile_sums(obs) is None:
        return None
    covered, _gaps = trace_reduce.union_seconds(
        [(s["startNs"], s["endNs"]) for s in first_tree(obs)
         if s.get("name") in names and keep(s.get("attrs") or {})])
    return covered / 1e9


def ingest_seconds(obs: Dict[str, Any], *stages: str) -> Optional[float]:
    """Σ of the import's ``pio_ingest_seconds_total`` over ``stages``
    (all of them where none is named); None where the program has no
    such series or nothing was ingested (the store was reused)."""
    registry = obs.get("registry")
    if registry is None:
        try:
            from predictionio_tpu.utils.metrics import REGISTRY as registry
        except ImportError:
            return None
    series = next((m for m in registry.metrics()
                   if getattr(m, "name", None) == INGEST), None)
    if series is None:
        return None
    by_stage = {labels[0]: v for labels, v in series.items()}
    if not sum(by_stage.values()):
        return None
    return sum(v for k, v in by_stage.items() if not stages or k in stages)
