"""The four DASE roles: DataSource, Preparator, Algorithm, Serving.

Reference: [U] core/.../controller/{PDataSource,LDataSource,PPreparator,
LPreparator,PAlgorithm,P2LAlgorithm,LAlgorithm,LServing}.scala and
core/.../core/Base*.scala (unverified, SURVEY.md §2a). See the package
docstring for why the P/P2L/L split collapses to one spelling here.

Model persistence contract (replaces the reference's java-serialization
default + ``PersistentModel`` escape hatch): by default a trained model
is pickled into the model blob store; an Algorithm may override
``save_model``/``load_model`` to persist structured artifacts (e.g.
Orbax checkpoints of sharded factor matrices) into the per-instance
model directory instead — the ``PersistentModel``/
``PersistentModelLoader`` analogue.
"""

from __future__ import annotations

import pickle
from abc import ABC, abstractmethod
from typing import Any, Generic, List, Optional, Sequence, TypeVar, Union

from predictionio_tpu.controller.base import WorkflowContext

TD = TypeVar("TD")   # training data
PD = TypeVar("PD")   # prepared data
M = TypeVar("M")     # model
Q = TypeVar("Q")     # query
PR = TypeVar("PR")   # prediction
A = TypeVar("A")     # actual (ground truth for eval)
EI = TypeVar("EI")   # eval info


class DataSource(ABC, Generic[TD, EI, Q, A]):
    """Reads training (and evaluation) data from the event store."""

    def __init__(self, params: Any = None) -> None:
        self.params = params

    @abstractmethod
    def read_training(self, ctx: WorkflowContext) -> TD:
        ...

    def read_eval(self, ctx: WorkflowContext) -> List[tuple]:
        """Return ``[(training_data, eval_info, [(query, actual), ...]), ...]``
        — one tuple per fold (reference: PDataSource.readEval)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement read_eval; "
            "evaluation is unavailable for this engine")


class Preparator(ABC, Generic[TD, PD]):
    def __init__(self, params: Any = None) -> None:
        self.params = params

    @abstractmethod
    def prepare(self, ctx: WorkflowContext, training_data: TD) -> PD:
        ...


class IdentityPreparator(Preparator[TD, TD]):
    """Pass-through (reference: IdentityPreparator)."""

    def prepare(self, ctx: WorkflowContext, training_data: TD) -> TD:
        return training_data


class Algorithm(ABC, Generic[PD, M, Q, PR]):
    """P2L semantics: ``train`` runs on the mesh and returns a local model
    (pytree of jax.Arrays / numpy / plain objects); ``predict`` serves one
    query from the resident model."""

    def __init__(self, params: Any = None) -> None:
        self.params = params
        #: set by prepare_deploy — the Storage serving-time lookups must
        #: use (live business rules, feedback); None during training
        self.serving_storage: Any = None

    def set_serving_context(self, storage: Any) -> None:
        """Called once at deploy time with the Storage backing this
        serving process (the LEventStore-at-serve-time analogue)."""
        self.serving_storage = storage

    @abstractmethod
    def train(self, ctx: WorkflowContext, prepared_data: PD) -> M:
        ...

    @abstractmethod
    def predict(self, model: M, query: Q) -> PR:
        ...

    #: True when ``batch_predict`` understands AOT-bucket ``PAD``
    #: sentinels (``server/aot.PAD``) inline — it must then return one
    #: (discarded) slot per PAD. False (default) → the deploy layer
    #: strips pads before calling and re-inserts the empty slots.
    accepts_padding: bool = False

    def batch_predict(self, model: M, queries: Sequence[Q]) -> List[PR]:
        """Bulk scoring for `pio batchpredict` and evaluation. Default maps
        ``predict``; algorithms override to batch onto the device."""
        return [self.predict(model, q) for q in queries]

    def aot_warm(self, model: M, ladder: Any,
                 ks: Sequence[int] = (16,)) -> Optional[dict]:
        """Deploy-time AOT warmup hook (``server/aot.AOTWarmup``):
        compile this algorithm's serving program for every batch bucket
        in ``ladder`` (× each top-k width in ``ks``) so no query shape
        ≤ max_batch ever compiles on the hot path. Return
        ``{"targets", "compiled", "cached"}`` counts, or None.
        Default: nothing to warm (host-side serving)."""
        return None

    @classmethod
    def train_many(cls, ctx: WorkflowContext, prepared_data: PD,
                   params_list: Sequence[Any]) -> List[M]:
        """Train one model per params on the SAME prepared data — the
        grid-search fan-out (`pio eval`, SURVEY.md §2d P4). Default is
        sequential; algorithms whose hyperparameters are continuous
        (e.g. regularization) override this to STACK same-geometry
        candidates into one vmapped program, turning k separate
        trace+compile+run cycles into one."""
        return [cls(p).train(ctx, prepared_data) for p in params_list]

    @classmethod
    def sweep_programs(cls, ctx: WorkflowContext, prepared_data: PD,
                       params_list: Sequence[Any], qpa: Sequence[Any],
                       metric: Any) -> Optional[List[Any]]:
        """Distributed-sweep hook (``core/sweep.py``): return a list of
        ``SweepProgram``s that together cover every candidate in
        ``params_list`` — each a pure vmappable train+score fn over a
        stacked hyperparameter axis, bucketed by compile geometry — or
        None when this algorithm (or ``metric.sweep_kind``) can only run
        on the serial qpa path. ``qpa`` is the fold's ``[(q, a), ...]``."""
        return None

    # -- persistence (PersistentModel analogue) --------------------------------

    def save_model(self, model: M, instance_dir: Optional[str]
                   ) -> Union[None, bytes, Sequence[Any]]:
        """Serialize the model. Return bytes for the blob store, or None if
        everything was written into ``instance_dir`` (structured artifacts)
        — or a sequence of bytes-like PARTS (``bytes``, ``memoryview``,
        contiguous numpy arrays): a small head and the arrays' own
        buffers, which the store writes one after the other without
        joining or copying them (``utils/model_parts.py`` packs and
        unpacks such a blob). ``load_model`` is then handed a read-only
        ``memoryview`` of the joined parts, where a ``bytes`` result
        comes back as ``bytes``."""
        return pickle.dumps(model)

    def load_model(self, blob: Optional[bytes], instance_dir: Optional[str]) -> M:
        if blob is None:
            raise ValueError(
                f"{type(self).__name__}.load_model got no blob; override "
                "load_model to restore from the instance directory")
        return pickle.loads(blob)

    def sanity_check(self, data: Any) -> None:
        """Hook mirroring the reference's SanityCheck trait: raise if the
        data/model is degenerate (empty training set etc.)."""


class Serving(ABC, Generic[Q, PR]):
    """Combines per-algorithm predictions into the served response."""

    def __init__(self, params: Any = None) -> None:
        self.params = params

    @abstractmethod
    def serve(self, query: Q, predictions: List[PR]) -> PR:
        ...

    def supplement(self, query: Q) -> Q:
        """Pre-processing hook applied to the query before prediction
        (reference: LServing.supplement)."""
        return query


class FirstServing(Serving[Q, PR]):
    """Serve the first algorithm's prediction (reference: FirstServing)."""

    def serve(self, query: Q, predictions: List[PR]) -> PR:
        if not predictions:
            raise ValueError("no predictions to serve")
        return predictions[0]
