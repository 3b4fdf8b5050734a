"""Recommendation template: ALS collaborative filtering.

Behavioral equivalent of the reference's quickstart template
(reference: [U] examples/scala-parallel-recommendation/ — DataSource
reads "rate"/"buy" events into Ratings, ALSAlgorithm wraps MLlib
``ALS.train`` into an ALSModel with user/item BiMaps, Serving = first;
SURVEY.md §2c). Query/response wire shapes match the reference:

    POST /queries.json  {"user": "1", "num": 4}
    → {"itemScores": [{"item": "22", "score": 4.5}, ...]}

The compute is :mod:`predictionio_tpu.models.als` (JAX, mesh-aware).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from predictionio_tpu.controller import (
    Algorithm,
    DataSource,
    Engine,
    EngineParams,
    EngineParamsGenerator,
    Evaluation,
    FirstServing,
    Metric,
    Preparator,
    WorkflowContext,
)
from predictionio_tpu.data.cleaning import SelfCleaningDataSource
from predictionio_tpu.models.als import (
    ALSParams,
    RatingsCOO,
    als_train,
    recommend,
)
from predictionio_tpu.utils import model_parts, tracing
from predictionio_tpu.utils.bimap import BiMap


@dataclass
class Rating:
    user: str
    item: str
    rating: float


@dataclass
class TrainingData:
    """Columnar, index-mapped interactions + id vocabularies.

    Built by the STREAMING read path (``data/pipeline.read_interactions``
    — the RDD-partition read analogue, SURVEY.md §3.1/§2d C4): the read
    holds O(chunk + vocabulary) transient host memory instead of the
    round-2 ~1 KB/event ``List[Rating]`` materialization; what remains
    is the 12 B/event columnar result ALS consumes directly.

    ``ratings`` materializes Rating objects lazily for small-data
    consumers (tests, debugging) — avoid it on large datasets.
    """

    user_idx: np.ndarray   # int32 [n]
    item_idx: np.ndarray   # int32 [n]
    rating: np.ndarray     # float32 [n]
    user_ids: BiMap
    item_ids: BiMap

    @property
    def n(self) -> int:
        return int(self.user_idx.shape[0])

    @property
    def ratings(self) -> List[Rating]:
        u_inv = self.user_ids.inverse()
        i_inv = self.item_ids.inverse()
        return [Rating(u_inv[int(u)], i_inv[int(i)], float(r))
                for u, i, r in zip(self.user_idx, self.item_idx,
                                   self.rating)]

    @classmethod
    def from_ratings(cls, ratings: List[Rating]) -> "TrainingData":
        user_ids = BiMap.string_int(r.user for r in ratings)
        item_ids = BiMap.string_int(r.item for r in ratings)
        return cls(
            np.fromiter((user_ids[r.user] for r in ratings), np.int32,
                        len(ratings)),
            np.fromiter((item_ids[r.item] for r in ratings), np.int32,
                        len(ratings)),
            np.fromiter((r.rating for r in ratings), np.float32,
                        len(ratings)),
            user_ids, item_ids)

    def subset(self, mask: np.ndarray) -> "TrainingData":
        """Rows where ``mask`` holds, vocabularies trimmed (eval-fold
        cold-entity rule — see ``data/pipeline.subset_columnar``)."""
        from predictionio_tpu.data.pipeline import subset_columnar

        uu, ii, u_ids, i_ids, rr = subset_columnar(
            mask, self.user_idx, self.item_idx,
            self.user_ids, self.item_ids, self.rating)
        return TrainingData(uu, ii, rr, u_ids, i_ids)


@dataclass
class DataSourceParams:
    app_name: str = ""
    event_names: List[str] = field(default_factory=lambda: ["rate", "buy"])
    # rating assigned to implicit "buy" events (reference quickstart: 4.0)
    buy_rating: float = 4.0
    eval_k: int = 0          # >0 enables read_eval with k folds
    eval_seed: int = 3
    #: optional {"duration": "30 days", "removeDuplicates": bool,
    #: "compressProperties": bool} — SelfCleaningDataSource window
    event_window: Optional[Dict[str, Any]] = None


class RecDataSource(SelfCleaningDataSource, DataSource):
    ParamsClass = DataSourceParams

    def _read(self, ctx: WorkflowContext) -> TrainingData:
        """Read the event store into columnar TrainingData. On the C++
        EVENTLOG backend this is a native columnar scan (no per-event
        Python objects — the rating extraction runs in C++); elsewhere
        it streams ``find()`` in two passes with O(chunk) Event objects
        alive at any moment (``data/store.read_training_interactions``).
        "rate" events carry ``properties["rating"]`` (malformed → event
        skipped); any other configured event is an implicit positive at
        ``buy_rating``."""
        from predictionio_tpu.data.store import read_training_interactions

        p: DataSourceParams = self.params
        data = read_training_interactions(
            p.app_name,
            entity_type="user",
            target_entity_type="item",
            event_names=p.event_names,
            value_key="rating",
            value_spec={"rate": "prop"},
            default_spec=p.buy_rating,
            storage=ctx.storage,
        )
        with tracing.span("train.read.arrays") as sp:
            uu, ii, rr = data.arrays()
            sp.set_attr("bytes", int(uu.nbytes + ii.nbytes + rr.nbytes))
        return TrainingData(uu, ii, rr, data.user_ids, data.item_ids)

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        self.clean(ctx, self.params.app_name)
        td = self._read(ctx)
        if td.n == 0:
            raise ValueError(
                "no rate/buy events found; import events before `pio train`")
        return td

    def read_eval(self, ctx: WorkflowContext):
        p: DataSourceParams = self.params
        if p.eval_k <= 0:
            raise ValueError("set dataSourceParams.evalK > 0 to evaluate")
        td = self._read(ctx)
        rng = np.random.default_rng(p.eval_seed)
        fold_of = rng.integers(0, p.eval_k, size=td.n)
        u_inv = td.user_ids.inverse()
        i_inv = td.item_ids.inverse()
        folds = []
        for f in range(p.eval_k):
            train = td.subset(fold_of != f)
            test = np.nonzero(fold_of == f)[0]
            qa = [({"user": u_inv[int(td.user_idx[j])],
                    "item": i_inv[int(td.item_idx[j])], "num": 1},
                   float(td.rating[j])) for j in test]
            folds.append((train, {"fold": f}, qa))
        return folds


class RecPreparator(Preparator):
    """Pass-through (reference quickstart Preparator)."""

    def prepare(self, ctx: WorkflowContext, training_data: TrainingData) -> TrainingData:
        return training_data


@dataclass
class ALSAlgorithmParams:
    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.01
    seed: Optional[int] = None
    implicit_prefs: bool = False
    alpha: float = 1.0
    # mid-train checkpoint cadence (iterations per block) when the
    # workflow provides a checkpoint dir; 0 disables (SURVEY.md §5)
    checkpoint_every: int = 5
    # bf16 factor gathers: ~half the training HBM traffic for ~1e-2
    # relative factor error (see models/als.py ALSParams.bf16_gather)
    bf16_gather: bool = False


class ALSModel:
    """Resident serving model: factor matrices + id↔index BiMaps.

    Serving is DEVICE-RESIDENT for production-size catalogs: the first
    query builds a lazy :class:`~predictionio_tpu.models.als.ResidentScorer`
    (U and V live in HBM across requests; each query is one fused
    gather→score→top-k dispatch with a single packed fetch — the
    reference keeps MatrixFactorizationModel in JVM heap, [U] MLlib
    recommendProducts). Tiny catalogs score host-side instead; policy
    + ``PIO_ALS_SERVE`` override live in
    ``models/als.maybe_resident_scorer`` (shared with e-commerce).
    """

    def __init__(self, U: np.ndarray, V: np.ndarray,
                 user_ids: BiMap, item_ids: BiMap) -> None:
        self.U = U
        self.V = V
        self.user_ids = user_ids
        self.item_ids = item_ids
        self._item_inv = item_ids.inverse()
        self._scorer = None

    def _device_scorer(self):
        from predictionio_tpu.models.als import maybe_resident_scorer

        self._scorer = maybe_resident_scorer(self.U, self.V, self._scorer)
        return self._scorer

    def recommend_products(self, user: str, num: int) -> List[Dict[str, Any]]:
        uidx = self.user_ids.get(user)
        if uidx is None:
            return []
        scorer = self._device_scorer()
        if scorer is not None:
            top, scores = scorer.recommend(uidx, num)
        else:
            top, scores = recommend(self.U, self.V, uidx, num)
        return [
            {"item": self._item_inv[int(i)], "score": float(s)}
            for i, s in zip(top, scores)
        ]

    def predict_rating(self, user: str, item: str) -> Optional[float]:
        uidx = self.user_ids.get(user)
        iidx = self.item_ids.get(item)
        if uidx is None or iidx is None:
            return None
        return float(self.U[uidx] @ self.V[iidx])


class ALSAlgorithm(Algorithm):
    ParamsClass = ALSAlgorithmParams

    def sanity_check(self, data: TrainingData) -> None:
        if data.n == 0:
            raise ValueError("empty TrainingData")

    @staticmethod
    def _to_coo(pd: TrainingData):
        # the streaming read already index-mapped everything: this is a
        # zero-copy repackaging, not a conversion
        coo = RatingsCOO(
            user_idx=pd.user_idx,
            item_idx=pd.item_idx,
            rating=pd.rating,
            n_users=len(pd.user_ids),
            n_items=len(pd.item_ids),
        )
        return coo, pd.user_ids, pd.item_ids

    @staticmethod
    def _als_params(p: ALSAlgorithmParams) -> ALSParams:
        return ALSParams(
            rank=p.rank, iterations=p.num_iterations, reg=p.lambda_,
            implicit=p.implicit_prefs, alpha=p.alpha,
            seed=0 if p.seed is None else p.seed,
            bf16_gather=p.bf16_gather,
        )

    @classmethod
    def train_many(cls, ctx: WorkflowContext, pd: TrainingData,
                   params_list) -> List[ALSModel]:
        """Grid fan-out (`pio eval`): the id maps + bucketed layout
        build once, and candidates differing only in lambda/alpha share
        one compiled executable (reg/alpha are traced scalars — see
        models/als.als_train_many). SURVEY.md §2d P4."""
        from predictionio_tpu.models.als import als_train_many

        coo, user_ids, item_ids = cls._to_coo(pd)
        results = als_train_many(
            coo, [cls._als_params(p) for p in params_list], mesh=ctx.mesh)
        return [ALSModel(U, V, user_ids, item_ids) for U, V in results]

    def train(self, ctx: WorkflowContext, pd: TrainingData) -> ALSModel:
        p: ALSAlgorithmParams = self.params
        with tracing.span("als.index") as sp:
            coo, user_ids, item_ids = self._to_coo(pd)
            sp.set_attr("nnz", int(coo.nnz))
        U, V = als_train(
            coo,
            self._als_params(p),
            mesh=ctx.mesh,
            # restart-from-checkpoint (run_train --resume): save V every
            # checkpoint_every iterations under the workflow's ckpt dir
            checkpointer=ctx.checkpointer("als"),
            checkpoint_every=p.checkpoint_every,
        )
        return ALSModel(U, V, user_ids, item_ids)

    def predict(self, model: ALSModel, query: Dict[str, Any]) -> Dict[str, Any]:
        user = str(query["user"])
        if "item" in query:  # rating-prediction shape (used by evaluation)
            r = model.predict_rating(user, str(query["item"]))
            return {"itemScores": (
                [{"item": str(query["item"]), "score": r}] if r is not None else [])}
        num = int(query.get("num", 10))
        return {"itemScores": model.recommend_products(user, num)}

    #: serve_topk_batch skips AOT-bucket PAD sentinels inline (their
    #: slots come back None), so the deploy layer can hand us the
    #: padded batch directly
    accepts_padding = True

    def batch_predict(self, model: ALSModel, queries) -> List[Dict[str, Any]]:
        """Micro-batched serving (`pio deploy --batching`, batchpredict,
        evaluation): all top-k-shaped queries in the batch score in ONE
        device dispatch via the shared `models/als.serve_topk_batch`.
        Rating-prediction shapes and cold users fall back per-query."""
        from predictionio_tpu.models.als import serve_topk_batch

        return serve_topk_batch(
            model._device_scorer(), model.user_ids, model._item_inv,
            queries, fallback=lambda q: self.predict(model, q),
            per_query=lambda q: "item" in q)

    @classmethod
    def sweep_programs(cls, ctx: WorkflowContext, pd: TrainingData,
                       params_list, qa, metric):
        """Distributed `pio eval` (core/sweep.py): candidates sharing
        (rank, iterations, implicit, seed, bf16) bucket into ONE
        vmapped train+score program over stacked [lambda, alpha] rows
        — the canonical regularization grid compiles once per rank.
        Held-out pairs are mapped to the fold's dense ids here; cold
        pairs (user/item unseen by the trained fold) get valid=False,
        mirroring NegRMSE's skip-empty-prediction convention."""
        if getattr(metric, "sweep_kind", None) != "sq_err":
            return None
        from predictionio_tpu.core.sweep import SweepProgram
        from predictionio_tpu.models.als import als_prepare, als_sweep_program

        coo, user_ids, item_ids = cls._to_coo(pd)
        prep = als_prepare(coo)
        n = len(qa)
        users = np.zeros(n, np.int32)
        items = np.zeros(n, np.int32)
        ratings = np.zeros(n, np.float32)
        valid = np.zeros(n, bool)
        for j, (q, a) in enumerate(qa):
            uidx = user_ids.get(str(q.get("user")))
            iidx = (item_ids.get(str(q["item"])) if "item" in q else None)
            if uidx is not None and iidx is not None:
                users[j], items[j], valid[j] = uidx, iidx, True
            ratings[j] = float(a)
        device = (ctx.mesh.devices.flat[0] if ctx.mesh is not None
                  else None)
        groups: Dict[tuple, List[int]] = {}
        for i, p in enumerate(params_list):
            key = (int(p.rank), int(p.num_iterations),
                   bool(p.implicit_prefs),
                   0 if p.seed is None else int(p.seed),
                   bool(p.bf16_gather))
            groups.setdefault(key, []).append(i)
        progs = []
        for idxs in groups.values():
            p0 = cls._als_params(params_list[idxs[0]])
            geometry, build, data = als_sweep_program(
                prep, p0, users, items, ratings, valid, device=device)
            hyper = np.asarray(
                [[params_list[i].lambda_, params_list[i].alpha]
                 for i in idxs], np.float32)
            progs.append(SweepProgram(geometry, build, hyper, data, idxs))
        return progs

    def aot_warm(self, model: ALSModel, ladder, ks=(16,)):
        """Compile the gather→score→top-k serving executable for every
        (bucket, k) before traffic arrives (server/aot warmup contract);
        host-path catalogs (no resident scorer) have nothing to warm."""
        scorer = model._device_scorer()
        if scorer is None:
            return {"targets": 0, "compiled": 0, "cached": 0}
        return scorer.warm_buckets(ladder, ks)

    # structured persistence: a pickled head, then the factors' own
    # buffers — float32 factors do not compress, and the store writes
    # the parts as they lie in memory (utils/model_parts.py)
    def save_model(self, model: ALSModel, instance_dir: Optional[str]
                   ) -> List[Any]:
        return model_parts.pack_named({
            "user_ids": model.user_ids.to_dict(),
            "item_ids": model.item_ids.to_dict(),
        }, U=model.U, V=model.V)

    def load_model(self, blob: Optional[bytes], instance_dir: Optional[str]) -> ALSModel:
        assert blob is not None
        d, arrs = model_parts.unpack_named(blob)
        return ALSModel(arrs["U"], arrs["V"],
                        BiMap(d["user_ids"]), BiMap(d["item_ids"]))


def engine_factory() -> Engine:
    return Engine(
        data_source_cls=RecDataSource,
        preparator_cls=RecPreparator,
        algorithm_cls_map={"als": ALSAlgorithm},
        serving_cls=FirstServing,
    )


# -- evaluation (pio eval out of the box) -------------------------------------


class NegRMSE(Metric):
    """-RMSE of predicted vs held-out ratings over the eval folds
    (higher is better, so the evaluator's argmax picks the lowest
    error). Cold (user, item) pairs — unknown to the trained fold —
    are skipped, the OptionAverageMetric convention."""

    higher_is_better = True
    #: distributed sweeps (core/sweep.py) accumulate (Σ sq_err, #warm)
    #: on device; sweep_finalize folds them into the same -RMSE
    sweep_kind = "sq_err"

    def sweep_finalize(self, stat_sum: float, stat_count: float) -> float:
        import math

        return (-math.sqrt(stat_sum / stat_count) if stat_count > 0
                else float("nan"))

    def calculate(self, ctx, eval_data):
        import math

        errs = []
        for _, qpa in eval_data:
            for q, p, a in qpa:
                scores = p.get("itemScores", [])
                if scores and scores[0].get("score") is not None:
                    errs.append((float(scores[0]["score"]) - float(a)) ** 2)
        return (-math.sqrt(sum(errs) / len(errs)) if errs
                else float("nan"))

    @property
    def header(self) -> str:
        return "NegRMSE"


class RecEvaluation(Evaluation):
    engine_factory = staticmethod(engine_factory)
    metric = NegRMSE()


class DefaultGrid(EngineParamsGenerator):
    """Rank/λ candidates over 2 folds; app via $PIO_EVAL_APP_NAME."""

    @property
    def engine_params_list(self):
        import os

        app = os.environ.get("PIO_EVAL_APP_NAME", "MyApp1")
        return [EngineParams(
            data_source_params=DataSourceParams(app_name=app, eval_k=2),
            algorithms_params=[("als", ALSAlgorithmParams(
                rank=r, num_iterations=8, lambda_=lam, seed=3))])
            for r in (8, 16) for lam in (0.01, 0.1)]
