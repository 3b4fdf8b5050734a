"""Similar Product template: item-to-item similarity from ALS factors.

Behavioral equivalent of the reference's similar-product template
(reference: [U] examples/scala-parallel-similarproduct/ — "view" events
→ implicit ALS; query = list of liked items → top-K cosine-similar
items, with category/whitelist/blacklist filters; SURVEY.md §2c).

    POST /queries.json {"items": ["i1", "i3"], "num": 4,
                        "categories": ["c1"], "blackList": ["i5"]}
    → {"itemScores": [{"item": "i2", "score": 0.87}, ...]}
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from predictionio_tpu.controller import (
    Algorithm,
    AverageMetric,
    DataSource,
    Engine,
    EngineParams,
    EngineParamsGenerator,
    Evaluation,
    FirstServing,
    IdentityPreparator,
    WorkflowContext,
)
from predictionio_tpu.data import store as event_store
from predictionio_tpu.models.als import (
    ALSParams,
    RatingsCOO,
    als_train,
    similar_items,
)
from predictionio_tpu.utils import model_parts
from predictionio_tpu.utils.bimap import BiMap


@dataclass
class DataSourceParams:
    app_name: str = ""
    event_names: List[str] = field(default_factory=lambda: ["view"])


@dataclass
class TrainingData:
    """Columnar, index-mapped view events (streaming read — see
    ``data/pipeline.read_interactions``; O(chunk + vocab) transient
    host memory, event ORDER preserved for the last-view eval split).
    ``views`` materializes (user, item) string pairs lazily for
    small-data consumers."""

    user_idx: np.ndarray   # int32 [n], event order
    item_idx: np.ndarray   # int32 [n]
    user_ids: BiMap
    item_ids: BiMap
    item_categories: Dict[str, List[str]]  # from $set item properties

    @property
    def n(self) -> int:
        return int(self.user_idx.shape[0])

    @property
    def views(self) -> List[tuple]:
        u_inv = self.user_ids.inverse()
        i_inv = self.item_ids.inverse()
        return [(u_inv[int(u)], i_inv[int(i)])
                for u, i in zip(self.user_idx, self.item_idx)]

    def subset(self, mask: np.ndarray) -> "TrainingData":
        """Rows where ``mask`` holds, vocabularies trimmed (eval-fold
        cold-entity rule — see ``data/pipeline.subset_columnar``)."""
        from predictionio_tpu.data.pipeline import subset_columnar

        uu, ii, u_ids, i_ids = subset_columnar(
            mask, self.user_idx, self.item_idx,
            self.user_ids, self.item_ids)
        return TrainingData(uu, ii, u_ids, i_ids, self.item_categories)


class SimilarProductDataSource(DataSource):
    ParamsClass = DataSourceParams

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        from predictionio_tpu.data.store import read_training_interactions

        p: DataSourceParams = self.params
        data = read_training_interactions(
            p.app_name, entity_type="user", target_entity_type="item",
            event_names=p.event_names, storage=ctx.storage)
        uu, ii, _ones = data.arrays()
        if uu.size == 0:
            raise ValueError("no view events found; import events before training")
        cats = {
            entity_id: list(props.get("categories") or [])
            for entity_id, props in event_store.aggregate_properties(
                p.app_name, "item", storage=ctx.storage).items()
        }
        return TrainingData(uu, ii, data.user_ids, data.item_ids, cats)

    def read_eval(self, ctx: WorkflowContext):
        """Item-to-item retrieval protocol: each user's LAST viewed
        item is held out; the query carries the user's remaining items
        and the held-out one must rank in the top-k similars."""
        td = self.read_training(ctx)
        n_u = len(td.user_ids)
        counts = np.bincount(td.user_idx, minlength=n_u)
        last_row = np.full(n_u, -1, np.int64)
        last_row[td.user_idx] = np.arange(td.n)  # later rows overwrite
        held = np.sort(last_row[(last_row >= 0) & (counts >= 3)])
        if held.size == 0:
            raise ValueError("no user has >= 3 views to hold one out")
        keep_mask = np.ones(td.n, bool)
        keep_mask[held] = False
        u_inv = td.user_ids.inverse()
        i_inv = td.item_ids.inverse()
        held_users = set(td.user_idx[held].tolist())
        by_user: Dict[int, List[str]] = {}
        for u, i in zip(td.user_idx[keep_mask].tolist(),
                        td.item_idx[keep_mask].tolist()):
            if u in held_users:
                by_user.setdefault(u, []).append(i_inv[i])
        qa = [({"items": by_user[int(td.user_idx[j])], "num": 10},
               i_inv[int(td.item_idx[j])]) for j in held]
        return [(td.subset(keep_mask), {"fold": 0}, qa)]


@dataclass
class ALSAlgorithmParams:
    rank: int = 10
    num_iterations: int = 10
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: Optional[int] = None
    # -- approximate item-to-item retrieval (predictionio_tpu/ann):
    # builds the PQ index over the NORMALIZED item factors at train
    # time, so the ADC scan + exact re-rank computes cosine directly.
    # engine.json spelling: ann, annM, annK, annShortlist, annShards.
    ann: bool = False
    ann_m: int = 5            # subspaces (must divide rank)
    ann_k: int = 256          # centroids per subspace
    ann_shortlist: int = 128  # k′ re-rank candidates
    ann_shards: int = 0       # serving-mesh width hint (> 1 = sharded)


class SimilarProductModel:
    def __init__(self, V: np.ndarray, item_ids: BiMap,
                 item_categories: Dict[str, List[str]],
                 ann_index=None, ann_shortlist: int = 128,
                 ann_shards: int = 0) -> None:
        self.V = V
        self.item_ids = item_ids
        self._inv = item_ids.inverse()
        self.item_categories = item_categories
        self.ann_index = ann_index
        self.ann_shortlist = ann_shortlist
        self.ann_shards = ann_shards
        self._Vn: Optional[np.ndarray] = None
        self._scorer = None

    def _normalized(self) -> np.ndarray:
        if self._Vn is None:
            norms = np.linalg.norm(self.V, axis=1, keepdims=True)
            self._Vn = (self.V / np.maximum(norms, 1e-12)).astype(
                np.float32)
        return self._Vn

    def _device_scorer(self):
        """Lazy ANN scorer over the normalized corpus with itself as
        the query table: ``U[i] · V[j] = cos(v_i, v_j)``, so a
        single-liked-item query is ONE ADC-shortlist dispatch — the
        same serving program (sharded or not) as the user-to-item
        templates. Multi-item queries keep the host mean-direction
        path (`models/als.similar_items`)."""
        if self.ann_index is None:
            return None
        from predictionio_tpu.ann import maybe_ann_scorer

        Vn = self._normalized()
        s = maybe_ann_scorer(Vn, Vn, self.ann_index, self._scorer,
                             shortlist=self.ann_shortlist,
                             shards=self.ann_shards)
        if s is not None:
            self._scorer = s
        return s

    def query(self, items: List[str], num: int,
              categories: Optional[List[str]] = None,
              white_list: Optional[List[str]] = None,
              black_list: Optional[List[str]] = None) -> List[Dict[str, Any]]:
        idxs = np.asarray([self.item_ids[i] for i in items
                           if i in self.item_ids], np.int32)
        if idxs.size == 0:
            return []
        # over-fetch so post-filters still fill `num`
        fetch = min(len(self.item_ids), num + idxs.size + 50)
        scorer = self._device_scorer() if idxs.size == 1 else None
        if scorer is not None:
            top, scores = scorer.recommend(int(idxs[0]), fetch,
                                           exclude=idxs)
        else:
            top, scores = similar_items(self.V, idxs, fetch)
        cats = set(categories or [])
        white = set(white_list or [])
        black = set(black_list or [])
        out = []
        for i, s in zip(top, scores):
            item = self._inv[int(i)]
            if white and item not in white:
                continue
            if item in black:
                continue
            if cats and not cats.intersection(self.item_categories.get(item, [])):
                continue
            out.append({"item": item, "score": float(s)})
            if len(out) >= num:
                break
        return out


class ALSAlgorithm(Algorithm):
    ParamsClass = ALSAlgorithmParams

    def sanity_check(self, data: TrainingData) -> None:
        if data.n == 0:
            raise ValueError("empty view data")

    @staticmethod
    def _to_coo(pd: TrainingData) -> RatingsCOO:
        # repeat-view counts by linearized (user, item) pair — the
        # vectorized Counter (no per-event Python objects)
        n_items = len(pd.item_ids)
        lin = pd.user_idx.astype(np.int64) * n_items + pd.item_idx
        uniq, cnt = np.unique(lin, return_counts=True)
        return RatingsCOO((uniq // n_items).astype(np.int32),
                          (uniq % n_items).astype(np.int32),
                          cnt.astype(np.float32),
                          len(pd.user_ids), n_items)

    @staticmethod
    def _als_params(p: ALSAlgorithmParams) -> ALSParams:
        return ALSParams(rank=p.rank, iterations=p.num_iterations,
                         reg=p.lambda_, implicit=True, alpha=p.alpha,
                         seed=0 if p.seed is None else p.seed)

    @staticmethod
    def _maybe_index(V: np.ndarray, p: ALSAlgorithmParams):
        """PQ index over the NORMALIZED factors (cosine = inner
        product there); None when ANN is off or the rank doesn't split
        into ``ann_m`` subspaces."""
        if not p.ann:
            return None
        from predictionio_tpu import ann

        norms = np.linalg.norm(V, axis=1, keepdims=True)
        Vn = (V / np.maximum(norms, 1e-12)).astype(np.float32)
        return ann.build_index(
            Vn, p.ann_m, min(p.ann_k, max(2, V.shape[0])),
            shards=(int(p.ann_shards) if p.ann_shards
                    and int(p.ann_shards) > 1 else None))

    @classmethod
    def train_many(cls, ctx: WorkflowContext, pd: TrainingData,
                   params_list) -> List[SimilarProductModel]:
        """Grid fan-out: one COO + prepared layout for every candidate;
        lambda/alpha-only candidates share a compiled executable
        (models/als.als_train_many)."""
        from predictionio_tpu.models.als import als_train_many

        coo = cls._to_coo(pd)
        results = als_train_many(
            coo, [cls._als_params(p) for p in params_list], mesh=ctx.mesh)
        return [SimilarProductModel(V, pd.item_ids, pd.item_categories,
                                    ann_index=cls._maybe_index(V, p),
                                    ann_shortlist=p.ann_shortlist,
                                    ann_shards=p.ann_shards)
                for p, (_, V) in zip(params_list, results)]

    def train(self, ctx: WorkflowContext, pd: TrainingData) -> SimilarProductModel:
        p: ALSAlgorithmParams = self.params
        _, V = als_train(self._to_coo(pd), self._als_params(p),
                         mesh=ctx.mesh)
        return SimilarProductModel(V, pd.item_ids, pd.item_categories,
                                   ann_index=self._maybe_index(V, p),
                                   ann_shortlist=p.ann_shortlist,
                                   ann_shards=p.ann_shards)

    def predict(self, model: SimilarProductModel, query: Dict[str, Any]) -> Dict[str, Any]:
        return {"itemScores": model.query(
            [str(i) for i in query.get("items", [])],
            int(query.get("num", 10)),
            query.get("categories"),
            query.get("whiteList"),
            query.get("blackList"),
        )}

    def save_model(self, model: SimilarProductModel,
                   instance_dir: Optional[str]) -> List[Any]:
        d = {
            "item_ids": model.item_ids.to_dict(),
            "cats": model.item_categories,
            "ann_shortlist": model.ann_shortlist,
            "ann_shards": model.ann_shards,
        }
        # same persistence contract as the twotower template: wire
        # bytes inside the blob, plus the fsck-auditable sidecar
        # layout when the model store has a real directory
        if model.ann_index is not None:
            from predictionio_tpu import ann

            d["ann_index"] = model.ann_index.to_bytes()
            if instance_dir:
                ann.save_index(model.ann_index, instance_dir)
        return model_parts.pack_named(d, V=model.V)

    def load_model(self, blob: Optional[bytes], instance_dir: Optional[str]) -> SimilarProductModel:
        assert blob is not None
        d, arrs = model_parts.unpack_named(blob)
        ann_index = None
        if instance_dir:
            from predictionio_tpu import ann

            ann_index = ann.load_index(instance_dir)
        if ann_index is None and d.get("ann_index") is not None:
            from predictionio_tpu.ann import PQIndex

            ann_index = PQIndex.from_bytes(d["ann_index"])
        return SimilarProductModel(arrs["V"], BiMap(d["item_ids"]),
                                   d["cats"], ann_index=ann_index,
                                   ann_shortlist=d.get("ann_shortlist", 128),
                                   ann_shards=d.get("ann_shards", 0))


def engine_factory() -> Engine:
    return Engine(
        data_source_cls=SimilarProductDataSource,
        preparator_cls=IdentityPreparator,
        algorithm_cls_map={"als": ALSAlgorithm},
        serving_cls=FirstServing,
    )


# -- evaluation (pio eval out of the box) -------------------------------------


class HitRateAtK(AverageMetric):
    def __init__(self, k: int = 10) -> None:
        self.k = k

    def calculate_one(self, query, predicted, actual) -> float:
        items = [s["item"] for s in predicted.get("itemScores", [])][: self.k]
        return 1.0 if actual in items else 0.0

    @property
    def header(self) -> str:
        return f"HitRate@{self.k}"


class SPEvaluation(Evaluation):
    engine_factory = staticmethod(engine_factory)
    metric = HitRateAtK(10)


class DefaultGrid(EngineParamsGenerator):
    """Rank candidates; app via $PIO_EVAL_APP_NAME."""

    @property
    def engine_params_list(self):
        import os

        app = os.environ.get("PIO_EVAL_APP_NAME", "MyApp1")
        return [EngineParams(
            data_source_params=DataSourceParams(app_name=app),
            algorithms_params=[("als", ALSAlgorithmParams(rank=r))])
            for r in (8, 16)]
