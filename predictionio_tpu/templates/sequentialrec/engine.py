"""Sequential Recommendation template: self-attentive next-item model.

No counterpart in the reference (it has no sequence models — SURVEY.md
§5); this template extends the gallery with the framework's long-context
model family (:mod:`predictionio_tpu.models.seq_rec`, SASRec-style).
DASE shape mirrors the other recommenders:

- DataSource: interaction events (default ``view``/``buy``/``rate``)
  grouped per user, ordered by eventTime → item-id sequences.
- Algorithm: causal-transformer next-item model; one compiled training
  program; ring attention over a mesh sequence axis for long histories.
- Serving: the user's recent history is read LIVE from the event store
  at query time (like the e-commerce template's seen-items rule), so
  new events shift predictions without retraining.

    POST /queries.json {"user": "u1", "num": 4}
    → {"itemScores": [{"item": "i9", "score": 3.1}, ...]}

The backbone is chosen by the algorithm's parameters: an
``architecture`` object — a published config's keys, its ``model_type``
naming the block stack that trains on packed histories
(:func:`predictionio_tpu.models.seq_backbone.backbone`:
``glm4_moe_lite`` — latent attention, sparse experts, an MTP module;
``lfm2_moe`` — gated short convolutions, grouped-query attention,
sparse experts, a tied head; ``smallthinker`` — window and full
attention, a router before attention; ``sdar_moe`` — trained by block
diffusion on a clean and a noised copy of every history) — or, absent,
the SASRec stack.

Optional query keys: ``history`` (explicit item list overriding the
live lookup — supports anonymous sessions), ``blackList``.
"""

from __future__ import annotations

import pickle
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
from predictionio_tpu.models.seq_rec import (
    SeqRecParams,
    seq_rec_scores,
    seq_rec_train,
)
from predictionio_tpu.utils import model_parts, tracing
from predictionio_tpu.utils.bimap import BiMap

#: a model blob whose arrays follow its pickled head as raw bytes
_RAW_MAGIC = b"PIOSEQRAW1\n"


def _backbone(model_type: Optional[str]):
    """The block-stack backbone an ``architecture`` (or a saved model)
    names; None = the default one. Imported here: the table's module
    imports jax."""
    from predictionio_tpu.models.seq_backbone import backbone

    return backbone(model_type)


@dataclass
class DataSourceParams:
    app_name: str = ""
    event_names: List[str] = field(
        default_factory=lambda: ["view", "buy", "rate"])


@dataclass
class TrainingData:
    app_name: str
    # per user: item ids ordered by event time (strings, raw)
    sequences: Dict[str, List[str]]


class SeqDataSource(DataSource):
    ParamsClass = DataSourceParams

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        """Stream events into columnar (user, item) arrays (O(chunk)
        transient Event objects — ``data/pipeline``), then one STABLE
        sort by user groups each user's items. Time order inside each
        group comes for free: the EventStore.find contract is
        "ordered by eventTime asc", and a stable sort preserves it."""
        from predictionio_tpu.data.store import read_training_interactions

        p: DataSourceParams = self.params
        data = read_training_interactions(
            p.app_name, entity_type="user", target_entity_type="item",
            event_names=p.event_names, storage=ctx.storage)
        uu, ii, _ones = data.arrays()
        if uu.size == 0:
            raise ValueError("no interaction events found")
        order = np.argsort(uu, kind="stable")
        uu, ii = uu[order], ii[order]
        i_inv = data.item_ids.inverse()
        u_inv = data.user_ids.inverse()
        seqs: Dict[str, List[str]] = {}
        bounds = np.concatenate(
            ([0], np.nonzero(np.diff(uu))[0] + 1, [uu.size]))
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            seqs[u_inv[int(uu[lo])]] = [i_inv[int(j)] for j in ii[lo:hi]]
        return TrainingData(p.app_name, seqs)

    def read_eval(self, ctx: WorkflowContext):
        """Leave-one-out next-item evaluation (the standard SASRec
        protocol): each user's LAST item is held out; the query replays
        the remaining history through the anonymous-session path, so
        eval needs no serving-time storage."""
        td = self.read_training(ctx)
        train_seqs: Dict[str, List[str]] = {}
        qa = []
        for u, seq in td.sequences.items():
            if len(seq) >= 3:
                train_seqs[u] = seq[:-1]
                qa.append(({"history": seq[:-1], "num": 10}, seq[-1]))
            else:
                train_seqs[u] = seq
        if not qa:
            raise ValueError(
                "no user has a sequence of length ≥ 3 to hold out")
        return [(TrainingData(td.app_name, train_seqs), {"fold": 0}, qa)]


@dataclass
class SeqRecAlgorithmParams:
    hidden: int = 64
    num_blocks: int = 2
    num_heads: int = 2
    seq_len: int = 64
    epochs: int = 20
    lr: float = 1e-3
    batch_size: int = 128
    seed: int = 7
    # serving: which events form the live history
    history_events: List[str] = field(
        default_factory=lambda: ["view", "buy", "rate"])
    # sequential consumption is often repeat-friendly (music, groceries);
    # flip on to ban already-seen items like the ALS recommenders do
    exclude_seen: bool = False
    # the backbone: None = the SASRec stack above; an object holding a
    # published config's keys (and the training job's: seq_len,
    # seqs_per_step, ep_size, …) = the block stack its ``model_type``
    # names (models/seq_backbone: glm4_moe_lite — the default —,
    # lfm2_moe, smallthinker, sdar_moe) on packed histories. ``hidden`` … ``batch_size`` above
    # are then unused; ``epochs``, ``lr`` and ``seed`` apply.
    architecture: Optional[Dict[str, Any]] = None


class SeqRecModel:
    def __init__(self, params: Dict, item_ids: BiMap, app_name: str,
                 hp: SeqRecParams, algo_params: "SeqRecAlgorithmParams",
                 losses: np.ndarray, model_type: Optional[str] = None
                 ) -> None:
        self.params = params
        #: the block-stack backbone that reads ``params`` and ``hp``
        #: (its config); None = the SASRec stack
        self.model_type = model_type
        self._on_device = None
        self.item_ids = item_ids  # raw item id → 1-based index
        self._inv = item_ids.inverse()
        self.app_name = app_name
        self.hp = hp
        self.algo_params = algo_params
        self.losses = losses

    def live_history(self, user: str, storage) -> List[str]:
        # only the last seq_len interactions can influence the model; with
        # exclude_seen the FULL history is needed to ban every seen item
        limit = None if self.algo_params.exclude_seen else self.hp.seq_len
        evs = event_store.find_by_entity(
            self.app_name, "user", user,
            event_names=self.algo_params.history_events,
            target_entity_type="item", limit=limit, latest=True,
            storage=storage)
        ordered = sorted(evs, key=lambda e: e.event_time)
        return [e.target_entity_id for e in ordered if e.target_entity_id]

    def device_params(self) -> Dict:
        """The parameters on the device, put there once (a loaded
        model's arrays are host views of its 2.8 GB blob)."""
        if self._on_device is None:
            import jax

            self._on_device = jax.device_put(self.params)
        return self._on_device

    def next_items(self, history_raw: List[str], num: int,
                   black_list: Optional[List[str]] = None
                   ) -> List[Dict[str, Any]]:
        hist = [self.item_ids[i] + 1 for i in history_raw
                if i in self.item_ids]
        if self.model_type is None:
            scores = seq_rec_scores(self.params, hist, self.hp)  # PAD = -inf
        else:
            # rows past the catalog (a vocabulary slice's spare rows)
            # are no items
            scores = _backbone(self.model_type).next_item_scores(
                self.device_params(), hist, self.hp)[:len(self.item_ids) + 1]
        banned = set(black_list or [])
        if self.algo_params.exclude_seen:
            banned |= set(history_raw)
        for raw in banned:  # ban by -inf, then one partial top-k (als.py shape)
            idx = self.item_ids.get(raw)
            if idx is not None:
                scores[idx + 1] = -np.inf
        num = min(num, len(self.item_ids))
        top = np.argpartition(-scores, num)[:num]
        top = top[np.argsort(-scores[top])]
        return [{"item": self._inv[int(i) - 1], "score": float(scores[i])}
                for i in top if np.isfinite(scores[i])]


class SeqRecAlgorithm(Algorithm):
    ParamsClass = SeqRecAlgorithmParams

    def sanity_check(self, data: TrainingData) -> None:
        if not any(len(s) >= 2 for s in data.sequences.values()):
            raise ValueError("no user has a sequence of length ≥ 2")

    def train(self, ctx: WorkflowContext, pd: TrainingData) -> SeqRecModel:
        p: SeqRecAlgorithmParams = self.params
        with tracing.span("seqrec.index") as sp:
            item_ids = BiMap.string_int(
                i for seq in pd.sequences.values() for i in seq)
            # vocab ids are 1-based (0 = PAD)
            sequences = [[item_ids[i] + 1 for i in seq]
                         for seq in pd.sequences.values()]
            sp.set_attr("items", len(item_ids))
        # the workflow's per-run checkpoint dir enables mid-train
        # restart-from-checkpoint (SURVEY §5), like the ALS/two-tower
        # templates
        ckpt_dir = None
        if ctx.checkpoint_dir:
            import os

            ckpt_dir = os.path.join(ctx.checkpoint_dir, "seq_rec")
        if p.architecture is not None:
            backbone = _backbone(p.architecture.get("model_type"))
            cfg = backbone.config.from_architecture(p.architecture)
            params, losses = backbone.train(sequences, cfg, p.epochs, p.lr,
                                            p.seed, checkpoint_dir=ckpt_dir)
            return SeqRecModel(params, item_ids, pd.app_name, cfg, p, losses,
                               backbone.model_type)
        hp = SeqRecParams(hidden=p.hidden, num_blocks=p.num_blocks,
                          num_heads=p.num_heads, seq_len=p.seq_len,
                          epochs=p.epochs, lr=p.lr,
                          batch_size=p.batch_size, seed=p.seed,
                          checkpoint_dir=ckpt_dir)
        # meshConf routes attention through ring attention over the mesh's
        # sequence axis (falls back to local if seq_len doesn't divide)
        params, losses = seq_rec_train(sequences, len(item_ids), hp,
                                       mesh=ctx.mesh)
        return SeqRecModel(params, item_ids, pd.app_name, hp, p, losses)

    def predict(self, model: SeqRecModel, query: Dict[str, Any]
                ) -> Dict[str, Any]:
        num = int(query.get("num", 10))
        if "history" in query:  # anonymous-session path
            history = [str(i) for i in query["history"]]
        else:
            history = model.live_history(str(query["user"]),
                                         self.serving_storage)
        return {"itemScores": model.next_items(
            history, num, query.get("blackList"))}

    def save_model(self, model: SeqRecModel, instance_dir: Optional[str]
                   ) -> List[Any]:
        """``magic | head length | pickled head`` and then each array's
        own buffer, as parts (utils/model_parts.py): no compression and
        NO copy of the parameters — a join or a pickle of the tree
        would copy 2.8 GB of a 706 M parameter model, zlib would take
        minutes."""
        import jax

        leaves, treedef = jax.tree.flatten(
            jax.tree.map(np.asarray, model.params))
        return model_parts.pack_arrays({
            # the tree with each array's number in its place
            "tree": jax.tree.unflatten(treedef, range(len(leaves))),
            "item_ids": model.item_ids.to_dict(),
            "app_name": model.app_name,
            "hp": model.hp,
            "model_type": model.model_type,
            "algo_params": model.algo_params,
            "losses": model.losses,
        }, leaves, _RAW_MAGIC)

    def load_model(self, blob: Optional[bytes],
                   instance_dir: Optional[str]) -> SeqRecModel:
        assert blob is not None
        got = model_parts.unpack_arrays(blob, _RAW_MAGIC)
        if got is None:                         # saved the old way
            d = pickle.loads(blob)
            params = d["params"]
        else:
            import jax

            d, leaves = got
            params = jax.tree.map(lambda i: leaves[i], d["tree"])
        # a model saved before the backbones had a table says nothing:
        # its config is then the default backbone's
        model_type = d.get("model_type") or (
            None if isinstance(d["hp"], SeqRecParams)
            else _backbone(None).model_type)
        return SeqRecModel(params, BiMap(d["item_ids"]), d["app_name"],
                           d["hp"], d["algo_params"], d["losses"],
                           model_type)


def engine_factory() -> Engine:
    return Engine(
        data_source_cls=SeqDataSource,
        preparator_cls=IdentityPreparator,
        algorithm_cls_map={"seqrec": SeqRecAlgorithm},
        serving_cls=FirstServing,
    )


# -- evaluation (pio eval out of the box) -------------------------------------


class HitRate(AverageMetric):
    """1 if the held-out item appears in the top-k, else 0 — averaged
    over users (hit rate @ k, the SASRec leave-one-out metric)."""

    def __init__(self, k: int = 10) -> None:
        self.k = k

    def calculate_one(self, query, predicted, actual) -> float:
        items = [s["item"] for s in predicted.get("itemScores", [])][: self.k]
        return 1.0 if actual in items else 0.0

    @property
    def header(self) -> str:
        return f"HitRate@{self.k}"


class SeqRecEvaluation(Evaluation):
    engine_factory = staticmethod(engine_factory)
    metric = HitRate(10)
    other_metrics = (HitRate(1),)


def _candidate(app_name: str, hidden: int) -> EngineParams:
    return EngineParams(
        data_source_params=DataSourceParams(app_name=app_name),
        algorithms_params=[("seqrec", SeqRecAlgorithmParams(
            hidden=hidden, num_blocks=1, num_heads=2, seq_len=32,
            epochs=30))],
    )


class DefaultGrid(EngineParamsGenerator):
    """Two hidden-size candidates. App name from $PIO_EVAL_APP_NAME
    (edit or subclass for real use — the reference's generators
    hardcode the app name the same way):

        PIO_EVAL_APP_NAME=MyApp pio eval \\
          predictionio_tpu.templates.sequentialrec.engine:SeqRecEvaluation \\
          predictionio_tpu.templates.sequentialrec.engine:DefaultGrid
    """

    @property
    def engine_params_list(self):
        import os

        app = os.environ.get("PIO_EVAL_APP_NAME", "MyApp1")
        return [_candidate(app, 32), _candidate(app, 64)]
