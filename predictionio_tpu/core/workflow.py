"""Train / evaluation / deploy workflows.

Equivalent of the reference's CreateWorkflow + CoreWorkflow +
CreateServer.prepareDeploy (reference: [U] core/.../workflow/
{CreateWorkflow,CoreWorkflow,CreateServer}.scala — unverified, SURVEY.md
§3.1–3.2), minus the process gymnastics: where the reference execs
``spark-submit`` and stands up a SparkContext, we build a
:class:`WorkflowContext` with a device mesh in-process.

Train lifecycle (meta-store contract preserved):
INIT row → TRAINING → engine.train → persist per-algorithm models →
COMPLETED (or FAILED). Deploy loads the latest COMPLETED instance for
(engine_factory, variant-id).
"""

from __future__ import annotations

import contextlib
import json
import os
import pickle
import shutil
import traceback
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

from predictionio_tpu.controller.base import WorkflowContext, params_to_json
from predictionio_tpu.controller.engine import (
    Engine,
    EngineFactory,
    EngineParams,
    load_variant,
)
from predictionio_tpu.controller.evaluation import Evaluation, MetricEvaluatorResult
from predictionio_tpu.data.event import utcnow
from predictionio_tpu.parallel.mesh import MeshConfig, make_mesh
from predictionio_tpu.storage.meta import EngineInstance, EvaluationInstance
from predictionio_tpu.storage.registry import Storage, get_storage
from predictionio_tpu.utils import model_parts


def _algorithms_params_json(engine_params: EngineParams) -> str:
    return json.dumps([
        {"name": n, "params": params_to_json(p)}
        for n, p in engine_params.algorithms_params
    ])


def _build_context(
    storage: Storage,
    mesh_conf: Optional[Dict[str, Any]],
    verbose: int,
    instance_id: str,
    use_mesh: bool,
    checkpoint_dir: Optional[str] = None,
) -> WorkflowContext:
    mesh = None
    if use_mesh:
        mesh = make_mesh(MeshConfig.from_json(mesh_conf))
    return WorkflowContext(
        storage=storage, mesh=mesh, verbose=verbose, instance_id=instance_id,
        checkpoint_dir=checkpoint_dir,
    )


def _ckpt_root(storage: Storage, engine_factory: str, variant_id: str) -> str:
    safe = "".join(ch if ch.isalnum() else "_"
                   for ch in f"{engine_factory}_{variant_id}")
    return os.path.join(storage.config.home, "train_ckpt", safe)


#: first bytes of a ``model.bin`` that :func:`frame_models` laid out;
#: one from before starts with a pickle's 0x80
_FRAME_MAGIC = b"PIOMODEL1\n"


def frame_models(saved: Sequence[Any]) -> List[memoryview]:
    """The parts of one instance's ``model.bin``, from what each
    algorithm's ``save_model`` returned (None, ``bytes``, or a sequence
    of bytes-like parts), in order and WITHOUT joining them::

        magic | <Q n | header: n bytes of JSON | stretch | stretch ...

    The header says, per algorithm, ``null`` or where its stretch lies
    after the header (``offset``, ``bytes``) and whether ``load_model``
    is handed a ``view`` of it (the result was parts) or ``bytes`` (it
    was ``bytes``: an engine that knows nothing of parts gets back what
    it gave). Header and stretches are padded to a multiple of
    ``model_parts.ALIGN``, so that arrays an algorithm aligned in its
    own blob are aligned in the file."""
    header, body, at = [], [], 0
    for result in saved:
        if result is None:
            header.append(None)
            continue
        if at % model_parts.ALIGN:
            pad = memoryview(bytes(-at % model_parts.ALIGN))
            body.append(pad)
            at += pad.nbytes
        whole = isinstance(result, (bytes, bytearray, memoryview))
        views = [model_parts.byte_view(p)
                 for p in ([result] if whole else result)]
        n = sum(v.nbytes for v in views)
        header.append({"offset": at, "bytes": n, "view": not whole})
        body += views
        at += n
    # filled with spaces: JSON's own padding
    return [memoryview(model_parts.lead(
        _FRAME_MAGIC, json.dumps(header).encode("ascii"), b" "))] + body


def unframe_models(raw: bytes) -> List[Any]:
    """Per algorithm what ``load_model`` is handed, from the bytes of a
    ``model.bin``: None, ``bytes``, or a zero-copy read-only view of
    the algorithm's stretch. A blob that does not start with the magic
    was written before the framing: a pickled list of ``bytes``."""
    got = model_parts.split_lead(raw, _FRAME_MAGIC)
    if got is None:
        return pickle.loads(raw)
    header, body = got
    blobs = []
    for entry in json.loads(bytes(header)):
        if entry is None:
            blobs.append(None)
            continue
        stretch = body[entry["offset"]:entry["offset"] + entry["bytes"]]
        if stretch.nbytes != entry["bytes"]:
            raise ValueError(
                f"model blob is cut short: {len(raw)} bytes, its header "
                f"places {entry['bytes']} at {entry['offset']} of the "
                f"{body.nbytes} after it")
        blobs.append(stretch if entry["view"] else bytes(stretch))
    return blobs


@contextlib.contextmanager
def _train_run(engine_factory: str, verbose: int):
    """The ``train.run`` root span of one train verb — and, with
    ``PIO_PROFILE_DIR=<dir>``, a JAX profiler trace around it (xplane →
    Perfetto/TensorBoard; SURVEY.md §5). The trace starts BEFORE the
    root: an annotation made while no session runs is not recorded, and
    every verb span is one (``pio:train.run`` … in the host plane,
    utils/tracing.py). A verb that compiled says so in one ``compile:``
    line, verbose or not (a first ``pio train`` is mostly that);
    ``verbose`` prints the finished tree."""
    from predictionio_tpu.utils import compilecache, tracing

    try:
        with contextlib.ExitStack() as stack:
            profile_dir = os.environ.get("PIO_PROFILE_DIR")
            if profile_dir:
                import jax

                stack.enter_context(jax.profiler.trace(profile_dir))
            yield stack.enter_context(
                tracing.verb("train.run", engine_factory=engine_factory))
    finally:
        tree = tracing.last_verb("train.run")
        if tree:
            attrs = tree[0].get("attrs") or {}
            iid = attrs.get("instance_id") or "-"
            compiled = compilecache.compile_line(attrs)
            if compiled:
                print(f"[workflow {iid}] {compiled}", flush=True)
            if verbose:
                print(f"[workflow {iid}] train spans:\n"
                      + tracing.render_trace_tree(tree), flush=True)


def run_train(
    engine_factory: str,
    variant: Optional[Dict[str, Any]] = None,
    variant_path: Optional[str] = None,
    engine_params: Optional[EngineParams] = None,
    storage: Optional[Storage] = None,
    verbose: int = 0,
    use_mesh: bool = True,
    batch: str = "",
    resume: bool = False,
    scan_cache: Optional[bool] = None,
) -> str:
    """Train and persist one engine instance; returns its id.

    Exactly one of ``variant``/``variant_path``/``engine_params`` supplies
    parameters (variant = parsed engine.json dict). ``resume=True``
    (``pio train --resume``) keeps the per-(factory, variant) checkpoint
    directory from an interrupted run so iterative trainers restore the
    latest mid-train checkpoint and continue; by default a fresh run
    clears it (SURVEY.md §5 checkpoint/resume).

    ``scan_cache`` pins the columnar snapshot cache for this run:
    False = full rescan (``pio train --no-scan-cache`` — the escape
    hatch when a cached read is suspect), True = force-enable, None =
    the process default (``PIO_SCAN_CACHE`` env, on by default).
    """
    from predictionio_tpu.data.store import set_scan_cache
    from predictionio_tpu.parallel import distributed
    from predictionio_tpu.utils import compilecache, tracing

    # Multi-host (SURVEY.md §2d P5): when the PIO_* rendezvous vars are
    # set (or a Cloud-TPU slice announces itself), every host runs this
    # same function in lockstep — jax.distributed rendezvous here (before
    # the profiler of ``_train_run`` touches a backend), the coordinator
    # mints the instance id and owns all meta/model writes, barriers keep
    # hosts aligned around training.
    multi = distributed.initialize()
    # every stretch below is inside a named child span of ``train.run``,
    # so that the verb's wall time is accounted for by name
    # (docs/observability.md "Train verb")
    with _train_run(engine_factory, verbose) as root:
        with tracing.span("train.init"):
            compilecache.enable()
            coord = distributed.is_coordinator()

            storage = storage or get_storage()
            engine = EngineFactory.create(engine_factory)
            if variant_path is not None:
                variant = load_variant(variant_path)
            variant = variant or {}
            if engine_params is None:
                engine_params = engine.params_from_variant(variant)

            instance_id = storage.meta.new_instance_id() if coord else ""
            if multi:
                instance_id = distributed.broadcast_string(instance_id)
            root.set_attr("instance_id", instance_id)
            mesh_conf = (variant.get("meshConf") or variant.get("sparkConf")
                         or {})
            ei = EngineInstance(
                id=instance_id,
                status="INIT",
                start_time=utcnow(),
                end_time=None,
                engine_factory=engine_factory,
                engine_variant=str(variant.get("id", "")),
                batch=batch or str(variant.get("description", "")),
                env={},
                mesh_conf=mesh_conf,
                data_source_params=json.dumps(params_to_json(engine_params.data_source_params)),
                preparator_params=json.dumps(params_to_json(engine_params.preparator_params)),
                algorithms_params=_algorithms_params_json(engine_params),
                serving_params=json.dumps(params_to_json(engine_params.serving_params)),
            )
            if coord:
                storage.meta.insert_engine_instance(ei)
            ckpt_root = _ckpt_root(storage, engine_factory, ei.engine_variant)
            if coord and not resume:
                shutil.rmtree(ckpt_root, ignore_errors=True)
            if multi:
                distributed.barrier("pio_ckpt_ready")
            ctx = _build_context(storage, mesh_conf, verbose, instance_id,
                                 use_mesh, checkpoint_dir=ckpt_root)
        _prev_scan_cache = (set_scan_cache(scan_cache)
                            if scan_cache is not None else None)
        try:
            ei.status = "TRAINING"
            if coord:
                with tracing.span("train.init"):
                    storage.meta.update_engine_instance(ei)
            models = engine.train(ctx, engine_params)
            if ctx.timings:
                phases = ", ".join(f"{k}={v:.3f}s"
                                   for k, v in ctx.timings.items())
                ctx.log(f"train phases: {phases}")
            if multi:
                distributed.barrier("pio_train_done")

            # persist per-algorithm models (coordinator only under multi-host:
            # the trained arrays are replicated, one writer suffices)
            if coord:
                with tracing.span("train.save", instance_id=instance_id,
                                  algorithms=len(models)):
                    instance_dir = storage.models.model_dir(instance_id)
                    saved = []
                    with tracing.span("model.serialize") as sp:
                        for (name, algo), model in zip(
                                engine.make_algorithms(engine_params), models):
                            algo_dir = None
                            if instance_dir is not None:
                                algo_dir = os.path.join(instance_dir, name)
                                os.makedirs(algo_dir, exist_ok=True)
                            saved.append(algo.save_model(model, algo_dir))
                        parts = frame_models(saved)
                        sp.set_attr("bytes",
                                    sum(p.nbytes for p in parts[1:]))
                    with tracing.span("model.put") as sp:
                        streamed = storage.models.put_parts(instance_id,
                                                            parts)
                        sp.set_attr("bytes", sum(p.nbytes for p in parts))
                        sp.set_attr("parts", len(parts))
                        sp.set_attr("streamed", int(streamed))

                with tracing.span("train.finish"):
                    ei.status = "COMPLETED"
                    ei.end_time = utcnow()
                    storage.meta.update_engine_instance(ei)
                    # the run completed: its mid-train checkpoints are consumed
                    shutil.rmtree(ckpt_root, ignore_errors=True)
            if multi:
                distributed.barrier("pio_persist_done")
            root.set_attr("status", ei.status)
            return instance_id
        except Exception:
            ei.status = "FAILED"
            ei.end_time = utcnow()
            root.set_attr("status", ei.status)
            if coord:
                storage.meta.update_engine_instance(ei)
            traceback.print_exc()
            raise
        finally:
            if scan_cache is not None:
                set_scan_cache(_prev_scan_cache)


@dataclass
class DeployedEngine:
    """A trained engine loaded for serving: the resident-model bundle."""

    engine: Engine
    engine_params: EngineParams
    algorithms: List[Tuple[str, Any]]  # (name, Algorithm instance)
    models: List[Any]
    serving: Any
    instance: EngineInstance

    def query(self, query: Any) -> Any:
        q = self.serving.supplement(query)
        preds = [algo.predict(model, q)
                 for (_, algo), model in zip(self.algorithms, self.models)]
        return self.serving.serve(q, preds)

    def batch_query(self, queries: Sequence[Any]) -> List[Any]:
        """Answer a batch; AOT-bucket ``PAD`` sentinels (server/aot) pass
        through untouched: pad slots are never supplemented or served and
        come back as PAD so the batcher can slice them off. Algorithms
        that batch onto the device (``accepts_padding``) see the padded
        list inline — their executable was compiled for the bucket shape
        — while per-query algorithms only ever see real queries."""
        from predictionio_tpu.server.aot import PAD, is_pad

        qs = [q if is_pad(q) else self.serving.supplement(q)
              for q in queries]
        real = [q for q in qs if not is_pad(q)]
        per_algo = []
        for (_, algo), model in zip(self.algorithms, self.models):
            if getattr(algo, "accepts_padding", False) or len(real) == len(qs):
                per_algo.append(algo.batch_predict(model, qs))
            else:
                preds = algo.batch_predict(model, real)
                it = iter(preds)
                per_algo.append(
                    [None if is_pad(q) else next(it) for q in qs])
        return [
            PAD if is_pad(q)
            else self.serving.serve(q, [preds[i] for preds in per_algo])
            for i, q in enumerate(qs)
        ]


def prepare_deploy(
    engine_factory: Optional[str] = None,
    instance_id: Optional[str] = None,
    storage: Optional[Storage] = None,
    variant_id: str = "",
) -> DeployedEngine:
    """Load the latest COMPLETED instance (or a specific one) for serving
    (reference: CreateServer / engine.prepareDeploy, SURVEY.md §3.2)."""
    from predictionio_tpu.utils import compilecache

    compilecache.enable()
    storage = storage or get_storage()
    if instance_id is not None:
        ei = storage.meta.get_engine_instance(instance_id)
        if ei is None:
            raise ValueError(f"engine instance {instance_id!r} not found")
    else:
        if engine_factory is None:
            raise ValueError("need engine_factory or instance_id")
        ei = storage.meta.get_latest_completed_engine_instance(engine_factory, variant_id)
        if ei is None:
            raise ValueError(
                f"no COMPLETED engine instance for {engine_factory!r}; "
                "run `pio train` first")

    engine = EngineFactory.create(ei.engine_factory)
    # Rebuild EngineParams from the instance's recorded JSON
    variant = {
        "datasource": {"params": json.loads(ei.data_source_params)},
        "preparator": {"params": json.loads(ei.preparator_params)},
        "algorithms": json.loads(ei.algorithms_params),
        "serving": {"params": json.loads(ei.serving_params)},
    }
    engine_params = engine.params_from_variant(variant)
    algorithms = engine.make_algorithms(engine_params)

    raw = storage.models.get(ei.id)
    if raw is None:
        raise ValueError(f"no model blob for instance {ei.id}")
    blobs = unframe_models(raw)
    instance_dir = storage.models.model_dir(ei.id)
    models = []
    for (name, algo), blob in zip(algorithms, blobs):
        algo_dir = os.path.join(instance_dir, name) if instance_dir else None
        algo.set_serving_context(storage)
        models.append(algo.load_model(blob, algo_dir))
    serving = engine.serving_cls(engine_params.serving_params)
    return DeployedEngine(
        engine=engine, engine_params=engine_params, algorithms=algorithms,
        models=models, serving=serving, instance=ei)


def run_evaluation(
    evaluation: Evaluation,
    candidates: Sequence[EngineParams],
    storage: Optional[Storage] = None,
    verbose: int = 0,
    use_mesh: bool = True,
    evaluation_class: str = "",
    generator_class: str = "",
    distributed: bool = False,
    sweep_shards: int = 0,
) -> Tuple[str, MetricEvaluatorResult]:
    """Grid-search evaluation; persists an EvaluationInstance row the
    dashboard renders (reference: EvaluationWorkflow, SURVEY.md §3.4)
    plus a versioned ``leaderboard.json`` artifact next to it (the
    promotion gate's input — storage/leaderboard.py).

    ``distributed=True`` routes the grid through ``core/sweep.py``:
    candidates bucketed by compile geometry, each bucket's sub-grid
    one vmapped (and, with ``sweep_shards > 1``, shard_map'd) device
    program instead of a per-candidate loop. Rankings are identical
    to the serial path; groups the sweep can't stack fall back to it.
    """
    from predictionio_tpu.utils import compilecache

    compilecache.enable()
    storage = storage or get_storage()
    instance_id = storage.meta.new_instance_id()
    vi = EvaluationInstance(
        id=instance_id, status="EVALUATING", start_time=utcnow(), end_time=None,
        evaluation_class=evaluation_class or type(evaluation).__name__,
        engine_params_generator_class=generator_class,
        batch="", env={},
    )
    storage.meta.insert_evaluation_instance(vi)
    ctx = _build_context(storage, None, verbose, instance_id, use_mesh)
    try:
        assert evaluation.metric is not None, "Evaluation.metric not set"
        sweep_stats = None
        fold_scores = None
        if distributed:
            from predictionio_tpu.core.sweep import run_sweep

            sres = run_sweep(
                ctx, evaluation.get_engine(), candidates,
                evaluation.metric, evaluation.other_metrics,
                sweep_shards=sweep_shards)
            result = sres.result
            sweep_stats = sres.stats()
            fold_scores = sres.fold_scores
        else:
            result = evaluation.run(ctx, candidates)
        vi.status = "EVALCOMPLETED"
        vi.end_time = utcnow()
        vi.evaluator_results = (
            f"best {evaluation.metric.header} = {result.best_score:.6f} "
            f"(candidate {result.best_index} of {len(result.candidates)})")
        vi.evaluator_results_json = result.to_json()
        storage.meta.update_evaluation_instance(vi)
        _write_leaderboard(storage, instance_id, evaluation.metric, result,
                           fold_scores=fold_scores, sweep_stats=sweep_stats,
                           distributed=distributed)
        return instance_id, result
    except Exception as e:
        vi.status = "FAILED"
        vi.end_time = utcnow()
        # record WHY: `pio evals show` must be able to explain a dead
        # sweep without anyone grepping driver logs
        vi.evaluator_results = f"{type(e).__name__}: {e}"
        storage.meta.update_evaluation_instance(vi)
        raise


def _write_leaderboard(storage: Storage, instance_id: str, metric,
                       result: MetricEvaluatorResult,
                       fold_scores=None, sweep_stats=None,
                       distributed: bool = False) -> Optional[str]:
    """Persist the versioned leaderboard artifact for this evaluation
    under ``<home>/leaderboards/<instance_id>.json``. Best-effort: a
    leaderboard write failure must not fail a completed evaluation."""
    import warnings

    from predictionio_tpu.storage import leaderboard as lb

    try:
        ep_rows = json.loads(result.to_json())["candidates"]
        doc = lb.build(
            instance_id, metric.header, bool(metric.higher_is_better),
            [row["engineParams"] for row in ep_rows],
            [s for _, s, _ in result.candidates],
            fold_scores=fold_scores,
            mode="distributed" if distributed else "serial",
            stats=sweep_stats)
        return lb.write(storage.config.home, doc)
    except Exception as e:  # pragma: no cover - defensive
        warnings.warn(f"leaderboard write failed: {e}", RuntimeWarning)
        return None
