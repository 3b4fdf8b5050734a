"""Traffic kind ``lfm2_train_jobs``: whole warm ``pio train`` verbs of
the ``sequentialrec`` template (``core/workflow.run_train``: read →
index → pack → init → fit → fetch → save, status COMPLETED) back to
back on the configuration's event store, for ANY block-stack backbone
of the template's table (``models/seq_backbone.backbone``): the
configuration's ``model_type`` names the backbone, its plain reference
(``reference/<model_type>_jnp.py``) and its needs (``ROOFLINES``).

Parameters (the traffic file): ``min_complete`` — trains that must
complete inside the window. Whole trains are started until ``--seconds``
have passed, and the one that is running is finished. With ``--trace 1``
the window is ONE train under the profiler.

``correct``: every verb COMPLETED, nothing compiled in the window, the
losses finite, no (token, expert) pair dropped, the loss fell — and the
program against the plain reference at the timed sizes: the first
step's loss of every head and per-group gradient norms, and the saved
model's logits through ``prepare_deploy`` (:func:`check_reference`).

What it shares with ``seq_train_jobs`` (one verb with its spans, the
batches made again, the logits' comparison, the reference's compiler
options) is imported from there, not copied.
"""

from __future__ import annotations

import gc
import importlib
import os
import shutil
import statistics
import time

import numpy as np

# the model FIRST: a tree without the backbone table fails here, in
# seconds, before any data is made or a store imported
from predictionio_tpu.models import seq_backbone

import checks
import piostore
import scope_reduce
import seqdata
import spans
import trace_reduce
from harness import (CACHE, BenchFailure, load_module, memory_peak_bytes,
                     peaks_for, profiler_options, say)

shared = load_module("generators", "seq_train_jobs")

#: ``model_type`` → the module of ``benchmark/`` whose ``needs(c, fit,
#: pack)`` counts what one train needs
ROOFLINES = {"glm4_moe_lite": "roofline_seq", "lfm2_moe": "roofline_lfm2"}
#: a head's target in a packed batch, and its weight in the step's loss
TARGETS = {"loss": "tgt1", "mtp_loss": "tgt2"}
HEAD_WEIGHT = {"loss": lambda c: 1.0, "mtp_loss": lambda c: c.mtp_loss_weight}


def backbone_of(config: dict):
    return seq_backbone.backbone(config.get("model_type"))


def architecture(config: dict, shape: dict) -> dict:
    """The ``architecture`` object of the variant: the keys of the
    configuration that the backbone's config knows (``--tiny``: the
    sample's over them) and its job."""
    known = backbone_of(config).config.known_keys()
    arch = {k: shape.get(k, config[k]) for k in config if k in known}
    arch.update(shape["job"])
    return arch


def _variant(config: dict, shape: dict, seed: int) -> dict:
    return {
        "id": "default",
        "description": f"benchmark {config['name']}",
        "engineFactory": config["engine_factory"],
        "datasource": {"params": {"appName": piostore.APP,
                                  "eventNames": [seqdata.EVENT]}},
        "algorithms": [{"name": "seqrec", "params": {
            "epochs": shape["train"]["epochs"], "lr": shape["train"]["lr"],
            "seed": seed % (1 << 31),
            "historyEvents": [seqdata.EVENT],
            "architecture": architecture(config, shape)}}],
        "meshConf": {},
    }


def _span_attrs(tree, name: str) -> dict:
    return dict((spans.named(tree, name) or [{}])[0].get("attrs") or {})


def run(cell) -> dict:
    import jax

    from predictionio_tpu.storage import get_storage

    config, shape = cell.settings(), cell.shape()
    backbone = backbone_of(config)
    cfg = backbone.config.from_architecture(architecture(config, shape))
    n_params = backbone.n_params(cfg)
    t0 = time.perf_counter()
    data = seqdata.Histories(shape, cell.seed)
    say(f"data: {config['name']} seed {cell.seed}: {data.n_users:,} "
        f"histories of {int(data.lengths.min())}..{int(data.lengths.max())} "
        f"events (mean {data.lengths.mean():.0f}), {data.nnz:,} events over "
        f"{data.n_items:,} items; popularity entropy "
        f"{data.popularity_entropy():.3f} nats; made in "
        f"{time.perf_counter() - t0:.1f} s")
    say(f"model: {backbone.model_type}, {n_params:,} parameters, "
        f"{16 * n_params / 1e9:.2f} GB at 16 B; experts held "
        f"{cfg.held[0]}..{cfg.held[-1]} of {cfg.router_experts}")
    seqdata.ensure_events(cell.config_name, cell.tiny, data, cell.seed)
    storage = get_storage()
    variant = _variant(config, shape, cell.seed)

    obs = {"nnz": data.nnz}
    before = (cell.compiles.requests, cell.compiles.hits)
    t0 = time.perf_counter()
    warm = shared._one_train("warm-up train (set-up)", config, variant,
                             storage)
    say(f"warm-up: {time.perf_counter() - t0:.1f} s; programs sent to the "
        f"compiler {cell.compiles.requests - before[0]}, of which the "
        f"persistent cache answered {cell.compiles.hits - before[1]}")
    if warm["status"] != "COMPLETED":
        raise BenchFailure("the warm-up train did not complete")

    trains = []
    t_window = cell.start_window()
    t_window_ns = time.perf_counter_ns()
    if cell.trace:
        trace_dir = os.path.join(CACHE, "trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=profiler_options())
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                trains.append(shared._one_train("traced train", config,
                                                variant, storage))
        finally:
            jax.profiler.stop_trace()
    else:
        while (time.perf_counter() - t_window < cell.seconds
               or len(trains) < cell.traffic["min_complete"]):
            trains.append(shared._one_train(f"train {len(trains) + 1}",
                                            config, variant, storage))
    window = time.perf_counter() - t_window
    compiled = cell.compiled_in_window()
    done = [t for t in trains if t["status"] == "COMPLETED"]
    say(f"window: {len(done)} of {len(trains)} trains completed in "
        f"{window:.1f} s; programs compiled inside it: {compiled}")

    verdict = checks.Verdict()
    verdict.check(len(done) == len(trains), "every train COMPLETED")
    verdict.check(compiled == 0, "nothing compiled inside the window")
    end_to_end = {}
    if done:
        last = done[-1]
        wall = statistics.median(t["wall"] for t in done)
        end_to_end["train_updates_per_s"] = (
            data.nnz * shape["train"]["epochs"] / wall / cell.chips)
        say(f"median train {wall:.2f} s -> "
            f"{end_to_end['train_updates_per_s'] / 1e3:.2f} k "
            "updates/s/chip")
        obs["read_training_s"] = statistics.median(
            t["phases"]["train.read"] for t in done)
        obs["spans"] = last["tree"]
        obs["fit"] = last["fit"]
        for t in done:
            f = t["fit"]
            verdict.check(bool(f["losses_finite"]),
                          "every recorded loss is finite")
            verdict.check(f["moe_dropped_pairs"] == 0,
                          f"no (token, expert) pair dropped of "
                          f"{f['moe_pairs_here']:,} held here "
                          f"({f['moe_pairs']:,} routed)")
            drop = f["loss_first"] - f["loss_last4"]
            verdict.check(
                drop >= config["correct"]["loss_drop_min"],
                f"the mean loss of the last 4 steps {f['loss_last4']:.4f} "
                f"is below the first step's {f['loss_first']:.4f} by "
                f"{drop:.4f} >= {config['correct']['loss_drop_min']}")
    obs["programs_compiled"] = compiled
    obs["memory_peak_bytes"] = memory_peak_bytes(cell.temporaries)

    breakdown = None
    if cell.trace:
        xplane = trace_reduce.find_xplane(trace_dir)
        trace = obs["trace"] = trace_reduce.reduce_file(xplane)
        obs["scopes"] = scope_reduce.scope_seconds(xplane)
        if cell.keep_trace:
            os.makedirs(cell.keep_trace, exist_ok=True)
            shutil.copy(xplane, cell.keep_trace)
        say(f"trace: window {trace.window_s:.2f} s, device busy "
            f"{trace.busy_s:.2f} s on {trace.n_devices} device(s)")
        for name, secs in sorted(obs["scopes"].items(),
                                 key=lambda kv: -kv[1]):
            say(f"device scope: {secs:.3f} s  {name}")
        say(f"device scopes sum to {sum(obs['scopes'].values()):.3f} s of "
            f"{trace.busy_s:.3f} s busy; under no seqrec.* scope: "
            f"{obs['scopes'].get('other', 0.0):.3f} s")
        if trace.n_devices and done:
            obs["peaks"] = peaks_for(jax.devices()[0].device_kind)
            tree = done[-1]["tree"]
            obs["need"] = importlib.import_module(
                ROOFLINES[backbone.model_type]).needs(
                    cfg, _span_attrs(tree, "seqrec.fit"),
                    _span_attrs(tree, "seqrec.pack"))
            say(f"one train needs: {obs['need']}")
        breakdown = {
            "device_ops": trace.top_ops(10),
            "device_scopes": sorted(([k, v] for k, v in
                                     obs["scopes"].items()),
                                    key=lambda kv: -kv[1]),
            "idle_gaps": trace_reduce.label_gaps(
                trace.gaps,
                shared._host_spans(trains[0]["tree"], t_window_ns), 10)}
        for row in breakdown["idle_gaps"]:
            say(f"idle gap: {row[1]:.3f} s  {row[0]}")
        shutil.rmtree(trace_dir, ignore_errors=True)

    if done:
        gc.collect()
        check_reference(verdict, config, backbone, cfg, cell.seed, storage,
                        _span_attrs(done[-1]["tree"], "seqrec.fit"))
    return {"correct": verdict.ok, "attempted": len(trains),
            "failed": len(trains) - len(done), "end_to_end": end_to_end,
            "obs": obs, "breakdown": breakdown}


# -- the comparison with the plain reference ----------------------------------


class Reference:
    """The backbone's plain reference on the chip, ONE compiled program
    for every use: a sequence's share of a step's loss, every head's
    logits, and its gradients ADDED to an accumulator —

        run(w, b, seq, scales, acc) -> ([Σ CE of each head], [logits of
                    each head]), acc + ∂(Σ_h scales[h] · ΣCE_h)/∂w

    under ``highest`` matmul precision, each layer in
    ``jax.checkpoint`` so that the gradients fit beside a sequence's
    float32 activations. ``dtype`` computes everything lower (the
    precision probe)."""

    def __init__(self, backbone, cfg, dtype=None) -> None:
        self.backbone, self.cfg, self.dtype = backbone, cfg, dtype
        self.exe = None

    def _build(self, *args):
        import jax
        import jax.numpy as jnp

        ref = importlib.import_module(
            f"reference.{self.backbone.model_type}_jnp")
        rcfg, held = dict(self.cfg.__dict__), self.cfg.held
        kw = {} if self.dtype is None else {"dtype": self.dtype}
        targets = [TARGETS[h] for h in self.backbone.heads]

        def part(w, b, seq, scales):
            *logits, _loads = ref.forward(w, b, seq, rcfg, held,
                                          jax.checkpoint, **kw)
            ces = [ref.ce_sum(lg, seq[t]) for lg, t in zip(logits, targets)]
            return sum(s * ce for s, ce in zip(scales, ces)), (ces, logits)

        def run(w, b, seq, scales, acc):
            with jax.default_matmul_precision("highest"):
                (_, out), g = jax.value_and_grad(part, has_aux=True)(
                    w, b, seq, scales)
            return out, jax.tree.map(jnp.add, acc, g)

        return jax.jit(run, donate_argnums=(4,)).lower(*args).compile(
            compiler_options=shared.REFERENCE_COMPILER_OPTIONS)

    def __call__(self, w, b, seq, scales, acc):
        import jax.numpy as jnp

        args = (w, b, seq, [jnp.float32(s) for s in scales], acc)
        if self.exe is None:
            self.exe = self._build(*args)
        return self.exe(*args)


def _sequence(backbone, packed, i: int) -> dict:
    import jax.numpy as jnp

    return {k: jnp.asarray(getattr(packed, k)[i])
            for k in backbone.batch_keys}


def reference_first_step(reference: Reference, seed: int, packed):
    """The reference's loss of each head and per-group gradient norms
    on the seeded initial parameters (the program's own ``init_state``,
    handed over as data) and the first batch, a sequence at a time."""
    import jax

    backbone, cfg = reference.backbone, reference.cfg
    weights, _opt, bias = backbone.init_state(cfg, seed % (1 << 31),
                                              with_optimizer=True)
    del _opt
    B = cfg.seqs_per_step
    counts = [max(int((getattr(packed, TARGETS[h])[:B] > 0).sum()), 1)
              for h in backbone.heads]
    scales = [HEAD_WEIGHT[h](cfg) / n
              for h, n in zip(backbone.heads, counts)]
    acc, sums = shared._zeros_like(weights), [0.0] * len(counts)
    for i in range(B):
        (ces, _), acc = reference(weights, bias,
                                  _sequence(backbone, packed, i), scales,
                                  acc)
        sums = [s + float(ce) for s, ce in zip(sums, ces)]
    squares = jax.jit(backbone.group_squares)(acc)
    return ([s / n for s, n in zip(sums, counts)],
            {g: float(v) ** 0.5 for g, v in squares.items()})


def reference_logits(reference: Reference, model: dict, packed, n: int):
    """Each head's reference logits of the first ``n`` sequences on the
    model's weights, on the HOST, through the same compiled program
    (its gradients are computed and left)."""
    backbone = reference.backbone
    acc, out = shared._zeros_like(model["params"]), []
    for i in range(n):
        (_, logits), acc = reference(
            model["params"], model["bias"], _sequence(backbone, packed, i),
            [0.0] * len(backbone.heads), acc)
        out.append([np.asarray(lg) for lg in logits])
    return [np.stack([o[h] for o in out])
            for h in range(len(backbone.heads))]


def check_reference(verdict, config: dict, backbone, cfg, seed: int,
                    storage, fit: dict) -> dict:
    import jax.numpy as jnp

    from predictionio_tpu.core.workflow import prepare_deploy

    tol = config["reference"]
    t0 = time.perf_counter()
    deployed = prepare_deploy(engine_factory=config["engine_factory"],
                              variant_id="default", storage=storage)
    model = deployed.models[0]
    packed = shared.first_batches(cfg, seed, storage, model.item_ids)
    say(f"the saved model ({model.model_type}) loaded back, the batches "
        f"made again: {time.perf_counter() - t0:.1f} s")
    verdict.check(model.model_type == backbone.model_type,
                  f"the saved model names its backbone "
                  f"{backbone.model_type!r}")
    reference, out = Reference(backbone, cfg), {}

    # (a) the first step of the last timed verb
    t0 = time.perf_counter()
    losses, norms = reference_first_step(reference, seed, packed)
    out["first_step_s"] = time.perf_counter() - t0
    say(f"reference, first step: {out['first_step_s']:.1f} s incl. compile")
    for head, want in zip(backbone.heads, losses):
        got = fit[f"{head}_first"]
        diff = out[f"{head}_diff"] = abs(got - want)
        say(f"reference: {head} {want:.5f} (program {got:.5f})")
        verdict.check(diff <= tol["loss_abs_max"],
                      f"|{head} − reference| {diff:.2e} <= "
                      f"{tol['loss_abs_max']}")
    worst = 0.0
    for group, want in sorted(norms.items()):
        got = fit["grad_norms_first"][group]
        rel = abs(got - want) / max(want, 1e-30)
        worst = max(worst, rel)
        say(f"reference: gradient norm of {group}: {want:.5e} (program "
            f"{got:.5e}, relative difference {rel:.2e})")
    out["grad_norm_rel_worst"] = worst
    verdict.check(worst <= tol["grad_norm_rel_max"],
                  f"worst relative difference of a group's gradient norm "
                  f"{worst:.2e} <= {tol['grad_norm_rel_max']}")
    del norms
    gc.collect()

    # (b) the saved model, loaded back as `pio deploy` does
    t0 = time.perf_counter()
    n = int(tol["sequences_compared"])
    on_device = model.device_params()
    got = [np.asarray(g) for g in backbone.sequence_logits(on_device, {
        k: jnp.asarray(getattr(packed, k)[:n])
        for k in backbone.batch_keys}, cfg)]
    want = reference_logits(reference, on_device, packed, n)
    for head, g, w in zip(backbone.heads, got, want):
        finite = bool(np.isfinite(g).all())
        verdict.check(finite, f"the {head} head's logits of the loaded "
                      "model are finite")
        d = out[head] = shared.compare_logits(g, w)
        say(f"reference: the {head} head's logits of {n} sequences: {d}")
        verdict.check(
            finite and d["token_median"] <= tol["logits_token_median_max"],
            f"the {head} head's logits: median over tokens of |diff| / "
            f"rms |logits| {d['token_median']:.2e} <= "
            f"{tol['logits_token_median_max']}")
    out["logits_s"] = time.perf_counter() - t0
    say(f"reference, logits: {out['logits_s']:.1f} s incl. compile")
    del got, want
    return out
