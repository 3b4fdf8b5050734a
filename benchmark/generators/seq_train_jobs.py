"""Traffic kind ``seq_train_jobs``: whole warm ``pio train`` verbs of the
``sequentialrec`` template (``core/workflow.run_train``: read → index →
pack → init → fit → fetch → save, status COMPLETED) back to back on the
configuration's event store.

Parameters (the traffic file): ``min_complete`` — trains that must
complete inside the window. Whole trains are started until ``--seconds``
have passed, and the one that is running is finished. With ``--trace 1``
the window is ONE train under the profiler.

``correct``: every verb COMPLETED, nothing compiled in the window, the
losses finite, no (token, expert) pair dropped, the loss fell — and the
program against the plain reference (``reference/glm4_moe_lite_jnp.py``)
at the timed sizes: the first step's losses and per-group gradient
norms, and the saved model's logits through ``prepare_deploy``
(:func:`check_reference`).
"""

from __future__ import annotations

import gc
import os
import shutil
import statistics
import time

import numpy as np

# the model FIRST: a tree without it fails here, in seconds, before any
# data is made or a store imported
from predictionio_tpu.models import glm4_moe_lite as glm

import checks
import piostore
import roofline_seq
import scope_reduce
import seqdata
import spans
import trace_reduce
from harness import (CACHE, BenchFailure, memory_peak_bytes, peaks_for,
                     profiler_options, say, tee_stdout)

#: the published config's keys, as the catalog row holds them
PUBLISHED = (
    "attention_bias", "hidden_act", "hidden_size", "intermediate_size",
    "max_position_embeddings", "model_type", "moe_intermediate_size",
    "topk_method", "norm_topk_prob", "num_attention_heads", "n_group",
    "topk_group", "n_routed_experts", "n_shared_experts",
    "routed_scaling_factor", "num_experts_per_tok", "first_k_dense_replace",
    "num_hidden_layers", "num_key_value_heads", "num_nextn_predict_layers",
    "partial_rotary_factor", "rms_norm_eps", "rope_scaling", "rope_theta",
    "tie_word_embeddings", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "vocab_size")


def architecture(config: dict, shape: dict) -> dict:
    """The ``architecture`` object of the variant: the configuration's
    published keys (``--tiny``: the sample's over them) and its job."""
    arch = {k: shape.get(k, config[k]) for k in PUBLISHED if k in config}
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


def _one_train(label: str, config: dict, variant: dict, storage) -> dict:
    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.utils import tracing

    t0 = time.perf_counter()
    status = "FAILED"
    with tee_stdout():
        try:
            iid = run_train(config["engine_factory"], variant=variant,
                            storage=storage, verbose=0, use_mesh=False)
            status = storage.meta.get_engine_instance(iid).status
        except Exception as e:  # noqa: BLE001 — a failed train is counted
            say(f"{label}: {type(e).__name__}: {e}")
    wall = time.perf_counter() - t0
    tree = tracing.last_verb(spans.ROOT) if status == "COMPLETED" else None
    fit = {k: spans.attr_of(tree, "seqrec.fit", k) for k in (
        "steps", "loss_first", "mtp_loss_first", "loss_last4",
        "losses_finite", "grad_norms_first", "moe_pairs", "moe_pairs_here",
        "moe_dropped_pairs", "moe_load_max_over_mean",
        "router_bias_absmax")}
    phases = {n: spans.seconds_of(tree, n) for n in (
        "train.read", "seqrec.index", "seqrec.pack", "seqrec.init",
        "seqrec.fit", "seqrec.fetch", "model.serialize", "model.put")}
    say(f"{label}: {status} in {wall:.2f} s; "
        + ", ".join(f"{k}={v:.2f}" for k, v in phases.items()
                    if v is not None)
        + f"; loss {fit['loss_first']} -> {fit['loss_last4']}")
    return {"wall": wall, "status": status, "fit": fit, "tree": tree,
            "phases": phases}


def _host_spans(tree, t0_ns: int) -> list:
    """The traced verb's leaf spans as (name, start_s, end_s) from the
    window's start, for naming the device's idle gaps."""
    return [(s["name"], (s["startNs"] - t0_ns) / 1e9,
             (s["endNs"] - t0_ns) / 1e9) for s in spans.leaves(tree or [])]


def run(cell) -> dict:
    import jax

    from predictionio_tpu.storage import get_storage

    config, shape = cell.settings(), cell.shape()
    cfg = glm.GlmConfig.from_architecture(architecture(config, shape))
    t0 = time.perf_counter()
    data = seqdata.Histories(shape, cell.seed)
    say(f"data: {config['name']} seed {cell.seed}: {data.n_users:,} "
        f"histories of {int(data.lengths.min())}..{int(data.lengths.max())} "
        f"events (mean {data.lengths.mean():.0f}), {data.nnz:,} events over "
        f"{data.n_items:,} items; popularity entropy "
        f"{data.popularity_entropy():.3f} nats; made in "
        f"{time.perf_counter() - t0:.1f} s")
    say(f"model: {glm.n_params(cfg):,} parameters, "
        f"{16 * glm.n_params(cfg) / 1e9:.2f} GB at 16 B; experts held "
        f"{cfg.held[0]}..{cfg.held[-1]} of {cfg.router_experts}")
    seqdata.ensure_events(cell.config_name, cell.tiny, data, cell.seed)
    storage = get_storage()
    variant = _variant(config, shape, cell.seed)

    obs = {"nnz": data.nnz}
    before = (cell.compiles.requests, cell.compiles.hits)
    t0 = time.perf_counter()
    warm = _one_train("warm-up train (set-up)", config, variant, storage)
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
                trains.append(_one_train("traced train", config, variant,
                                         storage))
        finally:
            jax.profiler.stop_trace()
    else:
        while (time.perf_counter() - t_window < cell.seconds
               or len(trains) < cell.traffic["min_complete"]):
            trains.append(_one_train(f"train {len(trains) + 1}", config,
                                     variant, storage))
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
        if trace.n_devices and done:
            obs["peaks"] = peaks_for(jax.devices()[0].device_kind)
            obs["need"] = roofline_seq.needs(cfg, done[-1]["fit"], {
                k: spans.attr_of(done[-1]["tree"], "seqrec.pack", k)
                for k in ("sequences", "real_tokens", "attn_pairs")})
            say(f"one train needs: {obs['need']}")
        breakdown = {
            "device_ops": trace.top_ops(10),
            "device_scopes": sorted(([k, v] for k, v in
                                     obs["scopes"].items()),
                                    key=lambda kv: -kv[1]),
            "idle_gaps": trace_reduce.label_gaps(
                trace.gaps, _host_spans(trains[0]["tree"], t_window_ns),
                10)}
        for row in breakdown["idle_gaps"]:
            say(f"idle gap: {row[1]:.3f} s  {row[0]}")
        shutil.rmtree(trace_dir, ignore_errors=True)

    if done:
        gc.collect()
        check_reference(verdict, config, cfg, cell.seed, storage,
                        done[-1]["fit"])
    return {"correct": verdict.ok, "attempted": len(trains),
            "failed": len(trains) - len(done), "end_to_end": end_to_end,
            "obs": obs, "breakdown": breakdown}


# -- the comparison with the plain reference ----------------------------------


def first_batches(cfg, seed: int, storage, item_ids):
    """The packed sequences a train of this store sees, made again the
    program's own way: the template's DataSource, the model's item
    index, ``pack_histories`` under the variant's seed."""
    from predictionio_tpu.controller import WorkflowContext
    from predictionio_tpu.templates.sequentialrec.engine import (
        DataSourceParams, SeqDataSource)

    td = SeqDataSource(DataSourceParams(
        app_name=piostore.APP, event_names=[seqdata.EVENT])).read_training(
            WorkflowContext(storage=storage))
    sequences = [[item_ids[i] + 1 for i in seq]
                 for seq in td.sequences.values()]
    return glm.pack_histories(sequences, cfg.seq_len, cfg.seqs_per_step,
                              seed % (1 << 31))


#: the reference is compiled for compile time, not for speed: at
#: ``highest`` precision XLA's default effort takes 106 s over its
#: program, the least effort 12 s (CPU-side compile for the v5e, PR 31)
REFERENCE_COMPILER_OPTIONS = {"exec_time_optimization_effort": -1.0}


class Reference:
    """The plain reference on the chip, ONE compiled program for every
    use: a sequence's share of a step's loss, both heads' logits, and
    its gradients ADDED to an accumulator —

        run(w, b, seq, s1, s2, acc) -> (Σ CE, Σ CE_MTP, logits,
                                        MTP logits), acc + ∂(s1·ΣCE +
                                        s2·ΣCE_MTP)/∂w

    under ``highest`` matmul precision, each layer in
    ``jax.checkpoint`` so that 706 M gradients fit beside a sequence's
    float32 activations. ``dtype`` computes everything lower (the
    precision probe)."""

    def __init__(self, cfg, dtype=None) -> None:
        self.cfg, self.dtype, self.exe = cfg, dtype, None

    def _build(self, *args):
        import jax
        import jax.numpy as jnp

        from reference import glm4_moe_lite_jnp as ref

        rcfg, held = dict(self.cfg.__dict__), self.cfg.held
        kw = {} if self.dtype is None else {"dtype": self.dtype}

        def part(w, b, seq, s1, s2):
            logits, mtp, _ = ref.forward(w, b, seq, rcfg, held,
                                         jax.checkpoint, **kw)
            ce, ce_mtp = (ref.ce_sum(logits, seq["tgt1"]),
                          ref.ce_sum(mtp, seq["tgt2"]))
            return s1 * ce + s2 * ce_mtp, (ce, ce_mtp, logits, mtp)

        def run(w, b, seq, s1, s2, acc):
            with jax.default_matmul_precision("highest"):
                (_, out), g = jax.value_and_grad(part, has_aux=True)(
                    w, b, seq, s1, s2)
            return out, jax.tree.map(jnp.add, acc, g)

        return jax.jit(run, donate_argnums=(5,)).lower(*args).compile(
            compiler_options=REFERENCE_COMPILER_OPTIONS)

    def __call__(self, w, b, seq, s1, s2, acc):
        import jax.numpy as jnp

        args = (w, b, seq, jnp.float32(s1), jnp.float32(s2), acc)
        if self.exe is None:
            self.exe = self._build(*args)
        return self.exe(*args)


def _sequence(packed, i: int) -> dict:
    import jax.numpy as jnp

    return {k: jnp.asarray(getattr(packed, k)[i]) for k in glm.BATCH_KEYS}


def _zeros_like(tree):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))(tree)


def reference_first_step(reference: Reference, seed: int, packed):
    """The reference's loss, MTP loss and per-group gradient norms on
    the seeded initial parameters (the program's own ``init_state``,
    handed over as data) and the first batch, a sequence at a time."""
    import jax

    cfg = reference.cfg
    weights, _opt, bias = glm.init_state(cfg, seed % (1 << 31),
                                         with_optimizer=True)
    del _opt
    rows = range(cfg.seqs_per_step)
    n1 = max(int((packed.tgt1[:cfg.seqs_per_step] > 0).sum()), 1)
    n2 = max(int((packed.tgt2[:cfg.seqs_per_step] > 0).sum()), 1)
    acc, ce, ce_mtp = _zeros_like(weights), 0.0, 0.0
    for i in rows:
        (a, b, _, _), acc = reference(weights, bias, _sequence(packed, i),
                                      1.0 / n1, cfg.mtp_loss_weight / n2,
                                      acc)
        ce, ce_mtp = ce + float(a), ce_mtp + float(b)
    squares = jax.jit(glm.group_squares)(acc)
    return ce / n1, ce_mtp / n2, {g: float(v) ** 0.5
                                  for g, v in squares.items()}


def reference_logits(reference: Reference, model: dict, packed, n: int):
    """Both heads' reference logits of the first ``n`` sequences on the
    model's weights, on the HOST, through the same compiled program
    (its gradients are computed and left)."""
    acc, out = _zeros_like(model["params"]), []
    for i in range(n):
        (_, _, logits, mtp), acc = reference(
            model["params"], model["bias"], _sequence(packed, i), 0.0, 0.0,
            acc)
        out.append((np.asarray(logits), np.asarray(mtp)))
    return [np.stack([o[h] for o in out]) for h in (0, 1)]


def compare_logits(got: np.ndarray, want: np.ndarray) -> dict:
    """``rel_rms`` and ``worst_over_rms`` over every element, and
    ``token_median``: the median over tokens of ‖difference of the
    token's logits‖ over the rms of ‖its reference logits‖. A token
    whose top-k flipped on a rounding (about one in a hundred, each
    moved by a third of its size) dominates the rms; the median is
    what rounding does to a token that kept its experts."""
    diff = got.astype(np.float64) - want
    scale = float(np.sqrt(np.mean(np.square(want, dtype=np.float64))))
    per_token = np.sqrt(np.square(diff).sum(-1)).reshape(-1)
    token_scale = float(np.sqrt(np.mean(np.square(
        want, dtype=np.float64).sum(-1))))
    return {"rel_rms": float(np.sqrt(np.mean(np.square(diff)))) / scale,
            "worst_over_rms": float(np.abs(diff).max()) / scale,
            "token_median": float(np.median(per_token)) / token_scale,
            "token_p99": float(np.quantile(per_token, 0.99)) / token_scale}


def check_reference(verdict, config: dict, cfg, seed: int, storage,
                    fit: dict) -> dict:
    import jax.numpy as jnp

    from predictionio_tpu.core.workflow import prepare_deploy

    tol = config["reference"]
    t0 = time.perf_counter()
    deployed = prepare_deploy(engine_factory=config["engine_factory"],
                              variant_id="default", storage=storage)
    model = deployed.models[0]
    packed = first_batches(cfg, seed, storage, model.item_ids)
    say(f"the saved model loaded back, the batches made again: "
        f"{time.perf_counter() - t0:.1f} s")
    reference, out = Reference(cfg), {}

    # (a) the first step of the last timed verb
    t0 = time.perf_counter()
    ce, ce_mtp, norms = reference_first_step(reference, seed, packed)
    out["first_step_s"] = time.perf_counter() - t0
    out["loss_diff"] = abs(fit["loss_first"] - ce)
    out["mtp_loss_diff"] = abs(fit["mtp_loss_first"] - ce_mtp)
    say(f"reference, first step ({out['first_step_s']:.1f} s incl. "
        f"compile): loss {ce:.5f} (program {fit['loss_first']:.5f}), MTP "
        f"loss {ce_mtp:.5f} (program {fit['mtp_loss_first']:.5f})")
    verdict.check(out["loss_diff"] <= tol["loss_abs_max"],
                  f"|loss − reference| {out['loss_diff']:.2e} <= "
                  f"{tol['loss_abs_max']}")
    verdict.check(out["mtp_loss_diff"] <= tol["loss_abs_max"],
                  f"|MTP loss − reference| {out['mtp_loss_diff']:.2e} <= "
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
    got = [np.asarray(g) for g in glm.sequence_logits(on_device, {
        k: jnp.asarray(getattr(packed, k)[:n]) for k in glm.BATCH_KEYS}, cfg)]
    want = reference_logits(reference, on_device, packed, n)
    for head, g, w in zip(("next-item", "MTP"), got, want):
        finite = bool(np.isfinite(g).all())
        verdict.check(finite, f"{head} logits of the loaded model are "
                      "finite")
        d = out[head] = compare_logits(g, w)
        say(f"reference: {head} logits of {n} sequences: {d}")
        verdict.check(
            finite and d["token_median"] <= tol["logits_token_median_max"],
            f"{head} logits: median over tokens of |diff| / rms |logits| "
            f"{d['token_median']:.2e} <= {tol['logits_token_median_max']}")
    out["logits_s"] = time.perf_counter() - t0
    say(f"reference, logits: {out['logits_s']:.1f} s incl. compile")
    del got, want
    return out
