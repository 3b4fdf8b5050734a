"""Traffic kind ``train_jobs``: whole warm ``pio train`` verbs
(``core/workflow.run_train``: read → prepare → upload → iterations →
fetch → save, status COMPLETED) back to back on the configuration's
event store.

Parameters (the traffic file): ``min_complete`` — trains that must
complete inside the window. Whole trains are started until ``--seconds``
have passed, and the one that is running is finished. With ``--trace 1``
the window is ONE train under the profiler.
"""

from __future__ import annotations

import gc
import logging
import os
import re
import shutil
import statistics
import time

import numpy as np

import checks
import piostore
import roofline
import trace_reduce
from datagen import Interactions
from harness import (CACHE, BenchFailure, memory_peak_bytes, peaks_for,
                     profiler_options, say, tee_stdout)

_PHASES = re.compile(r"train phases: (.*)$")


class _Capture(logging.Handler):
    """The trainer's own ``ALS train: platform=… gram=… solve=…``."""

    def __init__(self) -> None:
        super().__init__(level=logging.INFO)
        self.lines: list = []

    def emit(self, record: logging.LogRecord) -> None:
        self.lines.append(record.getMessage())


def _variant(config: dict, seed: int) -> dict:
    return {
        "id": "default",
        "description": f"benchmark {config['name']}",
        "engineFactory": config["engine_factory"],
        "datasource": {"params": {"appName": piostore.APP,
                                  "eventNames": ["rate"]}},
        "algorithms": [{"name": "als", "params": {
            "rank": config["rank"],
            "numIterations": config["iterations"],
            "lambda": config["lambda"],
            "implicitPrefs": config["implicit"],
            "alpha": config["alpha"], "seed": seed}}],
        "meshConf": {},
    }


def _one_train(label: str, config: dict, variant: dict, storage) -> dict:
    from predictionio_tpu.core.workflow import run_train

    t0 = time.perf_counter()
    status, phases = "FAILED", {}
    with tee_stdout() as lines:
        try:
            iid = run_train(config["engine_factory"], variant=variant,
                            storage=storage, verbose=1)
            status = storage.meta.get_engine_instance(iid).status
        except Exception as e:  # noqa: BLE001 — a failed train is counted
            say(f"{label}: {type(e).__name__}: {e}")
    wall = time.perf_counter() - t0
    for ln in lines:
        m = _PHASES.search(ln)
        if m:
            phases = {k: float(v.rstrip("s")) for k, v in
                      (kv.split("=") for kv in m.group(1).split(", "))}
    say(f"{label}: {status} in {wall:.2f} s ({phases})")
    return {"wall": wall, "status": status, "phases": phases}


def _host_spans(train: dict, trace) -> list:
    """What the host was doing along the traced train, from the
    workflow's own phase clock: (name, start_s, end_s) from the
    window's start. ``train:als`` is split at the first and last device
    operation, since everything the trainer does on the host
    (``als_prepare``, upload; checkpoint writes; fetch) sits inside it."""
    ph = train["phases"]
    read = ph.get("read_training", 0.0)
    prep = ph.get("prepare", 0.0)
    als = sum(v for k, v in ph.items() if k.startswith("train:"))
    a0, a1 = read + prep, read + prep + als
    spans = [("read_training", 0.0, read), ("prepare", read, a0)]
    first, last = trace.first_op_s, trace.last_op_s
    if first is None or not a0 <= first <= last <= a1:
        spans.append(("train:als", a0, a1))
    else:
        spans += [("train:als before the first device op (als_prepare, "
                   "upload)", a0, first),
                  ("train:als between device ops (checkpoint, dispatch)",
                   first, last),
                  ("train:als after the last device op (fetch)", last, a1)]
    spans.append(("save", a1, train["wall"]))
    return spans


def run(cell) -> dict:
    from predictionio_tpu.storage import get_storage

    config, shape = cell.settings(), cell.shape()
    t0 = time.perf_counter()
    data = Interactions(shape, config["values"], config["heldout_share"],
                        cell.seed)
    say(f"data: {config['name']} seed {cell.seed}: {data.n_users:,} users "
        f"x {data.n_items:,} items x {data.nnz:,} interactions, "
        f"{len(data.held_users):,} held out; user degree "
        f"{int(data.deg_u.min())}..{int(data.deg_u.max())}, item degree "
        f"{int(data.deg_i.min())}..{int(data.deg_i.max())}; made in "
        f"{time.perf_counter() - t0:.1f} s")
    piostore.ensure_events(cell.config_name, cell.tiny, data, cell.seed,
                           config["values"]["decimals"])
    storage = get_storage()
    variant = _variant(config, cell.seed)
    cap = _Capture()
    als_log = logging.getLogger("predictionio_tpu.models.als")
    als_log.setLevel(logging.INFO)
    als_log.addHandler(cap)

    obs = {"nnz": data.nnz, "iterations": config["iterations"]}
    before = (cell.compiles.requests, cell.compiles.hits)
    warm = _one_train("warm-up train (set-up)", config, variant, storage)
    say(f"warm-up: programs sent to the compiler "
        f"{cell.compiles.requests - before[0]}, of which the persistent "
        f"cache answered {cell.compiles.hits - before[1]}")
    for ln in dict.fromkeys(cap.lines):
        say(f"trainer: {ln}")
    if warm["status"] != "COMPLETED":
        raise BenchFailure("the warm-up train did not complete")
    if cell.trace:
        # the layout step alone, on the cell's data, outside the window
        from predictionio_tpu.models.als import RatingsCOO, als_prepare

        t0 = time.perf_counter()
        prep = als_prepare(RatingsCOO(data.users, data.items, data.values,
                                      data.n_users, data.n_items))
        obs["als_prepare_s"] = time.perf_counter() - t0
        obs["gather_gram_need"] = roofline.gather_gram_need(
            prep, config["rank"], config["iterations"])
        say(f"als_prepare alone: {obs['als_prepare_s']:.2f} s; "
            f"gather_gram needs {obs['gather_gram_need']}")
        del prep
        gc.collect()

    trains = []
    t_window = cell.start_window()
    if cell.trace:
        import jax

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
        wall = statistics.median(t["wall"] for t in done)
        end_to_end["train_updates_per_s"] = (
            data.nnz * config["iterations"] / wall / cell.chips)
        say(f"median train {wall:.2f} s -> "
            f"{end_to_end['train_updates_per_s'] / 1e6:.3f} M "
            "updates/s/chip")
        obs["read_training_s"] = statistics.median(
            t["phases"].get("read_training", float("nan")) for t in done)
    obs["programs_compiled"] = compiled
    obs["memory_peak_bytes"] = memory_peak_bytes(cell.temporaries)

    breakdown = None
    if cell.trace:
        xplane = trace_reduce.find_xplane(trace_dir)
        trace = trace_reduce.reduce_file(xplane)
        if cell.keep_trace:
            os.makedirs(cell.keep_trace, exist_ok=True)
            shutil.copy(xplane, cell.keep_trace)
        obs["trace"] = trace
        say(f"trace: window {trace.window_s:.2f} s, device busy "
            f"{trace.busy_s:.2f} s on {trace.n_devices} device(s)")
        if trace.n_devices:
            peaks = obs["peaks"] = peaks_for(jax.devices()[0].device_kind)
            size = (data.nnz, data.n_users, data.n_items, config["rank"],
                    config["iterations"])
            flops, moved = roofline.train_flops(*size), roofline.train_bytes(
                *size)
            obs["als_train_flops"] = flops
            say(f"the ALS mathematics of one train needs {flops:.3e} "
                f"operations and {moved:.3e} bytes: over the verb's "
                f"{trains[0]['wall']:.1f} s that is "
                f"{100 * flops / trains[0]['wall'] / peaks['bf16_flops_per_s']:.3f}"
                f" % of the bf16 peak and "
                f"{100 * moved / trains[0]['wall'] / peaks['hbm_bytes_per_s']:.3f}"
                " % of the HBM bandwidth")
        breakdown = {
            "device_ops": trace.top_ops(10),
            "idle_gaps": trace_reduce.label_gaps(
                trace.gaps, _host_spans(trains[0], trace), 10)}
        for row in breakdown["device_ops"]:
            say(f"device op: {row[1]:.3f} s  {row[0]}")
        for row in breakdown["idle_gaps"]:
            say(f"idle gap: {row[1]:.3f} s  {row[0]}")
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the last model, loaded back from the registry as `pio deploy` does
    if done:
        from predictionio_tpu.core.workflow import prepare_deploy

        model = prepare_deploy(engine_factory=config["engine_factory"],
                               variant_id="default").models[0]
        verdict.check(model.U.shape == (data.n_users, config["rank"])
                      and model.V.shape == (data.n_items, config["rank"]),
                      f"the model holds every user and item at rank "
                      f"{config['rank']}: U {model.U.shape} V "
                      f"{model.V.shape}")
        ut = checks.dense_ids(model.user_ids, "u", data.n_users)
        it = checks.dense_ids(model.item_ids, "i", data.n_items)
        checks.check_heldout(
            verdict, config, model.U, model.V, ut[data.held_users],
            it[data.held_items], data.held_values,
            float(np.mean(data.values, dtype=np.float64)))
    checks.check_reference(verdict, config, cell.seed + 1)
    return {"correct": verdict.ok, "attempted": len(trains),
            "failed": len(trains) - len(done), "end_to_end": end_to_end,
            "obs": obs, "breakdown": breakdown}
