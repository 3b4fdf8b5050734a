"""What every cell shares: finding the cell's files by name, the device
check, the compile counter, the window's clock and the last line."""

from __future__ import annotations

import contextlib
import importlib.util
import io
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache")


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class BenchFailure(Exception):
    """The run cannot give a result."""


def load_json(*parts: str) -> Any:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by name."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchFailure(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_applies(spec: dict, cell_name: str) -> bool:
    return "workloads" not in spec or cell_name in spec["workloads"]


def check_table(bench: dict) -> None:
    """A per-layer metric is declared ONCE, under its reader's name
    (``benchmark/layers/<name>.py``), with its cells in ``workloads``:
    a name with a dot (a copy under a suffix, as before PR 49), a name
    that comes twice or a ``workloads`` entry that names no cell is
    refused before anything runs."""
    cells = {w["name"] for w in bench["workloads"]}
    seen = set()
    for spec in bench["per_layer"]:
        name = spec["name"]
        if "." in name:
            raise BenchFailure(
                f"per-layer metric {name!r}: a name is its reader's, "
                f"with no dot; list the cell in {name.split('.')[0]!r}'s "
                "workloads instead of copying it under a suffix")
        if name in seen:
            raise BenchFailure(f"per-layer metric {name!r} is declared "
                               "twice in BENCHMARK.json")
        seen.add(name)
        orphans = [w for w in spec.get("workloads", ()) if w not in cells]
        if orphans:
            raise BenchFailure(f"per-layer metric {name!r} lists "
                               f"{orphans}: no such workload")


class Cell:
    """One entry of BENCHMARK.json's ``workloads`` with its files."""

    def __init__(self, name: str, seed: int, seconds: float, trace: bool,
                 tiny: bool, t_start: float) -> None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        check_table(bench)
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise BenchFailure(
                f"no workload {name!r} in BENCHMARK.json (has: "
                f"{[w['name'] for w in bench['workloads']]})")
        self.name, self.seed, self.seconds = name, seed, seconds
        self.trace, self.tiny, self.t_start = trace, tiny, t_start
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        conf = next(c for c in bench["configs"]
                    if c["name"] == entry["config"])
        with open(os.path.join(ROOT, conf["file"])) as f:
            self.config = json.load(f)
        self.traffic = load_json("traffic", f"{entry['traffic']}.json")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if metric_applies(m, name)]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if metric_applies(m, name) and m["moves"] in e2e]
        self.keep_trace: Optional[str] = None
        self.setup_s: Optional[float] = None
        self.compiles: Optional[CompileCounter] = None
        self.temporaries: Optional[ProgramTemporaries] = None
        self._compiled_at_window = 0

    def shape(self) -> dict:
        """The sizes this run uses: the configuration's own, or with
        ``--tiny`` its ``sample`` (also the reference check's size)."""
        return self.config["sample"] if self.tiny else self.config

    def settings(self) -> dict:
        """The configuration as this run uses it: ``--tiny`` lets the
        sample override the checks' thresholds too."""
        if not self.tiny:
            return self.config
        return dict(self.config, **{k: v for k, v in
                                    self.config["sample"].items()
                                    if k in ("correct", "reference")})

    def start_window(self) -> float:
        """Set-up ends here; nothing may compile from now on."""
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        self._compiled_at_window = self.compiles.requests
        say(f"set-up done in {self.setup_s:.1f} s; window starts "
            f"({self.seconds:g} s)")
        return now

    def compiled_in_window(self) -> int:
        return self.compiles.requests - self._compiled_at_window


class CompileCounter:
    """Programs that reached the backend's compiler, and how many of
    them the persistent cache answered (jax.monitoring events; copied
    from ``chip_smoke.py``)."""

    def __init__(self) -> None:
        from jax import monitoring

        self.requests = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name: str, _secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class _Tee(io.TextIOBase):
    def __init__(self, out) -> None:
        self.out, self.lines, self._part = out, [], ""

    def write(self, s: str) -> int:
        self.out.write(s)
        self._part += s
        *whole, self._part = self._part.split("\n")
        self.lines.extend(whole)
        return len(s)

    def flush(self) -> None:
        self.out.flush()


@contextlib.contextmanager
def tee_stdout():
    """The program's own stdout lines (the workflow prints its
    ``train phases:`` there), kept and passed on."""
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        yield tee.lines


def device_report() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def peaks_for(kind: str) -> dict:
    table = load_json("peaks.json")
    if kind not in table:
        raise BenchFailure(
            f"device kind {kind!r} is not in benchmark/peaks.json "
            f"(has: {sorted(table)}): no peak, no roofline, no result")
    return table[kind]


class ProgramTemporaries:
    """The compiler's own count of the temporaries of every program this
    process compiled or loaded from the cache (``temp_size_in_bytes`` of
    the executable's memory stats, what ``memory_analysis`` prints).

    Needed because the TPU runtime's ``peak_bytes_in_use`` counts the
    buffers a program is given and gives back, not the scratch it runs
    in: after a train whose program holds 11 GB of temporaries it read
    1.5 GB (my chip run, PR 23). Reads them where JAX hands the
    executable over (``jax._src.compiler.compile_or_get_cached``, the
    installed JAX 0.9's); where that is gone, nothing is added."""

    def __init__(self) -> None:
        self.largest = 0
        try:
            from jax._src import compiler
            inner = compiler.compile_or_get_cached
        except (ImportError, AttributeError):
            say("program temporaries: this JAX has no "
                "compile_or_get_cached to read them from")
            return

        def reading(*args, **kwargs):
            executable = inner(*args, **kwargs)
            try:
                stats = executable.get_compiled_memory_stats()
                self.largest = max(self.largest,
                                   int(stats.temp_size_in_bytes))
            except Exception:  # noqa: BLE001 — a count, never a failure
                pass
            return executable

        compiler.compile_or_get_cached = reading


def memory_peak_bytes(temporaries: Optional[ProgramTemporaries] = None
                      ) -> Optional[int]:
    """Peak bytes on the fullest chip: the runtime's
    ``peak_bytes_in_use`` and, where that is smaller than the largest
    program's temporaries (so cannot have counted them), the two
    together."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    if not peaks:
        return None
    peak, temp = max(peaks), temporaries.largest if temporaries else 0
    say(f"memory: runtime peak_bytes_in_use {peak:,}; largest program's "
        f"temporaries {temp:,}; stats: {jax.devices()[0].memory_stats()}")
    return peak if peak >= temp else peak + temp


def profiler_options():
    """Device ops and our own annotations; no Python call tracing (a
    whole train under it would be a trace of numpy)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def read_layers(cell: Cell, obs: Dict[str, Any]) -> Dict[str, dict]:
    """Each per-layer metric of this cell through its own reader:
    ``benchmark/layers/<name>.py``. A reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for spec in cell.per_layer:
        reader = load_module("layers", spec["name"])
        value = reader.read(obs)
        if value is None:
            say(f"layer metric {spec['name']}: nothing to read, left out")
            continue
        out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def metrics_line(cell: Cell, out: Dict[str, Any]) -> Dict[str, dict]:
    """``--trace 0``: the cell's end-to-end metrics, taken by the
    benchmark's own clock. ``--trace 1``: its per-layer metrics."""
    if cell.trace:
        return read_layers(cell, out["obs"])
    values = dict(out["end_to_end"], setup_s=cell.setup_s)
    missing = [m["name"] for m in cell.end_to_end
               if values.get(m["name"]) is None]
    if missing:
        raise BenchFailure(f"the generator gave no {missing}")
    return {m["name"]: {"value": float(values[m["name"]]),
                        "unit": m["unit"]} for m in cell.end_to_end}


def last_line(correct: bool, attempted: int, failed: int,
              metrics: Dict[str, dict], device: dict,
              breakdown: Optional[dict] = None,
              compared: Optional[List[str]] = None) -> str:
    """``compared``: every check of the run, its number beside its
    limit, under a key of its own that comes LAST (what the driver keeps
    of a run that is not correct is the line's end)."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if compared is not None:
        line["checks"] = list(compared)
    return json.dumps(line)
