"""Persistent XLA compilation cache.

The reference has no compile step at run time (Spark ships JVM
bytecode); here every `pio train` jit-compiles the training program,
and at ML-20M geometry a cold compile is most of the wall-clock a user
experiences. JAX's persistent compilation cache stores the compiled
executable keyed by program + compiler fingerprint + **the cache
directory's path**, so every `pio train` / `pio deploy` / `bench.py`
after the first skips XLA entirely — as long as the directory does not
move.

Enabled by :func:`enable` from the workflow entry points. Placement:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module
  uses that directory and writes no ``jax_compilation_cache_dir``.
- unset: one fixed directory, :data:`DEFAULT_DIR` (``.jax_cache/`` at
  the root of the checkout, git-ignored) — independent of ``PIO_HOME``,
  pid, time or temp names, so two runs with different homes share it.

Switching the cache off is JAX's own ``jax_enable_compilation_cache``
(``JAX_ENABLE_COMPILATION_CACHE=0``).

**Compile is a layer of the verb record.** :func:`enable` also
registers, once a process, listeners for what JAX reports of every
jit through ``jax.monitoring``: the entry and the exit of its three
stages — Python tracing, lowering to MLIR, the backend (the compiler,
or the persistent cache's read and the executable's load) — and the
cache's own events, all in the compiling thread. A stage entered while
no other stage of that thread is open is RECORDED; one that begins
inside another (the ``jnp`` functions a step function calls are jits
of their own: hundreds of trace events inside one trace) is part of
it. So the record is three stages a program, and the stages of one
thread never overlap: their seconds add.

- Inside a verb each recorded stage is a child span of the span that
  caused it — ``compile.trace``, ``compile.lower``,
  ``compile.backend`` — with ``program`` and, on the backend's,
  ``cache`` (``hit`` | ``miss`` | ``off``: the request never asked the
  cache) and ``cache_read_s``; the verb's root carries the sums
  (:data:`ROOT_SUMS`).
- Verb or not, ``pio_compile_seconds_total{stage}`` and
  ``pio_compile_programs_total{stage,cache}`` count them
  (``stage``: ``trace`` | ``lower`` | ``compile`` | ``cache_load``).

A warm verb traces nothing, so no listener runs in it.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Optional, Tuple

from predictionio_tpu.utils import tracing
from predictionio_tpu.utils.metrics import REGISTRY

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_enabled = False

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"

#: what a verb's root span carries once one of its spans compiled:
#: stages recorded, programs that reached the backend's compiler (cache
#: ``miss`` + ``off``), the persistent cache's answers, and the seconds
#: of each (``cache_load_s``: read + deserialise + load of the hits)
ROOT_SUMS = ("programs_traced", "programs_lowered", "programs_compiled",
             "cache_hits", "cache_misses", "trace_s", "lower_s",
             "compile_s", "cache_load_s")

_COUNT_OF = {"trace": "programs_traced", "lower": "programs_lowered",
             "compile": "programs_compiled", "cache_load": "cache_hits"}

_M_SECONDS = REGISTRY.counter(
    "pio_compile_seconds_total",
    "Seconds in jit stages (cache_load: a persistent-cache hit's read "
    "and load)", ("stage",))
_M_PROGRAMS = REGISTRY.counter(
    "pio_compile_programs_total",
    "Jit stages run, by stage and the persistent cache's answer",
    ("stage", "cache"))


class _Compiling(threading.local):
    """One thread's open stages: how many, and the outermost one."""

    depth = 0
    #: (stage, span handle or None, perf_counter_ns at entry)
    outer: Optional[Tuple[str, Any, int]] = None
    asked = hit = False
    read_s = 0.0


_THREAD = _Compiling()
_listening = False
_sums_lock = threading.Lock()


def _on_entry(event: str, _value: float, fun_name: str = "",
              **_kw: Any) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        return
    t = _THREAD
    t.depth += 1
    if t.depth > 1:
        return
    handle = None
    cur = tracing.current_span()
    if cur is not None and cur.verb is not None:
        handle = tracing.span(f"compile.{stage}", program=str(fun_name))
        handle.__enter__()
    t.asked = t.hit = False
    t.read_s = 0.0
    t.outer = (stage, handle, time.perf_counter_ns())


def _on_exit(event: str, secs: float, **_kw: Any) -> None:
    t = _THREAD
    if event == _CACHE_READ:
        t.read_s = secs
        return
    if event not in _STAGES or t.depth == 0:
        return
    t.depth -= 1
    if t.depth:
        return
    (stage, handle, t0), t.outer = t.outer, None
    cache = ""
    if stage == "backend":
        cache = "hit" if t.hit else "miss" if t.asked else "off"
        stage = "cache_load" if t.hit else "compile"
    if handle is None:
        secs = (time.perf_counter_ns() - t0) / 1e9
    else:
        if cache:
            handle.span.set_attr("cache", cache)
            handle.span.set_attr("cache_read_s", round(t.read_s, 6))
        handle.__exit__(None, None, None)
        # the span's own length: the root's sums are its spans' sums
        secs = handle.span.seconds
        _add_to_root(handle.span.verb.root, stage, cache, secs)
    _M_SECONDS.inc((stage,), secs)
    _M_PROGRAMS.inc((stage, cache))


def _on_event(event: str, **_kw: Any) -> None:
    if event == _CACHE_ASKED:
        _THREAD.asked = True
    elif event == _CACHE_HIT:
        _THREAD.hit = True


def _add_to_root(root: "tracing.Span", stage: str, cache: str,
                 secs: float) -> None:
    with _sums_lock:
        a = root.attrs
        for k in ROOT_SUMS:
            a.setdefault(k, 0.0 if k.endswith("_s") else 0)
        a[_COUNT_OF[stage]] += 1
        a[f"{stage}_s"] = round(a[f"{stage}_s"] + secs, 9)
        if cache == "miss":
            a["cache_misses"] += 1


def compile_line(root_attrs: dict) -> Optional[str]:
    """What ``pio train`` prints after a verb that compiled: the root's
    sums in one line; None where the verb compiled nothing."""
    a = root_attrs
    if "programs_traced" not in a:
        return None
    return (f"compile: traced {a['programs_traced']} ({a['trace_s']:.1f} s), "
            f"lowered {a['programs_lowered']} ({a['lower_s']:.1f} s), "
            f"compiled {a['programs_compiled']} ({a['compile_s']:.1f} s), "
            f"cache answered {a['cache_hits']} ({a['cache_load_s']:.1f} s)")


def _listen() -> None:
    global _listening
    if _listening:
        return
    from jax import monitoring

    monitoring.register_scalar_listener(_on_entry)
    monitoring.register_event_duration_secs_listener(_on_exit)
    monitoring.register_event_listener(_on_event)
    _listening = True


def enable() -> str:
    """Idempotently turn on JAX's persistent compilation cache and the
    compile record (module docstring); returns the cache dir. Safe to
    call before or after the first jax use — the config is read at
    compile time."""
    global _enabled
    import jax

    _listen()
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_dir = from_env or DEFAULT_DIR
    if _enabled:
        return cache_dir
    os.makedirs(cache_dir, exist_ok=True)
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every program that took ≥1s to compile (default is 60s,
    # which would skip everything but the ALS train program itself)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _enabled = True
    return cache_dir
