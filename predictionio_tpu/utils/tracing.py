"""Request-scoped tracing (dependency-free, fail-open).

The metrics in :mod:`predictionio_tpu.utils.metrics` say *how often*;
this module says *why this one*. Every request entering
:mod:`predictionio_tpu.server.http` gets a 128-bit trace id, and every
decision point on the hot path — deadline checks, breaker trips,
coalesced commits, storage scans, train stages — can open a nested
:func:`span` under it. Spans are linked by ``(trace_id, span_id,
parent_id)``, timed with the monotonic clock, and exported to:

- a bounded in-memory **ring buffer** (always, while tracing is
  enabled) that backs the ``/traces`` debug endpoint and the
  slow-query log;
- an optional **JSONL file** (``pio trace`` tails/greps it) with
  size-based rotation in the :mod:`atomic_write` style (``os.replace``
  + directory fsync — a reader never sees a half-rotated file).

Sampling is hybrid head+tail: the probabilistic decision is made once
per trace at the root span (children inherit it), but a span whose
status is ``error`` or whose duration crosses ``slow_span_ms`` is
exported regardless — the interesting 1% is never the sampled 1%.

Context propagation uses :mod:`contextvars`: nested ``with span(...)``
blocks parent correctly across ``await`` points and through
``asyncio.to_thread`` (which copies the context). Plain
``ThreadPoolExecutor.submit`` does NOT copy context — wrap the callable
with :func:`bind_current` to carry the active span into the pool.

Tracing is **disabled by default** and fail-open by construction:
``span()`` on the disabled path is one attribute read returning a
shared no-op handle, and every exporter call is wrapped so a failing
exporter (drill it with the ``trace.export`` fault site) increments
``pio_trace_export_failures_total`` and nothing else — a trace is never
worth failing the request it describes.

**Verbs** are the exception to "disabled by default": a span opened
under a :func:`verb` root (``pio train`` opens ``train.run``) is
recorded whether or not tracing is enabled — a verb runs for seconds
to minutes and opens a few dozen spans, so the record costs nothing
that matters and is what every timing of the verb is read from. The
finished tree of the newest verb of each root name stays in memory
(:func:`last_verb`), beside that of the FIRST one of the process
(:func:`first_verb`: the cold verb, the only one that holds the
``compile.*`` spans of :mod:`compilecache` and the full scan), and
every verb span enters a
``jax.profiler.TraceAnnotation("pio:<name>")``: under a profiler
session (``PIO_PROFILE_DIR``) the span lands in the trace's host plane,
on the clock of the device operations; with no session that is a flag
test. JAX is imported when a verb opens, never by importing this module
(the event and router servers run without it).

Interop: inbound W3C ``traceparent`` headers are honoured
(``00-<trace>-<span>-<flags>``), as is the simpler ``X-PIO-Trace-Id``;
responses are tagged with ``X-PIO-Trace-Id`` so a client can quote the
id back at ``/traces`` or ``pio trace``.
"""

from __future__ import annotations

import contextvars
import json
import logging
import os
import random
import re
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from predictionio_tpu.utils import faults
from predictionio_tpu.utils.atomic_write import fsync_dir
from predictionio_tpu.utils.metrics import REGISTRY

logger = logging.getLogger("pio.trace")

_M_SPANS = REGISTRY.counter(
    "pio_trace_spans_total", "Spans finished", ("status",))
_M_EXPORT_FAILURES = REGISTRY.counter(
    "pio_trace_export_failures_total",
    "Span exports that raised (fail-open: the request was unaffected)")

_TRACEPARENT_RE = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$")
_TRACE_ID_RE = re.compile(r"^[0-9a-fA-F]{16,64}$")

_CURRENT: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "pio_current_span", default=None)


# ids need uniqueness, not unpredictability: a Mersenne PRNG seeded
# from the OS once is ~30% cheaper per span than an os.urandom syscall
_ID_RNG = random.Random(os.urandom(16))


def new_trace_id() -> str:
    # | 1 — the all-zero trace id is invalid per W3C trace-context
    return f"{_ID_RNG.getrandbits(128) | 1:032x}"


def new_span_id() -> str:
    return f"{_ID_RNG.getrandbits(64) | 1:016x}"


class Span:
    """One timed operation. Created via :func:`span`/:func:`root_span`,
    finished (and exported) when its ``with`` block exits."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "attrs",
                 "start_us", "duration_us", "status", "error", "sampled",
                 "verb", "duration_ns", "_t0")

    def __init__(self, name: str, trace_id: str, parent_id: Optional[str],
                 sampled: bool, attrs: Optional[Dict[str, Any]] = None,
                 verb: Optional["_Verb"] = None) -> None:
        self.name = name
        self.trace_id = trace_id
        self.span_id = new_span_id()
        self.parent_id = parent_id
        self.sampled = sampled
        #: the verb record this span belongs to (None: a request span)
        self.verb = verb
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        self.status = "ok"
        self.error: Optional[str] = None
        self.start_us = time.time_ns() // 1000
        self.duration_us = 0
        self.duration_ns = 0
        self._t0 = time.perf_counter_ns()

    @property
    def seconds(self) -> float:
        """Length of the finished span (0.0 while it is open)."""
        return self.duration_ns / 1e9

    def set_attr(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def set_error(self, message: str) -> None:
        self.status = "error"
        self.error = message

    def traceparent(self) -> str:
        flags = "01" if self.sampled else "00"
        return f"00-{self.trace_id}-{self.span_id}-{flags}"

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "parentId": self.parent_id,
            "name": self.name,
            "startUs": self.start_us,
            "durationUs": self.duration_us,
            "status": self.status,
        }
        if self.error is not None:
            d["error"] = self.error
        if self.attrs:
            d["attrs"] = self.attrs
        return d


class _SpanHandle:
    """Context manager (sync AND async) that activates a span on enter
    and finishes/exports it on exit. Exceptions mark the span ``error``
    and propagate."""

    __slots__ = ("span", "_tracer", "_token", "_annotation")

    def __init__(self, tracer: "Tracer", span: Span) -> None:
        self.span = span
        self._tracer = tracer
        self._token: Optional[contextvars.Token] = None
        self._annotation: Optional[Any] = None

    def __enter__(self) -> Span:
        self._token = _CURRENT.set(self.span)
        verb = self.span.verb
        if verb is not None:
            if self.span.parent_id is None:
                self._tracer._count_open_verbs(+1)  # finish() takes it off
            # the profiler's own span of the same stretch: with no
            # profiler session this is a flag test
            self._annotation = verb.annotation(f"pio:{self.span.name}")
            self._annotation.__enter__()
        return self.span

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        if self._token is not None:
            _CURRENT.reset(self._token)
            self._token = None
        self._tracer.finish(self.span, exc_type, exc)
        return False

    async def __aenter__(self) -> Span:
        return self.__enter__()

    async def __aexit__(self, exc_type, exc, tb) -> bool:
        return self.__exit__(exc_type, exc, tb)


class _NoopSpan:
    """Shared do-nothing handle returned while tracing is disabled —
    the whole disabled-path cost of ``with span(...)`` is one attribute
    read plus this object's (empty) enter/exit."""

    __slots__ = ()
    trace_id = ""
    span_id = ""
    parent_id = None
    sampled = False
    status = "ok"
    verb = None
    #: no span, no timing (``Engine.train`` outside a verb)
    seconds = None

    def set_attr(self, key: str, value: Any) -> None:
        pass

    def set_error(self, message: str) -> None:
        pass

    def traceparent(self) -> str:
        return ""

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    async def __aenter__(self) -> "_NoopSpan":
        return self

    async def __aexit__(self, exc_type, exc, tb) -> bool:
        return False


NOOP_SPAN = _NoopSpan()


# -- exporters -----------------------------------------------------------------


class RingBufferExporter:
    """Bounded deque of finished span dicts — the store behind the
    ``/traces`` endpoint and the slow-query log. Receives EVERY span
    while tracing is enabled (sampling gates only the file exporter):
    the ring's job is "what just happened", and a bounded recent window
    costs the same either way."""

    def __init__(self, capacity: int = 2048) -> None:
        self.capacity = capacity
        self._buf: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()

    def export(self, span_dict: Dict[str, Any]) -> None:
        with self._lock:
            self._buf.append(span_dict)

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()

    def spans(self, trace_id: Optional[str] = None,
              min_duration_ms: Optional[float] = None,
              errors_only: bool = False,
              limit: int = 100) -> List[Dict[str, Any]]:
        """Newest-first filtered view (the ``/traces`` contract)."""
        with self._lock:
            snap = list(self._buf)
        out: List[Dict[str, Any]] = []
        for d in reversed(snap):
            if trace_id is not None and d.get("traceId") != trace_id:
                continue
            if min_duration_ms is not None and \
                    d.get("durationUs", 0) < min_duration_ms * 1000.0:
                continue
            if errors_only and d.get("status") != "error":
                continue
            out.append(d)
            if len(out) >= limit:
                break
        return out

    def trace(self, trace_id: str) -> List[Dict[str, Any]]:
        """All buffered spans of one trace, oldest first."""
        with self._lock:
            snap = list(self._buf)
        got = [d for d in snap if d.get("traceId") == trace_id]
        got.sort(key=lambda d: d.get("startUs", 0))
        return got

    def export_by_trace_ids(self, trace_ids) -> List[Dict[str, Any]]:
        """All buffered spans belonging to any of the given trace ids,
        oldest first — the incident-bundle pin of the traces the
        offending latency buckets name via exemplars."""
        wanted = set(trace_ids)
        if not wanted:
            return []
        with self._lock:
            snap = list(self._buf)
        got = [d for d in snap if d.get("traceId") in wanted]
        got.sort(key=lambda d: d.get("startUs", 0))
        return got


class JSONLExporter:
    """Append-one-JSON-line-per-span file exporter with size-based
    rotation. Rotation follows the :mod:`atomic_write` discipline:
    ``os.replace`` to ``<path>.1`` then directory fsync, so ``pio
    trace`` never reads a half-moved file. Thread-safe; opens lazily so
    configuring a path costs nothing until the first sampled span."""

    def __init__(self, path: str, max_bytes: int = 32 * 1024 * 1024) -> None:
        self.path = path
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        self._f: Optional[Any] = None
        self._size = 0

    def _open(self) -> None:
        """Caller holds the lock."""
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(self.path, "ab")
        self._size = self._f.tell()

    def _rotate(self) -> None:
        """Caller holds the lock."""
        assert self._f is not None
        self._f.flush()
        os.fsync(self._f.fileno())
        self._f.close()
        self._f = None
        os.replace(self.path, self.path + ".1")
        d = os.path.dirname(self.path)
        fsync_dir(d if d else ".")
        self._open()

    def export(self, span_dict: Dict[str, Any]) -> None:
        data = (json.dumps(span_dict, separators=(",", ":"),
                           default=str) + "\n").encode("utf-8")
        with self._lock:
            if self._f is None:
                self._open()
            assert self._f is not None
            if self._size and self._size + len(data) > self.max_bytes:
                self._rotate()
            self._f.write(data)
            self._f.flush()
            self._size += len(data)

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None


# -- tracer --------------------------------------------------------------------


class Tracer:
    """Process-wide tracing state: the enabled flag, the sampling
    policy, the ring buffer, and any extra exporters. There is one
    instance, :data:`TRACER`; :meth:`configure` is how the CLI flags
    reach it."""

    def __init__(self) -> None:
        #: per-request tracing (``--tracing``); changed by
        #: :meth:`configure` only, which keeps :attr:`active` in step
        self.enabled = False
        #: what ``span()`` reads: enabled, or a verb is open somewhere
        #: in the process (its spans are recorded either way)
        self.active = False
        self._open_verbs = 0
        self._verb_lock = threading.Lock()
        #: root name → the finished tree of the newest verb of that
        #: name (:func:`last_verb`)
        self.last_verbs: Dict[str, List[Dict[str, Any]]] = {}
        #: root name → the finished tree of the FIRST verb of that name
        #: in this process, written once (:func:`first_verb`)
        self.first_verbs: Dict[str, List[Dict[str, Any]]] = {}
        #: probability a NEW trace is file-exported (errors and slow
        #: spans always are — tail sampling)
        self.sample_rate = 1.0
        #: spans at/over this duration export regardless of sampling
        self.slow_span_ms = 250.0
        #: root spans at/over this get their full tree logged (0 = off)
        self.slow_query_ms = 0.0
        self.ring = RingBufferExporter()
        self.exporters: List[Any] = []
        self._rng = random.Random()

    def configure(self, enabled: bool = True,
                  sample_rate: Optional[float] = None,
                  slow_span_ms: Optional[float] = None,
                  slow_query_ms: Optional[float] = None,
                  jsonl_path: Optional[str] = None,
                  ring_capacity: Optional[int] = None,
                  exporters: Optional[List[Any]] = None) -> "Tracer":
        if sample_rate is not None:
            if not (0.0 <= sample_rate <= 1.0):
                raise ValueError(
                    f"sample_rate must be in [0, 1], got {sample_rate}")
            self.sample_rate = sample_rate
        if slow_span_ms is not None:
            self.slow_span_ms = slow_span_ms
        if slow_query_ms is not None:
            self.slow_query_ms = slow_query_ms
        if ring_capacity is not None:
            self.ring = RingBufferExporter(ring_capacity)
        if exporters is not None:
            self.exporters = list(exporters)
        if jsonl_path is not None:
            self.exporters = [e for e in self.exporters
                              if not isinstance(e, JSONLExporter)]
            self.exporters.append(JSONLExporter(jsonl_path))
        self.enabled = enabled
        self.active = enabled or self._open_verbs > 0
        return self

    def _count_open_verbs(self, delta: int) -> None:
        with self._verb_lock:
            self._open_verbs = max(0, self._open_verbs + delta)
            self.active = self.enabled or self._open_verbs > 0

    def reset(self) -> None:
        """Back to the disabled defaults (tests)."""
        for e in self.exporters:
            close = getattr(e, "close", None)
            if close:
                try:
                    close()
                except Exception:
                    pass
        self.__init__()  # type: ignore[misc]

    # -- span lifecycle --------------------------------------------------------

    def _decide_sampled(self) -> bool:
        r = self.sample_rate
        return r >= 1.0 or (r > 0.0 and self._rng.random() < r)

    def finish(self, span: Span, exc_type=None, exc=None) -> None:
        """Close the books on a span: stamp duration, fold in any
        in-flight exception, export (fail-open), maybe log slowness."""
        if exc is not None and span.status != "error":
            span.set_error(f"{getattr(exc_type, '__name__', 'Exception')}: {exc}")
        span.duration_ns = time.perf_counter_ns() - span._t0
        span.duration_us = span.duration_ns // 1000
        if span.verb is not None:
            span.verb.record(span)
            if span.parent_id is None:
                self._count_open_verbs(-1)
        if not self.enabled:
            return
        _M_SPANS.inc((span.status,))
        d = span.to_dict()
        try:
            faults.inject("trace.export")
            self.ring.export(d)
        except Exception:
            _M_EXPORT_FAILURES.inc()
        if span.sampled or span.status == "error" or \
                span.duration_us >= self.slow_span_ms * 1000.0:
            for exp in self.exporters:
                try:
                    faults.inject("trace.export")
                    exp.export(d)
                except Exception:
                    _M_EXPORT_FAILURES.inc()
        if span.parent_id is None and self.slow_query_ms > 0 and \
                span.duration_us >= self.slow_query_ms * 1000.0:
            try:
                self._log_slow(span)
            except Exception:  # the log is best-effort like the export
                _M_EXPORT_FAILURES.inc()

    def _log_slow(self, root: Span) -> None:
        tree = self.ring.trace(root.trace_id)
        logger.warning(
            "slow request trace=%s %s took %.1fms (threshold %.0fms)\n%s",
            root.trace_id, root.name, root.duration_us / 1000.0,
            self.slow_query_ms, render_trace_tree(tree))


TRACER = Tracer()


# -- verbs ---------------------------------------------------------------------


class _Verb:
    """The spans of one running verb, finished ones only, as dicts:
    ``Span.to_dict()`` plus ``startNs`` / ``endNs`` on
    ``time.perf_counter_ns`` (what lengths and the order of siblings
    are read from; ``startUs`` is wall-clock and may step)."""

    __slots__ = ("name", "spans", "annotation", "root")

    def __init__(self, name: str) -> None:
        # the train path has imported JAX long before it opens its
        # verb; the servers that import this module never get here
        from jax.profiler import TraceAnnotation

        self.name = name
        self.spans: List[Dict[str, Any]] = []
        self.annotation = TraceAnnotation
        #: the verb's root span (set by :func:`verb`): where a child
        #: leaves what it sums over the whole verb (``compilecache``)
        self.root: Optional[Span] = None

    def record(self, span: Span) -> None:
        d = span.to_dict()
        d["startNs"] = span._t0
        d["endNs"] = span._t0 + span.duration_ns
        self.spans.append(d)     # list.append: safe from any thread
        if span.parent_id is None:
            self.spans.sort(key=lambda s: s["startNs"])
            TRACER.last_verbs[self.name] = self.spans
            TRACER.first_verbs.setdefault(self.name, self.spans)


def verb(name: str, **attrs: Any):
    """Open the root of a verb (``train.run``): a new trace, whatever
    the context holds, whose spans are recorded whether or not tracing
    is enabled and written into the profiler's trace as ``pio:<name>``
    (module docstring). When tracing IS enabled the spans also go to
    the ring and the exporters, like any other."""
    tr = TRACER
    record = _Verb(name)
    s = record.root = Span(name, new_trace_id(), None,
                           tr.enabled and tr._decide_sampled(), attrs,
                           record)
    return _SpanHandle(tr, s)


def last_verb(name: str) -> Optional[List[Dict[str, Any]]]:
    """The span dicts of the newest FINISHED verb rooted at ``name``,
    root first, in start order; None when no such verb has run in this
    process. One tree is kept per root name; the next verb replaces
    it."""
    spans = TRACER.last_verbs.get(name)
    return None if spans is None else list(spans)


def first_verb(name: str) -> Optional[List[Dict[str, Any]]]:
    """As :func:`last_verb`, of the FIRST finished verb rooted at
    ``name`` in this process: kept once and never replaced. In a ``pio
    train`` process it is the same verb; in a long-lived one (the
    continuous trainer, the benchmark) it is the cold one."""
    spans = TRACER.first_verbs.get(name)
    return None if spans is None else list(spans)


# -- span entry points ---------------------------------------------------------


def span(name: str, **attrs: Any):
    """Open a child span of the context's current span (or a new root
    if there is none). Usable as ``with`` and ``async with``. On the
    disabled path this returns the shared no-op handle; under a verb
    (:func:`verb`) the span is recorded either way."""
    tr = TRACER
    if not tr.active:
        return NOOP_SPAN
    parent = _CURRENT.get()
    if parent is not None:
        if parent.verb is None and not tr.enabled:
            return NOOP_SPAN
        s = Span(name, parent.trace_id, parent.span_id, parent.sampled,
                 attrs, parent.verb)
    elif tr.enabled:
        s = Span(name, new_trace_id(), None, tr._decide_sampled(), attrs)
    else:       # a verb is open elsewhere in the process, not here
        return NOOP_SPAN
    return _SpanHandle(tr, s)


def root_span(name: str, trace_id: Optional[str] = None,
              parent_span_id: Optional[str] = None,
              sampled: Optional[bool] = None, **attrs: Any):
    """Open a trace root, honouring inbound propagation headers: an
    inbound trace id continues that trace; an inbound sampled flag
    overrides the local sampling decision. Ignores any span already in
    context (this IS the context boundary)."""
    tr = TRACER
    if not tr.enabled:
        return NOOP_SPAN
    if sampled is None:
        sampled = tr._decide_sampled()
    s = Span(name, trace_id or new_trace_id(), parent_span_id, sampled, attrs)
    return _SpanHandle(tr, s)


def detached_span(name: str, **attrs: Any):
    """A new root regardless of context — for background work (e.g. the
    coalescer's group commit) that serves MANY requests' traces and
    links to them via attributes instead of parentage."""
    tr = TRACER
    if not tr.enabled:
        return NOOP_SPAN
    s = Span(name, new_trace_id(), None, tr._decide_sampled(), attrs)
    return _SpanHandle(tr, s)


# -- context helpers -----------------------------------------------------------


def current_span() -> Optional[Span]:
    return _CURRENT.get()


def current_trace_id() -> Optional[str]:
    s = _CURRENT.get()
    return s.trace_id if s is not None else None


def exemplar() -> Optional[str]:
    """Trace id for histogram exemplars — None when tracing is off or
    no span is active, so ``observe(..., exemplar=tracing.exemplar())``
    is safe on every path."""
    if not TRACER.enabled:
        return None
    s = _CURRENT.get()
    return s.trace_id if s is not None else None


def add_attrs(**attrs: Any) -> None:
    """Attach attributes to the current span, if any — lets deep code
    (e.g. a storage backend) annotate the span its caller opened."""
    s = _CURRENT.get()
    if s is not None:
        s.attrs.update(attrs)


def bind_current(fn: Callable) -> Callable:
    """Carry the caller's context (current span included) into a plain
    ``ThreadPoolExecutor``; ``asyncio.to_thread`` does this natively,
    raw ``submit`` does not."""
    ctx = contextvars.copy_context()

    def _bound(*args, **kwargs):
        return ctx.run(fn, *args, **kwargs)

    return _bound


# -- propagation headers -------------------------------------------------------


def parse_traceparent(value: str) -> Optional[Tuple[str, str, bool]]:
    """``(trace_id, parent_span_id, sampled)`` or None if malformed.
    Per W3C: all-zero ids are invalid; unknown versions are accepted on
    the 00 field layout."""
    m = _TRACEPARENT_RE.match(value.strip().lower())
    if m is None:
        return None
    version, trace_id, span_id, flags = m.groups()
    if version == "ff" or trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id, bool(int(flags, 16) & 1)


def extract_headers(
        headers: Dict[str, str]) -> Tuple[Optional[str], Optional[str],
                                          Optional[bool]]:
    """Inbound propagation from lowercase-keyed headers: prefer W3C
    ``traceparent``, fall back to ``x-pio-trace-id`` (id only, local
    sampling decision)."""
    tp = headers.get("traceparent")
    if tp:
        parsed = parse_traceparent(tp)
        if parsed is not None:
            return parsed
    tid = headers.get("x-pio-trace-id")
    if tid and _TRACE_ID_RE.match(tid):
        return tid.lower(), None, None
    return None, None, None


# -- presentation --------------------------------------------------------------


def render_trace_tree(spans: List[Dict[str, Any]]) -> str:
    """Indented one-line-per-span tree of a trace's span dicts (the
    slow-query log and ``pio trace --tree`` share this)."""
    by_id = {d["spanId"]: d for d in spans if d.get("spanId")}
    children: Dict[Optional[str], List[Dict[str, Any]]] = {}
    for d in spans:
        pid = d.get("parentId")
        key = pid if pid in by_id else None
        children.setdefault(key, []).append(d)
    for kids in children.values():
        # a verb's spans carry the monotonic start too: wall-clock
        # microseconds tie (and may step) between siblings
        kids.sort(key=lambda d: d.get("startNs", d.get("startUs", 0)))
    lines: List[str] = []

    def emit(d: Dict[str, Any], depth: int) -> None:
        dur = d.get("durationUs", 0) / 1000.0
        status = d.get("status", "ok")
        attrs = d.get("attrs") or {}
        extra = " ".join(f"{k}={v}" for k, v in attrs.items())
        err = f" error={d['error']!r}" if d.get("error") else ""
        lines.append(f"{'  ' * depth}{d.get('name', '?')} {dur:.2f}ms "
                     f"[{status}]{err}{' ' + extra if extra else ''}")
        for kid in children.get(d.get("spanId"), []):
            emit(kid, depth + 1)

    for root in children.get(None, []):
        emit(root, 0)
    return "\n".join(lines)


def traces_payload(trace_id: Optional[str] = None,
                   min_ms: Optional[float] = None,
                   errors_only: bool = False,
                   limit: int = 100) -> Dict[str, Any]:
    """The ``/traces`` endpoint body (shared by both servers)."""
    spans = TRACER.ring.spans(trace_id=trace_id, min_duration_ms=min_ms,
                              errors_only=errors_only, limit=limit)
    return {"enabled": TRACER.enabled, "count": len(spans), "spans": spans}


def default_trace_path(home: str) -> str:
    """Where servers write (and ``pio trace`` reads) the JSONL export
    when ``--trace-file`` is not given."""
    return os.path.join(home, "traces", "spans.jsonl")
