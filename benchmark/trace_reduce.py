"""From the profiler's ``.xplane.pb`` to the numbers the layers read.

Reads the trace with ``jax.profiler.ProfileData`` only. A device plane
is one named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event
per operation that ran on the chip (``XLA Modules`` and ``Steps`` are
envelopes around them and are not counted as work). Host planes carry
the benchmark's own ``TraceAnnotation`` spans on the same clock; the
span named :data:`WINDOW` bounds what is reduced.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench:window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclass
class TraceSummary:
    window_s: float                 # length of the traced window
    busy_s: float                   # device busy, averaged over chips
    n_devices: int
    #: self seconds per operation (:func:`short_name`), mean over chips
    op_seconds: Dict[str, float] = field(default_factory=dict)
    op_counts: Dict[str, int] = field(default_factory=dict)
    #: idle gaps on the first chip, longest first: (start_s, length_s),
    #: start relative to the window's start
    gaps: List[Tuple[float, float]] = field(default_factory=list)
    #: first start and last end of an operation on the first chip,
    #: relative to the window's start (None: nothing ran)
    first_op_s: Optional[float] = None
    last_op_s: Optional[float] = None

    def seconds_of(self, name_part: str) -> Optional[float]:
        """Summed device seconds of the operations whose name holds
        ``name_part``; None where the trace has no such operation."""
        hit = [s for n, s in self.op_seconds.items() if name_part in n]
        return sum(hit) if hit else None

    def top_ops(self, n: int = 10) -> List[list]:
        top = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        return [[name, secs] for name, secs in top]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union_seconds(intervals: List[Tuple[int, int]]):
    """(covered ns, gaps) of half-open [start, end) ns intervals; gaps
    are the stretches between covered runs, as (start, end)."""
    covered, gaps = 0, []
    cur_a = cur_b = None
    for a, b in sorted(intervals):
        if cur_b is None:
            cur_a, cur_b = a, b
        elif a <= cur_b:
            cur_b = max(cur_b, b)
        else:
            covered += cur_b - cur_a
            gaps.append((cur_b, a))
            cur_a, cur_b = a, b
    if cur_b is not None:
        covered += cur_b - cur_a
    return covered, gaps


def short_name(op: str) -> str:
    """The trace names an operation by its whole HLO text; keep the
    instruction's name, and for the gather→Gram kernel the rows × width
    of the bucket it served (its first operand)."""
    name = op.split(" = ")[0].lstrip("%")
    if name.startswith("gather_gram"):
        m = re.search(r"custom-call\(s32\[(\d+),(\d+)\]", op)
        if m:
            name += f"[{m.group(1)}x{m.group(2)}]"
    return name


def self_seconds(ops) -> Dict[str, float]:
    """Seconds per operation name NOT covered by an operation nested in
    it: the device's ops line nests a loop's body inside the ``while``,
    and summing both would count the body twice."""
    out: Dict[str, float] = {}
    stack: list = []        # [name, end, start, ns covered by children]

    def close(until: int) -> None:
        while stack and stack[-1][1] <= until:
            name, end, start, inner = stack.pop()
            out[name] = out.get(name, 0.0) + (end - start - inner) / 1e9
            if stack:
                stack[-1][3] += end - start

    for name, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        close(a)
        stack.append([name, b, a, 0])
    close(1 << 62)
    return out


def reduce_planes(planes, window_name: str = WINDOW) -> TraceSummary:
    """``planes``: an iterable of objects shaped like ProfileData's
    (``name``, ``lines`` → ``name``, ``events`` → ``name``,
    ``start_ns``, ``duration_ns``)."""
    window = None
    device_ops = {}
    for plane in planes:
        for line in plane.lines:
            if plane.name.startswith(DEVICE_PREFIX):
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (ev.name, int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns))
                        for ev in line.events]
            elif window is None:
                for ev in line.events:
                    if ev.name == window_name:
                        window = (int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns))
                        break
    if window is None:
        # no span of ours: the window is what the devices' events cover
        stamps = [t for ops in device_ops.values() for _, a, b in ops
                  for t in (a, b)]
        if not stamps:
            return TraceSummary(0.0, 0.0, 0)
        window = (min(stamps), max(stamps))
    w0, w1 = window
    busy, op_seconds, op_counts, gaps = [], {}, {}, []
    first_op = last_op = None
    for k, (_, ops) in enumerate(sorted(device_ops.items())):
        clipped = [(short_name(n), max(a, w0), min(b, w1))
                   for n, a, b in ops if b > w0 and a < w1]
        # the window's two ends as empty intervals, so that the idle
        # stretches before the first and after the last op are gaps too
        covered, holes = union_seconds(
            [(w0, w0)] + [(a, b) for _, a, b in clipped] + [(w1, w1)])
        busy.append(covered)
        for n, secs in self_seconds(clipped).items():
            op_seconds[n] = op_seconds.get(n, 0.0) + secs
        for n, _, _ in clipped:
            op_counts[n] = op_counts.get(n, 0) + 1
        if k == 0:
            gaps = sorted((((a - w0) / 1e9, (b - a) / 1e9)
                           for a, b in holes), key=lambda g: -g[1])
            if clipped:
                first_op = (min(a for _, a, _ in clipped) - w0) / 1e9
                last_op = (max(b for _, _, b in clipped) - w0) / 1e9
    n = len(device_ops)
    if n:
        op_seconds = {k: v / n for k, v in op_seconds.items()}
    return TraceSummary(
        window_s=(w1 - w0) / 1e9,
        busy_s=(sum(busy) / n / 1e9) if n else 0.0,
        n_devices=n, op_seconds=op_seconds, op_counts=op_counts,
        gaps=gaps, first_op_s=first_op, last_op_s=last_op)


def reduce_file(path: str, window_name: str = WINDOW) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window_name)


def label_gaps(gaps, spans, n: int = 10) -> List[list]:
    """Idle seconds by what the host was doing: each gap is cut at the
    boundaries of the host spans (name, start_s, end_s — same origin as
    the gaps) and each piece goes under its span's name; the ``n``
    largest sums, largest first."""
    idle: Dict[str, float] = {}
    for start, length in gaps:
        left = length
        for name, a, b in spans:
            cover = min(b, start + length) - max(a, start)
            if cover > 0:
                idle[name] = idle.get(name, 0.0) + cover
                left -= cover
        if left > 1e-9:
            idle["outside-any-span"] = idle.get("outside-any-span",
                                                0.0) + left
    top = sorted(idle.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs] for name, secs in top]


def describe(path: str) -> str:
    """Planes, lines and event counts — to look at a trace by hand."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            first = events[0].name if events else ""
            rows.append(f"{plane.name} | {line.name} | {len(events)} "
                        f"events | first: {first[:60]}")
    return "\n".join(rows)


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1]))
    s = reduce_file(sys.argv[1])
    print(f"window {s.window_s:.6f} s, busy {s.busy_s:.6f} s on "
          f"{s.n_devices} device(s)")
    for name, secs in s.top_ops():
        print(f"  {secs:.6f} s  x{s.op_counts[name]}  {name}")
