#!/usr/bin/env python3
"""Device idle gaps of a traced train, named by the program's own spans.

Run by hand, as ``compile_check.py`` is:

    python3 benchmark/span_gaps.py <trace.xplane.pb> [--spans tree.json]
    python3 benchmark/span_gaps.py --run DIR --workload <cell> --seed <n> \\
        --seconds <s>

Every span of the ``pio train`` verb enters a
``jax.profiler.TraceAnnotation("pio:<span name>")``
(``predictionio_tpu/utils/tracing.py``), so a trace of the verb holds
the span tree in its host plane, on the clock of the device operations.
This tool cuts the device's idle gaps (``trace_reduce.reduce_planes``) at
the boundaries of those events and sums the pieces per LEAF span
(``trace_reduce.label_gaps``); what a parent span does outside its
children goes under ``<parent> (outside its children)``. It is what a
later ``benchmark`` issue puts in the place of the generator's
``_host_spans``, which can only cut ``train:als`` at the first and last
device operation.

``--run DIR`` makes the traced run itself (``run.py --trace 1
--keep-trace DIR`` in THIS process, which then holds the chip), writes
the program's record of the traced verb to ``DIR/train_run_spans.json``
and holds the trace's ``pio:*`` events against it: length and start
(from the root's) of every span, and the distance between a span's
wall-clock ``startUs`` and its event on the profiler's clock.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402

PREFIX = "pio:"
OUTSIDE = " (outside its children)"
SPANS_FILE = "train_run_spans.json"


def host_events(planes, window_name: str = trace_reduce.WINDOW):
    """(window, events, profile_start_ns): the window as
    ``trace_reduce.reduce_planes`` takes it (the ``bench:window``
    annotation, else what the devices' operations cover), the ``pio:*``
    events of the host planes as (name, start_ns, end_ns) in start
    order, and the profiler's own start on the wall clock (None where
    the trace does not say)."""
    window = start = None
    events, stamps = [], []
    for plane in planes:
        if plane.name == "Task Environment":
            start = dict(getattr(plane, "stats", ())).get(
                "profile_start_time", start)
        for line in plane.lines:
            device = plane.name.startswith(trace_reduce.DEVICE_PREFIX)
            if device and line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                a, b = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                if device:
                    stamps += [a, b]
                elif ev.name == window_name and window is None:
                    window = (a, b)
                elif ev.name.startswith(PREFIX):
                    events.append((ev.name, a, b))
    if window is None and stamps:
        window = (min(stamps), max(stamps))
    return window, sorted(events, key=lambda e: (e[1], -e[2])), start


def exclusive(events) -> List[Tuple[str, int, int, bool]]:
    """Cut nested (name, start, end) events into pieces that do not
    overlap: a leaf keeps its whole stretch, a parent the stretches none
    of its children covers. (name, start, end, is_leaf), in start order.
    Events come sorted by (start, -end), so a parent precedes its
    children."""
    pieces: List[Tuple[str, int, int, bool]] = []
    stack: list = []        # [name, end, cursor, has_children]

    def close(until: int) -> None:
        while stack and stack[-1][1] <= until:
            name, end, cursor, parent = stack.pop()
            if end > cursor:
                pieces.append((name, cursor, end, not parent))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, a, b in events:
        close(a)
        if stack:
            top = stack[-1]
            if a > top[2]:
                pieces.append((top[0], top[2], a, False))
            top[2], top[3] = max(top[2], a), True
            b = min(b, top[1])      # a child ends with its parent
        stack.append([name, b, a, False])
    close(1 << 62)
    return sorted(pieces, key=lambda p: p[1])


def gaps_by_span(planes) -> dict:
    """The table: idle seconds of the first chip per leaf span, from
    planes shaped like ProfileData's."""
    planes = list(planes)
    trace = trace_reduce.reduce_planes(planes)
    window, events, _ = host_events(planes)
    idle = sum(length for _, length in trace.gaps)
    out = {"window_s": trace.window_s, "busy_s": trace.busy_s,
           "idle_s": idle, "rows": [], "named_leaf_share": None,
           "root_inside_window": None}
    if window is None or not events:
        return out
    w0, w1 = window
    root = next((e for e in events if e[0] == PREFIX + "train.run"), None)
    if root is not None:
        out["root_inside_window"] = bool(w0 <= root[1] and root[2] <= w1)
    spans = [(name if leaf else name + OUTSIDE,
              (a - w0) / 1e9, (b - w0) / 1e9)
             for name, a, b, leaf in exclusive(events)]
    rows = trace_reduce.label_gaps(trace.gaps, spans, n=1 << 30)
    out["rows"] = rows
    named = sum(secs for name, secs in rows
                if name.startswith(PREFIX) and not name.endswith(OUTSIDE))
    out["named_leaf_share"] = named / idle if idle else None
    return out


def against_record(planes, tree: List[dict]) -> dict:
    """The trace's ``pio:*`` events against the program's record of the
    same verb: the k-th event of a name is the k-th span of that name.
    Lengths, starts from the root's start, and — where the trace gives
    the profiler's start on the wall clock — how far a span's wall-clock
    ``startUs`` lies from its event."""
    _, events, profile_start = host_events(planes)
    by_name: Dict[str, list] = {}
    for ev in events:
        by_name.setdefault(ev[0], []).append(ev)
    root_ev = next(iter(by_name.get(PREFIX + "train.run", [])), None)
    root_sp = next((s for s in tree if s["name"] == "train.run"), None)
    seen: Dict[str, int] = {}
    rows, missing = [], []
    for sp in tree:
        k = seen.get(sp["name"], 0)
        seen[sp["name"]] = k + 1
        evs = by_name.get(PREFIX + sp["name"], [])
        if k >= len(evs):
            missing.append(sp["name"])
            continue
        _, a, b = evs[k]
        row = {"span": sp["name"],
               "length_diff_ms": ((b - a) - (sp["endNs"] - sp["startNs"]))
               / 1e6}
        if root_ev is not None and root_sp is not None:
            row["start_diff_ms"] = ((a - root_ev[1])
                                    - (sp["startNs"] - root_sp["startNs"])
                                    ) / 1e6
        if profile_start is not None:
            row["wall_clock_offset_ms"] = (
                (profile_start + a) / 1e3 - sp["startUs"]) / 1e3
        rows.append(row)

    def worst(key: str) -> Optional[float]:
        vals = [abs(r[key]) for r in rows if key in r]
        return max(vals) if vals else None

    return {"spans": len(tree), "events": len(events), "matched": len(rows),
            "missing_in_trace": missing,
            "worst_length_diff_ms": worst("length_diff_ms"),
            "worst_start_diff_ms": worst("start_diff_ms"),
            "worst_wall_clock_offset_ms": worst("wall_clock_offset_ms"),
            "rows": rows}


def report(table: dict, agreement: Optional[dict] = None) -> str:
    lines = [f"window {table['window_s']:.3f} s, device busy "
             f"{table['busy_s']:.3f} s, idle {table['idle_s']:.3f} s; "
             f"pio:train.run inside the window: "
             f"{table['root_inside_window']}"]
    for name, secs in table["rows"]:
        lines.append(f"  {secs:9.3f} s  {name}")
    share = table["named_leaf_share"]
    lines.append("idle seconds under a named leaf span: "
                 + ("nothing to divide (no pio:* event in the trace, or no "
                    "idle second)" if share is None
                    else f"{100 * share:.2f} %"))
    if agreement is not None:
        lines.append(
            f"trace against the program's record: {agreement['matched']} "
            f"of {agreement['spans']} spans found; worst difference in "
            f"length {agreement['worst_length_diff_ms']} ms, in start from "
            f"the root's {agreement['worst_start_diff_ms']} ms; wall-clock "
            f"startUs against the event: at most "
            f"{agreement['worst_wall_clock_offset_ms']} ms; missing: "
            f"{agreement['missing_in_trace']}")
    return "\n".join(lines)


def traced_run(keep: str, argv: List[str]) -> int:
    """``run.py <argv> --trace 1 --keep-trace keep`` in this process,
    then the program's record of the traced verb beside the trace."""
    import run

    for old in glob.glob(os.path.join(keep, "*.xplane.pb")):
        os.remove(old)
    rc = run.main(argv + ["--trace", "1", "--keep-trace", keep])
    from predictionio_tpu.utils import tracing

    tree = tracing.last_verb("train.run")
    if tree is not None:
        os.makedirs(keep, exist_ok=True)
        with open(os.path.join(keep, SPANS_FILE), "w") as f:
            json.dump(tree, f)
    return rc


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane", nargs="?", help="a kept .xplane.pb")
    ap.add_argument("--spans", help="the program's record of the traced "
                    "verb (JSON list of span dicts)")
    ap.add_argument("--run", metavar="DIR", help="make the traced run "
                    "first and keep trace and record in DIR; every other "
                    "argument goes to run.py")
    rc = 0
    if "--run" in argv[:-1]:
        at = argv.index("--run")
        args = ap.parse_args(argv[at:at + 2])
        rc = traced_run(args.run, argv[:at] + argv[at + 2:])
        found = sorted(glob.glob(os.path.join(args.run, "*.xplane.pb")))
        if not found:
            print(f"no trace was kept in {args.run} (run.py gave {rc})")
            return rc or 1
        args.xplane = found[-1]
        args.spans = os.path.join(args.run, SPANS_FILE)
    else:
        args = ap.parse_args(argv)
        if not args.xplane:
            ap.error("give a trace, or --run DIR and run.py's arguments")
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(args.xplane).planes)
    table = gaps_by_span(planes)
    agreement = None
    if args.spans and os.path.isfile(args.spans):
        with open(args.spans) as f:
            agreement = against_record(planes, json.load(f))
    print(report(table, agreement))
    if args.run:
        with open(os.path.join(args.run, "span_gaps.json"), "w") as f:
            json.dump({"gaps": table, "agreement": agreement}, f)
    return rc


if __name__ == "__main__":
    sys.exit(main())
