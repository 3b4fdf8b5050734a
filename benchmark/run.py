#!/usr/bin/env python3
"""One cell of the benchmark, once, in a new process that owns the chip:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

set-up (data from the seed, compile or cache load, warm-up) → the
measured window → the checks → ONE last line of JSON on stdout
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``: every comparison that
decided ``correct``, its number beside its limit — also the last lines
on stderr). Everything else goes on earlier lines.

It sets no platform and no ``PIO_*`` flag that chooses a code path. On
anything but a TPU whose ``device_kind`` is in ``benchmark/peaks.json``
it prints no result and exits non-zero; ``--tiny`` runs every phase at
the configuration's ``sample`` size as a rehearsal (the line it would
print goes on an earlier line, marked) and still fails off a TPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import logging       # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402
import traceback     # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

import checks        # noqa: E402
import harness       # noqa: E402
from harness import BenchFailure, say    # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal size; never a result")
    ap.add_argument("--keep-trace", metavar="DIR", default=None,
                    help="with --trace 1: copy the .xplane.pb there")
    args = ap.parse_args(argv)
    logging.basicConfig(stream=sys.stderr, level=logging.WARNING)

    if not os.path.isdir(os.path.join(ROOT, "predictionio_tpu")):
        say(f"{ROOT} holds no predictionio_tpu/: nothing to measure")
        return 2
    try:
        cell = harness.Cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), args.tiny, T_START)
        cell.keep_trace = args.keep_trace
        device = harness.device_report()
        say(f"device: {device}")
        on_chip = device["platform"] == "tpu"
        if on_chip:
            harness.peaks_for(device["kind"])
            if device["count"] < cell.chips:
                raise BenchFailure(f"{cell.chips} chip(s) wanted, jax "
                                   f"reports {device['count']}")
        elif not args.tiny:
            raise BenchFailure(
                f"platform {device['platform']!r} is not a TPU: no "
                "result (--tiny rehearses the phases)")
        from predictionio_tpu.utils import compilecache

        cell.compiles = harness.CompileCounter()
        cell.temporaries = harness.ProgramTemporaries()
        say(f"compile cache: {compilecache.enable()}")
        generator = harness.load_module("generators",
                                        cell.traffic["generator"])
        out = generator.run(cell)
        metrics = harness.metrics_line(cell, out)
        device["memory_peak_bytes"] = out["obs"]["memory_peak_bytes"]
        breakdown = None
        if cell.trace:
            device["busy_s"] = out["obs"]["trace"].busy_s
            device["window_s"] = out["obs"]["trace"].window_s
            breakdown = out.get("breakdown")
        line = harness.last_line(out["correct"], out["attempted"],
                                 out["failed"], metrics, device, breakdown,
                                 checks.RECORD)
    except BenchFailure as e:
        say(f"FAILED: {e}")
        return 1
    except Exception as e:  # noqa: BLE001 — report, then fail
        traceback.print_exc()
        say(f"FAILED: {type(e).__name__}: {e}")
        return 1
    for told in checks.RECORD:     # each number beside its limit, last
        print(f"[bench] {told}", file=sys.stderr)
    sys.stderr.flush()
    if not on_chip:
        say(f"rehearsal line: {line}")
        say(f"verdict: FAILED (platform {device['platform']!r} is not a "
            "TPU); no result")
        return 1
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
