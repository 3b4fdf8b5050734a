"""``span_gaps.py``: nested ``pio:*`` events cut into pieces that do not
overlap; the idle gaps of a trace recorded on the chip
(``benchmark/testdata/``) named by a made-up host plane; the trace's
events held against a made-up record of the same verb."""

import glob
import os
from collections import namedtuple

import pytest

import span_gaps as sg
import trace_reduce as tr

Ev = namedtuple("Ev", "name start_ns duration_ns")
Line = namedtuple("Line", "name events")
Plane = namedtuple("Plane", "name lines")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_exclusive_cuts_parents_at_their_children():
    events = sorted([("pio:train.run", 0, 100), ("pio:train.read", 10, 40),
                     ("pio:storage.scan", 12, 30), ("pio:train.fit", 40, 90),
                     ("pio:als.prepare", 40, 60), ("pio:als.fetch", 80, 90)],
                    key=lambda e: (e[1], -e[2]))
    pieces = sg.exclusive(events)
    assert pieces == [
        ("pio:train.run", 0, 10, False),
        ("pio:train.read", 10, 12, False),
        ("pio:storage.scan", 12, 30, True),
        ("pio:train.read", 30, 40, False),
        ("pio:als.prepare", 40, 60, True),
        ("pio:train.fit", 60, 80, False),
        ("pio:als.fetch", 80, 90, True),
        ("pio:train.run", 90, 100, False),
    ]
    # the pieces tile the root: nothing counted twice, nothing lost
    assert sum(b - a for _, a, b, _ in pieces) == 100
    assert sg.exclusive([("pio:alone", 5, 9)]) == [("pio:alone", 5, 9, True)]


def made_up_planes():
    host = Plane("/host:CPU", [Line("python", [
        Ev(tr.WINDOW, 1_000, 10_000),                    # [1000, 11000)
        Ev("pio:train.run", 1_200, 9_600),               # [1200, 10800)
        Ev("pio:train.read", 1_200, 800),                # [1200, 2000)
        Ev("pio:storage.scan", 1_300, 600),              # [1300, 1900)
        Ev("pio:train.fit", 2_000, 8_000),               # [2000, 10000)
        Ev("pio:als.prepare", 2_000, 1_000),             # [2000, 3000)
        Ev("pio:als.iterate", 3_000, 5_000),             # [3000, 8000)
        Ev("pio:als.fetch", 8_000, 2_000),               # [8000, 10000)
        Ev("unrelated", 0, 50_000)])])
    dev = Plane("/device:TPU:0", [Line("XLA Ops", [
        Ev("%fusion.1 = f32[8] fusion(...)", 3_500, 4_000),     # [3500, 7500)
        Ev("%copy.2 = f32[8] copy(...)", 8_500, 500)])])        # [8500, 9000)
    return [host, dev]


def test_gaps_go_under_the_leaf_span_that_covers_them():
    table = sg.gaps_by_span(made_up_planes())
    assert table["root_inside_window"] is True
    assert table["idle_s"] == pytest.approx((10_000 - 4_500) * 1e-9)
    rows = {name: secs for name, secs in table["rows"]}
    # idle: [1000,3500) [7500,8500) [9000,11000)
    assert rows == {
        "outside-any-span": pytest.approx((200 + 200) * 1e-9),
        "pio:train.read" + sg.OUTSIDE: pytest.approx(200e-9),
        "pio:storage.scan": pytest.approx(600e-9),
        "pio:als.prepare": pytest.approx(1000e-9),
        "pio:als.iterate": pytest.approx((500 + 500) * 1e-9),
        "pio:als.fetch": pytest.approx((500 + 1000) * 1e-9),
        "pio:train.run" + sg.OUTSIDE: pytest.approx(800e-9),
    }
    named = (600 + 1000 + 1000 + 1500) / 5_500
    assert table["named_leaf_share"] == pytest.approx(named)
    assert [r[1] for r in table["rows"]] == sorted(
        (r[1] for r in table["rows"]), reverse=True)


def test_a_trace_without_pio_events_gives_an_empty_table():
    planes = [Plane("/host:CPU", [Line("python", [
        Ev(tr.WINDOW, 1_000, 10_000)])]), made_up_planes()[1]]
    table = sg.gaps_by_span(planes)
    assert table["rows"] == [] and table["named_leaf_share"] is None
    assert "nothing to divide" in sg.report(table)


def test_the_chips_own_trace_named_by_a_made_up_host_plane():
    """The recorded trace's idle gaps (a tiny train on the v5e) under
    two made-up spans that split its window in the middle."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(BENCH, "testdata", "*.xplane.pb"))
    real = list(ProfileData.from_file(path).planes)
    window, events, _ = sg.host_events(real)
    assert events == []               # recorded before the program had spans
    w0, w1 = window
    mid, end = (w0 + w1) // 2, w1 - 1000
    host = Plane("/host:made-up", [Line("python", [
        Ev("pio:train.run", w0 + 1000, end - w0 - 1000),
        Ev("pio:als.prepare", w0 + 1000, mid - w0 - 1000),
        Ev("pio:als.iterate", mid, end - mid)])])
    table = sg.gaps_by_span(real + [host])
    summary = tr.reduce_planes(real)
    assert table["idle_s"] == pytest.approx(summary.window_s
                                            - summary.busy_s)
    assert sum(secs for _, secs in table["rows"]) == pytest.approx(
        table["idle_s"])
    assert table["named_leaf_share"] > 0.99
    assert {name for name, _ in table["rows"]} <= {
        "pio:als.prepare", "pio:als.iterate", "outside-any-span"}
    assert "pio:train.run inside the window: True" in sg.report(table)


def test_events_against_the_programs_record():
    planes = made_up_planes() + [
        namedtuple("P", "name lines stats")(
            "Task Environment", [], [("profile_start_time", 5_000_000_000)])]
    origin = 777_000          # the program's monotonic clock at train.run

    def rec(name, start, length, wall_us_late=0):
        ns = origin + (start - 1_200)
        return {"name": name, "startNs": ns, "endNs": ns + length,
                # wall clock: profiler start 5 s + the event's offset
                "startUs": (5_000_000_000 + start) / 1e3 - wall_us_late}

    record = [rec("train.run", 1_200, 9_600), rec("train.read", 1_200, 800),
              rec("storage.scan", 1_300, 600),
              rec("train.fit", 2_000, 8_000),
              rec("als.prepare", 2_000, 1_000 + 2_000_000),   # 2 ms longer
              rec("als.iterate", 3_000, 5_000, wall_us_late=3_000),
              rec("als.fetch", 8_000, 2_000),
              rec("als.checkpoint", 9_000, 10)]               # not traced
    got = sg.against_record(planes, record)
    assert got["matched"] == 7 and got["missing_in_trace"] == [
        "als.checkpoint"]
    assert got["worst_length_diff_ms"] == pytest.approx(2.0)
    assert got["worst_start_diff_ms"] == pytest.approx(0.0)
    assert got["worst_wall_clock_offset_ms"] == pytest.approx(3.0)
    assert "7 of 8 spans found" in sg.report(sg.gaps_by_span(planes), got)
