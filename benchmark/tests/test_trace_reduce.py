"""The reduction from a profiler trace to busy time, kernel time and
idle gaps: arithmetic on made-up planes, and a trace recorded on the
chip (benchmark/testdata/)."""

import glob
import os
from collections import namedtuple

import pytest

import trace_reduce as tr

Ev = namedtuple("Ev", "name start_ns duration_ns")
Line = namedtuple("Line", "name events")
Plane = namedtuple("Plane", "name lines")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_union_merges_overlaps_and_lists_gaps():
    covered, gaps = tr.union_seconds([(10, 20), (15, 30), (50, 60),
                                      (60, 65)])
    assert covered == 20 + 15
    assert gaps == [(30, 50)]
    assert tr.union_seconds([]) == (0, [])


def planes():
    host = Plane("/host:CPU", [Line("python", [
        Ev("other", 0, 5), Ev(tr.WINDOW, 1_000, 10_000)])])
    gram = ("%gather_gram.1 = (f32[8,64,64]) custom-call(s32[8,128]{1,0} "
            "%a, s32[8,128]{1,0} %b)")
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_train", 1_500, 9_000)]),
        Line("XLA Ops", [
            Ev("%while.3 = (s32[]) while(...)", 2_000, 1_500),  # [2000,3500)
            Ev(gram, 2_000, 1_000),              # nested: [2000, 3000)
            Ev("%fusion.7 = f32[8] fusion(...)", 3_000, 400),   # nested
            Ev(gram, 6_000, 2_000),              # [6000, 8000)
            Ev("%before-window = f32[]", 0, 500),
            Ev("%straddles-end = f32[]", 10_500, 2_000)])])  # [10500,11000)
    return [host, dev]


def test_reduce_counts_only_ops_inside_the_window():
    s = tr.reduce_planes(planes())
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(10_000e-9)
    # busy: [2000,3500) + [6000,8000) + [10500,11000)
    assert s.busy_s == pytest.approx((1500 + 2000 + 500) * 1e-9)
    # names are cut to the instruction (and the kernel's bucket), and a
    # loop keeps only what its body does not cover
    assert s.op_seconds["gather_gram.1[8x128]"] == pytest.approx(3000e-9)
    assert s.op_seconds["while.3"] == pytest.approx(100e-9)
    assert s.op_seconds["fusion.7"] == pytest.approx(400e-9)
    assert sum(s.op_seconds.values()) == pytest.approx(s.busy_s)
    assert s.seconds_of("gather_gram") == pytest.approx(3000e-9)
    assert s.op_counts["gather_gram.1[8x128]"] == 2
    assert s.seconds_of("chol_solve") is None
    assert s.first_op_s == pytest.approx(1000e-9)
    assert s.last_op_s == pytest.approx(10_000e-9)
    # gaps from the window's start, longest first
    assert s.gaps[0] == (pytest.approx(2500e-9), pytest.approx(2500e-9))
    assert {round(g[1] * 1e9) for g in s.gaps} == {1000, 2500}
    assert s.top_ops(1)[0][0] == "gather_gram.1[8x128]"


def test_two_devices_are_averaged():
    p = planes()
    p.append(Plane("/device:TPU:1", [Line("XLA Ops", [
        Ev("fusion.7", 2_000, 2_000)])]))
    s = tr.reduce_planes(p)
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx((4000 + 2000) / 2 * 1e-9)


def test_no_device_plane_gives_nothing_to_read():
    s = tr.reduce_planes(planes()[:1])
    assert s.n_devices == 0 and s.busy_s == 0.0
    assert s.window_s == pytest.approx(10_000e-9)


def test_idle_seconds_are_cut_at_span_boundaries_and_summed():
    spans = [("read_training", 0.0, 4.0), ("train:als", 4.0, 9.0)]
    got = tr.label_gaps([(3.0, 2.5), (8.0, 3.0)], spans)
    assert got == [["train:als", pytest.approx(2.5)],
                   ["outside-any-span", pytest.approx(2.0)],
                   ["read_training", pytest.approx(1.0)]]
    assert tr.label_gaps([(3.0, 2.5), (8.0, 3.0)], spans, n=1) == [
        ["train:als", pytest.approx(2.5)]]


def test_recorded_chip_trace():
    """A ``--tiny --trace 1`` train of als-ml20m-train recorded on one
    v5e chip (PR 23): the device plane, the kernel's name and our own
    window span are where the reduction looks for them."""
    found = glob.glob(os.path.join(BENCH, "testdata", "*.xplane.pb"))
    if not found:
        pytest.skip("no recorded trace in benchmark/testdata/")
    s = tr.reduce_file(found[0])
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.seconds_of("gather_gram") > 0
    assert sum(s.op_seconds.values()) == pytest.approx(s.busy_s)
    # ten iterations: each bucket's kernel ran ten times
    assert s.op_counts["gather_gram.25[1416x128]"] == 10
    assert s.first_op_s is not None and s.gaps
