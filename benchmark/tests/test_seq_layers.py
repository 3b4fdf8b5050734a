"""The sequence cell's readers on hand-made observations and on the
trace recorded on the chip (``JAX_PLATFORMS=cpu python3 -m pytest
benchmark/tests/test_seq_layers.py -q``)."""

import json
import os

import numpy as np
import pytest

import harness
import roofline_seq
import scope_reduce
import seqdata
import trace_reduce

TRACE = os.path.join(harness.BENCH, "testdata",
                     "als-ml20m-train-tiny.v5e.xplane.pb")


def _reader(name):
    return harness.load_module("layers", name)


# -- scope_reduce -------------------------------------------------------------


def _varint(v):
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name, ops):
    """An XPlane with one ``XLA Ops`` line: ops = [(tf_op, start_ps,
    duration_ps)], one event metadata each."""
    stat_meta = _field(5, _field(1, 7) + _field(2, _field(1, 7)
                                                + _field(2, b"tf_op")))
    metas, events = b"", b""
    for k, (path, start, dur) in enumerate(ops, 1):
        stat = _field(1, 7) + _field(5, path.encode())
        metas += _field(4, _field(1, k) + _field(2, _field(
            1, k) + _field(2, b"%fusion") + _field(5, stat)))
        events += _field(4, _field(1, k) + _field(2, start) + _field(3, dur))
    line = _field(3, _field(2, trace_reduce.OPS_LINE.encode())
                  + _field(3, 0) + events)
    return _field(1, _field(2, name.encode()) + line + metas + stat_meta)


def test_the_wire_reader_reads_what_was_written():
    msg = _field(1, 300) + _field(2, b"abc") + _field(3, _field(1, 5))
    got = [(n, w, bytes(v) if w == 2 else v)
           for n, w, v in scope_reduce.fields(memoryview(msg))]
    assert got == [(1, 0, 300), (2, 2, b"abc"), (3, 2, _field(1, 5))]


@pytest.mark.parametrize("path,scope", [
    ("jit(train)/while/body/seqrec.mla/seqrec.mla.attention/dot_general:",
     "seqrec.mla.attention"),
    ("jit(train)/transpose(jvp(seqrec.mtp))/seqrec.mla/mul:", "seqrec.mla"),
    ("jit(train)/while/transpose(jvp(seqrec.moe.experts))/ragged_dot:",
     "seqrec.moe.experts"),
    ("jit(train)/while/body/closed_call/add:", "other"),
    ("", "other"),
])
def test_the_innermost_scope_wins(path, scope):
    assert scope_reduce.innermost_scope(path) == scope


def test_scope_seconds_on_a_hand_made_trace(tmp_path):
    ps = 10 ** 12
    ops = [("jit(train)/seqrec.mla/seqrec.mla.attention/dot:", 0, 2 * ps),
           # a loop and its body: the body's second is not counted twice
           ("jit(train)/while", 2 * ps, 3 * ps),
           ("jit(train)/while/body/seqrec.moe.experts/ragged:", 2 * ps, ps),
           ("jit(train)/transpose(jvp(seqrec.mla))/mul:", 6 * ps, ps // 2)]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_plane("/device:TPU:0", ops)
                     + _plane("/host:CPU", [("seqrec.head/x:", 0, ps)]))
    got = scope_reduce.scope_seconds(str(path))
    assert got == pytest.approx({"seqrec.mla.attention": 2.0, "other": 2.0,
                                 "seqrec.moe.experts": 1.0,
                                 "seqrec.mla": 0.5})


def test_a_trace_without_the_scopes_reads_other_only():
    """The recorded ALS trace (a program without ``seqrec.*`` scopes):
    every device second under ``other``, and as many as
    ``trace_reduce`` counts — the two readers see the same events."""
    got = scope_reduce.scope_seconds(TRACE)
    assert set(got) == {"other"}
    assert got["other"] == pytest.approx(
        sum(trace_reduce.reduce_file(TRACE).op_seconds.values()), rel=1e-6)


# -- the layer readers ----------------------------------------------------------


def _Trace(**op_seconds):
    """A traced window of 20 s, the device busy for 10 of them."""
    return trace_reduce.TraceSummary(20.0, 10.0, 1, op_seconds=op_seconds)


OBS = {
    # the grouped products lie under NO scope: found by their names
    "trace": _Trace(**{"ragged-dot-none.9": 0.2, "ragged-dot-none.12": 0.05,
                       "fusion.7": 3.05}),
    "scopes": {"seqrec.mla.attention": 2.0, "seqrec.mla": 1.0,
               "seqrec.moe.route": 0.1, "seqrec.moe.dispatch": 0.2,
               "seqrec.moe.combine": 0.3, "seqrec.moe.experts": 0.5,
               "seqrec.ffn": 1.5, "seqrec.head": 0.7,
               "seqrec.optimizer": 0.4, "other": 3.3},
    "peaks": {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12},
    "need": {"train_flops": 200e12,
             "attention": {"flops": 100e12, "bytes": 1e9},
             "experts": {"flops": 1e12, "bytes": 0.1e12}},
}


@pytest.mark.parametrize("name,want", [
    ("seqrec_device_s", 10.0),
    ("seqrec_step_mfu_pct", 100 * 200e12 / (10.0 * 100e12)),
    ("mla_attention_ms", 2000.0), ("mla_proj_ms", 1000.0),
    ("moe_route_dispatch_ms", 600.0),
    ("moe_experts_ms", 500.0 + 250.0),      # the scope + the kernels
    ("moe_ragged_dot_ms", 250.0),
    ("seqrec_ffn_ms", 1500.0), ("seqrec_head_loss_ms", 700.0),
    ("seqrec_optimizer_ms", 400.0),
    ("mla_attention_roofline", 100 * 1.0 / 2.0),     # bound by flops
    ("moe_experts_roofline", 100 * 0.1 / 0.75),      # bound by bytes
])
def test_device_readers(name, want):
    assert _reader(name).read(OBS) == pytest.approx(want)


def test_the_experts_seconds_are_the_scope_plus_the_kernels():
    """PR 49: ``moe_experts_ms`` and the share's divisor are ALL the
    seconds the experts cost; the scopes, ``other`` less the kernels
    (``seqrec_unscoped_ms``) and the experts still add up to what the
    scopes sum to; None, never the kernels alone, where the trace names
    no ``seqrec.moe.experts`` — and never a share of 0."""
    import seq_layers

    assert seq_layers.seconds(OBS, "moe_experts") == pytest.approx(0.75)
    named = sum(v for k, v in OBS["scopes"].items()
                if k not in ("other", "seqrec.moe.experts"))
    assert (named + _reader("seqrec_unscoped_ms").read(OBS) / 1e3
            + _reader("moe_experts_ms").read(OBS) / 1e3) == pytest.approx(
        sum(OBS["scopes"].values()))
    # a trace with no such kernel (another grouped product): the scope
    quiet = dict(OBS, trace=_Trace(**{"fusion.7": 3.3}))
    assert _reader("moe_experts_ms").read(quiet) == pytest.approx(500.0)
    assert _reader("moe_experts_roofline").read(quiet) == pytest.approx(20.0)
    # the kernels alone, under no scope of that name: nothing to read
    bare = dict(OBS, scopes={"other": 3.3})
    assert seq_layers.seconds(bare, "moe_experts") is None
    assert _reader("moe_experts_ms").read(bare) is None
    assert _reader("moe_experts_roofline").read(bare) is None
    assert _reader("moe_ragged_dot_ms").read(bare) == pytest.approx(250.0)
    # no need, no peak, no such part: no share
    for without in ("need", "peaks"):
        assert _reader("moe_experts_roofline").read(
            {k: v for k, v in OBS.items() if k != without}) is None
    assert _reader("moe_experts_roofline").read(
        dict(OBS, need={"train_flops": 1.0})) is None


@pytest.mark.parametrize("name", [
    "seqrec_device_s", "seqrec_step_mfu_pct", "mla_attention_ms",
    "mla_attention_roofline", "moe_route_dispatch_ms", "moe_experts_ms",
    "moe_experts_roofline", "seqrec_head_loss_ms", "seqrec_optimizer_ms",
    "mla_proj_ms", "seqrec_ffn_ms", "moe_load_max_over_mean",
    "seq_pack_real_pct", "seqrec_pack_s"])
def test_a_program_without_the_spans_or_scopes_reads_nothing(name):
    """What the parent commit gives: no scope in the trace, no
    ``seqrec.*`` span in the record — None, never an exception."""
    obs = {"trace": _Trace(), "scopes": {"other": 9.0}, "spans": [
        {"name": "train.run", "spanId": "a", "parentId": None,
         "startNs": 0, "endNs": 10, "attrs": {}}]}
    assert _reader(name).read(obs) is None or name == "seqrec_device_s"
    assert _reader(name).read({"spans": []}) is None


def test_span_readers():
    tree = [
        {"name": "train.run", "spanId": "r", "parentId": None,
         "startNs": 0, "endNs": 10 ** 10, "attrs": {}},
        {"name": "seqrec.pack", "spanId": "p", "parentId": "r",
         "startNs": 0, "endNs": 5 * 10 ** 7,
         "attrs": {"real_tokens": 900, "slots": 1000}},
        {"name": "seqrec.fit", "spanId": "f", "parentId": "r",
         "startNs": 10 ** 8, "endNs": 10 ** 9,
         "attrs": {"moe_load_max_over_mean": 1.25}}]
    obs = {"spans": tree}
    assert _reader("seq_pack_real_pct").read(obs) == pytest.approx(90.0)
    assert _reader("seqrec_pack_s").read(obs) == pytest.approx(0.05)
    assert _reader("moe_load_max_over_mean").read(obs) == 1.25


# -- roofline_seq -----------------------------------------------------------------


def _cell_config():
    from predictionio_tpu.models.glm4_moe_lite import GlmConfig

    with open(os.path.join(harness.BENCH, "configs",
                           "seqrec-glm47flash-ep8.json")) as f:
        conf = json.load(f)
    generator = harness.load_module("generators", "seq_train_jobs")
    return GlmConfig.from_architecture(generator.architecture(conf, conf))


def test_per_token_operations_at_the_published_shapes():
    """The issue's arithmetic: 352 M matmul parameters touched a token
    (dense layer 84.6; 4 × (MLA 21.8 + shared 9.4 + router 0.1); head
    39.7; MTP 8.4 + 36 + 39.7), with half a routed expert a layer."""
    c = _cell_config()
    macs = roofline_seq.per_token_macs(c)
    assert macs["mla_proj"] == 6 * 21_757_952
    assert macs["dense_ffn"] == 3 * 2048 * 10240
    assert macs["shared_router"] == 5 * (3 * 2048 * 1536 + 2048 * 64)
    assert macs["heads"] == 2 * 2048 * 19360
    routed = 5 * 0.5 * 3 * 2048 * 1536
    assert (sum(macs.values()) + routed) / 1e6 == pytest.approx(352, abs=2)


def test_needs_follow_the_counters():
    c = _cell_config()
    pack = {"sequences": 64, "real_tokens": 262144,
            "attn_pairs": 64 * 4096 * 4097 // 2}
    fit = {"steps": 16, "moe_pairs_here": 16 * 5 * 8192}
    need = roofline_seq.needs(c, fit, pack)
    assert need["experts"]["flops"] == 6 * fit["moe_pairs_here"] * 3 \
        * 2048 * 1536
    assert need["attention"]["flops"] == 6 * pack["attn_pairs"] * 6 * 20 \
        * 512
    twice = roofline_seq.needs(c, dict(fit, steps=32,
                                       moe_pairs_here=2 * 16 * 5 * 8192),
                               pack)
    assert twice["train_flops"] == pytest.approx(2 * need["train_flops"])
    # 47 TFLOP a step when no sequence is cut into segments, as the
    # issue sized it
    assert 30e12 < need["train_flops"] / 16 < 47.1e12


# -- seqdata ------------------------------------------------------------------------

SPEC = {"n_events": 2000, "n_users": 40, "n_items": 120, "data": {
    "user_degree_quantiles": [[0, 4], [0.5, 30], [1, 400]],
    "item_degree_quantiles": [[0, 1], [0.5, 5], [1, 300]],
    "follow_share": 0.6, "successors": 3}}


def test_histories_are_the_seeds_and_exact():
    a, b = seqdata.Histories(SPEC, 7), seqdata.Histories(SPEC, 7)
    c = seqdata.Histories(SPEC, 2 ** 31 + 5)
    assert a.digest() == b.digest() != c.digest()
    assert a.nnz == 2000 == int(a.lengths.sum())
    assert a.lengths.min() >= 4 and a.lengths.max() <= 400
    assert a.items.min() >= 0 and a.items.max() < 120
    # position-major: positions never fall along the import order
    assert (np.diff(a.pos) >= 0).all()
    assert [h.size for h in a.histories()] == a.lengths.tolist()


def test_the_planted_signal_is_there():
    h = seqdata.Histories(dict(SPEC, n_events=20000, n_users=100), 3)
    follows = total = 0
    for seq in h.histories():
        total += seq.size - 1
        follows += sum(int(b in h.successors[a])
                       for a, b in zip(seq[:-1], seq[1:]))
    assert follows / total > 0.55       # follow_share 0.6 and chance hits


def test_wire_lines_carry_rising_event_times():
    h = seqdata.Histories(SPEC, 11)
    lines = [json.loads(ln) for ln in
             seqdata.ndjson(h, 0, h.nnz).decode().splitlines()]
    assert len(lines) == h.nnz
    assert {ln["event"] for ln in lines} == {seqdata.EVENT}
    last = {}
    for ln in lines:
        assert ln["eventTime"] > last.get(ln["entityId"], "")
        last[ln["entityId"]] = ln["eventTime"]
