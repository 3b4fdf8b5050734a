"""Device seconds by ``jax.named_scope``, from the profiler's
``.xplane.pb``.

``trace_reduce.short_name`` keeps an operation's instruction name, and
``jax.profiler.ProfileData`` shows an event's own stats only; the scope
an operation was traced under is in its event METADATA (the ``tf_op``
stat: ``jit(train)/…/transpose(jvp(seqrec.mla))/…/dot_general``). So
this file reads the protobuf's wire format itself — the few fields of
``XSpace`` it needs, no generated code, no TensorFlow — and gives each
operation of the devices' ``XLA Ops`` lines to the INNERMOST scope
named ``seqrec.*`` in that path (self time: a loop's body is not
counted again in its ``while``). Operations under no such scope go
under ``other``. A trace without the stat (a program without the
scopes) gives ``{}`` or only ``other``.
"""

from __future__ import annotations

import re
from typing import Dict, Iterator, Tuple

import trace_reduce

SCOPE = re.compile(r"seqrec\.[a-z_.]+[a-z_]")


def _varint(buf, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one protobuf message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield number, wire, v


def _map_entry(buf):
    key = value = None
    for n, _w, v in fields(buf):
        if n == 1:
            key = v
        elif n == 2:
            value = v
    return key, value


def innermost_scope(op_path: str) -> str:
    found = SCOPE.findall(op_path)
    return found[-1] if found else "other"


def device_ops(path: str):
    """Per device plane: [(scope, start_ps, end_ps)] of its ``XLA Ops``
    line, scopes from the event metadata's ``tf_op`` stat."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for n, _w, plane in fields(space):
        if n != 1:
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for pn, _pw, pv in fields(plane):
            if pn == 2:
                name = bytes(pv).decode()
            elif pn == 3:
                lines.append(pv)
            elif pn == 4:
                event_meta.__setitem__(*_map_entry(pv))
            elif pn == 5:
                key, meta = _map_entry(pv)
                for sn, _sw, sv in fields(meta):
                    if sn == 2:
                        stat_names[key] = bytes(sv).decode()
        if not name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        scope_of = {}
        for key, meta in event_meta.items():
            path_text = ""
            for mn, _mw, mv in fields(meta):
                if mn != 5:
                    continue
                stat = dict((sn, sv) for sn, _sw, sv in fields(mv))
                if stat.get(1) in tf_op and 5 in stat:
                    path_text = bytes(stat[5]).decode()
            scope_of[key] = innermost_scope(path_text)
        for line in lines:
            lname, t0_ns, events = "", 0, []
            for ln, _lw, lv in fields(line):
                if ln == 2:
                    lname = bytes(lv).decode()
                elif ln == 3:
                    t0_ns = lv
                elif ln == 4:
                    events.append(lv)
            if lname != trace_reduce.OPS_LINE:
                continue
            ops = []
            for ev in events:
                e = dict((en, ev_) for en, _ew, ev_ in fields(ev)
                         if en in (1, 2, 3))
                start = t0_ns * 1000 + e.get(2, 0)
                ops.append((scope_of.get(e.get(1), "other"), start,
                            start + e.get(3, 0)))
            out[name] = ops
    return out


def scope_seconds(path: str) -> Dict[str, float]:
    """Self seconds per scope, mean over the device planes."""
    planes = device_ops(path)
    total: Dict[str, float] = {}
    for ops in planes.values():
        # picoseconds through ``self_seconds``'s nanosecond arithmetic
        for scope, secs in trace_reduce.self_seconds(ops).items():
            total[scope] = total.get(scope, 0.0) + secs / 1e3
    return {k: v / len(planes) for k, v in total.items()} if planes else {}


if __name__ == "__main__":
    import sys

    for scope, secs in sorted(scope_seconds(sys.argv[1]).items(),
                              key=lambda kv: -kv[1]):
        print(f"{secs:.6f} s  {scope}")
