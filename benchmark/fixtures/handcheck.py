#!/usr/bin/env python3
"""A second reading of ``trace_small.xplane.pb``, for the expected numbers.

    python3 benchmark/fixtures/handcheck.py            # prints the events
    python3 benchmark/fixtures/handcheck.py --write    # and trace_small.expected.json

Shares nothing with ``harness/trace_reduce.py``: no JAX, no ``ProfileData``,
no union of intervals. It walks the protobuf wire format itself (XSpace →
XPlane → XLine → XEvent, field numbers from tsl's ``xplane.proto``), lists
every event of the TPU plane's ``XLA Modules`` and ``XLA Ops`` lines with
its start and duration in picoseconds, and adds durations up. The recorded
programs run one after the other, so no two operations overlap (asserted)
and the device's busy time is the plain sum of the operations' durations;
an idle gap is the next operation's start less the last one's end. The
expected file keeps the single durations, so the sums can be added by hand.
"""

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def fields(buf: bytes):
    """(field number, value) of one message: ints for varints, bytes for
    length-delimited fields."""
    i = 0
    while i < len(buf):
        key, shift = 0, 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                break
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, shift = 0, 0
            while True:
                b = buf[i]
                i += 1
                val |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
        elif wire == 2:
            n, shift = 0, 0
            while True:
                b = buf[i]
                i += 1
                n |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            val = buf[i:i + n]
            i += n
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield num, val


def tpu_lines(space: bytes) -> dict:
    """→ {line name: [(event name, start ps, duration ps), ...]} of the
    plane ``/device:TPU:0``; starts count from the earliest line's origin."""
    for num, plane in fields(space):
        if num != 1:
            continue
        f = list(fields(plane))
        if next(v for n, v in f if n == 2) != b"/device:TPU:0":
            continue
        names = {}  # event metadata id → name
        for n, v in f:
            if n == 4:  # map entry: 1 = key, 2 = XEventMetadata (2 = name)
                entry = dict(fields(v))
                names[entry[1]] = dict(fields(entry[2]))[2].decode()
        lines = {}
        for n, v in f:
            if n == 3:
                lf = list(fields(v))
                t_ns = next((x for k, x in lf if k == 3), 0)
                events = []
                for k, ev in lf:
                    if k == 4:
                        e = dict(fields(ev))
                        events.append((names[e[1]], t_ns * 1000 + e.get(2, 0),
                                       e.get(3, 0)))
                lines[next(x for k, x in lf if k == 2).decode()] = events
        return lines
    raise SystemExit("no plane /device:TPU:0")


def main() -> None:
    lines = tpu_lines((HERE / "trace_small.xplane.pb").read_bytes())
    modules = {}
    for name, _start, dur in lines["XLA Modules"]:
        modules.setdefault(name.split("(")[0], []).append(dur)
    ops = sorted(lines["XLA Ops"], key=lambda e: e[1])
    gaps = []
    for (_n, s0, d0), (_m, s1, _d) in zip(ops, ops[1:]):
        assert s1 >= s0 + d0, "two operations overlap: busy is not the plain sum"
        if s1 > s0 + d0:
            gaps.append(s1 - s0 - d0)
    by_op = {}
    for name, _s, dur in ops:
        by_op[name] = by_op.get(name, 0) + dur
    out = {
        "how": "benchmark/fixtures/handcheck.py: the protobuf read field by field, "
               "durations added up; picoseconds unless the key says seconds",
        "device_planes": 1,
        "module_runs_ps": modules,
        "modules": {k: [len(v), sum(v) / 1e12] for k, v in sorted(modules.items())},
        "operations": len(ops),
        "busy_ps": sum(d for _n, _s, d in ops),
        "busy_s": sum(d for _n, _s, d in ops) / 1e12,
        "ops_s": [[k, v / 1e12] for k, v in
                  sorted(by_op.items(), key=lambda kv: -kv[1])[:10]],
        "gaps_s": [g / 1e12 for g in sorted(gaps, reverse=True)[:10]],
    }
    for name, runs in modules.items():
        print(name, runs, "sum", sum(runs), "ps")
    print("operations", len(ops), "busy", out["busy_ps"], "ps")
    print("longest gaps", out["gaps_s"][:3], "s")
    if "--write" in sys.argv:
        (HERE / "trace_small.expected.json").write_text(json.dumps(out, indent=1) + "\n")


if __name__ == "__main__":
    main()
