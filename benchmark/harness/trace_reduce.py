"""From a profiler trace (``.xplane.pb``) to the device's numbers.

    python trace_reduce.py <trace dir or .xplane.pb> [--dump]

Read with ``jax.profiler.ProfileData`` and nothing else. A TPU's plane is
named ``/device:TPU:<n>``; on it the line ``XLA Ops`` holds one event per
executed operation and ``XLA Modules`` one per run of a jitted program
(named ``jit_<function>(<fingerprint>)``). Busy time is the union of the
``XLA Ops`` intervals — a sum would count overlapping events twice —
averaged over the device planes found; a program's time is the sum of its
``XLA Modules`` events.

Import this module only when no process holds the chip: it imports jax (the
harness sets ``JAX_PLATFORMS=cpu`` first, and reads the trace after the
broker has exited).
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(path: Path) -> Path:
    if path.is_file():
        return path
    found = sorted(path.rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} .xplane.pb files under {path}")
    return found[0]


def _union(intervals) -> tuple:
    """→ (covered ns, gaps) of (start, end) intervals; ``gaps`` are the
    lengths in ns of the idle stretches between them."""
    busy, gaps, end = 0.0, [], None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                gaps.append(s - end)
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    return busy, gaps


def module_name(event_name: str) -> str:
    """``jit_match_fused_impl(1234567)`` → ``jit_match_fused_impl``."""
    return event_name.split("(", 1)[0]


def reduce(path: Path) -> dict:
    """→ {device_planes, busy_s, modules: {name: [runs, seconds]},
    ops: [[name, seconds], ...] (ten longest), gaps: [seconds, ...]}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(Path(path))))
    planes = [p for p in data.planes if DEVICE_PLANE.match(p.name)]
    busy_ns, modules, ops, gaps = [], {}, {}, []
    for plane in planes:
        intervals = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    intervals.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                    ops[ev.name] = ops.get(ev.name, 0.0) + ev.duration_ns
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    m = modules.setdefault(module_name(ev.name), [0, 0.0])
                    m[0] += 1
                    m[1] += ev.duration_ns
        b, g = _union(intervals)
        busy_ns.append(b)
        gaps += g
    return {
        "device_planes": len(planes),
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9 if busy_ns else 0.0,
        "modules": {k: [n, ns / 1e9] for k, (n, ns) in sorted(modules.items())},
        "ops": [[k if len(k) <= 80 else k[:77] + "...", ns / 1e9] for k, ns in
                sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "gaps": [ns / 1e9 for ns in sorted(gaps, reverse=True)[:10]],
    }


def dump(path: Path) -> None:
    """Planes, lines and the first events of each: for looking at a trace by
    hand before trusting ``reduce`` on it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(Path(path))))
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            total = sum(e.duration_ns for e in events)
            print(f"  line {line.name!r}: {len(events)} events, {total / 1e6:.3f} ms")
            for e in events[:4]:
                print(f"    {e.name[:90]!r} start={e.start_ns:.0f} dur={e.duration_ns:.0f}")


if __name__ == "__main__":
    import json
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if "--dump" in sys.argv:
        dump(Path(sys.argv[1]))
    else:
        print(json.dumps(reduce(Path(sys.argv[1])), indent=1))
