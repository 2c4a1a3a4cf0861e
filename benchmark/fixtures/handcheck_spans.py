#!/usr/bin/env python3
"""A second reading of ``trace_spans.xplane.pb``, for the expected numbers.

    python3 benchmark/fixtures/handcheck_spans.py            # prints them
    python3 benchmark/fixtures/handcheck_spans.py --write    # and trace_spans.expected.json

Shares nothing with ``harness/host_spans.py``: no JAX, no ``ProfileData``,
no numpy, no sweep. It walks the protobuf with ``handcheck.fields`` (XSpace
→ XPlane → XLine → XEvent; names and the ``tf_op`` stat from the plane's
metadata maps), keeps every time in picoseconds, and answers the question
the slow way: the trace is cut at EVERY edge of every span and operation,
and each piece between two neighbouring edges is looked at on its own — is
an operation running? else which ``matcher.*`` span is open (the one opened
last)? else which span has the loop thread open (the one opened last;
``routing.match.side`` is called ``hybrid.side``)? else ``loop.unspanned``.
The loop thread is the line that holds the ``rmqtt/loop.idle`` events. A
scope's device time is the plain sum of its operations' durations (no two
of one scope overlap here: asserted).
"""

import json
import sys
from pathlib import Path

from handcheck import fields

HERE = Path(__file__).resolve().parent
SCOPES = {"scan", "compact", "resolve", "sort", "counts"}


def planes(space: bytes) -> dict:
    """→ {plane name: {"lines": [[(event name, start ps, duration ps)]],
    "line_names": [...], "tf_op": {event name: text}}}."""
    out = {}
    for num, plane in fields(space):
        if num != 1:
            continue
        f = list(fields(plane))
        pname = next(v for n, v in f if n == 2).decode()
        stat_names = {}
        for n, v in f:
            if n == 5:
                e = dict(fields(v))
                stat_names[e[1]] = dict(fields(e[2])).get(2, b"").decode()
        names, tf_op = {}, {}
        for n, v in f:
            if n == 4:
                e = dict(fields(v))
                meta = list(fields(e[2]))
                name = next((x for k, x in meta if k == 2), b"").decode()
                names[e[1]] = name
                for k, x in meta:
                    if k == 5:
                        st = dict(fields(x))
                        if stat_names.get(st.get(1)) == "tf_op":
                            # the text itself, or the id of the stat
                            # metadata that is named by it
                            tf_op[name] = (st[5].decode() if 5 in st
                                           else stat_names[st[7]])
        lines, line_names = [], []
        for n, v in f:
            if n == 3:
                lf = list(fields(v))
                t_ns = next((x for k, x in lf if k == 3), 0)
                lines.append([(names[e[1]], t_ns * 1000 + e.get(2, 0), e.get(3, 0))
                              for e in (dict(fields(ev)) for k, ev in lf if k == 4)])
                line_names.append(next((x for k, x in lf if k == 2), b"").decode())
        out[pname] = {"lines": lines, "line_names": line_names, "tf_op": tf_op}
    return out


def open_last(spans, t):
    """Of the (name, start, dur) spans that cover instant ``t``, the one
    that started last; None where none covers it."""
    best = None
    for name, s, d in spans:
        if s <= t < s + d and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else None


def main() -> None:
    p = planes((HERE / "trace_spans.xplane.pb").read_bytes())
    dev = p["/device:TPU:0"]
    ops = dev["lines"][dev["line_names"].index("XLA Ops")]
    mods = dev["lines"][dev["line_names"].index("XLA Modules")]
    threads = [[(n[len("rmqtt/"):], s, d) for n, s, d in line if n.startswith("rmqtt/")]
               for line in p["/host:CPU"]["lines"]]
    threads = [t for t in threads if t]
    (loop,) = [t for t in threads if any(n == "loop.idle" for n, _s, _d in t)]
    matcher = [e for t in threads for e in t if e[0].startswith("matcher.")]
    loop_own = [e for e in loop if not e[0].startswith("matcher.")]
    every = [e for t in threads for e in t]
    lo = min(s for _n, s, _d in every + ops)
    hi = max(s + d for _n, s, d in every + ops)
    edges = sorted({x for _n, s, d in every + ops for x in (s, s + d)})
    idle, total = {}, 0
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) // 2  # every piece is longer than 2 ps or empty
        if b - a < 2 or any(s <= mid < s + d for _n, s, d in ops):
            continue
        name = open_last(matcher, mid) or open_last(loop_own, mid) or "loop.unspanned"
        name = {"routing.match.side": "hybrid.side"}.get(name, name)
        idle[name] = idle.get(name, 0) + (b - a)
        total += b - a
    # gaps between operations, longest first; named by the largest share
    runs = sorted((s, s + d) for _n, s, d in ops)
    gaps = []
    for (_s0, e0), (s1, _e1) in zip(runs, runs[1:]):
        if s1 > e0:
            share = {}
            for a, b in zip(edges, edges[1:]):
                if e0 <= a and b <= s1 and b - a >= 2:
                    mid = (a + b) // 2
                    n = open_last(matcher, mid) or open_last(loop_own, mid) \
                        or "loop.unspanned"
                    n = {"routing.match.side": "hybrid.side"}.get(n, n)
                    share[n] = share.get(n, 0) + (b - a)
            gaps.append((s1 - e0, max(share, key=share.get)))
    gaps.sort(key=lambda g: -g[0])
    scope = {}
    for name, s, d in ops:
        parts = dev["tf_op"].get(name, "").rstrip(":").split("/")
        sc = next((x for x in parts if x in SCOPES), "unscoped")
        scope.setdefault(sc, []).append((s, d))
    for sc, evs in scope.items():
        evs.sort()
        assert all(s1 >= s0 + d0 for (s0, d0), (s1, _d) in zip(evs, evs[1:])), \
            f"operations of scope {sc} overlap: its time is not the plain sum"
    spans = {}
    for n, _s, d in every:
        row = spans.setdefault(n, [0, 0])
        row[0] += 1
        row[1] += d
    match = [d for n, _s, d in mods if n.startswith("jit_match_")]
    out = {
        "how": "benchmark/fixtures/handcheck_spans.py: the protobuf read field by "
               "field, the trace cut at every edge; picoseconds",
        "events": len(every) + len(ops),
        "extent_ps": [lo, hi],
        "idle_ps": dict(sorted(idle.items())),
        "idle_total_ps": total,
        "gaps": [[n, ps] for ps, n in gaps[:10]],
        "scope_ps": {k: sum(d for _s, d in v) for k, v in sorted(scope.items())},
        "spans_ps": dict(sorted(spans.items())),
        "loop_spans": sorted({n for n, _s, _d in loop}),
        "match_runs": len(match),
        "match_ps": sum(match),
    }
    print(json.dumps(out, indent=1))
    if "--write" in sys.argv:
        (HERE / "trace_spans.expected.json").write_text(json.dumps(out, indent=1) + "\n")


if __name__ == "__main__":
    main()
