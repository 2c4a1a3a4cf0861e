"""The program's own spans and scopes, read from the profiler trace.

    python host_spans.py <trace dir or .xplane.pb>

A traced broker (PR 25 on) writes two things into the ``.xplane.pb`` that
``trace_reduce.py`` reads device busy time from:

- on ``/host:CPU``, one line per thread, an event ``rmqtt/<stage>`` for every
  stage section the thread ran (``rmqtt_tpu/broker/telemetry.py``), on the
  clock of the device's ``XLA Ops``;
- on each operation of the ``jit_match_*`` programs the named scope it was
  traced under (``scan`` / ``compact`` / ``resolve`` / ``sort`` / ``counts``)
  inside its ``tf_op`` stat, e.g. ``jit(match_fused_impl)/resolve/gather:``.
  That stat sits on the operation's XEventMetadata, which ``ProfileData``
  does not hand out, so ``_op_scopes`` walks the file's wire format for just
  that map (operation text → ``tf_op``) and joins on the event's name.

``read`` gives (a) every idle second of the device a name: what the host
was doing in it, (b) the device's seconds by scope, (c) the spans' own
time by name. The rule of (a), applied instant by instant (a gap is cut
at every span edge, so each piece has one answer): a ``matcher.*`` span
open on any thread — a device batch is on its way in or out — gives its
name (the one opened last, where several are open); else the span the
loop thread has open (the innermost), with ``routing.match.side`` read as
``hybrid.side`` (the device idles by the hybrid's choice) and ``loop.idle``
/ ``loop.poll`` as they are (the loop waited for the network / looked
without waiting); else ``loop.unspanned``. A trace with no ``rmqtt/*``
event (a program from before PR 25) gives None.

Import only when no process holds the chip (it imports jax, as
``trace_reduce`` does).
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

if __name__ == "__main__":  # run by hand: find the harness package
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from harness.trace_reduce import DEVICE_PLANE, MODULES_LINE, OPS_LINE, find_xplane

SPAN_PREFIX = "rmqtt/"
SCOPES = ("scan", "compact", "resolve", "sort", "counts")
UNSPANNED, UNSCOPED = "loop.unspanned", "unscoped"
RENAME = {"routing.match.side": "hybrid.side"}  # loop-thread spans only
_cache: dict = {}


# ------------------------------------------------------- wire format, briefly
def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _message(buf) -> list:
    """→ [(field number, int | memoryview)] of one protobuf message."""
    out, i, n = [], 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        else:  # fixed64 / fixed32
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        out.append((key >> 3, val))
    return out


def _op_scopes(pb: Path) -> dict:
    """→ {operation's event name: its ``tf_op`` text} over the device
    planes (XSpace.planes=1; XPlane: name=2, event_metadata=4 (map: value=2),
    stat_metadata=5 (map: key=1, value=2); XEventMetadata: name=2, stats=5;
    XStat: metadata_id=1, str_value=5 or ref_value=7, the id of a stat
    metadata whose NAME is the text; XStatMetadata: name=2)."""
    out = {}
    for num, plane in _message(memoryview(pb.read_bytes())):
        if num != 1:
            continue
        fields = _message(plane)
        name = next((bytes(v).decode() for n, v in fields if n == 2), "")
        if not DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for n, v in fields:
            if n == 5:
                entry = dict(_message(v))
                stat_names[entry[1]] = bytes(
                    dict(_message(entry[2])).get(2, b"")).decode()
        tf_op = next((k for k, v in stat_names.items() if v == "tf_op"), None)
        if tf_op is None:
            continue
        for n, v in fields:
            if n != 4:
                continue
            meta = _message(dict(_message(v))[2])
            text = next((bytes(x) for k, x in meta if k == 2), b"").decode()
            for k, x in meta:
                if k == 5:
                    stat = dict(_message(x))
                    if stat.get(1) != tf_op:
                        continue
                    if 5 in stat:
                        out[text] = bytes(stat[5]).decode()
                    elif 7 in stat:
                        out[text] = stat_names.get(stat[7], "")
    return out


def scope_of(tf_op: str) -> str:
    """``jit(match_fused_impl)/resolve/jit(_take)/gather:`` → ``resolve``."""
    for part in tf_op.rstrip(":").split("/"):
        if part in SCOPES:
            return part
    return UNSCOPED


# ------------------------------------------------------------------ intervals
def _union(iv: np.ndarray) -> np.ndarray:
    """[[start, end], ...] → the same set as sorted, disjoint intervals."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    top = np.maximum.accumulate(iv[:, 1])
    first = np.r_[True, iv[1:, 0] > top[:-1]]
    last = np.r_[first[1:], True]
    return np.c_[iv[first, 0], top[last]]


def _complement(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """What of [lo, hi] the disjoint, sorted ``iv`` leaves uncovered."""
    edges = np.r_[lo, np.clip(iv, lo, hi).ravel(), hi].reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def _covered(iv: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Length of the disjoint, sorted ``iv`` that lies before each ``t``."""
    if not len(iv):
        return np.zeros_like(t)
    done = np.r_[0.0, np.cumsum(iv[:, 1] - iv[:, 0])]
    k = np.searchsorted(iv[:, 0], t, side="right")  # intervals begun by t
    over = np.where(k > 0, np.maximum(iv[np.maximum(k, 1) - 1, 1] - t, 0.0), 0.0)
    return done[k] - over


def _innermost(start: np.ndarray, end: np.ndarray, label: np.ndarray):
    """Spans that may nest or overlap → (segments [[s, e], ...], label of
    each): at every instant the span opened last among those open."""
    order = np.argsort(start, kind="stable")
    events = sorted([(float(start[i]), 1, int(i)) for i in order]
                    + [(float(end[i]), 0, int(i)) for i in order])
    segs, labs, open_, at = [], [], [], None  # open_: span ids, by start
    alive = set()
    for t, opening, i in events:
        while open_ and open_[-1] not in alive:
            open_.pop()
        if open_ and at is not None and t > at:
            segs.append((at, t))
            labs.append(int(label[open_[-1]]))
        at = t
        if opening:
            open_.append(i)
            alive.add(i)
        else:
            alive.discard(i)
    return np.array(segs, float).reshape(-1, 2), np.array(labs, int)


def _by_label(segs, labs, names, within: np.ndarray) -> dict:
    """Seconds (of ns) of ``within`` under each label's segments."""
    if not len(segs):
        return {}
    w = _covered(within, segs[:, 1]) - _covered(within, segs[:, 0])
    total = np.bincount(labs, weights=w, minlength=len(names))
    return {names[k]: float(s) / 1e9 for k, s in enumerate(total) if s > 0}


# ----------------------------------------------------------------------- read
def read(path) -> dict | None:
    """→ {idle_s: {name: seconds}, idle_total_s, gap_names: [[name, seconds]
    of the ten longest gaps, as ``trace_reduce`` orders them], scope_s:
    {scope: seconds}, spans: {stage: [events, seconds]}, loop_spans: the
    same on the loop thread alone, match_runs, match_s}; None where the
    trace holds no span of the program's."""
    pb = find_xplane(Path(path))
    if pb in _cache:
        return _cache[pb]
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(pb))
    ops, op_names, modules = [], [], []
    lines = []  # per host thread: (starts, ends, stage names)
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        ops.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                        op_names.append(ev.name)
                elif line.name == MODULES_LINE:
                    modules += [(ev.name, ev.duration_ns) for ev in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                got = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                        ev.name[len(SPAN_PREFIX):]) for ev in line.events
                       if ev.name.startswith(SPAN_PREFIX)]
                if got:
                    lines.append(got)
    if not lines:
        _cache[pb] = None
        return None
    names = sorted({n for got in lines for _s, _e, n in got}
                   | set(RENAME.values()) | {UNSPANNED})
    index = {n: k for k, n in enumerate(names)}
    # the loop thread: the one that sits in the selector; where a trace
    # holds no such span (a recording of older code), the one that decodes
    keys = ("loop.idle", "loop.poll")
    if not any(n in keys for g in lines for *_x, n in g):
        keys = ("ingress.decode",)
    loop = max(lines, key=lambda g: sum(n in keys for *_x, n in g))
    spans, loop_spans = {}, {}
    for got in lines:
        for s, e, n in got:
            for book in (spans, loop_spans) if got is loop else (spans,):
                row = book.setdefault(n, [0, 0.0])
                row[0] += 1
                row[1] += (e - s) / 1e9
    everything = np.array([(s, e) for g in lines for s, e, _n in g] + ops, float)
    lo, hi = everything[:, 0].min(), everything[:, 1].max()
    busy = _union(np.array(ops, float).reshape(-1, 2))
    idle = _complement(busy, lo, hi)
    # (a) matcher spans first, on whichever thread; then the loop's own
    m = [(s, e, index[n]) for g in lines for s, e, n in g if n.startswith("matcher.")]
    m_segs, m_labs = _innermost(*map(np.array, zip(*m))) if m else (np.zeros((0, 2)), [])
    idle_s = _by_label(m_segs, m_labs, names, idle)
    # where the device idles and no matcher span is open: the loop's answer
    idle_rest = _pairwise(idle, _complement(_union(m_segs), lo, hi))
    l = [(s, e, index[RENAME.get(n, n)]) for s, e, n in loop
         if not n.startswith("matcher.")]
    l_segs, l_labs = _innermost(*map(np.array, zip(*l))) if l else (np.zeros((0, 2)), [])
    for n, s in _by_label(l_segs, l_labs, names, idle_rest).items():
        idle_s[n] = idle_s.get(n, 0.0) + s
    idle_total = float((idle[:, 1] - idle[:, 0]).sum()) / 1e9
    idle_s[UNSPANNED] = max(0.0, idle_total - sum(idle_s.values()))
    # the ten longest gaps between operations, named by the largest share
    gaps = np.c_[busy[:-1, 1], busy[1:, 0]]
    gaps = gaps[np.argsort(-(gaps[:, 1] - gaps[:, 0]), kind="stable")[:10]]
    gap_names = []
    for g in gaps:
        one = g.reshape(1, 2)
        share = _by_label(m_segs, m_labs, names, one)
        left = _complement(_union(np.clip(m_segs, g[0], g[1])), g[0], g[1])
        for n, s in _by_label(l_segs, l_labs, names, left).items():
            share[n] = share.get(n, 0.0) + s
        length = float(g[1] - g[0]) / 1e9
        share[UNSPANNED] = max(0.0, length - sum(share.values()))
        gap_names.append([max(share, key=share.get), length])
    # (b) device seconds by scope: the union of a scope's operations (a
    # while loop's event spans its body's)
    tf_ops = _op_scopes(pb)
    by_scope = {}
    for (s, e), name in zip(ops, op_names):
        by_scope.setdefault(scope_of(tf_ops.get(name, "")), []).append((s, e))
    scope_s = {k: float((u[:, 1] - u[:, 0]).sum()) / 1e9 for k, u in
               ((k, _union(np.array(v, float))) for k, v in sorted(by_scope.items()))}
    match = [(n, d) for n, d in modules if n.startswith("jit_match_")]
    out = {"idle_s": dict(sorted(idle_s.items(), key=lambda kv: -kv[1])),
           "idle_total_s": idle_total, "gap_names": gap_names,
           "scope_s": scope_s, "spans": dict(sorted(spans.items())),
           "loop_spans": dict(sorted(loop_spans.items())),
           "match_runs": len(match), "match_s": sum(d for _n, d in match) / 1e9}
    _cache[pb] = out
    return out


def _pairwise(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersections of two sorted, disjoint interval lists, by merging."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if e > s:
            out.append((s, e))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.array(out, float).reshape(-1, 2)


def from_run(run: dict) -> dict | None:
    """For the readers in ``layer_metrics``: the reduction of the run's
    trace, or None where there is no trace or no span in it."""
    tr = run.get("trace")
    return read(tr["dir"]) if tr and tr.get("dir") else None


def gap_names(trace_dir) -> list:
    """``breakdown.idle_gaps`` with names in place of ``unattributed``:
    ``cell.py`` can take this in place of its own list."""
    red = read(trace_dir)
    return red["gap_names"] if red else []


if __name__ == "__main__":
    import json
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(json.dumps(read(Path(sys.argv[1])), indent=1))
