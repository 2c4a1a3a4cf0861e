"""One run of one cell: set-up, window, settle, compare, reduce.

The window drives the broker child over MQTT/TCP on loopback, so what is
timed and what is compared is everything between the publishers' sockets
and the subscribers' sockets: codec, session, routing service, hybrid,
device matcher and host mirror (whichever served), relations expansion,
egress.

``correct`` compares what the window itself delivered: every (subscriber,
publish) pair received for a publish sent inside the window against what
the plain trie (``trie.py``, in a process of its own) says of the same
filters and topics, every QoS1 publish of the window against its PUBACK, and
every PUBACK of the run against the order its connection's publishes left in
([MQTT-4.6.0-2]; it bites where a mix keeps more than one publish in flight).
Where the table holds ``$share`` groups, each group a publish matches is due
one delivery, to one of its members ([MQTT 5.0 §4.8.2]; ``reference.py``).
The comparison is exact: each limit is 0. A pair counts once, in the
comparison and in the rate alike: a subscriber that holds two matching
filters, or that is sent a QoS1 delivery again, has received the publish
once, and its latency is that of the first copy.

A publish belongs to the window by its **send** instant, and its latencies
are taken from its **due** instant. In a closed loop the two are one. In an
open loop (``fleet.py``) a publish that fell due while its connection's
window was full was sent later, and its latency counts that wait.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from harness import broker as brokermod
from harness import spec
from harness.fleet import Fleet
from harness.reference import Reference

WARMUP_MIN_S = 10.0      # traffic before the window, at the least
WARMUP_CAP_S = 60.0
COMPILE_CAP_S = 480.0    # so long a compile worker is waited for: a checkout's first run compiles
PROBE_PERIODS = 2        # device batches in a row that must compile nothing
NO_DEVICE_GIVE_UP_S = 15.0  # so long without a device batch: none is coming
SETTLE_LIMIT_S = 60.0    # a delivery that comes within a minute is late, not lost
GO_LEAD_S = 0.5          # an open mix's schedule starts this long after "go" is told
LOADGEN_WALL_PCT = 90.0  # a publisher process over this share of a core kept no schedule
LATE_TAIL_SHARE = 0.5    # the generator's lateness at p99 over the PUBACK's p99: the tail is the fleet's
TRACE_SLICE_S = 60.0    # of the window's middle; the whole window where it is shorter


def say(**obj) -> None:
    """An earlier line of standard output (the contract's line comes last)."""
    print(json.dumps(obj), flush=True)


def pct(values: np.ndarray, q: float) -> float:
    return float(np.percentile(values, q)) if values.size else float("nan")


# ------------------------------------------------------------------ records
class Records:
    """What the fleet's processes recorded, gathered drain by drain."""

    def __init__(self, pub_procs: int) -> None:
        self.P = pub_procs
        self.topics = [[] for _ in range(pub_procs)]
        self.t_send = [[] for _ in range(pub_procs)]
        self.t_due = [[] for _ in range(pub_procs)]
        self.waited = [[] for _ in range(pub_procs)]
        self.t_ack = [np.zeros(0) for _ in range(pub_procs)]
        self.ids, self.times, self.subs = [], [], []
        self.cpu = {}
        self.inflight = 0
        self.lost = 0
        self.out_of_order = 0

    def drain(self, fleet: Fleet) -> int:
        """Pull what is new; → the number of new deliveries."""
        new = 0
        self.lost = 0
        for k, r in enumerate(fleet.ask(fleet.subs, "drain")):
            ids = np.frombuffer(r["ids"], dtype=np.int64)
            new += ids.size
            self.ids.append(ids)
            self.times.append(np.frombuffer(r["times"], dtype=np.float64))
            self.subs.append(np.frombuffer(r["subs"], dtype=np.dtype("l")))
            self.cpu[f"sub{k}"] = np.frombuffer(r["cpu"]).reshape(-1, 2)
            self.lost += r["lost"]
        self.inflight = self.out_of_order = 0
        for k, r in enumerate(fleet.ask(fleet.pubs, "drain")):
            self.topics[k] += r["topics"]
            self.t_send[k].append(np.frombuffer(r["t_send"]))
            self.t_due[k].append(np.frombuffer(r["t_due"]))
            self.waited[k].append(np.frombuffer(r["waited"], dtype=np.int8))
            self.t_ack[k] = np.frombuffer(r["t_ack"])
            self.cpu[f"pub{k}"] = np.frombuffer(r["cpu"]).reshape(-1, 2)
            self.inflight += r["inflight"]
            self.out_of_order += r["out_of_order"]
            self.lost += r["lost"]
        return new

    def table(self):
        """Publishes by id (``record * P + process``): send, PUBACK and due
        instants, and whether the publish queued on a full window; nan (0)
        where no publish has the id."""
        P = self.P
        cat = lambda parts: np.concatenate(parts) if parts else np.zeros(0)  # noqa: E731
        sends = [cat(s) for s in self.t_send]
        size = max(len(s) for s in sends) * P
        send, ack, due = (np.full(size, np.nan) for _ in range(3))
        waited = np.zeros(size, np.int8)
        for k in range(P):
            n = len(sends[k])
            send[k::P][:n] = sends[k]
            ack[k::P][:n] = self.t_ack[k][:n]
            due[k::P][:n] = cat(self.t_due[k])
            waited[k::P][:n] = cat(self.waited[k])
        return send, ack, due, waited

    def topic_of(self, ident: int) -> str:
        return self.topics[ident % self.P][ident // self.P]

    def cpu_busy_pct(self, t0: float, t1: float) -> dict:
        """Each fleet process's CPU seconds over [t0, t1], as a share."""
        out = {}
        for name, log in self.cpu.items():
            if len(log) < 2:
                continue
            used = np.interp([t0, t1], log[:, 0], log[:, 1])
            out[name] = 100.0 * float(used[1] - used[0]) / (t1 - t0)
        return out


# ------------------------------------------------------------------ compare
def compare(rec: Records, ref: Reference, subscribers: int,
            t0: float, t1: float) -> dict:
    """The window's publishes against the plain reference. → the numbers
    compared (each with the limit 0), ``attempted`` and the arrays the
    end-to-end metrics are taken from. A reference that answers without
    groups (two arrays) is a table without ``$share`` rows."""
    send, ack, due, _waited = rec.table()
    in_window = (send >= t0) & (send < t1)
    W = np.flatnonzero(in_window)
    ids = np.concatenate(rec.ids) if rec.ids else np.zeros(0, np.int64)
    times = np.concatenate(rec.times) if rec.times else np.zeros(0)
    subs = np.concatenate(rec.subs) if rec.subs else np.zeros(0, np.int64)
    known = (ids >= 0) & (ids < send.size)
    known[known] = ~np.isnan(send[ids[known]])
    hit = known.copy()
    hit[known] = in_window[ids[known]]
    # one entry per (subscriber, publish) pair, at its first receipt
    keys, when = ids[hit] * subscribers + subs[hit], times[hit]
    order = np.lexsort((when, keys))
    got, first = np.unique(keys[order], return_index=True)
    first = order[first]
    counts, flat, *groups = ref.expected([rec.topic_of(int(i)) for i in W])
    want = np.repeat(W, counts) * subscribers + flat  # unique by construction
    missing = np.setdiff1d(want, got, assume_unique=True)
    by_sub = np.bincount(missing % subscribers, minlength=1)
    if groups:
        shared = shared_groups(groups[0], W, want, got, subscribers)
    else:
        shared = {"unexpected": int(np.setdiff1d(got, want, assume_unique=True).size),
                  "missed": 0, "extra": 0, "groups": 0}
    return {
        "checks": {
            "missing_pairs": int(missing.size),
            "unexpected_pairs": shared["unexpected"],
            "unacked_qos1": int(np.isnan(ack[W]).sum()),
            "acks_out_of_order": int(rec.out_of_order),
            "unknown_publish_ids": int((~known).sum()),
            "connections_lost": int(rec.lost),
            "shared_missed": shared["missed"],
            "shared_extra": shared["extra"],
        },
        "attempted": int(want.size) + shared["groups"],
        "publishes": int(W.size),
        # who went without: the four subscribers that miss most, [index, pairs]
        "missing_most": [[int(i), int(by_sub[i])] for i in
                         np.argsort(-by_sub)[:4] if by_sub[i]],
        "pairs": int(got.size),
        "copies": int(hit.sum()),  # PUBLISH packets: pairs + further copies
        "deliver_ms": (when[first] - due[got // subscribers]) * 1e3,
        "puback_ms": (ack[W] - due[W]) * 1e3,
        # pairs by the 5 s slice their publish was sent in: shows stalls
        "pairs_by_5s": np.bincount(
            ((send[got // subscribers] - t0) // 5.0).astype(np.int64)).tolist(),
    }


def shared_groups(groups: tuple, W: np.ndarray, want: np.ndarray, got: np.ndarray,
                  subscribers: int) -> dict:
    """The window's publishes against the ``$share`` groups they match
    (``Reference.expected``'s third item), one member of each receiving.
    Pairs are ``publish * subscribers + subscriber``; ``want`` are the plain
    ones, ``got`` those received (sorted, unique). For each publish, with R
    its receivers, N its plain subscribers and G1..Gk its groups:

    - ``unexpected``: |R \\ (N ∪ G1 ∪ … ∪ Gk)|;
    - ``missed``: the (publish, group) pairs no member of which is in R;
    - ``extra``: the receivers in some group and not in N, less the largest
      matching of them to the groups (a receiver to a group it is a member
      of, a group to one receiver at most): Σ max(0, |R ∩ Gj \\ N| − 1)
      where the groups share no member. A member that is also in N is never
      extra: the records cannot say through which subscription it came;
    - ``groups``: the (publish, group) pairs, each one delivery due."""
    gcounts, gids, offsets, members = groups
    sizes = offsets[gids + 1] - offsets[gids]
    pair = np.repeat(np.arange(gids.size), sizes)  # the (publish, group) of each member row
    start = np.repeat(offsets[gids] - (np.cumsum(sizes) - sizes), sizes)
    key = np.repeat(W, gcounts)[pair] * subscribers + members[start + np.arange(pair.size)]
    received = np.isin(key, got)
    extra_rows = received & ~np.isin(key, want)
    keys, where = key[extra_rows], pair[extra_rows]
    # a receiver in two of its publish's groups: that publish is matched whole
    uniq, n = np.unique(keys, return_counts=True)
    tangled = np.isin(keys // subscribers, uniq[n > 1] // subscribers)
    extra = int((~tangled).sum()) - np.unique(where[~tangled]).size
    for pub in np.unique(keys[tangled] // subscribers):
        mine = tangled & (keys // subscribers == pub)
        edges = {}
        for k, g in zip(keys[mine].tolist(), where[mine].tolist()):
            edges.setdefault(k, []).append(g)
        extra += len(edges) - _matching(edges)
    return {"unexpected": int(np.setdiff1d(got, np.union1d(want, key)).size),
            "missed": int(gids.size - np.unique(pair[received]).size),
            "extra": extra, "groups": int(gids.size)}


def _matching(edges: dict) -> int:
    """Size of a largest matching of receivers to groups (``edges``:
    receiver → its groups), by augmenting paths."""
    owner = {}  # group → the receiver matched to it

    def augment(r, seen: set) -> bool:
        for g in edges[r]:
            if g not in seen:
                seen.add(g)
                if g not in owner or augment(owner[g], seen):
                    owner[g] = r
                    return True
        return False

    return sum(augment(r, set()) for r in edges)


def end_to_end(cmp: dict, seconds: float, setup_s: float) -> dict:
    """Every end-to-end metric the fleet's records give; a cell reports the
    ones ``BENCHMARK.json`` lists for it. All over ALL work of the window."""
    acked = cmp["puback_ms"][~np.isnan(cmp["puback_ms"])]
    return {
        "deliveries_per_s": cmp["pairs"] / seconds,
        "deliver_p50_ms": pct(cmp["deliver_ms"], 50),
        "deliver_p99_ms": pct(cmp["deliver_ms"], 99),
        "puback_p99_ms": pct(acked, 99),
        "setup_s": setup_s,
    }


def open_loop(rec: Records, cmp: dict, n_due: int, t0: float, t1: float) -> dict:
    """What an open mix's window offered and what of it left: publishes
    **due** in the window (``n_due``, counted from the schedule itself),
    **sent** in it (by send instant, as the comparison counts them), their
    ratio, the share of the due that met a full window (queued and sent
    later, or never sent: still queued at the close), and the generator's
    own lateness (send minus due of the sent publishes that had a free slot)."""
    send, _ack, due, waited = rec.table()
    due_in = (due >= t0) & (due < t1)
    never_sent = n_due - int(due_in.sum())
    met_full = int(waited[due_in].sum()) + never_sent
    had_slot = (send >= t0) & (send < t1) & (waited == 0)
    late = (send[had_slot] - due[had_slot]) * 1e3
    out = {
        "due": n_due, "sent": cmp["publishes"], "never_sent": never_sent,
        "sent_of_due_pct": 100.0 * cmp["publishes"] / n_due if n_due else None,
        "window_full_share_pct": 100.0 * met_full / n_due if n_due else None,
        "late_p50_ms": pct(late, 50), "late_p99_ms": pct(late, 99),
        "late_max_ms": float(late.max()) if late.size else float("nan"),
    }
    return {k: None if v != v else v for k, v in out.items()}  # nan: nothing to read


def require_schedule_kept(cell: str, traffic: dict, loadgen: dict,
                          offered: dict, puback_p99_ms: float) -> None:
    """An open mix is an instrument only while the fleet, not the broker,
    keeps the schedule. Two rules, each failing in words. A publisher
    process burned over ``LOADGEN_WALL_PCT`` of a core across the window:
    the generator was the wall. Or the generator's own lateness (publishes
    that had a free slot and still left after they fell due) reached, at its
    99th percentile, ``LATE_TAIL_SHARE`` of the PUBACK's 99th percentile:
    every latency is taken from the due instant, so the tail that would be
    reported is then the fleet's own. A broker that cannot keep up is NOT
    such a run: the windows fill, the publisher processes idle, a publish
    that queued on a full window is not late by the generator's doing, and
    the falling rate is the reading. A closed mix has no schedule to keep
    and is not held to this."""
    if traffic["loop"] != "open":
        return
    sent = (f"the fleet sent its publishes {offered['late_p50_ms']} / "
            f"{offered['late_p99_ms']} / {offered['late_max_ms']} ms (p50 / p99 / max) "
            f"after they fell due, {offered['sent']} sent of {offered['due']} due")
    name, share = max(((k, v) for k, v in loadgen.items() if k.startswith("pub")),
                      key=lambda kv: kv[1], default=(None, 0.0))
    if share > LOADGEN_WALL_PCT:
        spec.fail(
            f"cell {cell!r}: publisher process {name} of the load generator used "
            f"{share:.1f} % of a core across the window (over {LOADGEN_WALL_PCT:.0f} %) "
            f"and {sent}: the generator, not the broker, was the wall, so the "
            "schedule of this open mix was not the one offered. This is not a "
            "measurement of the broker, and no result is printed.")
    late = offered["late_p99_ms"]
    if late is not None and late >= LATE_TAIL_SHARE * puback_p99_ms:
        spec.fail(
            f"cell {cell!r}: {sent}, and the 99th percentile of that lateness is "
            f"{100 * late / puback_p99_ms:.0f} % of the PUBACK's ({puback_p99_ms:.1f} ms; "
            f"the line is {100 * LATE_TAIL_SHARE:.0f} %): every latency counts "
            "from the due instant, so the tail this run would report is the load "
            "generator's own lateness (publisher processes at "
            f"{share:.1f} % of a core at the most), not the broker's. This is "
            "not a measurement of the broker, and no result is printed.")


# ------------------------------------------------------------------ warm-up
def _offered(d: dict) -> tuple:
    """→ (large batches the hybrid has routed, batches the device has
    served) of one ``/api/v1/device`` body. The hybrid counts every batch
    over ``hybrid_max`` whichever side then serves it; a program from before
    PR 27 has no such count and is read by the device's batches alone."""
    be = d["backend"]
    return be.get("hybrid_large_batches", 0), be["hybrid_served"]["device"][0]


def _probes(d: dict):
    """→ how often the hybrid has shown a large batch to the path it does not
    choose (``hybrid_probes``, both paths summed); None on a program from
    before PR 27, which does not count them."""
    probes = d["backend"].get("hybrid_probes")
    return None if probes is None else sum(probes.values())


def warm_up(b, go, cell: str) -> dict:
    """The cell's own traffic, started by ``go()``, until the shapes it uses
    are compiled, as far as traffic can tell: at least ``WARMUP_MIN_S``, no
    compile worker of the program is at work (``Broker.compiling``), the
    hybrid has timed both of its paths (``hybrid_choice`` is set: two device
    batches have come back) and has probed once since ``go`` (below), and
    the last ``PROBE_PERIODS`` batches the device served brought no new
    program (``compile.traces`` did not rise with them). The hybrid shows
    its slower path one large batch in 64
    (``ops/hybrid.py``), so a shape that the device has not met yet can
    still turn up later; the cap (``WARMUP_CAP_S`` past the last compile
    worker's end) bounds the wait and a cap that is hit is printed.

    A program that the device has not met is compiled off the routing path,
    one after another, seconds each where the persistent cache holds it and
    ten to thirty where it does not (a checkout's first run: ~110 s for the
    seven programs of the 1M table), and while one is traced the event loop
    waits for the GIL. So the warm-up never ends while the worker lives (up
    to ``COMPILE_CAP_S``), and the wait for a device batch
    (``NO_DEVICE_GIVE_UP_S``) counts from the worker's end: the first batch
    the device serves stands behind the first compile.

    Where the host mirror wins, the device is shown only the probes, so the
    everyday batch shape (65 to 128 topics) may first reach it with the first
    probe, ~18 s after ``go`` on the 1M table, and is compiled then: the
    warm-up neither settles nor gives the device up before the hybrid's
    first probe since ``go`` (``hybrid_probes``), where large batches form at
    all and the program counts its probes.

    The validity rule (PERF.md §4): where the warm-up would return and the
    hybrid was never offered a batch since ``go`` (no batch over
    ``hybrid_max`` formed, none reached the device) though the routing
    service dispatched, the run is not a result of a cell: it ends here, in
    words. The ``--cpu`` rehearsal is held to it too: its fleet is three
    times ``hybrid_max`` and forms large batches in every run."""
    d = b.get("/api/v1/device")
    stats0 = b.get("/api/v1/stats")[0]["stats"]
    traces = d["compile"]["traces"]
    large0, dev0 = _offered(d)
    probes0 = _probes(d)
    dev = dev0
    clean = 0  # device batches in a row that compiled nothing
    t_go = time.perf_counter()
    go()
    last_dev = t_go  # when the device last served a batch, or a compile worker last lived
    compiled_s = 0.0  # seconds since go at which no compile worker was left
    while True:
        time.sleep(0.5)
        now = time.perf_counter()
        compiling = b.compiling()
        if compiling:
            last_dev, compiled_s = now, now - t_go
        d = b.get("/api/v1/device")
        be = d["backend"]
        large, served = _offered(d)
        probed = probes0 is None or large == large0 or _probes(d) > probes0
        if not probed:
            last_dev = now
        if d["compile"]["traces"] != traces:
            traces, clean = d["compile"]["traces"], 0
            dev = served
            last_dev = now
        elif served != dev:
            clean += served - dev
            dev = served
            last_dev = now
        elapsed = now - t_go
        settled = (be["hybrid_choice"] is not None and clean >= PROBE_PERIODS
                   and probed)
        # where batches large enough to reach the device are rare there is
        # nothing to wait for: the device's counters stand still
        no_device = now - last_dev >= NO_DEVICE_GIVE_UP_S
        done = elapsed >= WARMUP_MIN_S and (settled or no_device) and not compiling
        # the cap counts from the compile worker's end; the worker has its own
        if done or elapsed >= COMPILE_CAP_S or (
                not compiling and elapsed - compiled_s >= WARMUP_CAP_S):
            break
    warm = {"seconds": elapsed, "cap_hit": not done, "compiling_until_s": compiled_s,
            "probed": probed, "large_batches": large - large0,
            "device_batches": dev - dev0, "clean_device_batches": clean,
            "compile_traces": traces, "hybrid_choice": be["hybrid_choice"]}
    if large == large0 and dev == dev0:
        stats = b.get("/api/v1/stats")[0]["stats"]
        n = stats["routing_dispatches"] - stats0["routing_dispatches"]
        items = stats["routing_dispatched_items"] - stats0["routing_dispatched_items"]
        if n > 0:
            spec.fail(
                f"cell {cell!r}: in {elapsed:.1f} s of its own traffic the "
                f"routing service routed {items} publishes in {n} dispatches "
                f"({items / n:.1f} topics a dispatch) and not one batch was "
                f"over hybrid_max = {be.get('hybrid_max')}: the hybrid was "
                "never offered a large batch and the device served none "
                "(hybrid_large_batches +0, hybrid_served.device +0). Under "
                "this traffic --router xla is inert on this program. A cell "
                "has to drive the device path; a run that cannot is not a "
                "result of a cell, and no result is printed.")
    return warm


def require_device_time(cell: str, traced: dict, red: dict) -> None:
    """The validity rule's last line of defence: a traced span in which no
    operation ran on the device fails in words; it is not printed as
    ``busy_s`` 0. (In a run that passed the warm-up: the span held fewer
    large batches than one probe period and the host mirror won them all,
    or the trace is broken.)"""
    if red["busy_s"] > 0:
        return
    d0, d1 = traced["before"]["device"], traced["after"]["device"]
    large = _offered(d1)[0] - _offered(d0)[0]
    batches, topics = (b - a for a, b in zip(
        d0["backend"]["hybrid_served"]["device"],
        d1["backend"]["hybrid_served"]["device"]))
    spec.fail(
        f"cell {cell!r}: the trace of {traced['window_s']:.1f} s holds "
        f"{red['device_planes']} device plane(s), the programs "
        f"{sorted(red['modules']) or 'none'} and no operation on the device "
        f"(busy_s {red['busy_s']}); inside the traced span the hybrid routed "
        f"{large} large batches and the device served {batches} batches of "
        f"{topics} topics. Either every large batch of the span went to the "
        "host mirror or the trace is broken: a cell has to drive the device "
        "path, this is not a measurement, and no result is printed.")


def idle_gaps(trace_dir, red: dict) -> list:
    """``breakdown.idle_gaps``: the device's longest idle gaps, each under
    the host span that covered most of it; a trace without the program's
    spans names none of them."""
    from harness import host_spans

    return host_spans.gap_names(trace_dir) \
        or [["unattributed", g] for g in red["gaps"]]


# ------------------------------------------------------------------- settle
def settle(rec: Records, fleet: Fleet, ref: Reference, subscribers: int,
           t0: float, t1: float) -> dict:
    """Gather the fleet's records until nothing is in flight and no delivery
    has come for a second, then compare. A delivery or PUBACK still missing
    then is waited for until ``SETTLE_LIMIT_S`` past the window's close: one
    that comes late is late, not wrong, and its latency counts the wait."""
    quiet = None
    while True:
        new = rec.drain(fleet)
        now = time.perf_counter()
        if new or rec.inflight:
            quiet = None
        elif quiet is None:
            quiet = now
        elif now - quiet >= 1.0:
            break
        if now > t1 + SETTLE_LIMIT_S:
            break
        time.sleep(0.25)
    cmp = compare(rec, ref, subscribers, t0, t1)
    while (cmp["checks"]["missing_pairs"] or cmp["checks"]["shared_missed"]
           or cmp["checks"]["unacked_qos1"]) and time.perf_counter() < t1 + SETTLE_LIMIT_S:
        time.sleep(1.0)
        rec.drain(fleet)
        cmp = compare(rec, ref, subscribers, t0, t1)
    return cmp


# ---------------------------------------------------------------------- run
def run_cell(name: str, seed: int, seconds: float, trace: bool, t_start: float,
             cpu: bool = False, launcher: Path = brokermod.LAUNCHER) -> dict:
    """→ the contract's result object. ``cpu`` asks for the rehearsal on the
    CPU backend (tiny sizes from ``rehearsal.json``); ``launcher`` is for
    the tests under ``benchmark/tests``, which break the broker."""
    cell = spec.load_cell(name)
    config, traffic = cell["config"], cell["traffic"]
    if cpu:
        tiny = spec.load_json(spec.BENCH_DIR / "harness" / "rehearsal.json")
        config = dict(config, **tiny["config"])
        traffic = dict(traffic, **tiny["traffic"], **(
            tiny["open_traffic"] if traffic["loop"] == "open" else {}))
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    elif env.get("JAX_PLATFORMS") == "cpu":
        spec.fail("JAX_PLATFORMS=cpu is set: no accelerator can be found. "
                  "Pass --cpu for the tiny rehearsal on the CPU backend.")
    workdir = Path(tempfile.mkdtemp(prefix="rmqtt-bench-"))
    b = fleet = ref = None
    try:
        # ---- set-up: broker, reference and the fleet's tables side by side
        b = brokermod.Broker(workdir, env, launcher)
        ref = Reference(config["generator"], seed, config, traffic["subscribers"])
        fleet = Fleet(b.port, seed, config, traffic, seconds)
        fleet.tell(fleet.subs, "prepare")  # tables made while the broker starts
        dev = b.wait_up()
        be = dev["backend"]
        up_s = time.perf_counter() - b.t0
        want = "cpu" if cpu else "tpu"
        if be["platform"] != want:
            spec.fail(f"the broker runs on platform {be['platform']!r}, not {want!r}")
        if be["device_count"] < cell["chips"]:
            spec.fail(f"{be['device_count']} devices, the cell asks for {cell['chips']}")
        if be["host_mirror"] != "native":
            spec.fail("the broker's host mirror is not the native trie "
                      "(runtime/librmqtt_runtime.so did not build)")
        fleet.gather(fleet.subs, "prepare")
        loaded = fleet.ask(fleet.subs, "load")
        ref.built()  # a table the comparison cannot judge ends here, in words
        resident = b.get("/api/v1/stats")[0]["stats"]["subscriptions"]
        n_subs = sum(r["subscribed"] for r in loaded)
        if not resident == n_subs == config["subscriptions"]:
            spec.fail(f"{resident} subscriptions resident, {n_subs} sent, "
                      f"{config['subscriptions']} in the configuration")
        load_s = max(r["seconds"] for r in loaded)
        prepared = fleet.ask(fleet.pubs, "prepare")
        warm = warm_up(b, lambda: fleet.go(GO_LEAD_S), name)
        say(phase="setup", broker_up_s=up_s, load_s=load_s,
            subscriptions=resident, load_per_s=n_subs / load_s,
            publishers=sum(r["connections"] for r in prepared), warm_up=warm,
            backend={k: be.get(k) for k in (
                "platform", "device_kind", "device_count", "matcher",
                "words_producer", "host_mirror", "hybrid_max")})
        if warm["cap_hit"]:
            say(phase="warning", what=f"warm-up hit its cap ({WARMUP_CAP_S:.0f} s past the "
                f"last compile worker, {COMPILE_CAP_S:.0f} s in all)")

        # ---- the window
        before = b.snapshot()
        t0 = time.perf_counter()
        t1 = t0 + seconds
        fleet.stop_at.value = t1
        setup_s = t0 - t_start
        traced = None
        if trace:
            span = min(TRACE_SLICE_S, seconds)
            time.sleep(max(0.0, t0 + (seconds - span) / 2 - time.perf_counter()))
            b.trace_start()
            slice_before = b.snapshot()
            # the profiler takes a moment to start: never trace past the window
            time.sleep(max(0.0, min(span, t1 - 0.3 - time.perf_counter())))
            slice_after = b.snapshot()
            traced = dict(b.trace_stop(), before=slice_before, after=slice_after)
        time.sleep(max(0.0, t1 - time.perf_counter()))
        after = b.snapshot()

        # ---- after: let every delivery come, then compare
        rec = Records(traffic["publisher_procs"])
        cmp = settle(rec, fleet, ref, traffic["subscribers"], t0, t1)
        settle_s = time.perf_counter() - t1
        peak = b.memory_peak_bytes()
        final = b.snapshot()  # also: the broker outlived the run
        n_due = fleet.due_between(t0, t1) if traffic["loop"] == "open" else None
        fleet.close()
        fleet = None
        b.stop()
        ref.close()

        # ---- reduce
        st0, st1 = before["stats"], after["stats"]
        failover = {k: st1[k] - st0[k] for k in (
            "routing_failovers", "routing_failover_host_routed",
            "routing_device_failures")}
        served = {k: [x - y for x, y in zip(
            after["device"]["backend"]["hybrid_served"][k],
            before["device"]["backend"]["hybrid_served"][k])]
            for k in ("side", "device")}
        dropped = {k: v - before["metrics"].get(k, 0)
                   for k, v in after["metrics"].items()
                   if k.startswith("messages.dropped") and v != before["metrics"].get(k, 0)}
        loadgen = rec.cpu_busy_pct(t0, t1)
        compiles = (after["device"]["compile"]["traces"]
                    - before["device"]["compile"]["traces"])
        e2e = end_to_end(cmp, seconds, setup_s)
        offered = None
        if traffic["loop"] == "open":
            offered = open_loop(rec, cmp, n_due, t0, t1)
            say(phase="open_loop", rate_publishes_per_s=traffic["rate_publishes_per_s"],
                arrival=traffic["arrival"], burst_size=traffic["burst_size"],
                inflight=traffic["inflight"], **offered)
        if not cpu:  # a rehearsal's few bursts on a shared CPU measure no schedule
            require_schedule_kept(name, traffic, loadgen, offered, e2e["puback_p99_ms"])
        say(phase="window", seconds=seconds, publishes=cmp["publishes"],
            publishes_per_s=cmp["publishes"] / seconds,
            pairs_expected=cmp["attempted"], pairs=cmp["pairs"],
            copies=cmp["copies"], missing_most=cmp["missing_most"],
            settle_s=settle_s, reference_build_s=ref.build_s,
            failover_deltas=failover, dropped_by_reason=dropped,
            hybrid_served=served,
            hybrid_choice=after["device"]["backend"]["hybrid_choice"],
            compile_cache=final["device"]["backend"]["compile_cache"],
            loadgen_cpu_busy_pct=loadgen, compiles_in_window=compiles,
            pairs_by_5s=cmp["pairs_by_5s"], client=e2e)
        device = {"platform": be["platform"], "kind": be["device_kind"],
                  "count": be["device_count"], "memory_peak_bytes": peak}
        out_metrics, breakdown = {}, None
        if trace:
            os.environ["JAX_PLATFORMS"] = "cpu"  # the broker is gone; stay off the chip
            from harness import trace_reduce

            t_red = time.perf_counter()
            red = trace_reduce.reduce(Path(traced["dir"]))
            traced.update(red, window_s=traced["stop"] - traced["start"])
            if not cpu:  # a CPU trace has no device plane
                require_device_time(name, traced, red)
            device["busy_s"], device["window_s"] = red["busy_s"], traced["window_s"]
            breakdown = {"device_ops": red["ops"],
                         "idle_gaps": idle_gaps(traced["dir"], red)}
            say(phase="trace", reduce_s=time.perf_counter() - t_red,
                device_planes=red["device_planes"], modules=red["modules"],
                window_s=traced["window_s"], busy_s=red["busy_s"])
            run = {"before": before, "after": after, "trace": traced,
                   "loadgen_cpu_busy_pct": loadgen,
                   "deliver_ms": cmp["deliver_ms"], "puback_ms": cmp["puback_ms"],
                   "open_loop": offered, "config": config,
                   "device": device, "seconds": seconds}
            for m in cell["per_layer"]:
                value = spec.load_reader(m["name"]).read(run)
                if value is not None:
                    out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            for m in cell["end_to_end"]:
                out_metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        checks = dict(cmp["checks"])
        failed = sum(checks.values())
        correct = failed == 0 and cmp["attempted"] > 0
        result = {"correct": bool(correct), "attempted": cmp["attempted"],
                  "failed": int(failed), "metrics": out_metrics, "device": device}
        if breakdown:
            result["breakdown"] = breakdown
        # each number compared beside its limit: last in the line, and the
        # last lines on standard error
        result["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
        result["checks"]["expected_pairs"] = {"value": cmp["attempted"], "at_least": 1}
        for k, v in result["checks"].items():
            limit = v.get("limit", v.get("at_least"))
            word = "limit" if "limit" in v else "at_least"
            sys.stderr.write(f"check {k} value={v['value']} {word}={limit}\n")
        sys.stderr.write(f"correct={correct}\n")
        sys.stderr.flush()
        return result
    except BaseException:
        if b is not None:
            sys.stderr.write(f"--- broker log tail ---\n{b.log_tail()}\n")
        raise
    finally:
        if fleet is not None:
            fleet.close()
        if b is not None:
            b.stop()
        if ref is not None and ref.proc.is_alive():
            ref.close()
        shutil.rmtree(workdir, ignore_errors=True)
