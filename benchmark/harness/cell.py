"""One run of one cell: set-up, window, settle, compare, reduce.

The window drives the broker child over MQTT/TCP on loopback, so what is
timed and what is compared is everything between the publishers' sockets
and the subscribers' sockets: codec, session, routing service, hybrid,
device matcher and host mirror (whichever served), relations expansion,
egress.

``correct`` compares what the window itself delivered: every (subscriber,
publish) pair received for a publish sent inside the window against what
the plain trie (``trie.py``, in a process of its own) says of the same
filters and topics, and every QoS1 publish of the window against its PUBACK.
The comparison is exact: each limit is 0. A pair counts once, in the
comparison and in the rate alike: a subscriber that holds two matching
filters, or that is sent a QoS1 delivery again, has received the publish
once, and its latency is that of the first copy.
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
PROBE_PERIODS = 2        # device batches in a row that must compile nothing
NO_DEVICE_GIVE_UP_S = 15.0  # so long without a device batch: none is coming
SETTLE_LIMIT_S = 60.0    # a delivery that comes within a minute is late, not lost
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
        self.t_ack = [np.zeros(0) for _ in range(pub_procs)]
        self.ids, self.times, self.subs = [], [], []
        self.cpu = {}
        self.inflight = 0
        self.lost = 0

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
        self.inflight = 0
        for k, r in enumerate(fleet.ask(fleet.pubs, "drain")):
            self.topics[k] += r["topics"]
            self.t_send[k].append(np.frombuffer(r["t_send"]))
            self.t_ack[k] = np.frombuffer(r["t_ack"])
            self.cpu[f"pub{k}"] = np.frombuffer(r["cpu"]).reshape(-1, 2)
            self.inflight += r["inflight"]
            self.lost += r["lost"]
        return new

    def table(self):
        """Publishes by id (``record * P + process``): send instant and
        PUBACK instant; nan where no publish has the id."""
        P = self.P
        sends = [np.concatenate(s) if s else np.zeros(0) for s in self.t_send]
        size = max(len(s) for s in sends) * P
        send, ack = np.full(size, np.nan), np.full(size, np.nan)
        for k in range(P):
            n = len(sends[k])
            send[k::P][:n] = sends[k]
            ack[k::P][:n] = self.t_ack[k][:n]
        return send, ack

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
    end-to-end metrics are taken from."""
    send, ack = rec.table()
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
    counts, flat = ref.expected([rec.topic_of(int(i)) for i in W])
    want = np.repeat(W, counts) * subscribers + flat  # unique by construction
    missing = np.setdiff1d(want, got, assume_unique=True)
    by_sub = np.bincount(missing % subscribers, minlength=1)
    return {
        "checks": {
            "missing_pairs": int(missing.size),
            "unexpected_pairs": int(np.setdiff1d(got, want, assume_unique=True).size),
            "unacked_qos1": int(np.isnan(ack[W]).sum()),
            "unknown_publish_ids": int((~known).sum()),
            "connections_lost": int(rec.lost),
        },
        "attempted": int(want.size),
        "publishes": int(W.size),
        # who went without: the four subscribers that miss most, [index, pairs]
        "missing_most": [[int(i), int(by_sub[i])] for i in
                         np.argsort(-by_sub)[:4] if by_sub[i]],
        "pairs": int(got.size),
        "copies": int(hit.sum()),  # PUBLISH packets: pairs + further copies
        "deliver_ms": (when[first] - send[got // subscribers]) * 1e3,
        "puback_ms": (ack[W] - send[W]) * 1e3,
        # pairs by the 5 s slice their publish was sent in: shows stalls
        "pairs_by_5s": np.bincount(
            ((send[got // subscribers] - t0) // 5.0).astype(np.int64)).tolist(),
    }


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


# ------------------------------------------------------------------ warm-up
def warm_up(b, t_go: float) -> dict:
    """The cell's own traffic until the shapes it uses are compiled, as far
    as traffic can tell: at least ``WARMUP_MIN_S``, the hybrid has timed both
    of its paths (``hybrid_choice`` is set: two device batches have come
    back), and the last ``PROBE_PERIODS`` batches the device served brought
    no new program (``compile.traces`` did not rise with them). The hybrid
    shows its slower path one large batch in 64 (``ops/hybrid.py``), so a
    shape that the device has not met yet can still turn up later; the cap
    bounds the wait and a cap that is hit is printed."""
    d = b.get("/api/v1/device")
    traces = d["compile"]["traces"]
    dev = dev0 = d["backend"]["hybrid_served"]["device"][0]
    clean = 0  # device batches in a row that compiled nothing
    last_dev = t_go  # when the device last served a batch
    while True:
        time.sleep(0.5)
        now = time.perf_counter()
        d = b.get("/api/v1/device")
        be = d["backend"]
        if d["compile"]["traces"] != traces:
            traces, clean = d["compile"]["traces"], 0
            dev = be["hybrid_served"]["device"][0]
            last_dev = now
        elif be["hybrid_served"]["device"][0] != dev:
            clean += be["hybrid_served"]["device"][0] - dev
            dev = be["hybrid_served"]["device"][0]
            last_dev = now
        elapsed = now - t_go
        settled = be["hybrid_choice"] is not None and clean >= PROBE_PERIODS
        # where batches large enough to reach the device are rare there is
        # nothing to wait for: the device's counters stand still
        no_device = now - last_dev >= NO_DEVICE_GIVE_UP_S
        done = elapsed >= WARMUP_MIN_S and (settled or no_device)
        if done or elapsed >= WARMUP_CAP_S:
            return {"seconds": elapsed, "cap_hit": not done,
                    "device_batches": dev - dev0, "clean_device_batches": clean,
                    "compile_traces": traces, "hybrid_choice": be["hybrid_choice"]}


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
    while (cmp["checks"]["missing_pairs"] or cmp["checks"]["unacked_qos1"]) \
            and time.perf_counter() < t1 + SETTLE_LIMIT_S:
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
        traffic = dict(traffic, **tiny["traffic"])
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
        resident = b.get("/api/v1/stats")[0]["stats"]["subscriptions"]
        n_subs = sum(r["subscribed"] for r in loaded)
        if not resident == n_subs == config["subscriptions"]:
            spec.fail(f"{resident} subscriptions resident, {n_subs} sent, "
                      f"{config['subscriptions']} in the configuration")
        load_s = max(r["seconds"] for r in loaded)
        prepared = fleet.ask(fleet.pubs, "prepare")
        t_go = time.perf_counter()
        fleet.ask(fleet.pubs, "go")
        warm = warm_up(b, t_go)
        say(phase="setup", broker_up_s=up_s, load_s=load_s,
            subscriptions=resident, load_per_s=n_subs / load_s,
            publishers=sum(r["connections"] for r in prepared), warm_up=warm,
            backend={k: be.get(k) for k in (
                "platform", "device_kind", "device_count", "matcher",
                "words_producer", "host_mirror", "hybrid_max")})
        if warm["cap_hit"]:
            say(phase="warning", what=f"warm-up hit its cap of {WARMUP_CAP_S:.0f}s")

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
            device["busy_s"], device["window_s"] = red["busy_s"], traced["window_s"]
            breakdown = {"device_ops": red["ops"],
                         "idle_gaps": [["unattributed", g] for g in red["gaps"]]}
            say(phase="trace", reduce_s=time.perf_counter() - t_red,
                device_planes=red["device_planes"], modules=red["modules"],
                window_s=traced["window_s"], busy_s=red["busy_s"])
            run = {"before": before, "after": after, "trace": traced,
                   "loadgen_cpu_busy_pct": loadgen,
                   "deliver_ms": cmp["deliver_ms"], "puback_ms": cmp["puback_ms"],
                   "config": config,
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
