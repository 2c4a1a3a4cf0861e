#!/usr/bin/env python
"""First light on the chip: the broker's main path, end to end, on one TPU.

    python chip_smoke.py            # one chip; what the driver runs
    python chip_smoke.py --chips 4  # only the mesh-sharded matcher, 4 chips
    python chip_smoke.py --cpu      # tiny rehearsal on the CPU backend

Default run, in order:

1. rebuild ``runtime/librmqtt_runtime.so`` from source (a stale or missing
   library would let the Python trie stand in for the native host mirror);
2. broker A — ``python -m rmqtt_tpu.broker --router xla`` with
   ``RMQTT_HYBRID_MAX=0``, so every batch goes through the device matcher:
   load the BASELINE config-3 table (1,000,000 mixed ``+``/``#`` filters over
   the 6-level tree, ``bench.gen_mixed`` from ``--seed``) through SUBSCRIBE
   packets, publish singles and QoS0/QoS1 bursts, set retained messages and
   scan them with wildcard SUBSCRIBEs; delivered (subscriber, publish) sets
   must EQUAL the ``core/trie.py`` oracle's, and the broker's own counters
   must show the device did the work (uploads, fused batches, no failover);
3. broker B, after A has exited — the same load and traffic with the hybrid
   at its defaults, the share of batches the device served reported, and
   the persistent compile cache A filled being hit;
4. after B has exited, in this process: the three constants the design was
   sized by — compile seconds, dispatch round trip (8 and 16K topics, timed
   around ``block_until_ready``) and device→host bytes/s.

ONE process at a time touches JAX. While a broker child owns the chip this
process stays off the backend and takes the device's identity from the
child's ``/api/v1/device``; it initialises JAX itself only for step 4 (and
for ``--chips 4``, which starts no child at all).

Every phase prints one JSON line. Any failed phase exits non-zero; nothing
is caught and skipped. On anything but ``platform == "tpu"`` the run fails,
unless ``--cpu`` asked for the rehearsal — which reports the platform it
really ran on. The last line is the contract line:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import signal
import statistics
import subprocess
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

import bench  # noqa: E402  (generators only; imports nothing of JAX)
from rmqtt_tpu.bench.scenarios import _free_port, _http_json  # noqa: E402
from rmqtt_tpu.broker.codec import packets as pk  # noqa: E402
from rmqtt_tpu.broker.codec.packets import SubOpts  # noqa: E402
from rmqtt_tpu.core.trie import RetainTree, TopicTree  # noqa: E402
from tests.mqtt_client import TestClient  # noqa: E402

#: the real run, and the tiny one ``--cpu`` rehearses the control flow with
REAL = dict(subs=1_000_000, sub_conns=16, per_packet=1000, pub_conns=256,
            singles=8, bursts_q0=3, burst_q0=1024, bursts_q1=2, burst_q1=512,
            retained=52_000, retained_chunk=1024, scans=6, big_batch=16384,
            fetch_mb=64, retain_threshold=None)
TINY = dict(subs=4_000, sub_conns=4, per_packet=250, pub_conns=96,
            singles=4, bursts_q0=2, burst_q0=160, bursts_q1=1, burst_q1=96,
            retained=400, retained_chunk=200, scans=4, big_batch=1024,
            fetch_mb=4, retain_threshold=100)


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


# --------------------------------------------------------------- workload
class Workload:
    """Everything a broker phase sends, and what the oracle says must come
    back — built once from the seed, replayed against both brokers."""

    def __init__(self, seed: int, z: dict) -> None:
        rng = random.Random(seed)
        t0 = time.perf_counter()
        self.filters = bench.gen_mixed(rng, z["subs"])
        self.owner = {f: i % z["sub_conns"] for i, f in enumerate(self.filters)}
        self.tree = TopicTree()  # the oracle: filter → subscriber connection
        for f, c in self.owner.items():
            self.tree.insert(f, c)
        self.z = z
        # publishes: (id, topic, qos, retain); the id rides the payload
        self.pubs = []

        def add(n, qos, retain=False, topics=None):
            out = []
            for k in range(n):
                topic = topics[k] if topics else bench._tree_topic(rng)
                out.append((len(self.pubs), topic, qos, retain))
                self.pubs.append(out[-1])
            return out

        self.singles = [add(1, k % 2)[0] for k in range(z["singles"])]
        self.bursts = ([add(z["burst_q0"], 0) for _ in range(z["bursts_q0"])]
                       + [add(z["burst_q1"], 1) for _ in range(z["bursts_q1"])])
        rtopics = set()
        while len(rtopics) < z["retained"]:
            rtopics.add(bench._tree_topic(rng, rng.randint(3, 6)))
        rtopics = sorted(rtopics)
        self.retained = [
            add(len(chunk), 0, True, chunk) for chunk in (
                rtopics[i:i + z["retained_chunk"]]
                for i in range(0, len(rtopics), z["retained_chunk"]))]
        # oracle: publish id → set of subscriber connections that get it
        self.expect = {
            pid: {c for _lv, cs in self.tree.matches(topic) for c in cs}
            for pid, topic, _q, _r in self.pubs}
        # retained scans: wildcard filters with a non-empty, queue-sized
        # answer (a session's message queue holds 1000), by RetainTree
        rtree = RetainTree()
        for t in rtopics:
            rtree.insert(t, True)
        self.scans = {}
        tries = 0
        while len(self.scans) < z["scans"]:
            tries += 1
            check(tries < 10_000, "no retained-scan filter with a usable answer")
            lv = bench._tree_topic(rng, rng.randint(2, 5)).split("/")
            shape = len(self.scans) % 3
            if shape == 0:
                lv[rng.randrange(len(lv))] = "+"
            elif shape == 1:
                lv[-1] = "#"
            else:  # leading wildcards: the scanner's broad, full-stream tier
                lv = ["+"] * (len(lv) - 1) + [lv[-1], "#"]
            f = "/".join(lv)
            want = {"/".join(lvls) for lvls, _ in rtree.matches(f)}
            if f not in self.scans and 1 <= len(want) <= 800:
                self.scans[f] = want
        self.build_s = time.perf_counter() - t0


# ----------------------------------------------------------------- broker
class Broker:
    """One ``python -m rmqtt_tpu.broker --router xla`` child."""

    def __init__(self, name: str, workdir: Path, env: dict, z: dict) -> None:
        self.name, self.port, self.api = name, _free_port(), _free_port()
        self.log = workdir / f"{name}.log"
        retain = "tpu = true\n" + (
            f"tpu_threshold = {z['retain_threshold']}\n"
            if z["retain_threshold"] else "")
        conf = workdir / f"{name}.toml"
        conf.write_text(
            f'[listener]\nhost = "127.0.0.1"\nport = {self.port}\n'
            f'[http_api]\nhost = "127.0.0.1"\nport = {self.api}\n'
            f"[retain]\n{retain}")
        full_env = dict(os.environ, **env)
        full_env["PYTHONPATH"] = os.pathsep.join(
            p for p in (str(ROOT), full_env.get("PYTHONPATH", "")) if p)
        self.t0 = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "rmqtt_tpu.broker", "--router", "xla",
             "--config", str(conf)],
            cwd=ROOT, env=full_env, stdout=self.log.open("w"),
            stderr=subprocess.STDOUT)

    async def get(self, path: str) -> dict:
        status, body = await _http_json(self.api, path, timeout=60.0)
        check(status == 200, f"{self.name}: GET {path} -> {status}")
        return body

    async def stats(self) -> dict:
        return (await self.get("/api/v1/stats"))[0]["stats"]

    async def wait_up(self, limit: float = 300.0) -> dict:
        """→ the broker's ``/api/v1/device`` body once the API answers."""
        while True:
            check(self.proc.poll() is None,
                  f"{self.name} exited rc={self.proc.returncode} at start:\n"
                  + self.log.read_text()[-3000:])
            check(time.perf_counter() - self.t0 < limit,
                  f"{self.name} not up after {limit:.0f}s")
            try:
                return await self.get("/api/v1/device")
            except OSError:
                await asyncio.sleep(0.5)

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
                raise SystemExit(f"chip_smoke: FAILED: {self.name} ignored SIGTERM")


async def subscribe_many(c: TestClient, filters, qos: int = 1) -> None:
    pid = c._next_pid()
    await c._send(pk.Subscribe(pid, [(f, SubOpts(qos=qos)) for f in filters], {}))
    ack = await c._wait(("suback", pid), timeout=600.0)
    check(all(rc < 0x80 for rc in ack.reason_codes), "SUBACK refused a filter")


async def send_burst(pubs, batch) -> None:
    """One burst, split over the publisher connections, each writing its
    share in ONE send; QoS1 shares wait for every PUBACK."""

    async def share(c: TestClient, mine):
        frames, acks = [], []
        loop = asyncio.get_running_loop()
        for pid, topic, qos, retain in mine:
            mid = c._next_pid() if qos else None
            if qos:
                fut = loop.create_future()
                c._acks[("puback", mid)] = fut
                acks.append(fut)
            frames.append(c.codec.encode(pk.Publish(
                topic=topic, payload=str(pid).encode(), qos=qos, retain=retain,
                packet_id=mid)))
        c.writer.write(b"".join(frames))
        await c.writer.drain()
        if acks:
            await asyncio.wait_for(asyncio.gather(*acks), 300.0)

    await asyncio.gather(*(share(c, batch[i::len(pubs)])
                           for i, c in enumerate(pubs)))


async def run_broker_phase(name: str, env: dict, wl: Workload, workdir: Path,
                           want_platform: str) -> dict:
    z = wl.z
    b = Broker(name, workdir, env, z)
    clients = []
    try:
        dev = await b.wait_up()
        be = dev["backend"]
        emit(phase=f"{name}.start", seconds=round(time.perf_counter() - b.t0, 2),
             backend={k: be.get(k) for k in (
                 "platform", "device_kind", "device_count", "matcher",
                 "mesh_devices", "host_mirror", "hybrid_max")})
        check(be["platform"] == want_platform,
              f"{name} runs on platform {be['platform']!r}, not "
              f"{want_platform!r} (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
        check(be["host_mirror"] == "native", "host mirror is not the native trie")

        # ---- load the table through SUBSCRIBE packets
        t0 = time.perf_counter()
        subs = [await TestClient.connect(b.port, f"sub-{i}", keepalive=0)
                for i in range(z["sub_conns"])]
        clients += subs
        mine = [[] for _ in subs]
        for f, c in wl.owner.items():
            mine[c].append(f)

        async def load(c, fs):
            for i in range(0, len(fs), z["per_packet"]):
                await subscribe_many(c, fs[i:i + z["per_packet"]])

        await asyncio.gather(*(load(c, fs) for c, fs in zip(subs, mine)))
        load_s = time.perf_counter() - t0
        st = await b.stats()
        emit(phase=f"{name}.load", subscriptions=st["subscriptions"],
             seconds=round(load_s, 2),
             per_second=round(len(wl.filters) / load_s))
        check(st["subscriptions"] == len(wl.filters),
              f"{st['subscriptions']} subscriptions resident, sent {len(wl.filters)}")

        # ---- publishes: singles, bursts, retained; compare with the oracle
        got = [set() for _ in subs]
        copies = [0]

        async def drain(i, c):
            while True:
                p = await c.publishes.get()
                got[i].add(int(p.payload))
                copies[0] += 1

        drains = [asyncio.create_task(drain(i, c)) for i, c in enumerate(subs)]
        pubs = [await TestClient.connect(b.port, f"pub-{i}", keepalive=0)
                for i in range(z["pub_conns"])]
        clients += pubs
        sent = []

        async def settle(limit: float = 300.0):
            """Wait for every delivery the oracle expects of what was sent."""
            want = sum(len(wl.expect[pid]) for pid, *_ in sent)
            end = time.perf_counter() + limit
            while sum(len(g) for g in got) < want:
                check(time.perf_counter() < end,
                      f"{name}: {sum(len(g) for g in got)} of {want} deliveries "
                      f"after {limit:.0f}s")
                await asyncio.sleep(0.02)

        seconds = {}
        for kind, batches, conns in (
                ("singles", [[one] for one in wl.singles], pubs[:1]),
                ("bursts", wl.bursts, pubs), ("retained", wl.retained, pubs)):
            t0 = time.perf_counter()
            for batch in batches:  # each settles before the next is sent
                sent.extend(batch)
                await send_burst(conns, batch)
                await settle()
            seconds[kind] = round(time.perf_counter() - t0, 2)
        await asyncio.sleep(1.0)  # anything the oracle does NOT expect
        for t in drains:
            t.cancel()
        want = [{pid for pid, *_ in sent if i in wl.expect[pid]}
                for i in range(len(subs))]
        wrong = sum(len(g ^ w) for g, w in zip(got, want))
        pairs = sum(len(w) for w in want)
        emit(phase=f"{name}.publish", publishes=len(sent), pairs_expected=pairs,
             pairs_delivered=sum(len(g) for g in got), copies=copies[0],
             mismatched_pairs=wrong,
             qos1_acked=sum(1 for _p, _t, q, _r in sent if q), seconds=seconds)
        check(wrong == 0, f"{name}: delivered sets differ from the trie oracle "
                          f"in {wrong} (subscriber, publish) pairs")
        check(pairs > len(sent), "oracle expects almost nothing: bad workload")

        # ---- retained scans: a fresh client's wildcard SUBSCRIBEs
        scan = await TestClient.connect(b.port, "scan", keepalive=0)
        clients.append(scan)
        t0 = time.perf_counter()
        bad = 0
        for f, want_topics in wl.scans.items():
            await subscribe_many(scan, [f], qos=0)
            bad += len(await read_retained(scan, len(want_topics)) ^ want_topics)
            await scan.unsubscribe(f)
        dev = await b.get("/api/v1/device")
        emit(phase=f"{name}.retained_scan", filters=len(wl.scans),
             topics_expected=sum(len(w) for w in wl.scans.values()),
             mismatched_topics=bad, seconds=round(time.perf_counter() - t0, 2),
             scanner=dev["retained"])
        check(bad == 0, f"{name}: retained scans differ from RetainTree in {bad} topics")
        check(dev["retained"]["scans"] >= len(wl.scans)
              and dev["retained"]["uploads"] >= 1
              and dev["retained"]["rows"] == sum(len(c) for c in wl.retained),
              f"{name}: the device scanner did not serve the scans: {dev['retained']}")

        # ---- the broker's own account of who did the work
        st = await b.stats()
        be = dev["backend"]
        device_proof = {k: st[k] for k in (
            "routing_dispatches", "routing_dispatched_items", "routing_uploads",
            "routing_delta_uploads", "routing_upload_bytes",
            "routing_fused_batches", "routing_failovers",
            "routing_failover_host_routed", "routing_device_failures",
            "routing_failover_state")}
        emit(phase=f"{name}.device", stats=device_proof,
             hybrid_served=be["hybrid_served"], hybrid_choice=be["hybrid_choice"],
             words_producer=be["words_producer"], hbm=dev["hbm"],
             compile={"traces": dev["compile"]["traces"],
                      "trace_ms_total": dev["compile"]["trace_ms_total"],
                      "kernels": {k: {"traces": v["traces"], "trace_ms": v["trace_ms"]}
                                  for k, v in dev["compile"]["kernels"].items()}},
             dispatch={k: dev["dispatch"][k] for k in (
                 "dispatches", "items", "padded_items", "fused", "fallback",
                 "p50_ms", "p99_ms")})
        check(st["routing_failover_host_routed"] == 0 and st["routing_failovers"] == 0
              and st["routing_device_failures"] == 0
              and st["routing_failover_state"] == 0,
              f"{name}: the failover plane engaged: {device_proof}")
        return {"backend": be, "stats": st, "device": dev}
    except BaseException:
        sys.stderr.write(f"--- {name} log tail ---\n{b.log.read_text()[-4000:]}\n")
        raise
    finally:
        for c in clients:
            await c.close()
        b.stop()


async def read_retained(scan: TestClient, want_n: int, limit: float = 120.0,
                        grace: float = 0.5) -> set:
    """Retained topics the scan client receives: until ``want_n`` have come
    and ``grace`` more seconds have passed — a message the oracle does not
    expect must show up as a mismatch, not be left unread."""
    seen = set()
    end = time.perf_counter() + limit
    while time.perf_counter() < end:
        try:
            p = await asyncio.wait_for(scan.publishes.get(), 0.1)
        except asyncio.TimeoutError:
            continue
        if p.retain:
            seen.add(p.topic)
            if len(seen) == want_n:
                end = min(end, time.perf_counter() + grace)
    return seen


# --------------------------------------------------------------- constants
def block(handle) -> None:
    """Wait for a submitted batch's device work without fetching it."""
    import jax

    jax.block_until_ready([x for x in jax.tree_util.tree_leaves(handle)
                           if isinstance(x, jax.Array)])


def timed_dispatch(m, topics, reps: int) -> dict:
    """Medians over ``reps`` of one already-compiled batch shape, stage by
    stage: host encode (the matcher's stage clock), the device round trip
    (submit → ``block_until_ready``, encode taken out), then the fetch and
    the host decode of ``match_complete``."""
    cols = {"host_encode_ms": [], "dispatch_rtt_ms": [], "fetch_ms": [],
            "host_decode_ms": []}
    for _ in range(reps):
        s0 = dict(m.stage_ns)
        t0 = time.perf_counter()
        h = m.match_submit(topics)
        block(h)
        dt = time.perf_counter() - t0
        m.match_complete(h)
        d = {k: (m.stage_ns[k] - s0[k]) / 1e6 for k in s0}
        cols["host_encode_ms"].append(d["encode"])
        cols["dispatch_rtt_ms"].append(dt * 1e3 - d["encode"])
        cols["fetch_ms"].append(d["fetch"])
        cols["host_decode_ms"].append(d["decode"])
    return {**{k: statistics.median(v) for k, v in cols.items()},
            "kind": h[0], "reps": reps}


def constants_phase(wl: Workload, want_platform: str) -> dict:
    import jax
    import numpy as np

    from rmqtt_tpu.ops.partitioned import PartitionedMatcher, PartitionedTable
    from rmqtt_tpu.utils.jaxenv import (
        compile_cache_stats,
        device_identity,
        setup_compile_cache,
    )

    setup_compile_cache()
    ident = device_identity()
    check(ident["platform"] == want_platform,
          f"this process got platform {ident['platform']!r}")
    z = wl.z
    rng = random.Random(1)
    t0 = time.perf_counter()
    table = PartitionedTable()
    fid_owner = {}
    for f in wl.filters:
        fid_owner[table.add(f)] = wl.owner[f]
    build_s = time.perf_counter() - t0
    table.compact()  # now, not on a background thread under the timings
    m = PartitionedMatcher(table)
    m.stage_timing = True
    small = [bench._tree_topic(rng) for _ in range(8)]
    big = [bench._tree_topic(rng) for _ in range(z["big_batch"])]

    def first(topics):
        """→ seconds of the first match at a shape (upload/compile inside),
        rows checked against the oracle."""
        t0 = time.perf_counter()
        rows = m.match(topics)
        dt = time.perf_counter() - t0
        for topic, row in list(zip(topics, rows))[:512]:
            want = sorted(c for _lv, cs in wl.tree.matches(topic) for c in cs)
            check(sorted(fid_owner[f] for f in row.tolist()) == want,
                  f"matcher disagrees with the trie oracle on {topic!r}")
        return dt

    t0 = time.perf_counter()
    m._refresh()
    jax.block_until_ready([m._dev_arrays, m._dev_fids])
    upload_s = time.perf_counter() - t0
    first_small = first(small)   # fused verify + reference compile at B=8
    first_big = first(big)       # the 16K programs
    second_big = first(big)      # budget regrow, if any, settled
    small_t = timed_dispatch(m, small, 30)
    big_t = timed_dispatch(m, big, 7)
    nbytes = z["fetch_mb"] << 20
    host = np.ones(nbytes // 4, np.int32)
    writes = []
    for _ in range(3):
        t0 = time.perf_counter()
        x = jax.device_put(host)
        x.block_until_ready()
        writes.append(time.perf_counter() - t0)
    reads = []
    for _ in range(3):
        y = x + 1  # a fresh device buffer: np.asarray caches per array
        y.block_until_ready()
        t0 = time.perf_counter()
        np.asarray(y)
        reads.append(time.perf_counter() - t0)
    out = {
        "table": {"filters": len(wl.filters), "chunks": table.nchunks,
                  "build_s": round(build_s, 2), "upload_s": round(upload_s, 3),
                  "upload_bytes": m.upload_bytes, "hbm": m.hbm_breakdown()},
        "compile": {"first_match_8_s": round(first_small, 2),
                    f"first_match_{len(big)}_s": round(first_big, 2),
                    f"second_match_{len(big)}_s": round(second_big, 2),
                    "cache": compile_cache_stats(),
                    "note": "first 16K match = trace + compile (or cache "
                            "load) of the fused programs at that shape; the "
                            "fused step alone compiles ~40 s for v5e at "
                            "16384x32 (ahead-of-time figure, not a chip time)"},
        "dispatch_8": small_t, f"dispatch_{len(big)}": big_t,
        "fused_batches": m.fused_batches,
        "routes_per_big_batch": int(sum(len(r) for r in m.match(big))),
        "device_to_host": {"bytes": nbytes, "seconds": statistics.median(reads),
                           "mb_per_s": nbytes / 1e6 / statistics.median(reads)},
        "host_to_device": {"bytes": nbytes, "seconds": statistics.median(writes),
                           "mb_per_s": nbytes / 1e6 / statistics.median(writes)},
    }
    check(m.fused_batches > 0, "the fused pipeline served no batch")
    return {"ident": ident, "constants": out}


# ---------------------------------------------------------------- 4 chips
def four_chip_phase(seed: int, z: dict, want_platform: str) -> dict:
    """ONLY the mesh path and what it is compared with: the sharded matcher
    on a 4-device mesh over the config-3 table and one 16K batch, the
    single-device matcher on the same batch, and the trie oracle."""
    import jax
    import numpy as np

    from rmqtt_tpu.ops.partitioned import PartitionedMatcher, PartitionedTable
    from rmqtt_tpu.parallel.sharded import ShardedPartitionedMatcher, make_mesh
    from rmqtt_tpu.utils.jaxenv import device_identity, setup_compile_cache

    setup_compile_cache()
    ident = device_identity()
    check(ident["platform"] == want_platform and ident["device_count"] >= 4,
          f"need 4 {want_platform} devices, have {ident}")
    rng = random.Random(seed)
    filters = bench.gen_mixed(rng, z["subs"])
    topics = [bench._tree_topic(rng) for _ in range(z["big_batch"])]
    table = PartitionedTable()
    fid_of = {f: table.add(f) for f in filters}
    tree = TopicTree()
    for f, fid in fid_of.items():
        tree.insert(f, fid)
    devs = jax.devices()[:4]
    sharded = ShardedPartitionedMatcher(table, make_mesh(devices=devs, dp=4, fp=1))
    single = PartitionedMatcher(table, device=devs[0])
    # a small batch first, on both: the fused pipelines' first-use verify
    # then compiles its reference at 8 topics, not at 16K
    for m in (sharded, single):
        m.match(topics[:8])
    t0 = time.perf_counter()
    rows_s = sharded.match(topics)
    first_sharded = time.perf_counter() - t0
    t0 = time.perf_counter()
    rows_1 = single.match(topics)
    first_single = time.perf_counter() - t0
    warm = {}
    for name, m in (("sharded", sharded), ("single", single)):
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            m.match(topics)
            ts.append(time.perf_counter() - t0)
        warm[name] = round(statistics.median(ts) * 1e3, 2)
    bad_single = bad_oracle = 0
    for topic, a, b in zip(topics, rows_s, rows_1):
        a, b = np.sort(a), np.sort(b)
        bad_single += not np.array_equal(a, b)
        want = sorted(v for _lv, vs in tree.matches(topic) for v in vs)
        bad_oracle += a.tolist() != want
    tab = [(s.device.id, tuple(s.data.shape))
           for s in sharded._dev_rows.addressable_shards]
    out = {"table_shards": tab, "batch_shards": sharded.last_out_shards,
           "table_shape": tuple(sharded._dev_rows.shape),
           "topics": len(topics), "routes": int(sum(len(r) for r in rows_s)),
           "differs_from_single_device": bad_single,
           "differs_from_oracle": bad_oracle,
           "fused_batches": sharded.fused_batches,
           "first_match_s": {"sharded": round(first_sharded, 2),
                             "single": round(first_single, 2)},
           "warm_match_ms_end_to_end": warm}
    emit(phase="four_chips", **out)
    check(bad_single == 0 and bad_oracle == 0, "sharded results differ")
    check(sum(len(r) for r in rows_s) > len(topics), "almost no routes: bad workload")
    check(len({d for d, _ in tab}) == 4
          and all(shape == tuple(sharded._dev_rows.shape) for _, shape in tab),
          f"table is not replicated whole on 4 devices: {tab}")
    check(len({d for d, _ in sharded.last_out_shards}) == 4,
          f"batch shards are not on 4 distinct devices: {sharded.last_out_shards}")
    check(sharded.fused_batches > 0, "the sharded fused step served no batch")
    return ident


# ------------------------------------------------------------------- main
def rebuild_runtime() -> None:
    t0 = time.perf_counter()
    subprocess.run(["make", "-B", "-s"], cwd=ROOT / "runtime", check=True,
                   timeout=300)
    emit(phase="runtime_build", seconds=round(time.perf_counter() - t0, 2))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu", action="store_true",
                    help="tiny rehearsal on the CPU backend (never the default)")
    args = ap.parse_args()
    z = TINY if args.cpu else REAL
    want = "cpu" if args.cpu else "tpu"
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    rebuild_runtime()
    if args.chips == 4:
        ident = four_chip_phase(args.seed, z, want)
    else:
        wl = Workload(args.seed, z)
        emit(phase="workload", seed=args.seed, filters=len(wl.filters),
             publishes=len(wl.pubs), retained=sum(len(c) for c in wl.retained),
             scans=len(wl.scans), oracle_build_s=round(wl.build_s, 2),
             sizes="tiny (--cpu rehearsal)" if args.cpu else "real")
        with tempfile.TemporaryDirectory(prefix="chip_smoke-") as tmp:
            a = asyncio.run(run_broker_phase(
                "forced_device", {"RMQTT_HYBRID_MAX": "0"}, wl, Path(tmp), want))
            st, be = a["stats"], a["backend"]
            check(st["routing_uploads"] >= 1
                  and st["routing_upload_bytes"] >= 12 * len(wl.filters)
                  and st["routing_fused_batches"] > 0
                  and be["hybrid_served"]["side"][0] == 0
                  and be["hybrid_served"]["device"][0] > 0,
                  f"forced_device: counters do not show the device serving "
                  f"a {len(wl.filters)}-filter table: {st['routing_uploads']} "
                  f"uploads, {st['routing_upload_bytes']} B, "
                  f"{st['routing_fused_batches']} fused, {be['hybrid_served']}")
            b = asyncio.run(run_broker_phase("defaults", {}, wl, Path(tmp), want))
        served = b["backend"]["hybrid_served"]
        total = served["side"][0] + served["device"][0]
        emit(phase="hybrid_share_at_defaults", batches=total,
             device_batches=served["device"][0], side_batches=served["side"][0],
             device_topics=served["device"][1], side_topics=served["side"][1],
             device_share=round(served["device"][0] / total, 4) if total else None,
             choice=b["backend"]["hybrid_choice"])
        # A is cold unless the machine came with a cache (then it hits too);
        # B starts after A and must find what A compiled
        ca, cb = be["compile_cache"], b["backend"]["compile_cache"]
        emit(phase="compile_cache", forced_device=ca, defaults=cb,
             broker_trace_ms={"forced_device": a["device"]["compile"]["trace_ms_total"],
                              "defaults": b["device"]["compile"]["trace_ms_total"]})
        check(ca["writes"] + ca["hits"] > 0, "broker A never used the compile cache")
        check(cb["hits"] > 0, "the second broker start hit nothing in the compile cache")
        res = constants_phase(wl, want)
        emit(phase="constants", **res["constants"])
        ident = res["ident"]
        check((be["platform"], be["device_kind"], be["device_count"])
              == (ident["platform"], ident["device_kind"], ident["device_count"]),
              f"broker reported {be['platform']}/{be['device_kind']} x"
              f"{be['device_count']}, this process sees {ident}")
    print(json.dumps({"ok": True, "device": {
        "platform": ident["platform"], "kind": ident["device_kind"],
        "count": ident["device_count"]}}), flush=True)


if __name__ == "__main__":
    main()
