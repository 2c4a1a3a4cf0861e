#!/usr/bin/env python
"""Routing benchmark: PUBLISH routes/sec + p99 match latency vs CPU baseline.

Implements the five configs of BASELINE.json. The reference publishes no
routing-match microbenchmark (BASELINE.md), so the baseline is our own CPU
``DefaultRouter``-equivalent (the TopicTree trie oracle, mirroring
`/root/reference/rmqtt/src/router.rs:174-265` + `trie.rs:288-408`), measured
on the *same* filter set over a topic subsample; the TPU side runs the
batched automaton matcher end-to-end (host encode → kernel → fid decode).

Prints ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}
Per-config detail goes to stderr.

Usage (needs a TPU; without one it exits non-zero — add --cpu to run the
same configs on the CPU backend on purpose, labelled as such):
  python bench.py              # every config (on a TPU: incl. the 10M ones)
  python bench.py --smoke      # tiny config 1 only
  python bench.py --config N   # run just config N (headline = it)
  python bench.py --cpu --smoke   # CI-sized CPU run
Exit code 1 when any config failed.
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- generators


def gen_exact(rng, n):
    """Config 1: exact-match filters, no wildcards (depth 3-5)."""
    filters = set()
    while len(filters) < n:
        depth = rng.randint(3, 5)
        filters.add("/".join(f"l{d}n{rng.randrange(max(4, n >> (8 - d)))}" for d in range(depth)))
    return sorted(filters)


def gen_single_plus(rng, n):
    """Config 2: single-level '+' wildcards (depth 3-5, one + each)."""
    filters = set()
    while len(filters) < n:
        depth = rng.randint(3, 5)
        levels = [f"l{d}n{rng.randrange(max(4, n >> (8 - d)))}" for d in range(depth)]
        levels[rng.randrange(depth)] = "+"
        filters.add("/".join(levels))
    return sorted(filters)


VOCAB6 = [50, 80, 100, 150, 200, 400]  # per-level vocabulary of the 6-level tree


def _tree_topic(rng, depth=6):
    return "/".join(f"v{d}_{rng.randrange(VOCAB6[d])}" for d in range(depth))


def gen_mixed(rng, n, shared_frac=0.0):
    """Configs 3/4: mixed +/# wildcards over a 6-level topic tree."""
    filters = set()
    while len(filters) < n:
        depth = rng.randint(2, 6)
        levels = [f"v{d}_{rng.randrange(VOCAB6[d])}" for d in range(depth)]
        r = rng.random()
        if r < 0.35:  # sprinkle +
            for _ in range(rng.randint(1, 2)):
                levels[rng.randrange(depth)] = "+"
        if r >= 0.25 and r < 0.55:
            levels[-1] = "#"
        f = "/".join(levels)
        if shared_frac and rng.random() < shared_frac:
            f = "$share/g%d/%s" % (rng.randrange(16), f)
        filters.add(f)
    return sorted(filters)


def gen_topics_uniform(rng, n, depth=6):
    return [_tree_topic(rng, depth) for _ in range(n)]


def gen_topics_zipf(rng, n, depth=6, a=1.3):
    """Zipf-skewed publish stream over the topic tree (config 4)."""
    nprng = np.random.default_rng(rng.randrange(2**31))
    out = []
    for _ in range(n):
        ranks = nprng.zipf(a, size=depth)
        out.append("/".join(f"v{d}_{(int(ranks[d]) - 1) % VOCAB6[d]}" for d in range(depth)))
    return out


# ---------------------------------------------------------------- measurement


def build_tpu_table(filters, kind="dense"):
    from rmqtt_tpu.core.topic import parse_shared

    if kind == "dense":
        from rmqtt_tpu.ops.encode import FilterTable

        table = FilterTable()
    else:
        from rmqtt_tpu.ops.partitioned import PartitionedTable

        table = PartitionedTable()
    fids = {}
    t0 = time.perf_counter()
    for f in filters:
        _, stripped = parse_shared(f)
        fids[table.add(stripped)] = stripped
    log(f"  {kind} table build: {len(filters)} filters in {time.perf_counter() - t0:.2f}s "
        f"(L={table.max_levels}, vocab={len(table.tokens)})")
    return table, fids


def build_cpu_tree(filters):
    from rmqtt_tpu.core.topic import parse_shared
    from rmqtt_tpu.core.trie import TopicTree

    tree = TopicTree()
    t0 = time.perf_counter()
    for i, f in enumerate(filters):
        _, stripped = parse_shared(f)
        tree.insert(stripped, i)
    log(f"  trie build: {time.perf_counter() - t0:.2f}s")
    return tree


def make_matcher(table):
    from rmqtt_tpu.ops.encode import FilterTable
    from rmqtt_tpu.ops.match import TpuMatcher
    from rmqtt_tpu.ops.partitioned import PartitionedMatcher

    return TpuMatcher(table) if isinstance(table, FilterTable) else PartitionedMatcher(table)


def measure_tpu(matcher, topics, batch_size, warmup=2, min_batches=8, pipeline_depth=3):
    """End-to-end topics/sec + per-batch latency through the batched matcher.

    Throughput is measured PIPELINED when the matcher supports
    submit/complete (jax dispatch is async, so batch N+1's host encode
    overlaps batch N's device compute — what hides the dispatch round
    trip); latency percentiles come from serial round trips."""
    batches = [topics[i : i + batch_size] for i in range(0, len(topics), batch_size)]
    batches = [b for b in batches if len(b) == batch_size]
    if len(batches) < warmup + min_batches:
        batches = batches * ((warmup + min_batches) // max(1, len(batches)) + 1)
    # warmup (compile)
    t0 = time.perf_counter()
    try:
        for b in batches[:warmup]:
            matcher.match(b)
    except Exception as e:
        # round 2's cfg4 died here on-chip (10M-sub table → one huge
        # device_put/compile → "TPU backend setup/compile error"): retry
        # once with the table split into bounded segments before giving up
        if not hasattr(matcher, "_seg_bytes") or matcher._segments is not None:
            raise
        log(f"  warmup failed ({type(e).__name__}: {e}); retrying with a "
            f"segmented device table")
        matcher._seg_bytes = min(matcher._seg_bytes, 128 << 20)
        matcher._dev_version = -1
        matcher._dev_arrays = None
        for b in batches[:warmup]:
            matcher.match(b)
    log(f"  tpu warmup/compile: {time.perf_counter() - t0:.2f}s")
    # latency: serial round trips on a few batches
    lat = []
    for b in batches[warmup : warmup + max(4, min_batches // 2)]:
        t1 = time.perf_counter()
        matcher.match(b)
        lat.append(time.perf_counter() - t1)
    # throughput: pipelined over all measurement batches
    routes = 0
    done = 0
    work = batches[warmup:]
    t_start = time.perf_counter()
    if hasattr(matcher, "match_submit"):
        from collections import deque

        pending = deque()
        for b in work:
            pending.append((len(b), matcher.match_submit(b)))
            if len(pending) >= pipeline_depth:
                n, h = pending.popleft()
                rows = matcher.match_complete(h)
                routes += sum(len(r) for r in rows)
                done += n
        while pending:
            n, h = pending.popleft()
            rows = matcher.match_complete(h)
            routes += sum(len(r) for r in rows)
            done += n
    else:
        for b in work:
            rows = matcher.match(b)
            routes += sum(len(r) for r in rows)
            done += len(b)
    total = time.perf_counter() - t_start
    return {
        "topics_per_sec": done / total,
        "routes_per_sec": routes / total,
        "routes": routes,
        "topics": done,
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p99_ms": float(np.percentile(lat, 99) * 1e3),
        "batch_size": batch_size,
        "pipelined": hasattr(matcher, "match_submit"),
    }


def build_native_trie(filters):
    """C++ trie (runtime/topics.cc) — the honest native CPU baseline."""
    from rmqtt_tpu import runtime
    from rmqtt_tpu.core.topic import parse_shared

    if not runtime.available():
        return None
    t0 = time.perf_counter()
    trie = runtime.NativeTrie()
    for i, f in enumerate(filters):
        _, stripped = parse_shared(f)
        trie.add(stripped, i)
    log(f"  native trie build: {time.perf_counter() - t0:.2f}s")
    return trie


def measure_cpu_native(trie, topics, sample, time_budget_s=20.0):
    sub = topics[:sample]
    t0 = time.perf_counter()
    routes = 0
    done = 0
    step = 512
    for i in range(0, len(sub), step):
        rows = trie.match_batch(sub[i : i + step])
        routes += sum(len(r) for r in rows)
        done += len(rows)
        if time.perf_counter() - t0 > time_budget_s:
            break
    total = time.perf_counter() - t0
    return {"topics_per_sec": done / total, "routes_per_sec": routes / total,
            "topics": done, "routes": routes}


def measure_cpu(tree, topics, sample, time_budget_s=20.0):
    """CPU trie matches/sec over a subsample of the same topic stream."""
    sub = topics[:sample]
    t0 = time.perf_counter()
    routes = 0
    done = 0
    for topic in sub:
        for _f, vals in tree.matches(topic):
            routes += len(vals)
        done += 1
        if time.perf_counter() - t0 > time_budget_s:
            break
    total = time.perf_counter() - t0
    return {
        "topics_per_sec": done / total,
        "routes_per_sec": routes / total,
        "topics": done,
        "routes": routes,
    }


def spot_check(matcher, fids, tree, topics, n=32):
    """Correctness: TPU fids ≡ trie values on a topic sample."""
    sample = topics[:n]
    rows = matcher.match(sample)
    for topic, row in zip(sample, rows):
        tpu_filters = sorted(fids[fid] for fid in row.tolist())
        cpu_filters = sorted(
            fids_str for _lv, vals in tree.matches(topic) for fids_str in ["/".join(_lv)] * len(vals)
        )
        assert tpu_filters == cpu_filters, f"mismatch on {topic!r}:\n{tpu_filters}\nvs\n{cpu_filters}"
    log(f"  spot check: {n} topics agree with CPU oracle")


# ---------------------------------------------------------------- configs


_PROFILE_DIR = None  # set by main --profile; traces the DEVICE phase only


class _DeviceProfile:
    """Profile just the measured device phase — a trace spanning the
    minutes of data generation / CPU baselines would bury the kernels.
    Profiler failures (unwritable dir, double-start) must never fail the
    bench: they log and measurement continues untraced."""

    def __init__(self, name: str) -> None:
        self.name = name
        self._cm = None

    def __enter__(self):
        if _PROFILE_DIR is None:
            return self
        try:
            import jax

            self._cm = jax.profiler.trace(f"{_PROFILE_DIR}/{self.name}")
            self._cm.__enter__()
        except Exception as e:
            log(f"profiler unavailable ({e}); continuing without trace")
            self._cm = None
        return self

    def __exit__(self, *exc):
        if self._cm is not None:
            try:
                self._cm.__exit__(*exc)
            except Exception as e:
                log(f"profiler stop failed ({e})")
        return False


def _device_profile(name):
    return _DeviceProfile(name)


def run_config(name, filters, topics, batch_size, cpu_sample, retained=None):
    log(f"[{name}] {len(filters)} subs, {len(topics)} publish topics")
    tree = build_cpu_tree(filters)
    cpu = measure_cpu(tree, topics, cpu_sample)
    native = build_native_trie(filters)
    cpu_native = measure_cpu_native(native, topics, cpu_sample * 4) if native else None
    # ≤2M subs: keep the C++ trie — the hybrid router-level measurement
    # reuses it as the side mirror (the deployed XlaRouter holds both).
    # Above that, free it before the big device-table builds (round-2 OOM
    # guard); the router-level figure is then derived from measured rates
    # instead of holding trie+table resident twice in this one process.
    keep_side = native is not None and len(filters) <= 2_000_000
    if not keep_side:
        del native
        native = None
    variants = {}
    kinds = ("partitioned", "dense")
    if len(filters) > 2_000_000:
        # dense scans every filter row per topic: at 10M subs one batch is
        # 10M rows x batch of compares and it can never beat the partitioned
        # automaton there — skip it instead of burning most of the bench
        # budget on a known-losing variant
        kinds = ("partitioned",)
    for kind in kinds:
        table, fids = build_tpu_table(filters, kind)
        # ONE matcher (and one device table upload) per variant: spot check,
        # measurement and the retained interleave all share it
        matcher = make_matcher(table)
        spot_check(matcher, fids, tree, topics)
        with _device_profile(f"{name}_{kind}"):
            variants[kind] = measure_tpu(matcher, topics, batch_size)
            if retained is not None and kind == kinds[-1]:
                variants["retained"] = run_retained(matcher, retained, topics)
        if kind == "partitioned":
            # ROUTER-LEVEL measurement: the XlaRouter as deployed races the
            # host trie mirror against the device per regime (ops/hybrid.py)
            # — this is the number a broker user actually gets, reported as
            # the headline alongside the raw device figure
            if keep_side:
                variants["hybrid"] = measure_hybrid(matcher, native, topics,
                                                    batch_size)
            elif cpu_native is not None:
                # 10M-sub configs: derive the deployed choice from the two
                # measured rates (see keep_side above)
                dev = dict(variants[kind])
                dev_wins = dev["topics_per_sec"] >= cpu_native["topics_per_sec"]
                if not dev_wins:
                    dev.update({k2: cpu_native[k2] for k2 in
                                ("topics_per_sec", "routes_per_sec")})
                dev["hybrid_choice"] = "device" if dev_wins else "side(derived)"
                variants["hybrid"] = dev
            if _ON_TPU:
                # the stream sweep measures DEVICE dispatch overlap (the
                # burst-p99 artifact); on the CPU fallback it only burns
                # the snapshot run's budget
                stream = measure_stream(matcher, topics)
                if stream is not None:
                    variants["stream"] = stream
            # analytic HBM model against THIS table + topic stream, embedded
            # next to the measured rate so every artifact carries its own
            # modeled-vs-measured delta (roofline claim checkable per run)
            try:
                from rmqtt_tpu.bench.roofline_model import model_table
                from rmqtt_tpu.core.topic import split_levels

                ncs = [len(table._candidates_for(split_levels(tp)))
                       for tp in topics[:2048]]
                import jax

                variants["roofline_model"] = model_table(
                    table, ncs, jax.devices()[0].device_kind,
                    measured_topics_per_sec=variants[kind]["topics_per_sec"])
            except Exception as e:  # the bench must not die on the model
                log(f"  roofline model skipped: {e}")
        del table, fids, matcher
    best_kind = max(kinds, key=lambda k: variants[k]["topics_per_sec"])
    tpu = variants[best_kind]
    # the honest baseline is the native (C++) trie when the toolchain exists
    baseline = cpu_native or cpu
    res = {
        "name": name,
        "device": tpu,
        "matcher": best_kind,
        "variants": variants,
        "cpu": cpu,
        "cpu_native": cpu_native,
        "baseline_kind": "cpu_native" if cpu_native else "cpu_python",
        "speedup": tpu["topics_per_sec"] / baseline["topics_per_sec"],
    }
    hyb = variants.get("hybrid")
    if hyb is not None:
        res["router"] = hyb
        res["router_speedup"] = hyb["topics_per_sec"] / baseline["topics_per_sec"]
    if "stream" in variants:
        res["stream"] = variants.pop("stream")
    if "retained" in variants:
        res["retained"] = variants.pop("retained")
    if "roofline_model" in variants:
        res["roofline_model"] = variants.pop("roofline_model")
    nat = f" native {cpu_native['topics_per_sec']:.0f}" if cpu_native else ""
    rtr = (f" | router(hybrid→{hyb.get('hybrid_choice')}) "
           f"{hyb['topics_per_sec']:.0f} topics/s "
           f"{res['router_speedup']:.2f}x" if hyb else "")
    log(
        f"[{name}] device[{best_kind}] {tpu['topics_per_sec']:.0f} topics/s "
        f"({tpu['routes_per_sec']:.0f} routes/s, p50 {tpu['p50_ms']:.1f}ms "
        f"p99 {tpu['p99_ms']:.1f}ms) | CPU {cpu['topics_per_sec']:.0f}{nat} topics/s "
        f"| speedup {res['speedup']:.2f}x vs {res['baseline_kind']}{rtr}"
    )
    return res


# set once in main() from the probe + resolved platform (single source of
# truth; run_config must not re-touch the backend to learn it)
_ON_TPU = False


def measure_stream(matcher, topics, micro_sizes=(2048, 4096), depth=3,
                   min_batches=24):
    """Burst p99 under a CONTINUOUS pipelined micro-batch stream:
    instead of one serial batch-sized dispatch (sum of stages —
    258.7ms standing at cfg3/16K), micro-batches stream through
    submit/complete with ``depth`` in flight, so per-batch latency tends to
    the slowest stage. Per-batch latency = submit→complete wall time while
    the pipeline is kept full; reports the best micro size by p99."""
    if not hasattr(matcher, "match_submit"):
        return None
    from collections import deque

    best = None
    for micro in micro_sizes:
        stream = [topics[i:i + micro] for i in range(0, len(topics), micro)]
        stream = [b for b in stream if len(b) == micro]
        if not stream:
            continue
        while len(stream) < min_batches + depth:
            stream = stream + stream
        stream = stream[: min_batches + depth]
        matcher.match(stream[0])  # warm this shape
        lat = []
        pending = deque()
        t_all = time.perf_counter()
        for b in stream:
            pending.append((time.perf_counter(), len(b), matcher.match_submit(b)))
            if len(pending) >= depth:
                t_sub, _n, h = pending.popleft()
                matcher.match_complete(h)
                lat.append(time.perf_counter() - t_sub)
        while pending:
            t_sub, _n, h = pending.popleft()
            matcher.match_complete(h)
            lat.append(time.perf_counter() - t_sub)
        total = time.perf_counter() - t_all
        rec = {
            "micro_batch": micro,
            "depth": depth,
            "stream_topics_per_sec": round(len(stream) * micro / total, 1),
            "stream_p50_ms": round(float(np.percentile(lat, 50) * 1e3), 2),
            "stream_p99_ms": round(float(np.percentile(lat, 99) * 1e3), 2),
        }
        log(f"  stream micro={micro} depth={depth}: "
            f"{rec['stream_topics_per_sec']:.0f} topics/s, "
            f"p50 {rec['stream_p50_ms']}ms p99 {rec['stream_p99_ms']}ms")
        if best is None or rec["stream_p99_ms"] < best["stream_p99_ms"]:
            best = rec
    return best


def measure_hybrid(matcher, side, topics, batch_size):
    """The router-level number: AdaptiveHybrid (host C++ trie vs device
    kernel, measured per regime) over the same stream — plus the 1-topic
    p99 the sub-threshold path guarantees. ``side`` is the baseline's
    already-built NativeTrie (fid value spaces differ from the device
    table's; only match COUNTS and rates matter here — correctness of both
    engines is pinned by spot_check and the differential suite)."""
    from rmqtt_tpu.ops.hybrid import AdaptiveHybrid

    hybrid = AdaptiveHybrid(side, matcher, probe_every=16)
    out = measure_tpu(hybrid, topics, batch_size, warmup=1)
    out["hybrid_choice"] = hybrid.choice or "device"
    # small-batch latency: the deployed router's 1-topic publish path
    lat1 = []
    for t in topics[:64]:
        t1 = time.perf_counter()
        hybrid.match([t])
        lat1.append(time.perf_counter() - t1)
    out["p99_1topic_ms"] = float(np.percentile(lat1, 99) * 1e3)
    return out


def run_retained(matcher, retained_topics, publish_topics):
    """Config 5 extra: concurrent retained-scan (SUBSCRIBE) + publish routing.

    The scan side runs the PARTITIONED inverse matcher (ops/retained_part):
    a realistic subscriber mix — mostly prefix filters
    that prune to a few partition chunks, a tail of broad multi-wildcard
    filters that genuinely scan everything — pipelined against the publish
    stream so scan dispatch overlaps publish compute."""
    from rmqtt_tpu.ops.retained_part import PartitionedRetainedScanner, RetainedTable

    rt = RetainedTable()
    t0 = time.perf_counter()
    for t in retained_topics:
        rt.add(t)
    log(f"  retained table: {len(retained_topics)} topics in {time.perf_counter() - t0:.2f}s "
        f"({rt.nchunks} chunks)")
    scanner = PartitionedRetainedScanner(rt)
    # subscriber filter mix: 70% device/prefix-scoped (the reference's
    # retained replay is per-subscription, e.g. home/+/temp), 20% mid-tree
    # wildcards, 10% broad
    rng = random.Random(5)
    sub_filters = []
    for _ in range(512):
        r = rng.random()
        if r < 0.7:
            f = f"v0_{rng.randrange(VOCAB6[0])}/v1_{rng.randrange(VOCAB6[1])}/+"
            if rng.random() < 0.5:
                f += "/#"
        elif r < 0.9:
            f = f"v0_{rng.randrange(VOCAB6[0])}/+/+/#"
        else:
            f = "/".join(["+"] * rng.randint(1, 4)) + "/#"
        sub_filters.append(f)
    pb, sb = 1024, 64
    scanner.scan(sub_filters[:sb])
    matcher.match(publish_topics[:pb])  # warm
    t0 = time.perf_counter()
    rounds = 8

    def scan_slice(r):
        lo = (r * sb) % (len(sub_filters) - sb)
        return sub_filters[lo: lo + sb]

    for r in range(rounds):
        ph = matcher.match_submit(publish_topics[r * pb: (r + 1) * pb]) \
            if hasattr(matcher, "match_submit") else None
        sh = scanner.scan_submit(scan_slice(r))
        if ph is None:
            matcher.match(publish_topics[r * pb: (r + 1) * pb])
        else:
            matcher.match_complete(ph)
        scanner.scan_complete(sh)
    total = time.perf_counter() - t0
    # the interleaved figure above couples scans to the publish matcher's
    # round time (on the CPU fallback the publish side dominates by ~10x);
    # a scan-only phase isolates the retained path itself
    t1 = time.perf_counter()
    for r in range(rounds):
        scanner.scan_complete(scanner.scan_submit(scan_slice(r)))
    scan_only = time.perf_counter() - t1
    return {
        "publish_topics_per_sec": rounds * pb / total,
        "subscribe_scans_per_sec": rounds * sb / total,
        "scan_only_scans_per_sec": rounds * sb / scan_only,
        "scan_backend": "partitioned",
    }


def run_cache_config(name, rng):
    """Config 6: the epoch-versioned match-result cache on the CPU/native
    router path under zipf-skewed publish traffic (the hot-topic regime the
    cache targets) — cache-on vs cache-off topics/s with hit rate, plus the
    uniform miss-heavy stream to bound the cache's overhead. Runs entirely
    host-side: the number is provable without a TPU window."""
    from rmqtt_tpu.core.topic import parse_shared
    from rmqtt_tpu.router.base import Id, SubscriptionOptions
    from rmqtt_tpu.router.cache import MatchCache, cached_matches_raw

    n_filters, n_topics, pool_size = 200_000, 100_000, 20_000
    capacity = 8192
    try:
        from rmqtt_tpu import runtime

        native = runtime.available()
    except Exception:
        native = False
    if native:
        from rmqtt_tpu.router.native import NativeRouter as R

        kind = "native"
    else:
        from rmqtt_tpu.router.default import DefaultRouter as R

        kind = "python"
    router = R()
    # topic pool first: the $share work queues subscribe to CONCRETE pool
    # topics (the realistic shared-sub shape — wildcard-$share correctness
    # rides the property suite, broad-shared device perf rides cfg4)
    pool = sorted({_tree_topic(rng) for _ in range(pool_size)})
    n_shared = n_filters // 50  # 2% shared work-queue subscriptions
    filters = gen_mixed(rng, n_filters - n_shared)
    filters += [f"$share/g{rng.randrange(8)}/{rng.choice(pool)}"
                for _ in range(n_shared)]
    t0 = time.perf_counter()
    for i, f in enumerate(filters):
        grp, stripped = parse_shared(f)
        router.add(stripped, Id(1, f"c{i}"),
                   SubscriptionOptions(qos=1, shared_group=grp))
    log(f"[{name}] {kind} router: {n_filters} subs in {time.perf_counter() - t0:.2f}s")
    # daemon GC hygiene: the ~10^6-object subscription table must not be
    # re-scanned by every gen-2 collection the measurement loops trigger —
    # without the freeze, GC artifacts (not routing work) dominate the
    # cached-vs-uncached comparison
    import gc

    gc.collect()
    gc.freeze()
    # zipf-ranked hot-key stream over the pool (a=1.3: ~94% of the mass
    # inside the cache capacity) + a uniform miss-heavy stream
    nprng = np.random.default_rng(rng.randrange(2**31))
    ranks = (nprng.zipf(1.3, size=n_topics).astype(np.int64) - 1) % len(pool)
    zipf_topics = [pool[i] for i in ranks]
    uniform_topics = gen_topics_uniform(rng, n_topics)

    def run_once(topics, cached, budget_s):
        cache = MatchCache(router.epochs, capacity=capacity) if cached else None
        t1 = time.perf_counter()
        routes = done = 0
        for t in topics:
            if cache is not None:
                rel = router.collapse(cached_matches_raw(router, cache, None, t))
            else:
                rel = router.matches(None, t)
            routes += sum(len(v) for v in rel.values())
            done += 1
            if done % 4096 == 0 and time.perf_counter() - t1 > budget_s:
                break
        total = time.perf_counter() - t1
        rec = {"topics_per_sec": round(done / total, 1),
               "routes_per_sec": round(routes / total, 1), "topics": done}
        if cache is not None:
            rec["hit_rate"] = round(cache.hits / max(1, cache.hits + cache.misses), 4)
            rec["evictions"] = cache.evictions
        return rec

    def run(topics, cached, budget_s=8.0, reps=2):
        # best-of-N: the cached-vs-uncached ratio is the artifact — machine
        # noise between two 8-second windows must not masquerade as cache
        # overhead (or speedup)
        recs = [run_once(topics, cached, budget_s) for _ in range(reps)]
        return max(recs, key=lambda r: r["topics_per_sec"])

    run(uniform_topics[:2000], False, budget_s=5.0, reps=1)  # warm caches
    zipf_on = run(zipf_topics, True)
    zipf_off = run(zipf_topics, False)
    uni_on = run(uniform_topics, True)
    uni_off = run(uniform_topics, False)
    res = {
        "name": name,
        "router": kind,
        "subs": n_filters,
        "cache_capacity": capacity,
        "zipf": {
            "cached": zipf_on,
            "uncached": zipf_off,
            "speedup_cached": round(
                zipf_on["topics_per_sec"] / zipf_off["topics_per_sec"], 2),
        },
        "uniform_miss": {
            "cached": uni_on,
            "uncached": uni_off,
            # >1 means the cache costs throughput on all-miss traffic;
            # the acceptance bound is <= 1.05 (no >5% regression)
            "overhead_ratio": round(
                uni_off["topics_per_sec"] / max(1e-9, uni_on["topics_per_sec"]), 3),
        },
    }
    log(f"[{name}] zipf: cached {zipf_on['topics_per_sec']:.0f} topics/s "
        f"(hit {zipf_on['hit_rate']:.1%}) vs uncached "
        f"{zipf_off['topics_per_sec']:.0f} → {res['zipf']['speedup_cached']:.2f}x | "
        f"uniform miss overhead {res['uniform_miss']['overhead_ratio']:.3f}x")
    return res


def run_telemetry_config(name, rng):
    """Config 7: latency-telemetry overhead (broker/telemetry.py) on the
    REAL publish path.

    Runs an in-process broker (real sockets, real sessions, the deployed
    RoutingService + match cache) with one QoS0 publisher → one subscriber
    over a rotating topic set, telemetry OFF vs ON in interleaved windows,
    and reports the throughput delta. This is the path every telemetry
    stage actually instruments — a stripped router-only loop triples the
    apparent relative cost because it deletes most of the per-publish work
    the substrate's ~1-2µs rides on. The enabled windows' p50/p99 for
    publish e2e and the match stage ride into the bench JSON so
    BENCH_*.json rounds carry a latency trajectory, not just throughput.

    Also reports the raw substrate cost per op (tight-loop microbench of
    one clock pair + one recorder call) for transparency."""
    import asyncio

    from rmqtt_tpu.broker.codec import MqttCodec, packets as pk
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
    from rmqtt_tpu.broker.server import MqttBroker
    from rmqtt_tpu.broker.telemetry import Telemetry

    msgs = 15_000
    ntopics = 64  # rotating topics: exercises both cache-hit and miss paths
    payload = b"x" * 64

    async def _read_until(reader, codec, ptype):
        while True:
            data = await reader.read(4096)
            if not data:
                raise ConnectionError(f"peer closed before {ptype.__name__}")
            for p in codec.feed(data):
                if isinstance(p, ptype):
                    return p

    async def _connect(port, cid):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        codec = MqttCodec()
        writer.write(codec.encode(pk.Connect(client_id=cid, keepalive=600)))
        await writer.drain()
        await _read_until(reader, codec, pk.Connack)
        return reader, writer, codec

    async def _pipe(enable):
        """Broker + 1 subscriber + 1 publisher; → (burst fn, broker)."""
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, telemetry_enable=enable, allow_anonymous=True)))
        await b.start()
        sr, sw, scodec = await _connect(b.port, f"c7-sub-{enable}")
        sw.write(scodec.encode(pk.Subscribe(1, [("bench/#", pk.SubOpts(qos=0))])))
        await sw.drain()
        await _read_until(sr, scodec, pk.Suback)
        _pr, pw, pcodec = await _connect(b.port, f"c7-pub-{enable}")
        frames = [pcodec.encode(pk.Publish(
            topic=f"bench/t{i}", payload=payload, qos=0))
            for i in range(ntopics)]

        async def burst(n):
            """Blast n publishes, drain n deliveries; → elapsed seconds."""
            t0 = time.perf_counter()
            sent = 0
            got = 0
            deadline = time.monotonic() + 60.0
            while sent < n:
                k = min(64, n - sent)
                pw.write(b"".join(
                    frames[(sent + j) % ntopics] for j in range(k)))
                sent += k
                if pw.transport.get_write_buffer_size() > 1 << 18:
                    await pw.drain()
                while got < sent - 2048:
                    data = await asyncio.wait_for(
                        sr.read(1 << 16), deadline - time.monotonic())
                    if not data:
                        raise ConnectionError("subscriber closed")
                    got += sum(1 for p in scodec.feed(data)
                               if isinstance(p, pk.Publish))
            await pw.drain()
            while got < sent:
                data = await asyncio.wait_for(
                    sr.read(1 << 16), deadline - time.monotonic())
                if not data:
                    raise ConnectionError("subscriber closed")
                got += sum(1 for p in scodec.feed(data)
                           if isinstance(p, pk.Publish))
            return time.perf_counter() - t0

        return burst, b

    async def _measure():
        """BOTH brokers live at once; off/on bursts alternate back-to-back
        so host-load drift on this shared-core machine — far larger than
        the effect under test across whole-broker windows — hits both
        conditions equally and cancels in the ratio (the artifact)."""
        burst_off, b_off = await _pipe(False)
        burst_on, b_on = await _pipe(True)
        try:
            await burst_off(1024)  # warm: codec, cache, deliver path
            await burst_on(1024)
            # small bursts = fine-grained pairing: host-load drift on this
            # shared core moves ±10% between half-second windows, so the
            # pair must fit well inside one
            per = 256
            pairs = []
            done = 0
            while done < msgs:
                # order-symmetric QUAD (off,on,on,off): each condition runs
                # once in each position, and taking the min of its two
                # bursts filters one-sided load spikes before the ratio is
                # formed — the estimator that finally resolves a ~1-2%
                # effect under this host's ±10% half-second drift
                t_off1 = await burst_off(per)
                t_on1 = await burst_on(per)
                t_on2 = await burst_on(per)
                t_off2 = await burst_off(per)
                pairs.append((min(t_off1, t_off2), min(t_on1, t_on2)))
                done += 2 * per
            med_ratio = float(np.median([tn / tf for tf, tn in pairs]))
            best_off = min(tf for tf, _ in pairs)
            tps_off = per / best_off
            return tps_off, tps_off / med_ratio, b_on.ctx.telemetry
        finally:
            await b_off.stop()
            await b_on.stop()

    tps_off, tps_on, tele_on = asyncio.run(_measure())
    overhead = (tps_off - tps_on) / tps_off

    # substrate microbench: one clock pair + one fast-recorder call
    sub_tele = Telemetry(enabled=True)
    rec = sub_tele.recorder("publish.e2e")
    pcns = time.perf_counter_ns
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        ts = pcns()
        rec(pcns() - ts)
    per_record_ns = (time.perf_counter() - t0) / n * 1e9

    res = {
        "name": name,
        "path": "broker_e2e_qos0_pipe",
        "msgs_per_window": msgs,
        "msgs_per_sec_off": round(tps_off, 1),
        "msgs_per_sec_on": round(tps_on, 1),
        # may be slightly negative (noise floor); the bound is one-sided
        "overhead_pct": round(100.0 * overhead, 2),
        "target_overhead_pct": 3.0,
        "substrate_ns_per_record": round(per_record_ns, 1),
        "latency_ms": {
            "match_p50": tele_on.p_ms("routing.match", 0.50),
            "match_p99": tele_on.p_ms("routing.match", 0.99),
            "e2e_p50": tele_on.p_ms("publish.e2e", 0.50),
            "e2e_p99": tele_on.p_ms("publish.e2e", 0.99),
        },
        "samples": tele_on.hist("publish.e2e").count,
    }
    log(f"[{name}] broker pipe: off {tps_off:.0f} vs on {tps_on:.0f} msg/s "
        f"→ overhead {res['overhead_pct']:.2f}% "
        f"(substrate {per_record_ns:.0f}ns/record) | e2e p50 "
        f"{res['latency_ms']['e2e_p50']}ms p99 {res['latency_ms']['e2e_p99']}ms")
    return res


def run_overload_config(name, rng):
    """Config 8: overload soak (broker/overload.py) — a QoS0 publisher
    outruns a paced subscriber 10:1 through a real broker, controller OFF
    vs ON.

    OFF: the slow consumer's deliver queue grows toward its (large) cap for
    the whole soak, and the surviving traffic's e2e latency is dominated by
    queue dwell — the throughput-cliff shape the edge-broker benchmark
    study attributes to unmanaged queue growth. ON: the watermark machine
    trips ELEVATED, QoS0 to the backlogged consumer is shed at the slow-
    consumer fraction, the queue stays pinned near the shed threshold, and
    delivered messages keep a bounded p99. Records goodput, shed counts by
    reason, peak queue depth and delivered-traffic p50/p99 for both runs."""
    import asyncio
    import struct

    from rmqtt_tpu.broker.codec import MqttCodec, packets as pk
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
    from rmqtt_tpu.broker.fitter import FitterConfig
    from rmqtt_tpu.broker.server import MqttBroker

    pub_rate = 2000  # publisher msgs/s
    sub_rate = pub_rate / 10.0  # subscriber paced 10:1 behind
    soak_s = 6.0
    mqueue = 10_000  # large cap: OFF-run growth is visible, not clipped early
    # ~1KB frames: the 10:1 deficit (several MB over the soak) must exceed
    # what kernel socket buffers can absorb, or the backlog never reaches
    # the broker's deliver queue and the controller has nothing to bound
    pad = b"p" * 1016

    async def _connect(port, cid, rcvbuf=None):
        import socket as _s

        sk = _s.socket()
        if rcvbuf:
            # shrink the client's receive window BEFORE connect: kernel
            # socket buffers otherwise absorb megabytes of backlog and the
            # latency under test (broker-side queue dwell) never shows
            sk.setsockopt(_s.SOL_SOCKET, _s.SO_RCVBUF, rcvbuf)
        sk.setblocking(False)
        await asyncio.get_running_loop().sock_connect(sk, ("127.0.0.1", port))
        reader, writer = await asyncio.open_connection(sock=sk)
        codec = MqttCodec(pk.V311)
        writer.write(codec.encode(pk.Connect(client_id=cid, keepalive=600)))
        await writer.drain()
        while True:
            data = await reader.read(4096)
            if not data:
                raise ConnectionError("no CONNACK")
            if codec.feed(data):
                return reader, writer, codec

    async def soak(enable):
        kw = dict(port=0, fitter=FitterConfig(max_mqueue=mqueue, max_inflight=64))
        if enable:
            kw.update(
                overload_enable=True, overload_sample_interval=0.05,
                # aggregate occupancy over ~2 sessions * 10k cap: ELEVATED
                # once the sub's backlog passes ~80 items. The watermark sits
                # BELOW the shed floor (100 items = 0.005 occupancy), so while
                # shedding holds the queue at the floor the state stays
                # pinned ELEVATED instead of flapping through its clear band
                overload_mqueue_elevated=0.004, overload_mqueue_critical=0.9,
                overload_shed_slow_fraction=0.01,  # slow = >100 queued
                overload_hold=2,
            )
        b = MqttBroker(ServerContext(BrokerConfig(**kw)))
        await b.start()
        sid = f"c8-sub-{enable}"
        sr, sw, sc = await _connect(b.port, sid, rcvbuf=32 * 1024)
        sw.write(sc.encode(pk.Subscribe(1, [("ov8/#", pk.SubOpts(qos=0))])))
        await sw.drain()
        # shrink the broker→subscriber send buffer too (same for both runs):
        # the backlog must land in the broker's deliver queue, the thing the
        # controller manages, not in invisible kernel buffering
        import socket as _s

        srv = b.ctx.registry.get(sid)
        srv_sock = srv.state.writer.get_extra_info("socket")
        if srv_sock is not None:
            srv_sock.setsockopt(_s.SOL_SOCKET, _s.SO_SNDBUF, 32 * 1024)
        pr, pw, pcodec = await _connect(b.port, f"c8-pub-{enable}")
        lat = []
        received = [0]
        peak_q = [0]
        stop = asyncio.Event()

        async def sub_loop():
            # paced consumer: sleep per processed publish → TCP backpressure
            # stalls the broker's deliver loop, the 10:1 deficit lands in
            # the broker-side deliver queue (the scenario under test)
            while not stop.is_set():
                try:
                    data = await asyncio.wait_for(sr.read(4096), 0.25)
                except asyncio.TimeoutError:
                    continue
                if not data:
                    return
                n = 0
                now = time.perf_counter()
                for p in sc.feed(data):
                    if isinstance(p, pk.Publish):
                        lat.append(now - struct.unpack("d", p.payload[:8])[0])
                        n += 1
                if n:
                    received[0] += n
                    await asyncio.sleep(n / sub_rate)

        async def sampler():
            while not stop.is_set():
                s = b.ctx.registry.get(sid)
                if s is not None:
                    peak_q[0] = max(peak_q[0], len(s.deliver_queue))
                await asyncio.sleep(0.05)

        tasks = [asyncio.get_running_loop().create_task(sub_loop()),
                 asyncio.get_running_loop().create_task(sampler())]
        sent = 0
        burst = 20
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < soak_s:
            for _ in range(burst):
                payload = struct.pack("d", time.perf_counter()) + pad
                pw.write(pcodec.encode(pk.Publish(topic="ov8/t", payload=payload)))
            sent += burst
            await pw.drain()
            await asyncio.sleep(burst / pub_rate)
        elapsed = time.perf_counter() - t0
        await asyncio.sleep(0.5)  # grace: in-flight deliveries land
        stop.set()
        for t in tasks:
            t.cancel()
        for t in tasks:
            try:
                await t
            except (asyncio.CancelledError, Exception):
                pass
        m = b.ctx.metrics.to_json()
        ctrl = b.ctx.overload
        res = {
            "sent": sent,
            "received": received[0],
            "goodput_msgs_per_sec": round(received[0] / elapsed, 1),
            "peak_sub_queue_depth": peak_q[0],
            "delivered_p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 1) if lat else None,
            "delivered_p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 1) if lat else None,
            "dropped_by_reason": {
                k[len("messages.dropped."):]: v for k, v in m.items()
                if k.startswith("messages.dropped.")
            },
            "dropped_total": m.get("messages.dropped", 0),
            "overload_state_final": ctrl.state.name,
            "overload_transitions": ctrl.transitions,
        }
        for w in (sw, pw):
            try:
                w.close()
            except Exception:
                pass
        await b.stop()
        return res

    off = asyncio.run(soak(False))
    on = asyncio.run(soak(True))
    res = {
        "name": name,
        "pub_rate": pub_rate,
        "sub_rate": sub_rate,
        "soak_s": soak_s,
        "max_mqueue": mqueue,
        "controller_off": off,
        "controller_on": on,
        # the two acceptance numbers: ON bounds the backlog (memory) and
        # the surviving traffic's tail where OFF lets both grow all soak
        "queue_depth_ratio_off_over_on": round(
            off["peak_sub_queue_depth"] / max(1, on["peak_sub_queue_depth"]), 2),
        "p99_ratio_off_over_on": round(
            (off["delivered_p99_ms"] or 0) / max(0.001, on["delivered_p99_ms"] or 0.001), 2),
    }
    log(f"[{name}] OFF: peak queue {off['peak_sub_queue_depth']} "
        f"p99 {off['delivered_p99_ms']}ms goodput {off['goodput_msgs_per_sec']}/s | "
        f"ON: peak queue {on['peak_sub_queue_depth']} "
        f"p99 {on['delivered_p99_ms']}ms goodput {on['goodput_msgs_per_sec']}/s "
        f"shed {on['dropped_by_reason'].get('shed_qos0', 0)} "
        f"→ queue ratio {res['queue_depth_ratio_off_over_on']}x, "
        f"p99 ratio {res['p99_ratio_off_over_on']}x")
    return res


def run_churn_config(name, rng):
    """Config 9: churn soak — sustained subscribe/unsubscribe concurrent
    with the cfg3 publish mix through the partitioned matcher.

    Three legs:
      free   — no churn: the baseline match p50/p99;
      churn  — K mutations between every batch, DELTA refresh (the
               tentpole): per-mutation upload bytes must be O(dirty
               chunks), and p99 must hold within ~2x of the free leg;
      full   — same churn with delta uploads disabled: every mutation
               costs a full table repack + re-upload (the pre-delta
               cliff this PR removes), measured for the comparison.
    Emits upload_bytes_per_mutation + the delta-vs-full reduction factor
    into the bench JSON (acceptance: ≥10x at the bench table size)."""
    from rmqtt_tpu.ops.partitioned import PartitionedMatcher, pack_device_rows

    n, nt, bs = 100_000, 6_144, 1024
    muts_per_batch = 16
    filters = gen_mixed(rng, n)
    topics = gen_topics_uniform(rng, nt)
    log(f"[{name}] {n} subs, churn {muts_per_batch} ops/batch, batch {bs}")
    table, fids = build_tpu_table(filters, "partitioned")
    matcher = make_matcher(table)
    # a reserve of fresh filters so churn adds are as varied as the table
    fset = set(filters)
    reserve = [f for f in gen_mixed(rng, n // 10) if f not in fset]
    # live fid pool for O(1) random removal (swap-pop) — a list(fids) per
    # mutation would put O(table) host work inside the measured loop
    fid_pool = list(fids)
    batches = [topics[i : i + bs] for i in range(0, len(topics), bs)]
    batches = [b for b in batches if len(b) == bs]

    def _measure(leg_batches, mutate):
        lat = []
        mutations = 0
        bytes0 = matcher.upload_bytes
        t0 = time.perf_counter()
        for b in leg_batches:
            mutations += mutate()
            t1 = time.perf_counter()
            matcher.match(b)
            lat.append(time.perf_counter() - t1)
        wall = time.perf_counter() - t0
        lat.sort()
        return {
            "p50_ms": round(lat[len(lat) // 2] * 1e3, 2),
            "p99_ms": round(lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3, 2),
            "topics_per_sec": round(len(leg_batches) * bs / wall, 1),
            "mutations": mutations,
            "mutation_rate_per_sec": round(mutations / wall, 1),
            "upload_bytes": matcher.upload_bytes - bytes0,
        }

    def _paired_measure(leg_batches, mutate):
        """Interleaved (churned, churn-free) matches in ONE window, order
        alternating per pair — cfg7's order-symmetric estimator: a host-
        noise stall lands on both series equally, so the churn-vs-free
        ratio reflects churn cost, not scheduler luck. The churned match
        runs right after `mutate()` (pending delta refresh); its partner
        sees a clean table."""
        lf: list = []
        lc: list = []
        ratios = []
        mutations = 0
        bytes0 = matcher.upload_bytes
        t0 = time.perf_counter()
        for i, b in enumerate(leg_batches):
            def one(lat_list, mut):
                nonlocal mutations
                if mut:
                    mutations += mutate()
                t1 = time.perf_counter()
                matcher.match(b)
                lat_list.append(time.perf_counter() - t1)
            if i % 2:
                one(lf, False)
                one(lc, True)
            else:
                one(lc, True)
                one(lf, False)
            ratios.append(lc[-1] / max(1e-9, lf[-1]))
        wall = time.perf_counter() - t0
        lf.sort()
        lc.sort()
        ratios.sort()

        def p(lat, q):
            return round(lat[min(len(lat) - 1, int(len(lat) * q))] * 1e3, 2)

        return {
            "free_p50_ms": p(lf, 0.5), "free_p99_ms": p(lf, 0.99),
            "p50_ms": p(lc, 0.5), "p99_ms": p(lc, 0.99),
            "median_pair_ratio": round(ratios[len(ratios) // 2], 2),
            "topics_per_sec": round(2 * len(leg_batches) * bs / wall, 1),
            "mutations": mutations,
            "mutation_rate_per_sec": round(mutations / wall, 1),
            "upload_bytes": matcher.upload_bytes - bytes0,
        }

    def no_churn():
        return 0

    def churn():
        k = 0
        for _ in range(muts_per_batch // 2):
            if reserve:
                f = reserve.pop()
                fid_pool.append(table.add(f))
                fids[fid_pool[-1]] = f
                k += 1
            i = rng.randrange(len(fid_pool))
            fid_pool[i], fid_pool[-1] = fid_pool[-1], fid_pool[i]
            fid = fid_pool.pop()
            table.remove(fid)
            reserve.append(fids.pop(fid))
            k += 1
        return k

    # warmup (compile) then the three legs on the same table
    for b in batches[:2]:
        matcher.match(b)
    loop_batches = batches[2:]
    while len(loop_batches) < 32:  # p99 over a handful of batches is noise
        loop_batches = loop_batches + batches[2:]
    free = _measure(loop_batches, no_churn)
    # a few churned warm batches absorb the NC-regrowth recompiles (the
    # sticky candidate-count cap crosses pow2 tiers as churn adds chunks)
    # so the churn leg's p99 measures churn, not one-off jit flips
    for wb in loop_batches[:4]:
        churn()
        matcher.match(wb)
    d0, f0, c0 = matcher.delta_uploads, matcher.full_uploads, table.compactions
    churn_res = _paired_measure(loop_batches, churn)
    churn_res["delta_uploads"] = matcher.delta_uploads - d0
    churn_res["full_uploads"] = matcher.full_uploads - f0
    churn_res["compactions"] = table.compactions - c0
    full_table_bytes = pack_device_rows(table).nbytes
    per_mut = churn_res["upload_bytes"] / max(1, churn_res["mutations"])
    churn_res["upload_bytes_per_mutation"] = round(per_mut, 1)
    # the pre-delta cliff: disable delta uploads, every mutation → full
    # repack + upload (fewer batches — it is exactly as slow as it sounds)
    matcher.delta_enabled = False
    churn()
    matcher.match(loop_batches[0])
    cliff = _measure(loop_batches[: max(4, len(loop_batches) // 4)], churn)
    cliff["upload_bytes_per_mutation"] = round(
        cliff["upload_bytes"] / max(1, cliff["mutations"]), 1)
    matcher.delta_enabled = True
    res = {
        "name": name,
        "table_size": len(fids),
        "full_table_bytes": full_table_bytes,
        "free": free,
        "churn_delta": churn_res,
        "churn_full_refresh": cliff,
        "upload_bytes_per_mutation": churn_res["upload_bytes_per_mutation"],
        "delta_reduction_x": round(
            cliff["upload_bytes_per_mutation"]
            / max(1.0, churn_res["upload_bytes_per_mutation"]), 1),
        # within-window comparison (the paired leg's own free series), so
        # host-load drift between legs can't fake or mask a cliff
        "p99_churn_over_free": round(
            churn_res["p99_ms"] / max(0.001, churn_res["free_p99_ms"]), 2),
        "median_pair_ratio": churn_res["median_pair_ratio"],
        "p99_full_over_free": round(
            cliff["p99_ms"] / max(0.001, free["p99_ms"]), 2),
    }
    log(f"[{name}] free p99 {free['p99_ms']}ms | churn(delta) p99 "
        f"{churn_res['p99_ms']}ms ({res['p99_churn_over_free']}x in-window, "
        f"median pair ratio {churn_res['median_pair_ratio']}x) "
        f"{churn_res['upload_bytes_per_mutation']}B/mutation | "
        f"churn(full) p99 {cliff['p99_ms']}ms ({res['p99_full_over_free']}x) "
        f"{cliff['upload_bytes_per_mutation']}B/mutation → "
        f"{res['delta_reduction_x']}x less upload traffic")
    return res


def run_smallbatch_config(name, rng):
    """Config 11: the cfg1 small-batch regime, attributable PER STAGE.

    cfg1's standing 0.06x on chip is a single ratio — it cannot say whether
    the loss sits in host encode, device dispatch, result fetch or host
    decode. This config drives MICRO-batches (16 topics, the cfg1 shape)
    through two matchers over ONE table — the fused match→compact→decode
    pipeline vs the unfused words+host-decode path — as cfg7-style
    order-symmetric pairs (order alternates per pair, so a host-noise
    stall lands on both legs equally), with ``stage_timing`` accumulating
    encode/dispatch/fetch/decode wall ns inside each matcher. Emits
    per-leg p50/p99, per-stage shares, and the fused/unfused median pair
    ratio: the DECODE share collapsing on the fused leg is the acceptance
    evidence that host decode left the per-batch path."""
    import os

    from rmqtt_tpu.ops.partitioned import PartitionedMatcher

    n, pairs, bs = 1000, 96, 16
    filters = gen_exact(rng, n)
    # cfg1 shape: ~50% of publishes hit a subscribed topic
    topics = [rng.choice(filters) if rng.random() < 0.5
              else _tree_topic(rng, 4) for _ in range(pairs * bs)]
    log(f"[{name}] {n} subs, {pairs} pairs of micro-batches of {bs}")
    table, fids = build_tpu_table(filters, "partitioned")
    m_fused = PartitionedMatcher(table)
    prior = os.environ.get("RMQTT_FUSED")
    os.environ["RMQTT_FUSED"] = "0"
    try:
        m_plain = PartitionedMatcher(table)
    finally:
        if prior is None:
            os.environ.pop("RMQTT_FUSED", None)
        else:
            os.environ["RMQTT_FUSED"] = prior
    batches = [topics[i: i + bs] for i in range(0, len(topics), bs)]
    batches = [b for b in batches if len(b) == bs]
    for m in (m_fused, m_plain):  # warmup/compile + fused verify
        m.match(batches[0])
        m.match(batches[1])
        m.prewarm(bs)
        m.stage_timing = True

    lat = {"fused": [], "unfused": []}
    ratios = []
    t0 = time.perf_counter()
    for i, b in enumerate(batches):
        def one(m, key):
            t1 = time.perf_counter()
            m.match(b)
            lat[key].append(time.perf_counter() - t1)
        if i % 2:
            one(m_plain, "unfused")
            one(m_fused, "fused")
        else:
            one(m_fused, "fused")
            one(m_plain, "unfused")
        ratios.append(lat["fused"][-1] / max(1e-9, lat["unfused"][-1]))
    wall = time.perf_counter() - t0
    ratios.sort()

    def leg(key, m):
        ls = sorted(lat[key])
        total = max(1, sum(m.stage_ns.values()))
        return {
            "p50_ms": round(ls[len(ls) // 2] * 1e3, 3),
            "p99_ms": round(ls[min(len(ls) - 1, int(len(ls) * 0.99))] * 1e3, 3),
            "stage_ms": {k: round(v / 1e6, 2) for k, v in m.stage_ns.items()},
            "stage_share": {k: round(v / total, 4)
                            for k, v in m.stage_ns.items()},
        }

    res = {
        "name": name,
        "table_size": len(fids),
        "micro_batch": bs,
        "pairs": len(batches),
        "topics_per_sec": round(2 * len(batches) * bs / wall, 1),
        "fused_verified": m_fused._fused is True,
        "fused": leg("fused", m_fused),
        "unfused": leg("unfused", m_plain),
        "median_pair_ratio": round(ratios[len(ratios) // 2], 3),
        "decode_share_unfused": leg("unfused", m_plain)["stage_share"]["decode"],
        "decode_share_fused": leg("fused", m_fused)["stage_share"]["decode"],
    }
    log(f"[{name}] fused p50 {res['fused']['p50_ms']}ms vs unfused "
        f"{res['unfused']['p50_ms']}ms (median pair ratio "
        f"{res['median_pair_ratio']}x) | decode share "
        f"{res['decode_share_unfused']:.1%} → {res['decode_share_fused']:.1%}")
    return res


def run_devprof_overhead_config(name, rng):
    """Config 12: device-profiler overhead, cfg7-style order-symmetric
    paired estimator.

    Same matcher, same batches; leg A runs with ``device_profile`` ON
    (the global DEVPROF registry + flight ring + the matcher's
    stage_timing — exactly what the [observability] knob enables), leg B
    with both off. Order alternates per pair so a host-noise stall lands
    on both legs equally; the median pair ratio bounds the enabled cost.
    The profiler adds only host work (no new jit signatures), so one
    warmup covers both legs. Acceptance: overhead ≤ 2% — a standalone
    ``--config 12`` run exits nonzero past the bound so CI can gate on it."""
    from rmqtt_tpu.broker.devprof import DEVPROF
    from rmqtt_tpu.broker.telemetry import Telemetry

    n, pairs, bs = 50_000, 192, 512
    filters = gen_mixed(rng, n)
    # batches draw from a BOUNDED topic pool and every batch is warmed
    # once below: the first match of a fresh batch pays candidate-cache
    # misses (~20x the steady encode), which would otherwise land on
    # whichever leg runs first and swamp the profiler cost being measured
    pool = gen_topics_uniform(rng, 4096)
    log(f"[{name}] {n} subs, {pairs} pairs of batches of {bs}")
    table, fids = build_tpu_table(filters, "partitioned")
    matcher = make_matcher(table)
    batches = [[pool[rng.randrange(len(pool))] for _ in range(bs)]
               for _ in range(pairs)]
    prior_enabled = DEVPROF.enabled
    prior_tele = DEVPROF.telemetry
    # a throwaway telemetry registry so storm/floor annotations (if any)
    # pay their real cost without touching the process-global slow ring
    DEVPROF.configure(enabled=True, telemetry=Telemetry(enabled=True))
    try:
        for b in batches:  # compile + warm every batch's candidate sets
            matcher.match(b)
        lat = {"on": [], "off": []}
        ratios = []
        t0 = time.perf_counter()
        for i, b in enumerate(batches):
            def one(key, enabled):
                DEVPROF.enabled = enabled
                matcher.stage_timing = enabled
                t1 = time.perf_counter()
                matcher.match(b)
                lat[key].append(time.perf_counter() - t1)
            if i % 2:
                one("off", False)
                one("on", True)
            else:
                one("on", True)
                one("off", False)
            ratios.append(lat["on"][-1] / max(1e-9, lat["off"][-1]))
        wall = time.perf_counter() - t0
    finally:
        DEVPROF.configure(enabled=prior_enabled, telemetry=prior_tele)
        matcher.stage_timing = False
    ratios.sort()

    def p(key, q):
        ls = sorted(lat[key])
        return round(ls[min(len(ls) - 1, int(len(ls) * q))] * 1e3, 3)

    median_ratio = ratios[len(ratios) // 2]
    overhead_pct = round((median_ratio - 1.0) * 100.0, 2)
    res = {
        "name": name,
        "table_size": len(fids),
        "batch": bs,
        "pairs": len(batches),
        "topics_per_sec": round(2 * len(batches) * bs / wall, 1),
        "on_p50_ms": p("on", 0.5), "on_p99_ms": p("on", 0.99),
        "off_p50_ms": p("off", 0.5), "off_p99_ms": p("off", 0.99),
        "median_pair_ratio": round(median_ratio, 4),
        "overhead_pct": overhead_pct,
        "bound_pct": 2.0,
        "ok": overhead_pct <= 2.0,
    }
    log(f"[{name}] profiler ON p50 {res['on_p50_ms']}ms vs OFF "
        f"{res['off_p50_ms']}ms (median pair ratio {res['median_pair_ratio']}x"
        f" = {overhead_pct}% overhead, bound 2%) → "
        f"{'OK' if res['ok'] else 'FAIL'}")
    return res


def run_hostprof_overhead_config(name, rng):
    """Config 14: host-plane profiler overhead (broker/hostprof.py) on the
    REAL publish path, cfg7-style order-symmetric paired estimator.

    One live broker pipe (real sockets, the deployed RoutingService); the
    profiler is ARMED (sampler task + gc callbacks + watchdog thread —
    exactly what ``[observability] host_profile`` enables) for the ON
    bursts and fully DISARMED for the OFF bursts. HOSTPROF is
    process-global and the loop is shared, so unlike cfg7 the conditions
    cannot run as two live brokers — per-burst arm/disarm on one pipe is
    the honest design (the profiler's cost IS its background wakeups +
    per-collection gc callback, and those run during the armed bursts).
    Quads (off,on,on,off) with min-of-two per condition filter one-sided
    host-load spikes; the median pair ratio bounds the enabled cost at
    ≤2% of e2e p50 burst time (standalone ``--config 14`` exits 1 past
    the bound so CI can gate on it)."""
    import asyncio

    from rmqtt_tpu.broker.codec import MqttCodec, packets as pk
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
    from rmqtt_tpu.broker.hostprof import HOSTPROF
    from rmqtt_tpu.broker.server import MqttBroker

    msgs = 15_000
    ntopics = 64
    payload = b"x" * 64

    async def _read_until(reader, codec, ptype):
        while True:
            data = await reader.read(4096)
            if not data:
                raise ConnectionError(f"peer closed before {ptype.__name__}")
            for p in codec.feed(data):
                if isinstance(p, ptype):
                    return p

    async def _connect(port, cid):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        codec = MqttCodec()
        writer.write(codec.encode(pk.Connect(client_id=cid, keepalive=600)))
        await writer.drain()
        await _read_until(reader, codec, pk.Connack)
        return reader, writer, codec

    async def _measure():
        # host_profile=False at construction: the bench owns arm/disarm
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, host_profile=False, allow_anonymous=True)))
        await b.start()
        sr, sw, scodec = await _connect(b.port, "c14-sub")
        sw.write(scodec.encode(pk.Subscribe(1, [("bench/#", pk.SubOpts(qos=0))])))
        await sw.drain()
        await _read_until(sr, scodec, pk.Suback)
        _pr, pw, pcodec = await _connect(b.port, "c14-pub")
        frames = [pcodec.encode(pk.Publish(
            topic=f"bench/t{i}", payload=payload, qos=0))
            for i in range(ntopics)]

        async def burst(n):
            t0 = time.perf_counter()
            sent = got = 0
            deadline = time.monotonic() + 60.0
            while sent < n:
                k = min(64, n - sent)
                pw.write(b"".join(
                    frames[(sent + j) % ntopics] for j in range(k)))
                sent += k
                if pw.transport.get_write_buffer_size() > 1 << 18:
                    await pw.drain()
                while got < sent - 2048:
                    data = await asyncio.wait_for(
                        sr.read(1 << 16), deadline - time.monotonic())
                    if not data:
                        raise ConnectionError("subscriber closed")
                    got += sum(1 for p in scodec.feed(data)
                               if isinstance(p, pk.Publish))
            await pw.drain()
            while got < sent:
                data = await asyncio.wait_for(
                    sr.read(1 << 16), deadline - time.monotonic())
                if not data:
                    raise ConnectionError("subscriber closed")
                got += sum(1 for p in scodec.feed(data)
                           if isinstance(p, pk.Publish))
            return time.perf_counter() - t0

        def arm():
            HOSTPROF.configure(enabled=True, dump_dir=None,
                               telemetry=b.ctx.telemetry)
            HOSTPROF.start()

        async def disarm():
            await HOSTPROF.stop()
            HOSTPROF.configure(enabled=False)

        prior_enabled = HOSTPROF.enabled
        try:
            await burst(1024)  # warm: codec, cache, deliver path
            arm()
            await burst(1024)
            await disarm()
            per = 256
            pairs = []
            done = 0
            while done < msgs:
                t_off1 = await burst(per)
                arm()
                t_on1 = await burst(per)
                t_on2 = await burst(per)
                await disarm()
                t_off2 = await burst(per)
                pairs.append((min(t_off1, t_off2), min(t_on1, t_on2)))
                done += 2 * per
            med_ratio = float(np.median([tn / tf for tf, tn in pairs]))
            best_off = min(tf for tf, _ in pairs)
            tele = b.ctx.telemetry
            lat = {"e2e_p50": tele.p_ms("publish.e2e", 0.50),
                   "e2e_p99": tele.p_ms("publish.e2e", 0.99)}
            return per / best_off, med_ratio, lat
        finally:
            await HOSTPROF.stop()
            HOSTPROF.configure(enabled=prior_enabled)
            await b.stop()

    tps_off, med_ratio, lat = asyncio.run(_measure())
    overhead_pct = round((med_ratio - 1.0) * 100.0, 2)
    res = {
        "name": name,
        "path": "broker_e2e_qos0_pipe",
        "msgs_per_window": msgs,
        "msgs_per_sec_off": round(tps_off, 1),
        "msgs_per_sec_on": round(tps_off / med_ratio, 1),
        "median_pair_ratio": round(med_ratio, 4),
        "overhead_pct": overhead_pct,
        "bound_pct": 2.0,
        "ok": overhead_pct <= 2.0,
        "latency_ms": lat,
    }
    log(f"[{name}] host profiler OFF {tps_off:.0f} msg/s, median pair "
        f"ratio {res['median_pair_ratio']}x = {overhead_pct}% overhead "
        f"(bound 2%) | e2e p50 {lat['e2e_p50']}ms → "
        f"{'OK' if res['ok'] else 'FAIL'}")
    return res


def run_history_overhead_config(name, rng):
    """Config 17: telemetry-history collector overhead (broker/history.py)
    on the REAL publish path, cfg14-style order-symmetric paired estimator.

    One live broker pipe; the history collector is ARMED (periodic
    cross-plane ``collect_once`` samples + EWMA/MAD anomaly pass —
    exactly what ``[observability] history`` enables, memory-only like
    the default ``history_dir=\"\"`` deployment) for the ON bursts and
    fully stopped for the OFF bursts. The collector runs at a 250 ms
    cadence here — 20× the 5 s production default — and ``_run``
    samples at tick START, so every armed window contains at least one
    real collection and the measured bound is a deliberate upper
    estimate of the deployed cost. Quads (off,on,on,off) with
    min-of-two per condition filter one-sided host spikes; the median
    pair ratio bounds the enabled cost at ≤2% of e2e burst time
    (standalone ``--config 17`` exits 1 past the bound so CI can gate
    on it)."""
    import asyncio

    from rmqtt_tpu.broker.codec import MqttCodec, packets as pk
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
    from rmqtt_tpu.broker.server import MqttBroker

    msgs = 15_000
    ntopics = 64
    payload = b"x" * 64

    async def _read_until(reader, codec, ptype):
        while True:
            data = await reader.read(4096)
            if not data:
                raise ConnectionError(f"peer closed before {ptype.__name__}")
            for p in codec.feed(data):
                if isinstance(p, ptype):
                    return p

    async def _connect(port, cid):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        codec = MqttCodec()
        writer.write(codec.encode(pk.Connect(client_id=cid, keepalive=600)))
        await writer.drain()
        await _read_until(reader, codec, pk.Connack)
        return reader, writer, codec

    async def _measure():
        # history=False at construction: the bench owns arm/disarm
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, history_enable=False, allow_anonymous=True)))
        await b.start()
        hist = b.ctx.history
        sr, sw, scodec = await _connect(b.port, "c17-sub")
        sw.write(scodec.encode(pk.Subscribe(1, [("bench/#", pk.SubOpts(qos=0))])))
        await sw.drain()
        await _read_until(sr, scodec, pk.Suback)
        _pr, pw, pcodec = await _connect(b.port, "c17-pub")
        frames = [pcodec.encode(pk.Publish(
            topic=f"bench/t{i}", payload=payload, qos=0))
            for i in range(ntopics)]

        async def burst(n):
            t0 = time.perf_counter()
            sent = got = 0
            deadline = time.monotonic() + 60.0
            while sent < n:
                k = min(64, n - sent)
                pw.write(b"".join(
                    frames[(sent + j) % ntopics] for j in range(k)))
                sent += k
                if pw.transport.get_write_buffer_size() > 1 << 18:
                    await pw.drain()
                while got < sent - 2048:
                    data = await asyncio.wait_for(
                        sr.read(1 << 16), deadline - time.monotonic())
                    if not data:
                        raise ConnectionError("subscriber closed")
                    got += sum(1 for p in scodec.feed(data)
                               if isinstance(p, pk.Publish))
            await pw.drain()
            while got < sent:
                data = await asyncio.wait_for(
                    sr.read(1 << 16), deadline - time.monotonic())
                if not data:
                    raise ConnectionError("subscriber closed")
                got += sum(1 for p in scodec.feed(data)
                           if isinstance(p, pk.Publish))
            return time.perf_counter() - t0

        def arm():
            hist.enabled = True
            hist.interval_s = 0.25  # 20× production cadence: upper bound
            hist.start()

        async def disarm():
            await hist.stop()
            hist.enabled = False

        try:
            await burst(1024)  # warm: codec, cache, deliver path
            arm()
            await burst(1024)
            await disarm()
            # 512-msg windows: long enough that one collection amortizes
            # to its steady-state share, short enough for ~15 pairs
            per = 512
            pairs = []
            done = 0
            while done < msgs:
                t_off1 = await burst(per)
                arm()
                t_on1 = await burst(per)
                t_on2 = await burst(per)
                await disarm()
                t_off2 = await burst(per)
                pairs.append((min(t_off1, t_off2), min(t_on1, t_on2)))
                done += 2 * per
            med_ratio = float(np.median([tn / tf for tf, tn in pairs]))
            best_off = min(tf for tf, _ in pairs)
            tele = b.ctx.telemetry
            lat = {"e2e_p50": tele.p_ms("publish.e2e", 0.50),
                   "e2e_p99": tele.p_ms("publish.e2e", 0.99)}
            return per / best_off, med_ratio, lat, len(hist.ring)
        finally:
            await hist.stop()
            hist.enabled = False
            await b.stop()

    tps_off, med_ratio, lat, samples = asyncio.run(_measure())
    overhead_pct = round((med_ratio - 1.0) * 100.0, 2)
    res = {
        "name": name,
        "path": "broker_e2e_qos0_pipe",
        "msgs_per_window": msgs,
        "msgs_per_sec_off": round(tps_off, 1),
        "msgs_per_sec_on": round(tps_off / med_ratio, 1),
        "median_pair_ratio": round(med_ratio, 4),
        "overhead_pct": overhead_pct,
        "bound_pct": 2.0,
        "ok": overhead_pct <= 2.0,
        # samples actually taken during the armed windows: the ON legs
        # measured a collector that really fired, not an idle task
        "samples_recorded": samples,
        "collector_interval_s": 0.25,
        "latency_ms": lat,
    }
    log(f"[{name}] history collector OFF {tps_off:.0f} msg/s, median pair "
        f"ratio {res['median_pair_ratio']}x = {overhead_pct}% overhead "
        f"(bound 2%, {samples} samples) | e2e p50 {lat['e2e_p50']}ms → "
        f"{'OK' if res['ok'] else 'FAIL'}")
    return res


def run_hotkeys_overhead_config(name, rng):
    """Config 18: hot-key attribution sketch overhead (broker/hotkeys.py)
    on the REAL publish path, cfg17-style order-symmetric paired estimator.

    One live broker pipe; the hot-key plane is ARMED (per-publish
    Space-Saving + Count-Min offers across all six key spaces, the
    per-dispatch prefix seam, the per-deliver subscriber seam, plus the
    live rotation/alert task — exactly what ``[observability] hotkeys``
    enables) for the ON bursts and fully disarmed (``enabled=False`` +
    routing seam nulled, the shipped-off configuration) for the OFF
    bursts. The rotation window runs at 0.5 s here — 60× the 30 s
    production default — so every armed leg contains real rotations and
    the measured bound is a deliberate upper estimate of the deployed
    cost. Quads (off,on,on,off) with min-of-two per condition filter
    one-sided host spikes; the median pair ratio bounds the enabled cost
    at ≤2% of e2e burst time (standalone ``--config 18`` exits 1 past
    the bound so CI can gate on it)."""
    import asyncio

    from rmqtt_tpu.broker.codec import MqttCodec, packets as pk
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
    from rmqtt_tpu.broker.server import MqttBroker

    msgs = 15_000
    ntopics = 64
    payload = b"x" * 64

    async def _read_until(reader, codec, ptype):
        while True:
            data = await reader.read(4096)
            if not data:
                raise ConnectionError(f"peer closed before {ptype.__name__}")
            for p in codec.feed(data):
                if isinstance(p, ptype):
                    return p

    async def _connect(port, cid):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        codec = MqttCodec()
        writer.write(codec.encode(pk.Connect(client_id=cid, keepalive=600)))
        await writer.drain()
        await _read_until(reader, codec, pk.Connack)
        return reader, writer, codec

    async def _measure():
        # hotkeys=False at construction: the bench owns arm/disarm
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, hotkeys_enable=False, history_enable=False,
            allow_anonymous=True)))
        await b.start()
        hk = b.ctx.hotkeys
        samples = 0
        sr, sw, scodec = await _connect(b.port, "c18-sub")
        sw.write(scodec.encode(pk.Subscribe(1, [("bench/#", pk.SubOpts(qos=0))])))
        await sw.drain()
        await _read_until(sr, scodec, pk.Suback)
        _pr, pw, pcodec = await _connect(b.port, "c18-pub")
        frames = [pcodec.encode(pk.Publish(
            topic=f"bench/t{i}", payload=payload, qos=0))
            for i in range(ntopics)]

        async def burst(n):
            t0 = time.perf_counter()
            sent = got = 0
            deadline = time.monotonic() + 60.0
            while sent < n:
                k = min(64, n - sent)
                pw.write(b"".join(
                    frames[(sent + j) % ntopics] for j in range(k)))
                sent += k
                if pw.transport.get_write_buffer_size() > 1 << 18:
                    await pw.drain()
                while got < sent - 2048:
                    data = await asyncio.wait_for(
                        sr.read(1 << 16), deadline - time.monotonic())
                    if not data:
                        raise ConnectionError("subscriber closed")
                    got += sum(1 for p in scodec.feed(data)
                               if isinstance(p, pk.Publish))
            await pw.drain()
            while got < sent:
                data = await asyncio.wait_for(
                    sr.read(1 << 16), deadline - time.monotonic())
                if not data:
                    raise ConnectionError("subscriber closed")
                got += sum(1 for p in scodec.feed(data)
                           if isinstance(p, pk.Publish))
            return time.perf_counter() - t0

        def arm():
            hk.enabled = True
            hk.window_s = 0.5  # 60× production cadence: rotation included
            b.ctx.routing.hotkeys = hk
            hk.start()

        async def disarm():
            nonlocal samples
            # events the armed legs actually attributed (topics space,
            # cur+prev windows): the ON legs measured sketches that
            # really recorded, not a dormant flag check
            hk.drain()
            samples += int(hk.spaces["topics"].total())
            await hk.stop()
            hk.enabled = False
            b.ctx.routing.hotkeys = None

        try:
            await burst(1024)  # warm: codec, cache, deliver path
            arm()
            await burst(1024)
            await disarm()
            # 512-msg windows, same shape as cfg17: long enough that a
            # rotation amortizes, short enough for ~15 pairs
            per = 512
            pairs = []
            done = 0
            while done < msgs:
                t_off1 = await burst(per)
                arm()
                t_on1 = await burst(per)
                t_on2 = await burst(per)
                await disarm()
                t_off2 = await burst(per)
                pairs.append((min(t_off1, t_off2), min(t_on1, t_on2)))
                done += 2 * per
            med_ratio = float(np.median([tn / tf for tf, tn in pairs]))
            best_off = min(tf for tf, _ in pairs)
            tele = b.ctx.telemetry
            lat = {"e2e_p50": tele.p_ms("publish.e2e", 0.50),
                   "e2e_p99": tele.p_ms("publish.e2e", 0.99)}
            return (per / best_off, med_ratio, lat, samples,
                    int(hk.rotations))
        finally:
            await hk.stop()
            hk.enabled = False
            b.ctx.routing.hotkeys = None
            await b.stop()

    tps_off, med_ratio, lat, samples, rotations = asyncio.run(_measure())
    overhead_pct = round((med_ratio - 1.0) * 100.0, 2)
    res = {
        "name": name,
        "path": "broker_e2e_qos0_pipe",
        "msgs_per_window": msgs,
        "msgs_per_sec_off": round(tps_off, 1),
        "msgs_per_sec_on": round(tps_off / med_ratio, 1),
        "median_pair_ratio": round(med_ratio, 4),
        "overhead_pct": overhead_pct,
        "bound_pct": 2.0,
        "ok": overhead_pct <= 2.0,
        # sketch offers actually recorded during the armed windows: the
        # ON legs measured a plane that really attributed traffic
        "samples_recorded": samples,
        "rotations": rotations,
        "window_s": 0.5,
        "latency_ms": lat,
    }
    log(f"[{name}] hotkeys plane OFF {tps_off:.0f} msg/s, median pair "
        f"ratio {res['median_pair_ratio']}x = {overhead_pct}% overhead "
        f"(bound 2%, {samples} events, {rotations} rotations) | e2e p50 "
        f"{lat['e2e_p50']}ms → {'OK' if res['ok'] else 'FAIL'}")
    return res


def run_failover_config(name, rng):
    """Config 10: device-plane failover soak (broker/failover.py).

    Steady QoS1 publish load through a broker whose routing is pinned to
    the DEVICE plane; at t=2s the ``device.dispatch`` failpoint kills the
    kernel path (every batch errors), at t=4s it recovers. The failover
    plane must serve the outage from the host trie with zero lost
    publishes, then probe, force a full HBM re-upload and switch back.
    Emits the goodput dip, per-phase delivered p99 (steady vs failover vs
    post-recovery) and time-to-switchback — the regression gate for
    recovery time in future PRs."""
    import asyncio
    import struct

    from rmqtt_tpu.broker.codec import MqttCodec, packets as pk
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
    from rmqtt_tpu.broker.server import MqttBroker
    from rmqtt_tpu.utils.failpoints import FAILPOINTS

    # rate the CPU-jax device path sustains headroom-free (each batch pays
    # a jax dispatch; on a real chip this is conservative) — oversubscribing
    # here would measure deliver-queue overflow, not failover behavior
    pub_rate = 90  # msgs/s
    soak_s = 6.0
    fault_at, clear_at = 2.0, 4.0
    pad = b"f" * 56

    async def _connect(port, cid):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        codec = MqttCodec(pk.V311)
        writer.write(codec.encode(pk.Connect(client_id=cid, keepalive=600)))
        await writer.drain()
        while True:
            data = await reader.read(4096)
            if not data:
                raise ConnectionError("no CONNACK")
            if codec.feed(data):
                return reader, writer, codec

    async def soak():
        # cache off: every publish must reach the dispatcher, or cache hits
        # would mask the device outage entirely
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, router="xla", route_cache=False,
            failover_cooldown=0.3, failover_threshold=2,
            failover_k_successes=2)))
        r = b.ctx.router
        r._hybrid_max = 0  # pin every batch to the device plane
        r._hybrid.small_max = 0
        r._hybrid.probe_every = 0
        await b.start()
        sw = pw = None
        try:
            fo = b.ctx.routing.failover
            assert fo is not None and fo.usable
            sr, sw, sc = await _connect(b.port, "c10-sub")
            sw.write(sc.encode(pk.Subscribe(1, [("fo10/#", pk.SubOpts(qos=0))])))
            await sw.drain()
            pr, pw, pcodec = await _connect(b.port, "c10-pub")
            # per-phase latency + arrival counts, bucketed by SEND time
            lat = {"steady": [], "failover": [], "recovered": []}
            received = [0]
            stop = asyncio.Event()
            t0 = None

            def phase_of(sent_rel):
                if sent_rel < fault_at:
                    return "steady"
                if sent_rel < clear_at:
                    return "failover"
                return "recovered"

            async def sub_loop():
                while not stop.is_set():
                    try:
                        data = await asyncio.wait_for(sr.read(65536), 0.25)
                    except asyncio.TimeoutError:
                        continue
                    if not data:
                        return
                    now = time.perf_counter()
                    for p in sc.feed(data):
                        # warm-up publishes ride a different topic: excluded
                        # from the measured counts and latencies
                        if isinstance(p, pk.Publish) and p.topic == "fo10/t":
                            ts = struct.unpack("d", p.payload[:8])[0]
                            lat[phase_of(ts - t0)].append(now - ts)
                            received[0] += 1

            # JIT warm OUTSIDE the measured window: the measured bursts run at
            # batch≈5 (pow2-padded to 8), so warm that shape too or the first
            # measured batch pays the compile and poisons the steady p99
            for _ in range(3):
                for _ in range(5):
                    pw.write(pcodec.encode(pk.Publish(
                        topic="fo10/warm",
                        payload=struct.pack("d", time.perf_counter()) + pad)))
                await pw.drain()
                await asyncio.sleep(0.3)
            await asyncio.sleep(1.0)
            task = asyncio.get_running_loop().create_task(sub_loop())
            sent = 0
            goodput = []  # per-0.5s received buckets
            switchback_s = None
            fault_set = cleared = False
            burst = 5
            t0 = time.perf_counter()
            last_bucket, last_rx = t0, 0
            while True:
                el = time.perf_counter() - t0
                # capture BEFORE the exit checks: a switchback landing after
                # soak_s (breaker backoff pushed the probe late) would otherwise
                # break out of the loop un-recorded
                if cleared and switchback_s is None and not fo.active:
                    switchback_s = time.perf_counter() - t0 - clear_at
                if el >= soak_s and not fo.active:
                    break
                if el >= soak_s + 20:
                    break  # no switchback: report it instead of hanging
                if not fault_set and el >= fault_at:
                    FAILPOINTS.set("device.dispatch", "error")
                    fault_set = True
                if not cleared and el >= clear_at:
                    FAILPOINTS.set("device.dispatch", "off")
                    cleared = True
                if el < soak_s:
                    for _ in range(burst):
                        payload = struct.pack("d", time.perf_counter()) + pad
                        pw.write(pcodec.encode(pk.Publish(topic="fo10/t", payload=payload)))
                    sent += burst
                    await pw.drain()
                now = time.perf_counter()
                if now - last_bucket >= 0.5:
                    goodput.append((received[0] - last_rx) / (now - last_bucket))
                    last_bucket, last_rx = now, received[0]
                await asyncio.sleep(burst / pub_rate)
            await asyncio.sleep(0.5)  # grace: in-flight deliveries land
            stop.set()
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass

            def p99(xs):
                return round(float(np.percentile(xs, 99)) * 1e3, 2) if xs else None

            steady_gp = [g for g in goodput[: max(1, int(fault_at / 0.5))] if g > 0]
            fault_gp = goodput[int(fault_at / 0.5): int(clear_at / 0.5)]
            res = {
                "sent": sent,
                "received": received[0],
                "lost": sent - received[0],
                "steady_p99_ms": p99(lat["steady"]),
                "failover_p99_ms": p99(lat["failover"]),
                "recovered_p99_ms": p99(lat["recovered"]),
                "steady_goodput_msgs_per_sec": round(
                    sum(steady_gp) / max(1, len(steady_gp)), 1),
                "failover_min_goodput_msgs_per_sec": round(min(fault_gp), 1)
                if fault_gp else None,
                "time_to_switchback_s": round(switchback_s, 2)
                if switchback_s is not None else None,
                "failovers": fo.failovers,
                "switchbacks": fo.switchbacks,
                "host_routed": fo.host_items,
                "device_failures": dict(fo.failures),
                "full_uploads": getattr(b.ctx.router.matcher, "full_uploads", 0),
            }
            if res["steady_goodput_msgs_per_sec"] and res["failover_min_goodput_msgs_per_sec"]:
                res["goodput_dip_pct"] = round(
                    100.0 * (1 - res["failover_min_goodput_msgs_per_sec"]
                             / res["steady_goodput_msgs_per_sec"]), 1)
            return res
        finally:
            # a mid-soak failure must not leak the armed process-
            # global failpoint or the running broker (same
            # discipline as tests/test_stress_chaos.py)
            FAILPOINTS.clear_all()
            for w in (sw, pw):
                try:
                    if w is not None:
                        w.close()
                except Exception:
                    pass
            await b.stop()

    res = {"name": name, "pub_rate": pub_rate, "soak_s": soak_s,
           "fault_window_s": [fault_at, clear_at],
           **asyncio.run(soak())}
    log(f"[{name}] sent {res['sent']} received {res['received']} "
        f"(lost {res['lost']}) | p99 steady {res['steady_p99_ms']}ms "
        f"failover {res['failover_p99_ms']}ms recovered {res['recovered_p99_ms']}ms | "
        f"switchback in {res['time_to_switchback_s']}s "
        f"(failovers {res['failovers']}, host-routed {res['host_routed']})")
    return res


def run_fabric_config(name, rng):
    """Config 13: intra-node routing fabric vs localhost-broadcast workers,
    cfg7-style order-symmetric paired estimator.

    Two live 4-worker topologies in one process (each worker a full broker
    with its own listener — deterministic client placement, unlike
    SO_REUSEPORT kernel balancing): the FABRIC leg wires them through
    broker/fabric.py over real UDS sockets; the BROADCAST leg peers them
    as the localhost broadcast cluster `--workers` used before (real TCP
    cluster RPC). The workload is the shape ROADMAP item 2 calls out —
    cross-worker fan-out with a *placed* subscriber fleet: ``npubs``
    concurrent publishers on worker 2, the subscriber fleet on worker 4,
    QoS0 at 512-byte payloads.
    This is exactly where the architectures diverge: broadcast mode has no
    idea where subscribers live, so EVERY publish pays full cluster-RPC
    serialization against EVERY peer and a scatter-gather match on all of
    them; the fabric matches once at the owner and writes one deliver
    frame to the one worker that owns the fleet. Bursts alternate legs in
    order-symmetric quads; the ratio of per-burst goodputs is the
    artifact's ``fanout_goodput_ratio`` (target ≥ 3× at 4 workers on CPU).
    The CONNECT-takeover probe reconnects a client id across workers and
    reports per-leg kick p99 — the fabric resolves it via the directory
    (one targeted RPC), broadcast scatters a kick RPC to every peer."""
    import asyncio
    import tempfile

    from rmqtt_tpu.broker.codec import MqttCodec, packets as pk
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
    from rmqtt_tpu.broker.fitter import FitterConfig
    from rmqtt_tpu.broker.server import MqttBroker

    nworkers = 4
    nsubs = 2  # the placed fleet on worker 4
    npubs = 32  # concurrent publisher sessions on worker 2
    per = 1024  # publishes per burst (×nsubs deliveries)
    quads = 5
    kick_iters = 30

    async def _read_until(reader, codec, ptype):
        while True:
            data = await reader.read(4096)
            if not data:
                raise ConnectionError(f"peer closed before {ptype.__name__}")
            for p in codec.feed(data):
                if isinstance(p, ptype):
                    return p

    async def _connect(port, cid):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        codec = MqttCodec()
        writer.write(codec.encode(pk.Connect(client_id=cid, keepalive=600)))
        await writer.drain()
        await _read_until(reader, codec, pk.Connack)
        return reader, writer, codec

    async def _leg_fabric():
        td = tempfile.mkdtemp(prefix="cfg13-fab-")
        workers = []
        for wid in range(1, nworkers + 1):
            b = MqttBroker(ServerContext(BrokerConfig(
                port=0, node_id=wid, telemetry_enable=False,
                fitter=FitterConfig(max_mqueue=100_000),
                fabric_enable=True, fabric_dir=td, fabric_worker_id=wid,
                fabric_workers=nworkers)))
            await b.start()
            workers.append(b)
        deadline = time.monotonic() + 10
        while not all(w.ctx.fabric.is_owner or w.ctx.fabric._owner_up.is_set()
                      for w in workers):
            assert time.monotonic() < deadline, "fabric never registered"
            await asyncio.sleep(0.05)
        return workers, None

    async def _leg_broadcast():
        from rmqtt_tpu.cluster.broadcast import BroadcastCluster
        from rmqtt_tpu.cluster.transport import PeerClient

        workers, clusters = [], []
        for wid in range(1, nworkers + 1):
            b = MqttBroker(ServerContext(BrokerConfig(
                port=0, node_id=wid, telemetry_enable=False, cluster=True,
                fitter=FitterConfig(max_mqueue=100_000))))
            await b.start()
            workers.append(b)
        for b in workers:
            c = BroadcastCluster(b.ctx, ("127.0.0.1", 0), [])
            await c.start()
            clusters.append(c)
        for i, c in enumerate(clusters):
            for j, other in enumerate(clusters):
                if i != j:
                    nid = workers[j].ctx.node_id
                    c.peers[nid] = PeerClient(nid, "127.0.0.1",
                                              other.bound_port)
            c.bcast.peers = list(c.peers.values())
        return workers, clusters

    async def _wire_traffic(workers, tag):
        """The placed fleet: nsubs subscribers on worker 4 + npubs
        publishers on worker 2; → (burst fn, close fn)."""
        subs = []
        for k in range(nsubs):
            r, w, c = await _connect(workers[3].port, f"{tag}s{k}")
            w.write(c.encode(pk.Subscribe(
                1, [("fab/#", pk.SubOpts(qos=0))])))
            await w.drain()
            await _read_until(r, c, pk.Suback)
            subs.append((r, w, c))
        pubs = [await _connect(workers[1].port, f"{tag}p{k}")
                for k in range(npubs)]
        frames = [pubs[0][2].encode(pk.Publish(
            topic=f"fab/t{i}", payload=b"x" * 512, qos=0))
            for i in range(32)]
        await asyncio.sleep(0.3)  # subscription replication settles

        async def burst(n):
            """n publishes spread across the npubs publisher sessions;
            → (active-window seconds, deliveries across the fleet)."""
            got = [0] * len(subs)
            done = asyncio.Event()
            want_total = n * len(subs)
            total = [0]
            last = [0.0]  # timestamp of the latest delivery (effective end)

            async def drain(si, reader, codec):
                while total[0] < want_total:
                    try:
                        data = await asyncio.wait_for(reader.read(1 << 16), 2.0)
                    except asyncio.TimeoutError:
                        return  # QoS0: late stragglers are counted as lost
                    if not data:
                        return
                    k = sum(1 for p in codec.feed(data)
                            if isinstance(p, pk.Publish))
                    got[si] += k
                    total[0] += k
                    last[0] = time.perf_counter()
                    if total[0] >= want_total:
                        done.set()

            t0 = time.perf_counter()
            drains = [asyncio.get_running_loop().create_task(
                drain(si, r, c)) for si, (r, _w, c) in enumerate(subs)]

            async def feed(pi, count):
                _r, w, _c = pubs[pi]
                sent = 0
                while sent < count:
                    k = min(32, count - sent)
                    w.write(b"".join(frames[(sent + j) % 32]
                                     for j in range(k)))
                    sent += k
                    await w.drain()

            await asyncio.gather(*(feed(pi, n // npubs)
                                   for pi in range(npubs)))
            try:
                await asyncio.wait_for(done.wait(), 30.0)
            except asyncio.TimeoutError:
                pass
            # goodput over the active delivery window: a leg that sheds
            # (or idles out) is measured to its LAST delivery, not to the
            # idle-timeout tail
            elapsed = (last[0] or time.perf_counter()) - t0
            for t in drains:
                t.cancel()
            return max(elapsed, 1e-6), total[0]

        async def close():
            for r, w, _c in [*subs, *pubs]:
                try:
                    w.close()
                except Exception:
                    pass

        return burst, close

    async def _kick_p99(workers, tag):
        """Reconnect one client id across workers; CONNECT wall time of the
        takeover side (includes the kick resolution) → p99 ms."""
        times = []
        for i in range(kick_iters):
            cid = f"{tag}kick{i}"
            _r1, w1, _c1 = await _connect(workers[2].port, cid)
            t0 = time.perf_counter()
            _r2, w2, _c2 = await _connect(workers[3].port, cid)
            times.append((time.perf_counter() - t0) * 1e3)
            for w in (w1, w2):
                try:
                    w.close()
                except Exception:
                    pass
        return float(np.percentile(times, 99)), float(np.percentile(times, 50))

    async def _measure():
        fab_workers, _ = await _leg_fabric()
        bc_workers, bc_clusters = await _leg_broadcast()
        try:
            fab_burst, fab_close = await _wire_traffic(fab_workers, "f")
            bc_burst, bc_close = await _wire_traffic(bc_workers, "b")
            await fab_burst(128)  # warm both paths (codec, links, caches)
            await bc_burst(128)
            pairs = []
            for _ in range(quads):
                # order-symmetric quad (fab, bc, bc, fab): taking each
                # condition's BEST goodput of its two bursts (= fastest
                # burst) filters one-sided load spikes before the ratio
                ef1, nf1 = await fab_burst(per)
                eb1, nb1 = await bc_burst(per)
                eb2, nb2 = await bc_burst(per)
                ef2, nf2 = await fab_burst(per)
                gf = max(nf1 / ef1, nf2 / ef2)
                gb = max(nb1 / eb1, nb2 / eb2)
                pairs.append((gf, gb))
            fk99, fk50 = await _kick_p99(fab_workers, "f")
            bk99, bk50 = await _kick_p99(bc_workers, "b")
            await fab_close()
            await bc_close()
            return pairs, (fk99, fk50), (bk99, bk50)
        finally:
            for c in bc_clusters or []:
                await c.stop()
            for b in [*fab_workers, *bc_workers]:
                await b.stop()

    pairs, fab_kick, bc_kick = asyncio.run(_measure())
    ratio = float(np.median([gf / gb for gf, gb in pairs]))
    fab_goodput = max(gf for gf, _ in pairs)
    bc_goodput = max(gb for _, gb in pairs)
    res = {
        "name": name,
        "workers": nworkers,
        "subscribers": nsubs,
        "publishers": npubs,
        "msgs_per_burst": per,
        "fanout_goodput_fabric": round(fab_goodput, 1),
        "fanout_goodput_broadcast": round(bc_goodput, 1),
        "fanout_goodput_ratio": round(ratio, 2),
        "target_ratio": 3.0,
        "ok": ratio >= 3.0,
        "connect_kick_ms": {
            "fabric_p50": round(fab_kick[1], 3),
            "fabric_p99": round(fab_kick[0], 3),
            "broadcast_p50": round(bc_kick[1], 3),
            "broadcast_p99": round(bc_kick[0], 3),
        },
    }
    log(f"[{name}] cross-worker fan-out: fabric {fab_goodput:.0f} vs "
        f"broadcast {bc_goodput:.0f} deliveries/s → {ratio:.2f}x "
        f"(target ≥3x) | CONNECT kick p99 fabric {res['connect_kick_ms']['fabric_p99']}ms "
        f"vs broadcast {res['connect_kick_ms']['broadcast_p99']}ms")
    return res


def run_autotune_config(name, rng):
    """Config 15: the device-plane autotuner vs static defaults over a
    SHIFTING-REGIME workload, cfg13-style order-symmetric quads.

    The workload is the regime sequence the static env-flag matrix cannot
    serve with one setting: small-batch bursts (batch 1 — the cfg1 cliff
    shape) → steady large batches (batch 64) → subscription churn with
    more small batches. Both legs start from the SAME defaults (prewarm
    latches the sticky pad floor at 8); the autotune leg additionally
    runs the real controller (broker/autotune.py) against the real knob
    registry + devprof rollups, ticked between dispatches. The expected
    adaptation: the batch-size histogram concentrates at 1 while
    pad-waste sits at 7/8, so the pad-floor ladder canaries 8→4→2→1 and
    every later small-batch dispatch pays 1/8th the padded compute the
    static leg keeps paying.

    Legs alternate in order-symmetric quads (auto, static, static, auto)
    so drift lands on both; per quad each condition keeps its best run.
    The artifact carries the decision timeline (canary/commit/rollback
    journal with before/after metrics) — the acceptance evidence of ≥1
    adaptation and 0 unrecovered rollbacks — plus per-phase p99 and
    whole-workload goodput per leg. Target: the autotune leg beats the
    static leg by ≥1.15x on small-regime p99 or goodput."""
    from rmqtt_tpu.broker.autotune import AutotuneService
    from rmqtt_tpu.broker.devprof import DEVPROF
    from rmqtt_tpu.broker.knobs import build_registry
    from rmqtt_tpu.ops.partitioned import PartitionedMatcher

    n = 20_000
    d_small, d_steady, d_churn = 400, 24, 200
    quads = 3
    bs_big = 64
    pool_n = 48  # bounded topic pool: bounded shapes, warm candidate sets

    # wildcard-heavy filter population (first level '+'): candidate sets
    # stay large per topic, so the padded-batch compute the pad floor
    # multiplies is REAL — the regime where the cfg1 cliff lives (a
    # pure-exact table is dispatch-overhead-bound and no floor can help)
    def gen_first_plus(count):
        fs = set()
        while len(fs) < count:
            depth = rng.randint(3, 6)
            lv = [f"v{d}_{rng.randrange(VOCAB6[d])}" for d in range(depth)]
            lv[0] = "+"
            if rng.random() < 0.4:
                lv[rng.randrange(1, depth)] = "+"
            if rng.random() < 0.3:
                lv[-1] = "#"
            fs.add("/".join(lv))
        return sorted(fs)

    filters = gen_first_plus(n)
    table, fids = build_tpu_table(filters, "partitioned")
    # churn must NOT trigger background compaction here: a layout-epoch
    # bump invalidates every warmed shape, and the autotune leg touches
    # 4x the shapes (floors 8/4/2/1) the static leg does — recompiles
    # would bill the ladder for table maintenance this config doesn't
    # measure (cfg9 owns the compaction story)
    table.compact_min_ops = 1 << 30
    pool = gen_topics_uniform(rng, pool_n)
    big_batches = [[pool[(i * 7 + j) % pool_n] for j in range(bs_big)]
                   for i in range(8)]
    churn_filters = gen_mixed(random.Random(rng.randrange(2**31)),
                              max(32, d_churn // 4))
    log(f"[{name}] {n} subs, regimes: {d_small}x1 -> {d_steady}x{bs_big} "
        f"-> {d_churn}x1+churn, {quads} order-symmetric quad(s)")

    # deterministic workload script, shared verbatim by every leg run:
    # (phase, batch, churn_step or None)
    seq = []
    for i in range(d_small):
        seq.append(("small", [pool[i % pool_n]], None))
    for i in range(d_steady):
        seq.append(("steady", big_batches[i % len(big_batches)], None))
    for i in range(d_churn):
        seq.append(("churn", [pool[(i * 3) % pool_n]],
                    i // 16 if i % 16 == 0 else None))

    churn_fids = []

    def apply_churn(step):
        # one add + one remove per step: steady version churn (delta
        # uploads + journal activity) without net table growth
        f = churn_filters[step % len(churn_filters)]
        fid = table.add(f + f"/c{step}n{len(churn_fids)}")
        if len(churn_fids) > 1:
            table.remove(churn_fids.pop(0))
        return fid

    def run_leg(auto_on, tag):
        # NO devprof reset here: the shape-key registry must stay as old
        # as the process or every warm executable re-counts as a "trace"
        # and phantom retrace storms hold the tuner (the controller's
        # counter baselines prime from the profiler at construction)
        m = PartitionedMatcher(table)
        m.prewarm()  # the static default: sticky pad floor 8
        svc = None
        if auto_on:
            shim = type("_RouterShim", (), {})()
            shim.matcher = m
            reg = build_registry(shim, None)
            svc = AutotuneService(
                reg, enabled=True, interval_s=0.05, canary_k=6,
                cooldown_s=0.5, p99_guard=2.0, confirm_ticks=2,
                devprof=DEVPROF)
        lat = {"small": [], "steady": [], "churn": []}
        t0 = time.perf_counter()
        for i, (phase, batch, churn_step) in enumerate(seq):
            if churn_step is not None:
                churn_fids.append(apply_churn(churn_step))
            t1 = time.perf_counter()
            m.match(batch)
            lat[phase].append(time.perf_counter() - t1)
            if svc is not None and i % 4 == 3:
                svc.tick()
        wall = time.perf_counter() - t0
        topics = sum(len(b) for _p, b, _c in seq)

        def p99(ls):
            ls = sorted(ls)
            return round(ls[min(len(ls) - 1, int(len(ls) * 0.99))] * 1e3, 3)

        # tail halves = the CONVERGED regime (the autotune leg spends its
        # head learning; the static leg's halves are statistically
        # identical, so the split is order-symmetric-fair). Full-phase
        # numbers ride alongside — the learning transient stays visible.
        tail = {k: v[len(v) // 2:] for k, v in lat.items()}
        small_churn_tail = tail["small"] + tail["churn"]
        out = {
            "goodput_topics_per_sec": round(topics / wall, 1),
            "tail_goodput_topics_per_sec": round(
                (len(small_churn_tail) + len(tail["steady"]) * bs_big)
                / max(1e-9, sum(small_churn_tail) + sum(tail["steady"])),
                1),
            # the pure small-batch regime is what the pad floor serves —
            # the pair metric reads THIS tail; steady proves the tuner
            # doesn't worsen large batches (p99_steady_ms) and churn that
            # upload traffic doesn't destabilize it (tail_p99_churn_ms),
            # both additive-equal costs that would only dilute the ratio
            "tail_small_goodput_topics_per_sec": round(
                len(tail["small"]) / max(1e-9, sum(tail["small"])), 1),
            "tail_smallchurn_goodput_topics_per_sec": round(
                len(small_churn_tail) / max(1e-9, sum(small_churn_tail)),
                1),
            "p99_small_ms": p99(lat["small"]),
            "p99_steady_ms": p99(lat["steady"]),
            "p99_churn_ms": p99(lat["churn"]),
            # combined small+churn tail: one percentile over every
            # converged small-batch dispatch — the per-phase tails are
            # ~100 samples each and their p99 is a coin-flip between
            # adjacent outliers
            "tail_p99_ms": p99(small_churn_tail),
            "tail_p99_small_ms": p99(tail["small"]),
            "tail_p99_churn_ms": p99(tail["churn"]),
            "pad_floor_final": m._pad_floor,
        }
        if svc is not None:
            out["decisions"] = list(svc.journal)
            out["commits"] = svc.commits
            out["rollbacks"] = svc.rollbacks
            out["aborts"] = svc.aborts
            out["canary_open_at_end"] = svc._canary is not None
            out["final_knobs"] = {r["name"]: r["value"]
                                  for r in reg.snapshot()}
        return out

    # shape warmup OUTSIDE measurement: every pool topic at every ladder
    # floor + the steady shape + a churn mutation, so neither leg pays an
    # XLA compile mid-measurement (the canary trace budget covers the
    # real-world compile cost story; this config measures steady state)
    DEVPROF.reset()
    prior = (DEVPROF.enabled, DEVPROF.interval_s)
    DEVPROF.configure(enabled=True, interval_s=0.05)
    warm = PartitionedMatcher(table)
    warm.match(big_batches[0])  # fused verify
    for floor in (8, 4, 2, 1):
        warm.set_pad_floor(floor)
        for t in pool:
            warm.match([t])
    for b in big_batches:
        warm.match(b)
    for step in range(4):  # delta-scatter + post-churn refresh shapes
        churn_fids.append(apply_churn(step))
        warm.match([pool[step]])

    try:
        autos, statics, quad_rows = [], [], []
        for _ in range(quads):
            a1 = run_leg(True, "auto")
            b1 = run_leg(False, "static")
            b2 = run_leg(False, "static")
            a2 = run_leg(True, "auto")
            autos += [a1, a2]
            statics += [b1, b2]
            # within-quad pairing (cfg13 discipline): each condition keeps
            # its best of two runs, so a host-noise window hitting one run
            # doesn't decide the quad; the MEDIAN across quads decides the
            # config (a global best-of-all-runs let one lucky static run
            # dilute the whole estimate)
            ga = max(a1["tail_small_goodput_topics_per_sec"],
                     a2["tail_small_goodput_topics_per_sec"])
            gb = max(b1["tail_small_goodput_topics_per_sec"],
                     b2["tail_small_goodput_topics_per_sec"])
            pa = min(a1["tail_p99_small_ms"], a2["tail_p99_small_ms"])
            pb = min(b1["tail_p99_small_ms"], b2["tail_p99_small_ms"])
            quad_rows.append({
                "tail_goodput_ratio": round(ga / max(1e-9, gb), 3),
                "tail_p99_ratio": round(pb / max(1e-9, pa), 3),
            })
    finally:
        DEVPROF.configure(enabled=prior[0], interval_s=prior[1])
        DEVPROF.reset()
        for fid in churn_fids:  # leave the shared table as we found it
            try:
                table.remove(fid)
            except Exception:
                pass

    best_auto = max(autos, key=lambda r: r["tail_goodput_topics_per_sec"])
    best_static = max(statics,
                      key=lambda r: r["tail_goodput_topics_per_sec"])
    goodput_ratio = (best_auto["goodput_topics_per_sec"]
                     / max(1e-9, best_static["goodput_topics_per_sec"]))
    # the converged (tail-half) regime is the autotuner's claim — the
    # learning transient rides in the full-phase numbers + the timeline.
    # Per-quad ratios, MEDIAN across quads (see quad_rows above).
    med = len(quad_rows) // 2
    tail_goodput_ratio = sorted(
        q["tail_goodput_ratio"] for q in quad_rows)[med]
    tail_p99_ratio = sorted(
        q["tail_p99_ratio"] for q in quad_rows)[med]
    pair_ratio = max(tail_goodput_ratio, tail_p99_ratio)
    adaptations = sum(a.get("commits", 0) for a in autos)
    unrecovered = sum(1 for a in autos if a.get("canary_open_at_end"))
    res = {
        "name": name,
        "table_size": len(fids),
        "regimes": {"small": d_small, "steady": d_steady,
                    "churn": d_churn, "big_batch": bs_big},
        "autotune": best_auto,
        "static": best_static,
        "quads": quad_rows,
        "goodput_ratio": round(goodput_ratio, 3),
        "tail_goodput_ratio": round(tail_goodput_ratio, 3),
        "tail_p99_ratio": round(tail_p99_ratio, 3),
        "pair_ratio": round(pair_ratio, 3),
        "target_ratio": 1.15,
        "adaptations": adaptations,
        "rollbacks": sum(a.get("rollbacks", 0) for a in autos),
        "unrecovered_rollbacks": unrecovered,
        "ok": (pair_ratio >= 1.15
               and adaptations >= 1 and unrecovered == 0),
    }
    log(f"[{name}] autotune tail p99(small) "
        f"{best_auto['tail_p99_small_ms']}ms / "
        f"{best_auto['tail_goodput_topics_per_sec']:.0f}/s (floor -> "
        f"{best_auto['pad_floor_final']}) vs static "
        f"{best_static['tail_p99_small_ms']}ms / "
        f"{best_static['tail_goodput_topics_per_sec']:.0f}/s -> tail p99 "
        f"{tail_p99_ratio:.2f}x, tail goodput {tail_goodput_ratio:.2f}x, "
        f"run goodput {goodput_ratio:.2f}x (target >=1.15x, "
        f"{adaptations} commits, {res['rollbacks']} rollbacks)")
    return res


def run_egress_config(name, rng):
    """Config 16: coalesced egress vs legacy per-frame writes at
    64-subscriber fan-out, cfg13-style order-symmetric paired estimator.

    Two live single-worker brokers in one process, identical except for
    ``[network] egress_coalesce``: the COALESCED leg batches every frame
    queued for a connection within one loop tick into a single vectored
    write (broker/egress.py); the LEGACY leg is the pre-coalescer data
    plane — one transport write per outbound frame. The workload is the
    fan-out shape where per-frame writes dominate: 64 subscribers
    sharing one wildcard filter, so each QoS0 publish becomes 64
    outbound frames and the write-call count is the data plane's real
    syscall budget. Bursts alternate legs in order-symmetric quads
    (coalesced, legacy, legacy, coalesced) with each condition keeping
    its best burst; the artifact carries syscalls-per-delivered-message
    per leg — the coalesced leg counts its ACTUAL vectored writes via
    the ``net.egress_flushes`` counter delta, the legacy send path is
    structurally one transport write per frame (broker/session.py
    send_raw) — plus the goodput ratio. Targets: ≥5x fewer send
    syscalls per delivered message and ≥1.25x goodput."""
    import asyncio

    from rmqtt_tpu.broker.codec import MqttCodec, packets as pk
    from rmqtt_tpu.broker.context import BrokerConfig, ServerContext
    from rmqtt_tpu.broker.fitter import FitterConfig
    from rmqtt_tpu.broker.server import MqttBroker

    nsubs = 64  # the fan-out fleet, one shared wildcard filter
    npubs = 32  # concurrent publishers: the coalescing window is one loop
    # tick, so frames-per-flush scales with how many publisher sessions
    # route a publish in the same tick (the production fan-in shape)
    per = 512  # publishes per burst (×nsubs deliveries)
    quads = 3

    async def _read_until(reader, codec, ptype):
        while True:
            data = await reader.read(4096)
            if not data:
                raise ConnectionError(f"peer closed before {ptype.__name__}")
            for p in codec.feed(data):
                if isinstance(p, ptype):
                    return p

    async def _connect(port, cid):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        codec = MqttCodec()
        writer.write(codec.encode(pk.Connect(client_id=cid, keepalive=600)))
        await writer.drain()
        await _read_until(reader, codec, pk.Connack)
        return reader, writer, codec

    async def _leg(coalesce):
        b = MqttBroker(ServerContext(BrokerConfig(
            port=0, telemetry_enable=False, egress_coalesce=coalesce,
            fitter=FitterConfig(max_mqueue=100_000))))
        await b.start()
        return b

    async def _wire_traffic(broker, tag, coalesce):
        """64 subscribers on eg/# + npubs publishers; → (burst, close).
        burst(n) → (active-window seconds, deliveries, send calls)."""
        subs = []
        for k in range(nsubs):
            r, w, c = await _connect(broker.port, f"{tag}s{k}")
            w.write(c.encode(pk.Subscribe(
                1, [("eg/#", pk.SubOpts(qos=0))])))
            await w.drain()
            await _read_until(r, c, pk.Suback)
            subs.append((r, w, c))
        pubs = [await _connect(broker.port, f"{tag}p{k}")
                for k in range(npubs)]
        frames = [pubs[0][2].encode(pk.Publish(
            topic=f"eg/t{i}", payload=b"x" * 512, qos=0))
            for i in range(32)]
        metrics = broker.ctx.metrics

        async def burst(n):
            got = [0] * len(subs)
            done = asyncio.Event()
            want_total = n * len(subs)
            total = [0]
            last = [0.0]  # timestamp of the latest delivery (effective end)

            async def drain(si, reader, codec):
                while total[0] < want_total:
                    try:
                        data = await asyncio.wait_for(reader.read(1 << 16), 2.0)
                    except asyncio.TimeoutError:
                        return  # QoS0: late stragglers are counted as lost
                    if not data:
                        return
                    k = sum(1 for p in codec.feed(data)
                            if isinstance(p, pk.Publish))
                    got[si] += k
                    total[0] += k
                    last[0] = time.perf_counter()
                    if total[0] >= want_total:
                        done.set()

            w0 = metrics.get("net.egress_flushes")
            t0 = time.perf_counter()
            drains = [asyncio.get_running_loop().create_task(
                drain(si, r, c)) for si, (r, _w, c) in enumerate(subs)]

            async def feed(pi, count):
                _r, w, _c = pubs[pi]
                sent = 0
                while sent < count:
                    k = min(32, count - sent)
                    w.write(b"".join(frames[(sent + j) % 32]
                                     for j in range(k)))
                    sent += k
                    await w.drain()

            await asyncio.gather(*(feed(pi, n // npubs)
                                   for pi in range(npubs)))
            try:
                await asyncio.wait_for(done.wait(), 30.0)
            except asyncio.TimeoutError:
                pass
            elapsed = (last[0] or time.perf_counter()) - t0
            for t in drains:
                t.cancel()
            # send calls: the coalesced leg's flush counter counts each
            # vectored write it issued; the legacy path is one
            # transport.write per frame, i.e. exactly the delivery count
            writes = ((metrics.get("net.egress_flushes") - w0)
                      if coalesce else total[0])
            return max(elapsed, 1e-6), total[0], writes

        async def close():
            for r, w, _c in [*subs, *pubs]:
                try:
                    w.close()
                except Exception:
                    pass

        return burst, close

    async def _measure():
        cb = await _leg(True)
        lb = await _leg(False)
        try:
            c_burst, c_close = await _wire_traffic(cb, "c", True)
            l_burst, l_close = await _wire_traffic(lb, "l", False)
            await c_burst(64)  # warm both paths (codec, routes, buffers)
            await l_burst(64)
            pairs = []
            deliv_c = writes_c = deliv_l = writes_l = 0
            for _ in range(quads):
                # order-symmetric quad (coal, legacy, legacy, coal):
                # each condition keeps its BEST goodput of its two
                # bursts, filtering one-sided load spikes (cfg13 rule)
                ec1, nc1, wc1 = await c_burst(per)
                el1, nl1, wl1 = await l_burst(per)
                el2, nl2, wl2 = await l_burst(per)
                ec2, nc2, wc2 = await c_burst(per)
                pairs.append((max(nc1 / ec1, nc2 / ec2),
                              max(nl1 / el1, nl2 / el2)))
                deliv_c += nc1 + nc2
                writes_c += wc1 + wc2
                deliv_l += nl1 + nl2
                writes_l += wl1 + wl2
            # counter snapshot BEFORE teardown: closing the sessions
            # fires their final flushes and would skew the totals
            eg = {k: cb.ctx.metrics.get(f"net.egress_{k}")
                  for k in ("frames", "flushes", "coalesced", "bytes")}
            await c_close()
            await l_close()
            return pairs, (deliv_c, writes_c), (deliv_l, writes_l), eg
        finally:
            await cb.stop()
            await lb.stop()

    pairs, (dc, wc), (dl, wl), eg = asyncio.run(_measure())
    ratio = float(np.median([gc / gl for gc, gl in pairs]))
    spm_c = wc / max(1, dc)
    spm_l = wl / max(1, dl)  # 1.0 by construction (one write per frame)
    reduction = spm_l / max(1e-9, spm_c)
    res = {
        "name": name,
        "subscribers": nsubs,
        "publishers": npubs,
        "msgs_per_burst": per,
        "fanout_goodput_coalesced": round(max(gc for gc, _ in pairs), 1),
        "fanout_goodput_legacy": round(max(gl for _, gl in pairs), 1),
        "goodput_ratio": round(ratio, 3),
        "syscalls_per_msg_coalesced": round(spm_c, 4),
        "syscalls_per_msg_legacy": round(spm_l, 4),
        "syscall_reduction_x": round(reduction, 2),
        "egress_counters": eg,
        "target_syscall_reduction": 5.0,
        "target_goodput_ratio": 1.25,
        "ok": reduction >= 5.0 and ratio >= 1.25,
    }
    log(f"[{name}] {nsubs}-sub fan-out: coalesced "
        f"{res['fanout_goodput_coalesced']:.0f} vs legacy "
        f"{res['fanout_goodput_legacy']:.0f} deliveries/s → {ratio:.2f}x "
        f"goodput (target ≥1.25x) | {spm_c:.3f} vs {spm_l:.3f} "
        f"send calls/msg → {reduction:.1f}x fewer (target ≥5x)")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny config 1 only")
    ap.add_argument("--full", action="store_true", help="include 10M-sub configs 4-5")
    ap.add_argument("--config", type=int, default=None, help="run a single config 1-17")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend on purpose (without it, no "
                         "TPU is an error)")
    ap.add_argument(
        "--profile", metavar="DIR", default=None,
        help="capture an XLA/device profile of the measured configs into DIR "
             "(view with tensorboard / xprof; stats.rs-era tracing analogue)",
    )
    args = ap.parse_args()

    import os

    import jax

    from rmqtt_tpu.utils.jaxenv import device_identity, setup_compile_cache

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    setup_compile_cache()  # before the first jit
    # first backend touch, in THIS process (a chip belongs to one process;
    # nothing here probes from a child): raises when no accelerator answers
    ident = device_identity()
    platform = ident["platform"]
    on_tpu = platform == "tpu"
    if not on_tpu and not args.cpu:
        sys.exit(f"bench.py measures the chip and found platform={platform!r}. "
                 "--cpu runs the same configs on the CPU backend on purpose; "
                 "its rates are then labelled with that platform.")
    rng = random.Random(args.seed)
    global _ON_TPU
    _ON_TPU = on_tpu
    # every artifact names the device its numbers were taken on
    device_tag = {"platform": platform, "device_kind": ident["device_kind"],
                  "device_count": ident["device_count"]}
    log(f"jax devices: {jax.devices()} ({ident})")

    # device-plane profiler (broker/devprof.py): every bench run carries
    # the devprof snapshot in its JSON, and a FAILED config persists a
    # flight-recorder dump so the next TPU window is diagnosable even when
    # the run dies (the postmortem cfg4/cfg5 never got)
    from rmqtt_tpu.broker.devprof import DEVPROF

    devprof_dir = os.path.join(os.path.dirname(__file__), ".devprof")
    DEVPROF.configure(enabled=True, dump_dir=devprof_dir)
    # a caller that times the run out sends TERM before KILL: freeze the
    # flight recorder on the way out so even a timed-out config leaves an
    # artifact (SIGKILL leaves nothing — that is why the TERM comes first).
    # The handler ONLY raises: signal handlers run on the main thread
    # between bytecodes, and the interrupted frame may be inside a
    # `with DEVPROF._lock:` block — dumping here would deadlock on the
    # non-reentrant lock. The KeyboardInterrupt unwinds those `with`
    # blocks (releasing the lock) and guarded()'s handler does the dump.
    import signal as _signal

    def _on_term(_sig, _frm):
        raise KeyboardInterrupt

    try:
        _signal.signal(_signal.SIGTERM, _on_term)
    except (ValueError, OSError):  # non-main thread / exotic platform
        pass

    results = {}

    def want(i):
        if args.smoke:
            return i == 1
        if args.config is not None:
            return i == args.config
        # on real TPU the default is ALL FIVE baseline configs; cfg6 (the
        # host-side match-result cache), cfg7 (telemetry overhead), cfg8
        # (overload soak), cfg9 (churn soak / delta uploads), cfg11
        # (small-batch stage attribution), cfg12/cfg14 (device/host
        # profiler overhead bounds), cfg13 (fabric-vs-broadcast fan-out),
        # cfg15 (autotune-vs-static shifting regime), cfg16
        # (coalesced-vs-legacy egress), cfg17 (history collector
        # overhead bound) and cfg18 (hot-key sketch overhead bound) are
        # cheap and always informative
        return (i <= 3 or i in (6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17,
                                18)
                or args.full or on_tpu)

    failures = {}
    if args.profile:
        global _PROFILE_DIR
        _PROFILE_DIR = args.profile

    interrupted = False

    def guarded(name, fn):
        """A late config failing (OOM at 10M subs, driver timeout nearing,
        the accelerator wedging mid-run) must not lose the results already
        measured — even SIGINT falls through to the JSON print below."""
        nonlocal interrupted
        if interrupted:
            failures[name] = "skipped: interrupted"
            return
        try:
            results[name] = fn()
        except KeyboardInterrupt:
            interrupted = True
            failures[name] = "KeyboardInterrupt (timed out?)"
            log(f"{name} INTERRUPTED — emitting the configs already measured")
            # safe here: the interrupt already unwound any profiler-lock
            # `with` blocks on this thread (see the SIGTERM handler note)
            DEVPROF.dump_to(os.path.join(devprof_dir, f"{name}.json"),
                            f"bench-config-interrupted: {name}")
        except BaseException as e:
            failures[name] = f"{type(e).__name__}: {e}"
            log(f"{name} FAILED: {failures[name]}")
            # persist the flight recorder for the dead config: the artifact
            # that makes a failed chip run diagnosable after the window
            DEVPROF.dump_to(os.path.join(devprof_dir, f"{name}.json"),
                            f"bench-config-failed: {failures[name]}")

    if want(1):
        def cfg1():
            n = 1000 if not args.smoke else 200
            filters = gen_exact(rng, n)
            # ~50% of publishes hit a subscribed topic
            topics = [rng.choice(filters) if rng.random() < 0.5 else _tree_topic(rng, 4) for _ in range(4096)]
            return run_config("cfg1_exact_1k", filters, topics, 4096, 1024)

        guarded("cfg1_exact_1k", cfg1)

    if want(2):
        def cfg2():
            n, nt, bs = 100_000, 20_000, 8192
            filters = gen_single_plus(rng, n)
            # depth 3-5 filters over l{d}n{...} names: generate matching-shape topics
            topics = ["/".join(f"l{d}n{rng.randrange(400)}" for d in range(rng.randint(3, 5))) for _ in range(nt)]
            return run_config("cfg2_plus_100k", filters, topics, bs, 512)

        guarded("cfg2_plus_100k", cfg2)

    if want(3):
        def cfg3():
            n, nt, bs = 1_000_000, 32_768, 16384
            filters = gen_mixed(rng, n)
            topics = gen_topics_uniform(rng, nt)
            return run_config("cfg3_mixed_1m", filters, topics, bs, 256)

        guarded("cfg3_mixed_1m", cfg3)

    if want(4):
        def cfg4():
            n, nt, bs, cs = 10_000_000, 16_384, 8192, 64
            filters = gen_mixed(rng, n, shared_frac=0.1)
            topics = gen_topics_zipf(rng, nt)
            return run_config("cfg4_shared_10m_zipf", filters, topics, bs, cs)

        guarded("cfg4_shared_10m_zipf", cfg4)

    if want(5):
        def cfg5():
            n, nt, bs, cs, nr = 10_000_000, 16_384, 8192, 64, 1_000_000
            filters = gen_mixed(rng, n, shared_frac=0.05)
            topics = gen_topics_zipf(rng, nt)
            retained = list({_tree_topic(rng, rng.randint(3, 6)) for _ in range(nr)})
            return run_config("cfg5_retained_10m", filters, topics, bs, cs,
                              retained=retained)

        guarded("cfg5_retained_10m", cfg5)

    if want(6):
        def cfg6():
            return run_cache_config("cfg6_cache_zipf", rng)

        guarded("cfg6_cache_zipf", cfg6)

    if want(7):
        def cfg7():
            return run_telemetry_config("cfg7_telemetry_overhead", rng)

        guarded("cfg7_telemetry_overhead", cfg7)

    if want(8):
        def cfg8():
            return run_overload_config("cfg8_overload_soak", rng)

        guarded("cfg8_overload_soak", cfg8)

    if want(9):
        def cfg9():
            return run_churn_config("cfg9_churn_soak", rng)

        guarded("cfg9_churn_soak", cfg9)

    if want(10):
        def cfg10():
            return run_failover_config("cfg10_failover_soak", rng)

        guarded("cfg10_failover_soak", cfg10)

    if want(11):
        def cfg11():
            return run_smallbatch_config("cfg11_smallbatch_paired", rng)

        guarded("cfg11_smallbatch_paired", cfg11)

    if want(12):
        def cfg12():
            return run_devprof_overhead_config("cfg12_devprof_overhead", rng)

        guarded("cfg12_devprof_overhead", cfg12)

    if want(13):
        def cfg13():
            return run_fabric_config("cfg13_fabric_paired", rng)

        guarded("cfg13_fabric_paired", cfg13)

    if want(14):
        def cfg14():
            return run_hostprof_overhead_config("cfg14_hostprof_overhead",
                                                rng)

        guarded("cfg14_hostprof_overhead", cfg14)

    if want(15):
        def cfg15():
            return run_autotune_config("cfg15_autotune_paired", rng)

        guarded("cfg15_autotune_paired", cfg15)

    if want(16):
        def cfg16():
            return run_egress_config("cfg16_egress_paired", rng)

        guarded("cfg16_egress_paired", cfg16)

    if want(17):
        def cfg17():
            return run_history_overhead_config("cfg17_history_overhead",
                                               rng)

        guarded("cfg17_history_overhead", cfg17)

    if want(18):
        def cfg18():
            return run_hotkeys_overhead_config("cfg18_sketch_overhead",
                                               rng)

        guarded("cfg18_sketch_overhead", cfg18)

    # cfg6/cfg7/cfg8 have their own shapes (on/off comparisons, no tpu/cpu
    # variants): they ride the artifact under "route_cache" /
    # "telemetry_overhead" / "overload_soak" instead of the configs table
    cache_res = results.pop("cfg6_cache_zipf", None)
    tele_res = results.pop("cfg7_telemetry_overhead", None)
    overload_res = results.pop("cfg8_overload_soak", None)
    churn_res = results.pop("cfg9_churn_soak", None)
    failover_res = results.pop("cfg10_failover_soak", None)
    smallbatch_res = results.pop("cfg11_smallbatch_paired", None)
    devprof_res = results.pop("cfg12_devprof_overhead", None)
    fabric_res = results.pop("cfg13_fabric_paired", None)
    hostprof_res = results.pop("cfg14_hostprof_overhead", None)
    autotune_res = results.pop("cfg15_autotune_paired", None)
    egress_res = results.pop("cfg16_egress_paired", None)
    history_res = results.pop("cfg17_history_overhead", None)
    hotkeys_res = results.pop("cfg18_sketch_overhead", None)
    if (not results and hotkeys_res is not None and history_res is None
            and egress_res is None and autotune_res is None
            and hostprof_res is None and fabric_res is None
            and devprof_res is None and smallbatch_res is None
            and failover_res is None and churn_res is None
            and overload_res is None and tele_res is None
            and cache_res is None):
        # a --config 18 run: its own artifact shape; the >2% bound FAILS
        # the run (exit 1) so CI can gate on the hot-key sketch cost
        print(json.dumps({
            "metric": "hotkeys_overhead_pct[cfg18_sketch_overhead]",
            "value": hotkeys_res["overhead_pct"],
            "unit": "pct_vs_off",
            "vs_baseline": hotkeys_res["overhead_pct"],
            "ok": hotkeys_res["ok"],
            "samples_recorded": hotkeys_res["samples_recorded"],
            **device_tag,
            "hotkeys_overhead": hotkeys_res,
            **({"failed_configs": failures} if failures else {}),
        }))
        if not hotkeys_res["ok"]:
            sys.exit(1)
        return
    if (not results and history_res is not None and egress_res is None
            and autotune_res is None and hostprof_res is None
            and fabric_res is None and devprof_res is None
            and smallbatch_res is None and failover_res is None
            and churn_res is None and overload_res is None
            and tele_res is None and cache_res is None
            and hotkeys_res is None):
        # a --config 17 run: its own artifact shape; the >2% bound FAILS
        # the run (exit 1) so CI can gate on the history-collector cost
        print(json.dumps({
            "metric": "history_overhead_pct[cfg17_history_overhead]",
            "value": history_res["overhead_pct"],
            "unit": "pct_vs_off",
            "vs_baseline": history_res["overhead_pct"],
            "ok": history_res["ok"],
            "samples_recorded": history_res["samples_recorded"],
            **device_tag,
            "history_overhead": history_res,
            **({"failed_configs": failures} if failures else {}),
        }))
        if not history_res["ok"]:
            sys.exit(1)
        return
    if (not results and egress_res is not None and autotune_res is None
            and hostprof_res is None and fabric_res is None
            and devprof_res is None and smallbatch_res is None
            and failover_res is None and churn_res is None
            and overload_res is None and tele_res is None
            and cache_res is None and history_res is None
            and hotkeys_res is None):
        # a --config 16 run: its own artifact shape; the ≥5x send-syscall
        # reduction AND ≥1.25x goodput bounds FAIL the run (exit 1) so CI
        # can gate on the coalesced data plane
        print(json.dumps({
            "metric": "egress_syscall_reduction[cfg16_egress_paired]",
            "value": egress_res["syscall_reduction_x"],
            "unit": "x_fewer_send_calls_per_msg",
            "vs_baseline": egress_res["syscall_reduction_x"],
            "ok": egress_res["ok"],
            "goodput_ratio": egress_res["goodput_ratio"],
            "syscalls_per_msg_coalesced":
                egress_res["syscalls_per_msg_coalesced"],
            **device_tag,
            "egress_paired": egress_res,
            **({"failed_configs": failures} if failures else {}),
        }))
        if not egress_res["ok"]:
            sys.exit(1)
        return
    if (not results and autotune_res is not None and hostprof_res is None
            and fabric_res is None and devprof_res is None
            and smallbatch_res is None and failover_res is None
            and churn_res is None and overload_res is None
            and tele_res is None and cache_res is None
            and egress_res is None
            and history_res is None
            and hotkeys_res is None):
        # a --config 15 run: its own artifact shape; the ≥1.15x
        # autotune-over-static bound (plus ≥1 adaptation and 0 unrecovered
        # rollbacks) FAILS the run (exit 1) so CI can gate on it
        print(json.dumps({
            "metric": "autotune_pair_ratio[cfg15_autotune_paired]",
            "value": autotune_res["pair_ratio"],
            "unit": "x_autotune_over_static",
            "vs_baseline": autotune_res["pair_ratio"],
            "ok": autotune_res["ok"],
            "adaptations": autotune_res["adaptations"],
            "unrecovered_rollbacks": autotune_res["unrecovered_rollbacks"],
            **device_tag,
            "autotune_paired": autotune_res,
            **({"failed_configs": failures} if failures else {}),
        }))
        if not autotune_res["ok"]:
            sys.exit(1)
        return
    if (not results and hostprof_res is not None and fabric_res is None
            and devprof_res is None and smallbatch_res is None
            and failover_res is None and churn_res is None
            and overload_res is None and tele_res is None
            and cache_res is None and egress_res is None
            and history_res is None
            and hotkeys_res is None):
        # a --config 14 run: its own artifact shape; the >2% bound FAILS
        # the run (exit 1) so CI can gate on the host-profiler cost
        print(json.dumps({
            "metric": "hostprof_overhead_pct[cfg14_hostprof_overhead]",
            "value": hostprof_res["overhead_pct"],
            "unit": "pct_vs_off",
            "vs_baseline": hostprof_res["overhead_pct"],
            "ok": hostprof_res["ok"],
            **device_tag,
            "hostprof_overhead": hostprof_res,
            **({"failed_configs": failures} if failures else {}),
        }))
        if not hostprof_res["ok"]:
            sys.exit(1)
        return
    if (not results and fabric_res is not None and devprof_res is None
            and smallbatch_res is None and failover_res is None
            and churn_res is None and overload_res is None
            and tele_res is None and cache_res is None
            and hostprof_res is None and egress_res is None
            and history_res is None
            and hotkeys_res is None):
        # a --config 13 run: its own artifact shape; the ≥3× cross-worker
        # fan-out bound FAILS the run (exit 1) so CI can gate on it
        print(json.dumps({
            "metric": "fanout_goodput_ratio[cfg13_fabric_paired]",
            "value": fabric_res["fanout_goodput_ratio"],
            "unit": "x_fabric_over_broadcast",
            "vs_baseline": fabric_res["fanout_goodput_ratio"],
            "ok": fabric_res["ok"],
            "connect_kick_ms": fabric_res["connect_kick_ms"],
            **device_tag,
            "fabric_paired": fabric_res,
            **({"failed_configs": failures} if failures else {}),
        }))
        if not fabric_res["ok"]:
            sys.exit(1)
        return
    # every bench JSON carries the device-plane profiler snapshot + the
    # tail of the flight ring (satellite of the devprof PR: on-chip runs
    # become diagnosable from the artifact alone)
    devprof_embed = {"devprof": {**DEVPROF.snapshot(),
                                 "flight": DEVPROF.flight()[-16:]}}
    if (not results and devprof_res is not None and smallbatch_res is None
            and failover_res is None and churn_res is None
            and overload_res is None and tele_res is None
            and cache_res is None and egress_res is None
            and history_res is None
            and hotkeys_res is None):
        # a --config 12 run: its own artifact shape; the >2% bound FAILS
        # the run (exit 1) so CI can gate on it
        print(json.dumps({
            "metric": "devprof_overhead_pct[cfg12_devprof_overhead]",
            "value": devprof_res["overhead_pct"],
            "unit": "pct_vs_off",
            "vs_baseline": devprof_res["overhead_pct"],
            "ok": devprof_res["ok"],
            **device_tag,
            "devprof_overhead": devprof_res,
            **devprof_embed,
            **({"failed_configs": failures} if failures else {}),
        }))
        if not devprof_res["ok"]:
            sys.exit(1)
        return
    if (not results and smallbatch_res is not None and failover_res is None
            and churn_res is None and overload_res is None
            and tele_res is None and cache_res is None
            and egress_res is None
            and history_res is None
            and hotkeys_res is None):
        # a --config 11 run: its own artifact shape
        print(json.dumps({
            "metric": "smallbatch_fused_pair_ratio[cfg11_smallbatch_paired]",
            "value": smallbatch_res["median_pair_ratio"],
            "unit": "x_fused_over_unfused",
            "vs_baseline": smallbatch_res["median_pair_ratio"],
            "decode_share_unfused": smallbatch_res["decode_share_unfused"],
            "decode_share_fused": smallbatch_res["decode_share_fused"],
            **device_tag,
            "smallbatch_paired": smallbatch_res,
            **({"failed_configs": failures} if failures else {}),
        }))
        return
    if (not results and failover_res is not None and churn_res is None
            and overload_res is None and tele_res is None
            and cache_res is None and egress_res is None
            and history_res is None
            and hotkeys_res is None):
        sb = failover_res["time_to_switchback_s"]
        no_sb = sb is None
        if no_sb:
            # the soak gives up soak_s+20s in (see run_failover_config);
            # emit that observation bound instead of null so numeric
            # consumers (regression gates, plots) see a finite worst case
            # in exactly the failure this metric exists to catch
            sb = round(failover_res["soak_s"] + 20.0
                       - failover_res["fault_window_s"][1], 2)
        print(json.dumps({
            "metric": "failover_switchback_s[cfg10_failover_soak]",
            "value": sb,
            "unit": "seconds_to_switchback",
            "vs_baseline": sb,
            **({"no_switchback": True} if no_sb else {}),
            "lost": failover_res["lost"],
            "failover_p99_ms": failover_res["failover_p99_ms"],
            "steady_p99_ms": failover_res["steady_p99_ms"],
            **device_tag,
            "failover_soak": failover_res,
            **({"failed_configs": failures} if failures else {}),
        }))
        return
    if (not results and churn_res is not None and overload_res is None
            and tele_res is None and cache_res is None
            and egress_res is None
            and history_res is None
            and hotkeys_res is None):
        print(json.dumps({
            "metric": "delta_upload_reduction[cfg9_churn_soak]",
            "value": churn_res["delta_reduction_x"],
            "unit": "x_vs_full_refresh",
            "vs_baseline": churn_res["delta_reduction_x"],
            "upload_bytes_per_mutation": churn_res["upload_bytes_per_mutation"],
            "p99_churn_over_free": churn_res["p99_churn_over_free"],
            "median_pair_ratio": churn_res["median_pair_ratio"],
            **device_tag,
            "churn_soak": churn_res,
            **({"failover_soak": failover_res} if failover_res else {}),
            **({"failed_configs": failures} if failures else {}),
        }))
        return
    if (not results and overload_res is not None and tele_res is None
            and cache_res is None and egress_res is None
            and history_res is None
            and hotkeys_res is None):
        print(json.dumps({
            "metric": "overload_p99_bound[cfg8_overload_soak]",
            "value": overload_res["p99_ratio_off_over_on"],
            "unit": "x_off_over_on",
            "vs_baseline": overload_res["p99_ratio_off_over_on"],
            **device_tag,
            "overload_soak": overload_res,
            **({"churn_soak": churn_res} if churn_res else {}),
            **({"failover_soak": failover_res} if failover_res else {}),
            **({"failed_configs": failures} if failures else {}),
        }))
        return
    if (not results and tele_res is not None and cache_res is None
            and egress_res is None
            and history_res is None
            and hotkeys_res is None):
        print(json.dumps({
            "metric": "telemetry_overhead_pct[cfg7_telemetry_overhead]",
            "value": tele_res["overhead_pct"],
            "unit": "pct_vs_off",
            "vs_baseline": tele_res["overhead_pct"],
            **device_tag,
            "latency_ms": tele_res["latency_ms"],
            "telemetry_overhead": tele_res,
            **({"overload_soak": overload_res} if overload_res else {}),
            **({"churn_soak": churn_res} if churn_res else {}),
            **({"failed_configs": failures} if failures else {}),
        }))
        return
    if (not results and cache_res is not None and egress_res is None
            and history_res is None
            and hotkeys_res is None):
        print(json.dumps({
            "metric": "route_cache_speedup[cfg6_cache_zipf]",
            "value": cache_res["zipf"]["speedup_cached"],
            "unit": "x_vs_uncached",
            "vs_baseline": cache_res["zipf"]["speedup_cached"],
            "hit_rate": cache_res["zipf"]["cached"].get("hit_rate"),
            **device_tag,
            "route_cache": cache_res,
            **({"telemetry_overhead": tele_res} if tele_res else {}),
            **({"overload_soak": overload_res} if overload_res else {}),
            **({"churn_soak": churn_res} if churn_res else {}),
            **({"failed_configs": failures} if failures else {}),
        }))
        return

    if devprof_res is not None and not devprof_res["ok"]:
        # surfaced as a failed config in the merged artifact; a standalone
        # --config 12 run (the CI gate) exits nonzero above
        failures["cfg12_devprof_overhead"] = (
            f"profiler overhead {devprof_res['overhead_pct']}% > "
            f"{devprof_res['bound_pct']}% bound")
    if hostprof_res is not None and not hostprof_res["ok"]:
        # same contract for the host-plane profiler (cfg14)
        failures["cfg14_hostprof_overhead"] = (
            f"host profiler overhead {hostprof_res['overhead_pct']}% > "
            f"{hostprof_res['bound_pct']}% bound")
    if history_res is not None and not history_res["ok"]:
        # same contract for the telemetry-history collector (cfg17)
        failures["cfg17_history_overhead"] = (
            f"history collector overhead {history_res['overhead_pct']}% > "
            f"{history_res['bound_pct']}% bound")
    if hotkeys_res is not None and not hotkeys_res["ok"]:
        # same contract for the hot-key attribution plane (cfg18)
        failures["cfg18_sketch_overhead"] = (
            f"hot-key sketch overhead {hotkeys_res['overhead_pct']}% > "
            f"{hotkeys_res['bound_pct']}% bound")

    # headline = the largest routing config that ran
    if not results:
        print(
            json.dumps(
                {
                    "metric": "publish_route_topics_per_sec",
                    "value": 0,
                    "unit": "topics/s",
                    "vs_baseline": 0,
                    **device_tag,
                    "error": failures or "no config ran",
                }
            )
        )
        sys.exit(1)
    for headline in ["cfg4_shared_10m_zipf", "cfg5_retained_10m", "cfg3_mixed_1m", "cfg2_plus_100k", "cfg1_exact_1k"]:
        if headline in results:
            break
    r = results[headline]
    # the headline is the ROUTER-LEVEL (hybrid) number when measured — the
    # throughput a broker user gets from the deployed XlaRouter; the raw
    # device figure rides alongside in every config entry
    head = r.get("router") or r["device"]
    head_speedup = r.get("router_speedup") or r["speedup"]
    out = {
        "metric": f"publish_route_topics_per_sec[{headline}]",
        "value": round(head["topics_per_sec"], 1),
        "unit": "topics/s",
        "vs_baseline": round(head_speedup, 2),
        "routes_per_sec": round(head["routes_per_sec"], 1),
        "p99_ms": round(head["p99_ms"], 2),
        "level": "router_hybrid" if r.get("router") else "device_raw",
        **device_tag,
        "baseline": r["baseline_kind"],
        "configs": {
            k: {
                # the device matcher's rate ON `platform` above — never a
                # chip figure unless platform says "tpu"
                "device_topics_per_sec": round(v["device"]["topics_per_sec"], 1),
                "matcher": v["matcher"],
                "cpu_topics_per_sec": round(v["cpu"]["topics_per_sec"], 1),
                "cpu_native_topics_per_sec": (
                    round(v["cpu_native"]["topics_per_sec"], 1) if v["cpu_native"] else None
                ),
                "speedup": round(v["speedup"], 2),
                "p99_ms": round(v["device"]["p99_ms"], 2),
                **({
                    "router_topics_per_sec": round(v["router"]["topics_per_sec"], 1),
                    "router_speedup": round(v["router_speedup"], 2),
                    "router_choice": v["router"].get("hybrid_choice"),
                    "router_p99_1topic_ms": round(
                        v["router"].get("p99_1topic_ms", 0.0), 3),
                } if v.get("router") else {}),
                **({"stream": v["stream"]} if "stream" in v else {}),
                **({"retained": v["retained"]} if "retained" in v else {}),
                **({"roofline_model": v["roofline_model"]}
                   if "roofline_model" in v else {}),
            }
            for k, v in results.items()
        },
        **({"route_cache": cache_res} if cache_res is not None else {}),
        # latency trajectory: p50/p99 for match + publish e2e (cfg7's
        # enabled run) so BENCH rounds track tails, not just throughput
        **({"telemetry_overhead": tele_res,
            "latency_ms": tele_res["latency_ms"]} if tele_res is not None else {}),
        # overload soak (cfg8): bounded-backlog + bounded-p99 evidence for
        # the overload controller, on vs off (broker/overload.py)
        **({"overload_soak": overload_res} if overload_res is not None else {}),
        # churn soak (cfg9): delta-upload traffic + p99-under-churn evidence
        # for the churn-resilient device table (ops/partitioned.py)
        **({"churn_soak": churn_res} if churn_res is not None else {}),
        # failover soak (cfg10): goodput dip + time-to-switchback evidence
        # for the device-plane failover (broker/failover.py)
        **({"failover_soak": failover_res} if failover_res is not None else {}),
        # small-batch paired estimator (cfg11): per-stage attribution of
        # the cfg1 regime, fused vs unfused (ops/partitioned.py)
        **({"smallbatch_paired": smallbatch_res}
           if smallbatch_res is not None else {}),
        # device-profiler overhead bound (cfg12): enabled-vs-disabled cost
        # of the [observability] device_profile knob (broker/devprof.py)
        **({"devprof_overhead": devprof_res}
           if devprof_res is not None else {}),
        # host-profiler overhead bound (cfg14): armed-vs-disarmed cost of
        # the [observability] host_profile knob (broker/hostprof.py)
        **({"hostprof_overhead": hostprof_res}
           if hostprof_res is not None else {}),
        # intra-node fabric paired estimator (cfg13): cross-worker fan-out
        # goodput fabric-vs-broadcast + per-leg CONNECT kick p99
        # (broker/fabric.py)
        **({"fabric_paired": fabric_res} if fabric_res is not None else {}),
        # autotune paired estimator (cfg15): autotune-vs-static goodput/p99
        # over the shifting-regime workload + the decision timeline
        # (broker/autotune.py)
        **({"autotune_paired": autotune_res}
           if autotune_res is not None else {}),
        # coalesced-egress paired estimator (cfg16): send-syscalls per
        # delivered message + fan-out goodput, coalesced vs legacy
        # per-frame writes (broker/egress.py)
        **({"egress_paired": egress_res} if egress_res is not None else {}),
        # history-collector overhead bound (cfg17): armed-vs-stopped cost
        # of the [observability] history knob at 100× production cadence
        # (broker/history.py)
        **({"history_overhead": history_res}
           if history_res is not None else {}),
        # hot-key sketch overhead bound (cfg18): armed-vs-disarmed cost
        # of the [observability] hotkeys knob at 60× production rotation
        # cadence (broker/hotkeys.py)
        **({"hotkeys_overhead": hotkeys_res}
           if hotkeys_res is not None else {}),
        **devprof_embed,
        **({"failed_configs": failures} if failures else {}),
    }
    print(json.dumps(out))
    if failures:
        # the artifact above carries what was measured and what failed; a
        # run in which any config failed is not a passing run
        sys.exit(1)


if __name__ == "__main__":
    main()
