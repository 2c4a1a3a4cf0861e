"""The north-star router: subscription matching on TPU.

Swaps the reference's trie DFS (`/root/reference/rmqtt/src/router.rs:174-265`)
for the batched XLA matcher over the flattened filter table in device HBM
(see `rmqtt_tpu.ops`). Publish ingress is micro-batched: `matches_batch()`
encodes B topics and resolves all of them in one kernel launch; matched
*filter ids* come back as packed bitmaps and are expanded host-side to
clients via the relations map — the same kernel/host split the reference
uses between trie and ``AllRelationsMap`` (router.rs:121-139), per
SURVEY.md §7 "hard parts".
"""

from __future__ import annotations

import logging
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from rmqtt_tpu.ops.encode import FilterTable
from rmqtt_tpu.ops.match import TpuMatcher
from rmqtt_tpu.utils.failpoints import FAILPOINTS
from rmqtt_tpu.router.base import (
    ClientId,
    Id,
    Router,
    SharedChoiceFn,
    SubRelationsMap,
    SubscriptionOptions,
    round_robin_choice_factory,
)
from rmqtt_tpu.router.relations import RelationsMap, expand_matches_raw


_LOG = logging.getLogger("rmqtt_tpu.router")


class _TreeSide:
    """Python-trie fallback for the hybrid mirror (NativeTrie API subset)."""

    def __init__(self, tree) -> None:
        self._tree = tree

    def add(self, topic_filter: str, fid: int) -> None:
        self._tree.insert(topic_filter, fid)

    def remove(self, topic_filter: str, fid: int) -> None:
        self._tree.remove(topic_filter, fid)

    def match(self, topic: str):
        # numpy is imported at module scope: this sits on the small-batch
        # dispatch path and must not pay a per-call import lookup
        vals = [v for _lv, vs in self._tree.matches(topic) for v in vs]
        return np.asarray(vals, dtype=np.int64)


class XlaRouter(Router):
    epochs_tracked = True  # add/remove bump the match-cache epochs

    def __init__(
        self,
        shared_choice: Optional[SharedChoiceFn] = None,
        is_online: Callable[[ClientId], bool] = lambda cid: True,
        table=None,
        device=None,
        backend: str = "partitioned",
        mesh="auto",
    ) -> None:
        """``mesh``: a ``jax.sharding.Mesh`` to data-parallelize the
        partitioned matcher over (batch sharded, table replicated);
        ``"auto"`` uses all devices when running on a multi-chip TPU slice
        (single-device and CPU-test environments keep the local matcher);
        ``None`` forces single-device."""
        if mesh not in (None, "auto") and (backend != "partitioned" or device is not None):
            raise ValueError(
                "mesh is only supported with backend='partitioned' and no "
                "explicit device (use parallel.ShardedMatcher for dense)"
            )
        # first backend touch of the process: takes the chip, raises when no
        # accelerator answers and the CPU was not asked for (utils/jaxenv.py)
        from rmqtt_tpu.utils.jaxenv import device_identity

        self.device_ident = device_identity()
        _LOG.info("device router on platform=%s device_kind=%s devices=%d",
                  self.device_ident["platform"],
                  self.device_ident["device_kind"],
                  self.device_ident["device_count"])
        if backend == "partitioned":
            from rmqtt_tpu.ops.partitioned import PartitionedMatcher, PartitionedTable

            self.table = table or PartitionedTable()
            use_mesh = None if mesh == "auto" else mesh
            if mesh == "auto" and device is None:
                import jax

                devs = jax.devices()
                if len(devs) > 1 and devs[0].platform == "tpu":
                    from rmqtt_tpu.parallel.sharded import make_mesh

                    use_mesh = make_mesh(devices=devs, dp=len(devs), fp=1)
            if use_mesh is not None:
                from rmqtt_tpu.parallel.sharded import ShardedPartitionedMatcher

                self.matcher = ShardedPartitionedMatcher(self.table, use_mesh)
            else:
                self.matcher = PartitionedMatcher(self.table, device=device)
        elif backend == "dense":
            self.table = table or FilterTable()
            self.matcher = TpuMatcher(self.table, device=device)
        else:
            raise ValueError(f"unknown backend {backend!r}")
        self._relations = RelationsMap()
        self._fid_to_filter: Dict[int, str] = {}
        self._filter_to_fid: Dict[str, int] = {}
        self._shared_choice = shared_choice or round_robin_choice_factory()
        self._is_online = is_online
        # small-batch hybrid: a host-side trie mirror answers sub-threshold
        # batches inline — one-topic publishes through the device path paid
        # a full dispatch round trip (broker p99 2.4x the trie router,
        # NOTES.md round 2); the device stays for bursts, where batching
        # amortizes the dispatch. Matches the per-message latency contract
        # of `/root/reference/rmqtt/src/shared.rs:735-820`.
        import os

        from rmqtt_tpu.ops.hybrid import AdaptiveHybrid

        self._hybrid_max = int(os.environ.get("RMQTT_HYBRID_MAX", "64"))
        # the mirror is built even with the hybrid fast path disabled
        # (RMQTT_HYBRID_MAX=0): it doubles as the failover plane's host
        # fallback table (broker/failover.py), which must stay maintained
        # precisely in the all-device regime where every batch depends on
        # the device router. Only the >200K Python-tree drop (add()) may
        # remove it.
        self._side = None
        self._side_native = False
        try:
            from rmqtt_tpu.runtime import NativeTrie

            self._side = NativeTrie()
            self._side_native = True
        except Exception as e:
            from rmqtt_tpu.core.trie import TopicTree

            _LOG.warning(
                "native runtime unavailable (%s); the host mirror is the "
                "Python trie — no adaptive hybrid, dropped above 200K filters "
                "(device_info().host_mirror says which one serves)", e)
            self._side = _TreeSide(TopicTree())
        # large batches route adaptively between the trie mirror and the
        # device (ops/hybrid.py): which path wins depends on table scale
        # and chip placement, so the hybrid measures instead of assuming.
        # Adaptivity needs the µs-scale NATIVE trie (the Python fallback
        # only serves the sub-threshold latency path); RMQTT_HYBRID_ADAPT=0
        # pins large batches to the device.
        probe = int(os.environ.get("RMQTT_PROBE_EVERY", "64"))
        if (self._hybrid_max <= 0 or not self._side_native
                or os.environ.get("RMQTT_HYBRID_ADAPT", "1") != "1"):
            # hybrid off pins large batches to the device (the mirror then
            # serves ONLY failover), and adaptivity needs the native trie
            probe = 0
        self._hybrid = AdaptiveHybrid(
            self._side, self.matcher, small_max=self._hybrid_max,
            probe_every=probe,
        )
        # fault-injection sites (utils/failpoints.py): the hybrid fires
        # them on its device branch (ops/hybrid.py) so trie-served batches
        # stay unaffected; the canary below fires them directly because it
        # bypasses the hybrid to exercise the device matcher on purpose
        self._fp_dispatch = FAILPOINTS.register("device.dispatch")
        self._fp_complete = FAILPOINTS.register("device.complete")
        from rmqtt_tpu.broker.telemetry import NULL_TELEMETRY

        self.use_telemetry(NULL_TELEMETRY)  # re-wired by ServerContext

    def add(self, topic_filter: str, id: Id, opts: SubscriptionOptions) -> None:
        if self._relations.add(topic_filter, id, opts):
            fid = self.table.add(topic_filter)
            self._fid_to_filter[fid] = topic_filter
            self._filter_to_fid[topic_filter] = fid
            if self._side is not None:
                if not self._side_native and len(self._fid_to_filter) > 200_000:
                    # the Python-trie fallback mirror would duplicate a
                    # million-filter table in dict nodes (GBs of host RAM)
                    # for a fast path that no longer is one — drop it; the
                    # device path serves every batch size
                    self._side = None
                    self._hybrid.side = None
                else:
                    self._side.add(topic_filter, fid)
        # version the match cache on real relations mutations (router base
        # epochs seam), not just device-table inserts; identical
        # re-subscribes don't bump
        if self._relations.last_add_changed:
            self.epochs.bump(topic_filter)

    def remove(self, topic_filter: str, id: Id) -> bool:
        existed, empty = self._relations.remove(topic_filter, id)
        if empty:
            fid = self._filter_to_fid.pop(topic_filter)
            del self._fid_to_filter[fid]
            self.table.remove(fid)
            if self._side is not None:
                self._side.remove(topic_filter, fid)
        if existed:
            self.epochs.bump(topic_filter)
        return existed

    def inline_ok(self, batch_size: int) -> bool:
        # hybrid-served batches on the C++ trie are µs-scale: run them on
        # the event loop. The Python-tree fallback still answers small
        # batches without a device round trip (matches_batch_raw), but its
        # ms-scale DFS must keep the executor hop off the event loop.
        return (self._side is not None and self._side_native
                and batch_size <= self._hybrid_max)

    def matches_raw(self, from_id: Optional[Id], topic: str):
        return self.matches_batch_raw([(from_id, topic)])[0]

    def use_telemetry(self, tele) -> None:
        """Wired by ServerContext beside ``router.telemetry``: the busy
        stages of the match path (broker/telemetry.py ``Stage``) — the
        relations expansion here, the hybrid's two backends and its compile
        worker, the device matcher's four sections."""
        self._st_expand = tele.stage("routing.expand")
        self._hybrid.use_stages(
            tele.stage("routing.match.side"), tele.stage("routing.match.device"),
            tele.stage("matcher.compile_offpath") if tele.enabled else None)
        use = getattr(self.matcher, "use_telemetry", None)
        if use is not None:
            use(tele)

    def matches_batch_raw(self, items: Sequence[Tuple[Optional[Id], str]]):
        topics = [topic for _, topic in items]
        tele = self.telemetry
        on = tele is not None and tele.enabled
        seq = tele.batch_begin() if on else 0
        try:
            return self._expand(items, self._hybrid.match(topics, on))
        finally:
            if seq:
                tele.batch_end(seq)

    def _expand(self, items, fid_rows):
        """Matched fids → per-item relations: the ``routing.expand`` stage,
        on whichever thread the match ran."""
        tele = self.telemetry
        tok = (self._st_expand.begin(len(items))
               if tele is not None and tele.enabled else 0)
        out = []
        f2f = self._fid_to_filter
        for (from_id, _topic), fids in zip(items, fid_rows):
            matched = [f2f[fid] for fid in fids.tolist()]
            out.append(
                expand_matches_raw(matched, self._relations, from_id, self._is_online)
            )
        if tok:
            self._st_expand.end(tok)
        return out

    # pipelined halves (RoutingService overlap): submit encodes + dispatches,
    # complete fetches + expands — batch N+1's submit runs while batch N is
    # still on the device, cutting burst p99 from sum-of-stages to ~max-stage.
    # submit returns (True, results) when the hybrid served the batch
    # synchronously from the host trie (no pipeline slot needed), else
    # (False, handle) for complete_batch_raw.
    def submit_batch_raw(self, items: Sequence[Tuple[Optional[Id], str]]):
        items = list(items)
        topics = [topic for _, topic in items]
        tele = self.telemetry
        on = tele is not None and tele.enabled
        seq = tele.batch_begin() if on else 0
        try:
            h = self._hybrid.match_submit(topics, on)
            if h[0] == "sync":
                return True, self._expand(items, h[1])
            # async device dispatch: the device stage closes at complete time
            return False, (items, h, on, seq)
        finally:
            if seq:
                tele.batch_end(seq)

    def complete_batch_raw(self, handle):
        items, h, on, seq = handle
        if seq:
            self.telemetry.batch_begin(seq)
        try:
            return self._expand(items, self._hybrid.match_complete(h, on))
        finally:
            if seq:
                self.telemetry.batch_end(seq)

    def prewarm(self) -> None:
        """Latch the device matcher's sticky pad floor and pre-compile its
        dispatch shape so the first lone publishes after start don't pay
        an XLA compile. Called by RoutingService.start()
        on a background thread; safe no-op for matchers without the hook
        or before any subscription exists (compiles are shape-keyed, so
        warming an empty table still covers the live shapes)."""
        m = getattr(self, "matcher", None)
        if m is not None and hasattr(m, "prewarm"):
            m.prewarm()

    def set_hybrid_max(self, n: int) -> int:
        """Knob seam (broker/knobs.py): move the trie-vs-device batch
        threshold live — both the inline_ok gate and the hybrid's own
        small_max, which must agree or sub-threshold batches would take
        the executor hop without the trie fast path. → the old value."""
        old = self._hybrid_max
        self._hybrid_max = max(0, int(n))
        self._hybrid.set_small_max(self._hybrid_max)
        return old

    def last_match_was_device(self) -> bool:
        """Did the most recent (synchronously resolved) match run on the
        DEVICE matcher? The routing service consults this before crediting
        a success to the failover breaker — the hybrid's trie-served
        batches say nothing about device health."""
        return self._hybrid.last_backend == "device"

    # ---- host fallback plane (device-plane failover, broker/failover.py).
    # The trie mirror is updated synchronously on every add/remove, so the
    # fallback routes against the CURRENT table — its only staleness is the
    # >200K-filter regime where the Python-tree mirror is dropped (then
    # host_available() is False and failover cannot engage).
    def host_available(self) -> bool:
        return self._side is not None

    def host_inline_ok(self) -> bool:
        # the native trie is µs-scale: run failover batches on the event
        # loop; the Python-tree fallback keeps the executor hop
        return self._side_native

    def host_matches_batch_raw(self, items: Sequence[Tuple[Optional[Id], str]]):
        """Match a batch via the host trie mirror ONLY — no device dispatch,
        no device failpoints. This is the degraded-but-correct routing path
        the failover plane serves publishes through while the breaker around
        the device router is open."""
        side = self._side
        if side is None:
            raise RuntimeError("no host-side trie mirror to fail over to")
        topics = [topic for _, topic in items]
        if len(topics) > 1 and hasattr(side, "match_batch"):
            rows = side.match_batch(list(topics))
        else:
            rows = [side.match(t) for t in topics]
        return self._expand(items, rows)

    def device_rewarm(self) -> None:
        """Force the next device refresh down the FULL pack+upload path
        (half-open probe prelude): the table's layout-epoch bump closes the
        delta gate, so no delta journal state from before the outage can be
        scattered into a table whose device mirror may be gone or torn."""
        t = self.table
        if hasattr(t, "force_full_refresh"):
            t.force_full_refresh()

    def canary_topics(self, k: int = 3) -> List[str]:
        """Concrete topics derived from up to ``k`` live filters (wildcards
        substituted with a literal level) so the failover canary compares
        NON-EMPTY rows whenever the table has routes — a static unmatched
        topic would make the device-vs-trie oracle vacuously pass on a
        device that recovered into silently-wrong matches. ``$``-prefixed
        filters are skipped (their first level has special match rules);
        an empty result tells the caller to fall back to its static topic."""
        out: List[str] = []
        for filt in self._filter_to_fid:
            if len(out) >= k:
                break
            if filt.startswith("$"):
                continue
            out.append("/".join(
                "canary" if lvl in ("+", "#") else lvl
                for lvl in filt.split("/")))
        return out

    def device_canary(self, topics: Sequence[str]) -> bool:
        """One canary match through the DEVICE matcher (bypassing the
        hybrid's trie routing), checked against the host trie oracle. The
        device failpoints stay armed here so a still-injected fault keeps
        the breaker open; the first canary after ``device_rewarm`` performs
        the full HBM re-upload."""
        if self._fp_dispatch.action is not None:
            self._fp_dispatch.fire_sync()
        rows = self.matcher.match(list(topics))
        if self._fp_complete.action is not None:
            self._fp_complete.fire_sync()
        if self._side is None:
            return True
        for topic, fids in zip(topics, rows):
            want = np.sort(np.asarray(self._side.match(topic), dtype=np.int64))
            got = np.sort(np.asarray(fids, dtype=np.int64))
            if want.shape != got.shape or not np.array_equal(want, got):
                return False
        return True

    def device_stats(self) -> Dict[str, float]:
        """Device-table lifecycle counters for RoutingService.stats():
        upload/compaction activity of the HBM mirror (delta vs full, bytes
        shipped, background compactions and their cost, selective
        candidate-cache invalidations)."""
        m, t = self.matcher, self.table
        # per-stage wall attribution (PR9 stage_timing, promoted from
        # bench-only to the live stats surface): cumulative ns → ms totals,
        # zeros while stage_timing is off (the dict exists either way)
        sn = getattr(m, "stage_ns", None) or {}
        return {
            "uploads": getattr(m, "uploads", 0),
            "delta_uploads": getattr(m, "delta_uploads", 0),
            "upload_bytes": getattr(m, "upload_bytes", 0),
            "compactions": getattr(t, "compactions", 0),
            "compact_ms": round(getattr(t, "compact_ms", 0.0), 3),
            "cand_cache_invalidations": getattr(t, "cand_cache_invalidations", 0),
            # topics encoded for the device / those whose candidate chunks
            # per-topic Python resolved (0 while the native encoder serves)
            "encode_topics": getattr(t, "encode_topics_total", 0),
            "encode_host_resolved": getattr(t, "encode_host_resolved", 0),
            # batches served end-to-end by the fused device pipeline
            # (ops/partitioned.py): nonzero proves host decode is off the
            # per-batch path
            "fused_batches": getattr(m, "fused_batches", 0),
            "stage_encode_ms_total": round(sn.get("encode", 0) / 1e6, 3),
            "stage_dispatch_ms_total": round(sn.get("dispatch", 0) / 1e6, 3),
            "stage_fetch_ms_total": round(sn.get("fetch", 0) / 1e6, 3),
            "stage_decode_ms_total": round(sn.get("decode", 0) / 1e6, 3),
        }

    def device_info(self) -> Dict[str, object]:
        """What serves matches in this process, for ``/api/v1/device``: the
        device as JAX reports it, the matcher class (and how many devices
        its mesh spans), the words producer in use and why, and which host
        mirror backs the hybrid/failover plane."""
        from rmqtt_tpu.utils.jaxenv import compile_cache_stats

        m = self.matcher
        mesh = getattr(m, "mesh", None)
        return {
            **self.device_ident,
            "matcher": type(m).__name__,
            "mesh_devices": int(mesh.devices.size) if mesh is not None else 1,
            "words_producer": {"name": "lax",
                               "why": "the only producer of this matcher"},
            "host_mirror": ("native" if self._side_native
                            else "python" if self._side is not None
                            else "none"),
            "hybrid_max": self._hybrid_max,
            # [batches, topics] each backend served, and the hybrid's
            # current large-batch choice (None until both paths are timed)
            "hybrid_served": {k: list(v)
                              for k, v in self._hybrid.served.items()},
            "hybrid_choice": self._hybrid.choice,
            # per backend: times _bump replaced the rate EMA outright, and
            # large batches sent to the slower path to refresh its EMA
            "hybrid_regime_jumps": dict(self._hybrid.regime_jumps),
            "hybrid_probes": dict(self._hybrid.probes),
            # large batches the hybrid routed, and [batches, topics] of
            # them the mirror answered because their device program was
            # still being compiled off the routing path (ops/hybrid.py)
            "hybrid_large_batches": self._hybrid.large_batches,
            "hybrid_compiling_side": list(self._hybrid.compiling_side),
            # 1 while a program is compiled off the routing path (the
            # worker thread ``rmqtt-compile`` lives), else 0
            "compiles_under_way": self._hybrid.compiles_under_way,
            "compile_cache": compile_cache_stats(),
        }

    def device_hbm(self) -> Dict[str, float]:
        """HBM occupancy model of the device table mirror (tiles, fid map,
        segments) — the device profiler's provider seam
        (broker/devprof.py); {} for matchers without a breakdown."""
        f = getattr(self.matcher, "hbm_breakdown", None)
        return f() if callable(f) else {}

    def is_match(self, topic: str) -> bool:
        if self._side is not None:
            return self._side.match(topic).size > 0
        (fids,) = self.matcher.match([topic])
        return fids.size > 0

    def gets(self, limit: int) -> List[dict]:
        out: List[dict] = []
        for tf, rels in self._relations.items():
            for cid in rels:
                if len(out) >= limit:
                    return out
                out.append({"topic_filter": tf, "client_id": cid})
        return out

    def subscribers_count(self, topic_filter: str, exclude_client=None) -> int:
        rels = self._relations.get(topic_filter)
        n = len(rels)
        if exclude_client is not None and exclude_client in rels:
            n -= 1
        return n

    def topics_count(self) -> int:
        return len(self._relations)

    def routes_count(self) -> int:
        return self._relations.edge_count
