"""Partitioned automaton: trie-style pruning flattened for the TPU.

The dense matcher scans every filter row per topic; the reference's trie
wins by pruning on the first levels (`/root/reference/rmqtt/src/trie.rs`
DFS only descends matching branches). This module flattens exactly that
pruning into static-shaped TPU compute:

Filters are bucketed by their first two levels into *partitions*
(NOTES.md design):

- ``("#",)``      — the bare ``#`` filter;
- ``("1", k0)``   — single-level filters (k0 = token or ``+``);
- ``("2", k0)``   — ``<k0>/#`` (prefix length 1);
- ``("3", k0, k1)`` — everything else, k0/k1 ∈ {token, ``+``}.

A publish topic (t0, t1, …) can only match filters in ≤7 partitions:
``#``, ``t0/#``, ``+/#``, (t0,t1), (t0,+), (+,t1), (+,+) — plus the
single-level partitions when the topic has one level. Each partition owns
fixed-size row *chunks* (``CHUNK`` rows) in the flat table, so churn is O(1)
and the kernel sees a per-topic list of chunk ids: one `lax.scan` step
gathers a [B, CHUNK] row tile per candidate chunk, applies the same level
formula as `ops.match`, and packs words; the fused tail compacts them
batch-globally, resolves rows to filter ids and sorts per topic on the
device. Per-topic work drops from O(F) to O(candidate rows) — the trie's
pruning, with dense regular tiles.
"""

from __future__ import annotations

import bisect
import functools
import logging
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from rmqtt_tpu.utils.failpoints import FAILPOINTS

#: chaos seam shared by every device-table mirror (PartitionedMatcher,
#: TpuMatcher, the sharded variants): fires when an HBM refresh — delta
#: scatter or full pack+put — is about to run (utils/failpoints.py)
_FP_UPLOAD = FAILPOINTS.register("device.upload")

#: device-plane profiler (broker/devprof.py): every jit entry seam below
#: goes through ``_pj``, which reports hit-vs-trace when it is enabled and
#: calls straight through when it is not
from rmqtt_tpu.broker.devprof import DEVPROF as _DEVPROF, NeedsCompile
from rmqtt_tpu.broker.telemetry import Stage as _Stage

_STAGE_KEYS = ("encode", "dispatch", "fetch", "decode")


def _pj(kernel: str, fn, *args, **kwargs):
    """One jit-seam call: ``fn(*args, **kwargs)``, profiled while the
    device profiler is enabled.
    The shape key mirrors jax's own executable-cache signature, so a
    never-seen key is a trace+compile by construction and the timed wall
    of that first call brackets its cost (jit traces synchronously): that
    call is the ``matcher.compile`` stage (broker/telemetry.py), on the
    same clock pair — where it compiles on the caller's own path.

    ``_key_extra`` (reserved, not forwarded to ``fn``) appends static
    state that is baked into the CALLABLE rather than its arguments —
    e.g. the sharded per-budget step closures, where arg shapes alone are
    identical across budget regrows but each regrow is a real recompile."""
    extra = kwargs.pop("_key_extra", None)
    if not _DEVPROF.enabled:
        return fn(*args, **kwargs)
    key = _DEVPROF.key_of(args, kwargs)
    if extra is not None:
        key = key + (extra,)
    # a never-seen key on a thread that may not compile (the hybrid's
    # routing path) is handed back; one compiled off the path is no time of
    # the path's (DeviceProfiler.compiles)
    fresh = not _DEVPROF.seen(kernel, key)
    rule = _DEVPROF.compile_rule() if fresh else None
    if rule == "forbid":
        raise NeedsCompile(kernel, key)
    tele = _DEVPROF.telemetry
    if fresh and rule is None and tele is not None and tele.enabled:
        st = tele.stage("matcher.compile")
        tok = st.begin()
        try:
            out = fn(*args, **kwargs)
        finally:
            dur = st.end(tok)
    else:
        t0 = time.perf_counter_ns()
        out = fn(*args, **kwargs)
        dur = time.perf_counter_ns() - t0
    _DEVPROF.note_jit(kernel, key, dur)
    return out

from rmqtt_tpu.core.topic import HASH, PLUS, is_metadata, split_levels
from rmqtt_tpu.ops.encode import (
    _FIRST_TOK,
    HASH_TOK,
    PACKED_MAX_LEVELS,
    PACKED_W1_MAX,
    PACKED_W2_MAX,
    PAD_TOK,
    PLUS_TOK,
    DeltaLog,
    PackedLayout,
    TokenDict,
    UNK_TOK,
    group_byte_planes,
)
from rmqtt_tpu.utils.devfetch import fetch

# module-scope logger: _refresh/_decide_fused sit on the dispatch path and
# must not pay a per-call `import logging`
_LOG = logging.getLogger("rmqtt_tpu.ops")

CHUNK = 128  # rows per partition chunk (4 packed words)
WORDS_PER_CHUNK = CHUNK // 32

# partition key kinds
_K_HASH = ("#",)


class _CompactState:
    """A fully-built compacted physical layout, ready to swap in."""

    __slots__ = ("arrays", "fid_of_row", "row_of_fid", "cap_chunks", "nchunks",
                 "excl_chunks", "excl_free", "shared_chunks_of",
                 "shared_rows_of", "shared_free", "open_shared", "spread")


def _build_compact_state(
    key_of: Dict[int, Tuple], row_of: Dict[int, int], arrays, max_lvl: int,
) -> _CompactState:
    """Gather a snapshot of live rows into a fresh compacted layout.

    Runs WITHOUT the table lock: ``key_of``/``row_of`` are point-in-time
    copies and ``arrays`` are references to the then-current host arrays.
    Rows of fids mutated after the snapshot may be read torn here — the
    install step re-writes exactly those fids from journal data."""
    tok_a, flen_a, pl_a, hh_a, fw_a = arrays
    by_key: Dict[Tuple, List[int]] = {}
    for fid, key in key_of.items():
        by_key.setdefault(key, []).append(fid)
    keys_sorted = sorted(by_key, key=repr)
    src_rows: List[int] = []
    fids_ordered: List[int] = []
    for key in keys_sorted:
        for fid in by_key[key]:
            fids_ordered.append(fid)
            src_rows.append(row_of[fid])
    src = np.asarray(src_rows, dtype=np.int64)
    n = len(src)
    need_chunks = 1 + (n + CHUNK - 1) // CHUNK + 1
    cap = 64
    while cap < need_chunks:
        cap *= 2
    st = _CompactState()
    st.cap_chunks = cap
    rows = cap * CHUNK
    tok = np.zeros((rows, max_lvl), dtype=np.int32)
    flen = np.full((rows,), -1, dtype=np.int32)
    pl = np.zeros((rows,), dtype=np.int32)
    hh = np.zeros((rows,), dtype=bool)
    fw = np.zeros((rows,), dtype=bool)
    dst = np.arange(CHUNK, CHUNK + n, dtype=np.int64)  # chunk 0 stays empty
    tok[dst] = tok_a[src, :max_lvl]
    flen[dst] = flen_a[src]
    pl[dst] = pl_a[src]
    hh[dst] = hh_a[src]
    fw[dst] = fw_a[src]
    st.arrays = (tok, flen, pl, hh, fw)
    fid_arr = np.asarray(fids_ordered, dtype=np.int64)
    fid_of_row = np.full(rows, -1, dtype=np.int64)
    fid_of_row[dst] = fid_arr
    st.fid_of_row = fid_of_row
    st.row_of_fid = {int(f): int(r) for f, r in zip(fid_arr, dst)}
    # partition structures: spanned chunks per key. Partitions below one
    # chunk stay classified as SHARED-resident so later adds keep packing
    # instead of each claiming a fresh exclusive chunk (which would
    # re-create the sparse layout the compaction just removed).
    st.excl_chunks = {}
    st.excl_free = {}
    st.shared_chunks_of = {}
    st.shared_rows_of = {}
    st.shared_free = {}
    st.open_shared = []
    st.spread = 0
    pos = CHUNK
    for key in keys_sorted:
        k = len(by_key[key])
        first_chunk = pos // CHUNK
        last_chunk = (pos + k - 1) // CHUNK
        if k < CHUNK:
            krows = list(range(pos, pos + k))
            st.shared_rows_of[key] = krows
            occ: Dict[int, int] = {}
            for r in krows:
                occ[r // CHUNK] = occ.get(r // CHUNK, 0) + 1
            st.shared_chunks_of[key] = occ
            st.spread += len(occ) > 1
        else:
            st.excl_chunks[key] = list(range(first_chunk, last_chunk + 1))
        pos += k
    st.nchunks = (pos + CHUNK - 1) // CHUNK
    # the tail of the last chunk is unowned free space: future adds for
    # any key fall through _alloc_row's shared path
    tail_start = pos
    tail_end = st.nchunks * CHUNK
    if tail_end > tail_start:
        st.shared_free[st.nchunks - 1] = list(range(tail_end - 1, tail_start - 1, -1))
        st.open_shared.append(st.nchunks - 1)
    return st


def partition_key(levels: Sequence[str]) -> Tuple:
    """Partition of a (stripped, validated) filter.

    Depth-3 keys: measurement showed the depth-2 wildcard-wildcard bucket
    dominates candidate counts (NOTES.md), so filters deep enough are split
    by their third level too:

    - ``("#",)``            bare ``#``
    - ``("1", k0)``         single-level filters
    - ``("2", k0)``         ``k0/#``
    - ``("2E", k0, k1)``    exactly two levels, no ``#``
    - ``("H3", k0, k1)``    ``k0/k1/#``
    - ``("4", k0, k1, k2)`` three or more levels (k2 = third level)

    with every ``k`` ∈ {token, ``+``}.
    """
    f0 = levels[0]
    if f0 == HASH:
        return _K_HASH
    k0 = PLUS if f0 == PLUS else f0
    if len(levels) == 1:
        return ("1", k0)
    if levels[1] == HASH:
        return ("2", k0)
    k1 = PLUS if levels[1] == PLUS else levels[1]
    if len(levels) == 2:
        return ("2E", k0, k1)
    if levels[2] == HASH:
        return ("H3", k0, k1)
    k2 = PLUS if levels[2] == PLUS else levels[2]
    return ("4", k0, k1, k2)


def topic_partitions(levels: Sequence[str]) -> List[Tuple]:
    """Candidate partitions for a publish topic (≤15, most tiny)."""
    t0 = levels[0]
    n = len(levels)
    out: List[Tuple] = [_K_HASH, ("2", t0), ("2", PLUS)]
    if n == 1:
        out += [("1", t0), ("1", PLUS)]
        return out
    t1 = levels[1]
    pairs = ((t0, t1), (t0, PLUS), (PLUS, t1), (PLUS, PLUS))
    for a, b in pairs:
        out.append(("H3", a, b))
    if n == 2:
        for a, b in pairs:
            out.append(("2E", a, b))
        return out
    t2 = levels[2]
    for a, b in pairs:
        out.append(("4", a, b, t2))
        out.append(("4", a, b, PLUS))
    return out


class PartitionedTable:
    """Flat filter-row arrays with partition-chunked allocation.

    Chunk 0 is reserved empty (the padding target for per-topic chunk lists).

    Small partitions PACK INTO SHARED CHUNKS: with depth-3 keys most
    partitions hold a handful of rows, and giving each its own chunk
    collapsed occupancy to ~2% at 1M filters (NOTES.md). A partition starts
    inside shared chunks (foreign rows in a candidate chunk cost a little
    compute — the match formula simply rejects them — not memory); once it
    accumulates a full chunk's worth of rows it migrates to exclusive
    chunks. Filter ids are therefore STABLE HANDLES decoupled from row
    positions (`fid ↔ row` maps), so migration never breaks the router.
    """

    def __init__(self, max_levels: int = 8) -> None:
        self.max_levels = max_levels
        self.nchunks = 1  # chunk 0 = reserved empty
        self._cap_chunks = 64
        self._alloc(self._cap_chunks, max_levels)
        self.tokens = TokenDict()
        # partition key → exclusive chunk ids / shared chunk ids it occupies
        self._excl_chunks: Dict[Tuple, List[int]] = {}
        self._shared_chunks_of: Dict[Tuple, Dict[int, int]] = {}  # cid → row count
        # how many partitions occupy MORE than one shared chunk: kept where
        # an occupancy gains or loses a chunk, read by _layout_is_tight
        self._spread = 0
        # free row slots inside partition-exclusive chunks
        self._excl_free: Dict[Tuple, List[int]] = {}
        # shared-chunk pool: cid → free row slots; _open_shared lists chunk
        # ids that still have free slots (O(1) allocation)
        self._shared_free: Dict[int, List[int]] = {}
        self._open_shared: List[int] = []
        self._key_of_fid: Dict[int, Tuple] = {}
        # stable fid ↔ physical row
        self._row_of_fid: Dict[int, int] = {}
        self._fid_of_row: np.ndarray = np.full(self._cap_chunks * CHUNK, -1, dtype=np.int64)
        self._next_fid = 0
        # rows of a partition currently living in shared chunks
        self._shared_rows_of: Dict[Tuple, List[int]] = {}
        self.size = 0
        self.version = 0
        self.dirty_ops = 0  # mutations since the last compact()
        # --- churn resilience (delta uploads / double buffer / bg compact)
        # one lock covers mutations, encode's layout walks, delta packing
        # and the compaction *install*; the compaction *build* runs outside
        # it so the dispatch path never waits on a table rebuild
        self._mu = threading.RLock()
        # bumped whenever the physical chunk layout changes wholesale
        # (compact): chunk ids encoded under one epoch must never meet a
        # device table from another
        self.layout_epoch = 0
        # dirty-CHUNK journal: matchers scatter-write only these chunks
        self.delta = DeltaLog()
        # fid-map undo journal for in-flight match handles: (version,
        # epoch, row, old_fid) — a handle submitted at version V decodes
        # rows through the fid map AS OF V by patching back newer writes
        self._fid_undo_v: List[int] = []
        self._fid_undo_e: List[int] = []
        self._fid_undo_row: List[int] = []
        self._fid_undo_old: List[int] = []
        self._fid_undo_max = 65536
        self._fid_undo_floor = 0
        # background-compaction machinery
        self.compact_async = True  # matcher-triggered compaction off-thread
        self.compact_min_ops = 1024
        self.compact_ratio = 5  # trigger above max(min_ops, size // ratio)
        self.compactions = 0
        self.compact_ms = 0.0
        self.compact_aborts = 0
        self._compacting = False
        self._compact_thread: Optional[threading.Thread] = None
        # serializes whole compactions (a sync compact() racing an async
        # one must run after it, not interleave journal/install phases)
        self._compact_lock = threading.Lock()
        # mutation journal recorded while a compaction build is in flight:
        # ('a', fid, key, levels) / ('r', fid, key) / ('m', fid) — replayed
        # against the freshly built layout at install time
        self._compact_journal: Optional[List[Tuple]] = None
        # transient per-mutation dirty set (chunks touched by the op)
        self._txn: Optional[List[int]] = None
        self._undo_pending: List[Tuple[int, int]] = []
        # python encoder's per-(t0[,t1[,t2]]) candidate cache: key ->
        # (chunk ids, gid); invalidated SELECTIVELY: partition key -> cache
        # keys consulting it, so a mutation only drops the entries it could
        # affect. The native encoder walks its partition mirror per topic
        # instead and keeps no such cache.
        self._cand_cache: Dict[Tuple, Tuple[np.ndarray, int]] = {}
        self._cand_keys_of: Dict[Tuple, Set] = {}
        self._gid_seq = 0
        self.cand_cache_invalidations = 0
        # size bound: selective invalidation means entries for never-mutated
        # partitions would otherwise accumulate forever under high-
        # cardinality publish streams; past the cap the cache (and the
        # key registry, which also holds invalidated-entry tombstones)
        # clears wholesale — cheap and rare
        self.cand_cache_max = 65536
        # native (C++) encoder: None = not tried yet, False = unavailable
        self._nenc = None
        # partition keys mutated since the native mirror was last synced
        # (pushed in one call by the next encode; never a call per mutation)
        self._parts_dirty: Set[Tuple] = set()
        # topics encoded for the device / those whose candidates per-topic
        # Python resolved (all under _encode_py, none under the native path)
        self.encode_topics_total = 0
        self.encode_host_resolved = 0
        self._nc_cap = 32
        # narrow dtypes while ids fit: halves the per-batch host→device
        # upload of ttok/chunk_ids AND the device
        # tiles' gather traffic (pack_device_rows shares _tok_wide, so the
        # bound is int16's, not uint16's); STICKY once widened so the jit
        # signature flips at most once each
        self._tok_wide = False
        self._cand_wide = False
        # --- bit-packed tile support (level-local token id spaces).
        # Every (level, global id) pair a filter row uses is assigned a
        # LOCAL id at write time; per-level LUT arrays translate global →
        # local for both tile packing and topic encode. Widths are sticky
        # grow-only (1 byte while a level's vocab fits 252 tokens, then 2);
        # a level past 65532 tokens disables the packed format for good.
        self.packed_ok = True
        self._lvl_counts: List[int] = [0] * max_levels
        self._lvl_widths: List[int] = [1] * max_levels
        self._lvl_luts: List[np.ndarray] = [
            self._new_lut() for _ in range(max_levels)
        ]
        # grow-only count of levels that carry token information (max
        # prefix_len over live rows); compaction recomputes the true max
        self._eff_levels = 1

    @staticmethod
    def _new_lut(cap: int = 1024) -> np.ndarray:
        lut = np.full((cap,), UNK_TOK, dtype=np.int32)
        lut[:_FIRST_TOK] = np.arange(_FIRST_TOK)  # reserved ids map to selves
        return lut

    def _register_level(self, level: int, gid: int) -> None:
        """Assign (level, global id) its local id on first use. Caller holds
        the table lock (all row writes do)."""
        if gid < _FIRST_TOK:
            return
        lut = self._lvl_luts[level]
        if gid >= len(lut):
            cap = len(lut)
            while cap <= gid:
                cap *= 2
            grown = np.full((cap,), UNK_TOK, dtype=np.int32)
            grown[: len(lut)] = lut
            self._lvl_luts[level] = lut = grown
        if lut[gid] != UNK_TOK:
            return
        n = self._lvl_counts[level] + 1
        self._lvl_counts[level] = n
        lut[gid] = _FIRST_TOK - 1 + n
        if n > PACKED_W1_MAX:
            self._lvl_widths[level] = 2
        if n > PACKED_W2_MAX:
            self.packed_ok = False

    def packed_layout(self) -> Optional[PackedLayout]:
        """Static descriptor of the current bit-packed tile layout, or None
        when the table is not packable (too-deep filters / a level's vocab
        past two bytes). Compared by VALUE: any width/depth change yields a
        different layout, which the delta-upload gate treats as a wholesale
        relayout (full re-upload)."""
        if not self.packed_ok or self.max_levels > PACKED_MAX_LEVELS:
            return None
        eff = min(max(self._eff_levels, 1), self.max_levels)
        return PackedLayout(tuple(self._lvl_widths[:eff]))

    def translate_packed(self, ttok: np.ndarray):
        """→ ``(layout, ttok_local [B, layout.nlvl] int32)`` — topic tokens
        re-keyed into the per-level local id spaces (unknown-at-level →
        ``UNK_TOK``, which is exactly right: no filter row carries that
        token at that level, so only wildcards can match it). Returns
        ``(None, None)`` when the table is not packable. Runs under the
        table lock so the layout and LUT contents are captured together."""
        with self._mu:
            layout = self.packed_layout()
            if layout is None:
                return None, None
            nlvl = layout.nlvl
            out = np.empty((ttok.shape[0], nlvl), dtype=np.int32)
            for i in range(nlvl):
                lut = self._lvl_luts[i]
                g = ttok[:, i].astype(np.int64, copy=False)
                out[:, i] = np.where(
                    g < len(lut), lut[np.minimum(g, len(lut) - 1)], UNK_TOK
                )
            return layout, out

    def _tok_dtype(self):
        if not self._tok_wide and _FIRST_TOK + len(self.tokens) >= 0x7FFF:
            self._tok_wide = True
        return np.int32 if self._tok_wide else np.int16

    def _cand_dtype(self):
        if not self._cand_wide and self.nchunks >= 0x10000:
            self._cand_wide = True
        return np.int32 if self._cand_wide else np.uint16

    # ------------------------------------------------------------- storage
    def _alloc(self, cap_chunks: int, lvl: int) -> None:
        rows = cap_chunks * CHUNK
        self.tok = np.zeros((rows, lvl), dtype=np.int32)
        self.flen = np.full((rows,), -1, dtype=np.int32)
        self.prefix_len = np.zeros((rows,), dtype=np.int32)
        self.has_hash = np.zeros((rows,), dtype=bool)
        self.first_wild = np.zeros((rows,), dtype=bool)

    def _grow(self, need_chunks: int, need_levels: int) -> None:
        new_cap = self._cap_chunks
        while new_cap < need_chunks:
            new_cap *= 2
        new_lvl = max(need_levels, self.max_levels)
        if new_cap == self._cap_chunks and new_lvl == self.max_levels:
            return
        old = (self.tok, self.flen, self.prefix_len, self.has_hash, self.first_wild,
               self._fid_of_row)
        old_rows, old_lvl = self._cap_chunks * CHUNK, self.max_levels
        self._cap_chunks, self.max_levels = new_cap, new_lvl
        for _ in range(old_lvl, new_lvl):
            self._lvl_counts.append(0)
            self._lvl_widths.append(1)
            self._lvl_luts.append(self._new_lut())
        self._alloc(new_cap, new_lvl)
        self._fid_of_row = np.full(new_cap * CHUNK, -1, dtype=np.int64)
        self.tok[:old_rows, :old_lvl] = old[0]
        self.flen[:old_rows] = old[1]
        self.prefix_len[:old_rows] = old[2]
        self.has_hash[:old_rows] = old[3]
        self.first_wild[:old_rows] = old[4]
        self._fid_of_row[:old_rows] = old[5]

    def _new_chunk(self) -> int:
        cid = self.nchunks
        self.nchunks += 1
        if self.nchunks > self._cap_chunks:
            self._grow(self.nchunks, self.max_levels)
        return cid

    def _alloc_row(self, key: Tuple) -> int:
        """Pick a physical row for a new filter of this partition."""
        # 1) free slot in one of the partition's exclusive chunks
        free = self._excl_free.get(key)
        if free:
            return free.pop()
        shared_rows = self._shared_rows_of.setdefault(key, [])
        excl = self._excl_chunks.get(key)
        if excl or len(shared_rows) + 1 >= CHUNK:
            # partition is (or becomes) big: use exclusive chunks; migrate
            # any shared-resident rows into the new chunk first
            cid = self._new_chunk()
            base = cid * CHUNK
            self._excl_chunks.setdefault(key, []).append(cid)
            slots = list(range(base, base + CHUNK))
            for src in shared_rows:
                dst = slots.pop(0)
                self._move_row(src, dst)
            shared_rows.clear()
            if len(self._shared_chunks_of.pop(key, ())) > 1:
                self._spread -= 1
            self._excl_free[key] = slots[1:][::-1]
            return slots[0]
        # 2) small partition: take a slot in a shared chunk, preferring
        # chunks this partition already occupies (keeps its candidate
        # chunk-set small)
        row = None
        occ = self._shared_chunks_of.setdefault(key, {})
        for cid in occ:
            free_slots = self._shared_free.get(cid)
            if free_slots:
                row = free_slots.pop()
                break
        if row is None:
            while self._open_shared:
                cid = self._open_shared[-1]
                free_slots = self._shared_free.get(cid)
                if free_slots:
                    row = free_slots.pop()
                    break
                self._open_shared.pop()  # exhausted chunk
            else:
                cid = self._new_chunk()
                base = cid * CHUNK
                self._shared_free[cid] = list(range(base + CHUNK - 1, base, -1))
                self._open_shared.append(cid)
                row = base
        shared_rows.append(row)
        cid = row // CHUNK
        if cid in occ:
            occ[cid] += 1
        else:
            occ[cid] = 1
            if len(occ) == 2:
                self._spread += 1
        return row

    def _free_shared_slot(self, row: int) -> None:
        cid = row // CHUNK
        slots = self._shared_free.setdefault(cid, [])
        if not slots:
            self._open_shared.append(cid)
        slots.append(row)

    def _move_row(self, src: int, dst: int) -> None:
        self.tok[dst] = self.tok[src]
        self.flen[dst] = self.flen[src]
        self.prefix_len[dst] = self.prefix_len[src]
        self.has_hash[dst] = self.has_hash[src]
        self.first_wild[dst] = self.first_wild[src]
        fid = int(self._fid_of_row[src])
        if self._txn is not None:
            # migration inside a mutation: both chunks changed on device,
            # and both fid-map cells need undo entries for in-flight handles
            self._txn.append(src // CHUNK)
            self._txn.append(dst // CHUNK)
            self._undo_pending.append((dst, int(self._fid_of_row[dst])))
            self._undo_pending.append((src, fid))
            if self._compact_journal is not None:
                self._compact_journal.append(("m", fid))
        self._fid_of_row[dst] = fid
        self._row_of_fid[fid] = dst
        self._clear_row(src)
        self._free_shared_slot(src)

    def _clear_row(self, row: int) -> None:
        self.tok[row, :] = PAD_TOK
        self.flen[row] = -1
        self.prefix_len[row] = 0
        self.has_hash[row] = False
        self.first_wild[row] = False
        self._fid_of_row[row] = -1

    # ------------------------------------------------ mutation bookkeeping
    def _begin_txn(self) -> None:
        self._txn = []
        self._undo_pending: List[Tuple[int, int]] = []

    def _finish_txn(self, key: Tuple) -> None:
        """Flush one mutation's tracking: version bump, dirty-chunk marks,
        fid-map undo entries, and selective candidate-cache invalidation."""
        self.version += 1
        self.dirty_ops += 1
        v, e = self.version, self.layout_epoch
        for cid in set(self._txn):
            self.delta.mark(v, cid)
        for row, old_fid in self._undo_pending:
            self._fid_undo_v.append(v)
            self._fid_undo_e.append(e)
            self._fid_undo_row.append(row)
            self._fid_undo_old.append(old_fid)
        if len(self._fid_undo_v) > self._fid_undo_max:
            half = self._fid_undo_max // 2
            self._fid_undo_floor = self._fid_undo_v[half - 1]
            del self._fid_undo_v[:half]
            del self._fid_undo_e[:half]
            del self._fid_undo_row[:half]
            del self._fid_undo_old[:half]
        self._txn = None
        self._undo_pending = []
        if self._nenc:
            self._parts_dirty.add(key)
        self._invalidate_cand(key)

    def _invalidate_cand(self, key: Tuple) -> None:
        """Drop only the candidate-cache entries whose partition key set
        includes the mutated key (everything else stays warm)."""
        cache_keys = self._cand_keys_of.pop(key, None)
        if not cache_keys:
            return
        n = 0
        cache = self._cand_cache
        for ck in cache_keys:
            if cache.pop(ck, None) is not None:
                n += 1
        self.cand_cache_invalidations += n

    def _register_cand(self, levels: Sequence[str], cache_key: Tuple) -> None:
        """Record which partition keys a cached candidate set consulted."""
        for key in topic_partitions(levels):
            self._cand_keys_of.setdefault(key, set()).add(cache_key)

    def fid_overlay(self, version: int, epoch: int):
        """→ ``(overlay, ok)`` for a match handle submitted at (version,
        epoch): ``overlay`` maps physical row → the fid it held AT that
        version (undone past the newer in-place writes). ``ok=False`` means
        the undo journal no longer reaches back that far — the caller must
        decode best-effort against the live map (dropping cleared rows)."""
        with self._mu:
            if version >= self.version:
                return {}, True
            if version < self._fid_undo_floor:
                return {}, False
            i = bisect.bisect_right(self._fid_undo_v, version)
            ov: Dict[int, int] = {}
            for j in range(i, len(self._fid_undo_v)):
                if self._fid_undo_e[j] != epoch:
                    continue
                row = self._fid_undo_row[j]
                if row not in ov:  # first write after `version` wins
                    ov[row] = self._fid_undo_old[j]
            return ov, True

    # ----------------------------------------------------------------- API
    def add(self, topic_filter: str | Sequence[str]) -> int:
        levels = split_levels(topic_filter) if isinstance(topic_filter, str) else list(topic_filter)
        with self._mu:
            nlev = len(levels)
            if nlev > self.max_levels:
                self._grow(self._cap_chunks, nlev)
            key = partition_key(levels)
            self._begin_txn()
            row = self._alloc_row(key)
            self._write_row(row, levels)
            fid = self._next_fid
            self._next_fid += 1
            self._key_of_fid[fid] = key
            self._row_of_fid[fid] = row
            self._txn.append(row // CHUNK)
            self._undo_pending.append((row, int(self._fid_of_row[row])))
            self._fid_of_row[row] = fid
            self.size += 1
            if self._compact_journal is not None:
                self._compact_journal.append(("a", fid, key, list(levels)))
            self._finish_txn(key)
            return fid

    def _write_row(self, row: int, levels: Sequence[str]) -> None:
        """Fill one physical row's data from filter levels."""
        tok_row = self.tok[row]
        tok_row[:] = PAD_TOK
        for i, lev in enumerate(levels):
            if lev == PLUS:
                tok_row[i] = PLUS_TOK
            elif lev == HASH:
                tok_row[i] = HASH_TOK
            else:
                gid = self.tokens.intern(lev)
                tok_row[i] = gid
                self._register_level(i, gid)
        nlev = len(levels)
        hh = levels[-1] == HASH
        self.flen[row] = nlev
        self.prefix_len[row] = nlev - 1 if hh else nlev
        self.has_hash[row] = hh
        self.first_wild[row] = levels[0] in (PLUS, HASH)
        prefix = nlev - 1 if hh else nlev
        if prefix > self._eff_levels:
            self._eff_levels = prefix

    def remove(self, fid: int) -> None:
        with self._mu:
            key = self._key_of_fid.pop(fid, None)
            if key is None:
                raise KeyError(f"fid {fid} not active")
            self._begin_txn()
            row = self._row_of_fid.pop(fid)
            self._txn.append(row // CHUNK)
            self._undo_pending.append((row, fid))
            self._release_row(key, row)
            self.size -= 1
            if self._compact_journal is not None:
                self._compact_journal.append(("r", fid, key))
            self._finish_txn(key)

    def _release_row(self, key: Tuple, row: int) -> None:
        """Clear a physical row and return its slot to the right free list."""
        self._clear_row(row)
        cid = row // CHUNK
        occ = self._shared_chunks_of.get(key)
        if occ is not None and cid in occ:
            # row lived in a shared chunk
            occ[cid] -= 1
            if occ[cid] == 0:
                del occ[cid]
                if len(occ) == 1:
                    self._spread -= 1
            self._shared_rows_of[key].remove(row)
            self._free_shared_slot(row)
        else:
            self._excl_free.setdefault(key, []).append(row)

    def needs_compact(self) -> bool:
        """Churn threshold at which the fragmented layout is worth a
        rebuild (the former ``encode_topics`` inline trigger)."""
        return self.dirty_ops > max(self.compact_min_ops, self.size // self.compact_ratio)

    def force_full_refresh(self) -> None:
        """Invalidate every device mirror's delta state: the next refresh
        must re-pack and re-upload the WHOLE table (device-plane failover
        rewarm, broker/failover.py — after an outage the HBM copy may be
        gone or torn, so no pre-outage delta may ever be scattered into
        it). The layout itself is unchanged — rows stay put — so the epoch
        bump only closes the delta gate; encode caches keyed on the epoch
        re-validate lazily (encode_topics' cache_epoch check)."""
        with self._mu:
            self.version += 1
            self.layout_epoch += 1
            self.delta.reset(self.version)

    def compact(self) -> None:
        """Synchronous rebuild (build + install). In the broker this never
        runs on the dispatch path: ``PartitionedMatcher.match_submit``
        triggers ``maybe_compact_async()`` instead, which runs the build on
        a background thread while matching continues against the old
        layout, then installs atomically."""
        th = self._compact_thread
        if th is not None and th.is_alive() and th is not threading.current_thread():
            th.join()  # background rebuild already in flight: let it land
            return
        self._compact()

    def maybe_compact_async(self) -> bool:
        """Kick off a background compaction if churn warrants one."""
        if not self.needs_compact():
            return False
        with self._mu:
            if self._compacting:
                return False
            if self._layout_is_tight():
                self.dirty_ops = 0  # nothing to rebuild: the churn is spent
                return False
            self._compacting = True
        try:
            th = threading.Thread(
                target=self._compact_bg, name="rmqtt-table-compact", daemon=True
            )
            self._compact_thread = th
            th.start()
        except Exception as e:
            # thread exhaustion must not latch _compacting (disabling
            # compaction forever) nor fail the dispatch that triggered it;
            # the next trigger retries
            self._compacting = False
            _LOG.warning("background compaction thread failed to start: %s", e)
            return False
        return True

    def _layout_is_tight(self) -> bool:
        """Could a rebuild tighten nothing? So where no partition has chunks
        of its own, every partition sits in ONE shared chunk, and the
        chunks are full bar the last: a topic's candidate set cannot
        shrink and no chunk can be saved. A bulk load of small partitions
        packs that way by itself — 1,000,000 two-level exact filters are
        1,000,000 one-row partitions in 7,813 full chunks — and its rebuild
        (17 s of Python per million partitions, then a re-upload of the
        whole table) would land in the first minute of traffic for
        nothing. Three compares: it is asked on the dispatch path, under
        ``self._mu`` (the caller holds it)."""
        if self._excl_chunks or (self.nchunks - 2) * CHUNK >= self.size:
            return False  # nchunks counts the reserved empty chunk 0
        return not self._spread

    def _compact_bg(self) -> None:
        try:
            self._compact()
        except Exception:  # pragma: no cover - defensive
            _LOG.exception("background table compaction failed")
        finally:
            self._compacting = False

    def _compact(self) -> None:
        """Rebuild the physical layout: each partition's rows contiguous,
        partitions packed back-to-back (boundary chunks shared between
        neighbors). Restores ~100% occupancy and minimal candidate chunk
        sets after bulk loads/churn; filter ids are stable across the move.

        Two phases: the BUILD gathers a snapshot of every live row into a
        fresh set of arrays without holding the table lock (mutations that
        land meanwhile are journaled), then the INSTALL swaps the new
        layout in under the lock and replays the journal. The old
        ``_fid_of_row`` array object is left untouched, so match handles
        submitted against the old layout keep decoding correctly."""
        t0 = time.perf_counter()
        with self._compact_lock:
            with self._mu:
                key_of = dict(self._key_of_fid)
                row_of = dict(self._row_of_fid)
                arrays = (self.tok, self.flen, self.prefix_len, self.has_hash,
                          self.first_wild)
                max_lvl = self.max_levels
                self._compact_journal = []
            try:
                state = _build_compact_state(key_of, row_of, arrays, max_lvl)
            except Exception:
                with self._mu:
                    self._compact_journal = None
                raise
            with self._mu:
                journal = self._compact_journal or []
                self._compact_journal = None
                if self.max_levels != max_lvl:
                    # a deeper filter landed mid-build: the built rows are
                    # too narrow — abort; the next trigger rebuilds at the
                    # new width
                    self.compact_aborts += 1
                    return
                self._install_compact(state, journal)
            self.compactions += 1
            self.compact_ms += (time.perf_counter() - t0) * 1e3

    def _install_compact(self, state: "_CompactState", journal: List[Tuple]) -> None:
        """Swap the built layout in and replay the build-window journal.
        Caller holds ``self._mu``."""
        # net journal effects + row data captured from the still-live old
        # layout (always consistent under the lock; the build-phase copies
        # of journal-touched fids may be torn)
        adds: Dict[int, Tuple[Tuple, List[str]]] = {}
        removed: Dict[int, Tuple] = {}
        moved: Dict[int, Optional[Tuple[Tuple, List[str]]]] = {}
        for op in journal:
            if op[0] == "a":
                adds[op[1]] = (op[2], op[3])
            elif op[0] == "r":
                removed[op[1]] = op[2]
                adds.pop(op[1], None)
                moved.pop(op[1], None)
            else:  # 'm': migrated by a concurrent add — data may be torn
                if op[1] not in adds:
                    moved[op[1]] = None
        for fid in list(moved):
            moved[fid] = (self._key_of_fid[fid], self._filter_of_fid(fid))
        # atomic swap: arrays + partition maps + fid maps change together
        (self.tok, self.flen, self.prefix_len, self.has_hash,
         self.first_wild) = state.arrays
        self._fid_of_row = state.fid_of_row
        self._row_of_fid = state.row_of_fid
        self._cap_chunks = state.cap_chunks
        self.nchunks = state.nchunks
        self._excl_chunks = state.excl_chunks
        self._excl_free = state.excl_free
        self._shared_chunks_of = state.shared_chunks_of
        self._spread = state.spread
        self._shared_rows_of = state.shared_rows_of
        self._shared_free = state.shared_free
        self._open_shared = state.open_shared
        # replay: mutations that landed during the build
        for fid, key in removed.items():
            row = self._row_of_fid.pop(fid, None)
            if row is not None:
                self._release_row(key, row)
        for fid, (key, levels) in adds.items():
            row = self._alloc_row(key)
            self._write_row(row, levels)
            self._row_of_fid[fid] = row
            self._fid_of_row[row] = fid
        for fid, kl in moved.items():
            row = self._row_of_fid.get(fid)
            if row is not None and kl is not None:
                self._write_row(row, kl[1])  # heal a possibly-torn copy
        # compaction is the one point where _eff_levels may legally SHRINK
        # (it is grow-only between compactions): the install already forces
        # every mirror down the full-upload path, so a narrower packed
        # layout costs nothing extra here
        rows = self.nchunks * CHUNK
        live = self.prefix_len[:rows][self._fid_of_row[:rows] >= 0]
        self._eff_levels = max(1, int(live.max())) if live.size else 1
        # epoch bump + invalidations land in the same locked region, so
        # matchers can never pair stale chunk ids with the new device table
        self.dirty_ops = len(journal)
        self.layout_epoch += 1
        self.version += 1
        self.delta.reset(self.version)
        self._cand_cache.clear()
        self._cand_keys_of.clear()
        # the epoch bump makes the next native encode resync its partition
        # mirror wholesale
        self._parts_dirty.clear()

    def _filter_of_fid(self, fid: int) -> List[str]:
        """Decode a live fid's filter levels back from the row data."""
        row = self._row_of_fid[fid]
        strs = self.tokens._strs
        out: List[str] = []
        for tok in self.tok[row, : int(self.flen[row])].tolist():
            if tok == PLUS_TOK:
                out.append(PLUS)
            elif tok == HASH_TOK:
                out.append(HASH)
            else:
                out.append(strs[tok - _FIRST_TOK])
        return out

    # -------------------------------------------------------- topic encode
    def _candidates_for(self, levels: Sequence[str]) -> np.ndarray:
        """Candidate chunk ids for a topic prefix (partition-map walk)."""
        chunks: List[int] = []
        seen: set = set()  # partitions share boundary/shared chunks
        for key in topic_partitions(levels):
            for cid in self._excl_chunks.get(key, ()):
                if cid not in seen:
                    seen.add(cid)
                    chunks.append(cid)
            occ = self._shared_chunks_of.get(key)
            if occ:
                for cid in occ:
                    if cid not in seen:
                        seen.add(cid)
                        chunks.append(cid)
        return np.asarray(chunks, dtype=np.int32)

    def encode_topics(
        self, topics: Sequence[str | Sequence[str]], pad_batch_to: Optional[int] = None,
        with_groups: bool = False,
    ):
        """→ (ttok, tlen, tdollar, chunk_ids [B, NC], nc)
        (+ ``groups`` [B] int32 when ``with_groups``).

        ``chunk_ids`` lists each topic's candidate chunks padded with the
        reserved empty chunk 0; NC is the batch max (padded to a power of
        two to bound recompiles). ``groups`` assigns topics sharing one
        candidate-cache entry the same positive id (0 = padded row): the
        matcher can then upload each distinct candidate row once (zipf
        publish streams share a few hot prefixes across the whole batch).
        """
        # NOTE: no inline compact() here — heavy churn used to trigger a
        # stop-the-world rebuild on the dispatch path; compaction now runs
        # in the background (maybe_compact_async, triggered from
        # PartitionedMatcher.match_submit) and swaps in atomically.
        return self.encode_topics_versioned(topics, pad_batch_to, with_groups)[0]

    def encode_topics_versioned(
        self, topics: Sequence[str | Sequence[str]],
        pad_batch_to: Optional[int] = None, with_groups: bool = False,
    ):
        """``(encode tuple, layout_epoch)`` captured atomically — matchers
        compare this epoch with their device snapshot's to detect a
        compaction installing between encode and refresh. Returned (not
        stashed on the table) so two matchers sharing one table can't
        clobber each other's epoch reads."""
        if self._nenc is None:
            try:
                from rmqtt_tpu.runtime import NativeEncoder

                self._nenc = NativeEncoder()
            except (RuntimeError, OSError):
                self._nenc = False
        with self._mu:
            epoch = self.layout_epoch
            if self._nenc:
                return self._encode_native(topics, pad_batch_to, with_groups), epoch
            return self._encode_py(topics, pad_batch_to, with_groups), epoch

    def _encode_py(
        self, topics: Sequence[str | Sequence[str]], pad_batch_to: Optional[int],
        with_groups: bool = False,
    ):
        batch = len(topics)
        self.encode_topics_total += batch
        self.encode_host_resolved += batch
        b = pad_batch_to or batch
        lvl = self.max_levels
        tlen = np.full((b,), -2, dtype=np.int16)
        tdollar = np.zeros((b,), dtype=bool)
        tok_rows: List[List[int]] = []
        per_topic_chunks: List[np.ndarray] = []
        lookup = self.tokens.lookup
        # the cache is invalidated SELECTIVELY at mutation time
        # (_invalidate_cand): entries whose partition keys a mutation never
        # touched survive version bumps
        if len(self._cand_cache) >= self.cand_cache_max:
            self._cand_cache.clear()
            self._cand_keys_of.clear()
        cache = self._cand_cache
        groups = np.full((b,), -1, dtype=np.int32)
        for j, topic in enumerate(topics):
            levels = split_levels(topic) if isinstance(topic, str) else list(topic)
            # clamp: every stored flen/prefix_len is <= max_levels, so any
            # deeper topic compares identically at lvl+1 — and the clamp
            # keeps int16 safe for arbitrarily deep (hostile) topics
            tlen[j] = min(len(levels), lvl + 1)
            tdollar[j] = bool(levels[0]) and is_metadata(levels[0])
            row = [lookup(lev) for lev in levels[:lvl]]
            row += [PAD_TOK] * (lvl - len(row))
            tok_rows.append(row)
            # candidate chunks: cached per effective prefix — topics share
            # these heavily (the wildcard partitions are common to all).
            # The key must cover every level the partition scheme inspects
            # (1, 2 or 3 depending on topic depth).
            ckey = tuple(levels[:3]) if len(levels) >= 3 else tuple(levels)
            ckey = (len(ckey),) + ckey
            ent = cache.get(ckey)
            if ent is None:
                # monotonic gid (NOT len(cache)): selective invalidation
                # means ids of evicted entries must never be reissued to a
                # different candidate set while survivors still carry them
                ent = (self._candidates_for(levels), self._gid_seq)
                self._gid_seq += 1
                cache[ckey] = ent
                self._register_cand(levels, ckey)
            cand, gid = ent
            groups[j] = gid
            per_topic_chunks.append(cand)
        ttok = np.zeros((b, lvl), dtype=self._tok_dtype())
        if batch:
            ttok[:batch] = np.asarray(tok_rows, dtype=np.int64).astype(ttok.dtype)
        mx = max((len(c) for c in per_topic_chunks), default=1)
        # sticky pow2 NC (grow-only per table): a light batch after a heavy
        # one must not flip the kernel signature back and forth
        self._nc_cap = max(self._nc_cap, 1 << (max(1, mx) - 1).bit_length())
        nc = self._nc_cap
        chunk_ids = np.zeros((b, nc), dtype=self._cand_dtype())  # 0 = empty chunk
        for j, chunks in enumerate(per_topic_chunks):
            chunk_ids[j, : len(chunks)] = chunks
        if with_groups:
            return ttok, tlen, tdollar, chunk_ids, nc, groups + 1  # padded -> 0
        return ttok, tlen, tdollar, chunk_ids, nc

    def _sync_native(self, enc) -> None:
        """Bring the native token and partition mirrors up to date: one call
        for the new tokens and two or three for the partitions, however
        much was mutated since the last encode. Caller holds the table
        lock."""
        toks = self.tokens._strs
        if enc.tokens_synced < len(toks):
            enc.add_tokens(toks[enc.tokens_synced:], _FIRST_TOK + enc.tokens_synced)
            enc.tokens_synced = len(toks)
        # per key: exclusive chunks first, then (appended) the shared ones —
        # _candidates_for's order
        if enc.parts_epoch != self.layout_epoch:
            # compaction install (or first use): the layout changed wholesale
            enc.parts_clear()
            enc.parts_put(self._excl_chunks.keys(), self._excl_chunks.values(), False)
            enc.parts_put(self._shared_chunks_of.keys(),
                          self._shared_chunks_of.values(), True)
            enc.parts_epoch = self.layout_epoch
        elif self._parts_dirty:
            keys = list(self._parts_dirty)
            enc.parts_put(keys, [self._excl_chunks.get(k, ()) for k in keys], False)
            shared = {k: occ for k in keys if (occ := self._shared_chunks_of.get(k))}
            if shared:
                enc.parts_put(shared.keys(), shared.values(), True)
        self._parts_dirty.clear()

    def _encode_native(
        self, topics: Sequence[str | Sequence[str]], pad_batch_to: Optional[int],
        with_groups: bool = False,
    ):
        """C++ hot path for ``encode_topics`` (runtime/encode.cc): tokenize
        and resolve every topic's candidate chunks natively, against a
        mirror of the partition maps, in ONE call for the batch — no Python
        and no native call per topic, however many prefixes are new."""
        enc = self._nenc
        batch = len(topics)
        self.encode_topics_total += batch
        b = pad_batch_to or batch
        lvl = self.max_levels
        self._sync_native(enc)
        try:
            blob = ("\x00".join(topics) + "\x00").encode()
        except TypeError:  # some topic came as a level sequence
            blob = ("\x00".join(
                [t if isinstance(t, str) else "/".join(t) for t in topics]
            ) + "\x00").encode()
        while True:
            nc_cap = self._nc_cap
            ttok = np.zeros((b, lvl), dtype=np.int32)
            tlen = np.full((b,), -2, dtype=np.int32)
            tdollar = np.zeros((b,), dtype=np.uint8)
            cand = np.zeros((b, nc_cap), dtype=np.int32)
            counts = np.zeros((b,), dtype=np.int32)
            group = np.full((b,), -1, dtype=np.int32)  # padded rows stay -1
            mx = enc.encode(
                blob, batch, lvl, ttok, tlen, tdollar, nc_cap, cand, counts, group,
            )
            nc = max(1, 1 << (max(1, mx) - 1).bit_length())  # pow2 bucket
            if nc > nc_cap:
                self._nc_cap = nc  # sticky: grows, never shrinks
                continue
            # the C ABI fills int32; shrink for upload when ids fit (the
            # narrowing copy is ~0.5ms/16K for half the bytes shipped).
            # tlen clamps like the python path: comparisons are invariant
            # beyond lvl+1 and hostile topic depths must not wrap int16
            out = (ttok.astype(self._tok_dtype(), copy=False),
                   np.minimum(tlen, lvl + 1).astype(np.int16, copy=False),
                   tdollar.view(bool),
                   cand.astype(self._cand_dtype(), copy=False), nc_cap)
            return out + (group + 1,) if with_groups else out  # padded -> 0


def scan_words_impl(packed_rows, ttok, tlen, tdollar, chunk_ids):
    """lax.scan partitioned match → packed words [B, NC*WPC] uint32.

    ``packed_rows`` is chunk-tiled FIELD-MAJOR ``[nchunks, L+3, CHUNK]``
    (see ``pack_device_rows``: the CHUNK-minor layout keeps HBM tiles
    un-padded) — per-chunk field rows of level tokens followed by (flen,
    prefix_len, hash|wild flags); each scan step issues ONE whole-tile
    gather by leading-axis index (measured ~40× faster on TPU than
    row-granular gathers, and one big gather beats five small ones —
    NOTES.md). Word w of topic b covers rows
    ``chunk_ids[b, w // WPC]*CHUNK + (w % WPC)*32 .. +31`` — the host maps
    set bits back to global fids.
    """
    b, nc = chunk_ids.shape
    lvl = packed_rows.shape[1] - 3
    # inputs may arrive narrow (int16 tokens, uint16 chunk ids, int16 tlen) to
    # halve the host→device transfer; widen on device
    ttok = ttok.astype(jnp.int32)
    tlen = tlen.astype(jnp.int32)
    chunk_ids = chunk_ids.astype(jnp.int32)
    lvl_idx = jnp.arange(lvl, dtype=jnp.int32)
    bit = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))

    def body(_, cid):  # cid: [B]
        g = packed_rows[cid]  # [B, L+3, CHUNK] single tile gather
        ftok_g = g[:, :lvl, :]
        flen_g = g[:, lvl, :]
        pl_g = g[:, lvl + 1, :]
        flags = g[:, lvl + 2, :]
        hh_g = (flags & 1) != 0
        fw_g = (flags & 2) != 0
        eq = ftok_g == ttok[:, :, None]
        plus = ftok_g == PLUS_TOK
        beyond = lvl_idx[None, :, None] >= pl_g[:, None, :]
        prefix_ok = jnp.all(eq | plus | beyond, axis=1)  # [B, CHUNK]
        len_ok = jnp.where(hh_g, tlen[:, None] >= pl_g, tlen[:, None] == flen_g)
        dollar_ok = jnp.logical_not(tdollar[:, None] & fw_g)
        m = prefix_ok & len_ok & dollar_ok
        packed = jnp.sum(
            m.reshape(b, WORDS_PER_CHUNK, 32).astype(jnp.uint32) * bit[None, None, :],
            axis=-1,
            dtype=jnp.uint32,
        )
        return None, packed  # [B, WPC]

    _, words = lax.scan(body, None, jnp.moveaxis(chunk_ids, 0, 1))  # [NC, B, WPC]
    return jnp.moveaxis(words, 0, 1).reshape(b, nc * WORDS_PER_CHUNK)


def _packed_plane(tile, k: int):
    """Byte plane ``k`` of a flat packed tile ``[.., groups*CHUNK]`` int32
    (four planes per lane, little-endian; see pack_device_rows_packed)."""
    grp, sh = k // 4, (k % 4) * 8
    x = tile[..., grp * CHUNK : (grp + 1) * CHUNK]
    if sh:
        x = x >> sh
    return x & 0xFF


def scan_words_packed_impl(packed32, ttok, tlen, tdollar, chunk_ids, *,
                           layout: PackedLayout):
    """``scan_words_impl`` over BIT-PACKED tiles → packed words
    ``[B, NC*WPC]`` uint32, bitwise identical to the legacy path on the
    same table state (the interp-mode property tests pin this).

    ``packed32`` is the flat ``[up_chunks, groups*CHUNK]`` int32 array
    (``pack_device_rows_packed``); ``ttok`` carries LEVEL-LOCAL token ids
    (``PartitionedTable.translate_packed``), so each level compares against
    its own ≤2-byte id space. Levels beyond ``layout.nlvl`` are omitted
    entirely — every live row's prefix ends at or before ``nlvl`` (grow-only
    ``_eff_levels``), so those comparisons are always-true ``beyond`` terms
    in the legacy formula. The per-step gather shrinks from
    ``(L+3)*CHUNK*2`` bytes to ``groups*CHUNK*4`` — the bytes-moved
    reduction ``scripts/roofline.py`` models."""
    b, nc = chunk_ids.shape
    ttok = ttok.astype(jnp.int32)
    tlen = tlen.astype(jnp.int32)
    chunk_ids = chunk_ids.astype(jnp.int32)
    bit = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
    offs = layout.plane_offsets()
    meta_p = layout.planes - 1

    def body(_, cid):  # cid: [B]
        g = packed32[cid]  # [B, G*CHUNK] single tile gather
        meta = _packed_plane(g, meta_p)
        flen_g = (meta & 31) - 1  # empty rows encode flen+1 = 0
        hh_g = (meta >> 5) & 1
        fw_g = (meta >> 6) & 1
        pl_g = flen_g - hh_g
        ok = jnp.ones((b, CHUNK), dtype=jnp.bool_)
        for i, w in enumerate(layout.widths):
            f = _packed_plane(g, offs[i])
            if w == 2:
                f = f | (_packed_plane(g, offs[i] + 1) << 8)
            eq = f == ttok[:, i, None]
            plus = f == PLUS_TOK
            beyond = pl_g <= i
            ok = ok & (eq | plus | beyond)
        len_ok = jnp.where(hh_g == 1, tlen[:, None] >= pl_g,
                           tlen[:, None] == flen_g)
        dollar_ok = jnp.logical_not(tdollar[:, None] & (fw_g == 1))
        m = ok & len_ok & dollar_ok
        packed = jnp.sum(
            m.reshape(b, WORDS_PER_CHUNK, 32).astype(jnp.uint32) * bit[None, None, :],
            axis=-1,
            dtype=jnp.uint32,
        )
        return None, packed  # [B, WPC]

    _, words = lax.scan(body, None, jnp.moveaxis(chunk_ids, 0, 1))
    return jnp.moveaxis(words, 0, 1).reshape(b, nc * WORDS_PER_CHUNK)


def words_any_impl(tiles, ttok, tlen, tdollar, chunk_ids, *, layout=None):
    """The one words-producer seam: the lax scan over legacy or packed
    tiles, statically selected by ``layout``.

    Every operation it lowers to carries the named scope ``scan`` (the
    tail's are ``compact`` / ``resolve`` / ``sort`` / ``counts``): metadata
    only — the compiled program is the same — by which a profiler trace's
    device time is summed per phase (PERF.md §3)."""
    with jax.named_scope("scan"):
        if layout is None:
            return scan_words_impl(tiles, ttok, tlen, tdollar, chunk_ids)
        return scan_words_packed_impl(tiles, ttok, tlen, tdollar, chunk_ids,
                                      layout=layout)


def compact_global_impl(words, budget: int):
    """Packed words [B, W] → batch-global ROUTE-level compaction.

    A per-topic fixed-width compaction must fetch the worst topic's slots
    for EVERY topic — measured 32 slots against a batch average of ~6
    nonzero words at 1M subs, so >80% of the device→host transfer is
    padding.
    And the measured word occupancy is ~1.12 set bits, so even compacted
    (key, bits) words cost ~7 bytes per route. Here the whole batch shares
    one ``budget`` of per-ROUTE slots, filled in two stages:

    1. word compaction — an exclusive prefix sum over the nonzero-word
       mask assigns each nonzero word a slot; disjoint scatters pack
       (word-index-within-topic, bits) into budget-sized arrays;
    2. route expansion — only the COMPACTED words ([budget, 32] bit
       matrix, ~33 MB at the measured budgets, vs [B, W, 32] for the raw
       batch) are expanded bit-wise; a second prefix sum packs one
       ``widx*32 + bitpos`` uint16 per set bit.

    Slot order is flat (topic-major, then word, then bit) by
    construction, so per-topic route counts are enough to reattribute
    slots on the host: the wire is 2 bytes per route + 2 per topic —
    ~3.8x less device→host transfer than the (key, bits) format at the
    measured match rates. Overflow (cnts.sum() > budget) drops entries
    on-device; the caller re-runs with a wider sticky budget (route
    count >= word count, so one check covers both stages).

    Routes and counts return CONCATENATED as one array: each host fetch
    of a device array is its own blocking round trip, so two arrays per
    match would pay it twice per batch.

    → packed [budget + B] uint16|uint32: [routes..., cnts...]
    """
    b, w = words.shape
    with jax.named_scope("compact"):
        flat = words.ravel()
        nz = flat != jnp.uint32(0)
        nzi = nz.astype(jnp.int32)
        pos = jnp.cumsum(nzi) - nzi  # exclusive prefix sum
        # non-nz (and overflow) slots land at index==budget → dropped. The
        # sentinel index is duplicated across every zero word, so this
        # scatter must NOT claim unique_indices (implementation-defined
        # corruption on backends that exploit the flag before dropping OOB
        # updates).
        idx = jnp.where(nz & (pos < budget), pos, budget)
        wsrc = lax.broadcasted_iota(jnp.int32, (b, w), 1).ravel()
        widx = jnp.zeros((budget,), jnp.int32).at[idx].set(wsrc, mode="drop")
        bits = jnp.zeros((budget,), jnp.uint32).at[idx].set(flat, mode="drop")
    # stage 2: expand the compacted words' bits into route slots
    with jax.named_scope("resolve"):
        bitm = (bits[:, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
        rnzi = bitm.astype(jnp.int32).ravel()  # [budget*32]
        rpos = jnp.cumsum(rnzi) - rnzi
        ridx = jnp.where((rnzi > 0) & (rpos < budget), rpos, budget)
        # one dtype for routes AND counts (they ship as one array); strict <
        # because a count can reach w*32 itself (a topic matching every row)
        rdt = jnp.uint16 if w * 32 < 0x10000 else jnp.uint32
        rval = (
            widx[:, None] * 32 + jnp.arange(32, dtype=jnp.int32)
        ).ravel().astype(rdt)
        routes = jnp.zeros((budget,), rdt).at[ridx].set(rval, mode="drop")
    with jax.named_scope("counts"):
        cnts = jnp.sum(lax.population_count(words).astype(jnp.int32), axis=1)
        return jnp.concatenate([routes, cnts.astype(rdt)])


def match_global_impl(packed_rows, ttok, tlen, tdollar, chunk_ids, budget: int,
                      layout=None):
    """Gather-based partitioned match → global-compact packed [budget+B]."""
    words = words_any_impl(packed_rows, ttok, tlen, tdollar, chunk_ids,
                           layout=layout)
    # same compile-time fence as match_fused_impl (220 s unfenced at 16K)
    words = lax.optimization_barrier(words)
    return compact_global_impl(words, budget)


def match_global_grouped_impl(packed_rows, ttok, tlen, tdollar, uniq_cand, inv,
                              budget: int, layout=None):
    """Global match with DEDUPLICATED candidate rows: upload [U, NC] distinct
    rows + a [B] inverse instead of [B, NC] (zipf publish streams share a
    few hot prefixes across the whole batch); the full per-topic chunk-id
    matrix is rebuilt by one device gather."""
    chunk_ids = uniq_cand[inv.astype(jnp.int32)]
    return match_global_impl(packed_rows, ttok, tlen, tdollar, chunk_ids,
                             budget, layout)


def match_global_split_impl(packed_rows, parts, budgets, layout=None):
    """NC split-dispatch: the scan costs B×NC tile gathers, but measured
    batches average ~7 candidate chunks against an NC=32 pad — most of the
    device compute was padding (NOTES.md). Topics are bucketed host-side by
    candidate count into a short NC-tier ladder; each bucket scans only its
    tier's chunks. One jit call runs every bucket and concatenates the
    per-bucket compacted outputs, so the batch still costs ONE dispatch and
    ONE fetch (each extra fetch is one more blocking round trip).

    ``parts``: per bucket ``(ttok, tlen, tdollar, chunk_ids)``;
    ``budgets``: per-bucket static slot budgets.
    → concatenation of each bucket's ``[budget_b + padded_b]`` packed array
    (a bucket's segment is ``[routes(budget_b)..., cnts(padded_b)...]``).
    """
    outs = [
        match_global_impl(packed_rows, *p, budget=g, layout=layout)
        for p, g in zip(parts, budgets)
    ]
    dt = (jnp.uint32 if any(o.dtype == jnp.uint32 for o in outs)
          else jnp.uint16)
    return jnp.concatenate([o.astype(dt) for o in outs])


# ------------------------------------------------- fused device pipeline
def fused_compact_decode_impl(words, fid_rows, chunk_ids, budget: int):
    """Packed words → final per-topic FID buffer, entirely on device: the
    fused tail that replaces ``compact_global_impl`` + the host decode.

    Same two prefix-sum stages as ``compact_global_impl``, but each route
    slot additionally remembers its TOPIC (scattered alongside the word
    index in stage 1), so stage 2 can compute the matched row's GLOBAL id
    ``chunk_ids[topic, widx//WPC]*CHUNK + (widx%WPC)*32 + bitpos`` and
    resolve it through the device-resident row→fid map — the indirection
    the host decode used to perform per route. A final two-key
    ``lax.sort`` over (topic, fid) puts the buffer in exactly the order
    the router contract wants (flat topic-major, fids ascending per
    topic), so the host's whole job is one ``np.split`` by counts.

    Unfilled slots carry the sentinel topic ``b`` (sorts after every real
    topic) — the host only reads ``cnts.sum()`` slots, which the sort
    packs to the front. Overflow stays detectable exactly as before:
    counts come from the words' popcount, independent of the slot budget.

    Wire: ``[budget + B]`` int32 ``[fids..., cnts...]`` — 4 B/route vs the
    unfused path's 2 B, bought back severalfold by eliminating the second
    dispatch and the host-side chunk-gather + fid-map + sort (the p99
    share cfg11 attributes)."""
    b, w = words.shape
    wpc = WORDS_PER_CHUNK
    with jax.named_scope("compact"):
        chunk_ids = chunk_ids.astype(jnp.int32)
        fid_flat = fid_rows.reshape(-1)
        flat = words.ravel()
        nz = flat != jnp.uint32(0)
        nzi = nz.astype(jnp.int32)
        pos = jnp.cumsum(nzi) - nzi
        # sentinel index == budget → OOB-dropped (see compact_global_impl
        # on why these scatters must not claim unique indices)
        idx = jnp.where(nz & (pos < budget), pos, budget)
        wsrc = lax.broadcasted_iota(jnp.int32, (b, w), 1).ravel()
        tsrc = lax.broadcasted_iota(jnp.int32, (b, w), 0).ravel()
        widx = jnp.zeros((budget,), jnp.int32).at[idx].set(wsrc, mode="drop")
        wtop = jnp.zeros((budget,), jnp.int32).at[idx].set(tsrc, mode="drop")
        bits = jnp.zeros((budget,), jnp.uint32).at[idx].set(flat, mode="drop")
    # stage 2: expand compacted words' bits into fid slots. Unfilled word
    # slots keep (widx=0, wtop=0) — their gathers stay in range and their
    # lanes all carry zero bits, so every one of them is dropped.
    with jax.named_scope("resolve"):
        bitm = (bits[:, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
        rnzi = bitm.astype(jnp.int32).ravel()
        rpos = jnp.cumsum(rnzi) - rnzi
        ridx = jnp.where((rnzi > 0) & (rpos < budget), rpos, budget)
        rows = (
            chunk_ids[wtop, widx // wpc] * CHUNK + (widx % wpc) * 32
        )[:, None] + jnp.arange(32, dtype=jnp.int32)[None, :]
        fvals = fid_flat[rows.ravel()]
        tvals = jnp.broadcast_to(wtop[:, None], (budget, 32)).ravel()
        tj = jnp.full((budget,), b, jnp.int32).at[ridx].set(tvals, mode="drop")
        fids = jnp.zeros((budget,), jnp.int32).at[ridx].set(fvals, mode="drop")
    with jax.named_scope("sort"):
        _tj_s, fid_s = lax.sort((tj, fids), num_keys=2)
    with jax.named_scope("counts"):
        cnts = jnp.sum(lax.population_count(words).astype(jnp.int32), axis=1)
        return jnp.concatenate([fid_s, cnts])


def match_fused_impl(tiles, fid_rows, ttok, tlen, tdollar, chunk_ids,
                     budget: int, layout=None):
    """The fused dispatch: words (legacy or packed tiles) →
    global compaction → on-device fid decode+sort, ONE jit call whose
    output is the final ``[budget + B]`` int32 fid buffer. Nothing but
    final fids and counts comes back to the host."""
    words = words_any_impl(tiles, ttok, tlen, tdollar, chunk_ids,
                           layout=layout)
    # compile-time fence, not a semantic one: with the scan and the
    # compact/sort tail in one fusion scope the v5e compiler took 212 s for
    # this program at B=16384, NC=32 (0.5 s + 35 s for the halves alone);
    # fenced, 35 s. Run time is unchanged — 203 ms fenced vs 204 ms
    # unfenced for the 16K split program on a v5e (PERF.md, PR 21): the
    # words array is the scan's stacked output either way.
    words = lax.optimization_barrier(words)
    return fused_compact_decode_impl(words, fid_rows, chunk_ids, budget)


def match_fused_grouped_impl(tiles, fid_rows, ttok, tlen, tdollar, uniq_cand,
                             inv, budget: int, layout=None):
    """Fused dispatch over the deduplicated candidate upload."""
    chunk_ids = uniq_cand[inv.astype(jnp.int32)]
    return match_fused_impl(tiles, fid_rows, ttok, tlen, tdollar, chunk_ids,
                            budget, layout)


def match_fused_split_impl(tiles, fid_rows, parts, budgets, layout=None):
    """Fused NC split-dispatch: every bucket's fused output concatenates on
    device — one dispatch, one fetch, zero host decode."""
    outs = [
        match_fused_impl(tiles, fid_rows, *p, budget=g, layout=layout)
        for p, g in zip(parts, budgets)
    ]
    return jnp.concatenate(outs)


_match_global_split = jax.jit(match_global_split_impl,
                              static_argnames=("budgets", "layout"))
_match_global = jax.jit(match_global_impl, static_argnames=("budget", "layout"))
_match_global_grouped = jax.jit(match_global_grouped_impl,
                                static_argnames=("budget", "layout"))
_match_fused = jax.jit(match_fused_impl, static_argnames=("budget", "layout"))
_match_fused_grouped = jax.jit(match_fused_grouped_impl,
                               static_argnames=("budget", "layout"))
_match_fused_split = jax.jit(match_fused_split_impl,
                             static_argnames=("budgets", "layout"))

#: the batch size ``prewarm`` latches as the sticky pad floor and compiles:
#: the least padded batch of a warmed matcher, so a lone publish reuses one
#: compiled program instead of compiling shapes 1, 2 and 4 of its own
PREWARM_FLOOR = 8


def pack_device_rows(t: PartitionedTable) -> np.ndarray:
    """The device mirror of a table: chunk-tiled ``[nchunks, L+3, CHUNK]``
    FIELD-MAJOR rows (tokens + flen + prefix_len + hash|wild flags), active
    prefix padded to a pow2 chunk count (floor 64) so table growth does not
    change the array shape on every new chunk — each pow2 bucket costs ONE
    kernel recompile. Padding rows are zeros (flen=0), rejected for every
    topic. Single source of the row layout for the local and mesh-sharded
    paths.

    Field-major matters: XLA tiles the two minor dims to (8, 128), so a
    row-major ``[.., CHUNK, L+3]`` tile pads L+3=11 lanes to 128 — 11.6x
    the HBM footprint and gather traffic (measured as a 1.07 GB resident
    table at 1M subs). ``[.., L+3, CHUNK]`` keeps the minor dim at 256
    full lanes; only the 11→16 sublane pad remains.

    Dtype matters the same way: while the token vocabulary fits (tracked
    by the table's upload narrowing), tiles ship as int16 — the per-batch
    gather traffic (the scan's HBM wall: B×NC tile reads per match)
    halves again, and int16 compares run at twice the VPU lane density.
    flen/prefix_len (≤ L+1) and the 2-bit flags always fit.
    """
    up_chunks = _pad_chunk_count(t.nchunks)
    rows = t.nchunks * CHUNK
    lvl = t.max_levels
    dt = np.int32 if t._tok_wide else np.int16
    packed = np.zeros((up_chunks * CHUNK, lvl + 3), dtype=dt)
    packed[:rows, :lvl] = t.tok[:rows].astype(dt)
    packed[:rows, lvl] = t.flen[:rows]
    packed[:rows, lvl + 1] = t.prefix_len[:rows]
    packed[:rows, lvl + 2] = t.has_hash[:rows].astype(dt) | (
        t.first_wild[:rows].astype(dt) << 1
    )
    return np.ascontiguousarray(
        packed.reshape(-1, CHUNK, lvl + 3).transpose(0, 2, 1)
    )


def _pad_chunk_count(nchunks: int) -> int:
    """Padded device chunk count: pow2 (floor 64) up to 16K chunks so table
    growth recompiles the kernel at most once per bucket; above that pow2
    padding wastes up to half the array exactly where tables are huge (10M
    subs ≈ 83K chunks → a 131072 pad = 200MB of zero tiles, round 2's cfg4
    compile-failure regime), so pad to a multiple of 4096 instead."""
    if nchunks <= 16384:
        return max(64, 1 << (nchunks - 1).bit_length())
    return (nchunks + 4095) // 4096 * 4096


def _byte_planes_for_rows(t: PartitionedTable, layout: PackedLayout, rows):
    """→ ``[n, layout.planes] uint8`` byte planes for the given physical
    rows (slice or index array): per-level LOCAL token ids (low byte, then
    the optional high byte) followed by the metadata byte
    ``flen+1 | has_hash<<5 | first_wild<<6`` (empty rows encode flen+1 = 0;
    ``prefix_len`` is derivable as ``flen - has_hash`` and not stored)."""
    tok = t.tok[rows]
    flen = t.flen[rows]
    hh = t.has_hash[rows]
    fw = t.first_wild[rows]
    planes = np.zeros((len(flen), layout.planes), dtype=np.uint8)
    p = 0
    for i, w in enumerate(layout.widths):
        lut = t._lvl_luts[i]
        g = tok[:, i].astype(np.int64, copy=False)
        loc = np.where(g < len(lut), lut[np.minimum(g, len(lut) - 1)], UNK_TOK)
        planes[:, p] = loc & 0xFF
        p += 1
        if w == 2:
            planes[:, p] = (loc >> 8) & 0xFF
            p += 1
    meta = np.where(flen < 0, 0, flen + 1).astype(np.int64)
    meta = meta | (hh.astype(np.int64) << 5) | (fw.astype(np.int64) << 6)
    planes[:, p] = meta
    return planes


def pack_device_rows_packed(t: PartitionedTable, layout: PackedLayout) -> np.ndarray:
    """Bit-packed device mirror: flat ``[up_chunks, groups*CHUNK]`` int32 —
    four byte planes per int32 lane (encode.group_byte_planes), chunk c's
    plane g occupying lanes ``[g*CHUNK, (g+1)*CHUNK)`` of row c. The flat
    2D shape is deliberate: the minor dim is a 128 multiple (whole
    lanes) and the sublane dim is the chunk count, so the array carries
    NO tile-padding waste — unlike a 3D int8 ``[.., planes, CHUNK]`` layout,
    whose 9→32 sublane pad would triple the resident bytes and erase the
    packing win. Per-chunk gather traffic drops from ``(L+3)*CHUNK*2`` bytes
    (legacy int16 field-major) to ``groups*CHUNK*4`` — 2816 → 1024 B at the
    bench's mixed-wildcard shape (L=8, six 1-byte levels + one 2-byte), the
    ≥2× HBM reduction scripts/roofline.py models. Padding chunks are zeros
    (flen+1 = 0 ⇒ empty), rejected for every topic."""
    up_chunks = _pad_chunk_count(t.nchunks)
    rows = t.nchunks * CHUNK
    planes = _byte_planes_for_rows(t, layout, slice(0, rows))
    arr32 = group_byte_planes(planes, layout.groups)
    full = np.zeros((up_chunks * CHUNK, layout.groups), dtype=np.int32)
    full[:rows] = arr32
    return np.ascontiguousarray(
        full.reshape(up_chunks, CHUNK, layout.groups)
        .transpose(0, 2, 1)
        .reshape(up_chunks, layout.groups * CHUNK)
    )


def pack_chunk_tiles_packed(
    t: PartitionedTable, cids: Sequence[int], layout: PackedLayout
) -> np.ndarray:
    """Delta-upload payload for the packed format: only the given chunks,
    same flat int32 lane layout as ``pack_device_rows_packed`` so tiles
    scatter straight into the resident array by leading-axis index."""
    k = len(cids)
    cid_arr = np.asarray(cids, dtype=np.int64)
    rows = (cid_arr[:, None] * CHUNK + np.arange(CHUNK, dtype=np.int64)).reshape(-1)
    planes = _byte_planes_for_rows(t, layout, rows)
    arr32 = group_byte_planes(planes, layout.groups)
    return np.ascontiguousarray(
        arr32.reshape(k, CHUNK, layout.groups)
        .transpose(0, 2, 1)
        .reshape(k, layout.groups * CHUNK)
    )


def pack_fid_rows(t: PartitionedTable) -> np.ndarray:
    """Device-resident row→fid map ``[up_chunks, CHUNK]`` int32 (the fused
    pipeline resolves matched rows to filter ids ON DEVICE, so only final
    fids are fetched). -1 marks empty rows; a -1 escaping through the
    fused output means a cleared row matched — a device bug the host fails
    loudly on, mirroring ``_group_sorted``'s contract. int32 bounds fids at
    2^31 (4 billion ``add()`` calls), same practical bound the composite-
    key host sort already enforces."""
    up_chunks = _pad_chunk_count(t.nchunks)
    rows = t.nchunks * CHUNK
    out = np.full((up_chunks * CHUNK,), -1, dtype=np.int32)
    out[:rows] = t._fid_of_row[:rows]
    return out.reshape(up_chunks, CHUNK)


def pack_fid_chunk_tiles(t: PartitionedTable, cids: Sequence[int]) -> np.ndarray:
    """Dirty-chunk slices of the device fid map (delta refresh payload)."""
    cid_arr = np.asarray(cids, dtype=np.int64)
    rows = (cid_arr[:, None] * CHUNK + np.arange(CHUNK, dtype=np.int64)).reshape(-1)
    return t._fid_of_row[rows].astype(np.int32).reshape(len(cids), CHUNK)


def pack_chunk_tiles(t: PartitionedTable, cids: Sequence[int], dt) -> np.ndarray:
    """Pack ONLY the given chunks into device tiles ``[K, L+3, CHUNK]`` —
    the delta-upload payload (same field-major layout as
    ``pack_device_rows``, so tiles scatter straight into the resident
    array by leading-axis index)."""
    lvl = t.max_levels
    k = len(cids)
    cid_arr = np.asarray(cids, dtype=np.int64)
    rows = (cid_arr[:, None] * CHUNK + np.arange(CHUNK, dtype=np.int64)).reshape(-1)
    packed = np.zeros((k * CHUNK, lvl + 3), dtype=dt)
    packed[:, :lvl] = t.tok[rows].astype(dt)
    packed[:, lvl] = t.flen[rows]
    packed[:, lvl + 1] = t.prefix_len[rows]
    packed[:, lvl + 2] = t.has_hash[rows].astype(dt) | (
        t.first_wild[rows].astype(dt) << 1
    )
    return np.ascontiguousarray(
        packed.reshape(k, CHUNK, lvl + 3).transpose(0, 2, 1)
    )


def delta_chunk_plan(t: PartitionedTable, *, enabled: bool, dev_version: int,
                     has_resident: bool, dev_epoch: int, dev_lvl: int,
                     dev_dtype, dt, dev_up_chunks: int,
                     dev_layout=None, layout=None):
    """The delta-refresh validity gate, shared by every chunk-tile mirror
    (local + mesh-replicated): → dirty chunk ids (possibly empty) when a
    scatter refresh is sound, else None (caller full-uploads). The gate is
    correctness-critical — a condition added here must hold for all
    consumers, which is why it lives in one place. ``dev_layout``/``layout``
    compare the resident vs current bit-packed tile layout (both None for
    legacy tiles): any width/depth/format change is a wholesale relayout."""
    if (
        not enabled
        or dev_version < 0
        or not has_resident
        or dev_epoch != t.layout_epoch
        or dev_lvl != t.max_levels
        or dev_dtype != dt
        or dev_layout != layout
        or t.nchunks > dev_up_chunks
    ):
        return None
    cids = t.delta.since(dev_version)
    if cids is None or len(cids) > max(64, t.nchunks // 2):
        return None  # journal too old / delta no cheaper than a repack
    return cids


def _pad_scatter_pow2(idx: np.ndarray, vals: np.ndarray):
    """Pad a scatter's (indices, updates) to a pow2 count by repeating the
    last entry: every distinct count would otherwise compile its own XLA
    scatter, turning steady churn into a recompile per refresh. Duplicate
    indices are safe — the repeated updates are identical."""
    k = len(idx)
    kp = 1 << (k - 1).bit_length() if k > 1 else 1
    if kp == k:
        return idx, vals
    pad = kp - k
    return (
        np.concatenate([idx, np.repeat(idx[-1:], pad)]),
        np.concatenate([vals, np.repeat(vals[-1:], pad, axis=0)]),
    )


class _Snap:
    """What a match handle was submitted against: the device snapshot's
    (version, layout epoch) plus the row→fid map array AS OF that version.
    Completes decode through this — never through the live table — so a
    mutation or compaction landing mid-flight can't tear a result."""

    __slots__ = ("version", "epoch", "fid_map")

    def __init__(self, version: int, epoch: int, fid_map: np.ndarray) -> None:
        self.version = version
        self.epoch = epoch
        self.fid_map = fid_map


class PartitionedMatcher:
    """Device mirror + batched match over a ``PartitionedTable``.

    Words come from the lax scan (packed or legacy tiles, by what the table
    can pack); the output is fused on the device (compact → fid resolve →
    sort), verified once against lax words → global compact → host decode,
    which is also the fallback — routing results must never depend on an
    unverified device path. NC-split and segmented are dispatch forms of
    those two.
    """

    def __init__(self, table: PartitionedTable, device=None) -> None:
        self.table = table
        self.device = device
        # sticky pow2 slot budgets of the batch-global compaction, PER
        # (padded batch, NC) shape: one shared budget would let a 16K-topic
        # batch (e.g. 128K slots) inflate every later 1-topic match's fetch
        # to megabytes —
        # the low-load p99 path must keep its own small budget
        self._budgets: Dict[Tuple[int, int], int] = {}
        # slots a topic a new shape's budget starts with; _regrown raises it
        self._slots_per_topic = 4
        # NC split-dispatch: bucket big batches by candidate count so
        # padding chunks stop dominating device compute
        self._split = True
        self._dev_version = -1
        self._dev_arrays = None
        # --- fused match→compact→decode pipeline (RMQTT_FUSED=0/1 forces
        # off/on; default verifies against the lax+host-decode reference on
        # the first batch with matches and falls back if anything
        # disagrees: an unverified fused path must never change routing
        # results)
        env_fused = os.environ.get("RMQTT_FUSED", "")
        self._fused: Optional[bool] = (
            False if env_fused == "0" else (True if env_fused == "1" else None)
        )
        self.fused_batches = 0  # batches served end-to-end on device
        # --- bit-packed tiles (RMQTT_PACKED=0 restores legacy int16/int32
        # field-major tiles); engages per refresh iff the table is packable
        self._packed_pref = os.environ.get("RMQTT_PACKED", "1") != "0"
        self._dev_playout = None  # PackedLayout of the resident tiles (None = legacy)
        self._dev_fids = None  # device row→fid map [up_chunks, CHUNK] int32
        # sticky small-batch pad floor (prewarm): tiny batches pad UP to one
        # already-compiled shape instead of compiling shapes 1/2/4/... each.
        # RMQTT_PAD_FLOOR seeds it at construction (the autotune-replay
        # seam: a process starts pre-tuned instead of from defaults) and
        # PINS it against prewarm()'s default latch —
        # a fitted seed of 2 must survive broker start, not get re-raised
        # to 8. The live autotuner still moves it via set_pad_floor().
        self._pad_floor_pinned = os.environ.get("RMQTT_PAD_FLOOR", "") != ""
        self._pad_floor = max(1, int(os.environ.get("RMQTT_PAD_FLOOR", "1")))
        # device-plane profiler glue (broker/devprof.py): submit-half flight
        # records awaiting their complete half, matched by handle IDENTITY
        # (so _complete_segmented's recursive sub-completes never consume a
        # top-level record); bounded — an abandoned handle flushes oldest.
        # The lock covers append vs scan: pipelined submits and completes
        # run on different executor threads (RoutingService), and iterating
        # a deque under a concurrent append raises
        self._prof_pending: deque = deque()
        self._prof_lock = threading.Lock()
        # per-stage wall-clock attribution (cfg11): zero-overhead when off.
        # The four sections are busy-clock stages (broker/telemetry.py
        # Stage): the matcher's own until ``use_telemetry`` hands it the
        # registry's ``matcher.*`` stages, whose sections are then also
        # spans of a profiler trace
        self.stage_timing = False
        self._stages = {k: _Stage("matcher." + k) for k in _STAGE_KEYS}
        # segmented-table mode: device tables above this byte budget split
        # into multiple arrays scanned per segment (one huge device_put +
        # compile at 10M subs is round 2's undiagnosed cfg4 on-chip failure;
        # bounded arrays give that scale a working path either way)
        self._seg_bytes = int(os.environ.get("RMQTT_SEG_BYTES", str(256 << 20)))
        self._segments: Optional[List[Tuple[int, int, object]]] = None
        self._seg_nc: Dict[int, int] = {}  # sticky per-segment NC cap
        self._seg_cap = 0  # chunks per segment at the last full build
        # --- incremental (delta) device refresh: mutations scatter-write
        # only their dirty chunks into the resident array(s) instead of
        # re-packing + re-uploading the whole table (RMQTT_DELTA_UPLOADS=0
        # restores the full-refresh behavior)
        self.delta_enabled = os.environ.get("RMQTT_DELTA_UPLOADS", "1") != "0"
        self.uploads = 0  # refresh events that shipped bytes (full + delta)
        self.full_uploads = 0
        self.delta_uploads = 0
        self.upload_bytes = 0
        # versioned device snapshot: what the resident arrays/fid map
        # correspond to. In-flight handles carry these so completes decode
        # against the snapshot they encoded with (double buffering)
        self._dev_epoch = -1
        self._dev_lvl = -1
        self._dev_dtype: Optional[type] = None
        self._dev_up_chunks = 0
        self._dev_fid_map: Optional[np.ndarray] = None

    def use_telemetry(self, tele) -> None:
        """Count the four sections into ``tele``'s ``matcher.*`` stages."""
        self._stages = {k: tele.stage("matcher." + k) for k in _STAGE_KEYS}

    def _timed(self) -> bool:
        """Are this call's four sections the routing path's? Not where the
        batch only runs to compile its programs off the path
        (``ops/hybrid.py``): its seconds are compile time, no batch's cost."""
        return self.stage_timing and _DEVPROF.compile_rule() != "off_path"

    @property
    def stage_ns(self) -> Dict[str, int]:
        """Cumulative ns per section (encode / dispatch / fetch / decode)."""
        return {k: st.busy_ns for k, st in self._stages.items()}

    def _refresh(self):
        t = self.table
        if self._dev_version == t.version and (
            self._dev_arrays is not None or self._segments is not None
        ):
            return self._dev_arrays
        # chaos seam: injected upload faults fire before the table lock so
        # a `hang` action wedges only this refresh, never subscribes
        if _FP_UPLOAD.action is not None:
            _FP_UPLOAD.fire_sync()
        with t._mu:
            if self._dev_version == t.version and (
                self._dev_arrays is not None or self._segments is not None
            ):
                return self._dev_arrays
            # tile format: bit-packed while the table is packable (and not
            # opted out); the packed device array is int32 (grouped byte
            # planes), so the layout token — not the dtype — is what the
            # delta gate compares for relayout detection
            layout = t.packed_layout() if self._packed_pref else None
            if layout is not None:
                dt = np.int32
            else:
                dt = np.int32 if t._tok_wide else np.int16
            if self._try_delta_refresh(t, dt, layout):
                return self._dev_arrays
            # full path: repack + re-upload everything (first refresh,
            # layout change, dtype widening, growth past the resident
            # padding, or a delta journal that no longer reaches back far
            # enough). Only the host-side PACK runs under the lock — the
            # device transfer below must not stall subscribes for a
            # multi-GB upload (the stall this PR removes); mutations that
            # land during the transfer stay pending because the version
            # installed is the one captured here.
            packed = (pack_device_rows_packed(t, layout) if layout is not None
                      else pack_device_rows(t))
            fids2d = pack_fid_rows(t) if self._want_fids() else None
            version, epoch, lvl = t.version, t.layout_epoch, t.max_levels
            fid_map = t._fid_of_row
        put = (
            functools.partial(jax.device_put, device=self.device)
            if self.device
            else jax.device_put
        )
        if packed.nbytes > self._seg_bytes:
            self._dev_arrays = None
            self._dev_fids = None
            self._segments = self._build_segments(packed, fids2d, put)
        else:
            self._segments = None
            try:
                self._dev_arrays = put(packed)
                self._dev_fids = put(fids2d) if fids2d is not None else None
            except Exception as e:
                # oversize-table fail-soft (cfg4's "pre NC-split table"
                # compile death): a failed whole-table upload retries as
                # bounded segments instead of wedging the run
                self._seg_bytes = max(
                    64 << 20, min(self._seg_bytes, packed.nbytes // 4)
                )
                _LOG.warning(
                    "whole-table device upload failed (%s: %s); retrying as "
                    "segmented arrays at %dMB/segment (tune RMQTT_SEG_BYTES "
                    "to pre-empt this)",
                    type(e).__name__, e, self._seg_bytes >> 20,
                )
                self._dev_arrays = None
                self._dev_fids = None
                self._segments = self._build_segments(packed, fids2d, put)
        self._dev_version = version
        self._dev_epoch = epoch
        self._dev_lvl = lvl
        self._dev_dtype = dt
        self._dev_playout = layout
        self._dev_up_chunks = (
            packed.shape[0] if self._segments is None
            else self._seg_cap * len(self._segments)
        )
        self._dev_fid_map = fid_map
        self.uploads += 1
        self.full_uploads += 1
        nb = packed.nbytes + (fids2d.nbytes if fids2d is not None else 0)
        self.upload_bytes += nb
        if _DEVPROF.enabled:
            _DEVPROF.note_upload("full", nb)
        return self._dev_arrays

    def _want_fids(self) -> bool:
        """Device fid rows are packed/uploaded only while the fused
        pipeline can serve batches (not ruled out)."""
        return self._fused is not False

    def _try_delta_refresh(self, t: PartitionedTable, dt, layout) -> bool:
        """Scatter-write only the dirty chunks into the resident device
        array(s). Possible iff the layout epoch, row width, tile dtype,
        packed-tile layout and padded capacity all still match the resident
        snapshot; otherwise (or when the delta journal overflowed) the
        caller full-uploads."""
        cids = delta_chunk_plan(
            t, enabled=self.delta_enabled, dev_version=self._dev_version,
            has_resident=self._dev_arrays is not None or self._segments is not None,
            dev_epoch=self._dev_epoch, dev_lvl=self._dev_lvl,
            dev_dtype=self._dev_dtype, dt=dt, dev_up_chunks=self._dev_up_chunks,
            dev_layout=self._dev_playout, layout=layout,
        )
        if cids is None:
            return False
        want_fids = self._want_fids()
        has_fids = (
            self._dev_fids is not None if self._segments is None
            else all(s[3] is not None for s in self._segments)
        )
        if want_fids and not has_fids:
            return False  # fused newly wants fid rows: full upload builds them
        if not want_fids and self._dev_fids is not None:
            # fused ruled out after the fid map went resident: drop it so
            # delta refreshes stop packing/shipping tiles nothing reads
            self._dev_fids = None
            has_fids = False
        if cids:
            tiles = (pack_chunk_tiles_packed(t, cids, layout)
                     if layout is not None else pack_chunk_tiles(t, cids, dt))
            ftiles = (pack_fid_chunk_tiles(t, cids)
                      if has_fids and want_fids else None)
            if self._segments is None:
                idx, vals = _pad_scatter_pow2(
                    np.asarray(cids, dtype=np.int32), tiles
                )
                # pow2-padded scatter: one compiled executable per pow2
                # dirty-chunk bucket — the "one compiled scatter under
                # steady churn" invariant the profiler makes checkable
                self._dev_arrays = _pj(
                    "delta_scatter", lambda a, i, v: a.at[i].set(v),
                    self._dev_arrays, idx, vals)
                if ftiles is not None:
                    fidx, fvals = _pad_scatter_pow2(
                        np.asarray(cids, dtype=np.int32), ftiles
                    )
                    self._dev_fids = _pj(
                        "delta_scatter_fids", lambda a, i, v: a.at[i].set(v),
                        self._dev_fids, fidx, fvals)
            else:
                self._apply_segment_delta(t, cids, tiles, ftiles)
            self.uploads += 1
            self.delta_uploads += 1
            nb = tiles.nbytes + (ftiles.nbytes if ftiles is not None else 0)
            self.upload_bytes += nb
            if _DEVPROF.enabled:
                _DEVPROF.note_upload("delta", nb)
        self._dev_version = t.version
        self._dev_fid_map = t._fid_of_row
        return True

    def _apply_segment_delta(self, t: PartitionedTable, cids, tiles,
                             ftiles=None) -> None:
        """Scatter dirty chunks into their segment arrays (global chunk
        ``cid`` lives at local index ``cid - base + 1`` for segments > 0;
        see ``_build_segments``) and advance each segment's live end as the
        table grows into the built-in padding. ``ftiles`` carries the
        matching fid-row chunks when the fused pipeline keeps the row→fid
        map device-resident."""
        cid_arr = np.asarray(cids, dtype=np.int64)
        segs = []
        for si, (base, _end, dev, fdev) in enumerate(self._segments):
            sel = (cid_arr >= base) & (cid_arr < base + self._seg_cap)
            loc = cid_arr[sel] if si == 0 else cid_arr[sel] - (base - 1)
            if len(loc):
                idx, vals = _pad_scatter_pow2(
                    loc.astype(np.int32), tiles[np.nonzero(sel)[0]]
                )
                dev = dev.at[idx].set(vals)
                if ftiles is not None and fdev is not None:
                    fidx, fvals = _pad_scatter_pow2(
                        loc.astype(np.int32), ftiles[np.nonzero(sel)[0]]
                    )
                    fdev = fdev.at[fidx].set(fvals)
            segs.append((base, min(base + self._seg_cap, t.nchunks), dev, fdev))
        self._segments = segs

    def _build_segments(self, packed: np.ndarray, fids2d, put):
        """Split the packed table into ≤``_seg_bytes`` device arrays.

        Segment 0 keeps the global chunk numbering (it contains the
        reserved empty chunk 0); segment s>0 gets ONE zero chunk prepended
        as its local padding target, so global chunk ``cid`` lives at local
        ``cid - base + 1`` and a local match row maps back to the global
        row space by the affine offset ``(base-1)*CHUNK`` (chunk 0 never
        matches, so every real match has local chunk ≥ 1). ``fids2d``
        (row→fid chunks, may be None) splits identically so the fused
        pipeline's device decode works per segment — its fids are GLOBAL,
        so segment results merge by plain concatenation."""
        total = packed.shape[0]
        nseg = -(-packed.nbytes // self._seg_bytes)
        seg_chunks = -(-total // nseg)
        # align for shape stability under growth; small alignment for small
        # tables (tests force segmentation at toy scale via _seg_bytes)
        align = 4096 if seg_chunks >= 4096 else (64 if seg_chunks >= 64 else 8)
        seg_chunks = (seg_chunks + align - 1) // align * align
        self._seg_cap = seg_chunks
        segs: List[Tuple] = []
        for base in range(0, total, seg_chunks):
            lead = 1 if base > 0 else 0

            def cut(arr, fill=0):
                part = arr[base : base + seg_chunks]
                pads = [(0, 0)] * part.ndim
                pads[0] = (lead, seg_chunks - part.shape[0])
                if any(p != (0, 0) for p in pads):
                    part = np.pad(part, pads, constant_values=fill)
                return put(part)

            fdev = cut(fids2d, fill=-1) if fids2d is not None else None
            segs.append((base, min(base + seg_chunks, total), cut(packed), fdev))
        return segs

    def match_submit(self, topics: Sequence[str], pad_to_pow2: bool = True):
        """Encode + dispatch WITHOUT fetching: jax dispatch is async, so the
        caller can submit batch N+1 (host encode) while N computes on
        device, then ``match_complete`` each handle in order. This is how
        the bench pipelines over a high-latency dispatch path.

        With the device profiler on (broker/devprof.py), the submit half
        opens a flight-recorder record (shape kind, compile hit-vs-trace,
        batch/padded rows) that ``match_complete`` closes with the fetch/
        decode stage deltas; off = one attribute check."""
        if not _DEVPROF.enabled:
            return self._submit_impl(topics, pad_to_pow2)
        # the traces delta is best-effort under concurrency: another
        # matcher tracing between the marks can mislabel this record
        # 'trace' — the registry totals themselves stay exact
        tr0 = _DEVPROF.traces
        sn0 = dict(self.stage_ns) if self.stage_timing else None
        t0 = time.perf_counter_ns()
        meta: dict = {}
        h = self._submit_impl(topics, pad_to_pow2, _meta=meta)
        traces = _DEVPROF.traces - tr0
        padded = meta.get("padded", len(topics))
        rec = {
            "ts": round(time.time(), 3),
            "kind": h[0],
            "batch": len(topics),
            "padded": padded,
            "pad_waste": round(1.0 - len(topics) / padded, 4)
            if padded else 0.0,
            "traces": traces,
            "compile": "trace" if traces else "hit",
            "submit_ns": time.perf_counter_ns() - t0,
        }
        old = None
        with self._prof_lock:
            self._prof_pending.append((h, rec, sn0))
            if len(self._prof_pending) > 16:
                # abandoned handle (caller never completed it): flush so the
                # record still reaches the ring and the deque stays bounded
                _h, old, _sn = self._prof_pending.popleft()
        if old is not None:
            # ring-only: it never completed, so it is not a dispatch — and
            # it must not inherit the CURRENT publish's trace id or land
            # in the current rollup bucket
            _DEVPROF.note_abandoned(old)
        return h

    def _submit_impl(self, topics: Sequence[str], pad_to_pow2: bool = True,
                     _meta: Optional[dict] = None):
        t = self.table
        if t.compact_async:
            # churn-triggered background compaction: the rebuild runs on
            # its own thread while this (and following) dispatches keep
            # matching against the fragmented-but-correct old layout
            t.maybe_compact_async()
        elif t.needs_compact():
            # compact_async=false restores the synchronous rebuild (the
            # pre-delta debugging behavior) — without this the layout
            # would fragment unboundedly
            t.compact()
        b = len(topics)
        if pad_to_pow2:
            padded = 1 << (b - 1).bit_length() if b > 1 else b
            if padded < self._pad_floor:
                # sticky small-batch shape floor (prewarm()): a 1-topic
                # publish reuses the already-compiled floor-shape
                # executable instead of compiling its own 1/2/4-shapes
                padded = self._pad_floor
        else:
            padded = b
        if _meta is not None:
            # profiler's pad-waste source — an out-param, not an instance
            # attribute: concurrent submits on one matcher (pipelined
            # executor threads) must not cross-attribute their padding
            _meta["padded"] = padded
        st = self._stages
        tok = st["encode"].begin(b) if self._timed() else 0
        while True:
            enc, enc_epoch = t.encode_topics_versioned(
                topics, pad_batch_to=padded, with_groups=True
            )
            try:
                dev = self._refresh()
            except NeedsCompile:  # a delta scatter of a never-seen shape
                if tok:
                    st["encode"].end(tok)
                raise
            if self._dev_epoch != enc_epoch:
                # a compaction installed between the encode and the device
                # refresh: the chunk ids reference the OLD layout while the
                # device now holds the new one — re-encode (rare, bounded
                # by compaction frequency)
                continue
            if self._dev_playout is not None:
                # bit-packed tiles: topic tokens re-key into the per-level
                # local id spaces. A layout change racing the refresh
                # (width widening / deeper prefix) re-encodes, same as the
                # compaction race above.
                lay, tt = t.translate_packed(enc[0])
                if lay != self._dev_playout:
                    continue
            else:
                tt = enc[0]
            break
        snap = _Snap(self._dev_version, self._dev_epoch, self._dev_fid_map)
        _ttok, tlen, tdollar, chunk_ids, _nc = enc[:5]
        if tok:
            # one clock read closes encode and opens dispatch
            tok = st["dispatch"].begin_at(abs(tok) + st["encode"].end(tok))
        try:
            if self._segments is not None:
                return self._submit_segmented(tt, tlen, tdollar, chunk_ids, b,
                                              snap)
            if self._fused is not False:
                handle = self._submit_fused(
                    dev, tt, tlen, tdollar, chunk_ids, enc[5], padded, b, snap)
                if handle is not None:
                    return handle
            split = self._split_plan(chunk_ids, b)
            if split is not None:
                return self._submit_split(
                    dev, tt, tlen, tdollar, chunk_ids, split, 0, snap
                )
            lay = self._dev_playout
            grouped = self._group_inputs(enc[5], chunk_ids)
            g = self._budget_for(padded, _nc)
            packed = self._run_global(dev, tt, tlen, tdollar, chunk_ids,
                                      grouped, g, lay)
            # the handle carries ITS OWN budget: a sticky widening by a
            # later handle must not mask this one's truncation
            return ("g", b, chunk_ids, (dev, tt, tlen, tdollar, grouped, lay),
                    packed, g, 0, snap)
        finally:
            if tok:
                st["dispatch"].end(tok)

    # ------------------------------------------------- NC split-dispatch
    SPLIT_MIN_BATCH = 1024  # small batches are dispatch-bound, not compute

    @staticmethod
    def _tier_ladder(nc: int) -> Tuple[int, ...]:
        """NC tiers: ~1.5×-step ladder (8, 12, 16, 24, 32, 48, …) capped
        at nc. Measured batches concentrate in a NARROW count band just
        under the sticky pow2 cap (cfg3: p50 14 / cap 32; cfg4: p50 45 /
        cap 64 — NOTES r3), so coarse pow2 tiers capture nothing at the
        top of the range; the 1.5 steps put a tier close above the band
        (cfg3 → 16: scan halves; cfg4 → 48: scan −25%) while small-bucket
        upward merging below keeps jit signatures few."""
        tiers: List[int] = []
        k = 0
        while (8 << k) < nc:
            tiers.append(8 << k)
            if (12 << k) < nc:
                tiers.append(12 << k)
            k += 1
        tiers.append(nc)
        return tuple(tiers)

    def _split_plan(self, chunk_ids: np.ndarray, b: int):
        """Bucket the REAL topics (not the pow2 pad) by candidate count;
        None when splitting can't save ≥25% of the scan work (the padding
        rows each bucket re-adds are part of the estimate)."""
        nc = chunk_ids.shape[1]
        if not self._split or b < self.SPLIT_MIN_BATCH or nc <= 8:
            return None
        counts = (chunk_ids[:b] != 0).sum(axis=1)
        tiers = np.asarray(self._tier_ladder(nc))
        assign = np.searchsorted(tiers, counts)  # smallest tier ≥ count
        sizes = np.bincount(assign, minlength=len(tiers))
        # merge small buckets upward (a bucket in a bigger tier stays
        # correct — extra columns are zero-padded): each non-empty bucket
        # is one more scan in the combined jit signature, and a tiny one
        # saves less compute than its compile + pow2 padding cost
        floor = max(256, b // 16)
        for i in range(len(tiers) - 1):
            if 0 < sizes[i] < floor:
                sizes[i + 1] += sizes[i]
                sizes[i] = 0
                assign[assign == i] = i + 1
        est = sum(
            (1 << (int(s) - 1).bit_length()) * int(t)
            for s, t in zip(sizes, tiers) if s
        )
        if est * 4 >= b * nc * 3:
            return None
        order = np.argsort(assign, kind="stable")
        return order, sizes, tuple(int(t) for t in tiers)

    def _budget_for(self, padded: int, nc: int) -> int:
        g = self._budgets.get((padded, nc))
        if g is None:
            g = max(256, 1 << (self._slots_per_topic * padded - 1).bit_length())
            self._budgets[(padded, nc)] = g
        return g

    def _regrown(self, n: int, b: int, padded: int) -> int:
        """The slot budget of a shape whose batch of ``b`` topics (padded to
        ``padded``) made ``n`` routes, more than it had slots for. Every
        budget is a program of its own, so a shape should reach its budget
        in one step, not one compile per doubling as its batches fill up:
        a bucket at least half full asks for what a FULL one would need at
        its rate; and what a topic of a real batch needed is where the next
        new shape starts (``_budget_for``) instead of at 4 slots a topic."""
        if 2 * b > padded:
            n = -(-n * padded // b)
        g = 1 << max(8, (n - 1).bit_length())
        if b >= 64:  # a lone topic's fan-out says little of a batch's
            self._slots_per_topic = max(self._slots_per_topic, g // padded)
        return g

    # ------------------------------------------------- fused pipeline
    def _submit_fused(self, dev, tt, tlen, tdollar, chunk_ids, groups,
                      padded: int, b: int, snap, fdev=None):
        """Dispatch one batch through the fused match→compact→decode
        pipeline (single-array tables). Returns a handle, a pre-resolved
        ``("r", results)`` handle (first-use verify consumed the batch), or
        None when fused is ruled out and the caller should fall back."""
        fdev = fdev if fdev is not None else self._dev_fids
        if fdev is None:
            return None
        g = self._budget_for(padded, chunk_ids.shape[1])
        if self._fused is None:
            ok, results = self._decide_fused(
                dev, fdev, tt, tlen, tdollar, chunk_ids, b, g, snap)
            if ok is not None:  # None = vacuous batch, stay undecided
                self._fused = ok
            if results is not None:
                return ("r", results)
            return None
        lay = self._dev_playout
        split = self._split_plan(chunk_ids, b)
        if split is not None:
            return self._submit_fused_split(
                dev, fdev, tt, tlen, tdollar, chunk_ids, split, lay)
        grouped = self._group_inputs(groups, chunk_ids) if groups is not None else None
        packed = self._run_fused(dev, fdev, tt, tlen, tdollar, chunk_ids,
                                 grouped, g, lay)
        return ("f", b, padded,
                (dev, fdev, tt, tlen, tdollar, chunk_ids, grouped, lay),
                packed, g)

    @staticmethod
    def _run_fused(dev, fdev, tt, tlen, tdollar, chunk_ids, grouped, g: int,
                   lay):
        """Dispatch the fused program (plain or grouped upload) at slot
        budget ``g``: the first run of a handle and its budget reruns."""
        if grouped is None:
            return _pj("match_fused", _match_fused, dev, fdev, tt, tlen,
                       tdollar, chunk_ids, budget=g, layout=lay)
        return _pj("match_fused_grouped", _match_fused_grouped, dev, fdev,
                   tt, tlen, tdollar, *grouped, budget=g, layout=lay)

    @staticmethod
    def _run_global(dev, tt, tlen, tdollar, chunk_ids, grouped, g: int, lay):
        """``_run_fused``'s twin for the reference / fallback program:
        words → global compact, decoded on the host."""
        if grouped is None:  # batch doesn't dedup; plain upload
            return _pj("match_global", _match_global, dev, tt, tlen, tdollar,
                       chunk_ids, budget=g, layout=lay)
        return _pj("match_global_grouped", _match_global_grouped, dev, tt,
                   tlen, tdollar, *grouped, budget=g, layout=lay)

    def _decide_fused(self, dev, fdev, tt, tlen, tdollar, chunk_ids, b: int,
                      g: int, snap, fid_base: int = 0):
        """First-use self-check of the fused pipeline against the lax
        reference (words → global compact → HOST decode through the
        snapshot machinery) on the live batch: routing results must never
        depend on an unverified device path. → ``(ok, results)``; results
        (from the reference, which is correct either way) may be served
        directly."""
        lay = self._dev_playout
        log = _LOG
        # the same call as the production dispatch (_run_fused): the verify
        # must not compile a second executable. A compile or run failure
        # here propagates: only a DISAGREEMENT (below) may rule the fused
        # pipeline out.
        packed = self._run_fused(dev, fdev, tt, tlen, tdollar, chunk_ids,
                                 None, g, lay)
        got = self._complete_fused(
            ("f", b, chunk_ids.shape[0],
             (dev, fdev, tt, tlen, tdollar, chunk_ids, None, lay),
             packed, g))
        ref_packed = self._run_global(dev, tt, tlen, tdollar, chunk_ids,
                                      None, g, lay)
        want = self._complete_global(
            ("g", b, chunk_ids, (dev, tt, tlen, tdollar, None, lay),
             ref_packed, g, fid_base, snap))
        if not any(len(w) for w in want):
            # a zero-match batch (empty table, the broker's prewarm probe)
            # would latch the verify on an empty-vs-empty comparison — the
            # vacuous-oracle trap the PR6 canary fell into. Serve the
            # (correct) reference and stay undecided until a batch with
            # real matches exercises the fid-resolve/sort path for real.
            self.fused_batches -= 1
            return None, want
        agree = len(got) == len(want) and all(
            np.array_equal(a, w) for a, w in zip(got, want))
        if not agree:
            log.warning("fused pipeline disagrees with the lax+host-decode "
                        "reference; disabled")
            # postmortem artifact: exactly the class of silent device-path
            # wrongness the flight recorder exists to capture
            _DEVPROF.auto_dump("fused_verify_disagreement")
            self.fused_batches -= 1  # the verify run doesn't count as served
            return False, want
        log.info("fused match→compact→decode pipeline verified; enabled")
        return True, want

    def _submit_fused_split(self, dev, fdev, tt, tlen, tdollar, chunk_ids,
                            split, lay):
        """Fused NC split-dispatch: same host-side bucketing as
        ``_submit_split``, fused epilogue per bucket, one dispatch."""
        order, sizes, tiers = split
        b = len(order)
        parts: List[Tuple] = []
        meta: List[Tuple[int, int, int]] = []
        budgets: List[int] = []
        pos = 0
        for tier, s in zip(tiers, sizes):
            s = int(s)
            if not s:
                continue
            idx = order[pos : pos + s]
            pos += s
            pb = 1 << (s - 1).bit_length() if s > 1 else 1
            pt = np.zeros((pb, tt.shape[1]), dtype=tt.dtype)
            pt[:s] = tt[idx]
            pl = np.full((pb,), -2, dtype=tlen.dtype)
            pl[:s] = tlen[idx]
            pd = np.zeros((pb,), dtype=bool)
            pd[:s] = tdollar[idx]
            pc = np.zeros((pb, tier), dtype=chunk_ids.dtype)
            pc[:s] = chunk_ids[idx, :tier]
            gb = self._budget_for(pb, tier)
            parts.append((pt, pl, pd, pc))
            meta.append((s, pb, tier))
            budgets.append(gb)
        packed = _pj("match_fused_split", _match_fused_split, dev, fdev,
                     tuple(parts), tuple(budgets), layout=lay)
        return ("fs", b, order, meta, parts, (dev, fdev, lay), packed,
                tuple(budgets))

    def _complete_fused(self, handle) -> List[np.ndarray]:
        """Block on a fused handle: ONE fetch of ``[fids..., cnts...]``;
        the host's whole decode is an ``np.split`` by counts (the device
        already resolved rows→fids and sorted per topic)."""
        _tag, b, padded, rerun, packed, g = handle
        dev, fdev, tt, tlen, tdollar, chunk_ids, grouped, lay = rerun
        st = self._stages
        tok = st["fetch"].begin(b) if self._timed() else 0
        try:
            while True:
                arr = fetch(packed, "fused match fetch")
                cn = arr[g:].astype(np.int64)
                n = int(cn.sum())
                if n <= g:
                    break
                g = self._regrown(n, b, padded)
                key = (chunk_ids.shape[0], chunk_ids.shape[1])
                self._budgets[key] = max(self._budgets.get(key, 0), g)
                packed = self._run_fused(dev, fdev, tt, tlen, tdollar,
                                         chunk_ids, grouped, g, lay)
        except NeedsCompile:  # a regrown budget has no program yet
            if tok:
                st["fetch"].end(tok)
            raise
        if tok:
            # one clock read closes fetch and opens decode
            tok = st["decode"].begin_at(abs(tok) + st["fetch"].end(tok))
        if cn[b:].any():
            # same fail-loudly contract as the host decoders: a padded topic
            # (tlen=-2, can match nothing) with routes is a device bug
            raise AssertionError("padded topic produced routes — device bug")
        out = self._split_fused_wire(arr, cn, n, b)
        self.fused_batches += 1
        if tok:
            st["decode"].end(tok)
        return out

    @staticmethod
    def _split_fused_wire(arr, cn, n: int, b: int) -> List[np.ndarray]:
        flat = arr[:n].astype(np.int64)
        if n and int(flat.min()) < 0:
            # a -1 here means a cleared row's bit survived to the final
            # output — device or compaction bug, never valid concurrency
            raise AssertionError(
                "cleared-row fid escaped the fused device decode")
        bounds = np.cumsum(cn[: b - 1])
        return np.split(flat, bounds)

    def _complete_fused_split(self, handle) -> List[np.ndarray]:
        _tag, b, order, meta, parts, ctx, packed, budgets = handle
        dev, fdev, lay = ctx
        st = self._stages
        tok = st["fetch"].begin(b) if self._timed() else 0
        try:
            while True:
                arr = fetch(packed, "fused match fetch")
                segs = []
                regrow = list(budgets)
                ok = True
                o = 0
                for bi, ((s, pb, tier), g) in enumerate(zip(meta, budgets)):
                    fid_seg = arr[o : o + g]
                    cn = arr[o + g : o + g + pb].astype(np.int64)
                    o += g + pb
                    segs.append((fid_seg, cn))
                    n = int(cn.sum())
                    if n > g:
                        ok = False
                        g2 = self._regrown(n, s, pb)
                        regrow[bi] = g2
                        self._budgets[(pb, tier)] = max(
                            self._budgets.get((pb, tier), 0), g2)
                if ok:
                    break
                budgets = tuple(regrow)
                packed = _pj("match_fused_split", _match_fused_split, dev,
                             fdev, tuple(parts), budgets, layout=lay)
        except NeedsCompile:  # a regrown budget has no program yet
            if tok:
                st["fetch"].end(tok)
            raise
        if tok:
            # one clock read closes fetch and opens decode
            tok = st["decode"].begin_at(abs(tok) + st["fetch"].end(tok))
        out: List[Optional[np.ndarray]] = [None] * b
        pos = 0
        for (s, pb, tier), (fid_seg, cn) in zip(meta, segs):
            if cn[s:].any():
                raise AssertionError("padded topic produced routes — device bug")
            rows = self._split_fused_wire(fid_seg, cn, int(cn.sum()), s)
            for orig, r in zip(order[pos : pos + s], rows):
                out[orig] = r
            pos += s
        self.fused_batches += 1
        if tok:
            st["decode"].end(tok)
        return out

    def prewarm(self, floor: int = PREWARM_FLOOR) -> None:
        """Latch ``floor`` as the sticky pad floor and compile its program,
        so cfg1-style traffic (a lone publish per dispatch) reuses one
        already-compiled executable instead of paying a fresh XLA compile
        per distinct tiny shape. The warm-up IS that traffic: one topic,
        padded to the floor. Safe to call from a background thread at
        broker start; the match runs against the live table and its
        result is discarded."""
        old = self._pad_floor
        if not self._pad_floor_pinned:
            # an explicit RMQTT_PAD_FLOOR seed (autotune replay) outranks
            # the default latch: warm the SEEDED floor's shape and leave
            # the floor where the operator/fitter put it
            self._pad_floor = max(old, int(floor))
        try:
            self.match(["\x00prewarm/nomatch"])
        except Exception as e:  # pragma: no cover - defensive
            self._pad_floor = old
            _LOG.warning("matcher prewarm failed (%s); first small "
                         "publishes will pay the compile", e)
            return
        if _DEVPROF.enabled:
            # pad-waste visibility (floor changes included): the cfg1
            # small-batch regime must SHOW why it pays what it pays
            _DEVPROF.note_pad_floor(self._pad_floor, old)
        elif self._pad_floor != old:
            _LOG.info("sticky pad floor %d -> %d (small batches pad up "
                      "to this compiled shape)", old, self._pad_floor)

    def set_pad_floor(self, floor: int) -> int:
        """Knob seam (broker/knobs.py): set the sticky pad floor to an
        exact value — unlike ``prewarm()``'s monotonic latch this may
        LOWER it (the autotuner's ladder; a new smaller shape compiles
        once on next use, a cost the canary epoch weighs). → the old
        floor (the rollback token)."""
        old = self._pad_floor
        self._pad_floor = max(1, int(floor))
        if self._pad_floor != old and _DEVPROF.enabled:
            _DEVPROF.note_pad_floor(self._pad_floor, old)
        return old

    def hbm_breakdown(self) -> dict:
        """Live HBM occupancy model of this matcher's device residency:
        automaton tiles (packed or legacy), the fused pipeline's row→fid
        map, per-segment arrays — plus the host-side overlay journal depth
        and what legacy field-major tiles would cost at the same padded
        capacity (the packed-vs-legacy delta the roofline models). The
        profiler reconciles the modeled total against ``jax.live_arrays()``
        (broker/devprof.py ``hbm_snapshot``)."""

        def nb(a) -> int:
            try:
                return int(a.nbytes) if a is not None else 0
            except Exception:  # pragma: no cover - exotic array types
                return 0

        tiles = fid = segs = 0
        if self._segments is not None:
            segs = len(self._segments)
            for _base, _end, dev, fdev in self._segments:
                tiles += nb(dev)
                fid += nb(fdev)
        else:
            tiles = nb(self._dev_arrays)
            fid = nb(self._dev_fids)
        t = self.table
        up = self._dev_up_chunks or _pad_chunk_count(t.nchunks)
        legacy = up * CHUNK * (t.max_levels + 3) * (4 if t._tok_wide else 2)
        return {
            "layout": "packed" if self._dev_playout is not None else "legacy",
            "tiles_bytes": tiles,
            "fid_map_bytes": fid,
            "segments": segs,
            "legacy_tiles_bytes_model": int(legacy),
            "overlay_journal_entries": len(t._fid_undo_v),
            "total_bytes": tiles + fid,
        }

    def _submit_segmented(self, ttok, tlen, tdollar, chunk_ids, b: int, snap):
        """One sub-handle per table segment: global candidate chunk ids are
        remapped to segment-local ids (front-packed, trimmed to a sticky
        per-segment NC), matched against the segment's device array, and
        decoded through the segment's affine slice of the fid map — or, on
        the fused pipeline, through the segment's device fid rows (which
        carry GLOBAL fids, so segment results merge by concatenation)."""
        cid = chunk_ids.astype(np.int32, copy=False)
        lay = self._dev_playout
        handles = []
        for si, (base, end, dev, fdev) in enumerate(self._segments):
            if base == 0:
                loc = np.where(cid < end, cid, 0)
                fid_base = 0
            else:
                loc = np.where((cid >= base) & (cid < end), cid - (base - 1), 0)
                fid_base = (base - 1) * CHUNK
            loc = _front_pack(loc)
            mx = int((loc != 0).sum(axis=1).max(initial=0))
            if mx == 0:
                # no candidate in this segment for the whole batch: skip the
                # kernel launch and result fetch entirely
                handles.append(("E", b))
                continue
            ncs = max(self._seg_nc.get(si, 8), 1 << (mx - 1).bit_length())
            self._seg_nc[si] = ncs
            if loc.shape[1] >= ncs:
                loc = loc[:, :ncs]
            else:
                loc = np.pad(loc, ((0, 0), (0, ncs - loc.shape[1])))
            if loc.max(initial=0) < 0x10000:
                loc = loc.astype(np.uint16)
            padded = loc.shape[0]
            if self._fused is not False and fdev is not None:
                if self._fused is None:
                    g = self._budget_for(padded, ncs)
                    ok, results = self._decide_fused(
                        dev, fdev, ttok, tlen, tdollar, loc, b, g, snap,
                        fid_base)
                    if ok is not None:  # None = vacuous, stay undecided
                        self._fused = ok
                    if results is not None:
                        handles.append(("r", results))
                        continue
                if self._fused:
                    h = self._submit_fused(dev, ttok, tlen, tdollar, loc,
                                           None, padded, b, snap, fdev=fdev)
                    if h is not None:
                        handles.append(h)
                        continue
            split = self._split_plan(loc, b)
            if split is not None:
                handles.append(self._submit_split(
                    dev, ttok, tlen, tdollar, loc, split, fid_base, snap
                ))
                continue
            g = self._budget_for(padded, ncs)
            packed = _match_global(dev, ttok, tlen, tdollar, loc, budget=g,
                                   layout=lay)
            handles.append(("g", b, loc,
                            (dev, ttok, tlen, tdollar, None, lay),
                            packed, g, fid_base, snap))
        return ("M", b, handles)

    _EMPTY_FIDS = np.empty(0, dtype=np.int64)

    def _complete_segmented(self, handle) -> List[np.ndarray]:
        _tag, b, handles = handle
        fused_before = self.fused_batches
        per_seg = [
            # sub-handles complete through the impl directly: only the
            # top-level "M" handle owns a profiler flight record
            [self._EMPTY_FIDS] * b if h[0] == "E" else self._complete_impl(h)
            for h in handles
        ]
        if self.fused_batches > fused_before:
            # per-segment completes each bump the counter, but they are ONE
            # logical batch — the stat must stay comparable with dispatches
            self.fused_batches = fused_before + 1
        out: List[np.ndarray] = []
        for i in range(b):
            arrs = [s[i] for s in per_seg if len(s[i])]
            if not arrs:
                out.append(per_seg[0][i])
            elif len(arrs) == 1:
                out.append(arrs[0])
            else:
                out.append(np.sort(np.concatenate(arrs)))
        return out

    def _submit_split(self, dev, ttok, tlen, tdollar, chunk_ids, split,
                      fid_base: int = 0, snap=None):
        order, sizes, tiers = split
        b = len(order)
        parts: List[Tuple] = []
        meta: List[Tuple[int, int, int]] = []  # (nb, padded_b, tier)
        budgets: List[int] = []
        pos = 0
        for tier, s in zip(tiers, sizes):
            s = int(s)
            if not s:
                continue
            idx = order[pos : pos + s]
            pos += s
            pb = 1 << (s - 1).bit_length() if s > 1 else 1
            pt = np.zeros((pb, ttok.shape[1]), dtype=ttok.dtype)
            pt[:s] = ttok[idx]
            pl = np.full((pb,), -2, dtype=tlen.dtype)
            pl[:s] = tlen[idx]
            pd = np.zeros((pb,), dtype=bool)
            pd[:s] = tdollar[idx]
            # candidate lists are stored front-packed, so a count ≤ tier
            # topic's chunks all live in the first `tier` columns
            pc = np.zeros((pb, tier), dtype=chunk_ids.dtype)
            pc[:s] = chunk_ids[idx, :tier]
            g = self._budget_for(pb, tier)
            parts.append((pt, pl, pd, pc))
            meta.append((s, pb, tier))
            budgets.append(g)
        lay = self._dev_playout
        packed = _pj("match_global_split", _match_global_split, dev,
                     tuple(parts), tuple(budgets), layout=lay)
        return ("s", b, order, meta, parts, (dev, lay), packed, tuple(budgets),
                fid_base, snap)

    def _complete_split(self, handle) -> List[np.ndarray]:
        _tag, b, order, meta, parts, ctx, packed, budgets, fid_base, snap = handle
        dev, lay = ctx
        while True:
            arr = fetch(packed, "match result fetch")
            segs: List[Tuple[np.ndarray, np.ndarray]] = []
            regrow = list(budgets)
            ok = True
            o = 0
            for bi, ((s, pb, tier), g) in enumerate(zip(meta, budgets)):
                routes_seg = arr[o : o + g]
                cn = arr[o + g : o + g + pb].astype(np.int64)
                o += g + pb
                segs.append((routes_seg, cn))
                n = int(cn.sum())
                if n > g:
                    ok = False
                    g2 = self._regrown(n, s, pb)
                    regrow[bi] = g2
                    self._budgets[(pb, tier)] = max(
                        self._budgets.get((pb, tier), 0), g2
                    )
            if ok:
                break
            budgets = tuple(regrow)
            packed = _pj("match_global_split", _match_global_split, dev,
                         tuple(parts), budgets, layout=lay)
        # the decode snapshot is taken AFTER the blocking fetch (like every
        # other complete path); _decode_revalidated closes the
        # overlay→gather write window without stalling mutations
        def decode(fid_map, overlay, strict):
            out: List[Optional[np.ndarray]] = [None] * b
            pos = 0
            for (s, pb, tier), part, (routes_seg, cn) in zip(meta, parts, segs):
                n = int(cn.sum())
                rows = _decode_routes(routes_seg[:n], cn, part[3], s, fid_map,
                                      overlay=overlay, strict=strict)
                for orig, r in zip(order[pos : pos + s], rows):
                    out[orig] = r
                pos += s
            return out

        return self._decode_revalidated(snap, fid_base, decode)

    def _decode_revalidated(self, snap, fid_base: int, decode):
        """Close the overlay→gather window without serializing decode
        against mutations: run ``decode(fid_map, overlay, strict)``
        optimistically lock-free, then revalidate ``table.version`` under
        the lock. Mutations write the fid map and bump version under that
        same lock, so an unchanged version proves no in-place write could
        have landed between the overlay snapshot and the gather and the
        result stands; a changed version (a subscribe raced this decode —
        rare) redoes the decode under the lock. Holding the lock
        unconditionally instead would stall every subscribe/unsubscribe
        for the full decode, native per-topic sort included
        (~10ms/200K routes)."""
        t = self.table
        v0 = t.version
        res = decode(*self._snap_decode_state(snap, fid_base))
        with t._mu:
            if t.version == v0:
                return res
            return decode(*self._snap_decode_state(snap, fid_base))

    def _snap_decode_state(self, snap, fid_base: int = 0):
        """→ (fid_map, overlay, strict) for decoding a handle.

        ``fid_map`` is the row→fid array the handle was submitted against;
        ``overlay`` patches rows mutated since back to their submit-time
        fids (None = nothing to patch); ``strict=False`` means the undo
        journal overflowed — decode best-effort against the live map and
        drop rows that have since been cleared instead of asserting."""
        if snap is None:
            fid_map = self.table._fid_of_row
            overlay, ok = None, True
        else:
            fid_map = snap.fid_map
            overlay, ok = self.table.fid_overlay(snap.version, snap.epoch)
            if not ok or not overlay:
                # journal too old (ok=False): the snapshot array still only
                # carries ITS epoch's in-place writes — decode against it
                # best-effort, dropping rows cleared since (never the live
                # map, which may belong to a newer layout entirely)
                overlay = None
        if fid_base:
            fid_map = fid_map[fid_base:]
            if overlay:
                overlay = {r - fid_base: f for r, f in overlay.items()
                           if r >= fid_base}
        return fid_map, overlay, ok

    def match_complete(self, handle) -> List[np.ndarray]:
        """Block on a ``match_submit`` handle and decode to fid arrays."""
        if not _DEVPROF.enabled:
            if self._prof_pending:
                # entries from a just-disabled profiler must still be
                # dropped: a pending record holds the handle (device
                # buffers included) and would pin it until 16 future
                # ENABLED submits flush it with bogus timing
                self._prof_drop(handle)
            return self._complete_impl(handle)
        ent = self._prof_drop(handle)
        if ent is None:
            # a handle submitted before the profiler flipped on (or an
            # internal sub-handle): complete without a flight record
            return self._complete_impl(handle)
        _h, rec, sn0 = ent
        fused0 = self.fused_batches
        t0 = time.perf_counter_ns()
        out = self._complete_impl(handle)
        rec["complete_ns"] = time.perf_counter_ns() - t0
        rec["fused"] = self.fused_batches > fused0
        rec["routes"] = int(sum(len(r) for r in out))
        if sn0 is not None:
            # per-stage ns deltas (PR9 stage_timing). Pipelined overlap can
            # smear attribution between ADJACENT records (stage counters
            # are matcher-cumulative); totals stay exact
            sn1 = self.stage_ns
            rec["stage_ns"] = {k: sn1[k] - sn0[k] for k in sn1}
        _DEVPROF.note_dispatch(rec, rec["submit_ns"] + rec["complete_ns"])
        return out

    def _prof_drop(self, handle):
        """Pop (by handle IDENTITY) this handle's pending flight record,
        if any — sub-handles and pre-profiler handles return None."""
        with self._prof_lock:
            for i, cand in enumerate(self._prof_pending):
                if cand[0] is handle:
                    del self._prof_pending[i]
                    return cand
        return None

    def _complete_impl(self, handle) -> List[np.ndarray]:
        if handle[0] == "M":
            return self._complete_segmented(handle)
        if handle[0] == "r":
            return handle[1]  # pre-resolved (first-use fused verify)
        if handle[0] == "f":
            return self._complete_fused(handle)
        if handle[0] == "fs":
            return self._complete_fused_split(handle)
        if handle[0] == "s":
            return self._complete_split(handle)
        if handle[0] == "g":
            return self._complete_global(handle)
        raise ValueError(f"unknown match handle kind {handle[0]!r}")

    def _group_inputs(self, groups: np.ndarray, chunk_ids: np.ndarray):
        """→ (uniq_cand [U_pow2, NC], inv [B]) for the grouped upload, or
        None when the batch doesn't dedup (synthetic uniform streams barely
        share prefixes; live MQTT traffic — devices republishing the same
        topics — is where U collapses and the upload shrinks)."""
        uq, first_idx, inv = np.unique(
            groups, return_index=True, return_inverse=True
        )
        u = len(uq)
        u_pow2 = 1 << (max(1, u) - 1).bit_length()
        if u_pow2 >= groups.shape[0]:
            # no dedup (or a batch so small the pow2 bucket erases it):
            # the plain [B, NC] upload is strictly cheaper
            return None
        self._u_cap = max(getattr(self, "_u_cap", 1), u_pow2)
        uniq_cand = np.zeros((self._u_cap, chunk_ids.shape[1]),
                             dtype=chunk_ids.dtype)
        uniq_cand[:u] = chunk_ids[first_idx]
        inv_dt = np.uint16 if self._u_cap <= 0x10000 else np.int32
        return uniq_cand, inv.astype(inv_dt, copy=False)

    def _complete_global(self, handle) -> List[np.ndarray]:
        _tag, b, chunk_ids, dev_inputs, packed, g, fid_base, snap = handle
        padded, nc = chunk_ids.shape
        dev, ttok, tlen, tdollar, grouped, lay = dev_inputs
        st = self._stages
        tok = st["fetch"].begin(b) if self._timed() else 0
        try:
            while True:
                # ONE fetch per match: [routes..., cnts...] (counts are
                # truncation-exact, so overflow is detectable from the same
                # array that carries the routes)
                arr = fetch(packed, "match result fetch")
                cn = arr[g:].astype(np.int64)
                n = int(cn.sum())
                if n <= g:
                    break
                g = self._regrown(n, b, padded)
                # sticky pow2 regrow for this batch shape
                self._budgets[(padded, nc)] = max(self._budgets.get((padded, nc), 0), g)
                packed = self._run_global(dev, ttok, tlen, tdollar,
                                          chunk_ids, grouped, g, lay)
        except NeedsCompile:  # a regrown budget has no program yet
            if tok:
                st["fetch"].end(tok)
            raise
        if tok:
            # one clock read closes fetch and opens decode
            tok = st["decode"].begin_at(abs(tok) + st["fetch"].end(tok))
        out = self._decode_revalidated(
            snap, fid_base,
            lambda fid_map, overlay, strict: _decode_routes(
                arr[:n], cn, chunk_ids, b, fid_map,
                overlay=overlay, strict=strict))
        if tok:
            st["decode"].end(tok)
        return out

    def match(self, topics: Sequence[str], pad_to_pow2: bool = True) -> List[np.ndarray]:
        return self.match_complete(self.match_submit(topics, pad_to_pow2))


def _front_pack(a: np.ndarray) -> np.ndarray:
    """Stable-move each row's nonzero entries to the front (zeros pad the
    tail) — segment remapping punches holes in the front-packed candidate
    lists, and the column trim below assumes front-packing."""
    order = np.argsort(a == 0, axis=1, kind="stable")
    return np.take_along_axis(a, order, axis=1)


def _overlay_fids(rows, fids, tj, overlay, strict):
    """Patch gathered fids through a submit-time overlay (rows mutated
    after the handle's snapshot get their AS-OF fids back) and, in
    non-strict mode, drop rows cleared since (their -1 is a legitimate
    concurrent unsubscribe, not a device bug)."""
    if overlay:
        ov_rows = np.fromiter(overlay.keys(), dtype=np.int64, count=len(overlay))
        m = np.isin(rows, ov_rows)
        if m.any():
            fids[m] = np.asarray(
                [overlay[int(r)] for r in rows[m]], dtype=np.int64
            )
    if not strict:
        keep = fids >= 0
        if not bool(keep.all()):
            return tj[keep], fids[keep]
    return tj, fids


def _decode_routes(
    routes: np.ndarray, cn: np.ndarray, chunk_ids: np.ndarray, b: int,
    fid_map: np.ndarray, overlay=None, strict: bool = True,
) -> List[np.ndarray]:
    """Route-level global compaction → per-topic sorted fid arrays.

    ``routes`` carries one ``widx*32 + bitpos`` entry per match, flat
    topic-major by the two-stage prefix-sum construction; ``cn`` is the
    per-(padded-)topic route count vector, which reattributes slots to
    topics. Native path in runtime/encode.cc (rt_match_decode_routes:
    fid map + per-topic sort); the numpy fallback doubles as its
    differential oracle, where the composite-key sort in
    ``_group_sorted`` dominates (~10ms/200K routes)."""
    if overlay is None and strict:
        native = _native_decode_routes(routes, cn, chunk_ids, b, fid_map)
        if native is not None:
            return native
    return _numpy_decode_routes(routes, cn, chunk_ids, b, fid_map, overlay, strict)


def _native_decode_routes(routes, cn, chunk_ids, b, fid_map) -> Optional[List[np.ndarray]]:
    try:
        from rmqtt_tpu import runtime as rt
    except Exception:
        return None
    flat = rt.match_decode_routes(
        np.ascontiguousarray(routes, dtype=np.uint32),
        np.ascontiguousarray(cn, dtype=np.int64),
        np.ascontiguousarray(chunk_ids, dtype=np.int32),
        b, WORDS_PER_CHUNK, CHUNK, fid_map,
    )
    if flat is None:
        return None
    bounds = np.cumsum(cn[: b - 1])
    return np.split(flat, bounds)


def _numpy_decode_routes(
    routes: np.ndarray, cn: np.ndarray, chunk_ids: np.ndarray, b: int,
    fid_map: np.ndarray, overlay=None, strict: bool = True,
) -> List[np.ndarray]:
    wpc = WORDS_PER_CHUNK
    padded = chunk_ids.shape[0]
    if cn[b:].any():
        # same fail-loudly contract as the native decoder: a padded topic
        # (tlen=-2, can match nothing) with a nonzero count is a device/
        # compaction bug — never misattribute its routes to topic b-1
        raise AssertionError("padded topic produced routes — device bug")
    tj = np.repeat(np.arange(padded, dtype=np.int64), cn)
    r = routes.astype(np.int64, copy=False)
    widx = r >> 5
    rows = (
        chunk_ids[tj, widx // wpc].astype(np.int64) * CHUNK
        + (widx % wpc) * 32
        + (r & 31)
    )
    fids = fid_map[rows]
    tj, fids = _overlay_fids(rows, fids, tj, overlay, strict)
    return _group_sorted(tj, fids, b)


def _group_sorted(tj: np.ndarray, fids: np.ndarray, b: int) -> List[np.ndarray]:
    """(topic index, fid) pairs → per-topic sorted fid arrays via one
    composite-key sort (the tail of the numpy decode oracle; it beats a
    two-key lexsort ~2x on 200K matches).

    The pack requires 0 <= fid < 2^32 — a -1 (cleared-row sentinel, would
    mean a kernel or compaction bug) or a fid past 2^32 (4.3 billion add()
    calls) must fail loudly, not silently corrupt cross-topic attribution."""
    if fids.size and (int(fids.min()) < 0 or int(fids.max()) >= 1 << 32):
        raise AssertionError(
            f"fid out of composite-key range: min={fids.min()} max={fids.max()}"
        )
    composite = np.sort((tj.astype(np.int64) << 32) | fids)
    tj_sorted = composite >> 32
    out = composite & np.int64(0xFFFFFFFF)
    bounds = np.searchsorted(tj_sorted, np.arange(1, b))
    return np.split(out, bounds)
