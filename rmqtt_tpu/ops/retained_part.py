"""Partitioned retained-topic scan: the SUBSCRIBE-side inverse match with
trie-style pruning.

The dense ``ops.retained.RetainedScanner`` scans every stored topic row per
SUBSCRIBE filter — O(retained) per scan, measured at 74 scans/s at 1M
retained topics on the r4 fallback. The reference prunes this with a trie
walk per SUBSCRIBE (`/root/reference/rmqtt/src/retain.rs:373-450`,
``RetainTree::matches``). This module flattens that pruning the same way
``ops.partitioned`` does for the publish direction — a SUBSCRIBE filter is
just a row query from the other side:

- stored retained *topics* (concrete: no wildcards) live in a
  ``PartitionedTable`` keyed by their first ≤3 levels — the same chunked
  layout, shared-chunk packing, stable fid↔row handles, and
  ``pack_device_rows`` device mirror as the router tables;
- an INVERSE index maps masked partition keys → partition keys, so a
  wildcard filter enumerates only the partitions it could match:
  ``home/+/temp/#`` resolves ("4", "home", None, "temp") instead of the
  whole table. Broad filters (``#``, ``+/#``) genuinely match everything
  and degrade to the dense scan's candidate set — no worse than before;
- the kernel is the chunk-tile gather of ``ops.partitioned.scan_words_impl``
  with the wildcard side swapped: rows carry (rtok, rlen, $-flag), the
  batch carries (ftok with ``+`` markers, flen, fprefix, fhash, fwild).
  Mixed batches split into a narrow and a broad NC tier inside ONE jit
  call (each extra device fetch is one more blocking round trip).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from rmqtt_tpu.core.topic import HASH, PLUS, is_metadata, split_levels
from rmqtt_tpu.ops.encode import PLUS_TOK, PAD_TOK
from rmqtt_tpu.ops.partitioned import (
    CHUNK,
    WORDS_PER_CHUNK,
    PartitionedTable,
    pack_device_rows,
)
from rmqtt_tpu.utils.devfetch import fetch


def _key_masks(key: Tuple) -> List[Tuple]:
    """All masked variants of a concrete partition key (None = free slot)."""
    kind, toks = key[0], key[1:]
    out = []
    for bits in range(1 << len(toks)):
        out.append((kind,) + tuple(
            None if (bits >> i) & 1 else toks[i] for i in range(len(toks))
        ))
    return out


def filter_masks(levels: Sequence[str]) -> List[Tuple]:
    """Masked partition keys a wildcard filter must consult.

    Concrete topics only occupy kinds ("1", t0) / ("2E", t0, t1) /
    ("4", t0, t1, t2); a filter with prefix length ``p`` (levels before a
    trailing ``#``) constrains topic level i < p to its literal token
    unless that level is ``+``.
    """
    h = levels[-1] == HASH
    p = len(levels) - 1 if h else len(levels)
    n = len(levels)

    def c(i: int) -> Optional[str]:
        return levels[i] if i < p and levels[i] != PLUS else None

    out: List[Tuple] = []
    if (h and p <= 1) or (not h and n == 1):
        out.append(("1", c(0)))
    if (h and p <= 2) or (not h and n == 2):
        out.append(("2E", c(0), c(1)))
    if h or n >= 3:
        out.append(("4", c(0), c(1), c(2)))
    return out


class RetainedTable(PartitionedTable):
    """Partition-chunked store of concrete retained-topic names.

    Reuses the router table's allocation (shared-chunk packing, stable
    fids, compact) and abuses the unused ``first_wild`` row flag — always
    False for concrete topics — to carry the row's ``$``-topic bit, so
    ``pack_device_rows`` ships it as flag bit 1 with zero layout changes.
    Its keys stay at three levels (no split): the inverse index masks
    exactly those.
    """

    split_rows = None

    def __init__(self, max_levels: int = 8) -> None:
        super().__init__(max_levels)
        # masked key → partition keys (grow-only; keys never disappear)
        self._inv_index: Dict[Tuple, set] = {}
        self._indexed: set = set()
        # filter string → (chunk ids, version) candidate cache
        self._fcand_cache: Dict[str, np.ndarray] = {}
        self._fcand_version = -1
        # version-keyed row→fid snapshot for in-flight scans (fid_snapshot)
        self._fid_snap: Optional[Tuple[int, np.ndarray]] = None

    def fid_snapshot(self) -> np.ndarray:
        """Immutable row→fid mapping AS OF NOW, for pipelined scan handles.

        remove() mutates ``_fid_of_row`` in place and compact() swaps in a
        wholesale-new array (bumping ``version`` either way), so a scan
        completing after a mutation would otherwise decode bit positions
        against the post-mutation mapping (wrong/ghost fids). Memoized on
        ``version``: steady-state scans share one copy (O(1) per scan);
        each mutation burst pays one table-sized copy on the next scan.
        The returned array is never written to — mutations go to the live
        ``_fid_of_row``, and the next snapshot call REPLACES the memo."""
        snap = self._fid_snap
        if snap is None or snap[0] != self.version:
            snap = self._fid_snap = (self.version, self._fid_of_row.copy())
        return snap[1]

    def _write_row(self, row: int, levels) -> None:
        # the base writer derives first_wild from wildcards (always False
        # here); re-derive the $-flag it carries instead, so a compaction
        # replay (install-time journal re-add) preserves it
        super()._write_row(row, levels)
        self.first_wild[row] = bool(levels[0]) and is_metadata(levels[0])

    def add(self, topic: str | Sequence[str]) -> int:
        levels = split_levels(topic) if isinstance(topic, str) else list(topic)
        if any(lev in (PLUS, HASH) for lev in levels):
            raise ValueError(f"retained topic may not contain wildcards: {topic!r}")
        # the $-topic marker in the first_wild flag slot is set by the
        # _write_row override above (single source, shared with replay)
        fid = super().add(levels)
        key = self._key_of_fid[fid]
        if key not in self._indexed:
            self._indexed.add(key)
            for mk in _key_masks(key):
                self._inv_index.setdefault(mk, set()).add(key)
        return fid

    def candidates_for_filter(self, topic_filter: str | Sequence[str]) -> np.ndarray:
        """Candidate chunk ids a wildcard filter must scan."""
        fstr = topic_filter if isinstance(topic_filter, str) else "/".join(topic_filter)
        if self._fcand_version != self.version:
            self._fcand_cache.clear()
            self._fcand_version = self.version
        hit = self._fcand_cache.get(fstr)
        if hit is not None:
            return hit
        levels = split_levels(fstr)
        masks = filter_masks(levels)
        # broad fast path: when the masks would enumerate more partitions
        # than there are chunks, the union is (nearly) the whole table and
        # the Python walk costs more than the scan — hand back every chunk
        # and let the kernel's full-stream tier take it
        total = sum(len(self._inv_index.get(mk, ())) for mk in masks)
        if total > max(4096, self.nchunks):
            out = np.arange(1, self.nchunks, dtype=np.int32)
            self._fcand_cache[fstr] = out
            return out
        chunks: List[int] = []
        seen: set = set()
        for mk in masks:
            for key in self._inv_index.get(mk, ()):
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
        out = np.asarray(chunks, dtype=np.int32)
        self._fcand_cache[fstr] = out
        return out


def retained_scan_words_impl(packed_rows, ftok, flen, fprefix, fhash, fwild,
                             chunk_ids):
    """Inverse partitioned match → packed words [B, NC*WPC] uint32.

    Same single-tile gather per scan step as the forward kernel
    (`ops.partitioned.scan_words_impl`), with the roles swapped::

        level_ok[b,c,i] = (rtok[c,i] == ftok[b,i]) | (ftok[b,i] == '+')
                          | (i >= fprefix[b])
        len_ok[b,c]     = fhash[b] ? rlen[c] >= fprefix[b]
                                   : rlen[c] == flen[b]
        dollar_ok[b,c]  = !(row is $-topic & filter starts with wildcard)
        live[c]         = rlen[c] >= 1     # padding/cleared rows have ≤0;
                                           # a bare '#' (fprefix 0) must not
                                           # match them

    Word w of filter b covers rows ``chunk_ids[b, w // WPC]*CHUNK +
    (w % WPC)*32 .. +31`` — the host maps set bits back to fids.
    """
    b, nc = chunk_ids.shape
    lvl = packed_rows.shape[1] - 3
    ftok = ftok.astype(jnp.int32)
    flen = flen.astype(jnp.int32)
    fprefix = fprefix.astype(jnp.int32)
    chunk_ids = chunk_ids.astype(jnp.int32)
    lvl_idx = jnp.arange(lvl, dtype=jnp.int32)
    bit = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
    plus = ftok == PLUS_TOK  # [B, L]

    def body(_, cid):  # cid: [B]
        g = packed_rows[cid]  # [B, L+3, CHUNK] single tile gather
        rtok = g[:, :lvl, :]
        rlen = g[:, lvl, :]
        flags = g[:, lvl + 2, :]
        rdollar = (flags & 2) != 0
        eq = rtok == ftok[:, :, None]
        beyond = lvl_idx[None, :, None] >= fprefix[:, None, None]
        prefix_ok = jnp.all(eq | plus[:, :, None] | beyond, axis=1)  # [B, CHUNK]
        len_ok = jnp.where(fhash[:, None], rlen >= fprefix[:, None],
                           rlen == flen[:, None])
        dollar_ok = jnp.logical_not(rdollar & fwild[:, None])
        m = prefix_ok & len_ok & dollar_ok & (rlen >= 1)
        packed = jnp.sum(
            m.reshape(b, WORDS_PER_CHUNK, 32).astype(jnp.uint32) * bit[None, None, :],
            axis=-1,
            dtype=jnp.uint32,
        )
        return None, packed  # [B, WPC]

    _, words = lax.scan(body, None, jnp.moveaxis(chunk_ids, 0, 1))
    return jnp.moveaxis(words, 0, 1).reshape(b, nc * WORDS_PER_CHUNK)


def retained_scan_full_impl(packed_rows, ftok, flen, fprefix, fhash, fwild,
                            slab: int):
    """Broad-filter path: stream the WHOLE packed table in contiguous slabs.

    A filter whose candidate set covers most chunks (``#``, ``+/#``) gains
    nothing from gather pruning, and the per-chunk ``lax.scan`` step
    overhead dominates (measured: the gather path lost to the dense scan
    on exactly these). Here the table is reshaped to ``[nsteps, slab]``
    chunk slabs and scanned with ZERO gathers — pure sequential HBM
    streaming; word index is the GLOBAL row word (no chunk indirection).
    → packed words [B, up_chunks*WPC] uint32.
    """
    up_chunks, lvlp3, _ = packed_rows.shape
    lvl = lvlp3 - 3
    b = ftok.shape[0]
    ftok = ftok.astype(jnp.int32)
    flen = flen.astype(jnp.int32)
    fprefix = fprefix.astype(jnp.int32)
    lvl_idx = jnp.arange(lvl, dtype=jnp.int32)
    bit = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
    plus = ftok == PLUS_TOK  # [B, L]
    nsteps = up_chunks // slab
    xs = packed_rows.reshape(nsteps, slab, lvlp3, CHUNK)

    def body(_, g):  # g: [slab, L+3, CHUNK]
        rtok = g[:, :lvl, :]  # [S, L, C]
        rlen = g[:, lvl, :]  # [S, C]
        flags = g[:, lvl + 2, :]
        rdollar = (flags & 2) != 0
        eq = rtok[None] == ftok[:, None, :, None]  # [B, S, L, C]
        beyond = lvl_idx[None, None, :, None] >= fprefix[:, None, None, None]
        prefix_ok = jnp.all(eq | plus[:, None, :, None] | beyond, axis=2)  # [B,S,C]
        len_ok = jnp.where(fhash[:, None, None], rlen[None] >= fprefix[:, None, None],
                           rlen[None] == flen[:, None, None])
        dollar_ok = jnp.logical_not(rdollar[None] & fwild[:, None, None])
        m = prefix_ok & len_ok & dollar_ok & (rlen[None] >= 1)
        packed = jnp.sum(
            m.reshape(b, slab * WORDS_PER_CHUNK, 32).astype(jnp.uint32)
            * bit[None, None, :],
            axis=-1,
            dtype=jnp.uint32,
        )
        return None, packed  # [B, S*WPC]

    _, words = lax.scan(body, None, xs)  # [nsteps, B, S*WPC]
    return jnp.moveaxis(words, 0, 1).reshape(b, up_chunks * WORDS_PER_CHUNK)


def retained_scan_combo_impl(packed_rows, gather_parts, full_parts, slab: int):
    """Run the narrow (gather) and broad (full-stream) tiers in one
    dispatch; 1-D concat so ONE fetch covers the whole batch (each fetch
    is its own blocking round trip)."""
    outs = [retained_scan_words_impl(packed_rows, *p).ravel()
            for p in gather_parts]
    outs += [retained_scan_full_impl(packed_rows, *p, slab=slab).ravel()
             for p in full_parts]
    return jnp.concatenate(outs) if len(outs) > 1 else outs[0]


_retained_scan_combo = jax.jit(retained_scan_combo_impl,
                               static_argnames=("slab",))


class PartitionedRetainedScanner:
    """Device mirror of a ``RetainedTable`` + batched inverse match.

    ``scan`` returns per-filter arrays of matched *fids* (the stable
    handles ``RetainedTable.add`` returned), so callers key messages by
    fid exactly like the dense scanner's row ids. ``scan_submit`` /
    ``scan_complete`` expose the pipelined halves (dispatch overlap).
    """

    #: filters whose candidate set exceeds this fraction of all chunks are
    #: routed to the broad tier (their NC pad would poison the narrow one)
    BROAD_FRAC = 0.25

    def __init__(self, table: RetainedTable, device=None) -> None:
        self.table = table
        self.device = device
        self._dev_version = -1
        self._dev_rows = None
        # sticky pow2 caps: every distinct (B, NC) pair is a fresh XLA
        # compile, so the pads only ever GROW (a 400ms recompile costs more
        # than scanning a few padded slots forever)
        self._nc_cap = 8
        self._b_narrow_cap = 8
        self._b_broad_cap = 4
        self.uploads = 0  # table refreshes that shipped the rows
        self.upload_bytes = 0

    def _refresh(self):
        t = self.table
        if self._dev_version != t.version or self._dev_rows is None:
            if t.needs_compact():  # honors compact_min_ops/compact_ratio
                t.compact()
            # sync the narrow-dtype flags BEFORE packing: pack_device_rows
            # reads _tok_wide directly, and the flag only flips inside
            # _tok_dtype() — packing first would ship int16-wrapped tokens
            # against the int32 filter encode of the same scan
            t._tok_dtype()
            t._cand_dtype()
            put = (functools.partial(jax.device_put, device=self.device)
                   if self.device else jax.device_put)
            packed = pack_device_rows(t)
            self._dev_rows = put(packed)
            self._dev_version = t.version
            self.uploads += 1
            self.upload_bytes += packed.nbytes
        return self._dev_rows

    def _encode_part(self, filters: List[Tuple[int, List[str], np.ndarray]],
                     nc: int, pad_b: int = 1):
        """One NC tier → (ftok, flen, fprefix, fhash, fwild, chunk_ids)."""
        t = self.table
        lvl = t.max_levels
        batch = len(filters)
        b = max(pad_b, 1 << (batch - 1).bit_length() if batch > 1 else batch)
        ftok = np.zeros((b, lvl), dtype=t._tok_dtype())
        flen = np.full((b,), -2, dtype=np.int16)
        fprefix = np.full((b,), lvl + 1, dtype=np.int16)
        fhash = np.zeros((b,), dtype=bool)
        fwild = np.zeros((b,), dtype=bool)
        chunk_ids = np.zeros((b, nc), dtype=t._cand_dtype())
        lookup = t.tokens.lookup
        for j, (_orig, levels, cand) in enumerate(filters):
            hh = levels[-1] == HASH
            # clamp like the forward encode: rows have rlen <= lvl, so
            # comparisons are invariant at lvl+1 and hostile depths can't
            # wrap int16
            flen[j] = min(len(levels), lvl + 1)
            fprefix[j] = min(len(levels) - 1 if hh else len(levels), lvl + 1)
            fhash[j] = hh
            fwild[j] = levels[0] in (PLUS, HASH)
            for i, lev in enumerate(levels[:lvl]):
                ftok[j, i] = PLUS_TOK if lev == PLUS else (
                    PAD_TOK if lev == HASH else lookup(lev))
            chunk_ids[j, : len(cand)] = cand[:nc]
        return ftok, flen, fprefix, fhash, fwild, chunk_ids

    def scan_submit(self, filters: Sequence[str]):
        t = self.table
        dev = self._refresh()
        up_chunks = dev.shape[0]
        slab = min(512, up_chunks)
        # in-batch dedup: subscriber batches repeat filter shapes heavily
        # (every broad ``+/#``-style filter scans the whole table — paying
        # that once per DISTINCT filter, not per subscriber, is most of the
        # mixed-batch win)
        slots: Dict[str, int] = {}
        dups: List[List[int]] = []
        enc: List[Tuple[int, List[str], np.ndarray]] = []
        for j, f in enumerate(filters):
            fstr = f if isinstance(f, str) else "/".join(f)
            s = slots.get(fstr)
            if s is None:
                slots[fstr] = len(enc)
                dups.append([j])
                enc.append((len(enc), split_levels(fstr),
                            t.candidates_for_filter(fstr)))
            else:
                dups[s].append(j)
        broad_floor = max(16, int(t.nchunks * self.BROAD_FRAC))
        narrow = [e for e in enc if len(e[2]) <= broad_floor]
        broad = [e for e in enc if len(e[2]) > broad_floor]
        gather_parts = []
        full_parts = []
        order: List[List[List[int]]] = []
        metas = []
        if narrow:
            mx = max(1, max(len(e[2]) for e in narrow))
            self._nc_cap = max(self._nc_cap, 1 << (mx - 1).bit_length())
            nc = self._nc_cap
            self._b_narrow_cap = max(
                self._b_narrow_cap, 1 << (len(narrow) - 1).bit_length())
            p = self._encode_part(narrow, nc, pad_b=self._b_narrow_cap)
            gather_parts.append(p)
            order.append([dups[e[0]] for e in narrow])
            metas.append(("gather", len(narrow), p[5].shape[0], nc, p[5]))
        if broad:
            # broad filters stream the whole table: no chunk-id plan at all
            self._b_broad_cap = max(
                self._b_broad_cap, 1 << (len(broad) - 1).bit_length())
            p = self._encode_part(broad, 1, pad_b=self._b_broad_cap)
            full_parts.append(p[:5])
            order.append([dups[e[0]] for e in broad])
            metas.append(("full", len(broad), p[0].shape[0], up_chunks, None))
        if not gather_parts and not full_parts:
            return ("empty", len(filters))
        out = _retained_scan_combo(dev, tuple(gather_parts), tuple(full_parts),
                                   slab=slab)
        # snapshot the row→fid mapping (memoized per table version):
        # remove() mutates _fid_of_row in place and compact() swaps the
        # array, so a pipelined scan completing after a mutation would
        # decode bit positions against the post-mutation mapping and
        # return wrong/ghost fids
        return ("h", out, metas, order, len(filters), t.fid_snapshot())

    def scan_complete(self, handle) -> List[np.ndarray]:
        if handle[0] == "empty":
            return [np.empty(0, dtype=np.int64) for _ in range(handle[1])]
        _, out, metas, order, nfilters, fid_of_row = handle
        flat = fetch(out, "retained partitioned scan fetch")
        res: List[Optional[np.ndarray]] = [None] * nfilters
        off = 0
        for (mode, _nreal, b, nc, chunk_ids), idxs in zip(metas, order):
            span = b * nc * WORDS_PER_CHUNK
            words = flat[off: off + span].reshape(b, nc * WORDS_PER_CHUNK)
            off += span
            for j, origs in enumerate(idxs):
                wj = words[j]
                if not wj.any():
                    fids = np.empty(0, dtype=np.int64)
                else:
                    bits = np.unpackbits(
                        np.ascontiguousarray(wj).view(np.uint8),
                        bitorder="little")
                    pos = np.nonzero(bits)[0]
                    if mode == "gather":
                        rows = (chunk_ids[j, pos // (WORDS_PER_CHUNK * 32)]
                                .astype(np.int64) * CHUNK
                                + pos % (WORDS_PER_CHUNK * 32))
                    else:  # full stream: bit position IS the global row
                        rows = pos
                    fids = fid_of_row[rows]
                    fids = np.sort(fids[fids >= 0])
                for orig in origs:  # duplicates share the result array
                    res[orig] = fids
        return res  # type: ignore[return-value]

    def scan(self, filters: Sequence[str]) -> List[np.ndarray]:
        """→ per-filter arrays of matched retained-topic fids."""
        return self.scan_complete(self.scan_submit(filters))
