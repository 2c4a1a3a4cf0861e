"""Pallas TPU kernel for the partitioned-match inner loop (BT-wave form).

Replaces the ``lax.scan`` body of `ops/partitioned.py::match_partitioned_impl`
(gather chunk tile → level match → pack bits) with a hand-pipelined kernel.
Grid = one program per ``BT`` topics; each step DMAs a WAVE of BT tiles —
the 8 topics' k-th candidate chunks — HBM→VMEM double-buffered, then
matches all BT topics at once as [BT, CHUNK] vectors.

Why waves (round-3 VERDICT item 4): the first-light kernel processed one
(topic, chunk) per step as [1, CHUNK] rows, using ONE of the VPU's 8
sublanes — 8× wasted vector throughput, and it lost the race to the lax
path (132 ms vs 79 ms at cfg3). The wave form does the same DMA volume in
BT-deep bursts (better DMA pipelining), runs the mask math in full
(8, 128) vregs, and issues one [BT, CHUNK]×[CHUNK, WPC] MXU bit-pack per
step instead of 2×BT [1, CHUNK] ones — 8× fewer steps at the same
per-step cost.

Mosaic-lowering constraints that shaped this kernel (each rejected an
earlier revision on real TPU — interpret mode hides all of them):
- no i1-vector reductions or i1-i1 binary ops (widen to i8 + unsupported
  trunci): every mask is int32; comparisons only feed where(cond, 1, 0);
- no unsigned reductions: bits pack via int32 sums of distinct powers of
  two (wrap-exact), bitcast to uint32 at the end;
- vector stores need static lane offsets: each step stores a full
  contiguous [BT, WPC] row range at a dynamic sublane offset, so the out
  block is chunk-major [nc*BT, WPC] — the wrapper transposes back to the
  caller's [B, NC*WPC] order inside the same jit;
- a DMA slice must cover whole tiles of the source array's layout, and
  the layout is XLA's, not the kernel's: the legacy table is field-major
  [L+3, CHUNK] with the L+3 field rows padded up to the dtype's sublane
  tile (8 rows of int32, 16 of int16) before the call, and the packed
  table is viewed as [chunks, groups, CHUNK] so that one chunk is one
  (groups, 128) tile — sliced as a row of a flat [chunks, groups*CHUNK]
  array it is a sub-tile (1, 256) window of an (8, 128) tiling, which
  Mosaic refuses ("Slice shape along dimension 0 must be aligned to
  tiling (8), but is 1"; likewise "dimension 1 ... but is 11" for the
  unpadded int16 legacy tile). tests/test_chip_compile.py compiles both
  entry points for a described v5e so the next such refusal costs no
  chip time;
- dynamic-sublane vector loads from VMEM blocks are avoided: per-topic
  values (tokens/tlen/tdollar) ride as [BT, ·] VMEM blocks read at STATIC
  level offsets and lane-broadcast; candidate chunk ids stay SMEM scalars
  (DMA descriptors need scalar indices); the level loop is unrolled.

The table operand is declared ``pl.ANY``: the compiler places it — in VMEM
when the whole table fits (the 1M-filter packed table is 8 MB), else HBM —
and the kernel only ever DMAs chunk tiles out of it.

Semantics are identical to the lax path (same [B, NC*WPC] packed words);
`PartitionedMatcher` verifies that on-device at first use and stays on lax
if the results disagree — an unprofiled kernel must never change routing
results. A kernel that is selected and does not COMPILE is an error there,
not a fallback.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rmqtt_tpu.ops.encode import PLUS_TOK, PackedLayout

BT = 8  # topics per program = one full VPU sublane dimension


def _kernel(nc: int, lvl: int, chunk: int, cid_ref, ttok_ref, tlen_ref,
            tdollar_ref, plo_ref, phi_ref, rows_hbm, out_ref):
    def body(scratch, sems):
        def start_wave(slot, k):
            # BT concurrent copies: topic t's k-th candidate tile → lane t
            for t in range(BT):
                pltpu.make_async_copy(
                    rows_hbm.at[cid_ref[t, k]], scratch.at[slot, t],
                    sems.at[slot, t],
                ).start()

        def wait_wave(slot, k):
            for t in range(BT):
                pltpu.make_async_copy(
                    rows_hbm.at[cid_ref[t, k]], scratch.at[slot, t],
                    sems.at[slot, t],
                ).wait()

        start_wave(0, 0)

        def step(k, _):
            slot = k % 2

            @pl.when(k + 1 < nc)
            def _():
                start_wave((k + 1) % 2, k + 1)

            wait_wave(slot, k)
            # [BT, L+3 (sublane-padded), CHUNK] field-major; tiles may ship
            # int16 (half the DMA bytes) — widen once after load, the mask
            # math stays int32. Pad rows sit past lvl+2 and are never read.
            tiles = scratch[slot].astype(jnp.int32)
            flen = tiles[:, lvl, :]  # [BT, CHUNK]
            plen = tiles[:, lvl + 1, :]
            flags = tiles[:, lvl + 2, :]
            # count failing levels in int32; a level passes when the filter
            # token equals the topic token, is '+', or lies beyond the
            # filter's prefix. Static (unrolled) level loop; topic tokens
            # are [BT, 1] VMEM columns lane-broadcast across CHUNK.
            bad = jnp.zeros((BT, chunk), jnp.int32)
            for level in range(lvl):
                f = tiles[:, level, :]  # [BT, CHUNK]
                tt = ttok_ref[:, level : level + 1]  # [BT, 1]
                e = (
                    jnp.where(f == tt, 1, 0)
                    + jnp.where(f == PLUS_TOK, 1, 0)
                    + jnp.where(plen <= level, 1, 0)
                )
                bad = bad + jnp.where(e == 0, 1, 0)
            hh = flags & 1
            fw = jnp.where((flags & 2) != 0, 1, 0)
            tl = tlen_ref[:, 0:1]  # [BT, 1]
            ge = jnp.where(tl >= plen, 1, 0)
            eqlen = jnp.where(tl == flen, 1, 0)
            len_ok = hh * ge + (1 - hh) * eqlen
            dollar_bad = tdollar_ref[:, 0:1] * fw  # tdollar is 0/1
            m32 = jnp.where(bad == 0, 1, 0) * len_ok * (1 - dollar_bad)
            # pack bits on the (otherwise idle) MXU: Mosaic cannot reshape
            # lanes into sublanes ((BT,CHUNK)->(BT*WPC,32)), so word j = Σ
            # m[j*32+i]<<i is computed as two exact f32 matmuls against
            # constant selectors (low/high 16 bits per word — each sum of
            # distinct powers of two stays < 2^16, exact in f32), then
            # recombined in int32 and bitcast to uint32
            mf = m32.astype(jnp.float32)  # [BT, CHUNK]
            dims = (((1,), (0,)), ((), ()))
            wlo = lax.dot_general(mf, plo_ref[...], dims,
                                  preferred_element_type=jnp.float32)
            whi = lax.dot_general(mf, phi_ref[...], dims,
                                  preferred_element_type=jnp.float32)
            words = wlo.astype(jnp.int32) + (whi.astype(jnp.int32) << 16)
            # one contiguous [BT, WPC] store per step (chunk-major layout)
            out_ref[pl.ds(k * BT, BT), :] = lax.bitcast_convert_type(
                words, jnp.uint32
            )

        lax.fori_loop(0, nc, step, None)

    pl.run_scoped(
        body,
        scratch=pltpu.VMEM((2, BT) + rows_hbm.shape[1:], rows_hbm.dtype),
        sems=pltpu.SemaphoreType.DMA((2, BT)),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def match_words_pallas(packed_rows, ttok, tlen, tdollar, chunk_ids,
                       interpret: bool = False):
    """→ packed match words [B, NC*WPC] uint32 (B must be a multiple of BT)."""
    b, nc = chunk_ids.shape
    nchunks, width, chunk = packed_rows.shape
    lvl = width - 3
    wpc = chunk // 32
    # whole-tile DMA (module docstring): pad the field rows up to the
    # dtype's sublane tile — 8 rows of int32, 16 of int16
    pad = -width % (8 * (4 // packed_rows.dtype.itemsize))
    if pad:
        packed_rows = jnp.pad(packed_rows, ((0, 0), (0, pad), (0, 0)))
    kernel = functools.partial(_kernel, nc, lvl, chunk)
    # constant bit-pack selectors: P[c, j] = 2^(c%32 - half*16) when word
    # c//32 == j and c%32 in the half's 16-bit range, else 0 (see _kernel)
    c = np.arange(chunk)
    sel = (c[:, None] // 32) == np.arange(wpc)[None, :]
    pos = c[:, None] % 32
    plo = np.where(sel & (pos < 16), 2.0**pos, 0.0).astype(np.float32)
    phi = np.where(sel & (pos >= 16), 2.0 ** (pos - 16), 0.0).astype(np.float32)
    out = pl.pallas_call(
        kernel,
        grid=(b // BT,),
        in_specs=[
            pl.BlockSpec((BT, nc), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((BT, lvl), lambda i: (i, 0)),  # VMEM: lane-broadcast
            pl.BlockSpec((BT, 1), lambda i: (i, 0)),
            pl.BlockSpec((BT, 1), lambda i: (i, 0)),
            pl.BlockSpec((chunk, wpc), lambda i: (0, 0)),
            pl.BlockSpec((chunk, wpc), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # never blocked: DMA source
        ],
        out_specs=pl.BlockSpec((nc * BT, wpc), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b // BT * nc * BT, wpc), jnp.uint32),
        interpret=interpret,
    )(
        chunk_ids.astype(jnp.int32),
        ttok.astype(jnp.int32),
        tlen.astype(jnp.int32).reshape(b, 1),
        tdollar.astype(jnp.int32).reshape(b, 1),
        plo,
        phi,
        packed_rows,
    )
    # chunk-major [B/BT, nc, BT, WPC] → topic-major [B, NC*WPC] (the
    # caller's contract); a single XLA transpose-copy, trivial next to the
    # scan it replaces
    return (
        out.reshape(b // BT, nc, BT, wpc)
        .transpose(0, 2, 1, 3)
        .reshape(b, nc * wpc)
    )


# ------------------------------------------------ bit-packed tile variant
def _kernel_packed(nc: int, layout: PackedLayout, chunk: int, cid_ref,
                   ttok_ref, tlen_ref, tdollar_ref, plo_ref, phi_ref,
                   rows_hbm, out_ref):
    """The wave kernel over BIT-PACKED tiles (pack_device_rows_packed):
    ``rows_hbm`` is ``[up_chunks, groups, CHUNK]`` int32 — four byte
    planes per lane — so each wave DMAs ``groups*CHUNK*4`` bytes per topic
    instead of the legacy ``(L+3)*CHUNK*2``: the same ≥2× HBM-traffic
    reduction the roofline models, in the kernel that is measured
    HBM-bandwidth-bound. Byte planes unpack with static shifts/masks on
    int32 vectors (no int8 vregs anywhere — Mosaic int8 arithmetic support
    is not something this kernel wants to depend on); everything downstream
    of the unpack (mask math in int32, MXU bit-pack via the f32 selector
    matmuls, chunk-major stores) is identical to ``_kernel``."""
    offs = layout.plane_offsets()
    meta_p = layout.planes - 1

    def body(scratch, sems):
        def start_wave(slot, k):
            for t in range(BT):
                pltpu.make_async_copy(
                    rows_hbm.at[cid_ref[t, k]], scratch.at[slot, t],
                    sems.at[slot, t],
                ).start()

        def wait_wave(slot, k):
            for t in range(BT):
                pltpu.make_async_copy(
                    rows_hbm.at[cid_ref[t, k]], scratch.at[slot, t],
                    sems.at[slot, t],
                ).wait()

        start_wave(0, 0)

        def step(k, _):
            slot = k % 2

            @pl.when(k + 1 < nc)
            def _():
                start_wave((k + 1) % 2, k + 1)

            wait_wave(slot, k)
            tiles = scratch[slot]  # [BT, groups, CHUNK] int32

            def plane(p):
                # byte plane p: static group row + static shift/mask
                grp, sh = p // 4, (p % 4) * 8
                x = tiles[:, grp, :]
                if sh:
                    x = x >> sh
                return x & 0xFF

            meta = plane(meta_p)
            flen = (meta & 31) - 1  # empty rows encode flen+1 = 0
            hh = (meta >> 5) & 1
            fw = (meta >> 6) & 1
            plen = flen - hh
            bad = jnp.zeros((BT, chunk), jnp.int32)
            for i, w in enumerate(layout.widths):
                f = plane(offs[i])
                if w == 2:
                    f = f + (plane(offs[i] + 1) << 8)  # disjoint bytes: + == |
                tt = ttok_ref[:, i : i + 1]  # [BT, 1] lane-broadcast
                e = (
                    jnp.where(f == tt, 1, 0)
                    + jnp.where(f == PLUS_TOK, 1, 0)
                    + jnp.where(plen <= i, 1, 0)
                )
                bad = bad + jnp.where(e == 0, 1, 0)
            tl = tlen_ref[:, 0:1]  # [BT, 1]
            ge = jnp.where(tl >= plen, 1, 0)
            eqlen = jnp.where(tl == flen, 1, 0)
            len_ok = hh * ge + (1 - hh) * eqlen
            dollar_bad = tdollar_ref[:, 0:1] * fw
            m32 = jnp.where(bad == 0, 1, 0) * len_ok * (1 - dollar_bad)
            # MXU bit-pack: same two exact-f32 selector matmuls as _kernel
            mf = m32.astype(jnp.float32)
            dims = (((1,), (0,)), ((), ()))
            wlo = lax.dot_general(mf, plo_ref[...], dims,
                                  preferred_element_type=jnp.float32)
            whi = lax.dot_general(mf, phi_ref[...], dims,
                                  preferred_element_type=jnp.float32)
            words = wlo.astype(jnp.int32) + (whi.astype(jnp.int32) << 16)
            out_ref[pl.ds(k * BT, BT), :] = lax.bitcast_convert_type(
                words, jnp.uint32
            )

        lax.fori_loop(0, nc, step, None)

    pl.run_scoped(
        body,
        scratch=pltpu.VMEM((2, BT, layout.groups, chunk), jnp.int32),
        sems=pltpu.SemaphoreType.DMA((2, BT)),
    )


@functools.partial(jax.jit, static_argnames=("layout", "interpret"))
def match_words_pallas_packed(packed_rows, ttok, tlen, tdollar, chunk_ids,
                              layout: PackedLayout, interpret: bool = False):
    """→ packed match words [B, NC*WPC] uint32 over bit-packed tiles
    (B must be a multiple of BT). Same semantics as ``match_words_pallas``
    and the lax ``scan_words_packed_impl`` — `PartitionedMatcher` verifies
    that on-device at first use and falls back if anything disagrees."""
    b, nc = chunk_ids.shape
    chunk = packed_rows.shape[1] // layout.groups
    wpc = chunk // 32
    # whole-tile DMA (module docstring): one chunk = one (groups, CHUNK)
    # tile. The resident array stays flat for the lax scan; the view costs
    # one relayout copy of the table per call, small next to the B*NC tile
    # reads it enables
    packed_rows = packed_rows.reshape(-1, layout.groups, chunk)
    nlvl = layout.nlvl
    kernel = functools.partial(_kernel_packed, nc, layout, chunk)
    c = np.arange(chunk)
    sel = (c[:, None] // 32) == np.arange(wpc)[None, :]
    pos = c[:, None] % 32
    plo = np.where(sel & (pos < 16), 2.0**pos, 0.0).astype(np.float32)
    phi = np.where(sel & (pos >= 16), 2.0 ** (pos - 16), 0.0).astype(np.float32)
    out = pl.pallas_call(
        kernel,
        grid=(b // BT,),
        in_specs=[
            pl.BlockSpec((BT, nc), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((BT, nlvl), lambda i: (i, 0)),  # VMEM: lane-broadcast
            pl.BlockSpec((BT, 1), lambda i: (i, 0)),
            pl.BlockSpec((BT, 1), lambda i: (i, 0)),
            pl.BlockSpec((chunk, wpc), lambda i: (0, 0)),
            pl.BlockSpec((chunk, wpc), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # never blocked: DMA source
        ],
        out_specs=pl.BlockSpec((nc * BT, wpc), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b // BT * nc * BT, wpc), jnp.uint32),
        interpret=interpret,
    )(
        chunk_ids.astype(jnp.int32),
        ttok.astype(jnp.int32),
        tlen.astype(jnp.int32).reshape(b, 1),
        tdollar.astype(jnp.int32).reshape(b, 1),
        plo,
        phi,
        packed_rows,
    )
    return (
        out.reshape(b // BT, nc, BT, wpc)
        .transpose(0, 2, 1, 3)
        .reshape(b, nc * wpc)
    )
