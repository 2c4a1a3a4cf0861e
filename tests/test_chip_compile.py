"""Ahead-of-time compiles of the served path's jitted programs for a
DESCRIBED TPU v5e (no chip attached): what the chip's compiler refuses is
found here, at no chip time. The CPU backend hides every one of these
failures (compile time, memory).

Shapes are the ones ``chip_smoke.py`` drives: the BASELINE config-3 table
(1,000,000 mixed ``+``/``#`` filters, seed 0 → 7,889 chunks padded to 8,192,
packed layout widths (1,1,1,1,1,2), NC cap 32) and a 60K-topic retained
table. The fused step at 16384x32 compiles in ~40 s (212 s before the
compile fence in ``match_fused_impl``) and is left to ``chip_smoke.py``.

The topology is described inside a module-scoped fixture: only one process
may load the TPU library at a time, so nothing here touches it at import or
collection (on-chip-measurement guide, section 2). A compile that passes is
not a chip run.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from rmqtt_tpu.ops import partitioned as pm
from rmqtt_tpu.ops.encode import PackedLayout

UP_CHUNKS = 8192  # 1M config-3 filters: 7,889 chunks, pow2-padded
NC = 32
LEVELS = 8
LAYOUT = PackedLayout(widths=(1, 1, 1, 1, 1, 2))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` → a ShapeDtypeStruct on the first described
    chip. The persistent compile cache is off around the module: an entry
    written for a described device cannot be read back without a chip, and
    every later run would warn and recompile."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", prior)
    cc.reset_cache()


def _batch(spec, b, packed=True):
    """(ttok, tlen, tdollar, chunk_ids) as the host encode ships them."""
    ttok = (spec((b, LAYOUT.nlvl), jnp.int32) if packed
            else spec((b, LEVELS), jnp.int16))
    return (ttok, spec((b,), jnp.int16), spec((b,), jnp.bool_),
            spec((b, NC), jnp.uint16))


def _tiles(spec, packed=True):
    if packed:
        return spec((UP_CHUNKS, LAYOUT.groups * pm.CHUNK), jnp.int32)
    return spec((UP_CHUNKS, LEVELS + 3, pm.CHUNK), jnp.int16)


def _budget(b):
    return max(256, 1 << (4 * b - 1).bit_length())


@pytest.mark.parametrize(
    "b", [pm.PREWARM_FLOOR, 128, 256, 512, 1024],
    ids=["prewarm8", "batch128", "batch256", "batch512", "batch1024"])
def test_fused_step_compiles(spec, b):
    """``_match_fused`` at the broker's prewarm shape, at the padded shapes
    the cells' device batches take (~508 topics a batch and the smaller
    ones the hybrid leaves: 128, 256, 512; PERF.md §5) and at its largest
    batch (``batch_max`` = 1024)."""
    c = pm._match_fused.lower(
        _tiles(spec), spec((UP_CHUNKS, pm.CHUNK), jnp.int32), *_batch(spec, b),
        budget=_budget(b), layout=LAYOUT).compile()
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("b", [128, 256, 512, 1024])
def test_fused_step_compiles_on_legacy_int32_tiles(spec, b):
    """``_match_fused`` as the ``b1_1m_exact`` table calls it (benchmark cell
    ``b1_1m_exact.pub40``): 1,000,000 two-level exact filters are 7,814
    chunks padded to 8,192, and a level with 1M distinct tokens is past what
    a packed level can name, so the tiles are the legacy field-major layout
    with int32 tokens (``layout=None``) and the topic tokens ship as int32."""
    c = pm._match_fused.lower(
        spec((UP_CHUNKS, LEVELS + 3, pm.CHUNK), jnp.int32),
        spec((UP_CHUNKS, pm.CHUNK), jnp.int32), spec((b, LEVELS), jnp.int32),
        spec((b,), jnp.int16), spec((b,), jnp.bool_), spec((b, NC), jnp.uint16),
        budget=_budget(b), layout=None).compile()
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


def test_fused_grouped_and_split_compile(spec):
    """The deduplicated-candidate form and the NC-split form of the same
    batch: [U, NC] distinct rows + inverse; two NC tiers in one program."""
    b, u = 1024, 64
    ttok, tlen, td, _cids = _batch(spec, b)
    fids = spec((UP_CHUNKS, pm.CHUNK), jnp.int32)
    pm._match_fused_grouped.lower(
        _tiles(spec), fids, ttok, tlen, td, spec((u, NC), jnp.uint16),
        spec((b,), jnp.int32), budget=_budget(b), layout=LAYOUT).compile()
    parts = tuple(
        (spec((pb, LAYOUT.nlvl), jnp.int32), spec((pb,), jnp.int16),
         spec((pb,), jnp.bool_), spec((pb, tier), jnp.uint16))
        for pb, tier in ((256, 12), (1024, 16)))
    pm._match_fused_split.lower(
        _tiles(spec), fids, parts, (_budget(256), _budget(1024)),
        layout=LAYOUT).compile()


def test_global_compact_reference_compiles(spec):
    """The fused pipeline's first-use reference and fallback: words →
    global compact (``_match_global``)."""
    b = 1024
    pm._match_global.lower(_tiles(spec), *_batch(spec, b), budget=_budget(b),
                           layout=LAYOUT).compile()


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "legacy"])
def test_lax_words_producer_compiles_16k(spec, packed):
    words = jax.jit(pm.words_any_impl, static_argnames=("layout",))
    c = words.lower(_tiles(spec, packed), *_batch(spec, 16384, packed),
                    layout=LAYOUT if packed else None).compile()
    assert "tpu_custom_call" not in c.as_text()


def test_retained_scan_step_compiles(spec):
    """The retained scanner's one program (gather tier + full-stream tier)
    over a 60K-topic table: 468 chunks padded to 512, int16 legacy tiles."""
    from rmqtt_tpu.ops.retained_part import _retained_scan_combo

    def part(b, nc=None):
        p = (spec((b, LEVELS), jnp.int16), spec((b,), jnp.int16),
             spec((b,), jnp.int16), spec((b,), jnp.bool_),
             spec((b,), jnp.bool_))
        return p + ((spec((b, nc), jnp.uint16),) if nc else ())

    _retained_scan_combo.lower(
        spec((512, LEVELS + 3, pm.CHUNK), jnp.int16), (part(8, 64),),
        (part(4),), slab=512).compile()


def test_sharded_fused_step_compiles_on_four_chips(topo):
    """``ShardedPartitionedMatcher``'s fused step on a 2x2 mesh of described
    chips: table replicated, a 16K batch split four ways, and NO collective
    in the program — the match is local to each chip's topic slice."""
    from rmqtt_tpu.parallel.sharded import ShardedPartitionedMatcher

    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("dp", "fp"))
    m = ShardedPartitionedMatcher(pm.PartitionedTable(), mesh)
    b = 16384
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(("dp", "fp"), None))
    vec = NamedSharding(mesh, P(("dp", "fp")))

    def s(shape, dt, sh):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    c = m._fused_step(_budget(b // 4)).lower(
        s((UP_CHUNKS, LEVELS + 3, pm.CHUNK), jnp.int16, rep),
        s((UP_CHUNKS, pm.CHUNK), jnp.int32, rep),
        s((b, LEVELS), jnp.int16, rows), s((b,), jnp.int16, vec),
        s((b,), jnp.bool_, vec), s((b, NC), jnp.uint16, rows)).compile()
    text = c.as_text()
    for op in ("all-reduce", "all-gather", "all-to-all", "collective-permute"):
        assert op not in text, f"unexpected {op} in the dp-sharded step"
    # the replicated table is resident once per chip: 23 MB of tiles + 4 MB
    # of fid rows here, far inside 16 GB
    assert c.memory_analysis().argument_size_in_bytes < 1 << 30
