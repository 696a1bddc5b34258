"""Compile the served programs for a described TPU v5e chip — no chip needed.

The TPU compiler is installed here and compiles for a chip that is described
(``jax.experimental.topologies``) rather than attached. These checks compile
each Pallas kernel and the jitted search/maintenance steps at the shapes of
the chip smoke's deployment — TPC-H lineitem at SF 10 in the default 4-shard
layout of ``ShardedHippoIndex.create`` — and assert that each program's
arguments, outputs and temporaries fit one v5e chip's 15.75 GiB of HBM.
Nothing runs, so nothing here says anything about results or times.

The topology is described inside a fixture (never at import), so under
pytest-xdist only the worker given this file loads the TPU library.
"""
from functools import partial

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import grouping
from repro.core import index as hix
from repro.core.histogram import Histogram
from repro.core.partition import default_max_slots, default_pages_per_shard
from repro.core.predicate import interval_bitmaps_sharded
from repro.kernels.batch_filter.ops import batch_filter
from repro.kernels.bitmap_and.ops import bitmap_and_any
from repro.kernels.bucketize.ops import bucketize_values
from repro.kernels.compact_inspect.ops import compact_inspect
from repro.kernels.page_inspect.ops import page_inspect

pytestmark = pytest.mark.slow

HBM_BYTES = int(15.75 * 2**30)          # one v5e chip, as the runtime reports
ROWS_SF10 = 59_986_052                  # TPC-H lineitem cardinality at SF 10
PAGE_CARD, RESOLUTION, DENSITY, SHARDS, BATCH, TOP_K = 50, 400, 0.2, 4, 64, 32
WORDS = -(-RESOLUTION // 32)
PAGES = -(-ROWS_SF10 // PAGE_CARD)
PPS = default_pages_per_shard(PAGES, SHARDS)      # pages per shard slab
SLOTS = default_max_slots(PPS)                    # entry slots per shard


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _fits(lowered) -> int:
    m = lowered.compile().memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total <= HBM_BYTES, f"{total / 2**30:.2f} GiB > 15.75 GiB"
    return total


def _state(spec, shards: int | None = SHARDS) -> hix.HippoState:
    """A ``HippoState`` of SF 10 shard shapes; stacked when ``shards``."""
    lead = () if shards is None else (shards,)
    return hix.HippoState(
        bounds=spec(lead + (RESOLUTION + 1,), jnp.float32),
        bitmaps=spec(lead + (SLOTS, WORDS), jnp.uint32),
        starts=spec(lead + (SLOTS,), jnp.int32),
        ends=spec(lead + (SLOTS,), jnp.int32),
        sorted_order=spec(lead + (SLOTS,), jnp.int32),
        slot_live=spec(lead + (SLOTS,), jnp.bool_),
        num_entries=spec(lead, jnp.int32),
        num_slots=spec(lead, jnp.int32),
        summarized_until=spec(lead, jnp.int32))


def _batch(spec):
    """(shards, query bitmaps, keys, valid, los, his) of one engine batch."""
    return (_state(spec),
            spec((SHARDS, BATCH, WORDS), jnp.uint32),
            spec((SHARDS, PPS, PAGE_CARD), jnp.float32),
            spec((SHARDS, PPS, PAGE_CARD), jnp.bool_),
            spec((BATCH,), jnp.float32), spec((BATCH,), jnp.float32))


# -- the five Pallas kernels at one shard's widths --------------------------

KERNELS = {
    "bitmap_and": lambda s: bitmap_and_any.lower(
        s((SLOTS, WORDS), jnp.uint32), s((WORDS,), jnp.uint32)),
    "batch_filter": lambda s: batch_filter.lower(
        s((BATCH, WORDS), jnp.uint32), s((SLOTS, WORDS), jnp.uint32)),
    "bucketize": lambda s: bucketize_values.lower(
        s((PPS * PAGE_CARD,), jnp.float32), s((RESOLUTION + 1,), jnp.float32),
        resolution=RESOLUTION),
    "page_inspect": lambda s: page_inspect.lower(
        s((PPS, PAGE_CARD), jnp.float32), s((PPS, PAGE_CARD), jnp.bool_),
        s((PPS,), jnp.bool_), s((), jnp.float32), s((), jnp.float32)),
    "compact_inspect": lambda s: compact_inspect.lower(
        s((PPS, PAGE_CARD), jnp.float32), s((PPS, PAGE_CARD), jnp.bool_),
        s((BATCH, PPS), jnp.bool_), s((BATCH,), jnp.float32),
        s((BATCH,), jnp.float32)),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(spec, name):
    lowered = KERNELS[name](spec)
    assert "tpu_custom_call" in lowered.as_text()   # Mosaic, not interpreted
    _fits(lowered)


# -- the served search steps and the drift remap ----------------------------

@pytest.mark.parametrize("max_selected", [64, PPS],
                         ids=["bucket64", "full_cap"])
def test_compact_search_fits_at_sf10(spec, max_selected):
    """The engine's first bucket and its never-truncating cap (the dense
    fallback's width), both carrying top_k row ids."""
    _fits(hix.search_compact_many_sharded.lower(
        *_batch(spec), max_selected=max_selected, top_k=TOP_K))


def test_dense_search_fits_at_sf10(spec):
    _fits(hix.search_many_sharded.lower(*_batch(spec)))


def test_predicate_conversion_fits(spec):
    """The batch's (S, Q, W) query bitmaps, one per shard's bounds epoch."""
    _fits(interval_bitmaps_sharded.lower(
        spec((SHARDS, RESOLUTION + 1), jnp.float32),
        spec((BATCH,), jnp.float32), spec((BATCH,), jnp.float32),
        spec((BATCH,), jnp.bool_)))


def test_resummarize_shard_fits_at_sf10(spec):
    cfg = hix.HippoConfig(resolution=RESOLUTION, density=DENSITY,
                          page_card=PAGE_CARD, max_slots=SLOTS)
    _fits(jax.jit(partial(hix.resummarize_shard, cfg)).lower(
        _state(spec, None), spec((PPS, PAGE_CARD), jnp.float32),
        spec((PPS, PAGE_CARD), jnp.bool_),
        spec((RESOLUTION + 1,), jnp.float32)))


def test_build_scan_fits_at_sf10(spec):
    """Algorithm 2 per shard: bucket bits, then the grouping scan."""
    _fits(grouping.page_bucket_bits.lower(
        Histogram(spec((RESOLUTION + 1,), jnp.float32)),
        spec((PPS, PAGE_CARD), jnp.float32), spec((PPS, PAGE_CARD), jnp.bool_),
        resolution=RESOLUTION))
    _fits(grouping.group_pages.lower(
        spec((PPS, RESOLUTION), jnp.bool_), resolution=RESOLUTION,
        density_threshold=DENSITY))
