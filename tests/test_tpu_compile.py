"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode runs the kernels on the CPU but cannot see what the chip's
compiler refuses (unsupported casts, unsigned ops, tiles that overflow the
scoped VMEM).  These tests compile every ``pallas_call`` the routing path
dispatches — route and u64 ingest of both bulk engines — and the
placement pass with its ``table_read`` kernel, for one chip of a ``v5e:2x2`` topology that is described, not
attached, at 2^20 keys and capacity 1024, and check the kernel is in the
compiled program.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
import every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.memento_jax import mask_words
from repro.core.registry import BULK_ENGINES
from repro.kernels.autotune import CANDIDATES
from repro.kernels.binomial_hash import binomial_route_fused_2d
from repro.kernels.fused import LANES
from repro.kernels.jump_hash import jump_route_fused_2d

CAPACITY = 1024
KEYS = 1 << 20

#: the jitted route program each engine's eager ``route_pallas`` enqueues
ROUTE_2D = {"binomial": binomial_route_fused_2d, "jump": jump_route_fused_2d}

#: a layout copy of the keys or of the result: ``u32[rows,128]`` or ``s32[N]``
LAYOUT_COPY = re.compile(r"= (u32\[\d+,128\]|s32\[\d+\])\S* copy\(")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()
    if log_dir == "disabled":
        os.environ.pop("TPU_LOG_DIR", None)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fleet(sharding):
    return (
        _shape(sharding, (1, mask_words(CAPACITY)), jnp.uint32),
        _shape(sharding, (1, CAPACITY), jnp.int32),
        _shape(sharding, (2,), jnp.uint32),
    )


def _compiled_text(sharding, engine: str, kernel: str, block_rows: int) -> str:
    eng = BULK_ENGINES[engine]
    keys = _shape(sharding, (KEYS,), jnp.uint32)
    fleet = _fleet(sharding)
    extents = (mask_words(CAPACITY), CAPACITY)
    if kernel == "route":
        fn = lambda k, *f: eng.route_pallas(  # noqa: E731
            k, *f, *extents, block_rows=block_rows)
        args = (keys, *fleet)
    else:
        fn = lambda lo, hi, *f: eng.ingest_pallas(  # noqa: E731
            lo, hi, *f, *extents, block_rows=block_rows)
        args = (keys, keys, *fleet)
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kernel", ["route", "ingest"])
@pytest.mark.parametrize("engine", sorted(BULK_ENGINES))
def test_kernel_compiles_for_v5e(one_chip, engine, kernel):
    """Smallest autotuner tile: the kernel body itself must lower."""
    assert "tpu_custom_call" in _compiled_text(
        one_chip, engine, kernel, min(CANDIDATES)
    )


@pytest.mark.parametrize("engine", sorted(ROUTE_2D))
def test_aligned_route_is_the_kernel_alone_on_v5e(one_chip, engine):
    """An aligned eager ``route_pallas`` enqueues ``route_2d`` on the flat
    keys as given; its layout in and out compile to bitcasts, so the program
    is the one kernel and no copy of the keys or the result."""
    text = ROUTE_2D[engine].lower(
        _shape(one_chip, (KEYS,), jnp.uint32), *_fleet(one_chip),
        mask_words(CAPACITY), CAPACITY, block_rows=min(CANDIDATES),
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert LAYOUT_COPY.search(text) is None, LAYOUT_COPY.search(text).group(0)


def test_largest_autotuned_tile_fits_v5e(one_chip):
    """The largest tile the autotuner may pick holds the most VMEM."""
    assert max(CANDIDATES) * LANES <= KEYS
    assert "tpu_custom_call" in _compiled_text(
        one_chip, "binomial", "route", max(CANDIDATES)
    )


@pytest.fixture(scope="module")
def placement_programs(one_chip):
    """The placement pass (``StorePlacement(r=3)``, and with ``zones=3``)
    compiled for the chip, its tables read by the XLA gather and by the
    ``table_read`` kernel: ``{(zones, read path): compiled}``."""
    from repro.core.bulk import PlacementSpec, RouterSpec, ZoneState
    from repro.core.memento_jax import table_width
    from repro.kernels.table_read import TableRead
    from repro.placement.store import _route_replicas_jit

    keys = _shape(one_chip, (KEYS,), jnp.uint32)
    static = dict(r=3, omega=16, n_words=mask_words(CAPACITY), max_resalt=3,
                  route=BULK_ENGINES["binomial"].route)
    spec = PlacementSpec(router=RouterSpec(capacity=CAPACITY), r=3, zones=3)
    zone = (_shape(one_chip, (1, table_width(3 * spec.zone_width)), jnp.int32),
            _shape(one_chip, (2, 3), jnp.uint32))
    programs = {}
    for read in (TableRead(), TableRead(vmem=True)):
        path = read.path(CAPACITY)
        programs[1, path] = _route_replicas_jit.lower(
            keys, *_fleet(one_chip), read=read, **static).compile()
        programs[3, path] = _route_replicas_jit.lower(
            keys, *_fleet(one_chip), ZoneState(*zone),
            _shape(one_chip, (2, 2), jnp.uint32), zones=3,
            zone_width=spec.zone_width, read=read, **static,
        ).compile()
    return programs


def test_zoned_placement_pass_compiles_for_v5e(placement_programs):
    """The placement pass with three zones (``StorePlacement(r=3,
    zones=3)``), either way its tables are read: while-free, and its
    temporaries no larger than the zone-free pass's but for the zone
    reads' lanes."""
    for path in ("gather", "vmem"):
        zoned, plain = placement_programs[3, path], placement_programs[1, path]
        assert " while(" not in zoned.as_text()
        extra = (zoned.memory_analysis().temp_size_in_bytes
                 - plain.memory_analysis().temp_size_in_bytes)
        assert extra <= 2 * 4 * KEYS  # two u32 lanes a key at most


#: an XLA gather and the shape it gathers
GATHER = re.compile(r"= (\S+) gather\(")


@pytest.mark.parametrize("zones", (1, 3))
def test_placement_pass_reads_its_tables_in_the_kernel_on_v5e(
        placement_programs, zones):
    """With the ``table_read`` kernel the pass keeps no XLA gather, where
    the XLA reads gather the route's divert over all 3 x 2^20 family
    lanes, six probes of the slots table and, zoned, two reads of the zone
    table too; and it adds no copy."""
    by_xla = placement_programs[zones, "gather"].as_text()
    by_kernel = placement_programs[zones, "vmem"].as_text()
    assert len(GATHER.findall(by_xla)) == 1 + 6 + (2 if zones > 1 else 0)
    assert GATHER.findall(by_kernel) == []
    assert by_kernel.count('custom_call_target="tpu_custom_call"') >= 1
    assert " while(" not in by_kernel
    assert by_kernel.count(" copy(") <= by_xla.count(" copy(")
