"""Fused route kernel on a healthy fleet vs the plain-lookup oracles:
shape/dtype/n/omega/block sweeps for every bulk engine.

With no slot removed the route skips its divert, so ``ops.route_bulk`` is
the engine's plain lookup: binomial is checked against the pure-jnp
``binomial_bulk_lookup_ref``, jump against its scalar ``jump32`` oracle,
and both against their scalar oracle at non-default ω.  The kernel runs
in interpret mode on CPU; results are integers so equality is exact."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bits import next_pow2
from repro.core.binomial import binomial_lookup32
from repro.core.bulk import FleetState, RouterSpec
from repro.core.jump_jax import jump_lookup32
from repro.core.memento_jax import pack_removed_mask, table_width
from repro.kernels import ops
from repro.kernels.ref import binomial_bulk_lookup_ref

RNG = np.random.default_rng(7)
ENGINES = ["binomial", "jump"]
SCALAR = {"binomial": binomial_lookup32, "jump": jump_lookup32}


def _per_engine(values):
    """``(engine, value)`` cases of a sweep: binomial's keep the value's own
    id, jump's prefix it with ``jump-``."""
    ids = [getattr(v, "__name__", str(v)) for v in values]
    return [pytest.param("binomial", v, id=i) for v, i in zip(values, ids)] + [
        pytest.param("jump", v, id=f"jump-{i}") for v, i in zip(values, ids)]


def _route(engine, keys, n, *, omega=16, block_rows=8, interpret=True):
    """The fused route over a fleet of ``n`` slots with none removed, at
    the smallest capacity that holds them (the divert's cascades, compiled
    though skipped, grow with it)."""
    capacity = max(64, next_pow2(n))
    table = np.zeros((1, table_width(capacity)), np.int32)
    table[0, :n] = np.arange(n)
    fleet = FleetState(pack_removed_mask([], capacity), table,
                       np.array([n, n], np.uint32))
    spec = RouterSpec(engine=engine, capacity=capacity, omega=omega,
                      interpret=interpret, block_rows=block_rows)
    return ops.route_bulk(keys, fleet, spec)


def _scalar(engine, keys, n, omega=16):
    flat = np.asarray(keys).astype(np.uint32).reshape(-1)
    out = [SCALAR[engine](int(k), n, omega) for k in flat]
    return np.asarray(out, np.int32).reshape(np.shape(keys))


def _ref(engine, keys, n, omega=16):
    if engine == "binomial":
        return np.asarray(binomial_bulk_lookup_ref(keys, n, omega=omega))
    return _scalar(engine, keys, n, omega)


@pytest.mark.parametrize("shape", [(128,), (1, 128), (256, 128), (8, 8), (3, 5, 7), (1000,), (4096,)])
@pytest.mark.parametrize("engine, n", _per_engine([2, 11, 16, 1000]))
def test_kernel_shapes(engine, shape, n):
    """One 512-row tile holds every shape: each is padded to it, routed and
    cut back to its own shape, by one compiled program a capacity."""
    keys = jnp.asarray(RNG.integers(0, 2**32, size=shape, dtype=np.uint32))
    out = _route(engine, keys, n, block_rows=512)
    assert out.shape == shape and out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), _ref(engine, keys, n))


@pytest.mark.parametrize(
    "engine, dtype", _per_engine([np.uint32, np.int32, np.uint16, np.int8, np.uint8]))
def test_kernel_dtypes(engine, dtype):
    info = np.iinfo(dtype)
    keys = jnp.asarray(RNG.integers(info.min, info.max, size=(512,), dtype=dtype))
    out = _route(engine, keys, 37, block_rows=4)
    np.testing.assert_array_equal(np.asarray(out), _ref(engine, keys, 37))


@pytest.mark.parametrize("engine, omega", _per_engine([1, 2, 8, 16, 32]))
def test_kernel_omega(engine, omega):
    keys = jnp.asarray(RNG.integers(0, 2**32, size=(2048,), dtype=np.uint32))
    out = np.asarray(_route(engine, keys, 300, omega=omega))
    np.testing.assert_array_equal(out, _ref(engine, keys, 300, omega=omega))
    # and against the scalar oracle
    np.testing.assert_array_equal(
        out[:64], _scalar(engine, keys[:64], 300, omega=omega))


@pytest.mark.parametrize("engine, block_rows", _per_engine([1, 2, 8, 64]))
def test_kernel_block_tiling(engine, block_rows):
    keys = jnp.asarray(RNG.integers(0, 2**32, size=(64, 128), dtype=np.uint32))
    out = _route(engine, keys, 77, block_rows=block_rows)
    np.testing.assert_array_equal(np.asarray(out), _ref(engine, keys, 77))


@pytest.mark.parametrize("engine, n", _per_engine([1, 2, 3]))
def test_kernel_degenerate_n(engine, n):
    keys = jnp.asarray(RNG.integers(0, 2**32, size=(256,), dtype=np.uint32))
    out = _route(engine, keys, n, block_rows=2)
    assert int(jnp.max(out)) < n and int(jnp.min(out)) >= 0


def test_ops_dispatcher_cpu_path():
    keys = jnp.asarray(RNG.integers(0, 2**32, size=(333,), dtype=np.uint32))
    for engine in ENGINES:
        auto = _route(engine, keys, 19, block_rows=None, interpret=False)
        np.testing.assert_array_equal(np.asarray(auto), _ref(engine, keys, 19))


def test_kernel_buckets_uniform():
    keys = jnp.asarray(RNG.integers(0, 2**32, size=(1 << 16,), dtype=np.uint32))
    for engine in ENGINES:
        out = np.asarray(_route(engine, keys, 11, block_rows=512))
        counts = np.bincount(out, minlength=11)
        rel = counts.std() / counts.mean()
        assert rel < 0.05, engine
