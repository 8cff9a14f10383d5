"""Fused single-dispatch routing kernel + device-resident BatchRouter state:
bit-exactness vs the scalar SessionRouter oracle (table resolution — the
serving-datapath semantics), the one-dispatch-per-batch guarantee, and zero
retraces / zero state re-uploads across fleet events."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.binomial_jax import mulhi32, umod32
from repro.core.memento_jax import (
    binomial_memento_route,
    mask_words,
    pack_removed_mask,
    pack_table,
)
from repro.kernels.binomial_hash import (
    binomial_route_fused_2d,
    binomial_route_pallas_fused,
)
from repro.kernels.ref import binomial_route_ref
from repro.serving.batch_router import BatchRouter
from repro.serving.router import SessionRouter

RNG = np.random.default_rng(7)


def _oracle(n, **kw):
    """The scalar oracle of the device datapath: u32 engine + table resolve."""
    return SessionRouter(n, engine="binomial32", chain_bits=32, resolve="table", **kw)


def _oracle_state(router: SessionRouter, capacity: int = 64):
    dom = router.domain
    packed = pack_removed_mask(dom.removed, capacity)
    table = pack_table(dom.replacement_table, capacity)
    state = np.array([dom.total_count, dom.alive_count], np.uint32)
    return packed, table, state


# ---------------------------------------------------------------------------
# divide-free building blocks (umod32 for the chain remap, mulhi32 for the
# table divert's Lemire range reduction)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 2, 3, 5, 16, 37, 1000, (1 << 16) + 1, (1 << 31) - 1])
def test_umod32_matches_native_mod(n):
    x = RNG.integers(0, 2**32, size=(2048,), dtype=np.uint32)
    out = np.asarray(umod32(jnp.asarray(x), np.uint32(n)))
    np.testing.assert_array_equal(out, x % np.uint32(n))


def test_mulhi32_matches_u64_reference():
    a = RNG.integers(0, 2**32, size=(4096,), dtype=np.uint32)
    b = RNG.integers(0, 2**32, size=(4096,), dtype=np.uint32)
    ref = ((a.astype(np.uint64) * b.astype(np.uint64)) >> 32).astype(np.uint32)
    np.testing.assert_array_equal(
        np.asarray(mulhi32(jnp.asarray(a), jnp.asarray(b))), ref
    )
    # edge operands: 0, 1, 2^31, 2^32-1
    e = np.array([0, 1, 1 << 31, (1 << 32) - 1], dtype=np.uint32)
    ee = np.stack(np.meshgrid(e, e)).reshape(2, -1)
    ref = ((ee[0].astype(np.uint64) * ee[1].astype(np.uint64)) >> 32).astype(np.uint32)
    np.testing.assert_array_equal(
        np.asarray(mulhi32(jnp.asarray(ee[0]), jnp.asarray(ee[1]))), ref
    )


# ---------------------------------------------------------------------------
# fused kernel vs the scalar SessionRouter oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 2, 3, 5])
@pytest.mark.parametrize("delta", [-1, 0, 1])
def test_fused_kernel_pow2_boundaries(k, delta):
    """Bit-exact vs SessionRouter at n in {2^k-1, 2^k, 2^k+1}, with failures."""
    n = (1 << k) + delta
    if n < 2:
        pytest.skip("n < 2 is the degenerate single-bucket case")
    oracle = _oracle(n)
    if n > 2:
        oracle.fail(n // 2)
    packed, table, state = _oracle_state(oracle)
    keys = RNG.integers(0, 2**32, size=(512,), dtype=np.uint32)
    out = np.asarray(
        binomial_route_pallas_fused(
            jnp.asarray(keys), jnp.asarray(packed), jnp.asarray(table),
            jnp.asarray(state),
            n_words=mask_words(64), n_slots=64, interpret=True, block_rows=2,
        )
    )
    expect = [oracle.domain.locate(int(x)) for x in keys]
    np.testing.assert_array_equal(out, expect)


def test_fused_kernel_randomized_fail_recover_stream():
    """The fused kernel tracks the oracle through a random event stream."""
    router = BatchRouter(16, interpret=True, block_rows=8)
    oracle = _oracle(16)
    keys = RNG.integers(0, 2**64, size=(2048,), dtype=np.uint64)
    rng = np.random.default_rng(5)
    for _ in range(15):
        removed = sorted(router.domain.removed)
        roll = rng.random()
        if removed and roll < 0.35:
            r = int(rng.choice(removed))
            router.recover(r), oracle.recover(r)
        elif roll < 0.55 and router.domain.total_count < router.capacity:
            router.scale_up(), oracle.scale_up()
        elif roll < 0.7 and router.alive > 2:
            router.scale_down(), oracle.scale_down()
        elif router.alive > 2:
            alive = [
                b for b in range(router.domain.total_count - 1)
                if b not in router.domain.removed
            ]
            r = int(rng.choice(alive))
            router.fail(r), oracle.fail(r)
        out = router.route_keys_np(keys)
        expect = [oracle.domain.locate(int(k)) for k in keys]
        np.testing.assert_array_equal(out, expect)


def test_fused_paths_agree_with_ref_and_two_pass():
    """pallas(interpret) == jnp jit == unjitted ref."""
    oracle = _oracle(12)
    for r in (1, 4, 9):
        oracle.fail(r)
    packed, table, state = _oracle_state(oracle)
    keys = RNG.integers(0, 2**32, size=(4096,), dtype=np.uint32)
    kj = jnp.asarray(keys)
    fused_pl = np.asarray(
        binomial_route_pallas_fused(
            kj, jnp.asarray(packed), jnp.asarray(table), jnp.asarray(state),
            n_words=mask_words(64), n_slots=64, interpret=True, block_rows=4,
        )
    )
    fused_jnp = np.asarray(
        binomial_memento_route(
            kj, jnp.asarray(packed), jnp.asarray(table), jnp.asarray(state),
            n_words=mask_words(64),
        )
    )
    ref = np.asarray(binomial_route_ref(kj, packed, table, state))
    np.testing.assert_array_equal(fused_pl, fused_jnp)
    np.testing.assert_array_equal(fused_pl, ref)


def test_fused_multiword_mask_and_table_cascade():
    """capacity > 32 exercises the multi-word mask cascade AND the deep
    (two-redirect) branch of the table gather cascade in the kernel."""
    cap = 256
    oracle = _oracle(100)
    for r in (0, 31, 32, 63, 64, 95, 97):
        oracle.fail(r)
    packed, table, state = _oracle_state(oracle, capacity=cap)
    assert mask_words(cap) == 8
    keys = RNG.integers(0, 2**32, size=(1024,), dtype=np.uint32)
    out = np.asarray(
        binomial_route_pallas_fused(
            jnp.asarray(keys), jnp.asarray(packed), jnp.asarray(table),
            jnp.asarray(state),
            n_words=mask_words(cap), n_slots=cap, interpret=True, block_rows=2,
        )
    )
    expect = [oracle.domain.locate(int(x)) for x in keys]
    np.testing.assert_array_equal(out, expect)


# ---------------------------------------------------------------------------
# the single-dispatch + device-resident-state guarantees
# ---------------------------------------------------------------------------


EVENTS = [
    ("fail", 2),
    ("scale_up", None),
    ("fail", 5),
    ("scale_down", None),
    ("recover", 2),
    ("scale_up", None),
]


def test_route_keys_is_exactly_one_dispatch_per_batch(monkeypatch):
    """The fused path issues ONE device dispatch per batch and never touches
    the jnp mirror or the plain lookup — asserted across scale/fail/recover
    events.

    The spec dispatcher resolves its engine bundle from ``BULK_ENGINES``
    per call, so swapping the entry intercepts every dispatch."""
    import dataclasses

    from repro.core import registry

    router = BatchRouter(8, interpret=True, block_rows=8)
    keys = RNG.integers(0, 2**64, size=(4096,), dtype=np.uint64)
    router.route_keys(keys)  # compile once

    calls = {"fused": 0}
    real = binomial_route_pallas_fused

    def counting(*a, **k):
        calls["fused"] += 1
        return real(*a, **k)

    def forbidden(*a, **k):  # pragma: no cover - the assertion IS the test
        raise AssertionError("a second entry point reached on the fused path")

    monkeypatch.setitem(
        registry.BULK_ENGINES,
        "binomial",
        dataclasses.replace(
            registry.BULK_ENGINES["binomial"],
            route_pallas=counting,
            route=forbidden,  # interpret mode must take the kernel, not jnp
            lookup_dyn=forbidden,
        ),
    )

    before = binomial_route_fused_2d._cache_size()
    n_batches = 0
    for ev, arg in EVENTS:
        getattr(router, ev)(*(() if arg is None else (arg,)))
        router.route_keys(keys)
        n_batches += 1
    assert calls["fused"] == n_batches  # exactly one dispatch per batch
    assert binomial_route_fused_2d._cache_size() == before  # zero retraces


class _RecordedSpan:
    """Stands in for a span of ``repro.kernels.fused``: keeps its name and
    tags in ``opened``, in the order the spans open."""

    def __init__(self, name, opened):
        self.name, self.tags = name, {}
        opened.append(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def tag(self, **tags):
        self.tags.update(tags)


# block_rows=8: the tile is 1,024 keys, so 4,096 keys are aligned and 4,000
# are ragged
ALIGNED_1D, ALIGNED_2D, RAGGED = (4096,), (32, 128), (4000,)


@pytest.mark.parametrize(
    "shape,spans",
    [
        (ALIGNED_1D, ["route.launch"]),
        (ALIGNED_2D, ["route.launch"]),
        (RAGGED, ["route.layout", "route.launch", "route.layout"]),
    ],
)
def test_route_pallas_spans_follow_alignment(monkeypatch, shape, spans):
    """Aligned keys go to ``route_2d`` as given, one ``route.launch`` and no
    layout span; ragged keys open ``route.layout`` around the pad and the
    slice."""
    from repro.kernels import fused

    router = BatchRouter(8, interpret=True, block_rows=8)
    router.fail(2)
    keys = jnp.asarray(RNG.integers(0, 2**32, size=shape, dtype=np.uint32))
    opened: list[_RecordedSpan] = []
    monkeypatch.setattr(fused, "span", lambda name: _RecordedSpan(name, opened))
    out = router.route_keys(keys)
    assert out.shape == keys.shape and out.dtype == jnp.int32
    assert [s.name for s in opened] == spans
    (launch,) = [s for s in opened if s.name == "route.launch"]
    assert launch.tags == {"rows": -(-keys.size // 1024) * 8, "block_rows": 8}


@pytest.mark.parametrize("shape", [ALIGNED_1D, ALIGNED_2D, RAGGED])
def test_route_pallas_tracks_oracle_without_retrace(shape):
    """Aligned (1-D and (rows, 128)) and ragged batches stay bit-exact with
    the scalar table-mode router across the event stream, and ``route_2d``
    compiles once per shape, never per event."""
    router = BatchRouter(8, interpret=True, block_rows=8)
    oracle = _oracle(8)
    keys_np = RNG.integers(0, 2**32, size=shape, dtype=np.uint32)
    keys = jnp.asarray(keys_np)
    router.route_keys(keys)  # compile once
    before = binomial_route_fused_2d._cache_size()
    for ev, arg in EVENTS:
        args = () if arg is None else (arg,)
        getattr(router, ev)(*args), getattr(oracle, ev)(*args)
        out = np.asarray(router.route_keys(keys))
        assert out.shape == shape
        expect = [oracle.domain.locate(int(x)) for x in keys_np.reshape(-1)]
        np.testing.assert_array_equal(out.reshape(-1), expect)
    assert binomial_route_fused_2d._cache_size() == before  # zero retraces


def test_route_2d_takes_flat_keys_in_its_own_program():
    """A 1-D aligned batch lowers to the one program the chip names
    ``jit_route_2d``; a key count of partial tiles is refused there."""
    router = BatchRouter(8, interpret=True, block_rows=8)
    fleet = router._fleet_dev
    args = (fleet.packed, fleet.table, fleet.state, router.spec.n_words,
            router.spec.n_slots)
    keys = jnp.zeros(ALIGNED_1D, jnp.uint32)
    text = binomial_route_fused_2d.lower(
        keys, *args, block_rows=8, interpret=True).as_text()
    assert text.startswith("module @jit_route_2d ")
    with pytest.raises(ValueError, match=r"key count \(4000\) must be a multiple"):
        binomial_route_fused_2d(keys[:4000], *args, block_rows=8, interpret=True)
    with pytest.raises(ValueError, match=r"rows \(31\) must be a multiple"):
        binomial_route_fused_2d(
            keys.reshape(-1, 128)[:31], *args, block_rows=8, interpret=True)


def test_route_keys_zero_per_batch_state_uploads():
    """Device fleet state is pinned at event time; route_keys re-uses the
    same buffers — no per-batch host->device rebuild/upload."""
    router = BatchRouter(8, interpret=True, block_rows=8)
    keys = RNG.integers(0, 2**64, size=(2048,), dtype=np.uint64)
    packed, table, state = router._packed_dev, router._table_dev, router._state_dev
    for _ in range(3):
        router.route_keys(keys)
        assert router._packed_dev is packed
        assert router._table_dev is table
        assert router._state_dev is state
    router.fail(3)  # event: state may be re-pinned...
    packed, table, state = router._packed_dev, router._table_dev, router._state_dev
    assert packed is not None and table is not None and state is not None
    for _ in range(3):  # ...but batches still don't touch it
        router.route_keys(keys)
        assert router._packed_dev is packed
        assert router._table_dev is table
        assert router._state_dev is state


def test_route_keys_jax_in_jax_out():
    """jax.Array in -> jax.Array out, no host round-trip forced; the numpy
    wrapper and the device path agree."""
    import jax

    router = BatchRouter(8)
    router.fail(2)
    keys_np = RNG.integers(0, 2**32, size=(1024,), dtype=np.uint32)
    keys_dev = jnp.asarray(keys_np)
    out_dev = router.route_keys(keys_dev)
    assert isinstance(out_dev, jax.Array)
    out_np = router.route_keys_np(keys_np)
    assert isinstance(out_np, np.ndarray)
    np.testing.assert_array_equal(np.asarray(out_dev), out_np)


def test_fail_last_slot_is_lifo_removal_not_stale_bit():
    """Failing the last slot shrinks the slot space in the control plane;
    the device mask/table must not keep stale entries that poison a later
    scale-up."""
    router = BatchRouter(8, interpret=True, block_rows=8)
    oracle = _oracle(8)
    keys = RNG.integers(0, 2**64, size=(1024,), dtype=np.uint64)
    for ev in (("fail", 7), ("scale_up", None), ("fail", 3), ("fail", 7)):
        getattr(router, ev[0])(*(() if ev[1] is None else (ev[1],)))
        getattr(oracle, ev[0])(*(() if ev[1] is None else (ev[1],)))
        np.testing.assert_array_equal(
            router.route_keys_np(keys), [oracle.domain.locate(int(k)) for k in keys]
        )


def test_coerce_keys_skips_redundant_conversions():
    router = BatchRouter(4)
    ku32 = np.ascontiguousarray(RNG.integers(0, 2**32, size=64, dtype=np.uint32))
    assert router._coerce_keys(ku32) is ku32  # no u64->u32 double conversion
    kdev = jnp.asarray(ku32)
    assert router._coerce_keys(kdev) is kdev  # no host round-trip at all
    wide = RNG.integers(0, 2**64, size=64, dtype=np.uint64)
    np.testing.assert_array_equal(router._coerce_keys(wide), wide.astype(np.uint32))


# ---------------------------------------------------------------------------
# constructor validation (clear errors at construction, not deep in a trace)
# ---------------------------------------------------------------------------


def test_batch_router_rejects_bad_block_rows():
    with pytest.raises(ValueError, match="multiple of 8"):
        BatchRouter(8, block_rows=12)
    with pytest.raises(ValueError, match="multiple of 8"):
        BatchRouter(8, block_rows=0)
    with pytest.raises(ValueError, match="multiple of 8"):
        BatchRouter(8, block_rows=-8)
    BatchRouter(8, block_rows=8)  # the smallest legal tiling


def test_batch_router_rejects_non_pow2_capacity():
    with pytest.raises(ValueError, match="power of two"):
        BatchRouter(8, capacity=48)
    with pytest.raises(ValueError, match="power of two"):
        BatchRouter(8, capacity=0)
    BatchRouter(8, capacity=16)


def test_batch_router_rejects_bad_n_replicas():
    with pytest.raises(ValueError, match="n_replicas"):
        BatchRouter(0)
    with pytest.raises(ValueError, match="exceeds capacity"):
        BatchRouter(100, capacity=64)


def test_batch_router_rejects_meaningless_mesh_combinations():
    """donate_keys is specific to the mesh-sharded datapath — silently
    ignoring it would invalidate benchmark comparisons."""
    with pytest.raises(ValueError, match="donate_keys"):
        BatchRouter(8, donate_keys=True)
