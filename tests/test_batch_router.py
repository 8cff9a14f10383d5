"""Batched recompile-free routing datapath: the route kernel on healthy
fleets of every size, device Memento remap, BatchRouter — bit-exactness vs
the scalar oracles and no-retrace guarantees across fleet events."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MementoWrapper, make
from repro.core.binomial import binomial_lookup32
from repro.core.binomial_jax import binomial_lookup_dyn, binomial_lookup_vec
from repro.core.bulk import FleetState
from repro.core.memento_jax import mask_words, memento_remap, pack_removed_mask
from repro.kernels.binomial_hash import binomial_route_fused_2d
from repro.serving.batch_router import BatchRouter
from repro.serving.router import SessionRouter

RNG = np.random.default_rng(11)
#: the healthy-fleet kernel tests' capacity: holds every n they route
CAP = 1024


# ---------------------------------------------------------------------------
# the route kernel on a healthy fleet: the plain lookup at a traced n
# ---------------------------------------------------------------------------


def _healthy_fleet(n):
    """A fleet of ``n`` slots, none removed, at capacity ``CAP``."""
    table = np.zeros((1, CAP), np.int32)
    table[0, :n] = np.arange(n)
    return FleetState(pack_removed_mask([], CAP), table,
                      np.array([n, n], np.uint32))


def _route_healthy(keys, n):
    """(16, 128) keys through ``route_2d``, one 8-row tile a grid step."""
    f = _healthy_fleet(n)
    return binomial_route_fused_2d(
        keys, f.packed, f.table, f.state, mask_words(CAP), CAP,
        block_rows=8, interpret=True,
    )


@pytest.mark.parametrize("k", [1, 2, 3, 5, 8])
@pytest.mark.parametrize("delta", [-1, 0, 1])
def test_dyn_kernel_pow2_boundaries(k, delta):
    """Bit-exact vs the scalar u32 oracle at n in {2^k-1, 2^k, 2^k+1}."""
    n = (1 << k) + delta
    if n < 2:
        pytest.skip("n < 2 is the degenerate single-bucket case")
    keys = jnp.asarray(RNG.integers(0, 2**32, size=(16, 128), dtype=np.uint32))
    out = np.asarray(_route_healthy(keys, n)).reshape(-1)
    scal = [binomial_lookup32(int(x), n) for x in np.asarray(keys).reshape(-1)]
    np.testing.assert_array_equal(out, scal)


@pytest.mark.parametrize("n", [1, 2, 7, 37, 128, 1000])
def test_dyn_kernel_matches_static(n):
    """The kernel at a traced n == the jnp lookup with n baked in."""
    keys = jnp.asarray(RNG.integers(0, 2**32, size=(16, 128), dtype=np.uint32))
    dyn = _route_healthy(keys, n)
    static = binomial_lookup_vec(keys, n)
    np.testing.assert_array_equal(np.asarray(dyn), np.asarray(static))


def test_dyn_kernel_no_retrace_across_resizes():
    """One executable serves every cluster size (the recompile-free core)."""
    keys = jnp.asarray(RNG.integers(0, 2**32, size=(16, 128), dtype=np.uint32))
    _route_healthy(keys, 3)
    before = binomial_route_fused_2d._cache_size()
    for n in [4, 7, 8, 9, 64, 1000, 2, 5]:  # crosses several pow2 boundaries
        _route_healthy(keys, n)
    assert binomial_route_fused_2d._cache_size() == before


# ---------------------------------------------------------------------------
# device-side Memento remap
# ---------------------------------------------------------------------------


def _scalar_oracle(n, removed):
    eng = MementoWrapper(lambda m: make("binomial32", m), n, chain_bits=32)
    for b in removed:
        eng.remove_bucket(b)
    return eng


def _device_route(keys_u32, eng, capacity=64):
    mask = np.zeros((capacity,), dtype=bool)
    mask[list(eng.removed)] = True
    buckets = binomial_lookup_dyn(keys_u32, np.uint32(eng.n_total))
    return np.asarray(
        memento_remap(keys_u32, buckets, mask, np.uint32(eng.n_total),
                      np.uint32(eng.first_alive()))
    )


@pytest.mark.parametrize("removed", [[], [0], [3], [1, 4], [0, 1, 2, 3, 4, 5]])
def test_remap_matches_scalar_wrapper(removed):
    n = 8
    eng = _scalar_oracle(n, removed)
    keys = RNG.integers(0, 2**32, size=(4096,), dtype=np.uint32)
    dev = _device_route(keys, eng)
    scal = [eng.get_bucket(int(k)) for k in keys]
    np.testing.assert_array_equal(dev, scal)
    assert not np.isin(dev, removed).any()


def test_remap_randomized_fail_recover_sequence():
    n = 16
    eng = _scalar_oracle(n, [])
    keys = RNG.integers(0, 2**32, size=(2048,), dtype=np.uint32)
    rng = np.random.default_rng(3)
    for _ in range(12):
        if eng.removed and rng.random() < 0.4:
            eng.restore_bucket(int(rng.choice(list(eng.removed))))
        elif eng.size > 2:
            alive = [b for b in range(eng.n_total) if b not in eng.removed]
            eng.remove_bucket(int(rng.choice(alive[:-1] or alive)))
        dev = _device_route(keys, eng)
        scal = [eng.get_bucket(int(k)) for k in keys]
        np.testing.assert_array_equal(dev, scal)


def test_remap_no_retrace_across_events():
    n = 8
    keys = RNG.integers(0, 2**32, size=(1024,), dtype=np.uint32)
    eng = _scalar_oracle(n, [2])
    _device_route(keys, eng)
    before = memento_remap._cache_size()
    for removed in [[2, 5], [5], [], [0, 1, 6]]:
        _device_route(keys, _scalar_oracle(n, removed))
    _device_route(keys, _scalar_oracle(12, [3]))  # resize, same capacity table
    assert memento_remap._cache_size() == before


# ---------------------------------------------------------------------------
# BatchRouter vs scalar SessionRouter
# ---------------------------------------------------------------------------


def _apply_events(router, events):
    for ev, arg in events:
        getattr(router, ev)(*(() if arg is None else (arg,)))


EVENTS = [
    ("fail", 2),
    ("scale_up", None),
    ("fail", 5),
    ("scale_down", None),
    ("recover", 2),
    ("scale_up", None),
    ("fail", 0),
    ("scale_up", None),
    ("recover", 0),
]


def test_batch_router_matches_scalar_session_router():
    """Key-for-key parity with SessionRouter(binomial32, table resolve)."""
    batch = BatchRouter(8)
    scalar = SessionRouter(8, engine="binomial32", chain_bits=32, resolve="table")
    sessions = [f"user-{i}" for i in range(500)]
    np.testing.assert_array_equal(
        batch.route_batch(sessions), [scalar.route(s) for s in sessions]
    )
    _apply_events(batch, EVENTS)
    _apply_events(scalar, EVENTS)
    np.testing.assert_array_equal(
        batch.route_batch(sessions), [scalar.route(s) for s in sessions]
    )
    # scalar path on the BatchRouter itself agrees with its own batch path
    assert [batch.route(s) for s in sessions[:50]] == list(batch.route_batch(sessions[:50]))


def test_batch_router_failure_reroutes_minimally():
    r = BatchRouter(8)
    sessions = [f"s{i}" for i in range(2000)]
    before = r.route_batch(sessions)
    r.fail(3)
    after = r.route_batch(sessions)
    moved = before != after
    assert (before[moved] == 3).all()  # only victims of the dead replica move
    assert (after != 3).all()
    r.recover(3)
    np.testing.assert_array_equal(r.route_batch(sessions), before)


def test_batch_router_non_default_omega_max_chain_parity():
    """omega reaches the scalar oracle too — scalar == batch holds."""
    r = BatchRouter(9, omega=2)
    r.fail(1)
    keys = RNG.integers(0, 2**64, size=(4096,), dtype=np.uint64)
    out = r.route_keys(keys)
    expect = [r.domain.locate(int(k)) for k in keys]
    np.testing.assert_array_equal(out, expect)


def test_batch_router_moved_sessions_metric():
    """route_batch keeps the moved_sessions observability metric alive."""
    r = BatchRouter(8)
    sessions = [f"m{i}" for i in range(1000)]
    before = r.route_batch(sessions)
    assert r.stats.moved_sessions == 0
    r.fail(4)
    after = r.route_batch(sessions)
    moved = int((before != after).sum())
    assert moved > 0
    assert r.stats.moved_sessions == moved


def test_batch_router_capacity_guard():
    r = BatchRouter(4, capacity=8)
    for _ in range(4):
        r.scale_up()
    with pytest.raises(ValueError, match="capacity"):
        r.scale_up()


@pytest.mark.slow
def test_batch_router_1m_keys_zero_retrace_acceptance():
    """Acceptance: 1M-key batches through the FUSED Pallas kernel with zero
    retraces across >= 8 scale/fail events, bit-exact with the scalar
    router."""
    router = BatchRouter(8, interpret=True)  # force the fused Pallas kernel (CPU)
    scalar = SessionRouter(8, engine="binomial32", chain_bits=32, resolve="table")
    keys = RNG.integers(0, 2**64, size=(1 << 20,), dtype=np.uint64)

    router.route_keys(keys)  # compile once
    fused_before = binomial_route_fused_2d._cache_size()

    sample = RNG.choice(len(keys), size=512, replace=False)
    assert len(EVENTS) >= 8
    for ev, arg in EVENTS:
        _apply_events(router, [(ev, arg)])
        _apply_events(scalar, [(ev, arg)])
        out = np.asarray(router.route_keys(keys))
        assert out.shape == keys.shape
        expect = [scalar.domain.locate(int(keys[j])) for j in sample]
        np.testing.assert_array_equal(out[sample], expect)

    assert binomial_route_fused_2d._cache_size() == fused_before


# ---------------------------------------------------------------------------
# MoE hash router: dynamic-n flavour matches the static one
# ---------------------------------------------------------------------------


def test_moe_hash_router_dynamic_matches_static():
    import dataclasses

    import jax
    from repro.configs import reduced_config
    from repro.models.layers import moe

    cfg = reduced_config("qwen3-moe-235b-a22b")
    mcfg = dataclasses.replace(cfg.moe, router="hash")
    token_ids = jnp.asarray(RNG.integers(0, 50000, size=(2, 16), dtype=np.int32))
    x = jnp.zeros((2, 16, cfg.d_model), jnp.float32)
    p = moe.init_moe(jax.random.PRNGKey(0), dataclasses.replace(cfg, moe=mcfg))
    ids_s, _, _ = moe.route(p, x, token_ids, 3, dataclasses.replace(cfg, moe=mcfg))
    mdyn = dataclasses.replace(mcfg, router_dynamic_n=True)
    ids_d, _, _ = moe.route(p, x, token_ids, 3, dataclasses.replace(cfg, moe=mdyn))
    np.testing.assert_array_equal(np.asarray(ids_s), np.asarray(ids_d))
