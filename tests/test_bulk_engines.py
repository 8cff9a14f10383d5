"""The engine-agnostic bulk routing API (DESIGN.md §10): the jump device
engine's bit-exactness chain (scalar oracle == jnp mirror == Pallas
kernel == BatchRouter), RouterSpec construction semantics, the deprecation
shims' bit-identical forwarding, and the curated ``repro`` public surface."""
import dataclasses
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bits
from repro.core.bulk import FleetState, RouterSpec
from repro.core.jump_jax import (
    _F_TOP,
    JumpHash32,
    jump_lookup32,
    jump_lookup_dyn,
    jump_lookup_vec,
    jump_memento_route,
    rn_top_quotient,
)
from repro.core.memento_jax import mask_words, pack_removed_mask, pack_table
from repro.kernels import ops
from repro.kernels.jump_hash import (
    jump_bulk_lookup_pallas_dyn,
    jump_route_pallas_fused,
)
from repro.serving.batch_router import BatchRouter
from repro.serving.router import SessionRouter, hash_session_ids

RNG = np.random.default_rng(31)


def _jump_oracle(n, **kw):
    """The scalar oracle of the jump device datapath."""
    return SessionRouter(n, engine="jump32", chain_bits=32, resolve="table", **kw)


def _oracle_state(router: SessionRouter, capacity: int = 64):
    dom = router.domain
    packed = pack_removed_mask(dom.removed, capacity)
    table = pack_table(dom.replacement_table, capacity)
    state = np.array([dom.total_count, dom.alive_count], np.uint32)
    return packed, table, state


# ---------------------------------------------------------------------------
# jump lookup: scalar == jnp == Pallas(interpret) incl. pow2 boundaries
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 2, 3, 5, 8])
@pytest.mark.parametrize("delta", [-1, 0, 1])
def test_jump_lookup_pow2_boundaries(k, delta):
    n = (1 << k) + delta
    if n < 2:
        pytest.skip("n < 2 is the degenerate single-bucket case")
    keys = RNG.integers(0, 2**32, size=(512,), dtype=np.uint32)
    dyn = np.asarray(jump_lookup_dyn(jnp.asarray(keys), np.uint32(n)))
    vec = np.asarray(jump_lookup_vec(jnp.asarray(keys), n))
    pal = np.asarray(
        jump_bulk_lookup_pallas_dyn(
            jnp.asarray(keys), np.uint32(n), interpret=True, block_rows=2
        )
    )
    scal = [jump_lookup32(int(x), n) for x in keys]
    np.testing.assert_array_equal(dyn, scal)
    np.testing.assert_array_equal(vec, scal)
    np.testing.assert_array_equal(pal, scal)


def test_jump_lookup_respects_omega_bound():
    """Non-default ω changes the (bounded) chain identically on both sides."""
    keys = RNG.integers(0, 2**32, size=(1024,), dtype=np.uint32)
    for omega in (1, 2, 4):
        out = np.asarray(jump_lookup_dyn(jnp.asarray(keys), np.uint32(1000), omega=omega))
        scal = [jump_lookup32(int(x), 1000, omega) for x in keys]
        np.testing.assert_array_equal(out, scal)
        assert (out >= 0).all() and (out < 1000).all()


@pytest.mark.parametrize("ulps", [-2, -1, 0, 1, 2])
def test_rn_top_quotient_corrects_an_inexact_divide(ulps):
    """The device rounds 2^31 / fr itself: an estimate a few ulps off (a
    TPU v5e's divide misses by one for ~1/4 of the divisors) still yields
    numpy's correctly rounded quotient, for every divisor the jump step can
    form (integer-valued f32 in [1, 2^31])."""
    edges = np.array([1, 2, 3, 2**23 - 1, 2**23, 2**23 + 1, 2**24 - 1,
                      2**24, 2**30 + 2**7, 2**31], dtype=np.float32)
    small = RNG.integers(1, 1 << 24, size=1 << 15).astype(np.float32)
    big = RNG.integers(0x4B800000, 0x4F000001, size=1 << 15,
                       dtype=np.int64).astype(np.uint32).view(np.float32)
    fr = np.concatenate([edges, small, big])
    exact = _F_TOP / fr
    estimate = (exact.view(np.uint32).astype(np.int64) + ulps).astype(
        np.uint32).view(np.float32)
    got = np.asarray(rn_top_quotient(jnp.asarray(fr), jnp.asarray(estimate)))
    np.testing.assert_array_equal(got.view(np.uint32), exact.view(np.uint32))


def test_jump_engine_scalar_facade():
    eng = JumpHash32(5, omega=8)
    assert eng.size == 5
    assert eng.get_bucket(123) == jump_lookup32(123, 5, 8)
    assert eng.add_bucket() == 5 and eng.remove_bucket() == 5
    with pytest.raises(ValueError, match="last bucket"):
        JumpHash32(1).remove_bucket()


# ---------------------------------------------------------------------------
# fused jump route: jnp mirror == Pallas kernel == scalar table oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("removed", [[], [0], [3], [1, 4, 7], list(range(6))])
def test_jump_fused_route_matches_oracle(removed):
    oracle = _jump_oracle(12)
    for r in removed:
        oracle.fail(r)
    packed, table, state = _oracle_state(oracle)
    keys = RNG.integers(0, 2**32, size=(2048,), dtype=np.uint32)
    kw = dict(omega=16, n_words=mask_words(64))
    jnp_out = np.asarray(
        jump_memento_route(
            jnp.asarray(keys), jnp.asarray(packed), jnp.asarray(table),
            jnp.asarray(state), **kw,
        )
    )
    pal_out = np.asarray(
        jump_route_pallas_fused(
            jnp.asarray(keys), jnp.asarray(packed), jnp.asarray(table),
            jnp.asarray(state), mask_words(64), 64, interpret=True,
            block_rows=4,
        )
    )
    expect = [oracle.domain.locate(int(k)) for k in keys]
    np.testing.assert_array_equal(jnp_out, expect)
    np.testing.assert_array_equal(pal_out, expect)
    assert not np.isin(jnp_out, removed).any()


@pytest.mark.parametrize("interpret", [False, True])
def test_jump_batch_router_event_stream_parity(interpret):
    """BatchRouter(engine='jump') == the jump32 scalar oracle through a
    randomized fleet-event stream (both dispatch flavours)."""
    kw = dict(interpret=True, block_rows=8) if interpret else {}
    router = BatchRouter(10, engine="jump", **kw)
    oracle = _jump_oracle(10)
    keys = RNG.integers(0, 2**64, size=(4096,), dtype=np.uint64)
    rng = np.random.default_rng(5)
    sample = rng.choice(len(keys), size=256, replace=False)
    for _ in range(10):
        removed = sorted(router.domain.removed)
        alive = [
            b for b in range(router.domain.total_count) if b not in removed
        ]
        roll = rng.random()
        if removed and roll < 0.35:
            ev, arg = "recover", int(rng.choice(removed))
        elif roll < 0.6 and len(alive) > 2:
            ev, arg = "fail", int(rng.choice(alive[:-1]))
        elif roll < 0.8 and router.domain.total_count < router.capacity:
            ev, arg = "scale_up", None
        elif router.scalar.alive > 2:
            ev, arg = "scale_down", None
        else:
            ev, arg = "scale_up", None
        for r in (router, oracle):
            getattr(r, ev)(*(() if arg is None else (arg,)))
        out = router.route_keys_np(keys)
        expect = [oracle.domain.locate(int(keys[j])) for j in sample]
        np.testing.assert_array_equal(out[sample], expect)


def test_jump_route_ids_matches_prehash():
    router = BatchRouter(16, engine="jump")
    router.fail(3)
    ids = RNG.integers(0, 2**64, size=(4096,), dtype=np.uint64)
    fused = np.asarray(router.route_ids(ids))
    prehash = router.route_keys_np(hash_session_ids(ids))
    np.testing.assert_array_equal(fused, prehash)


def test_jump_batch_router_pow2_fleet_boundaries():
    """Parity at fleet sizes crossing pow2 boundaries (the E/M edge)."""
    for n in (2, 3, 4, 7, 8, 9, 31, 32, 33):
        router = BatchRouter(n, capacity=128, engine="jump")
        oracle = _jump_oracle(n)
        keys = RNG.integers(0, 2**64, size=(1024,), dtype=np.uint64)
        np.testing.assert_array_equal(
            router.route_keys_np(keys),
            [oracle.domain.locate(int(k)) for k in keys],
        )


# ---------------------------------------------------------------------------
# RouterSpec semantics
# ---------------------------------------------------------------------------


def test_router_spec_equals_kwargs_construction():
    spec = RouterSpec(engine="jump", capacity=128, omega=8)
    a = BatchRouter(6, spec)
    b = BatchRouter(6, engine="jump", capacity=128, omega=8)
    assert a.spec == b.spec
    keys = RNG.integers(0, 2**64, size=(1024,), dtype=np.uint64)
    np.testing.assert_array_equal(a.route_keys_np(keys), b.route_keys_np(keys))


def test_router_spec_conflicts_and_validation():
    with pytest.raises(ValueError, match="not both"):
        BatchRouter(4, RouterSpec(), engine="jump")
    with pytest.raises(KeyError, match="unknown bulk engine"):
        BatchRouter(4, engine="binomial64k")
    with pytest.raises(ValueError, match="power of two"):
        RouterSpec(capacity=48)
    with pytest.raises(ValueError, match="omega"):
        RouterSpec(omega=0)
    with pytest.raises(ValueError, match="block_rows"):
        RouterSpec(block_rows=0)
    # frozen: specs are hashable config values, not mutable bags
    with pytest.raises(dataclasses.FrozenInstanceError):
        RouterSpec().capacity = 128
    assert RouterSpec(capacity=64).n_words == 2
    assert RouterSpec(capacity=64).n_slots == 64


# ---------------------------------------------------------------------------
# deprecation shims: bit-identical forwarding, warn once
# ---------------------------------------------------------------------------


def _shim_operands():
    oracle = SessionRouter(12, engine="binomial32", chain_bits=32, resolve="table")
    for r in (2, 7):
        oracle.fail(r)
    packed, table, state = _oracle_state(oracle)
    return (
        jnp.asarray(packed), jnp.asarray(table), jnp.asarray(state),
    )


def test_binomial_route_bulk_shim_is_bit_identical():
    packed, table, state = _shim_operands()
    keys = jnp.asarray(RNG.integers(0, 2**32, size=(2048,), dtype=np.uint32))
    ops._warned.clear()
    with pytest.warns(DeprecationWarning, match="binomial_route_bulk"):
        old = ops.binomial_route_bulk(
            keys, packed, table, state,
            n_words=mask_words(64), n_slots=64, use_pallas=False,
        )
    new = ops.route_bulk(
        keys,
        FleetState(packed, table, state),
        RouterSpec(engine="binomial", capacity=64, use_pallas=False),
    )
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))
    # warn ONCE: the second legacy call passes silently
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        ops.binomial_route_bulk(
            keys, packed, table, state,
            n_words=mask_words(64), n_slots=64, use_pallas=False,
        )


def test_binomial_route_ingest_bulk_shim_is_bit_identical():
    packed, table, state = _shim_operands()
    ids = RNG.integers(0, 2**64, size=(2048,), dtype=np.uint64)
    lo, hi = bits.np_split64(ids)
    ops._warned.clear()
    with pytest.warns(DeprecationWarning, match="binomial_route_ingest_bulk"):
        old = ops.binomial_route_ingest_bulk(
            jnp.asarray(lo), jnp.asarray(hi), packed, table, state,
            n_words=mask_words(64), n_slots=64, use_pallas=False,
        )
    new = ops.route_ingest_bulk(
        jnp.asarray(lo), jnp.asarray(hi),
        FleetState(packed, table, state),
        RouterSpec(engine="binomial", capacity=64, use_pallas=False),
    )
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))


def test_make_sharded_route_shim_is_bit_identical():
    import jax

    mesh = jax.make_mesh((1,), ("data",))
    packed, table, state = _shim_operands()
    keys = jnp.asarray(RNG.integers(0, 2**32, size=(1024,), dtype=np.uint32))
    spec = RouterSpec(engine="binomial", capacity=64, use_pallas=False)
    ops._warned.clear()
    with pytest.warns(DeprecationWarning, match="make_sharded_route"):
        legacy = ops.make_sharded_route(
            mesh, "data", n_words=mask_words(64), n_slots=64, use_pallas=False
        )
    old = legacy(keys, packed, table, state)
    new = ops.make_sharded_route(mesh, spec)(
        keys, FleetState(packed, table, state)
    )
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))


def test_shim_accepts_non_pow2_n_slots_on_every_path():
    """Pre-spec callers could pack for any slot bound (lane-padded, not
    pow2-padded); the shim re-pads to the rounded-up capacity, so both
    dispatch flavours keep returning the pre-spec results."""
    oracle = SessionRouter(
        200, engine="binomial32", chain_bits=32, resolve="table"
    )
    for r in (3, 77, 150):
        oracle.fail(r)
    dom = oracle.domain
    packed = pack_removed_mask(dom.removed, 300)  # width 128 words
    table = pack_table(dom.replacement_table, 300)  # width 384 < pow2(300)
    state = np.array([dom.total_count, dom.alive_count], np.uint32)
    keys = jnp.asarray(RNG.integers(0, 2**32, size=(1024,), dtype=np.uint32))
    kw = dict(n_words=mask_words(300), n_slots=300)
    ops._warned.clear()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        jnp_out = ops.binomial_route_bulk(
            keys, packed, table, state, use_pallas=False, **kw
        )
        pal_out = ops.binomial_route_bulk(
            keys, packed, table, state, interpret=True, block_rows=4, **kw
        )
    expect = [dom.locate(int(k)) for k in np.asarray(keys)]
    np.testing.assert_array_equal(np.asarray(jnp_out), expect)
    np.testing.assert_array_equal(np.asarray(pal_out), expect)


def test_shim_rejects_inconsistent_n_words():
    packed, table, state = _shim_operands()
    keys = jnp.asarray(RNG.integers(0, 2**32, size=(128,), dtype=np.uint32))
    ops._warned.clear()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        with pytest.raises(ValueError, match="disagrees with n_slots"):
            ops.binomial_route_bulk(
                keys, packed, table, state, n_words=7, n_slots=64,
                use_pallas=False,
            )


# ---------------------------------------------------------------------------
# MoE hash router: pluggable engine
# ---------------------------------------------------------------------------


def test_moe_hash_router_jump_engine():
    import jax
    from repro.configs import reduced_config
    from repro.models.layers import moe

    cfg = reduced_config("qwen3-moe-235b-a22b")
    token_ids = jnp.asarray(RNG.integers(0, 50000, size=(2, 16), dtype=np.int32))
    x = jnp.zeros((2, 16, cfg.d_model), jnp.float32)

    def ids_for(**moe_kw):
        mcfg = dataclasses.replace(cfg.moe, router="hash", **moe_kw)
        c = dataclasses.replace(cfg, moe=mcfg)
        p = moe.init_moe(jax.random.PRNGKey(0), c)
        ids, gates, aux = moe.route(p, x, token_ids, 3, c)
        return np.asarray(ids)

    jump_static = ids_for(router_hash_engine="jump")
    jump_dyn = ids_for(router_hash_engine="jump", router_dynamic_n=True)
    np.testing.assert_array_equal(jump_static, jump_dyn)
    assert (jump_static >= 0).all()
    assert (jump_static < cfg.moe.num_experts).all()
    # the config actually switches the lookup family
    assert not np.array_equal(jump_static, ids_for(router_hash_engine="binomial"))
    with pytest.raises(KeyError, match="unknown bulk engine"):
        ids_for(router_hash_engine="nope")


# ---------------------------------------------------------------------------
# curated public surface
# ---------------------------------------------------------------------------


def test_repro_public_api_resolves():
    import repro

    assert set(repro.__all__) == set(repro._EXPORTS)
    for name in repro.__all__:
        assert getattr(repro, name) is not None
    assert repro.BatchRouter is BatchRouter
    assert repro.RouterSpec is RouterSpec
    assert "BatchRouter" in dir(repro)
    with pytest.raises(AttributeError):
        repro.not_a_thing
