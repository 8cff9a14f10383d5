"""Failure-resolution edge cases, both flavours:

* chain (``memento_remap`` — library flavour): max_chain exhaustion ->
  first_alive fallback, bit-exact vs ``MementoWrapper(chain_bits=32)``;
* table (``binomial_memento_route`` / ``fused_route_impl`` — the serving
  datapath): all-removed-but-one fleets, the deep second redirect, and the
  alive-slot property under hypothesis.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MementoWrapper, make
from repro.core.binomial_jax import binomial_lookup_dyn
from repro.core.memento_jax import (
    binomial_memento_route,
    fused_route_impl,
    mask_words,
    memento_remap,
    pack_removed_mask,
    pack_table,
)
from repro.serving.batch_router import BatchRouter

RNG = np.random.default_rng(23)
CAP = 64


def _wrapper(n, removed, max_chain=4096, resolve="chain"):
    eng = MementoWrapper(lambda m: make("binomial32", m), n, max_chain=max_chain,
                         chain_bits=32, resolve=resolve)
    for b in removed:
        eng.remove_bucket(b)
    return eng


def _remap(keys, eng, max_chain):
    """Two-pass CHAIN remap (library flavour, scalar oracle = chain mode)."""
    mask = np.zeros((CAP,), dtype=bool)
    mask[list(eng.removed)] = True
    buckets = binomial_lookup_dyn(keys, np.uint32(eng.n_total))
    return np.asarray(
        memento_remap(keys, buckets, mask, np.uint32(eng.n_total),
                      np.uint32(eng.first_alive()), max_chain=max_chain)
    )


def _table_state(eng):
    packed = pack_removed_mask(eng.removed, CAP)
    table = pack_table(eng.table, CAP)
    state = np.array([eng.n_total, eng.size], np.uint32)
    return packed, table, state


def _fused(keys, eng):
    """Fused TABLE route (serving flavour, scalar oracle = table mode)."""
    packed, table, state = _table_state(eng)
    return np.asarray(
        binomial_memento_route(jnp.asarray(keys), jnp.asarray(packed),
                               jnp.asarray(table), jnp.asarray(state),
                               n_words=mask_words(CAP))
    )


def _remap_table(keys, eng):
    """TABLE divert through the unjitted body the test oracles share."""
    packed, table, state = _table_state(eng)
    return np.asarray(
        fused_route_impl(jnp.asarray(keys), jnp.asarray(packed),
                         jnp.asarray(table), jnp.asarray(state), 16,
                         mask_words(CAP))
    )


# ---------------------------------------------------------------------------
# chain flavour: max_chain exhaustion -> first_alive fallback
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("max_chain", [0, 1, 2])
@pytest.mark.parametrize("removed", [[0], [0, 1, 2], [3, 5]])
def test_max_chain_exhaustion_falls_back_to_first_alive(max_chain, removed):
    """With a tiny chain budget, lanes that exhaust it must land on
    first_alive — identically on the scalar chain and the device remap."""
    eng = _wrapper(8, removed, max_chain=max_chain)
    keys = RNG.integers(0, 2**32, size=(2048,), dtype=np.uint32)
    scal = np.array([eng.get_bucket(int(k)) for k in keys])
    np.testing.assert_array_equal(_remap(keys, eng, max_chain), scal)
    # max_chain=0 forces EVERY removed-slot lane onto first_alive
    if max_chain == 0:
        base = np.asarray(binomial_lookup_dyn(keys, np.uint32(eng.n_total)))
        hit = np.isin(base, list(eng.removed))
        assert hit.any()
        assert (scal[hit] == eng.first_alive()).all()


# ---------------------------------------------------------------------------
# table flavour: all-removed-but-one fleets and the deep second redirect
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("survivor", [0, 3, 7])
def test_all_removed_but_one_routes_everything_to_survivor(survivor):
    n = 8
    eng = _wrapper(n, [b for b in range(n) if b != survivor], resolve="table")
    keys = RNG.integers(0, 2**32, size=(4096,), dtype=np.uint32)
    out = _fused(keys, eng)
    assert (out == survivor).all()
    np.testing.assert_array_equal(out, _remap_table(keys, eng))
    scal = np.array([eng.get_bucket(int(k)) for k in keys])
    np.testing.assert_array_equal(out, scal)


def test_all_removed_but_one_via_batch_router_events():
    router = BatchRouter(8, interpret=True, block_rows=8)
    for r in range(7):
        router.fail(r)
    assert router.alive == 1
    keys = RNG.integers(0, 2**64, size=(2048,), dtype=np.uint64)
    assert (router.route_keys_np(keys) == 7).all()
    router.recover(3)
    out = router.route_keys_np(keys)
    assert set(np.unique(out)) <= {3, 7}
    expect = [router.domain.locate(int(k)) for k in keys]
    np.testing.assert_array_equal(out, expect)


def test_deep_second_redirect_is_exercised_and_exact():
    """With most slots removed, redirect 1 frequently lands on a removed
    position — the deep branch (redirect 2) must fire and stay bit-exact."""
    n = 32
    removed = [b for b in range(n) if b % 4 != 0]  # 75% removed
    eng = _wrapper(n, removed, resolve="table")
    keys = RNG.integers(0, 2**32, size=(8192,), dtype=np.uint32)
    # count scalar-side deep redirects to prove the branch is hit
    from repro.core import bits

    deep = 0
    for k in keys[:2048]:
        b = eng.base.get_bucket(int(k))
        if b in eng.removed:
            h = bits.hash_pair32(int(k), b)
            if bits.mulhi32(h, eng.table.n_total) >= eng.table.n_alive:
                deep += 1
    assert deep > 50
    out = _fused(keys, eng)
    scal = np.array([eng.get_bucket(int(k)) for k in keys])
    np.testing.assert_array_equal(out, scal)
    alive = np.array(eng.alive())
    assert np.isin(out, alive).all()


# ---------------------------------------------------------------------------
# property: resolved outputs always land on alive slots (both flavours)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - hypothesis is a test dependency
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @st.composite
    def fleets(draw):
        n = draw(st.integers(min_value=2, max_value=CAP))
        n_removed = draw(st.integers(min_value=0, max_value=n - 1))
        removed = draw(
            st.sets(st.integers(min_value=0, max_value=n - 1),
                    min_size=n_removed, max_size=n_removed)
        )
        return n, sorted(removed)

    @given(fleets(), st.integers(min_value=0, max_value=2**32 - 1))
    @settings(max_examples=150, deadline=None)
    def test_table_route_always_lands_on_alive_slots(fleet, key_seed):
        n, removed = fleet
        eng = _wrapper(n, removed, resolve="table")
        keys = np.asarray(
            np.random.default_rng(key_seed).integers(0, 2**32, size=(256,)),
            dtype=np.uint32,
        )
        out = _fused(keys, eng)
        alive = np.array(eng.alive())
        assert np.isin(out, alive).all(), (n, removed)
        np.testing.assert_array_equal(out, _remap_table(keys, eng))
        scal = np.array([eng.get_bucket(int(k)) for k in keys])
        np.testing.assert_array_equal(out, scal)

    @given(fleets(), st.integers(min_value=0, max_value=2**32 - 1),
           st.integers(min_value=0, max_value=3))
    @settings(max_examples=50, deadline=None)
    def test_chain_remap_always_lands_on_alive_slots(fleet, key_seed, max_chain_pow):
        n, removed = fleet
        max_chain = 4096 if max_chain_pow == 0 else (1 << max_chain_pow)
        eng = _wrapper(n, removed, max_chain=max_chain)
        keys = np.asarray(
            np.random.default_rng(key_seed).integers(0, 2**32, size=(128,)),
            dtype=np.uint32,
        )
        out = _remap(keys, eng, max_chain)
        alive = np.array(eng.alive())
        assert np.isin(out, alive).all(), (n, removed, max_chain)
        scal = np.array([eng.get_bucket(int(k)) for k in keys])
        np.testing.assert_array_equal(out, scal)
