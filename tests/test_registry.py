"""Registry coverage: every ``ENGINES`` entry constructs and routes, the
named comparison lists point at real engines and honour their advertised
properties, and every ``BULK_ENGINES`` device entry is bit-exact against
its scalar oracle across fleet-event streams."""
import numpy as np
import pytest

from repro.core.registry import (
    BULK_ENGINES,
    CONSTANT_TIME,
    ENGINES,
    FULLY_CONSISTENT,
    make,
    make_bulk,
)
from repro.serving.batch_router import BatchRouter
from repro.serving.router import SessionRouter

RNG = np.random.default_rng(23)
KEYS = [int(k) for k in RNG.integers(0, 2**64, size=400, dtype=np.uint64)]


# ---------------------------------------------------------------------------
# scalar registry
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(ENGINES))
def test_engine_constructs_routes_and_resizes(name):
    eng = ENGINES[name](7)
    assert eng.size == 7
    for k in KEYS[:50]:
        assert 0 <= eng.get_bucket(k) < 7
    new = eng.add_bucket()
    assert new == 7 and eng.size == 8
    assert eng.remove_bucket() == 7 and eng.size == 7
    assert isinstance(eng.name, str) and isinstance(eng.exact, bool)


def test_make_resolves_and_rejects():
    assert make("binomial", 5).size == 5
    with pytest.raises(KeyError, match="unknown engine"):
        make("not-an-engine", 5)
    with pytest.raises(KeyError, match="unknown bulk engine"):
        make_bulk("not-an-engine")


def test_named_lists_are_registry_members():
    assert set(CONSTANT_TIME) <= set(ENGINES)
    assert set(FULLY_CONSISTENT) <= set(ENGINES)
    # the paper's Fig. 5 comparison set and both device-word flavours exist
    for name in ("binomial", "jump", "binomial32", "jump32"):
        assert name in ENGINES


@pytest.mark.parametrize("name", sorted(FULLY_CONSISTENT))
def test_fully_consistent_engines_are_monotone(name):
    """Growing n -> n+1 moves keys only ONTO the new bucket; shrinking
    n+1 -> n moves only the keys OF the removed bucket (the §6 guarantee
    the FULLY_CONSISTENT list advertises), across small n incl. pow2
    boundaries."""
    for n in range(1, 18):
        eng = ENGINES[name](n)
        before = [eng.get_bucket(k) for k in KEYS]
        eng.add_bucket()
        after = [eng.get_bucket(k) for k in KEYS]
        movers = [(a, b) for a, b in zip(before, after) if a != b]
        assert all(b == n for _, b in movers), f"{name} n={n}: non-monotone grow"
        # shrink back: exactly the keys on bucket n return to their old home
        eng.remove_bucket()
        again = [eng.get_bucket(k) for k in KEYS]
        assert again == before, f"{name} n={n}: remove(add(x)) != x"


# ---------------------------------------------------------------------------
# bulk (device) registry: each entry vs its scalar oracle over event streams
# ---------------------------------------------------------------------------

EVENT_STREAM = [
    ("fail", 2),
    ("scale_up", None),
    ("fail", 5),
    ("scale_down", None),
    ("recover", 2),
    ("scale_up", None),
    ("fail", 0),
    ("recover", 0),
]


@pytest.mark.parametrize("name", sorted(BULK_ENGINES))
def test_bulk_engine_entry_is_complete(name):
    eng = make_bulk(name)
    assert eng.name == name
    assert eng.scalar_engine in ENGINES
    assert callable(eng.route)
    # the MoE hash router and the certifier need these
    assert callable(eng.lookup_dyn) and callable(eng.lookup_vec)


@pytest.mark.parametrize("name", sorted(BULK_ENGINES))
def test_bulk_engine_matches_scalar_oracle_across_events(name):
    """Key-for-key device == scalar parity through a fleet-event stream —
    the protocol contract every registered engine must honour."""
    eng = make_bulk(name)
    router = BatchRouter(8, engine=name)
    oracle = SessionRouter(
        8, engine=eng.scalar_engine, chain_bits=32, resolve="table"
    )
    keys = RNG.integers(0, 2**64, size=(2048,), dtype=np.uint64)
    sample = keys[:256]
    for ev, arg in EVENT_STREAM:
        for r in (router, oracle):
            getattr(r, ev)(*(() if arg is None else (arg,)))
        out = router.route_keys_np(keys)
        expect = [oracle.domain.locate(int(k)) for k in sample]
        np.testing.assert_array_equal(out[: len(sample)], expect)
        # and the router's own scalar control plane agrees with its batch
        assert int(out[0]) == router.domain.locate(int(keys[0]))


@pytest.mark.parametrize("name", sorted(BULK_ENGINES))
def test_bulk_engine_empty_batch(name):
    router = BatchRouter(4, engine=name)
    assert router.route_keys_np(np.empty(0, dtype=np.uint64)).shape == (0,)
    assert router.route_batch([]).shape == (0,)
    if make_bulk(name).ingest is not None:
        assert np.asarray(
            router.route_ids(np.empty(0, dtype=np.uint64))
        ).shape == (0,)
