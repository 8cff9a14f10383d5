"""The plain reference agrees with the program's own oracles at small sizes
on the CPU: the scalar u32 BinomialHash with the table divert, the jnp
route, and the R=3 placement (the checks ``chip_smoke.py`` makes)."""
import numpy as np
import pytest

import reference

from repro.core.binomial import binomial_lookup32
from repro.placement.store import StorePlacement
from repro.serving.batch_router import BatchRouter


def _keys(rng, n):
    return rng.integers(0, 1 << 32, n, dtype=np.uint32)


@pytest.mark.parametrize("n", [1, 2, 3, 5, 64, 100, 1000, 1023])
def test_binomial_matches_the_scalar_oracle(n):
    keys = _keys(np.random.default_rng(n), 2000)
    want = [binomial_lookup32(int(k), n, 16) for k in keys]
    assert reference.binomial(keys, n, 16).tolist() == want


@pytest.mark.parametrize("n,failed", [(100, 6), (1000, 60), (1000, 400)])
def test_route_matches_the_router_after_a_storm(n, failed):
    rng = np.random.default_rng(n + failed)
    router = BatchRouter(n, capacity=1024)
    fleet = reference.Fleet(n)
    for node in rng.choice(n - 1, failed, replace=False):
        router.fail(int(node))
        fleet.fail(int(node))
    keys = _keys(rng, 4096)
    want = reference.route(keys, fleet, 16)
    assert np.array_equal(np.asarray(router.route_keys(keys)), want)
    locate = router.scalar.domain.locate
    assert [locate(int(k)) for k in keys[:500]] == want[:500].tolist()
    assert not fleet.failed()[want].any()
    # the control leaves out the second redirect: it answers failed nodes
    control = reference.route(keys, fleet, 16, second_redirect=False)
    assert fleet.failed()[control].any()


def test_fleet_refuses_a_resize_and_a_second_failure():
    fleet = reference.Fleet(10)
    fleet.fail(3)
    with pytest.raises(ValueError):
        fleet.fail(3)
    with pytest.raises(ValueError):
        fleet.fail(9)


@pytest.mark.parametrize("failed", [0, 60])
def test_placement_matches_the_store(failed):
    rng = np.random.default_rng(failed + 3)
    router = BatchRouter(1000, capacity=1024)
    fleet = reference.Fleet(1000)
    for node in rng.choice(999, failed, replace=False):
        router.fail(int(node))
        fleet.fail(int(node))
    keys = _keys(rng, 4096)
    want = reference.place(keys, fleet, 3, 16)
    got = np.asarray(StorePlacement(router, r=3).place_keys(keys)[0])
    assert np.array_equal(got, want)
    assert (want[:, 0] != want[:, 1]).all() and (want[:, 1] != want[:, 2]).all()
    assert (want[:, 0] != want[:, 2]).all() and not fleet.failed()[want].any()
    control = reference.place(keys, fleet, 3, 16, resalt=False)
    same = (control[:, :, None] == control[:, None, :]).sum(axis=(1, 2))
    assert (same > 3).any()
