"""The placement pass's small-table read (``kernels/table_read.py``): the VMEM
kernel in interpret mode against ``table[idx]`` bit for bit, the route's
divert and the whole pass with it against the same with XLA gathers, the
path each table width takes, and the count of reads each dispatch adds to
the registry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bulk import PlacementSpec, RouterSpec
from repro.core.jump_jax import jump_unrolled_body
from repro.core.memento_jax import fused_route_impl
from repro.core.registry import BULK_ENGINES
from repro.kernels import ops
from repro.kernels.table_read import MAX_VMEM_ROWS, TableRead
from repro.placement.store import StorePlacement, divert_read_path, table_reads
from repro.serving.batch_router import BatchRouter

KEYS = np.random.default_rng(17).integers(0, 1 << 32, 3000, dtype=np.uint32)
VMEM = TableRead(vmem=True, block_rows=8, interpret=True)


# -- the read alone ------------------------------------------------------------


@pytest.mark.parametrize("width", (128, 1024, 1152, 8192))
def test_vmem_read_equals_indexing(width):
    rng = np.random.default_rng(width)
    table = rng.integers(0, 1 << 32, width, dtype=np.uint32)
    edges = np.concatenate([
        [0, width - 1],
        np.arange(0, width, 128),  # the first entry of every row
        np.arange(127, width, 128),  # the last entry of every row
    ])
    # 3,000 indices: three grid steps of 8 rows, the last one padded
    idx = np.concatenate([
        edges, rng.integers(0, width, 3000 - edges.size)
    ]).astype(np.uint32)
    assert VMEM.path(width) == "vmem"
    got = VMEM(jnp.asarray(table), jnp.asarray(idx))
    assert got.dtype == jnp.uint32
    assert np.array_equal(np.asarray(got), table[idx])


@pytest.mark.parametrize("read,width,path", [
    (VMEM, 128, "vmem"),
    (VMEM, 128 * MAX_VMEM_ROWS, "vmem"),
    (VMEM, 128 * MAX_VMEM_ROWS + 128, "gather"),
    (VMEM, 16384, "gather"),
    (TableRead(), 1024, "gather"),
])
def test_read_path_by_backend_and_table_width(read, width, path):
    assert read.path(width) == path
    table = jnp.arange(width, dtype=jnp.uint32)
    idx = jnp.asarray(KEYS[:1024] % width)
    traced = str(jax.make_jaxpr(read)(table, idx))
    assert ("pallas_call" in traced) == (path == "vmem")
    assert np.array_equal(np.asarray(read(table, idx)), np.asarray(idx))


def test_spec_picks_the_kernel_where_the_route_kernel_runs():
    # off the TPU the kernel runs only in interpret mode; the tile is the
    # spec's own, never the autotuner's
    assert TableRead.for_spec(RouterSpec(use_pallas=False)) == TableRead()
    assert TableRead.for_spec(RouterSpec(interpret=True, block_rows=8)) == VMEM
    assert TableRead.for_spec(RouterSpec(interpret=True)).block_rows == 512


# -- the whole pass --------------------------------------------------------------


def _storm(zones=1, **kw):
    router = BatchRouter(1000, capacity=1024, zones=zones, **kw)
    rng = np.random.default_rng(60)
    with router.coalesced_events():
        for node in rng.choice(999, 60, replace=False):
            router.fail(int(node))
    return router


def _zone_loss(**kw):
    router = BatchRouter(301, capacity=512, zones=3, **kw)
    with router.coalesced_events():
        for node in (*range(2, 300, 3), 4, 9, 31):  # zone 2 and 3 more
            router.fail(node)
    return router


def _tiny(n, failed, **kw):
    router = BatchRouter(n, capacity=64, **kw)
    for node in failed:
        router.fail(node)
    return router


FLEETS = {
    "storm": (_storm, 3, None),
    "zoned_storm": (lambda **kw: _storm(zones=3, **kw), 3, None),
    "zone_loss": (_zone_loss, 3, None),
    # three alive shards for three columns: most columns collide
    "three_alive": (lambda **kw: _tiny(5, (1, 3), **kw), 3, None),
    # n_alive <= r: the duplicate is the defined degraded answer
    "two_alive": (lambda **kw: _tiny(4, (0, 2), **kw), 3, None),
    "one_alive": (lambda **kw: _tiny(3, (0, 1), **kw), 3, None),
    # a probe bound too tight: the exhausted rows must agree too
    "exhausting": (lambda **kw: _tiny(4, (), **kw), 4, 1),
}


def _conds(jaxpr):
    """Every ``cond`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _conds(sub)


def _kernel_in_the_divert(closed) -> bool:
    """The divert's ``cond`` of a traced program reads by the kernel."""
    return any("pallas_call" in str(eqn) for eqn in _conds(closed.jaxpr))


@pytest.mark.parametrize("fleet", ("storm", "zone_loss"))
@pytest.mark.parametrize("engine", ("binomial", "jump"))
def test_route_with_vmem_read_equals_the_gather_route(engine, fleet):
    router = FLEETS[fleet][0]()
    fleet_ops = tuple(jnp.asarray(a) for a in (
        router._fleet_host.packed, router._fleet_host.table,
        router._fleet_host.state))
    keys = jnp.asarray(KEYS)  # 3,000 keys: not a whole number of 128-lane rows
    spec = router.spec
    lookup = {"jump": {"lookup": jump_unrolled_body}}.get(engine, {})
    want = fused_route_impl(keys, *fleet_ops, spec.omega, spec.n_words,
                            **lookup)
    got = fused_route_impl(keys, *fleet_ops, spec.omega, spec.n_words,
                           read=VMEM, **lookup)
    route = BULK_ENGINES[engine].route
    static = dict(omega=spec.omega, n_words=spec.n_words)
    want_jit = route(keys, *fleet_ops, **static)
    got_jit = route(keys, *fleet_ops, read=VMEM, **static)
    for a in (got, want_jit, got_jit):
        assert np.array_equal(np.asarray(a), np.asarray(want))
    # the divert ran, and read by the kernel inside its cond
    assert not np.isin(np.asarray(got), sorted(router.domain.removed)).any()
    assert _kernel_in_the_divert(
        jax.make_jaxpr(lambda k: route(k, *fleet_ops, read=VMEM, **static))(keys))
    assert not _kernel_in_the_divert(
        jax.make_jaxpr(lambda k: route(k, *fleet_ops, **static))(keys))


@pytest.mark.parametrize("fleet", sorted(FLEETS))
def test_pass_with_vmem_read_equals_the_gather_pass(fleet):
    make, r, max_resalt = FLEETS[fleet]
    answers = []
    for kw in ({}, {"interpret": True, "block_rows": 8}):
        router = make(**kw)
        store = StorePlacement(router, r=r, max_resalt=max_resalt,
                               zones=router.domain.zones)
        path = "vmem" if kw else "gather"
        assert set(table_reads(store.spec)) == {path}
        assert divert_read_path(store.spec) == path
        zone = () if store.spec.zones == 1 else (store._zone_state_dev(),)
        traced = jax.make_jaxpr(lambda k: ops.route_replicas_bulk(
            k, router._fleet_dev, store.spec, *zone))(KEYS)
        assert _kernel_in_the_divert(traced) == (path == "vmem")
        answers.append([np.asarray(a) for a in store.place_keys(KEYS)])
        # a failed slot opens the divert: one more read of the slots table
        divert = router.domain.alive_count != router.domain.total_count
        assert divert == (fleet != "exhausting")
        assert store.metrics.total("placement_table_reads_total", path=path) \
            == sum(table_reads(store.spec).values()) + divert
    want, got = answers
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    if fleet == "exhausting":
        assert want[1].any()


# -- the counter -----------------------------------------------------------------


def _counted(zones, interpret, path, per_call, failed=False):
    # a healthy fleet's cases keep the ids they had before the failed ones
    name = f"{zones}-{interpret}-{path}-{per_call}" + ("-failed" if failed else "")
    return pytest.param(zones, interpret, path, per_call, failed, id=name)


@pytest.mark.parametrize("zones,interpret,path,per_call,failed", [
    _counted(1, False, "gather", 6),
    _counted(1, True, "vmem", 6),
    _counted(3, False, "gather", 8),
    _counted(3, True, "vmem", 8),
    # one failed shard: the route's divert reads the slots table once more
    _counted(1, False, "gather", 7, failed=True),
    _counted(1, True, "vmem", 7, failed=True),
    _counted(3, False, "gather", 9, failed=True),
    _counted(3, True, "vmem", 9, failed=True),
])
def test_table_reads_counted_per_dispatch_by_path(zones, interpret, path,
                                                  per_call, failed):
    router = BatchRouter(64, capacity=64, zones=zones, interpret=interpret,
                         block_rows=8)
    if failed:
        router.fail(5)
    store = StorePlacement(router, r=3, zones=zones)
    m = store.metrics
    for calls in (1, 2):
        store.place_keys(KEYS[:256])
        assert m.total("placement_table_reads_total", path=path) == (
            calls * per_call)
    assert m.total("placement_table_reads_total") == 2 * per_call
    if failed:
        # the shard recovered: the divert is skipped, its read not counted
        router.recover(5)
        store.place_keys(KEYS[:256])
        assert m.total("placement_table_reads_total", path=path) == (
            3 * per_call - 1)


def test_wide_tables_count_as_gathers():
    # 16,384 slots are 128 rows: past MAX_VMEM_ROWS, the XLA gather reads
    spec = PlacementSpec(router=RouterSpec(capacity=16384, interpret=True),
                         r=3, zones=3)
    assert table_reads(spec) == {"gather": 8}
    spec = PlacementSpec(router=RouterSpec(capacity=1024, interpret=True),
                         r=3, max_resalt=2, zones=2)
    assert table_reads(spec) == {"vmem": 5}
