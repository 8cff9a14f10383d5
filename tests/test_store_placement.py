"""R-way replicated store placement (DESIGN.md §13): distinctness, typed
degradation, repair convergence, migration accounting, replay parity."""
import numpy as np
import pytest

from repro.core.bulk import PlacementSpec, RouterSpec
from repro.placement.assignment import MovementPlan
from repro.placement.store import NO_HOLDER, StorePlacement, family_salts
from repro.serving.batch_router import BatchRouter
from repro.serving.lifecycle import (
    FleetUnavailableError,
    LifecycleConfig,
    LifecycleManager,
    PlacementDegradedError,
    PlacementExhaustedError,
    PlacementRepairer,
)

ENGINES = ("binomial", "jump")
KEYS = np.random.default_rng(3).integers(0, 1 << 32, size=512, dtype=np.uint32)


def mk(n, engine="binomial", r=3, capacity=64, **kw):
    router = BatchRouter(n, engine=engine, capacity=capacity)
    mgr = LifecycleManager(router, LifecycleConfig(min_alive_floor=1))
    store = StorePlacement(router, r=r, **kw)
    return router, mgr, store


def distinct_per_row(replicas) -> np.ndarray:
    reps = np.asarray(replicas)
    return np.array([len(set(row.tolist())) for row in reps])


# -- the device pass: distinctness + alive-only -------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_placement_rows_distinct_and_alive(engine):
    _router, mgr, store = mk(8, engine=engine)
    batch = store.place(KEYS)
    assert batch.mode == "normal"
    assert batch.n_distinct == 3
    assert (distinct_per_row(batch.replicas) == 3).all()
    reps = np.asarray(batch.replicas)
    assert reps.shape == (KEYS.size, 3)
    assert ((reps >= 0) & (reps < 8)).all()
    # after failures every replica still lands on an ALIVE shard
    mgr.fail(2)
    mgr.fail(5)
    batch = store.place(KEYS)
    reps = np.asarray(batch.replicas)
    assert (distinct_per_row(reps) == 3).all()
    assert 2 not in set(reps.reshape(-1).tolist())
    assert 5 not in set(reps.reshape(-1).tolist())


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("n_alive", (2, 3, 4, 5, 8, 16))
def test_distinctness_guarantee_across_fleet_sizes(engine, n_alive):
    # default max_resalt: distinctness is DETERMINISTIC — every key gets
    # exactly min(r, n_alive) distinct shards, never a silent duplicate
    _router, _mgr, store = mk(n_alive, engine=engine)
    batch = store.place(KEYS)
    assert (distinct_per_row(batch.replicas) == min(3, n_alive)).all()


def test_r_equals_n_total_covers_every_shard():
    _router, _mgr, store = mk(4, r=4, capacity=4)
    batch = store.place(KEYS)
    reps = np.asarray(batch.replicas)
    # r == n_total: every row is a permutation of ALL four shards
    assert (np.sort(reps, axis=1) == np.arange(4)).all()


def test_family_salts_distinct():
    s = family_salts(8)
    assert np.unique(s).size == 8


# -- typed degradation --------------------------------------------------------


def test_r_exceeds_n_alive_degrades():
    _router, mgr, store = mk(8)
    for s in (0, 1, 2, 3, 4, 5):
        mgr.fail(s)
    assert mgr.n_alive == 2
    batch = store.place(KEYS)
    assert batch.mode == "degraded"
    assert batch.n_distinct == 2
    assert (distinct_per_row(batch.replicas) == 2).all()


def test_strict_raises_typed_degraded():
    _router, mgr, store = mk(4, strict=True)
    mgr.fail(1)
    mgr.fail(2)
    with pytest.raises(PlacementDegradedError) as ei:
        store.place(KEYS)
    assert ei.value.n_alive == 2
    assert ei.value.r == 3


def test_unavailable_stays_typed():
    _router, mgr, store = mk(2)
    store.register(KEYS[:32])
    mgr.fail(0)
    mgr.fail(1)
    assert mgr.n_alive == 0
    with pytest.raises(FleetUnavailableError):
        store.place(KEYS)
    with pytest.raises(FleetUnavailableError):
        store.read(0)


def test_resalt_exhaustion_is_typed_not_silent():
    # an explicitly too-tight probe bound: the collision is REPORTED as a
    # typed error, never resolved to a silent duplicate
    _router, _mgr, store = mk(4, r=2, max_resalt=0)
    with pytest.raises(PlacementExhaustedError) as ei:
        store.place(np.arange(1024, dtype=np.uint32))
    assert ei.value.n_keys > 0
    assert ei.value.max_resalt == 0
    # the raw expert path surfaces the per-key flags instead of raising
    replicas, exhausted = store.place_keys(np.arange(1024, dtype=np.uint32))
    ex = np.asarray(exhausted)
    assert ex.any()
    dup = distinct_per_row(replicas) == 1
    # exhausted keys are exactly the duplicated rows — nothing silent
    assert (dup == ex).all()


# -- degraded reads -----------------------------------------------------------


def test_all_but_one_holders_failed_still_readable():
    _router, mgr, store = mk(8)
    store.register(KEYS[:64])
    holders = store.holders[0].tolist()
    for s in holders[1:]:
        mgr.fail(int(s))
    found, mode = store.read(0)
    assert found.tolist() == [holders[0]]
    assert mode == "degraded"
    assert store.reachable_counts().min() >= 1


def test_read_normal_mode_when_fully_replicated():
    _router, _mgr, store = mk(8)
    store.register(KEYS[:16])
    found, mode = store.read(3)
    assert mode == "normal"
    assert len(set(found.tolist())) == 3


# -- migration plan -----------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_migration_diff_is_membership_not_positional(engine):
    router, mgr, store = mk(16, engine=engine, capacity=32)
    store.register(KEYS)
    mgr.scale_up()
    plan = store.plan_migration()
    # recompute the membership diff on the host and compare
    for i in range(plan.keys.size):
        old_row = set(plan.old[i].tolist())
        for j in range(3):
            assert plan.moved[i, j] == (int(plan.new[i, j]) not in old_row)
    assert plan.epoch == router.routing_epoch
    assert 0 < plan.moved_pairs < plan.total_pairs
    assert plan.moved_fraction == plan.moved_pairs / plan.total_pairs


def test_per_shard_moves_matches_mask():
    _router, mgr, store = mk(8, capacity=16)
    store.register(KEYS)
    mgr.scale_up()
    plan = store.plan_migration()
    sched = plan.per_shard_moves()
    assert sum(len(v) for v in sched.values()) == plan.moved_pairs
    for dst, moves in sched.items():
        assert dst in set(plan.new[plan.moved].tolist())
        assert all(isinstance(k, int) for k, _src in moves)


def test_as_movement_plan_shares_accounting():
    _router, mgr, store = mk(8, capacity=16)
    store.register(KEYS)
    mgr.scale_up()
    plan = store.plan_migration()
    mv = plan.as_movement_plan()
    assert mv.moved_count == plan.moved_pairs
    assert mv.total_keys == plan.total_pairs
    assert mv.destinations() <= set(plan.new.reshape(-1).tolist())


# -- MovementPlan unification -------------------------------------------------


def test_movement_plan_from_diff():
    keys = np.arange(6, dtype=np.uint64)
    before = np.array([0, 1, 2, 0, 1, 2])
    after = np.array([0, 1, 3, 3, 1, 2])
    plan = MovementPlan.from_diff(keys, before, after)
    assert plan.moved_count == 2
    assert plan.total_keys == 6
    assert plan.destinations() == {3}
    assert plan.sources() == {0, 2}
    assert {(m.key, m.src, m.dst) for m in plan.moves} == {(2, 2, 3), (3, 0, 3)}


# -- repair scheduler ---------------------------------------------------------


def test_repairer_budget_and_oldest_first():
    _router, mgr, store = mk(8)
    store.register(KEYS)
    rep = PlacementRepairer(store, mgr, budget_per_tick=5)
    assert rep.backlog == 0
    mgr.fail(1)  # first membership event -> older epoch
    epoch1 = mgr.epoch
    mid = rep.backlog
    assert mid > 0
    done = rep.tick()
    assert 0 < len(done) <= 5
    assert all(t.epoch == epoch1 for t in done)
    mgr.fail(3)  # second event: NEW under-replication at a later epoch
    assert rep.backlog > 0
    emitted = []
    while rep.backlog:
        emitted.extend(rep.tick())
    epochs = [t.epoch for t in emitted]
    assert epochs == sorted(epochs)  # oldest-first across the whole drain
    assert max(rep.batches) <= 5
    assert (store.reachable_counts() == 3).all()
    assert rep.lost == 0


def test_repair_convergence_after_churn():
    _router, mgr, store = mk(8, capacity=16)
    store.register(KEYS)
    rep = PlacementRepairer(store, mgr, budget_per_tick=16)
    mgr.fail(2)
    mgr.scale_up()
    mgr.fail(5)
    mgr.recover(2)
    rep.quiesce()
    n_eff = min(3, mgr.n_alive)
    assert (store.reachable_counts() == n_eff).all()
    assert rep.backlog == 0


def test_repairer_ticks_through_manager():
    _router, mgr, store = mk(8)
    store.register(KEYS[:64])
    rep = PlacementRepairer(store, mgr, budget_per_tick=1_000_000)
    mgr.fail(4)
    assert rep.backlog > 0
    mgr.tick()  # the manager drives attached repairers
    assert rep.backlog == 0
    assert (store.reachable_counts() == 3).all()


def test_repairer_requires_same_router():
    _router, mgr, store = mk(8)
    other_router, _other_mgr, _other_store = mk(8)
    other_store = StorePlacement(other_router, r=3)
    with pytest.raises(ValueError, match="SAME router"):
        PlacementRepairer(other_store, mgr)


# -- journal replay parity ----------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_placement_replay_parity_across_crash(engine):
    _router, mgr, store = mk(8, engine=engine, capacity=16)
    store.register(KEYS)
    rep = PlacementRepairer(store, mgr, budget_per_tick=64)
    mgr.fail(1)
    mgr.scale_up()
    snap = mgr.snapshot()
    mgr.fail(6)
    mgr.recover(1)
    rep.quiesce()
    # genesis replay AND snapshot+tail replay both reproduce the live
    # R-way placement bit-exactly
    rep.verify_placement_replay()
    rep.verify_placement_replay(snap)


# -- spec validation ----------------------------------------------------------


def test_placement_spec_validation():
    with pytest.raises(ValueError, match="r must be"):
        PlacementSpec(r=0)
    with pytest.raises(ValueError, match="capacity"):
        PlacementSpec(router=RouterSpec(capacity=4), r=5)
    with pytest.raises(ValueError, match="max_resalt"):
        PlacementSpec(max_resalt=-1)
    spec = PlacementSpec(r=4)
    assert spec.resolved_max_resalt == 4
    assert PlacementSpec(r=4, max_resalt=9).resolved_max_resalt == 9
    hash(spec)  # static-arg hashability


def test_sync_targets_purges_retired_slots():
    _router, mgr, store = mk(4, capacity=4)
    store.register(KEYS[:64])
    mgr.fail(3)  # top slot: LIFO retirement shrinks the fleet
    assert store.router.domain.total_count == 3
    store.sync_targets()
    assert (store.holders < 3).all()  # no holder references the retired id
    assert (store.holders != NO_HOLDER).sum() > 0


# -- certifier target ---------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_certifier_placement_target(engine):
    from repro.analysis.certify import certify_placement_route

    report = certify_placement_route(engine)
    assert report.target == "placement/route_replicas"
    assert report.ok, [c.invariant for c in report.failures()]
