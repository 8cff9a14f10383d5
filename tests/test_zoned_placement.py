"""Zone-aware R-way placement (DESIGN.md §13.5): the zoned pass against the
plain zoned reference, bit for bit, its five guarantees row by row, the
minimal-disruption gate, and the zone-free pass left as it was."""
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.binomial_jax import GOLDEN32, mix32, mulhi32
from repro.core.bulk import PlacementSpec, RouterSpec
from repro.core.memento import ZoneTables
from repro.core.registry import make_bulk
from repro.observability.export import to_prometheus
from repro.observability.metrics import MetricsRegistry
from repro.placement.store import (
    RESALT_SALT,
    StorePlacement,
    _route_replicas_jit,
    family_salts,
)
from repro.serving.batch_router import BatchRouter
from repro.serving.lifecycle import (
    JournalSnapshot,
    LifecycleConfig,
    LifecycleManager,
    PlacementRepairer,
)
from repro.serving.lifecycle.errors import (
    MODE_DEGRADED,
    MODE_NORMAL,
    MODE_ZONE_DEGRADED,
)

# the plain references live with the benchmark and import nothing of src/
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench"))
import reference  # noqa: E402
import reference_zoned  # noqa: E402

R = 3
Z = 3
OMEGA = 16
KEYS = np.random.default_rng(15).integers(0, 1 << 32, 4096, dtype=np.uint32)


class Deployment:
    """One router, its zoned store and the plain reference, taking the same
    events in the same order."""

    def __init__(self, n, capacity=512, zones=Z):
        self.router = BatchRouter(n, capacity=capacity, zones=zones)
        self.mgr = LifecycleManager(self.router, LifecycleConfig(min_alive_floor=1))
        self.store = StorePlacement(self.router, r=R, zones=zones)
        self.ref = reference_zoned.Zoned(n, zones)

    def fail(self, node):
        self.mgr.fail(node)
        self.ref.fail(node)

    def recover(self, node):
        self.mgr.recover(node)
        self.ref.recover(node)

    def scale_up(self):
        assert self.mgr.scale_up() == self.ref.grow()

    def lose_zone(self, zone):
        for node in range(zone, self.router.domain.total_count - 1, Z):
            self.fail(node)


def scenario(per_zone, events):
    """A 3-zone fleet of ``3 * per_zone + 1`` shards (the extra slot keeps
    zone 2's last node failable: failing the fleet's last slot is a
    resize) after ``events``."""
    dep = Deployment(Z * per_zone + 1)
    rng = np.random.default_rng(per_zone)
    if events in ("zone_lost", "recovered", "grown"):
        dep.lose_zone(2)
        for node in rng.choice([s for s in range(Z * per_zone) if s % Z != 2],
                               3, replace=False):
            dep.fail(int(node))
    if events == "recovered":
        dep.recover(2)
        dep.recover(5)
    if events == "grown":
        dep.scale_up()  # slot 3 * per_zone + 1: zone 1
    return dep


SCENARIOS = [(per_zone, events) for per_zone in (30, 64, 100)
             for events in ("healthy", "zone_lost", "recovered", "grown")]


# -- bit-exact against the plain reference -----------------------------------


@pytest.mark.parametrize("per_zone,events", SCENARIOS)
def test_zoned_pass_matches_the_plain_reference(per_zone, events):
    dep = scenario(per_zone, events)
    got = np.asarray(dep.store.place_keys(KEYS)[0])
    want = reference_zoned.place(KEYS, dep.ref, R, OMEGA)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("per_zone,events", SCENARIOS)
def test_zoned_guarantees_hold_row_by_row(per_zone, events):
    dep = scenario(per_zone, events)
    replicas, exhausted = dep.store.place_keys(KEYS)
    held = np.asarray(replicas)
    assert not np.asarray(exhausted).any()
    # 1: r distinct shards
    same = (held[:, :, None] == held[:, None, :]).sum(axis=(1, 2))
    assert (same == R).all()
    # 2: min(r, alive zones) zones; with fewer alive zones, every one
    alive_zones = dep.ref.alive_zones()
    assert dep.store.alive_zones == alive_zones
    spans = reference_zoned.zones_spanned(held, Z)
    assert (spans == min(R, alive_zones)).all()
    # 3: column 0 is the plain router's answer for the key's family 0
    fam0 = reference.mix32(KEYS ^ np.uint32(family_salts(1)[0]))
    assert np.array_equal(held[:, 0], np.asarray(dep.router.route_keys(fam0)))
    # 5: no holder is a failed shard
    assert not dep.ref.failed()[held].any()
    expected = MODE_NORMAL if alive_zones == Z else MODE_ZONE_DEGRADED
    assert dep.store.place(KEYS[:64]).mode == expected


@pytest.mark.parametrize("per_zone,events", SCENARIOS[:4])
def test_zone_blind_placement_fails_the_zone_check(per_zone, events):
    # the control: the zone-free reference spreads some rows over fewer
    # zones than the zoned pass guarantees
    dep = scenario(per_zone, events)
    blind = reference.place(KEYS, dep.ref.fleet, R, OMEGA)
    assert (reference_zoned.zones_spanned(blind, Z) < R).any()


def test_fallback_counts_reach_the_registry_on_read():
    dep = scenario(64, "zone_lost")
    keys = jnp.asarray(KEYS)
    for _ in range(3):
        dep.store.place_keys(keys)
    # the zone fallback moves a column whose routed zone an earlier column
    # holds, while a free alive zone is left: count those in the reference
    held = reference_zoned.place(KEYS, dep.ref, R, OMEGA)
    zone_moves = 0
    for j, salt in enumerate(family_salts(R)[1:], start=1):
        if dep.ref.alive_zones() > j:
            routed = reference.route(reference.mix32(KEYS ^ np.uint32(salt)),
                                     dep.ref.fleet, OMEGA)
            zone_moves += int(((routed % Z)[:, None] == held[:, :j] % Z)
                              .any(axis=1).sum())
    m = dep.store.metrics
    assert m.total("placement_zone_fallback_columns_total") == 3 * zone_moves
    assert m.total("placement_columns_total") == 3 * KEYS.size * R
    assert m.total("placement_shard_fallback_columns_total") > 0
    # a second read adds nothing: the drain takes deltas
    assert m.total("placement_columns_total") == 3 * KEYS.size * R


def test_fallback_counts_reach_a_registry_passed_in():
    dep = Deployment(Z * 30 + 1)
    registry = MetricsRegistry()
    store = StorePlacement(dep.router, r=R, zones=Z, metrics=registry)
    dep.lose_zone(2)
    store.place_keys(KEYS)
    assert store.metrics is registry
    text = to_prometheus(registry)
    assert f"placement_columns_total {KEYS.size * R}" in text
    assert "placement_zone_fallback_columns_total" in text


def test_fallback_counts_carry_past_32_bits():
    fresh, near = scenario(30, "zone_lost"), scenario(30, "zone_lost")
    top = (1 << 32) - 5  # a few columns short of the low word's wrap
    near.store._fallbacks = jnp.asarray([[top, top], [0, 0]], jnp.uint32)
    near.store._drained = (top, top)
    for dep in (fresh, near):
        dep.store.place_keys(KEYS)
    names = ("placement_zone_fallback_columns_total",
             "placement_shard_fallback_columns_total")
    counts = [fresh.store.metrics.total(n) for n in names]
    assert min(counts) > 5
    assert [near.store.metrics.total(n) for n in names] == counts
    assert np.asarray(near.store._fallbacks)[1].tolist() == [1, 1]


# -- minimal disruption ------------------------------------------------------


def movement_bound(n0, n1, r=R):
    """The gate of ``BENCH_placement.json``: 1.5 x (delta/n + re-salt churn)."""
    return 1.5 * (abs(n1 - n0) / max(n0, n1) + (r - 1) / min(n0, n1))


@pytest.mark.parametrize("per_zone", (30, 64, 100))
@pytest.mark.parametrize("victim", (7, 40, 86))
def test_one_failure_moves_within_the_bound(per_zone, victim):
    dep = Deployment(Z * per_zone + 1)
    dep.store.register(KEYS)
    n0 = dep.mgr.n_alive
    dep.fail(victim)
    plan = dep.store.plan_migration()
    assert plan.moved_fraction < movement_bound(n0, dep.mgr.n_alive)
    # what moved is exactly what the reference's two placements differ in
    before = Deployment(Z * per_zone + 1).ref
    old = reference_zoned.place(KEYS, before, R, OMEGA)
    new = reference_zoned.place(KEYS, dep.ref, R, OMEGA)
    assert np.array_equal(plan.old, old) and np.array_equal(plan.new, new)


# -- repair and replay run the zoned pass --------------------------------------


def test_repairer_restores_zone_spread_and_replays():
    dep = Deployment(Z * 40 + 1)
    dep.store.register(KEYS[:512])
    rep = PlacementRepairer(dep.store, dep.mgr, budget_per_tick=128)
    snap = dep.mgr.snapshot()
    dep.fail(4)
    dep.fail(10)
    assert rep.backlog > 0
    rep.quiesce()
    assert rep.backlog == 0
    holders = dep.store.holders
    assert np.array_equal(holders,
                          reference_zoned.place(KEYS[:512], dep.ref, R, OMEGA))
    assert (reference_zoned.zones_spanned(holders, Z) == R).all()
    rep.verify_placement_replay()
    rep.verify_placement_replay(snap)
    rep.verify_placement_replay(JournalSnapshot.from_json(snap.to_json()))


def test_read_reports_zone_degradation():
    dep = scenario(30, "zone_lost")
    dep.store.register(KEYS[:16])
    found, mode = dep.store.read(0)
    assert found.size == R and mode == MODE_ZONE_DEGRADED
    dep.fail(int(found[found != dep.router.domain.total_count - 1][0]))
    assert dep.store.read(0)[1] == MODE_DEGRADED


# -- the zone tables -----------------------------------------------------------


def test_zone_tables_built_late_equal_tables_kept_from_the_start():
    # built late: from a snapshot taken mid-stream plus the event tail, or
    # by a replay from genesis; kept from the start: the live fleet's
    router = BatchRouter(61, capacity=64, zones=Z)
    mgr = LifecycleManager(router, LifecycleConfig(min_alive_floor=1))
    mgr.fail(4)
    mgr.fail(9)
    snap = mgr.snapshot()
    mgr.fail(2)
    mgr.recover(9)
    mgr.fail(33)
    mgr.scale_up()
    mgr.scale_down()
    mgr.scale_down()
    kept = router.domain.zone_tables.capture()
    for since in (snap, JournalSnapshot.from_json(snap.to_json()), None):
        assert mgr.rebuild_domain(since).zone_tables.capture() == kept
        mgr.verify_replay(since)


def test_snapshot_without_zone_tables_restores_and_takes_events():
    # a snapshot in the form it had before zones: no "zones" key
    mgr = LifecycleManager(BatchRouter(40, capacity=64),
                           LifecycleConfig(min_alive_floor=1))
    mgr.fail(3)
    line = json.loads(mgr.snapshot().to_json())
    del line["zones"]
    snap = JournalSnapshot.from_json(json.dumps(line))
    mgr.fail(7)
    mgr.recover(3)
    mgr.verify_replay(snap)  # restores it, then replays the fail and recover
    domain = mgr.rebuild_domain(snap)
    domain.fail(11)
    domain.recover(11)
    assert JournalSnapshot.capture(mgr.epoch, domain).zones == ()
    # a zoned fleet's state is not in it
    zoned = LifecycleManager(BatchRouter(40, capacity=64, zones=Z),
                             LifecycleConfig(min_alive_floor=1))
    with pytest.raises(ValueError, match="zone tables"):
        zoned.rebuild_domain(snap)


def test_zoned_store_needs_a_fleet_that_keeps_its_zones():
    with pytest.raises(ValueError, match="zones=3"):
        StorePlacement(BatchRouter(40, capacity=64), r=R, zones=Z)


def test_zone_tables_split_the_slot_space_by_slot_mod_zones():
    view = ZoneTables(Z, 10)
    assert [t.n_total for t in view.tables] == [4, 3, 3]
    view.fail(7)  # zone 1, local index 2
    assert view.tables[1].slots[view.tables[1].n_alive:] == [2]
    with pytest.raises(ValueError):
        view.append(13)  # the next slot, 10, is due first


@pytest.mark.parametrize("zones", (0, 33, 65))
def test_placement_spec_bounds_zones(zones):
    with pytest.raises(ValueError, match="zones"):
        PlacementSpec(router=RouterSpec(capacity=64), zones=zones)


# -- zones=1 is the zone-free pass, program and all ----------------------------


def _route_replicas_before_zones(keys, packed, table, state, *, r, omega,
                                 n_words, max_resalt, route):
    """The placement pass as it was before zones, kept verbatim: what the
    zones=1 program must still compile to."""
    keys_u32 = keys.reshape(-1).astype(jnp.uint32)
    n_alive = state[1].astype(jnp.uint32)
    slots = table[0].astype(jnp.uint32)
    n = keys_u32.shape[0]
    fam = mix32(keys_u32 ^ family_salts(r)[:, None]).reshape(-1)
    base = route(
        fam, packed, table, state, omega=omega, n_words=n_words
    ).astype(jnp.uint32)
    used = [jnp.zeros_like(keys_u32) for _ in range(n_words)]

    def is_used(b):
        w = b >> np.uint32(5)
        word = jnp.zeros_like(b)
        for s in range(n_words):
            word = jnp.where(w == np.uint32(s), used[s], word)
        return ((word >> (b & np.uint32(31))) & np.uint32(1)) != 0

    def mark_used(b):
        w = b >> np.uint32(5)
        bit = jnp.uint32(1) << (b & np.uint32(31))
        for s in range(n_words):
            used[s] = jnp.where(w == np.uint32(s), used[s] | bit, used[s])

    cols = []
    exhausted = jnp.zeros(keys_u32.shape, bool)
    for j in range(r):
        b, fam_j = base[j * n : (j + 1) * n], fam[j * n : (j + 1) * n]
        if j > 0:
            coll = is_used(b)
            q = mulhi32(mix32(fam_j ^ RESALT_SALT), n_alive)
            for _probe in range(max_resalt):
                cand = slots.at[q].get(mode="promise_in_bounds")
                free = coll & ~is_used(cand)
                b = jnp.where(free, cand, b)
                coll = coll & ~free
                q = q + np.uint32(1)
                q = jnp.where(q >= n_alive, q - n_alive, q)
            exhausted = exhausted | (coll & (np.uint32(j) < n_alive))
        mark_used(b)
        cols.append(b)
    replicas = jnp.stack(cols, axis=-1).astype(jnp.int32)
    return replicas.reshape(*keys.shape, r), exhausted.reshape(keys.shape)


_before_zones_jit = jax.jit(
    _route_replicas_before_zones,
    static_argnames=("r", "omega", "n_words", "max_resalt", "route"),
)

#: the compiled module's debug tables name source files and lines
_DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _program_text(jitted, keys, fleet, spec) -> str:
    """Optimised HLO on the CPU, without names, metadata or debug tables."""
    text = jitted.lower(
        keys, fleet.packed, fleet.table, fleet.state, r=R, omega=spec.omega,
        n_words=spec.n_words, max_resalt=R, route=make_bulk(spec.engine).route,
    ).compile().as_text()
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"^HloModule \S+", "HloModule", text)
    return "\n\n".join(b for b in text.split("\n\n")
                       if not b.lstrip().startswith(_DEBUG_TABLES))


def test_zones_one_compiles_to_the_zone_free_program():
    router = BatchRouter(1000, capacity=1024)
    for node in (3, 77, 500):
        router.fail(node)
    keys = jax.ShapeDtypeStruct((1 << 20,), jnp.uint32)
    fleet, spec = router._fleet_host, router.spec
    assert (_program_text(_route_replicas_jit, keys, fleet, spec)
            == _program_text(_before_zones_jit, keys, fleet, spec))


@pytest.mark.parametrize("failed", ((), (3, 8, 20, 21, 40)))
def test_zones_one_places_as_the_zone_free_pass(failed):
    router = BatchRouter(64, capacity=64)
    for node in failed:
        router.fail(node)
    store = StorePlacement(router, r=R)
    assert store.spec.zones == 1 and not hasattr(store, "_fallbacks")
    spec = router.spec
    want = _before_zones_jit(
        KEYS, *jax.tree_util.tree_leaves(store._fleet_dev()), r=R,
        omega=spec.omega, n_words=spec.n_words, max_resalt=R,
        route=make_bulk(spec.engine).route,
    )
    got = store.place_keys(KEYS)
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_zone_of_slot_by_multiply_high_is_exact():
    # the pass finds a slot's zone as s - Z * mulhi32(s, ceil(2^32 / Z))
    rng = np.random.default_rng(0)
    slots = np.concatenate([
        np.arange(1 << 16), np.arange((1 << 24) - (1 << 16), 1 << 24),
        rng.integers(0, 1 << 24, 1 << 16),
    ]).astype(np.uint32)
    for zones in (2, 3, 5, 7, 31, 32):
        magic = np.uint32(-(-(1 << 32) // zones))
        zone = slots - np.uint32(zones) * np.asarray(mulhi32(slots, magic))
        assert np.array_equal(zone, slots % zones), zones
    assert GOLDEN32 == np.uint32(reference.GOLDEN)
