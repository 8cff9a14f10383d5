"""R-way replicated store placement on top of any ``BULK_ENGINES`` engine
(DESIGN.md §13).

The paper's actual use case is data placement: "distributed storage systems
rely on consistent hashing for scalable and fault-tolerant data
partitioning."  A router maps a key to exactly ONE shard, so a single
failure makes the key's data unreachable until the divert reroutes it —
and the rerouted shard does not *have* the data.  This module turns the
router into a placement system: every key lives on **R distinct alive
shards**, failures degrade reads to the surviving replica set, and
membership changes produce an explicit, bounded migration plan instead of
silent rerouting.

Three layers:

* ``route_replicas_impl`` — the device pass.  R salted key families (the
  same broadcast construction ``models/layers/moe.py`` uses for multi-K
  expert routing) go through ONE fused engine route, then a deterministic
  distinct-resolution pass breaks inter-family collisions: a per-lane used-
  shard bitmask (``n_words`` u32 words, the same select-cascade shape as
  the divert's membership test) detects a duplicate, a re-salt hash picks a
  fresh position in the table's alive prefix, and up to ``max_resalt``
  linear probes (+1 with conditional wrap — no division) settle it.  The
  default bound of ``r`` probes makes distinctness DETERMINISTIC whenever
  ``n_alive > column`` (column ``j`` probes ``j+1`` distinct alive-prefix
  positions, at most ``j`` of which are taken), so every key gets exactly
  ``min(r, n_alive)`` distinct alive shards.  While-free, affine in ``r``,
  u32-closed, zero transfers — certified as ``placement/route_replicas``.
  With ``zones > 1`` the same pass first moves a column whose shard lies
  in a zone an earlier column holds to a free alive zone, resolved over
  that zone's own replacement table (DESIGN.md §13.5), so a key's holders
  span ``min(r, alive zones)`` failure domains.

* ``StorePlacement`` — the host control plane: guarded placement with typed
  degradation (``n_alive == 0`` stays ``FleetUnavailableError``;
  ``n_alive < r`` is mode ``"degraded"`` or a ``PlacementDegradedError``
  under ``strict=True``; a too-tight explicit ``max_resalt`` surfaces as
  ``PlacementExhaustedError``, never a silent duplicate), a registry of
  placed keys with their current *holders* (where the data physically is —
  which lags the target placement until repair completes), degraded reads
  from the surviving holder set, and ``plan_migration`` — the old-vs-new
  placement diff as ONE device pass producing per-shard move lists.

* ``PlacementRepairer`` (``repro.serving.lifecycle.manager``) — the repair
  scheduler that drives holders back to the target placement in bounded-
  bandwidth batches after every journaled membership event.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.binomial_jax import GOLDEN32, hash_pair, mix32, mulhi32
from repro.core.bulk import FleetState, PlacementSpec, RouterSpec, ZoneState
from repro.core.memento_jax import table_width
from repro.kernels import ops
from repro.kernels.fused import LANES
from repro.kernels.table_read import TableRead
from repro.observability.metrics import MetricsRegistry
from repro.observability.trace import span
from repro.placement.assignment import MovementPlan
from repro.serving.lifecycle.errors import (
    MODE_DEGRADED,
    MODE_NORMAL,
    MODE_ZONE_DEGRADED,
    FleetUnavailableError,
    PlacementDegradedError,
    PlacementExhaustedError,
)

#: salt seeding the re-salt chain — distinct from every family salt so the
#: resolution probes decorrelate from the base placements they collide with
RESALT_SALT = np.uint32(0x7F4A7C15)

#: salt of the hash that picks a column's new zone — distinct from the
#: family and re-salt salts, so the zone choice decorrelates from both
ZONE_SALT = np.uint32(0x2C1B3C6D)

#: sentinel holder id: "this replica column holds no copy anywhere"
NO_HOLDER = -1


def family_salts(r: int) -> np.ndarray:
    """The ``r`` static per-replica salts — the MoE layer's per-k schedule
    ``(k * 7919 + 1) * GOLDEN32`` (``models/layers/moe.py``), so replica
    family 0 is the plain router placement."""
    base = (np.arange(r, dtype=np.uint64) * 7919 + 1).astype(np.uint32)
    return base * np.uint32(GOLDEN32)


# ---------------------------------------------------------------------------
# the device pass
# ---------------------------------------------------------------------------


def route_replicas_impl(
    keys: jax.Array,
    packed_mask: jax.Array,
    table: jax.Array,
    state: jax.Array,
    zone_table: jax.Array | None = None,
    zone_state: jax.Array | None = None,
    *,
    r: int,
    omega: int,
    n_words: int,
    max_resalt: int,
    route,
    zones: int = 1,
    zone_width: int = 0,
    read: TableRead = TableRead(),
) -> tuple:
    """Place every key on ``r`` distinct alive shards — ONE traced pass.

    keys         (N,) u32 key space (any int dtype; truncated like the
                 scalar oracle)
    packed_mask / table / state — the ``FleetState`` leaves (operand
                 contract of the fused engines; ``n_alive >= 1`` is the
                 caller-guarded precondition, as for ``route_bulk``)
    zone_table / zone_state — the ``ZoneState`` leaves; read only when
                 ``zones > 1``
    r            replication factor (static)
    max_resalt   static probe bound per column (``PlacementSpec``
                 resolves ``None`` to ``r``, the distinctness guarantee)
    route        the engine's fused jnp route
                 ``(keys, packed, table, state, omega=, n_words=)``
    zones        static zone count (slot ``s`` in zone ``s mod zones``);
                 1 traces the zone-free pass and nothing of the zones
    zone_width   static zone-table entries per zone (``PlacementSpec``)
    read         how the pass reads its slots and zone tables at every lane
                 (``TableRead``: the XLA gather, the default, or the VMEM
                 kernel); the answer is the same either way

    Returns ``(replicas, exhausted)``: ``replicas`` is ``(N, r)`` int32,
    every entry an ALIVE shard; column ``j`` is distinct from columns
    ``< j`` whenever ``n_alive > j`` and the probe bound sufficed, and a
    duplicate of an earlier column otherwise (degraded replication — the
    fleet is smaller than ``j+1``).  ``exhausted`` is ``(N,)`` bool, set
    for keys where distinctness was achievable (``n_alive > j``) but
    ``max_resalt`` probes ran out — impossible at the default bound.
    With ``zones > 1`` a third output, ``(2,)`` u32, counts the columns
    the zone fallback moved and the columns the shard re-salt handled.

    The zone fallback (DESIGN.md §13.5): column ``j >= 1`` whose routed
    shard lies in a zone an earlier column already uses, while more than
    ``j`` zones are alive, moves to the k-th free alive zone in zone order
    (``k = mulhi32(mix32(fam ^ ZONE_SALT), alive_zones - j)``), and to a
    shard there by the table divert's two redirects over that zone's own
    table.  With ``j`` or fewer zones alive the column keeps its shard and
    the re-salt below makes it distinct.

    The whole pass is one fused-route call (eqn count independent of
    ``r`` — all families route as one broadcast batch) plus O(r * (n_words
    + max_resalt + zones)) elementwise resolution ops: while-free and
    affine in ``r`` at a fixed probe bound, which is exactly what the
    certifier's ``placement/route_replicas`` targets pin.
    """
    keys_u32 = keys.reshape(-1).astype(jnp.uint32)
    n_alive = state[1].astype(jnp.uint32)
    slots = table[0].astype(jnp.uint32)

    # all r salted families through the fused engine as ONE broadcast batch,
    # family-major: column j is the flat run [j * n, (j + 1) * n), so each
    # column keeps the keys' own 1-D layout
    n = keys_u32.shape[0]
    fam = mix32(keys_u32 ^ family_salts(r)[:, None]).reshape(-1)  # (r * N,)
    base = route(
        fam, packed_mask, table, state, omega=omega, n_words=n_words
    ).astype(jnp.uint32)

    # per-lane used-shard bitmask: n_words u32 words, set/tested via the
    # same select cascade the divert uses for the removed mask
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

    zoned = zones > 1
    if zoned:
        z_slots = zone_table.reshape(-1).astype(jnp.uint32)
        z_total = zone_state[0].astype(jnp.uint32)
        z_alive = zone_state[1].astype(jnp.uint32)
        zone_up = [z_alive[z] > np.uint32(0) for z in range(zones)]
        n_zones_alive = sum(up.astype(jnp.uint32) for up in zone_up)
        # zone of a slot, s mod zones, by a multiply-high with
        # ceil(2^32 / zones): exact for every s < 2^32 / zones
        magic = np.uint32(-(-(1 << 32) // zones))

        def zone_of(b):
            return b - np.uint32(zones) * mulhi32(b, magic)

        used_zones = jnp.zeros_like(keys_u32)  # bit z: zone z holds a column
        moved_zone = jnp.uint32(0)
        moved_shard = jnp.uint32(0)

    cols = []
    exhausted = jnp.zeros(keys_u32.shape, bool)
    for j in range(r):
        b, fam_j = base[j * n : (j + 1) * n], fam[j * n : (j + 1) * n]
        if j > 0:
            if zoned and j < zones:
                # j columns hold j distinct zones; a free alive zone exists
                # iff more than j zones are alive
                free_left = n_zones_alive > np.uint32(j)
                move = free_left & (((used_zones >> zone_of(b)) & 1) != 0)
                n_free = jnp.where(free_left, n_zones_alive - np.uint32(j),
                                   np.uint32(0))
                k = mulhi32(mix32(fam_j ^ ZONE_SALT), n_free)
                # the k-th free alive zone in zone order, with its counts
                target = jnp.zeros_like(b)
                z_n = jnp.zeros_like(b)
                z_a = jnp.zeros_like(b)
                seen = jnp.zeros_like(b)
                for z in range(zones):
                    free = zone_up[z] & (((used_zones >> np.uint32(z)) & 1) == 0)
                    pick = free & (seen == k)
                    target = jnp.where(pick, np.uint32(z), target)
                    z_n = jnp.where(pick, z_total[z], z_n)
                    z_a = jnp.where(pick, z_alive[z], z_a)
                    seen = seen + free.astype(jnp.uint32)
                # the table divert's two redirects over that zone's table
                h = hash_pair(fam_j, target)
                q = mulhi32(h, z_n)
                q = jnp.where(q >= z_a, mulhi32(mix32(h ^ (q * GOLDEN32)), z_a), q)
                cand = read(z_slots, target * np.uint32(zone_width) + q)
                b = jnp.where(move, cand, b)
                moved_zone = moved_zone + jnp.sum(move, dtype=jnp.uint32)
            coll = is_used(b)
            if zoned:
                moved_shard = moved_shard + jnp.sum(coll, dtype=jnp.uint32)
            # re-salt into the alive-prefix POSITION space (every position
            # < n_alive holds an alive shard by the table's construction),
            # then probe linearly with a conditional-subtract wrap: the
            # probes visit min(max_resalt, n_alive) DISTINCT positions, of
            # which at most j are taken, so max_resalt >= j+1 guarantees a
            # distinct alive shard whenever n_alive > j
            q = mulhi32(mix32(fam_j ^ RESALT_SALT), n_alive)
            for _probe in range(max_resalt):
                cand = read(slots, q)
                free = coll & ~is_used(cand)
                b = jnp.where(free, cand, b)
                coll = coll & ~free
                q = q + np.uint32(1)
                q = jnp.where(q >= n_alive, q - n_alive, q)
            # n_alive <= j: a duplicate is the DEFINED degraded answer
            # (j+1 distinct shards cannot exist), not an exhaustion
            exhausted = exhausted | (coll & (np.uint32(j) < n_alive))
        mark_used(b)
        if zoned:
            used_zones = used_zones | (jnp.uint32(1) << zone_of(b))
        cols.append(b)

    replicas = jnp.stack(cols, axis=-1).astype(jnp.int32)
    out = replicas.reshape(*keys.shape, r), exhausted.reshape(keys.shape)
    if zoned:
        return *out, jnp.stack([moved_zone, moved_shard])
    return out


@functools.partial(
    jax.jit, static_argnames=("r", "omega", "n_words", "max_resalt", "route",
                              "zones", "zone_width", "read")
)
def _route_replicas_jit(keys, packed, table, state, zone=None, counts=None, *,
                        r, omega, n_words, max_resalt, route, zones=1,
                        zone_width=0, read=TableRead()):
    if zones == 1:
        return route_replicas_impl(
            keys, packed, table, state, r=r, omega=omega, n_words=n_words,
            max_resalt=max_resalt, route=route, read=read,
        )
    # the fallback counts ride across calls in a device accumulator of
    # (low, high) u32 words, the carry taken on the device
    replicas, exhausted, moved = route_replicas_impl(
        keys, packed, table, state, zone.table, zone.state, r=r, omega=omega,
        n_words=n_words, max_resalt=max_resalt, route=route, zones=zones,
        zone_width=zone_width, read=read,
    )
    lo = counts[0] + moved
    hi = counts[1] + (lo < moved).astype(jnp.uint32)
    return replicas, exhausted, jnp.stack([lo, hi])


def placement_diff_impl(
    keys, old_fleet: FleetState, new_fleet: FleetState,
    old_zone: ZoneState | None = None, new_zone: ZoneState | None = None,
    *, r, omega, n_words, max_resalt, route, zones=1, zone_width=0,
    read=TableRead(),
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Old-vs-new placement diff — the bulk migration plan, ONE traced pass.

    Routes the keys under BOTH fleet states (and, with ``zones > 1``, both
    zone states) and marks every (key, column) pair whose new shard holds
    no copy under the old placement: ``moved[i, j] = new[i, j] not in
    old[i, :]`` — membership, not positional inequality, because a replica
    that merely swapped columns needs no data transfer.  Returns ``(old,
    new, moved, exhausted_new)``.
    """
    def place(fleet, zone):
        zone_ops = () if zones == 1 else (zone.table, zone.state)
        return route_replicas_impl(
            keys, fleet.packed, fleet.table, fleet.state, *zone_ops, r=r,
            omega=omega, n_words=n_words, max_resalt=max_resalt, route=route,
            zones=zones, zone_width=zone_width, read=read,
        )

    old = place(old_fleet, old_zone)[0]
    new, exhausted = place(new_fleet, new_zone)[:2]
    moved = jnp.ones(new.shape, bool)
    for k in range(r):
        moved = moved & (new != old[..., k : k + 1])
    return old, new, moved, exhausted


_placement_diff_jit = jax.jit(
    placement_diff_impl,
    static_argnames=("r", "omega", "n_words", "max_resalt", "route", "zones",
                     "zone_width", "read"),
)


def table_reads(pspec: PlacementSpec) -> dict[str, int]:
    """The small-table reads one placement pass makes, by ``TableRead``
    path (``"vmem"`` or ``"gather"``): ``(r - 1) * max_resalt`` probes of
    the slots table, and with zones ``min(r, zones) - 1`` zone-table reads.
    Static, so the host counts them without reading the device."""
    read = TableRead.for_spec(pspec.router)
    reads: dict[str, int] = {}
    for width, n in (
        (table_width(pspec.router.capacity),
         (pspec.r - 1) * pspec.resolved_max_resalt),
        (table_width(pspec.zones * pspec.zone_width),
         min(pspec.r, pspec.zones) - 1),
    ):
        if n > 0:
            path = read.path(width)
            reads[path] = reads.get(path, 0) + n
    return reads


# ---------------------------------------------------------------------------
# host plans
# ---------------------------------------------------------------------------


class PlacedBatch(NamedTuple):
    """A placed key batch + the epoch/mode it was computed under (the
    placement tier's mirror of the lifecycle ``RoutedBatch``)."""

    replicas: object  #: (N, r) int32 alive shard ids, distinct per row up
    #: to min(r, n_alive)
    epoch: int
    mode: str  #: MODE_NORMAL; MODE_DEGRADED when n_alive < r; or
    #: MODE_ZONE_DEGRADED when fewer zones are alive than min(r, zones)
    n_distinct: int  #: min(r, n_alive) at placement time


@dataclasses.dataclass
class MigrationPlan:
    """The materialised old-vs-new placement diff of one membership change.

    keys   (M,) u32; old/new (M, r) int32 placements; moved (M, r) bool —
    True where ``new[i, j]`` holds no copy under ``old[i, :]`` (a genuine
    data transfer, computed device-side by ``placement_diff_impl``).
    """

    keys: np.ndarray
    old: np.ndarray
    new: np.ndarray
    moved: np.ndarray
    epoch: int = 0

    @property
    def total_pairs(self) -> int:
        return int(self.moved.size)

    @property
    def moved_pairs(self) -> int:
        return int(self.moved.sum())

    @property
    def moved_fraction(self) -> float:
        return self.moved_pairs / max(self.total_pairs, 1)

    def per_shard_moves(self) -> dict[int, list[tuple[int, int]]]:
        """Destination shard -> [(key, source shard)] move lists — the
        worker-facing transfer schedule.  The source is the same-column old
        holder (a shard that had a copy under the old placement; the
        repairer re-picks a *reachable* source at execution time)."""
        out: dict[int, list[tuple[int, int]]] = {}
        for i, j in zip(*np.nonzero(self.moved)):
            out.setdefault(int(self.new[i, j]), []).append(
                (int(self.keys[i]), int(self.old[i, j]))
            )
        return out

    def as_movement_plan(self) -> MovementPlan:
        """The host ``MovementPlan`` view over the device diff (one source
        of truth for movement accounting — ``moved_fraction`` here counts
        transfer pairs, not positional changes)."""
        r = self.new.shape[1]
        return MovementPlan.from_diff(
            np.repeat(self.keys, r),
            self.old.reshape(-1),
            self.new.reshape(-1),
            moved=self.moved.reshape(-1),
        )


# ---------------------------------------------------------------------------
# the placement control plane
# ---------------------------------------------------------------------------


class StorePlacement:
    """R-way replicated placement over a ``BatchRouter``'s fleet.

    Wraps (composition, like ``LifecycleManager``) any router exposing the
    fleet surface — ``spec``, ``domain``, ``_fleet_host``/``_fleet_dev``,
    ``routing_epoch`` — and adds the placement tier: guarded R-way
    ``place``, a registry of placed keys with their physical *holders*
    (which lag the target placement until repair completes), degraded
    reads, and the one-device-pass migration diff.
    """

    def __init__(self, router, r: int = 3, *, max_resalt: int | None = None,
                 zones: int = 1, strict: bool = False,
                 metrics: MetricsRegistry | None = None):
        self.router = router
        self.spec = PlacementSpec(
            router=router.spec, r=r, max_resalt=max_resalt, zones=zones
        )
        if zones > 1 and router.domain.zones != zones:
            raise ValueError(
                f"StorePlacement(zones={zones}) needs a fleet that keeps "
                f"{zones} zones from genesis: build the router with "
                f"zones={zones} (it keeps {router.domain.zones})"
            )
        #: strict=True turns an n_alive < r placement into a typed
        #: PlacementDegradedError instead of a degraded-mode batch
        self.strict = strict
        self._keys = np.zeros((0,), np.uint32)
        self._holders = np.zeros((0, r), np.int64)
        #: the registry the zone-fallback counters reach, synced from the
        #: device when it is read
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        #: the table reads each dispatch makes, as (counter, count) by path
        self._reads = [
            (self.metrics.counter("placement_table_reads_total", path=path), n)
            for path, n in table_reads(self.spec).items()
        ]
        self._n_reads = sum(n for _, n in self._reads)
        if zones > 1:
            #: (routing epoch, device ZoneState) last uploaded
            self._zone_dev: tuple[int, ZoneState] | None = None
            #: device accumulator of the (zone-fallback, shard-fallback)
            #: column counts across calls: low words, then high words
            self._fallbacks = jnp.zeros((2, 2), jnp.uint32)
            self._drained = (0, 0)
            self._columns = 0
            self.metrics.add_collector(self._drain_counts)
        #: fleet (and zone) snapshot the registered holders were last synced
        #: against — the implicit "old" side of plan_migration()
        self._synced = self._snapshot()

    # -- fleet state access --------------------------------------------------
    @property
    def r(self) -> int:
        return self.spec.r

    @property
    def epoch(self) -> int:
        return self.router.routing_epoch

    @property
    def n_alive(self) -> int:
        return self.router.domain.alive_count

    @property
    def alive_zones(self) -> int:
        """Zones with an alive shard."""
        return self.router.domain.zone_tables.alive_zones

    def _zone_host(self) -> ZoneState:
        return ZoneState.pack(
            self.router.domain.zone_tables, self.router.spec.capacity
        )

    def _snapshot(self) -> tuple[FleetState, ZoneState | None]:
        h = self.router._fleet_host
        fleet = FleetState(
            h.packed.copy(), h.table.copy(), h.state.copy(), h.capacity
        )
        return fleet, (None if self.spec.zones == 1 else self._zone_host())

    def _fleet_dev(self) -> FleetState:
        """The router's pinned device twin (flushing coalesced events)."""
        self.router._check_routable()
        return self.router._fleet_dev

    def _zone_state_dev(self) -> ZoneState:
        """The zone tables' device twin, re-pinned after fleet events (the
        host tables moved O(1) per event; one upload per changed epoch)."""
        epoch = self.router.routing_epoch
        if self._zone_dev is None or self._zone_dev[0] != epoch:
            self._zone_dev = (epoch, jax.device_put(self._zone_host()))
        return self._zone_dev[1]

    def _drain_counts(self) -> None:
        """Sync the device fallback counts into the registry's counters —
        run when the registry is read, never per call.  The device counts
        are 64-bit (a carry into the high word), so no read cadence loses
        a count."""
        lo, hi = np.asarray(self._fallbacks).astype(np.uint64)
        now = [int(c) for c in (hi << np.uint64(32)) | lo]
        zone, shard = (a - b for a, b in zip(now, self._drained))
        self._drained = tuple(now)
        m = self.metrics
        m.counter("placement_zone_fallback_columns_total").inc(zone)
        m.counter("placement_shard_fallback_columns_total").inc(shard)
        m.counter("placement_columns_total").inc(self._columns)
        self._columns = 0

    def _alive_mask(self) -> np.ndarray:
        """(capacity,) bool — slot id alive right now."""
        dom = self.router.domain
        alive = np.zeros(self.router.spec.capacity, bool)
        alive[: dom.total_count] = True
        for s in dom.removed:
            alive[s] = False
        return alive

    # -- guarded placement ---------------------------------------------------
    def _guard(self) -> str:
        n = self.n_alive
        if n == 0:
            raise FleetUnavailableError(epoch=self.epoch)
        if n < self.spec.r:
            if self.strict:
                raise PlacementDegradedError(n, self.spec.r, epoch=self.epoch)
            return MODE_DEGRADED
        return self._zone_mode()

    def _zone_mode(self) -> str:
        """MODE_ZONE_DEGRADED while fewer zones are alive than a key's
        holders should span, ``min(r, zones)``; else MODE_NORMAL."""
        zones = self.spec.zones
        if zones > 1 and self.alive_zones < min(self.spec.r, zones):
            return MODE_ZONE_DEGRADED
        return MODE_NORMAL

    def place_keys(self, keys) -> tuple[jax.Array, jax.Array]:
        """Raw device placement: ``(replicas (N, r) i32, exhausted (N,)
        bool)``, no degradation typing (the expert path; ``place`` wraps
        it).  Routability (``n_alive >= 1``) is still enforced.  With zones,
        the same dispatch advances the fallback counts on the device."""
        with span("route.call"):
            fleet = self._fleet_dev()
            keys_u32 = self.router._coerce_keys(keys)
            size = int(np.size(keys_u32))
            for counter, n in self._reads:
                counter.inc(n)
            if self.spec.zones == 1:
                with span("route.launch") as s:
                    if s:
                        s.tag(rows=-(-size // LANES), reads=self._n_reads)
                    return ops.route_replicas_bulk(keys_u32, fleet, self.spec)
            zone = self._zone_state_dev()
            with span("route.launch") as s:
                if s:
                    s.tag(rows=-(-size // LANES), reads=self._n_reads,
                          zones=self.spec.zones, alive_zones=self.alive_zones)
                replicas, exhausted, self._fallbacks = ops.route_replicas_bulk(
                    keys_u32, fleet, self.spec, zone, self._fallbacks
                )
            self._columns += size * self.spec.r
            return replicas, exhausted

    def place(self, keys) -> PlacedBatch:
        """Place keys on ``r`` distinct alive shards, typed and epoch-
        stamped: ``FleetUnavailableError`` at ``n_alive == 0``; fewer alive
        shards than ``r`` degrades (every key on all ``n_alive`` distinct
        shards) or raises under ``strict=True``; an exhausted re-salt chain
        (explicit ``max_resalt`` below the default only) raises
        ``PlacementExhaustedError``."""
        mode = self._guard()
        replicas, exhausted = self.place_keys(keys)
        exhausted = np.asarray(exhausted)
        if exhausted.any():
            raise PlacementExhaustedError(
                int(exhausted.sum()), self.spec.resolved_max_resalt,
                epoch=self.epoch,
            )
        return PlacedBatch(
            np.asarray(replicas), self.epoch, mode,
            min(self.spec.r, self.n_alive),
        )

    # -- the registered store ------------------------------------------------
    @property
    def keys(self) -> np.ndarray:
        """(M,) u32 registered keys."""
        return self._keys

    @property
    def holders(self) -> np.ndarray:
        """(M, r) int64 physical holders per registered key — where copies
        actually are, which lags the target placement until repair
        completes.  ``NO_HOLDER`` marks a column with no copy anywhere."""
        return self._holders

    def register(self, keys) -> PlacedBatch:
        """Place new keys and record them as stored: their holders start at
        the current target placement (writes go to the placement)."""
        batch = self.place(keys)
        keys_u32 = np.asarray(
            np.ascontiguousarray(keys, dtype=np.uint64).astype(np.uint32)
        ).reshape(-1)
        self._keys = np.concatenate([self._keys, keys_u32])
        self._holders = np.concatenate(
            [self._holders, np.asarray(batch.replicas, np.int64)], axis=0
        )
        self._synced = self._snapshot()
        return batch

    def reachable_mask(self) -> np.ndarray:
        """(M, r) bool — holder column is a DISTINCT, alive copy (duplicate
        holder entries count once; dead/retired/lost columns are False)."""
        alive = self._alive_mask()
        h = self._holders
        valid = (h >= 0) & (h < alive.size)
        live = np.zeros(h.shape, bool)
        live[valid] = alive[h[valid]]
        # first-occurrence filter: a duplicated shard id is one copy
        first = np.ones(h.shape, bool)
        for j in range(1, h.shape[1]):
            for k in range(j):
                first[:, j] &= h[:, j] != h[:, k]
        return live & first

    def reachable_counts(self) -> np.ndarray:
        """(M,) distinct alive copies per registered key — the durability
        metric the chaos harness asserts on (>= 1 while ``n_alive >= 1``;
        == min(r, n_alive) once repair quiesces)."""
        return self.reachable_mask().sum(axis=1).astype(np.int64)

    def read(self, key_index: int) -> tuple[np.ndarray, str]:
        """Degraded read: the distinct alive holders of one registered key,
        plus the mode they represent.  ``FleetUnavailableError`` when no
        copy is reachable (fleet empty, or — durability lost — every
        holder dead)."""
        if self.n_alive == 0:
            raise FleetUnavailableError(epoch=self.epoch)
        mask = self.reachable_mask()[key_index]
        found = self._holders[key_index][mask]
        if found.size == 0:
            raise FleetUnavailableError(
                f"key {int(self._keys[key_index])} has no reachable replica "
                f"(all holders failed)", epoch=self.epoch,
            )
        mode = self._zone_mode() if found.size >= min(self.spec.r, self.n_alive) \
            else MODE_DEGRADED
        return found.astype(np.int64), mode

    # -- migration + repair enumeration --------------------------------------
    def plan_migration(self) -> MigrationPlan:
        """Diff the registered keys' placement between the snapshot captured
        at the last register/sync and the CURRENT fleet — ONE device pass
        over both placements (DESIGN.md §13)."""
        old, old_zone = self._synced
        new = self._fleet_dev()
        new_zone = None if self.spec.zones == 1 else self._zone_state_dev()
        keys_u32 = self._keys
        o, n, moved, _ = ops.placement_diff_bulk(
            keys_u32, old, new, self.spec, old_zone, new_zone
        )
        return MigrationPlan(
            keys=keys_u32,
            old=np.asarray(o),
            new=np.asarray(n),
            moved=np.asarray(moved),
            epoch=self.epoch,
        )

    def sync_targets(self) -> list[tuple[int, int, int]]:
        """Recompute the target placement under the current fleet, realign
        the holder rows to it, and return the genuinely missing
        ``(key_index, column, dst_shard)`` repair triples.

        Realignment is pure bookkeeping: a holder whose shard appears in
        the target row moves to that column; surviving *stale* copies (old
        shards no longer in the target) keep occupying the to-be-repaired
        columns so degraded reads still reach them until the repair copy
        overwrites the slot.  Retired slot ids (``>= n_total``: LIFO
        scale-down wiped them) are invalidated to ``NO_HOLDER`` first.
        """
        if self._keys.size == 0 or self.n_alive == 0:
            return []
        replicas, _ = self.place_keys(self._keys)
        target = np.asarray(replicas, np.int64)
        total = self.router.domain.total_count
        h = self._holders
        h[h >= total] = NO_HOLDER
        needed: list[tuple[int, int, int]] = []
        r = self.spec.r
        for i in range(h.shape[0]):
            remaining = list(h[i])
            aligned: list[int | None] = [None] * r
            for j in range(r):
                t = int(target[i, j])
                if t in remaining:
                    remaining.remove(t)
                    aligned[j] = t
            missing = [j for j in range(r) if aligned[j] is None]
            for j, stale in zip(missing, remaining):
                aligned[j] = int(stale)
            for j in missing:
                needed.append((i, j, int(target[i, j])))
            h[i] = aligned
        self._synced = self._snapshot()
        return needed

    def repair_source(self, key_index: int) -> int:
        """A reachable copy to repair from: the first distinct alive holder
        of the key, or ``NO_HOLDER`` if durability is already lost."""
        mask = self.reachable_mask()[key_index]
        found = self._holders[key_index][mask]
        return int(found[0]) if found.size else NO_HOLDER

    def complete_repair(self, key_index: int, column: int, dst: int) -> None:
        """Record one finished repair copy: the column now holds ``dst``
        (any stale copy previously occupying it is garbage-collected)."""
        self._holders[key_index, column] = dst
