"""LifecycleManager: journal + detector + degradation around a BatchRouter.

The robustness layer of the serving tier (DESIGN.md §12).  Composition, not
inheritance: the manager *wraps* a ``BatchRouter`` (anything with the fleet
-event + route surface works) and adds

* **journaling** — every membership event that flows through the manager is
  epoch-stamped into a ``MembershipJournal``; ``snapshot()`` +
  ``verify_replay()`` prove the live control plane and the device operands
  are reproducible from the log (crash recovery = restore + tail replay);
* **failure detection** — replica heartbeats feed a deadline
  ``FailureDetector``; ``tick()`` turns deadline expiries into coalesced
  fail/recover events before the next dispatch;
* **coalescing** — a storm of N events becomes ONE device-state upload
  (``BatchRouter.coalesced_events``), with the final routing bit-exact
  against per-event application (the device operands are a pure function of
  the final control-plane state);
* **degradation** — typed route-time answers: ``FleetUnavailableError`` at
  ``n_alive == 0`` always; below ``min_alive_floor`` either a
  ``FleetDegradedError`` (``strict_floor=True``) or a routed batch marked
  ``mode="degraded"``;
* **epochs** — every routed batch carries the routing epoch it was computed
  under, so callers can detect placements staled by later events.

Everything here is host-side control plane: the device hot path is the same
single fused dispatch ``BatchRouter`` always ran (the constant-time
certifier pins this — ``repro.analysis`` certifies the lifecycle-wrapped
route entry too).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

from repro.observability.trace import SPAN_LIFECYCLE_TICK, span
from repro.serving.lifecycle.detector import (
    FailureDetector,
    HeartbeatConfig,
    MonotonicClock,
)
from repro.serving.lifecycle.errors import (
    MODE_DEGRADED,
    MODE_NORMAL,
    MODE_UNAVAILABLE,
    FleetDegradedError,
    FleetUnavailableError,
)
from repro.serving.lifecycle.journal import (
    JournalSnapshot,
    MembershipJournal,
    replay,
    restore,
)


@dataclasses.dataclass(frozen=True)
class LifecycleConfig:
    #: below this many alive replicas the fleet counts as degraded
    min_alive_floor: int = 1
    #: True: routing below the floor raises FleetDegradedError; False (the
    #: default): routing proceeds, the result is marked mode="degraded"
    strict_floor: bool = False
    heartbeat: HeartbeatConfig = dataclasses.field(default_factory=HeartbeatConfig)

    def __post_init__(self):
        if self.min_alive_floor < 1:
            raise ValueError(
                f"min_alive_floor must be >= 1, got {self.min_alive_floor}"
            )


class RoutedBatch(NamedTuple):
    """A routed batch + the epoch/mode it was computed under."""

    replicas: object  # jax.Array / np.ndarray of int32 replica ids
    epoch: int
    mode: str


class LifecycleManager:
    def __init__(
        self,
        router,
        config: LifecycleConfig | None = None,
        clock=None,
        tracer=None,
    ):
        self.router = router
        self.config = config or LifecycleConfig()
        self.clock = clock or MonotonicClock()
        #: optional SpanTrace — each tick() records a ``lifecycle_tick``
        #: span, measured on this manager's clock (the streaming front end
        #: attaches its shared trace here)
        self.tracer = tracer
        #: attached PlacementRepairer (None = no placement tier); every
        #: journaled membership mutation re-syncs it
        self._placement: "PlacementRepairer | None" = None
        self.journal = MembershipJournal(router.domain.total_count)
        self.detector = FailureDetector(
            (s for s in range(router.domain.total_count)
             if s not in router.domain.removed),
            self.config.heartbeat,
            self.clock,
        )
        # journal epochs continue from the router's own event counter so the
        # per-batch epoch is consistent whether events arrive via the
        # manager or (pre-attach) via the router directly
        if router.routing_epoch != 0:
            raise ValueError(
                "attach the LifecycleManager before mutating the fleet: the "
                f"router has already seen {router.routing_epoch} event(s) "
                "the journal cannot replay"
            )

    # -- health --------------------------------------------------------------
    @property
    def n_alive(self) -> int:
        return self.router.domain.alive_count

    @property
    def mode(self) -> str:
        n = self.n_alive
        if n == 0:
            return MODE_UNAVAILABLE
        if n < self.config.min_alive_floor:
            return MODE_DEGRADED
        return MODE_NORMAL

    @property
    def epoch(self) -> int:
        return self.journal.epoch

    # -- heartbeat plane -----------------------------------------------------
    def heartbeat(self, slot: int) -> None:
        self.detector.heartbeat(slot)

    def tick(self) -> list:
        """Poll the detector; apply any expiries as ONE coalesced update.

        Call once per dispatch (the serving tier does) — a whole failure
        storm between two batches lands as a single device-state upload.
        With a placement tier attached, each tick also emits ONE bounded
        repair batch (the repairer's budget), so re-replication bandwidth
        is metered by the dispatch cadence.
        """
        with span(SPAN_LIFECYCLE_TICK, self.tracer, self._now_us) as s:
            with span("detector.poll"):
                transitions = self.detector.poll()
            events = self.apply(transitions)
            if self._placement is not None:
                self._placement.tick()
            if s:
                s.tag(events=len(events), epoch=self.epoch)
        return events

    def _now_us(self) -> int:
        return int(self.clock.now() * 1_000_000)

    # -- membership events (all journaled) -----------------------------------
    def apply(self, transitions) -> list:
        """Apply ``("fail"|"recover", slot)`` pairs under one coalesced
        device update; journal each.  Returns the recorded events."""
        recorded = []
        if not transitions:
            return recorded
        with self.router.coalesced_events():
            for kind, slot in transitions:
                if kind == "fail":
                    self.router.fail(slot)
                elif kind == "recover":
                    self.router.recover(slot)
                else:
                    raise ValueError(f"unknown transition kind {kind!r}")
                recorded.append(self.journal.record(kind, slot))
        self._forget_retired()
        self._sync_placement()
        return recorded

    def _sync_placement(self) -> None:
        """Membership changed: re-enumerate the placement repair backlog."""
        if self._placement is not None:
            self._placement.sync()

    def _forget_retired(self) -> None:
        """Drop detector tracks for slots the control plane retired (failing
        the top slot is a LIFO retirement that may GC tombstones too)."""
        total = self.router.domain.total_count
        for slot in self.detector.slots:
            if slot >= total:
                self.detector.forget(slot)

    def fail(self, slot: int) -> None:
        """Operator-initiated failure (journaled; detector aligned)."""
        self.router.fail(slot)
        self.journal.record("fail", slot)
        if slot in self.router.domain.removed:
            self.detector.mark_removed(slot)
        self._forget_retired()
        self._sync_placement()

    def recover(self, slot: int) -> None:
        """Operator-initiated recovery (journaled; detector re-admits)."""
        self.router.recover(slot)
        self.journal.record("recover", slot)
        self.detector.register(slot)
        self._sync_placement()

    def scale_up(self) -> int:
        new = self.router.scale_up()
        self.journal.record("scale_up", new)
        self.detector.register(new)
        self._sync_placement()
        return new

    def scale_down(self) -> int:
        gone = self.router.scale_down()
        self.journal.record("scale_down", gone)
        # the retirement may have garbage-collected tombstones off the end
        for slot in self.detector.slots:
            if slot >= self.router.domain.total_count:
                self.detector.forget(slot)
        self._sync_placement()
        return gone

    # -- routing (degradation-guarded, epoch-stamped) ------------------------
    def _guard(self) -> str:
        mode = self.mode
        if mode == MODE_UNAVAILABLE:
            raise FleetUnavailableError(epoch=self.epoch)
        if mode == MODE_DEGRADED and self.config.strict_floor:
            raise FleetDegradedError(
                self.n_alive, self.config.min_alive_floor, epoch=self.epoch
            )
        return mode

    def route_keys(self, keys) -> RoutedBatch:
        mode = self._guard()
        return RoutedBatch(self.router.route_keys(keys), self.epoch, mode)

    def route_keys_np(self, keys) -> RoutedBatch:
        mode = self._guard()
        return RoutedBatch(self.router.route_keys_np(keys), self.epoch, mode)

    def route_batch(self, session_ids) -> RoutedBatch:
        mode = self._guard()
        return RoutedBatch(self.router.route_batch(session_ids), self.epoch, mode)

    # -- crash recovery ------------------------------------------------------
    def _domain_factory(self, n: int):
        """Build a domain EXACTLY like the router's control plane builds
        its oracle — same engine flavour, omega and resolution."""
        from repro.placement.elastic import FailureDomain

        return FailureDomain(
            n,
            engine=self.router._bulk.scalar_engine,
            chain_bits=32,
            omega=self.router.spec.omega,
            resolve="table",
            allow_empty=True,
            zones=self.router.domain.zones,
        )

    def snapshot(self) -> JournalSnapshot:
        return JournalSnapshot.capture(self.epoch, self.router.domain)

    def rebuild_domain(self, snapshot: JournalSnapshot | None = None):
        """Rebuild the control plane from the log (and optional snapshot)."""
        if snapshot is None:
            return replay(self.journal, self._domain_factory)
        return restore(
            snapshot, self._domain_factory, self.journal.events(since=snapshot.epoch)
        )

    def verify_replay(self, snapshot: JournalSnapshot | None = None) -> None:
        """Assert replay(journal) == live state, bit-exactly — the scalar
        control plane AND the packed device operands.  Raises on mismatch."""
        import numpy as np

        from repro.core.bulk import FleetState

        rebuilt = self.rebuild_domain(snapshot)
        live = self.router.domain
        if rebuilt.total_count != live.total_count:
            raise AssertionError(
                f"replay n_total {rebuilt.total_count} != live {live.total_count}"
            )
        if rebuilt.removed != live.removed:
            raise AssertionError(
                f"replay removed {sorted(rebuilt.removed)} != live "
                f"{sorted(live.removed)}"
            )
        rt_new, rt_live = rebuilt.replacement_table, live.replacement_table
        if (
            rt_new.slots != rt_live.slots
            or rt_new.pos != rt_live.pos
            or rt_new.n_alive != rt_live.n_alive
        ):
            raise AssertionError("replayed ReplacementTable differs from live")
        zones_new, zones_live = rebuilt.zone_tables, live.zone_tables
        if (zones_new is None) != (zones_live is None) or (
            zones_live is not None and zones_new.capture() != zones_live.capture()
        ):
            raise AssertionError("replayed zone tables differ from live")
        packed = FleetState.pack(rebuilt, self.router.spec.capacity)
        host = self.router._fleet_host
        for leaf in ("packed", "table", "state"):
            if not np.array_equal(getattr(packed, leaf), getattr(host, leaf)):
                raise AssertionError(
                    f"replayed device operand {leaf!r} differs from live"
                )


# ---------------------------------------------------------------------------
# placement repair scheduling (DESIGN.md §13)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RepairTask:
    """One executed repair copy: ``key``'s replica column ``column`` was
    re-materialised on ``dst`` from the reachable copy on ``src``.
    ``epoch`` is the journal epoch the under-replication was first
    observed at (the oldest-first scheduling key)."""

    key_index: int
    key: int
    column: int
    dst: int
    src: int
    epoch: int


class PlacementRepairer:
    """Bounded-bandwidth repair scheduler: drives a ``StorePlacement``'s
    holders back to the target placement after membership events.

    Attaches to a ``LifecycleManager`` (same fleet as the store's router):
    every journaled membership mutation triggers ``sync()`` — one device
    pass re-enumerating the under-replicated ``(key, column)`` pairs, each
    stamped with the journal epoch it was FIRST observed at — and each
    ``tick()`` emits at most ``budget_per_tick`` repair copies, oldest
    epoch first.  Crash recovery needs no repair journal of its own: the
    target placement is a pure function of the membership journal's fleet
    state, so replaying the journal reproduces it bit-exactly
    (``verify_placement_replay``); the backlog is then re-enumerated from
    the surviving holders.
    """

    def __init__(self, store, manager: LifecycleManager,
                 budget_per_tick: int = 64):
        if store.router is not manager.router:
            raise ValueError(
                "store and manager must wrap the SAME router: the repairer "
                "schedules against the fleet the journal records"
            )
        if budget_per_tick < 1:
            raise ValueError(
                f"budget_per_tick must be >= 1, got {budget_per_tick}"
            )
        self.store = store
        self.manager = manager
        self.budget_per_tick = budget_per_tick
        #: (key_index, column) -> (dst shard, first-observed epoch)
        self._pending: dict[tuple[int, int], tuple[int, int]] = {}
        #: repair copies executed / keys found with no reachable source
        self.completed = 0
        self.lost = 0
        #: per-tick emitted batch sizes — the bounded-bandwidth audit trail
        self.batches: list[int] = []
        manager._placement = self
        self.sync()

    @property
    def backlog(self) -> int:
        return len(self._pending)

    # -- enumeration ---------------------------------------------------------
    def sync(self) -> int:
        """Re-enumerate under-replication against the CURRENT fleet (one
        device pass via ``StorePlacement.sync_targets``).  Tasks still
        needed keep their first-observed epoch — oldest-first ordering
        survives re-syncs; tasks obsoleted by the new target are dropped.
        Returns the backlog size.  With ``n_alive == 0`` nothing is
        schedulable; the backlog is left as-is until capacity returns."""
        if self.manager.n_alive == 0:
            return len(self._pending)
        epoch = self.manager.epoch
        fresh: dict[tuple[int, int], tuple[int, int]] = {}
        for ki, col, dst in self.store.sync_targets():
            prev = self._pending.get((ki, col))
            if prev is not None and prev[0] == dst:
                fresh[(ki, col)] = prev
            else:
                fresh[(ki, col)] = (dst, epoch)
        self._pending = fresh
        return len(fresh)

    # -- bounded execution ---------------------------------------------------
    def tick(self, budget: int | None = None) -> list[RepairTask]:
        """Emit ONE repair batch: at most ``budget`` copies (default the
        configured per-tick budget), oldest first-observed epoch first.
        Keys whose every copy is unreachable are counted in ``lost`` and
        re-enumerated at the next membership sync."""
        if not self._pending:
            return []
        budget = self.budget_per_tick if budget is None else budget
        order = sorted(self._pending.items(), key=lambda kv: (kv[1][1], kv[0]))
        done: list[RepairTask] = []
        for (ki, col), (dst, epoch) in order[:budget]:
            del self._pending[(ki, col)]
            src = self.store.repair_source(ki)
            if src < 0:
                self.lost += 1
                continue
            self.store.complete_repair(ki, col, dst)
            done.append(RepairTask(
                key_index=ki, key=int(self.store.keys[ki]), column=col,
                dst=dst, src=src, epoch=epoch,
            ))
        self.completed += len(done)
        if done:
            self.batches.append(len(done))
        return done

    def quiesce(self, max_ticks: int = 100_000) -> int:
        """Drain the backlog in budgeted batches; returns copies executed."""
        total = 0
        for _ in range(max_ticks):
            if not self._pending:
                break
            total += len(self.tick())
        return total

    # -- crash recovery ------------------------------------------------------
    def verify_placement_replay(self, snapshot=None) -> None:
        """Assert placement(replayed journal) == live placement bit-exactly:
        the manager's device-operand replay parity, then the full R-way
        placement of every registered key recomputed from the rebuilt fleet
        state.  Raises ``AssertionError`` on mismatch."""
        import numpy as np

        from repro.core.bulk import FleetState, ZoneState
        from repro.kernels import ops

        self.manager.verify_replay(snapshot)
        if self.store.keys.size == 0 or self.manager.n_alive == 0:
            return
        rebuilt = self.manager.rebuild_domain(snapshot)
        capacity = self.manager.router.spec.capacity
        fleet = FleetState.pack(rebuilt, capacity)
        spec = self.store.spec
        zone = None if spec.zones == 1 else ZoneState.pack(
            rebuilt.zone_tables, capacity
        )
        replayed = ops.route_replicas_bulk(
            self.store.keys, fleet.device_put(), spec, zone
        )[0]
        live, _ = self.store.place_keys(self.store.keys)
        if not np.array_equal(np.asarray(replayed), np.asarray(live)):
            raise AssertionError(
                "replayed placement differs from live placement"
            )
