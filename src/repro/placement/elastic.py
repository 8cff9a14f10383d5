"""Elastic-scaling planners: minimal-migration plans for framework assets.

Three consumers:
* expert-parallel groups — expert -> device placement when the EP group grows
  or shrinks (MoE elastic scaling);
* data hosts — file-shard -> host placement (pipeline rescale, stragglers);
* failure handling — arbitrary node loss via the Memento wrapper.

Everything here is host-side control plane (pure python ints); the device
mesh consumes the resulting placements as sharding metadata.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.core import MementoWrapper, make
from repro.placement.assignment import Assignment, MovementPlan


@dataclass
class ExpertMigration:
    """Expert -> device migration plan between EP group sizes."""

    plan: MovementPlan
    old_devices: int
    new_devices: int
    num_experts: int

    @property
    def bytes_moved(self) -> int:  # filled by caller with per-expert bytes
        return len(self.plan.moves)


def plan_expert_migration(
    num_experts: int, old_devices: int, new_devices: int, engine: str = "binomial"
) -> ExpertMigration:
    """Place experts on devices consistently; return the minimal migration.

    Monotonicity guarantees that on scale-up only experts moving TO new
    devices migrate, and on scale-down only experts FROM removed devices.
    """
    a = Assignment(list(range(num_experts)), old_devices, engine)
    plan = a.resize(new_devices)
    return ExpertMigration(plan, old_devices, new_devices, num_experts)


def plan_shard_reassignment(
    num_shards: int, old_hosts: int, new_hosts: int, engine: str = "binomial"
) -> MovementPlan:
    """Data file-shard -> host reassignment on pipeline rescale."""
    a = Assignment(list(range(num_shards)), old_hosts, engine)
    return a.resize(new_hosts)


class FailureDomain:
    """Arbitrary-failure placement built on the Memento-style wrapper.

    Used by the serving router and the checkpoint manager: lookups always
    return an alive node; failures/recoveries move only the affected keys.

    ``chain_bits=32`` (with a u32 engine such as ``binomial32``) makes the
    whole lookup+remap path u32 — the word size of the batched device
    datapath (``repro.serving.batch_router.BatchRouter``), which mirrors
    this domain's state on device bit-exactly.

    ``resolve="table"`` switches failure resolution from the rejection
    chain to the constant-time replacement table (DESIGN.md §7) — the
    semantics the batched device datapath implements.

    ``zones > 1`` (table mode) splits the slot space into that many zones,
    slot ``s`` in zone ``s mod zones``, whose tables the domain keeps from
    genesis for zone-aware placement (DESIGN.md §13.5).
    """

    def __init__(
        self,
        n: int,
        engine: str = "binomial",
        chain_bits: int = 64,
        omega: int | None = None,
        max_chain: int = 4096,
        resolve: str = "chain",
        allow_empty: bool = False,
        zones: int = 1,
    ):
        def factory(m: int):
            eng = make(engine, m)
            if omega is not None:
                if not hasattr(eng, "omega"):
                    raise ValueError(f"engine '{engine}' does not take omega")
                eng.omega = omega
            return eng

        self._eng = MementoWrapper(
            factory,
            n,
            max_chain=max_chain,
            chain_bits=chain_bits,
            resolve=resolve,
            allow_empty=allow_empty,
            zones=zones,
        )

    @property
    def alive_count(self) -> int:
        return self._eng.size

    @property
    def total_count(self) -> int:
        """Total slot space of the base engine (alive + removed)."""
        return self._eng.n_total

    @property
    def removed(self) -> frozenset[int]:
        return frozenset(self._eng.removed)

    def first_alive(self) -> int:
        return self._eng.first_alive()

    @property
    def replacement_table(self):
        """The ``ReplacementTable`` (``resolve="table"`` domains only) —
        the host truth the device copies are uploaded from."""
        if self._eng.table is None:
            raise ValueError("domain was not constructed with resolve='table'")
        return self._eng.table

    @property
    def zones(self) -> int:
        """Zones of the slot space (1: no zones)."""
        view = self._eng.zone_tables
        return 1 if view is None else view.zones

    @property
    def zone_tables(self):
        """The ``ZoneTables`` kept from genesis (None without zones)."""
        return self._eng.zone_tables

    def locate(self, key: int) -> int:
        return self._eng.get_bucket(key)

    def fail(self, node: int) -> None:
        self._eng.remove_bucket(node)

    def recover(self, node: int) -> None:
        self._eng.restore_bucket(node)

    def scale_up(self) -> int:
        return self._eng.add_bucket()

    def scale_down(self) -> int:
        return self._eng.remove_bucket()
