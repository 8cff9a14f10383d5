"""Engine-agnostic bulk-routing API: ``RouterSpec``, ``FleetState``,
``BulkEngine`` (DESIGN.md §10).

The device datapath (fused lookup + replacement-table divert, one dispatch
per batch) is algorithm-agnostic: any consistent-hash engine whose lookup
loop is bounded and vectorizable can ride the same machinery.  This module
defines the three pieces the datapath is parameterised over:

* ``RouterSpec`` — the frozen configuration bundle that used to travel as
  six copy-pasted kwargs through every entry point (capacity, ω, kernel
  selection, tiling, shard axis, donation).  Hashable, so specs can key
  caches; validated at construction, so bad configs fail loudly instead of
  deep inside a trace.
* ``FleetState`` — the device-operand pytree of the fleet (packed
  removed-slot bit-words, replacement-table slots permutation, the
  ``[n_total, n_alive]`` 2-vector) with the pack / incremental-update hooks
  the serving tier drives at fleet-event time.  Registered as a jax pytree,
  so a whole ``FleetState`` passes through ``jit`` / ``shard_map`` /
  ``device_put`` as one value.
* ``BulkEngine`` — the per-engine bundle: the name of the bit-exact scalar
  oracle (an ``ENGINES`` entry — the control-plane truth the device path is
  tested against), the pure-jnp fused ``route``/``ingest`` mirrors, the
  optional Pallas kernels, and the plain bulk-lookup flavours the MoE hash
  router and the certifier consume.  Engines register in
  ``repro.core.registry.BULK_ENGINES``.

``repro.kernels.ops`` dispatches over a spec + fleet state; porting a new
engine means writing one unrolled jnp lookup body and registering the
bundle (see DESIGN.md §10 for the recipe).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import numpy as np

from repro.core.memento_jax import (
    mask_words,
    pack_removed_mask,
    pack_table,
    table_width,
)

#: default block tiling of the fused kernels (rows of 128 lanes per grid
#: step) — the one definition; ``repro.kernels.autotune`` re-exports it
DEFAULT_BLOCK_ROWS = 512

#: engines that step through f32 arithmetic (jump) need b+1 exact in a
#: float32 mantissa, so the slot space is bounded well below u32
MAX_CAPACITY = 1 << 24

#: zones a placement may span: a key's used-zone mask is one u32 word
MAX_ZONES = 32


@dataclasses.dataclass(frozen=True)
class RouterSpec:
    """Frozen configuration of one bulk-routing datapath.

    engine       BULK_ENGINES name selecting the device datapath (and its
                 scalar control-plane oracle)
    capacity     power-of-two bound on the fleet slot space — sizes the
                 packed mask words and replacement-table lanes (which tile
                 evenly only at pow2), fixed across arbitrary event streams
    omega        lookup iteration bound (binomial's ω; the jump engine's
                 unroll depth) — shared by oracle and kernel so scalar ==
                 batch holds at non-default values too
    use_pallas   None = auto (Pallas on TPU backends only); True/False force
    interpret    run the Pallas kernel in interpreter mode (CPU test rig)
    block_rows   kernel tiling in rows of 128 lanes; None = default /
                 autotune (``BatchRouter`` engages the measure-once tuner)
    shard_axis   mesh axis the sharded datapath splits key batches over
    donate_keys  donate uploaded key buffers to the sharded executable
    """

    engine: str = "binomial"
    capacity: int = 64
    omega: int = 16
    use_pallas: bool | None = None
    interpret: bool = False
    block_rows: int | None = None
    shard_axis: str = "data"
    donate_keys: bool = False

    def __post_init__(self):
        if self.capacity < 1 or self.capacity & (self.capacity - 1):
            raise ValueError(
                f"capacity must be a power of two (got {self.capacity}); the "
                "packed mask words and table lanes tile evenly only at pow2 "
                "capacities"
            )
        if self.capacity > MAX_CAPACITY:
            raise ValueError(
                f"capacity {self.capacity} exceeds {MAX_CAPACITY}; f32-stepping "
                "engines (jump) need slot ids exact in a float32 mantissa"
            )
        if self.omega < 1:
            raise ValueError(f"omega must be >= 1, got {self.omega}")
        if self.block_rows is not None and self.block_rows < 1:
            raise ValueError(
                f"block_rows must be >= 1, got {self.block_rows}; pass None "
                "for the default / autotune"
            )

    # -- derived static extents (the fused kernels' select-cascade bounds) --
    @property
    def n_words(self) -> int:
        """Static packed-mask word count: ceil(capacity / 32)."""
        return mask_words(self.capacity)

    @property
    def n_slots(self) -> int:
        """Static replacement-table slot count (= capacity)."""
        return self.capacity

    def resolved_block_rows(self) -> int:
        """Concrete tiling for the raw kernel entry points (None -> default;
        ``BatchRouter`` resolves None through the autotuner instead)."""
        return DEFAULT_BLOCK_ROWS if self.block_rows is None else self.block_rows

    def pallas_selected(self) -> bool:
        """Whether this spec dispatches to the Pallas kernel (auto = TPU)."""
        if self.use_pallas is None:
            return jax.default_backend() == "tpu"
        return self.use_pallas


@dataclasses.dataclass
class FleetState:
    """The traced device operands of one fleet — a registered jax pytree.

    packed    (1, W) uint32 removed-slot bit-words (bit b = slot b removed)
    table     (1, C) int32 replacement-table ``slots`` permutation
    state     (2,)   uint32 ``[n_total, n_alive]``
    capacity  the slot-space bound the arrays were packed for (pytree aux
              data, not a leaf; 0 = derive from the padded table width)

    Shapes are fixed by the spec's ``capacity`` across arbitrary fleet-event
    streams — that is what keeps the compiled datapath retrace-free.  The
    host-side instance (numpy arrays, built by ``pack``) is the mutable
    mirror the event hooks update; ``device_put`` pins a device twin in ONE
    transfer, re-done at event time only, never per batch.
    """

    packed: Any
    table: Any
    state: Any
    capacity: int = 0

    def __post_init__(self):
        if not self.capacity:
            # manual construction (tests, the placement tier): the padded
            # table width bounds the slot space, which is all packing needs.
            # Leaves without a (1, C) shape (PartitionSpec trees, tracing
            # placeholders) keep capacity 0 — they never pack.
            shape = getattr(self.table, "shape", None)
            if shape is not None and len(shape) == 2:
                self.capacity = int(shape[1])

    @classmethod
    def pack(cls, domain, capacity: int) -> "FleetState":
        """Host-side pack of a ``FailureDomain`` (table resolution) truth."""
        return cls(
            packed=pack_removed_mask(domain.removed, capacity),
            table=pack_table(domain.replacement_table, capacity),
            state=np.array(
                [domain.total_count, domain.alive_count], dtype=np.uint32
            ),
            capacity=capacity,
        )

    # -- incremental event-time hooks (host mirror only) --------------------
    def set_removed(self, replica: int, removed: bool) -> None:
        """Flip one mask bit — the fail/recover incremental update."""
        word, bit = replica >> 5, np.uint32(1) << np.uint32(replica & 31)
        if removed:
            self.packed[0, word] |= bit
        else:
            self.packed[0, word] &= ~bit

    def update(self, domain) -> None:
        """Re-pack table + state from the domain (the permutation swapped
        O(1) entries; the counters may have moved).  Mask bits are flipped
        separately by ``set_removed`` — scale-down GC goes through
        ``resync`` instead."""
        self.table = pack_table(domain.replacement_table, self.capacity)
        self.state = np.array(
            [domain.total_count, domain.alive_count], dtype=np.uint32
        )

    def resync(self, domain) -> None:
        """Wholesale rebuild (scale-down may garbage-collect tombstones off
        the end of the slot space, clearing mask bits non-incrementally)."""
        self.packed = pack_removed_mask(domain.removed, self.capacity)
        self.update(domain)

    def device_put(self, sharding=None) -> "FleetState":
        """Pin a device twin — ONE ``jax.device_put`` for the whole pytree."""
        if sharding is None:
            return jax.device_put(self)
        return jax.device_put(self, sharding)


# capacity is deliberately NOT treedef metadata: it only parameterises the
# host-side pack/update hooks, and two FleetStates over the same arrays must
# be the same pytree structure (shard_map prefix-matches in_specs by treedef)
jax.tree_util.register_pytree_node(
    FleetState,
    lambda f: ((f.packed, f.table, f.state), None),
    lambda _, children: FleetState(*children),
)


def zone_width(capacity: int, zones: int) -> int:
    """Entries a zone holds in the zone table: its slot count can reach
    ``ceil(capacity / zones)`` (slot ``s`` lies in zone ``s mod zones``)."""
    return -(-capacity // zones)


@dataclasses.dataclass
class ZoneState:
    """The zone fallback's device operands — a registered jax pytree, read
    by the placement pass only (DESIGN.md §13.5).

    table  (1, W) int32: zone ``z``'s ``ReplacementTable`` permutation, as
           global slot ids with the alive prefix first, at entries
           ``[z * width, z * width + total_z)``; ``width`` is
           ``zone_width(capacity, zones)``, and ``W`` that times ``zones``
           rounded up to whole lanes
    state  (2, Z) uint32: each zone's slot count, then its alive count

    Shapes are fixed by the capacity and the zone count, so no fleet event
    retraces the pass; the router's ``FleetState`` is left as it is.
    """

    table: Any
    state: Any

    @classmethod
    def pack(cls, zone_tables, capacity: int) -> "ZoneState":
        """Host-side pack of a ``ZoneTables`` truth."""
        zones = zone_tables.zones
        width = zone_width(capacity, zones)
        table = np.zeros((1, table_width(zones * width)), np.int32)
        for z, t in enumerate(zone_tables.tables):
            table[0, z * width : z * width + t.n_total] = (
                np.asarray(t.slots, np.int64) * zones + z
            )
        state = np.array(
            [[t.n_total for t in zone_tables.tables],
             [t.n_alive for t in zone_tables.tables]],
            dtype=np.uint32,
        )
        return cls(table, state)


jax.tree_util.register_pytree_node(
    ZoneState,
    lambda z: ((z.table, z.state), None),
    lambda _, children: ZoneState(*children),
)


@dataclasses.dataclass(frozen=True)
class PlacementSpec:
    """Frozen configuration of one R-way replicated placement tier.

    router      the ``RouterSpec`` of the underlying bulk engine — every
                replica column routes through the same fused datapath
    r           replication factor: each key is placed on ``r`` distinct
                alive shards (degrading to ``n_alive`` distinct copies when
                the fleet is smaller than ``r``)
    max_resalt  bound on the deterministic collision-resolution probes per
                replica column; ``None`` (the default) resolves to ``r``,
                which guarantees distinctness whenever ``n_alive > column``
                (column ``j`` probes ``j+1 <= r`` alive-prefix positions, at
                most ``j`` of which are taken).  Smaller explicit bounds are
                allowed for experiments — exhaustion then surfaces as a
                typed ``PlacementExhaustedError``, never a silent duplicate.
    zones       failure domains the slot space is split into (slot ``s`` in
                zone ``s mod zones``): a key's holders lie in
                ``min(r, alive zones)`` distinct zones (DESIGN.md §13.5).
                1, the default, is the zone-free pass.

    Hashable (it keys jit caches); validated at construction.
    """

    router: RouterSpec = dataclasses.field(default_factory=RouterSpec)
    r: int = 3
    max_resalt: int | None = None
    zones: int = 1

    def __post_init__(self):
        if self.r < 1:
            raise ValueError(f"replication factor r must be >= 1, got {self.r}")
        if self.r > self.router.capacity:
            raise ValueError(
                f"replication factor r ({self.r}) exceeds the fleet capacity "
                f"({self.router.capacity}); r distinct shards cannot exist"
            )
        if self.max_resalt is not None and self.max_resalt < 0:
            raise ValueError(
                f"max_resalt must be >= 0, got {self.max_resalt}; pass None "
                "for the distinctness-guaranteeing default"
            )
        if not 1 <= self.zones <= min(MAX_ZONES, self.router.capacity):
            raise ValueError(
                f"zones must be in [1, {min(MAX_ZONES, self.router.capacity)}] "
                f"(got {self.zones}): a key's used-zone mask is one u32 word, "
                "and a zone needs a slot"
            )

    @property
    def zone_width(self) -> int:
        """Static zone-table entries per zone."""
        return zone_width(self.router.capacity, self.zones)

    @property
    def resolved_max_resalt(self) -> int:
        """Concrete probe bound: column ``j`` needs ``j+1`` probes in the
        worst case (``j`` earlier replicas occupy ``j`` alive-prefix
        positions), so ``r`` probes make distinctness deterministic for
        every column whenever ``n_alive > j``."""
        return self.r if self.max_resalt is None else self.max_resalt


@dataclasses.dataclass(frozen=True)
class BulkEngine:
    """One pluggable device routing engine (DESIGN.md §10).

    scalar_engine     ``ENGINES`` name of the bit-exact scalar oracle (a u32
                      flavour — the device word size); the serving control
                      plane embeds it via ``SessionRouter`` and tests pin
                      device == scalar key-for-key
    route             pure-jnp fused lookup+divert mirror:
                      ``(keys, packed, table, state, omega=, *, n_words=,
                      read=)``; ``read`` (static) reads the slots table in
                      the divert, the XLA gather by default
    ingest            fused u64-id ingest mirror (u32 halves); None if the
                      engine has no in-kernel session-key mix
    route_pallas /    the Pallas kernel twins (same operand contract as the
    ingest_pallas     binomial flavours); None falls back to the jnp mirror
                      even when Pallas is selected
    lookup_dyn        traced-n bulk lookup ``(keys, n, omega=)`` — the
                      eager MoE hash router
    lookup_vec        static-n bulk lookup (constant-folded masks; the
                      jitted-model MoE router)
    """

    name: str
    scalar_engine: str
    route: Callable
    ingest: Callable | None = None
    route_pallas: Callable | None = None
    ingest_pallas: Callable | None = None
    lookup_dyn: Callable | None = None
    lookup_vec: Callable | None = None
