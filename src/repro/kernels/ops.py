"""Generic jit'd entry points for bulk consistent-hash routing.

The dispatcher over the engine protocol (DESIGN.md §10): every function
takes a ``RouterSpec`` (which engine, capacity, ω, kernel selection,
tiling) plus the traced operands, resolves the engine's bundle from
``repro.core.registry.BULK_ENGINES`` *per call* (so tests can swap entries
in to intercept dispatches), and picks the Pallas kernel on TPU backends /
interpret mode or the pure-jnp mirror elsewhere — model and serving code
calls one function everywhere.

* ``route_bulk(keys, fleet, spec)``                — fused lookup + divert;
* ``route_load_bulk(keys, fleet, counts, spec)``   — fused route + per-shard
  load accumulate (the observability tier's instrumented dispatch);
* ``route_ingest_bulk(lo, hi, fleet, spec)``       — fused u64-id ingest;
* ``route_replicas_bulk(keys, fleet, pspec)``      — R-way placement;
* ``placement_diff_bulk(keys, old, new, pspec)``   — the migration diff;
* ``make_sharded_route(mesh, spec)``               — the mesh-sharded route.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core.bulk import FleetState, RouterSpec
from repro.kernels.table_read import TableRead


def _engine(spec: RouterSpec):
    """Resolve the spec's engine bundle — live, so monkeypatched/updated
    ``BULK_ENGINES`` entries take effect immediately."""
    from repro.core.registry import make_bulk  # late: registry imports kernels

    return make_bulk(spec.engine)


def route_bulk(keys: jax.Array, fleet: FleetState, spec: RouterSpec) -> jax.Array:
    """Fused routing: keys + fleet state -> int32 replica ids, ONE dispatch.

    The single-dispatch serving hot path, engine-generic: the spec's engine
    runs its base lookup AND the replacement-table failure divert under one
    compiled executable (fused Pallas kernel on TPU / interpret mode, fused
    jnp jit elsewhere) — no intermediate ``buckets[N]`` HBM round-trip,
    every fleet-state operand is traced so scale/fail/recover streams never
    retrace, and the divert is two bounded hash rounds + ONE table gather
    per lane so an event storm never shows up on the batch critical path
    (DESIGN.md §7, §10).

    keys   any int shape (u32 key space)
    fleet  ``FleetState`` — packed (1, W) u32 mask words, (1, C) i32 slots
           permutation, (2,) u32 ``[n_total, n_alive]``
    spec   ``RouterSpec`` — engine, capacity (fixing W/C), ω, kernel choice
    """
    eng = _engine(spec)
    if (spec.pallas_selected() or spec.interpret) and eng.route_pallas is not None:
        return eng.route_pallas(
            keys,
            fleet.packed,
            fleet.table,
            fleet.state,
            spec.n_words,
            spec.n_slots,
            omega=spec.omega,
            block_rows=spec.resolved_block_rows(),
            interpret=spec.interpret,
        )
    return eng.route(
        keys, fleet.packed, fleet.table, fleet.state,
        omega=spec.omega, n_words=spec.n_words,
    )


def route_load_bulk(
    keys: jax.Array, fleet: FleetState, counts: jax.Array, spec: RouterSpec,
    *, sample_shift: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Instrumented fused routing: route + per-shard load accumulate in ONE
    dispatch — ``(replicas (N,) i32, new_counts (capacity,) u32)``.

    The observability tier's device pass (DESIGN.md §15): the spec'd
    engine's fused jnp route plus a bincount of the replica vector into a
    device-resident accumulator, all under one jitted executable.  With
    ``sample_shift > 0`` the bincount covers the ``[::2**shift]`` stride
    sample at weight ``2**shift`` — key-unit estimates for bulk batches
    where exact counting would break the overhead budget (the
    ``LoadMonitor`` picks the shift per batch via its exact cutoff).
    Replica ids are bit-exact with ``route_bulk`` at every shift — the
    instrumentation never changes routing — and the accumulator stays on
    device (the monitor drains it on its own cadence).  Pure-jnp on
    every backend (the accumulate is one comparison-sum or scatter — no
    Pallas twin); certified as ``observability/load_pass``.

    keys    any int shape (u32 key space)
    fleet   ``FleetState``;  counts  (capacity,) u32 running accumulator
    spec    ``RouterSpec`` — engine, capacity, ω
    """
    from repro.observability.load import _route_with_load_jit  # late:
    # observability imports this module

    eng = _engine(spec)
    return _route_with_load_jit(
        keys, fleet.packed, fleet.table, fleet.state, counts,
        omega=spec.omega, n_words=spec.n_words, route=eng.route,
        sample_shift=sample_shift,
    )


def route_ingest_bulk(
    ids_lo: jax.Array, ids_hi: jax.Array, fleet: FleetState, spec: RouterSpec
) -> jax.Array:
    """Fused ingest routing: raw u64 session ids (as u32 halves) + fleet
    state -> int32 replica ids, ONE dispatch (DESIGN.md §9, §10).

    The limb-wise splitmix64 session-key mix, the engine's base lookup AND
    the replacement-table divert all run under one compiled executable —
    the ``keys[N]`` array the pre-hash path materialises never exists.
    Engines without an in-kernel ingest mix raise; route pre-hashed keys
    through ``route_bulk`` instead.
    """
    eng = _engine(spec)
    if eng.ingest is None:
        raise ValueError(
            f"bulk engine '{spec.engine}' has no fused ingest path; pre-hash "
            "the ids (hash_session_ids) and call route_bulk"
        )
    if (spec.pallas_selected() or spec.interpret) and eng.ingest_pallas is not None:
        return eng.ingest_pallas(
            ids_lo,
            ids_hi,
            fleet.packed,
            fleet.table,
            fleet.state,
            spec.n_words,
            spec.n_slots,
            omega=spec.omega,
            block_rows=spec.resolved_block_rows(),
            interpret=spec.interpret,
        )
    return eng.ingest(
        ids_lo, ids_hi, fleet.packed, fleet.table, fleet.state,
        omega=spec.omega, n_words=spec.n_words,
    )


def route_replicas_bulk(keys: jax.Array, fleet: FleetState, pspec,
                        zone=None, counts=None) -> tuple:
    """R-way replicated placement: keys + fleet state -> ``(replicas (N, r)
    i32 distinct alive shards, exhausted (N,) bool)``, ONE dispatch.

    The placement tier's device pass (DESIGN.md §13): all ``r`` salted key
    families route through the spec'd engine's fused jnp datapath as one
    broadcast batch, then the bounded re-salt resolution breaks inter-family
    collisions in-trace.  Engine resolved per call like every dispatcher
    here.  The pass is XLA on every backend but for its small-table reads:
    where the route's kernel runs (a TPU, or interpret mode) the route's
    divert, the probes and the zone fallback read the slots and zone
    tables from VMEM by the ``table_read`` kernel, elsewhere by XLA
    gathers (``TableRead``).

    keys   any int shape (u32 key space); fleet  ``FleetState``;
    pspec  ``PlacementSpec`` — replication r, probe bound, zones, the
    RouterSpec.  With ``pspec.zones > 1``: ``zone`` is the ``ZoneState``,
    ``counts`` the ``(2, 2)`` u32 fallback accumulator (low words, then
    high words; zeros when None), and the same dispatch returns it advanced
    as a third output (§13.5).
    """
    from repro.placement.store import _route_replicas_jit  # late: placement
    # imports this module

    spec = pspec.router
    eng = _engine(spec)
    static = dict(r=pspec.r, omega=spec.omega, n_words=spec.n_words,
                  max_resalt=pspec.resolved_max_resalt, route=eng.route,
                  read=TableRead.for_spec(spec))
    if pspec.zones == 1:
        return _route_replicas_jit(
            keys, fleet.packed, fleet.table, fleet.state, **static
        )
    if counts is None:
        counts = np.zeros((2, 2), np.uint32)
    return _route_replicas_jit(
        keys, fleet.packed, fleet.table, fleet.state, zone, counts,
        zones=pspec.zones, zone_width=pspec.zone_width, **static,
    )


def placement_diff_bulk(
    keys: jax.Array, fleet_old: FleetState, fleet_new: FleetState, pspec,
    zone_old=None, zone_new=None,
) -> tuple:
    """Bulk migration diff: both placements + the transfer mask in ONE
    dispatch — ``(old (N, r), new (N, r), moved (N, r) bool, exhausted)``
    with ``moved[i, j] = new[i, j] not in old[i, :]`` (membership, not
    positional inequality: a column swap is free, only a shard with no
    prior copy needs bytes).  Operand contract as ``route_replicas_bulk``;
    with ``pspec.zones > 1`` both sides' ``ZoneState`` come too.
    """
    from repro.placement.store import _placement_diff_jit

    spec = pspec.router
    eng = _engine(spec)
    return _placement_diff_jit(
        keys, fleet_old, fleet_new, zone_old, zone_new,
        r=pspec.r, omega=spec.omega, n_words=spec.n_words,
        max_resalt=pspec.resolved_max_resalt, route=eng.route,
        zones=pspec.zones, zone_width=pspec.zone_width,
        read=TableRead.for_spec(spec),
    )


# ---------------------------------------------------------------------------
# mesh-sharded datapath
# ---------------------------------------------------------------------------


def make_sharded_route(mesh, spec: RouterSpec | None = None):
    """Build the mesh-sharded bulk routing callable (DESIGN.md §8).

    Returns ``route(keys, fleet) -> replica ids`` where 1-D ``keys`` are
    split along the mesh's ``spec.shard_axis`` (length must be a multiple
    of the axis size — the caller pads) and the ``FleetState`` operands are
    replicated on every device.  Each device runs the fused single-dispatch
    datapath on its shard — zero cross-device collectives, zero per-batch
    host round-trips — so multi-device hosts scale routed keys/s with the
    device count.  The whole thing is ONE jitted executable (``shard_map``
    under ``jit``); all fleet state stays traced, so scale/fail/recover
    event streams never retrace.

    ``spec.donate_keys=True`` donates the key buffer to the executable (the
    caller must not reuse it) — the serving tier enables this for key
    batches it uploads itself, making the sharded hot path allocation-free
    on the input side.
    """
    if not isinstance(spec, RouterSpec):
        raise TypeError(
            "make_sharded_route requires a RouterSpec: "
            "make_sharded_route(mesh, RouterSpec(...))"
        )
    return _make_sharded_route_impl(mesh, spec)


def _make_sharded_route_impl(mesh, spec: RouterSpec):
    from jax.sharding import PartitionSpec as P

    def inner(keys, fleet):
        return route_bulk(keys, fleet, spec)

    fleet_specs = FleetState(P(), P(), P(), capacity=spec.capacity)
    sharded = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(spec.shard_axis), fleet_specs),
        out_specs=P(spec.shard_axis),
        # a pallas_call's output carries no varying-axes type for the check
        # to read; each shard's result depends on its own keys only
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0,) if spec.donate_keys else ())

