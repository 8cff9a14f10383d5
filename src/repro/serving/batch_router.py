"""Batched, recompile-free, storm-proof session routing — the serving-tier
datapath, generic over the pluggable bulk engines (DESIGN.md §10).

``SessionRouter`` routes one session at a time through scalar Python
(``FailureDomain.locate``); fine for a control plane, hopeless for a serving
tier taking millions of lookups per second.  ``BatchRouter`` embeds a u32
``SessionRouter`` as its control plane — scalar lookups, stats and
fleet-event bookkeeping all live there — and routes whole key batches on
device in ONE dispatch (DESIGN.md §3, §7):

    keys[N] --route_bulk--> replicas[N]   (fused lookup + divert)

Which consistent-hash algorithm runs inside that dispatch is the
``RouterSpec.engine`` (``BatchRouter(engine="binomial")`` is the default;
``engine="jump"`` selects the JumpHash device datapath): each
``BULK_ENGINES`` entry pairs the device kernels with the scalar oracle
flavour the embedded control plane runs, so device == scalar bit-exactness
holds per engine (tests enforce).  The engine's fused kernel takes the
fleet state as *traced*, *device-resident* operands — one ``FleetState``
pytree: ``[n_total, n_alive]`` as a scalar-prefetch/SMEM 2-vector, the
removed-slot set as a fixed-shape packed bit-table, and the MementoHash-
style replacement table (``(1, capacity)`` i32 — the ``slots`` permutation;
``pos`` stays host-side) in VMEM — so an arbitrary stream of scale-up /
scale-down / fail / recover events re-uses one compiled executable per
batch shape: zero retraces.  Removed buckets resolve through AT MOST TWO
bounded table gathers instead of a data-dependent rejection walk, so an
event storm costs the same per batch as a healthy fleet — the paper's
constant-time guarantee carried through the compiled datapath *including*
its failure path.  Fleet events update the device copies incrementally (a
one-word bit flip + permutation swap on the host ``FleetState`` mirror,
then a few-KiB ``jax.device_put``, event-time only); ``route_keys`` itself
performs zero host->device state uploads and zero host round-trips — it
accepts and returns ``jax.Array`` (``route_keys_np`` / ``route_batch`` are
the numpy convenience wrappers).

Multi-device hosts hand ``BatchRouter`` a mesh: key batches are then split
across the mesh axis under one jitted ``shard_map`` (fleet state
replicated, per-device fused dispatch, no collectives — DESIGN.md §8) for
near-linear keys/s scaling.  ``block_rows=None`` engages the measure-once
persistent autotuner on Pallas backends (``repro.kernels.autotune``).

The pre-fusion two-stage pipeline (``lookup_bulk_dyn`` then
``memento_remap_table`` — two dispatches, ``buckets[N]`` materialised in
HBM between them) is kept behind ``fused=False`` as the benchmark baseline.

Configuration rides in one frozen ``RouterSpec`` (``BatchRouter(16,
spec)``); the individual keyword arguments remain as sugar that builds the
spec (``BatchRouter(16, engine="jump", capacity=128)``) — passing both is
an error, not a merge.

Bit-exactness (enforced by tests): for every key, the device path returns
exactly what the embedded scalar router's ``domain.locate`` returns — the
scalar router is the oracle for the batched one.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bits
from repro.core.bulk import FleetState, RouterSpec
from repro.core.memento_jax import memento_remap_table
from repro.core.registry import make_bulk
from repro.kernels import autotune
from repro.kernels import ops
from repro.kernels.fused import LANES
from repro.observability.trace import span
from repro.serving.lifecycle.errors import FleetUnavailableError
from repro.serving.router import SessionRouter, hash_session_ids

#: "this keyword was not passed" sentinel — None is meaningful for several
#: spec fields (use_pallas auto, block_rows autotune), so absence needs its
#: own marker to detect spec-vs-kwargs conflicts
_UNSET = object()


def _check_block_rows(block_rows) -> None:
    """The serving tier insists on whole sublane tiles; the raw kernel entry
    points accept any divisor (tests tile tiny batches)."""
    if block_rows is not None and (block_rows <= 0 or block_rows % 8):
        raise ValueError(
            f"block_rows must be a positive multiple of 8 (the i32 sublane "
            f"tile), got {block_rows}; pass None to autotune"
        )


class BatchRouter:
    """Route request batches through the fused single-dispatch kernel of a
    pluggable bulk engine.

    ``zones`` (keyword, default 1) is a fact of the deployment: the failure
    domain splits its slot space into that many zones and keeps their
    tables from genesis, for ``StorePlacement(..., zones=)``; the route
    itself does not read them."""

    def __init__(
        self,
        n_replicas: int,
        spec: RouterSpec | None = None,
        *,
        mesh=None,
        fused: bool = True,
        max_chain: int = 4096,
        engine=_UNSET,
        capacity=_UNSET,
        omega=_UNSET,
        use_pallas=_UNSET,
        interpret=_UNSET,
        block_rows=_UNSET,
        shard_axis=_UNSET,
        donate_keys=_UNSET,
        zones: int = 1,
    ):
        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
        kwargs = {
            name: value
            for name, value in (
                ("engine", engine),
                ("capacity", capacity),
                ("omega", omega),
                ("use_pallas", use_pallas),
                ("interpret", interpret),
                ("block_rows", block_rows),
                ("shard_axis", shard_axis),
                ("donate_keys", donate_keys),
            )
            if value is not _UNSET
        }
        if spec is not None:
            if not isinstance(spec, RouterSpec):
                raise TypeError(
                    f"the second positional argument is the RouterSpec (got "
                    f"{type(spec).__name__}); pre-spec positional callers "
                    "should pass capacity and friends as keywords: "
                    "BatchRouter(n, capacity=..., omega=...)"
                )
            if kwargs:
                raise ValueError(
                    f"pass either a RouterSpec or individual spec fields, not "
                    f"both (got spec and {sorted(kwargs)})"
                )
        else:
            if kwargs.get("capacity", _UNSET) is None:
                kwargs.pop("capacity")  # explicit capacity=None = default
            kwargs.setdefault(
                "capacity", max(64, bits.next_pow2(2 * n_replicas))
            )
            # before RouterSpec(**kwargs): the spec's own weaker check
            # (>= 1) would otherwise claim e.g. block_rows=0 first, with
            # the wrong error message for this constructor's contract
            _check_block_rows(kwargs.get("block_rows"))
            spec = RouterSpec(**kwargs)  # validates capacity/omega
        _check_block_rows(spec.block_rows)  # spec-mode path
        if n_replicas > spec.capacity:
            raise ValueError(
                f"n_replicas ({n_replicas}) exceeds capacity ({spec.capacity})"
            )
        if max_chain < 0:
            raise ValueError(
                f"max_chain must be >= 0, got {max_chain}; note the table "
                "resolution has a hard two-redirect bound, so max_chain only "
                "labels the (unused) chain budget — any value >= 0 routes "
                "identically"
            )
        if mesh is not None and not fused:
            raise ValueError(
                "the two-pass baseline (fused=False) is single-host only; "
                "the mesh-sharded datapath always runs the fused kernel"
            )
        if spec.donate_keys and mesh is None:
            raise ValueError(
                "donate_keys applies to the mesh-sharded datapath only; "
                "pass a mesh or drop donate_keys"
            )
        self.spec = spec
        self._bulk = make_bulk(spec.engine)  # fails loudly on unknown engines
        # control-plane truth: the engine's u32 scalar oracle + u32 table
        # resolution (the device semantics); omega mirrors the device
        # operand so scalar == batch holds for non-default values too.
        # max_chain is INERT under table resolution (hard two-redirect
        # bound) — accepted and validated for API stability with the
        # chain-mode library flavour, forwarded only so the control plane
        # would stay bit-exact if flipped to chain mode.
        # allow_empty: an all-failed fleet is a queryable state the route
        # entry points answer with FleetUnavailableError — the failure event
        # itself is never refused (DESIGN.md §12)
        self.scalar = SessionRouter(
            n_replicas,
            engine=self._bulk.scalar_engine,
            chain_bits=32,
            omega=spec.omega,
            max_chain=max_chain,
            resolve="table",
            allow_empty=True,
            zones=zones,
        )
        self.max_chain = max_chain
        self.fused = fused
        self.mesh = mesh
        self._n_shards = 1 if mesh is None else int(mesh.shape[spec.shard_axis])
        #: per-batch-rows resolved block size (autotuner results memoised)
        self._tuned_rows: dict[int, int] = {}
        #: per-block_rows dispatch specs (replace + re-validate once, not
        #: per batch — route_keys does zero host work beyond the dispatch)
        self._dispatch_specs: dict[int, RouterSpec] = {}
        #: per-(rows, block_rows) jitted sharded executables (mesh mode)
        self._sharded_route: dict[int, object] = {}
        # canonical host mirror of the device fleet state, mutated
        # incrementally on fleet events; the device twin is pinned once
        # here, then refreshed only on fleet events — never rebuilt or
        # re-uploaded per batch.  The two-pass baseline additionally keeps
        # the n scalar its first dispatch reads.
        self._fleet_host = FleetState.pack(self.domain, spec.capacity)
        self._fleet_dev: FleetState | None = None
        self._n_dev: jax.Array | None = None
        #: attached observability LoadMonitor (None = uninstrumented): when
        #: set, the fused dispatch runs the instrumented route so the
        #: per-shard bincount rides in the SAME device pass as the routing
        self._load_monitor = None
        #: routing epoch: one tick per fleet event — callers (and the
        #: lifecycle layer) use it to detect placements staled by later
        #: events; the journal's epochs match it one-to-one
        self._epoch = 0
        # event-storm coalescing state (see ``coalesced_events``)
        self._coalescing = False
        self._state_dirty = False
        self._put_state()

    # -- spec facade (the pre-spec attribute names, kept as properties) -----
    @property
    def engine(self) -> str:
        return self.spec.engine

    @property
    def capacity(self) -> int:
        return self.spec.capacity

    @property
    def n_words(self) -> int:
        return self.spec.n_words

    @property
    def omega(self) -> int:
        return self.spec.omega

    @property
    def use_pallas(self):
        return self.spec.use_pallas

    @property
    def interpret(self) -> bool:
        return self.spec.interpret

    @property
    def block_rows(self):
        return self.spec.block_rows

    @property
    def shard_axis(self) -> str:
        return self.spec.shard_axis

    @property
    def donate_keys(self) -> bool:
        return self.spec.donate_keys

    @property
    def domain(self):
        return self.scalar.domain

    @property
    def stats(self):
        return self.scalar.stats

    # the device FleetState leaves, as the historical attribute names
    @property
    def _packed_dev(self):
        return None if self._fleet_dev is None else self._fleet_dev.packed

    @property
    def _table_dev(self):
        return None if self._fleet_dev is None else self._fleet_dev.table

    @property
    def _state_dev(self):
        return None if self._fleet_dev is None else self._fleet_dev.state

    # -- device-side fleet state -------------------------------------------
    def _device_put(self, host_tree):
        """Pin host state on device — replicated across the mesh if sharded."""
        if self.mesh is None:
            return jax.device_put(host_tree)
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(host_tree, NamedSharding(self.mesh, P()))

    def _resync_device_state(self) -> None:
        """Rebuild the device operands from control-plane truth.

        Used after scale-down (which may garbage-collect removed-slot
        tombstones off the end of the slot space); fail/recover take the
        incremental single-bit + permutation-swap path instead.
        """
        self._fleet_host.resync(self.domain)  # includes the table/state pack
        self._upload_state()

    def _put_state(self) -> None:
        """Re-pack the ``FleetState`` mirror's table + state (the host
        ``ReplacementTable`` is updated O(1) per event by the control
        plane) and re-pin the device twin."""
        self._fleet_host.update(self.domain)
        self._upload_state()

    def _upload_state(self) -> None:
        """Re-pin every device operand of the fleet state — event-time only,
        never per batch, and ONE ``device_put`` for the lot (a few KiB; the
        per-call fixed cost dominates at these sizes, so batching the
        transfers keeps fleet events well under a millisecond)."""
        if self._coalescing:
            # inside coalesced_events: defer — the whole event burst lands
            # as ONE wholesale resync + upload on exit
            self._state_dirty = True
            return
        if self.fused:
            self._fleet_dev = self._device_put(self._fleet_host)
        else:
            self._fleet_dev, self._n_dev = self._device_put(
                (self._fleet_host, np.uint32(self.domain.total_count))
            )

    def _set_removed_bit(self, replica: int, removed: bool) -> None:
        """Incremental fleet-event update: flip one mask bit, re-pin."""
        self._fleet_host.set_removed(replica, removed)
        self._put_state()  # the permutation swapped O(1) entries

    # -- event-storm coalescing ---------------------------------------------
    @contextlib.contextmanager
    def coalesced_events(self):
        """Defer device-state refresh across a burst of fleet events.

        Inside the context every fail/recover/scale event still mutates the
        host control plane immediately (the scalar oracle, the journal
        epochs and ``routing_epoch`` all stay exact per event); only the
        device-twin refresh is deferred.  On exit the final state lands in
        ONE wholesale resync + upload — bit-exact with per-event
        application, because the device operands are a pure function of the
        final control-plane state.  Re-entrant: the outermost context owns
        the flush.  ``route_keys``/``route_ids`` flush defensively, so a
        dispatch can never read a stale device twin.
        """
        if self._coalescing:
            yield
            return
        self._coalescing = True
        try:
            yield
        finally:
            self._coalescing = False
            if self._state_dirty:
                self._flush_events()

    def _flush_events(self) -> None:
        """Land every deferred event in one resync + one device upload."""
        self._state_dirty = False
        self._fleet_host.resync(self.domain)
        self._upload_state()

    # -- block-size resolution ----------------------------------------------
    def _resolve_block_rows(self, rows: int) -> int:
        """Static tiling for a batch of ``rows`` x128 keys.

        Explicit ``block_rows`` wins; the jnp fallback and interpret mode
        (a test harness, not a perf target) take the default; otherwise the
        measure-once autotuner picks per (backend, rows, capacity) and
        persists the verdict (DESIGN.md §7).
        """
        if self.spec.block_rows is not None:
            return self.spec.block_rows
        if not self.spec.pallas_selected() or self.spec.interpret:
            return autotune.DEFAULT_BLOCK_ROWS
        if rows not in self._tuned_rows:
            # device-resident, laid out as route_keys would see it: timing a
            # host upload along with the kernel would hide the tiling
            from jax.sharding import NamedSharding, PartitionSpec as P

            probe = jax.device_put(
                np.zeros((rows * LANES * self._n_shards,), dtype=np.uint32),
                None if self.mesh is None
                else NamedSharding(self.mesh, P(self.spec.shard_axis)),
            )

            def measure(candidate: int) -> None:
                # probe batches are timing scaffolding, not traffic: keep
                # them out of any attached load accumulator
                monitor, self._load_monitor = self._load_monitor, None
                try:
                    jax.block_until_ready(self._route(probe, candidate))
                finally:
                    self._load_monitor = monitor

            flavour = "fused" if self.fused else "two_pass"
            if self.spec.engine != "binomial":
                flavour = f"{self.spec.engine}_{flavour}"
            self._tuned_rows[rows] = autotune.tuned_block_rows(
                jax.default_backend(),
                rows,
                self.spec.capacity,
                measure,
                variant=flavour,
            )
        return self._tuned_rows[rows]

    def _dispatch_spec(self, block_rows: int) -> RouterSpec:
        """The spec with the per-batch tiling resolved to a concrete int
        (memoised — block_rows takes a handful of values per router)."""
        if block_rows == self.spec.block_rows:
            return self.spec
        spec = self._dispatch_specs.get(block_rows)
        if spec is None:
            spec = dataclasses.replace(self.spec, block_rows=block_rows)
            self._dispatch_specs[block_rows] = spec
        return spec

    # -- routing ------------------------------------------------------------
    session_key = staticmethod(SessionRouter.session_key)

    def _check_routable(self) -> None:
        """Route-entry guard: typed error on an all-failed fleet, and land
        any coalesced events the dispatch would otherwise miss."""
        if self.scalar.alive == 0:
            raise FleetUnavailableError(epoch=self._epoch)
        if self._state_dirty and not self._coalescing:
            self._flush_events()

    def _coerce_keys(self, keys) -> jax.Array | np.ndarray:
        """Any int keys -> u32, truncating exactly like the scalar oracle.

        Already-u32 arrays (jax or contiguous numpy) pass straight through —
        no ``uint64 -> uint32`` double conversion, and for ``jax.Array`` no
        host round-trip at all (wider jax ints are truncated in-trace by the
        fused jit, which is the same mod-2^32 semantics).
        """
        if isinstance(keys, jax.Array):
            return keys
        if isinstance(keys, np.ndarray) and keys.dtype == np.uint32:
            # no-op for contiguous input, one widen-free copy for views
            return np.ascontiguousarray(keys)
        return np.ascontiguousarray(keys, dtype=np.uint64).astype(np.uint32)

    # -- load-monitor attachment (observability tier, DESIGN.md §15) --------
    def attach_load_monitor(self, monitor) -> None:
        """Instrument the fused dispatch with the monitor's device-side
        load accumulator (``ops.route_load_bulk``).  Replica ids stay
        bit-exact with the uninstrumented path; the accumulate is folded
        into the same single dispatch.  Single-host fused datapath only —
        the mesh-sharded and two-pass paths are not instrumented."""
        if self.mesh is not None:
            raise ValueError(
                "load monitoring is single-host only; the mesh-sharded "
                "datapath is not instrumented"
            )
        if not self.fused:
            raise ValueError(
                "load monitoring requires the fused datapath "
                "(fused=False is the two-pass benchmark baseline)"
            )
        self._load_monitor = monitor

    def detach_load_monitor(self) -> None:
        self._load_monitor = None

    def _dispatch(self, keys_u32, block_rows: int) -> jax.Array:
        """Single-host dispatch of one batch at a given tiling."""
        spec = self._dispatch_spec(block_rows)
        if self.fused:
            monitor = self._load_monitor
            if monitor is not None:
                # the instrumented route: same dispatch count, the per-shard
                # bincount rides along (always the fused jnp pass, which
                # has no Pallas twin; bit-exact with the kernel, as tests
                # enforce)
                n_keys = int(np.size(keys_u32))
                out, counts = ops.route_load_bulk(
                    keys_u32, self._fleet_dev, monitor.counts_dev, spec,
                    sample_shift=monitor.effective_shift(n_keys),
                )
                monitor.note_dispatch(counts, n_keys)
                return out
            return ops.route_bulk(keys_u32, self._fleet_dev, spec)
        # pre-fusion two-pass pipeline (benchmark baseline): buckets[N]
        # round-trips through HBM between two dispatches
        buckets = ops.lookup_bulk_dyn(keys_u32, self._n_dev, spec)
        return memento_remap_table(
            keys_u32,
            buckets,
            self._fleet_dev.packed,
            self._fleet_dev.table,
            self._fleet_dev.state,
            n_words=self.spec.n_words,
        )

    def _route_sharded(self, keys_u32, block_rows: int) -> jax.Array:
        """Mesh-sharded dispatch: keys split over the mesh axis, fleet state
        replicated, ONE jitted shard_map executable (DESIGN.md §8)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        shape = keys_u32.shape
        flat = keys_u32.reshape(-1)
        total = flat.shape[0]
        pad = (-total) % self._n_shards
        owned = not isinstance(keys_u32, jax.Array)  # we upload -> we may donate
        if pad:
            with span("route.layout"):
                flat = (np.pad if isinstance(flat, np.ndarray) else jnp.pad)(
                    flat, (0, pad))
            owned = True
        if isinstance(flat, np.ndarray):
            # upload already sharded along the mesh axis — the executable
            # never has to re-lay it out, and the buffer is ours to donate
            with span("route.layout"):
                flat = jax.device_put(
                    flat, NamedSharding(self.mesh, P(self.spec.shard_axis))
                )
        route = self._sharded_route.get(block_rows)
        if route is None:
            route = ops.make_sharded_route(self.mesh, self._dispatch_spec(block_rows))
            self._sharded_route[block_rows] = route
        if self.spec.donate_keys and not owned:
            # donation consumes the buffer; never consume one the caller owns
            with span("route.layout"):
                flat = jnp.asarray(flat).copy()
        with span("route.launch") as s:
            if s:
                s.tag(rows=(total + pad) // self._n_shards // LANES,
                      block_rows=block_rows)
            out = route(flat, self._fleet_dev)
        if pad:
            # a ragged length cannot stay split over the axis; name the
            # (replicated) result sharding, which Explicit mesh axes require
            with span("route.layout"):
                out = out.at[:total].get(
                    out_sharding=NamedSharding(self.mesh, P()))
        return out.reshape(shape)

    def route_keys(self, keys) -> jax.Array:
        """Pre-hashed keys (any int array) -> int32 replica ids, on device.

        The hot path: ONE device dispatch (the engine's fused lookup +
        table-divert kernel; one jitted shard_map over the mesh when
        sharded), no host round-trip — input ``jax.Array``s stay on device
        and the result is returned as a ``jax.Array`` without
        synchronising.  Keys are truncated to u32, identical to what the
        engine's scalar u32 oracle does with wide keys.  Skips per-session
        movement bookkeeping; use ``route_batch`` for session-level
        observability, ``route_keys_np`` for numpy.
        """
        with span("route.call"):
            self._check_routable()
            keys_u32 = self._coerce_keys(keys)
            size = int(np.size(keys_u32))
            if size == 0:
                # zero-row batches have nothing to dispatch (and the kernel
                # grid cannot be empty) — answer with an empty result of
                # the right type
                return jnp.zeros(np.shape(keys_u32), dtype=jnp.int32)
            rows = -(-size // LANES)
            # tune for what one device actually sees: the per-shard row count
            block_rows = self._resolve_block_rows(-(-rows // self._n_shards))
            out = self._route(keys_u32, block_rows)
            self.stats.lookups += size
            return out

    def _route(self, keys_u32, block_rows: int) -> jax.Array:
        if self.mesh is not None:
            return self._route_sharded(keys_u32, block_rows)
        return self._dispatch(keys_u32, block_rows)

    def route_keys_np(self, keys) -> np.ndarray:
        """Numpy-in/numpy-out convenience wrapper around ``route_keys``."""
        return np.asarray(self.route_keys(keys))

    def route_ids(self, session_ids) -> jax.Array:
        """Raw u64 int session ids -> int32 replica ids, ONE fused dispatch.

        The device ingest path (DESIGN.md §9): ids are split into u32 halves
        on the host (two cheap vectorised views) and the splitmix64 session
        hash, the engine's lookup and the table divert all run inside
        the SAME kernel — the ``keys[N]`` array the pre-hash path builds
        never exists.  Bit-exact with ``route_keys(hash_session_ids(ids))``.
        Single-host only (mesh users pre-hash and call ``route_keys``);
        skips movement bookkeeping like ``route_keys``.
        """
        if self.mesh is not None:
            raise ValueError(
                "route_ids is single-host only; under a mesh pre-hash with "
                "hash_session_ids and call route_keys"
            )
        self._check_routable()
        ids = np.ascontiguousarray(session_ids, dtype=np.uint64)
        if ids.size == 0:
            return jnp.zeros(ids.shape, dtype=jnp.int32)
        lo, hi = bits.np_split64(ids)
        rows = -(-int(ids.size) // LANES)
        block_rows = self._resolve_block_rows(rows)
        out = ops.route_ingest_bulk(
            lo, hi, self._fleet_dev, self._dispatch_spec(block_rows)
        )
        self.stats.lookups += int(ids.size)
        return out

    def route_batch(self, session_ids) -> np.ndarray:
        """Session ids (str/int) -> int32 replica ids, one device round-trip.

        The whole request path is batched (DESIGN.md §9): ids are hashed by
        the vectorised ``hash_session_ids`` (padded byte-matrix FNV-1a for
        strings, ``np_mix64`` for ints — bit-exact with the scalar
        ``session_key``), routed in one fused device dispatch, and movement
        bookkeeping lands in the bulk open-addressing ``SessionStore`` — no
        per-session Python anywhere, so ingest keeps up with the device
        rate instead of capping it.  For pre-hashed keys call ``route_keys``
        directly; for raw u64 int ids ``route_ids`` additionally fuses the
        hash into the routing kernel itself.
        """
        keys = hash_session_ids(session_ids)
        if keys.size == 0:
            return np.empty(keys.shape, dtype=np.int32)
        out = self.route_keys_np(keys)
        self.scalar.note_routes(keys, out)
        return out

    def route(self, session_id) -> int:
        """Scalar lookup through the control plane (bit-exact with the batch)."""
        return self.scalar.route(session_id)

    # -- fleet events --------------------------------------------------------
    # Each event mutates the scalar control plane (removed set + O(1)
    # replacement-table swaps), then refreshes the device state: fail/recover
    # flip one bit + re-pin the few-KiB table; scale-up re-pins table +
    # scalars; scale-down resyncs (tombstone GC can clear bits).
    def scale_up(self) -> int:
        if self.domain.total_count >= self.spec.capacity:
            raise ValueError(
                f"fleet at device-table capacity ({self.spec.capacity}); "
                "construct BatchRouter with a larger capacity"
            )
        r = self.scalar.scale_up()
        self._epoch += 1
        self._put_state()
        return r

    def scale_down(self) -> int:
        r = self.scalar.scale_down()
        self._epoch += 1
        self._resync_device_state()
        return r

    def fail(self, replica: int) -> None:
        self.scalar.fail(replica)
        self._epoch += 1
        if replica in self.domain.removed:
            self._set_removed_bit(replica, True)
        else:
            # failing the LAST slot is a true LIFO removal in the control
            # plane (slot space shrinks, tombstones may GC) — resync wholesale
            self._resync_device_state()

    def recover(self, replica: int) -> None:
        self.scalar.recover(replica)
        self._epoch += 1
        self._set_removed_bit(replica, False)

    @property
    def alive(self) -> int:
        return self.scalar.alive

    @property
    def routing_epoch(self) -> int:
        """Fleet-event counter: the epoch the next dispatch routes under."""
        return self._epoch
