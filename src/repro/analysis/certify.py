"""Layer 1 — the jaxpr certifier: machine-check the O(1) contract of every
registered device engine (DESIGN.md §11).

For each ``BULK_ENGINES`` entry the certifier traces the fused route and
the fused u64-id ingest — BOTH the pure-jnp mirrors and the Pallas kernels
(the kernel body jaxpr is reached by tracing the ``interpret=True``
lowering: the ``pallas_call`` equation carries the body as a sub-jaxpr, so
one recursive walk covers wrapper and kernel) — and the jnp dynamic-n
lookup to closed jaxprs and enforces, per target:

* ``while-free``       — no ``while`` primitive anywhere (incl. ``pjit`` /
  ``cond`` / ``scan`` / ``pallas_call`` sub-jaxprs).  ``scan`` is fine (its
  trip count is static); ``while_loop`` is the primitive whose trip count
  *can* depend on key data — the pre-PR-3 storm-cliff bug class.  Waivable
  via ``repro.analysis.markers.constant_time_waiver`` for paper-faithful
  baselines; the waiver reason lands in the report.
* ``unroll-affine``    — the jaxpr equation count is exactly affine in the
  ω unroll bound: tracing at ω, ω+1, ω+2 must yield equal first
  differences.  This proves the unroll depth is exactly ω (a hidden
  O(ω²) blow-up or a loop keyed on anything else breaks linearity) and
  records the per-iteration op cost; an absolute equation budget bounds
  the constant term.
* ``dtype-closed``     — every equation output dtype stays in the engine's
  allowed set (u32-limb arithmetic: uint32 / int32 / float32 / bool).
  Traced under ``enable_x64`` so a genuine f64 leak or a weak-type
  promotion to 64-bit surfaces instead of being silently clamped to 32-bit
  by the default config.
* ``callback-free``    — no host callbacks (``pure_callback`` /
  ``io_callback`` / ``debug_callback`` / ``debug_print``): a callback is a
  device->host sync, i.e. unbounded latency on the hot path.
* ``transfer-count``   — exactly the declared number of ``device_put``
  equations (0 for every engine: fleet state is pinned at event time, the
  hot path must never re-upload).

The certifier is pure tracing — no compilation, no execution — so it runs
in seconds and gates CI on every push.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterable, Iterator, Optional

import jax
import jax.extend.core as jax_core
import numpy as np

from repro.analysis.markers import waivers_of
from repro.analysis.report import (
    FAIL,
    PASS,
    SKIPPED,
    WAIVED,
    CheckResult,
    Report,
    TargetReport,
)

#: primitives that are host callbacks (device->host syncs) in disguise
_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback", "debug_print"}

#: primitives that move data between host and device
_TRANSFER_PRIMS = {"device_put"}


@dataclasses.dataclass(frozen=True)
class EngineContract:
    """The declared invariants one engine is certified against.

    omega             the ω unroll bound certification traces at (shared
                      with ``RouterSpec.omega`` — the serving default)
    capacity          fleet slot-space bound used for the trace operands
    batch             number of keys in the traced batch (shape only —
                      values never matter to a trace)
    block_rows        Pallas tiling for the kernel-path trace (small, so
                      the select cascades stay cheap to trace)
    allowed_dtypes    closure set for ``dtype-closed``
    device_transfers  declared ``device_put`` count (0 = hot path never
                      re-uploads state)
    max_eqns          absolute equation budget at ω (catches constant-term
                      blow-ups that affinity alone would pass)
    """

    omega: int = 16
    capacity: int = 64
    batch: int = 2048
    block_rows: int = 8
    allowed_dtypes: frozenset = frozenset({"uint32", "int32", "float32", "bool"})
    device_transfers: int = 0
    max_eqns: int = 8192


#: per-engine overrides of the default contract (empty = every engine is
#: held to the same strict default; a future engine with, say, a declared
#: f32 LUT upload would override ``device_transfers`` HERE, visibly)
CONTRACTS: dict[str, EngineContract] = {}


def contract_for(engine: str) -> EngineContract:
    return CONTRACTS.get(engine, EngineContract())


# ---------------------------------------------------------------------------
# recursive jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(params: dict) -> Iterator[jax_core.Jaxpr]:
    """Yield every sub-jaxpr found in an equation's params — covers pjit
    (``jaxpr``), cond (``branches``), while (``cond_jaxpr``/``body_jaxpr``),
    scan (``jaxpr``), pallas_call (``jaxpr`` — the kernel body) and any
    future primitive that follows the same convention."""
    for value in params.values():
        items = value if isinstance(value, (tuple, list)) else (value,)
        for item in items:
            if isinstance(item, jax_core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jax_core.Jaxpr):
                yield item


def iter_eqns(jaxpr: jax_core.Jaxpr) -> Iterator[jax_core.JaxprEqn]:
    """Depth-first walk over every equation, recursing into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _eqn_dtypes(eqn: jax_core.JaxprEqn) -> Iterator[str]:
    for var in eqn.outvars:
        aval = getattr(var, "aval", None)
        dtype = getattr(aval, "dtype", None)
        if dtype is not None:
            yield str(dtype)


# ---------------------------------------------------------------------------
# per-target certification
# ---------------------------------------------------------------------------


def certify_callable(
    engine: str,
    target: str,
    tracer: Callable[[int], jax_core.ClosedJaxpr],
    *,
    contract: Optional[EngineContract] = None,
    waivers: Optional[dict] = None,
    check_affine: bool = True,
) -> TargetReport:
    """Certify one traced callable against the contract.

    ``tracer(omega)`` must return the closed jaxpr of the target traced at
    that unroll bound (``certify_engine`` builds these per datapath; tests
    hand in fixture engines the same way).  ``waivers`` maps invariant name
    -> allowlist reason (see ``repro.analysis.markers``).
    """
    contract = contract or EngineContract()
    waivers = waivers or {}
    report = TargetReport(engine=engine, target=target)

    with jax.enable_x64(True):
        base = tracer(contract.omega)
        eqns = list(iter_eqns(base.jaxpr))
        counts = [len(eqns)]
        if check_affine:
            for extra in (1, 2):
                counts.append(
                    sum(1 for _ in iter_eqns(tracer(contract.omega + extra).jaxpr))
                )

    # -- while-free ---------------------------------------------------------
    whiles = [e for e in eqns if e.primitive.name == "while"]
    if not whiles:
        report.checks.append(
            CheckResult("while-free", PASS, "no while primitives in the trace")
        )
    elif "while-free" in waivers:
        report.checks.append(
            CheckResult(
                "while-free",
                WAIVED,
                f"{len(whiles)} while primitive(s), explicitly allowlisted",
                waiver=waivers["while-free"],
            )
        )
    else:
        report.checks.append(
            CheckResult(
                "while-free",
                FAIL,
                f"{len(whiles)} while primitive(s) — trip count may depend "
                "on key data (the storm-cliff bug class); unroll the loop "
                "to a static bound or add an explicit constant_time_waiver",
            )
        )

    # -- unroll-affine ------------------------------------------------------
    if not check_affine:
        report.checks.append(
            CheckResult(
                "unroll-affine", SKIPPED, "target is not ω-parameterised"
            )
        )
    else:
        d1 = counts[1] - counts[0]
        d2 = counts[2] - counts[1]
        if d1 != d2 or d1 < 0:
            report.checks.append(
                CheckResult(
                    "unroll-affine",
                    FAIL,
                    f"eqn counts {counts} at ω={contract.omega}..+2 are not "
                    f"affine (first differences {d1} vs {d2}) — unroll depth "
                    "is not exactly ω",
                )
            )
        elif counts[0] > contract.max_eqns:
            report.checks.append(
                CheckResult(
                    "unroll-affine",
                    FAIL,
                    f"{counts[0]} eqns at ω={contract.omega} exceeds the "
                    f"{contract.max_eqns}-eqn budget",
                )
            )
        else:
            report.checks.append(
                CheckResult(
                    "unroll-affine",
                    PASS,
                    f"{counts[0]} eqns at ω={contract.omega}, exactly "
                    f"+{d1}/iteration",
                )
            )

    # -- dtype-closed -------------------------------------------------------
    bad = sorted(
        {
            f"{e.primitive.name}->{d}"
            for e in eqns
            for d in _eqn_dtypes(e)
            if d not in contract.allowed_dtypes
        }
    )
    if bad:
        report.checks.append(
            CheckResult(
                "dtype-closed",
                FAIL,
                f"dtypes outside {sorted(contract.allowed_dtypes)}: "
                + ", ".join(bad[:8]),
            )
        )
    else:
        report.checks.append(
            CheckResult(
                "dtype-closed",
                PASS,
                f"all outputs in {sorted(contract.allowed_dtypes)} "
                "(traced under x64)",
            )
        )

    # -- callback-free ------------------------------------------------------
    callbacks = sorted(
        {
            e.primitive.name
            for e in eqns
            if e.primitive.name in _CALLBACK_PRIMS
            or "callback" in e.primitive.name
        }
    )
    report.checks.append(
        CheckResult("callback-free", FAIL, f"host callbacks: {callbacks}")
        if callbacks
        else CheckResult("callback-free", PASS, "no host callbacks")
    )

    # -- transfer-count -----------------------------------------------------
    transfers = sum(1 for e in eqns if e.primitive.name in _TRANSFER_PRIMS)
    if transfers != contract.device_transfers:
        report.checks.append(
            CheckResult(
                "transfer-count",
                FAIL,
                f"{transfers} device_put eqns, contract declares "
                f"{contract.device_transfers}",
            )
        )
    else:
        report.checks.append(
            CheckResult(
                "transfer-count",
                PASS,
                f"exactly {contract.device_transfers} device transfers",
            )
        )
    return report


# ---------------------------------------------------------------------------
# engine target construction
# ---------------------------------------------------------------------------


def _fleet_operands(contract: EngineContract):
    """Representative fixed-shape fleet operands (values are irrelevant to
    a trace; shapes/dtypes mirror ``FleetState.pack`` for the capacity)."""
    from repro.core.memento_jax import pack_removed_mask, table_width

    packed = pack_removed_mask([], contract.capacity)
    table = np.zeros((1, table_width(contract.capacity)), np.int32)
    state = np.array(
        [min(8, contract.capacity), min(8, contract.capacity)], np.uint32
    )
    keys = np.zeros((contract.batch,), np.uint32)
    return keys, packed, table, state


def engine_targets(
    engine_name: str, contract: EngineContract
) -> list[tuple[str, Callable[[int], jax_core.ClosedJaxpr], dict]]:
    """(target label, tracer, waivers) for every datapath of one engine —
    jnp mirrors and Pallas kernels (via ``interpret=True`` lowering)."""
    from repro.core.memento_jax import mask_words
    from repro.core.registry import make_bulk

    eng = make_bulk(engine_name)
    keys, packed, table, state = _fleet_operands(contract)
    lo = hi = keys
    n = np.uint32(min(8, contract.capacity))
    n_words = mask_words(contract.capacity)
    n_slots = contract.capacity
    rows = contract.block_rows

    targets = []

    def add(label, fn, tracer):
        if fn is not None:
            targets.append((label, tracer, waivers_of(fn)))

    add(
        "route/jnp",
        eng.route,
        lambda om: jax.make_jaxpr(
            lambda k, p, t, s: eng.route(k, p, t, s, omega=om, n_words=n_words)
        )(keys, packed, table, state),
    )
    add(
        "ingest/jnp",
        eng.ingest,
        lambda om: jax.make_jaxpr(
            lambda a, b, p, t, s: eng.ingest(a, b, p, t, s, omega=om, n_words=n_words)
        )(lo, hi, packed, table, state),
    )
    add(
        "lookup_dyn/jnp",
        eng.lookup_dyn,
        lambda om: jax.make_jaxpr(lambda k, m: eng.lookup_dyn(k, m, omega=om))(keys, n),
    )
    add(
        "route/pallas",
        eng.route_pallas,
        lambda om: jax.make_jaxpr(
            lambda k, p, t, s: eng.route_pallas(
                k, p, t, s, n_words, n_slots, omega=om, block_rows=rows,
                interpret=True,
            )
        )(keys, packed, table, state),
    )
    add(
        "ingest/pallas",
        eng.ingest_pallas,
        lambda om: jax.make_jaxpr(
            lambda a, b, p, t, s: eng.ingest_pallas(
                a, b, p, t, s, n_words, n_slots, omega=om, block_rows=rows,
                interpret=True,
            )
        )(lo, hi, packed, table, state),
    )
    return targets


def certify_engine(
    engine_name: str, contract: Optional[EngineContract] = None
) -> list[TargetReport]:
    """Certify every datapath of one registered ``BULK_ENGINES`` entry."""
    contract = contract or contract_for(engine_name)
    return [
        certify_callable(
            engine_name, label, tracer, contract=contract, waivers=waivers
        )
        for label, tracer, waivers in engine_targets(engine_name, contract)
    ]


def certify_chain_baseline(
    contract: Optional[EngineContract] = None,
) -> TargetReport:
    """Certify the paper-faithful chain-mode remap — the one datapath that
    legitimately carries a ``lax.while_loop``, passing only through its
    explicit ``constant_time_waiver`` (the allowlist mechanism's live
    demonstration: remove the marker and the gate goes red)."""
    from repro.core.memento_jax import memento_remap

    contract = contract or EngineContract()
    keys = np.zeros((contract.batch,), np.uint32)
    buckets = np.zeros((contract.batch,), np.int32)
    mask = np.zeros((contract.capacity,), bool)

    def tracer(_om):
        return jax.make_jaxpr(
            lambda k, b, m, n, f: memento_remap(k, b, m, n, f)
        )(keys, buckets, mask, np.uint32(8), np.uint32(0))

    return certify_callable(
        "binomial",
        "chain/memento_remap",
        tracer,
        contract=contract,
        waivers=waivers_of(memento_remap),
        check_affine=False,  # the chain is while-bounded, not ω-unrolled
    )


def certify_lifecycle_route(
    engine_name: str, contract: Optional[EngineContract] = None
) -> TargetReport:
    """Certify the route entry EXACTLY as the serving tier dispatches it:
    a ``LifecycleManager``-wrapped ``BatchRouter`` with an active storm
    state (tombstones + coalesced recovery already applied).

    The lifecycle layer (detector poll, journaling, coalescing, degradation
    guards) is host-side control plane by design — this target proves it:
    the traced device computation reached through the wrapped router must
    satisfy the same invariants as the bare engine datapaths (no
    data-dependent loops, no host callbacks, zero hot-path uploads), i.e.
    the robustness machinery adds NOTHING to the device hot path.
    """
    contract = contract or contract_for(engine_name)
    keys = np.zeros((contract.batch,), np.uint32)

    def tracer(om):
        from repro.core.bulk import RouterSpec
        from repro.serving.batch_router import BatchRouter
        from repro.serving.lifecycle import LifecycleManager

        spec = RouterSpec(engine=engine_name, capacity=contract.capacity, omega=om)
        router = BatchRouter(8, spec)
        mgr = LifecycleManager(router)
        # a real storm, applied through the manager: tombstones present,
        # one coalesced device refresh behind us — the state the divert
        # path actually runs against
        mgr.apply([("fail", 1), ("fail", 3), ("recover", 1), ("fail", 5)])
        return jax.make_jaxpr(mgr.router.route_keys)(keys)

    return certify_callable(engine_name, "route/lifecycle", tracer, contract=contract)


def certify_streaming_route(
    engine_name: str, contract: Optional[EngineContract] = None
) -> TargetReport:
    """Certify the streaming tier's dispatch EXACTLY as a closed
    micro-batch runs it (DESIGN.md §14).

    The ``serving/streaming`` front end wraps the route in admission
    control, micro-batching, deadline shedding, circuit breakers and a
    placement-repair tick — ALL host-side control plane.  This target
    assembles the full streaming stack (manager + placement store +
    repairer + front end), drives real micro-batches through it into a
    storm state with a non-empty repair backlog, then traces the device
    computation one more closed batch would dispatch: it must be
    while-free, callback-free and transfer-free just like the bare engine
    — the whole streaming apparatus adds NOTHING to the device hot path.
    """
    contract = contract or contract_for(engine_name)
    keys = np.zeros((contract.batch,), np.uint32)

    def tracer(om):
        from repro.core.bulk import RouterSpec
        from repro.placement.store import StorePlacement
        from repro.serving.batch_router import BatchRouter
        from repro.serving.lifecycle import LifecycleManager, PlacementRepairer
        from repro.serving.streaming import (
            StreamConfig,
            StreamingFrontEnd,
            StreamRequest,
            VirtualClockUs,
        )

        spec = RouterSpec(engine=engine_name, capacity=contract.capacity, omega=om)
        router = BatchRouter(8, spec)
        mgr = LifecycleManager(router)
        store = StorePlacement(router, r=3)
        store.register(np.arange(64, dtype=np.uint32) * 2654435761)
        PlacementRepairer(store, mgr, budget_per_tick=4)
        clock = VirtualClockUs()
        fe = StreamingFrontEnd(
            mgr,
            store=store,
            config=StreamConfig(max_batch=8, service_bound_us=10_000),
            clock=clock,
        )
        # a real storm plus live streamed batches: the repairer backlog is
        # non-empty and the breaker board is armed — the state an in-flight
        # stream actually dispatches against
        mgr.apply([("fail", 1), ("fail", 3), ("recover", 1), ("fail", 5)])
        for i in range(8):
            fe.submit(
                StreamRequest(key=i * 40_503, deadline_us=clock.now_us() + 50_000)
            )
        clock.advance_us(2_000)
        fe.pump()
        fe.drain()
        return jax.make_jaxpr(mgr.router.route_keys)(keys)

    return certify_callable(
        engine_name, "serving/streaming", tracer, contract=contract
    )


#: the placement pass certifies affinity in the replication factor R, not ω
#: (ω is a fixed inner parameter of the one fused-route call): ``omega``
#: here is the BASE R the tracer varies — R, R+1, R+2
PLACEMENT_CONTRACT = EngineContract(omega=3)

#: fixed re-salt probe bound for the placement trace — FIXED while R varies,
#: so each additional replica column adds an identical op count (the serving
#: default ``max_resalt=None`` resolves to r, which would make the per-column
#: cost itself grow with r and is certified per-spec by the same tracer)
PLACEMENT_TRACE_MAX_RESALT = 4


#: zone count of the zoned placement trace: the deployments' three
#: availability zones (the zone-free pass is the ``zones=1`` trace)
PLACEMENT_TRACE_ZONES = 3


def certify_placement_route(
    engine_name: str, contract: Optional[EngineContract] = None,
    zones: int = 1,
) -> TargetReport:
    """Certify the R-way replicated placement pass (DESIGN.md §13).

    ``placement/route_replicas`` is the device pass of
    ``repro.placement.store``: ONE fused engine route over all R salted key
    families plus the bounded distinct-resolution probes.  The tracer
    varies the REPLICATION factor (R, R+1, R+2 — the contract's ``omega``
    field repurposed as the base R) at a fixed ω and a fixed probe bound:
    while-free, affine in R (each extra replica column adds exactly the
    same resolution op count; the broadcast route call is shape-independent
    in eqn count), u32-closed, zero transfers — the O(1)-per-replica
    contract, machine-checked like every other engine path.

    With ``zones > 1`` the zone fallback (§13.5) is traced too, over
    representative ``ZoneState`` operands, as
    ``placement/route_replicas/zoned``: each column's zone step is a fixed
    O(zones) select walk plus one zone-table gather, so R-affinity holds.
    """
    contract = contract or PLACEMENT_CONTRACT
    from repro.core.bulk import zone_width
    from repro.core.memento_jax import mask_words, table_width
    from repro.core.registry import make_bulk
    from repro.placement.store import route_replicas_impl

    eng = make_bulk(engine_name)
    keys, packed, table, state = _fleet_operands(contract)
    n_words = mask_words(contract.capacity)
    width = zone_width(contract.capacity, zones)
    zone_ops = () if zones == 1 else (
        np.zeros((1, table_width(zones * width)), np.int32),
        np.ones((2, zones), np.uint32),
    )

    def tracer(r):
        return jax.make_jaxpr(
            lambda k, p, t, s, *z: route_replicas_impl(
                k, p, t, s, *z, r=r, omega=16, n_words=n_words,
                max_resalt=PLACEMENT_TRACE_MAX_RESALT, route=eng.route,
                zones=zones, zone_width=width,
            )
        )(keys, packed, table, state, *zone_ops)

    label = "placement/route_replicas" + ("" if zones == 1 else "/zoned")
    return certify_callable(engine_name, label, tracer, contract=contract)


def certify_load_pass(
    engine_name: str, contract: Optional[EngineContract] = None
) -> TargetReport:
    """Certify the observability-instrumented route (DESIGN.md §15).

    ``observability/load_pass`` is the device pass of
    ``repro.observability.load``: the engine's fused route plus ONE
    in-bounds bincount accumulating per-shard key counts — the
    instrumented dispatch ``BatchRouter`` runs with a ``LoadMonitor``
    attached.  Traced at the monitor's default bulk-batch config
    (``LoadConfig().sample_shift``) — the exact path (shift 0) is a
    strict sub-graph of it (drop the stride slice).  Same contract as
    the bare route: while-free, ω-affine (the accumulate adds a constant
    term only), dtype-closed, callback-free, zero transfers — proving
    the load accumulator costs one fused reduction and adds NOTHING
    host-visible to the hot path.
    """
    contract = contract or contract_for(engine_name)
    from repro.core.memento_jax import mask_words
    from repro.core.registry import make_bulk
    from repro.observability.load import LoadConfig, route_with_load_impl

    eng = make_bulk(engine_name)
    keys, packed, table, state = _fleet_operands(contract)
    counts = np.zeros((contract.capacity,), np.uint32)
    n_words = mask_words(contract.capacity)
    shift = LoadConfig().sample_shift

    def tracer(om):
        return jax.make_jaxpr(
            lambda k, p, t, s, c: route_with_load_impl(
                k, p, t, s, c, omega=om, n_words=n_words, route=eng.route,
                sample_shift=shift,
            )
        )(keys, packed, table, state, counts)

    return certify_callable(
        engine_name, "observability/load_pass", tracer, contract=contract
    )


def certify_all(
    engines: Optional[Iterable[str]] = None, *, include_chain_baseline: bool = True
) -> Report:
    """Layer-1 certification of every (or the named) registered engine."""
    from repro.core.registry import BULK_ENGINES

    names = list(engines) if engines is not None else sorted(BULK_ENGINES)
    report = Report()
    for name in names:
        report.targets.extend(certify_engine(name))
        report.targets.append(certify_lifecycle_route(name))
        report.targets.append(certify_placement_route(name))
        report.targets.append(
            certify_placement_route(name, zones=PLACEMENT_TRACE_ZONES)
        )
        report.targets.append(certify_streaming_route(name))
        report.targets.append(certify_load_pass(name))
    if include_chain_baseline:
        report.targets.append(certify_chain_baseline())
    return report
