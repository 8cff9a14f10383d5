"""The program's spans (DESIGN.md §15.1): one ``span`` API whose two sinks
are the JAX profiler's trace and the ``SpanTrace`` ring.

The profiler tests record a real trace on the CPU and read the ``repro.*``
host events back from its ``.xplane.pb``: each bulk entry and one served
dispatch open the documented spans, nested as documented.  The ring tests
run on a ``VirtualClockUs``, where every duration is deterministic.
"""
import glob
import os
import warnings

import jax
import numpy as np
import pytest

from repro.observability import (
    SPAN_COLLECT,
    SPAN_DISPATCH,
    SPAN_LIFECYCLE_TICK,
    SPAN_REQUEST,
    SpanTrace,
    span,
)
from repro.observability import trace as trace_module
from repro.observability.trace import NULL_SPAN
from repro.placement.store import StorePlacement
from repro.serving.batch_router import BatchRouter
from repro.serving.lifecycle import LifecycleManager
from repro.serving.streaming import (
    LifecycleDispatch,
    MicroBatcher,
    StreamConfig,
    StreamingFrontEnd,
    StreamRequest,
    VirtualClockUs,
)

PREFIX = trace_module.PROFILER_PREFIX


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _profiled(tmp_path, fn) -> list[tuple[str, int, int, dict, int]]:
    """Run ``fn`` under the JAX profiler; the ``repro.*`` host events of the
    trace as ``(name, start ns, end ns, stats, line id)``."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    events = []
    # the stats' binding type warns once, when first built
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line_id, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        start = int(e.start_ns)
                        events.append((e.name[len(PREFIX):], start,
                                       start + int(e.duration_ns),
                                       dict(e.stats), line_id))
    return sorted(events, key=lambda e: e[1])


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(child, parent) -> bool:
    return (child[4] == parent[4] and parent[1] <= child[1]
            and child[2] <= parent[2])


def _children(events, parent):
    """The spans directly inside ``parent``."""
    inside = [e for e in events if e is not parent and _inside(e, parent)]
    return [e for e in inside
            if not any(o is not e and _inside(e, o) for o in inside)]


FAILED = (3, 17, 40)


def _router(zones=1):
    """A small router on the interpret-mode kernel; no node failed yet."""
    return BatchRouter(64, capacity=64, interpret=True, block_rows=8,
                       zones=zones)


def _stormed_manager(zones=1):
    mgr = LifecycleManager(_router(zones))
    for node in FAILED:
        mgr.fail(node)
    return mgr


KEYS = np.random.default_rng(5).integers(0, 1 << 32, 2048, dtype=np.uint32)


# ---------------------------------------------------------------------------
# the span API
# ---------------------------------------------------------------------------


def test_span_without_a_live_sink_is_the_null_span():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    s = span("anything", size=3)
    assert s is NULL_SPAN and not s
    with s as inner:
        inner.tag(ignored=1)


def test_span_records_into_the_ring_on_the_component_clock():
    clock = VirtualClockUs(start_us=100)
    ring = SpanTrace(capacity=8)
    with span("work", ring, clock.now_us, size=2) as s:
        assert s
        clock.advance_us(37)
        s.tag(shed=1)
    (rec,) = ring.spans("work")
    assert (rec.t_start_us, rec.t_end_us) == (100, 137)
    assert rec.tag("size") == 2 and rec.tag("shed") == 1


def test_span_into_a_ring_needs_a_clock():
    with pytest.raises(ValueError, match="clock"):
        span("work", SpanTrace(capacity=2))


def test_no_span_is_opened_with_the_profiler_off_and_no_ring(monkeypatch):
    mgr = _stormed_manager()
    router = mgr.router
    store = StorePlacement(router, r=3)
    ring = SpanTrace(capacity=64)
    StreamingFrontEnd(mgr, tracer=ring)

    def refuse(*args, **kwargs):
        raise AssertionError(f"a span was opened: {args[0]!r}")

    monkeypatch.setattr(trace_module, "_LiveSpan", refuse)
    jax.block_until_ready(router.route_keys(KEYS))
    jax.block_until_ready(store.place_keys(KEYS))
    assert ring.total == 0  # the router writes nothing into the ring


# ---------------------------------------------------------------------------
# the profiler sink: what each entry emits, nested as documented
# ---------------------------------------------------------------------------


def test_route_keys_spans_under_the_profiler(tmp_path):
    router = _stormed_manager().router
    jax.block_until_ready(router.route_keys(KEYS))  # compile outside the trace
    events = _profiled(tmp_path, lambda: jax.block_until_ready(
        router.route_keys(KEYS)))
    (call,) = _named(events, "route.call")
    # 2,048 keys are whole tiles: the layout runs inside the route program
    (launch,) = _children(events, call)
    assert launch[0] == "route.launch"
    assert launch[3] == {"rows": KEYS.size // 128, "block_rows": 8}
    assert len(events) == 2  # the traced kernel body opened no span


def test_place_keys_spans_under_the_profiler(tmp_path):
    store = StorePlacement(_stormed_manager().router, r=3)
    jax.block_until_ready(store.place_keys(KEYS))
    events = _profiled(tmp_path, lambda: jax.block_until_ready(
        store.place_keys(KEYS)))
    (call,) = _named(events, "route.call")
    (launch,) = _children(events, call)
    assert launch[0] == "route.launch"
    # six probes, and the divert's read of the slots table: the fleet is stormed
    assert launch[3] == {"rows": KEYS.size // 128, "reads": 7}
    assert len(events) == 2


def test_zoned_place_keys_tags_its_zones(tmp_path):
    mgr = _stormed_manager(zones=3)
    for node in set(range(2, 63, 3)) - set(FAILED):  # all of zone 2 of 3
        mgr.fail(node)
    store = StorePlacement(mgr.router, r=3, zones=3)
    jax.block_until_ready(store.place_keys(KEYS))
    events = _profiled(tmp_path, lambda: jax.block_until_ready(
        store.place_keys(KEYS)))
    (call,) = _named(events, "route.call")
    (launch,) = _children(events, call)
    assert launch[0] == "route.launch"
    assert launch[3] == {"rows": KEYS.size // 128, "reads": 9, "zones": 3,
                         "alive_zones": 2}
    assert len(events) == 2


def test_served_dispatch_spans_under_the_profiler(tmp_path):
    mgr = _stormed_manager()
    ring = SpanTrace(capacity=64)
    fe = StreamingFrontEnd(mgr, config=StreamConfig(max_batch=4), tracer=ring)
    fe.batcher.dispatch_fn(KEYS[:4]).result()  # compile outside the trace
    far = 2**62

    def serve():
        for key in KEYS[:4]:  # the fourth closes the batch
            fe.submit(StreamRequest(key=int(key), deadline_us=far))
        assert len(fe.drain()) == 4

    events = _profiled(tmp_path, serve)
    (dispatch,) = _named(events, SPAN_DISPATCH)
    assert dispatch[3]["size"] == 4 and dispatch[3]["shed"] == 0
    assert dispatch[3]["bound_us"] == fe.config.service_bound_us
    assert "wait_us_sum" in dispatch[3]
    assert [e[0] for e in _children(events, dispatch)] == [
        SPAN_LIFECYCLE_TICK, "upload", "route.call"]
    (tick,) = _named(events, SPAN_LIFECYCLE_TICK)
    assert [e[0] for e in _children(events, tick)] == ["detector.poll"]
    (call,) = _named(events, "route.call")
    assert [e[0] for e in _children(events, call)] == [
        "route.layout", "route.launch", "route.layout"]
    (collect,) = _named(events, SPAN_COLLECT)
    assert collect[1] >= dispatch[2]
    assert len(_named(events, "breakers.observe")) == 1  # the drain's
    # the ring holds the measured dispatch and collect of the same batch
    assert ring.count(SPAN_DISPATCH) == ring.count(SPAN_COLLECT) == 1
    assert ring.count(SPAN_REQUEST) == 4


# ---------------------------------------------------------------------------
# the ring sink under a virtual clock
# ---------------------------------------------------------------------------


def _advancing_dispatch(clock, us):
    """A device-free dispatch that takes ``us`` of virtual time."""

    class Handle:
        def __init__(self, reps):
            self._reps = reps

        def result(self):
            return self._reps, 0, "normal"

    def dispatch(keys):
        clock.advance_us(us)
        return Handle(np.asarray(keys, np.int64) % 4)

    return dispatch


def test_dispatch_span_ends_when_the_dispatch_returned():
    clock = VirtualClockUs()
    ring = SpanTrace(capacity=64)
    b = MicroBatcher(
        _advancing_dispatch(clock, 37),
        config=StreamConfig(max_batch=3, max_wait_us=1_000,
                            service_bound_us=1_000),
        clock=clock, service_model=lambda n: 500, tracer=ring,
    )
    b.submit(StreamRequest(key=1, deadline_us=10_000))
    clock.advance_us(100)
    b.submit(StreamRequest(key=2, deadline_us=10_000))
    clock.advance_us(100)
    b.submit(StreamRequest(key=3, deadline_us=10_000))  # closes at 200
    (d,) = ring.spans(SPAN_DISPATCH)
    assert (d.t_start_us, d.t_end_us) == (200, 237)  # not 200 + 500 or + 1000
    assert d.tag("size") == 3 and d.tag("shed") == 0
    assert d.tag("bound_us") == 1_000  # the declared bound, not the model's 500
    assert d.tag("wait_us_sum") == 200 + 100 + 0


def test_dispatch_span_counts_the_gate_shed():
    clock = VirtualClockUs()
    ring = SpanTrace(capacity=64)
    b = MicroBatcher(
        _advancing_dispatch(clock, 5),
        config=StreamConfig(max_batch=4, max_wait_us=1_000,
                            service_bound_us=1_500),
        clock=clock, service_model=lambda n: 1_500, tracer=ring,
    )
    b.submit(StreamRequest(key=1, deadline_us=1_700))  # feasible now only
    b.submit(StreamRequest(key=2, deadline_us=50_000))
    clock.advance_us(1_300)  # the close runs late for the first
    b.pump()
    (d,) = ring.spans(SPAN_DISPATCH)
    assert d.tag("size") == 1 and d.tag("shed") == 1
    assert d.tag("wait_us_sum") == 1_300
    assert d.duration_us == 5


def _virtual_run():
    clock = VirtualClockUs()
    router = BatchRouter(16, engine="binomial")
    mgr = LifecycleManager(router, clock=clock.seconds_view())
    ring = SpanTrace(capacity=1 << 10)
    fe = StreamingFrontEnd(
        mgr,
        config=StreamConfig(max_batch=8, max_wait_us=500, service_bound_us=800),
        clock=clock, dispatch_fn=LifecycleDispatch(mgr),
        service_model=lambda n: 300 + 10 * n, tracer=ring,
    )
    rng = np.random.default_rng(3)
    for _ in range(40):
        fe.submit(StreamRequest(key=int(rng.integers(0, 1 << 32)),
                                deadline_us=clock.now_us() + 20_000))
        clock.advance_us(int(rng.integers(20, 120)))
        fe.pump()
    fe.drain()
    return ring


def test_ring_spans_are_deterministic_under_a_virtual_clock():
    ring = _virtual_run()
    names = {s.name for s in ring.spans()}
    assert names == {SPAN_DISPATCH, SPAN_COLLECT, SPAN_LIFECYCLE_TICK, SPAN_REQUEST}
    assert ring.count(SPAN_REQUEST) == 40
    dispatches = ring.spans(SPAN_DISPATCH)
    assert ring.count(SPAN_COLLECT) == ring.count(SPAN_LIFECYCLE_TICK) == len(dispatches)
    assert sum(d.tag("size") for d in dispatches) == 40
    assert all(d.tag("shed") == 0 and d.tag("wait_us_sum") >= 0 for d in dispatches)
    assert _virtual_run().spans() == ring.spans()
