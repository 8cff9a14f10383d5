"""Observability tier: registry, spans, device load pass, alarms, export.

Covers DESIGN.md §15 — the metric registry semantics, the instrumented
fused route (bit-exactness + bincount parity + the zero-upload drain
protocol), the theory-bound alarms (balance envelope, delta/n disruption
bound), the span trace ring, JSON/Prometheus exposition, the certifier's
``observability/load_pass`` target and the lazy top-level exports.
"""
import numpy as np
import pytest

import repro
from repro.core.bulk import RouterSpec
from repro.observability import (
    BalanceDriftAlarm,
    DisruptionBoundAlarm,
    LoadConfig,
    LoadMonitor,
    MetricsRegistry,
    SpanTrace,
    disruption_bound,
    expected_peak_over_mean,
    snapshot,
    to_json,
    to_prometheus,
)
from repro.serving.batch_router import BatchRouter
from repro.serving.streaming import VirtualClockUs

ENGINES = ("binomial", "jump")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    clock = VirtualClockUs()
    reg = MetricsRegistry(clock=clock)
    c = reg.counter("reqs_total", tenant="a")
    c.inc()
    c.inc(4)
    clock.advance_us(10)
    g = reg.gauge("depth")
    g.set(7)
    h = reg.histogram("lat_us", bounds=(10, 100))
    for v in (5, 50, 500):
        h.observe(v)
    assert c.value == 5
    assert g.value == 7
    assert h.count == 3
    assert h.sum == 555
    assert h.bucket_counts == [1, 1, 1]
    assert h.mean == pytest.approx(185.0)
    # identity: same (name, labels) -> same object
    assert reg.counter("reqs_total", tenant="a") is c
    assert reg.counter("reqs_total", tenant="b") is not c


def test_counter_rejects_negative_and_kind_is_pinned():
    reg = MetricsRegistry()
    reg.counter("x_total").inc()
    with pytest.raises(ValueError):
        reg.counter("x_total").inc(-1)
    with pytest.raises(ValueError):
        reg.gauge("x_total")  # name already pinned as a counter family


def test_histogram_bounds_pinned_at_first_creation():
    reg = MetricsRegistry()
    h = reg.histogram("lat", bounds=(1, 2))
    assert reg.histogram("lat") is h  # later callers inherit the bounds
    with pytest.raises(ValueError):
        reg.histogram("lat", bounds=(1, 2, 3))


def test_family_and_total_aggregate_views():
    reg = MetricsRegistry()
    reg.counter("shed_total", tenant="a", reason="late").inc(2)
    reg.counter("shed_total", tenant="b", reason="late").inc(3)
    reg.counter("shed_total", tenant="a", reason="rate").inc(5)
    assert reg.total("shed_total") == 10
    assert reg.total("shed_total", tenant="a") == 7
    assert reg.total("shed_total", reason="late") == 5
    assert len(reg.family("shed_total")) == 3
    assert reg.total("never_seen") == 0


def test_registry_timestamps_come_from_the_injected_clock():
    clock = VirtualClockUs()
    reg = MetricsRegistry(clock=clock)
    c = reg.counter("ticks_total")
    clock.advance_us(123)
    c.inc()
    assert c.last_update_us == 123


# ---------------------------------------------------------------------------
# span trace
# ---------------------------------------------------------------------------


def test_span_trace_ring_and_monotone_counts():
    t = SpanTrace(capacity=4)
    for i in range(10):
        t.record("request", i, i + 1, tenant="a", replica=i % 3)
    t.record("admit", 100, 100)
    assert t.count("request") == 10  # totals survive ring recycling
    assert t.count("admit") == 1
    assert t.count() == 11
    assert t.dropped == 7
    retained = t.spans("request")
    assert len(retained) + len(t.spans("admit")) == 4
    # oldest-first within the ring
    starts = [s.t_start_us for s in retained]
    assert starts == sorted(starts)
    span = retained[-1]
    assert span.duration_us == 1
    assert span.tag("replica") == 9 % 3
    assert t.spans(tenant="nobody") == []


# ---------------------------------------------------------------------------
# instrumented route + drain protocol
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_instrumented_route_bit_exact_and_bincount_parity(engine):
    spec = RouterSpec(engine=engine, capacity=64, omega=16)
    plain = BatchRouter(12, spec)
    router = BatchRouter(12, spec)
    for r in (plain, router):
        r.fail(3)
        r.fail(7)
    mon = LoadMonitor(router, config=LoadConfig(drain_every=1 << 30))
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 1 << 32, size=4096, dtype=np.uint32)
    expect = np.asarray(plain.route_keys(keys))
    got = np.asarray(router.route_keys(keys))
    np.testing.assert_array_equal(got, expect)
    window = mon.drain()
    np.testing.assert_array_equal(
        window, np.bincount(expect, minlength=router.capacity).astype(np.uint32)
    )
    assert mon.total_keys == keys.size


@pytest.mark.parametrize("engine", ENGINES)
def test_drain_cadence_and_zero_upload_reset(engine):
    router = BatchRouter(8, engine=engine)
    mon = LoadMonitor(router, config=LoadConfig(drain_every=3))
    keys = np.arange(256, dtype=np.uint32)
    for _ in range(2):
        router.route_keys(keys)
    assert mon.drains == 0  # below the cadence: accumulating on device
    router.route_keys(keys)
    assert mon.drains == 1  # third batch triggered the window drain
    assert mon.total_keys == 3 * keys.size
    # the reset re-points at the pinned zeros buffer: no upload happened
    assert mon.counts_dev is mon._zeros_dev
    assert int(np.asarray(mon.counts_dev).sum()) == 0
    assert mon.metrics.total("load_keys_total") == 3 * keys.size
    assert mon.metrics.gauge("load_peak_over_mean").value >= 1.0
    mon.reset()
    assert mon.total_keys == 0 and not mon.totals.any()


def test_detach_restores_uninstrumented_dispatch():
    router = BatchRouter(8, engine="binomial")
    mon = LoadMonitor(router, config=LoadConfig(drain_every=1 << 30))
    keys = np.arange(128, dtype=np.uint32)
    router.route_keys(keys)
    mon.detach()
    router.route_keys(keys)
    mon.drain()
    assert mon.total_keys == keys.size  # second batch was not accumulated


@pytest.mark.parametrize("engine", ENGINES)
def test_sampled_accumulate_is_scaled_stride_bincount(engine):
    """Above the exact cutoff the accumulator holds the deterministic
    ``[::2**shift]`` stride bincount at weight ``2**shift`` (key units),
    mixing coherently with exact batches in the same window — and the
    replica ids stay bit-exact with the bare route."""
    plain = BatchRouter(12, engine=engine)
    router = BatchRouter(12, engine=engine)
    mon = LoadMonitor(
        router,
        config=LoadConfig(
            drain_every=1 << 30, exact_cutoff=1024, sample_shift=3
        ),
    )
    rng = np.random.default_rng(11)
    bulk = rng.integers(0, 1 << 32, size=4096, dtype=np.uint32)
    small = rng.integers(0, 1 << 32, size=512, dtype=np.uint32)
    cap = router.capacity
    expect_bulk = np.asarray(plain.route_keys(bulk))
    expect_small = np.asarray(plain.route_keys(small))
    np.testing.assert_array_equal(np.asarray(router.route_keys(bulk)), expect_bulk)
    np.testing.assert_array_equal(np.asarray(router.route_keys(small)), expect_small)
    window = mon.drain().astype(np.int64)
    scaled = np.bincount(expect_bulk[::8], minlength=cap) * 8
    exact = np.bincount(expect_small, minlength=cap)
    np.testing.assert_array_equal(window, scaled + exact)
    # the stride estimate stays in key units: totals sum to the key count
    assert int(window.sum()) == bulk.size + small.size


def test_effective_shift_honors_exact_cutoff():
    router = BatchRouter(8, engine="binomial")
    mon = LoadMonitor(
        router, config=LoadConfig(exact_cutoff=1 << 15, sample_shift=6)
    )
    assert mon.effective_shift(1 << 15) == 0
    assert mon.effective_shift((1 << 15) + 1) == 6
    mon.detach()


def test_load_config_rejects_bad_sampling_knobs():
    with pytest.raises(ValueError, match="sample_shift"):
        LoadConfig(sample_shift=-1)
    with pytest.raises(ValueError, match="exact_cutoff"):
        LoadConfig(exact_cutoff=-1)


# ---------------------------------------------------------------------------
# theory-bound alarms
# ---------------------------------------------------------------------------


def test_envelope_helpers():
    assert expected_peak_over_mean(0, 8) == 1.0
    assert expected_peak_over_mean(1 << 20, 1) == 1.0
    e = expected_peak_over_mean(1 << 20, 64)
    assert 1.0 < e < 1.1
    assert disruption_bound(1, 16, 16, slack=2.0) == pytest.approx(0.125)
    assert disruption_bound(100, 4, 4, slack=2.0) == 1.0  # capped


def test_balance_alarm_fires_on_skew_and_holds_on_uniform():
    alarms = []
    router = BatchRouter(8, engine="binomial")
    mon = LoadMonitor(
        router,
        config=LoadConfig(drain_every=1 << 30, min_alarm_keys=100),
        on_alarm=alarms.append,
    )
    alive = mon._alive_slots()
    # uniform totals: comfortably inside the envelope
    mon.totals[alive] = 1_000
    mon._check_balance(mon.peak_over_mean(alive), alive)
    assert alarms == []
    # all the load on one shard: peak/mean == n_alive, way outside
    mon.totals[:] = 0
    mon.totals[alive[0]] = 8_000
    ratio = mon.peak_over_mean(alive)
    assert ratio == pytest.approx(len(alive))
    mon._check_balance(ratio, alive)
    assert len(alarms) == 1
    alarm = alarms[0]
    assert isinstance(alarm, BalanceDriftAlarm)
    assert alarm.peak_over_mean == pytest.approx(ratio)
    assert alarm.n_alive == len(alive)
    assert alarm.peak_over_mean > alarm.threshold > alarm.expected
    assert mon.metrics.total("balance_alarms_total") == 1


def test_balance_alarm_raises_without_callback():
    router = BatchRouter(4, engine="binomial")
    mon = LoadMonitor(
        router, config=LoadConfig(drain_every=1 << 30, min_alarm_keys=1)
    )
    alive = mon._alive_slots()
    mon.totals[alive[0]] = 5_000
    with pytest.raises(BalanceDriftAlarm, match="peak/mean"):
        mon._check_balance(mon.peak_over_mean(alive), alive)


def test_disruption_alarm_fires_on_seeded_pathological_remap():
    alarms = []
    router = BatchRouter(16, engine="binomial")
    mon = LoadMonitor(
        router,
        config=LoadConfig(drain_every=1 << 30, n_probe=256),
        on_alarm=alarms.append,
    )
    prev = np.zeros(256, np.int32)
    # a full remap after ONE membership event: moved fraction 1.0 vs the
    # delta/n bound 2/16 = 0.125 — the pathological case the bound exists
    # to catch (a naive mod-N rehash moves ~everything per event)
    moved = mon.tracker.observe(prev, prev + 1, 1, 16, 16, epoch=9)
    assert moved == 1.0
    assert len(alarms) == 1
    alarm = alarms[0]
    assert isinstance(alarm, DisruptionBoundAlarm)
    assert alarm.moved_fraction == 1.0
    assert alarm.bound == pytest.approx(0.125)
    assert alarm.epoch == 9
    assert mon.metrics.gauge("load_moved_fraction").value == 1.0
    assert mon.metrics.total("disruption_alarms_total") == 1
    # a compliant window: one shard's share moved, inside the bound
    now = prev.copy()
    now[:16] = 1
    mon.tracker.observe(prev, now, 1, 16, 16)
    assert len(alarms) == 1  # no new alarm


@pytest.mark.parametrize("engine", ENGINES)
def test_live_tracker_stays_inside_bound_on_single_fail(engine):
    alarms = []
    router = BatchRouter(16, engine=engine)
    mon = LoadMonitor(
        router, config=LoadConfig(drain_every=1 << 30), on_alarm=alarms.append
    )
    router.route_keys(np.arange(64, dtype=np.uint32))
    mon.drain()  # baselines the probe routes
    router.fail(5)
    router.route_keys(np.arange(64, dtype=np.uint32))
    mon.drain()  # epoch advanced: live moved-fraction check
    assert alarms == []
    frac = mon.metrics.gauge("load_moved_fraction").value
    bound = mon.metrics.gauge("load_moved_bound").value
    assert 0.0 < frac <= bound  # the fail's share moved, within delta/n


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------


def _small_stack():
    clock = VirtualClockUs()
    reg = MetricsRegistry(clock=clock)
    reg.counter("served_total", tenant="a").inc(3)
    reg.gauge("depth").set(2)
    reg.histogram("lat_us", bounds=(10, 100)).observe(42)
    trace = SpanTrace(capacity=8)
    trace.record("request", 0, 42, tenant="a")
    return reg, trace


def test_snapshot_and_json_shape():
    reg, trace = _small_stack()
    snap = snapshot(reg, trace=trace)
    series = {(s["name"], tuple(sorted(s["labels"].items()))): s
              for s in snap["metrics"]}
    assert series[("served_total", (("tenant", "a"),))]["value"] == 3
    hist = series[("lat_us", ())]
    assert hist["count"] == 1 and hist["sum"] == 42
    assert hist["bucket_counts"] == [0, 1, 0]
    assert snap["trace"]["recorded"] == 1
    assert snap["trace"]["spans"][0]["tenant"] == "a"
    text = to_json(reg, trace=trace)
    assert to_json(reg, trace=trace) == text  # deterministic


def test_prometheus_exposition():
    reg, _ = _small_stack()
    text = to_prometheus(reg)
    assert "# TYPE served_total counter" in text
    assert 'served_total{tenant="a"} 3' in text
    assert "# TYPE depth gauge" in text
    assert "# TYPE lat_us histogram" in text
    assert 'lat_us_bucket{le="100"} 1' in text
    assert 'lat_us_bucket{le="+Inf"} 1' in text
    assert "lat_us_count 1" in text and "lat_us_sum 42" in text


# ---------------------------------------------------------------------------
# certifier + public surface
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_certifier_load_pass_green(engine):
    from repro.analysis.certify import certify_load_pass

    rep = certify_load_pass(engine)
    assert rep.target == "observability/load_pass"
    assert {c.invariant: c.status for c in rep.checks} == {
        "while-free": "pass",
        "unroll-affine": "pass",
        "dtype-closed": "pass",
        "callback-free": "pass",
        "transfer-count": "pass",
    }


def test_lazy_top_level_exports():
    for name in (
        "MetricsRegistry",
        "LoadMonitor",
        "LoadConfig",
        "SpanTrace",
        "BalanceDriftAlarm",
        "DisruptionBoundAlarm",
        "route_load_bulk",
    ):
        assert name in repro.__all__
        assert getattr(repro, name) is not None
    with pytest.raises(AttributeError):
        repro.no_such_export
