"""Streaming serving-tier benchmark: open-loop zipf multi-tenant load
through the micro-batching front end (DESIGN.md §14).

A zipf-skewed multi-tenant open-loop generator offers requests at FIXED
loads (fractions of the declared capacity) to a ``StreamingFrontEnd``
whose dispatch is the REAL lifecycle-wrapped fused router on the actual
device.  Timeline discipline:

* every closed batch routes through ``LifecycleDispatch`` and the
  materialisation block is wall-measured — the bench's service times are
  real device dispatch times, not a synthetic model;
* those measured times are replayed onto a ``VirtualClockUs`` timeline
  (clamped to the declared ``service_bound_us``, clamp count reported),
  so arrivals, batching windows, deadlines and shedding are exactly
  reproducible while the datapath cost is measured, not assumed.

Per engine the bench first CALIBRATES: it times real max-batch dispatches
and declares ``service_bound_us`` (the SLO capacity statement) as a
margin over the observed p95.  Declared capacity is then
``max_batch / service_bound_us`` requests/s and the load grid is fixed
multipliers of it — at least one point above capacity, per the record's
contract.  Each point reports p50/p99 served latency, goodput
(in-SLO served requests/s of virtual makespan), and the shed fraction.

Invariants the record must witness (gated by
``check_router_regression.py --serving-current``):

* shed fraction is 0 at every point at or below capacity;
* p99 served latency never exceeds ``slo_us + max_wait_us`` — an
  admitted-and-served request misses its deadline by at most one batch
  window (the streaming tier's core guarantee);
* shed fraction is monotone non-decreasing in offered load.

Full runs write the tracked ``BENCH_serving.json`` at the repo root;
``--smoke`` (CI) writes ``benchmarks/out/BENCH_serving_smoke.json``.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from benchmarks.common import emit, enable_compile_cache, rows_to_csv, write_bench_json

ENGINES = ("binomial", "jump")

N_SLOTS = 16
MAX_BATCH = 64
MAX_WAIT_US = 1_000
#: declared bound = BOUND_MARGIN x calibrated p95 (an SLO statement with
#: headroom for dispatch jitter, not a best-case measurement)
BOUND_MARGIN = 2.0
#: per-request SLO, in declared service bounds
SLO_BOUNDS = 4
#: below-capacity offered loads, as multiples of DECLARED capacity
LOAD_MULTS_BELOW = (0.5, 0.9)
#: the overload point is anchored on MEASURED capacity (max_batch / p50):
#: declared capacity is a deliberately padded SLO statement, so a fixed
#: multiple of it can still sit inside what the device actually sustains —
#: the overload point must exceed the real datapath, not the declaration
OVERLOAD_X_MEASURED = 2.0
#: the overload point's arrival span, in SLO horizons (slo + one window):
#: shedding only starts once the backlog outgrows the horizon, so the run
#: must cover several of them to reach the shedding steady state
OVERLOAD_SPAN_HORIZONS = 8

N_TENANTS = 8
ZIPF_S = 1.1
KEYSPACE_PER_TENANT = 1 << 14

N_REQ_FULL = 3_000
N_REQ_SMOKE = 400
CAL_FULL = 40
CAL_SMOKE = 12


class _MeasuredDispatch:
    """Real fused dispatch, wall-measured.

    Each closed batch goes through the lifecycle-wrapped router on the
    device and is materialised HERE, inside the dispatch call, so the
    measured block is the true device cost.  The measurement (clamped to
    the declared bound so the deadline guarantee stays well-defined)
    becomes that dispatch's service time on the virtual timeline via the
    ``service_model`` hook.
    """

    def __init__(self, mgr, bound_us: int):
        from repro.serving.streaming import LifecycleDispatch

        self._inner = LifecycleDispatch(mgr)
        self.bound_us = int(bound_us)
        self.samples_us: list[int] = []
        self.clamped = 0
        self.last_us = 1

    def __call__(self, keys_u32):
        # pad to the fixed dispatch shape: micro-batches close at varying
        # sizes, and every new shape would recompile the fused route —
        # fixed-shape dispatch is the serving norm and keeps the measured
        # block a datapath cost, not an XLA compile
        n = len(keys_u32)
        padded = np.zeros(MAX_BATCH, dtype=np.uint32)
        padded[:n] = keys_u32
        t0 = time.perf_counter_ns()
        replicas, epoch, mode = self._inner(padded).result()
        payload = (replicas[:n], epoch, mode)
        us = max(1, (time.perf_counter_ns() - t0) // 1_000)
        self.samples_us.append(int(us))
        if us > self.bound_us:
            self.clamped += 1
            us = self.bound_us
        self.last_us = int(us)
        return _Done(payload)

    def service_model(self, _n: int) -> int:
        return self.last_us


class _Done:
    def __init__(self, payload):
        self._payload = payload

    def result(self):
        return self._payload


def _fresh_stack(engine: str):
    from repro.serving.batch_router import BatchRouter
    from repro.serving.lifecycle import LifecycleManager

    router = BatchRouter(N_SLOTS, engine=engine, capacity=N_SLOTS * 2)
    return LifecycleManager(router)


def calibrate(engine: str, n_dispatches: int) -> dict:
    """Time real max-batch dispatches; declare the service bound off p95."""
    mgr = _fresh_stack(engine)
    dispatch = _MeasuredDispatch(mgr, bound_us=1 << 30)  # no clamp here
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 1 << 32, size=MAX_BATCH, dtype=np.uint32)
    dispatch(keys)  # compile
    dispatch.samples_us.clear()
    for _ in range(n_dispatches):
        dispatch(keys)
    s = np.asarray(dispatch.samples_us, dtype=np.float64)
    bound_us = int(np.ceil(np.percentile(s, 95) * BOUND_MARGIN))
    out = {
        "dispatches": int(n_dispatches),
        "p50_us": float(np.percentile(s, 50)),
        "p95_us": float(np.percentile(s, 95)),
        "p99_us": float(np.percentile(s, 99)),
        "service_bound_us": bound_us,
        "capacity_rps": MAX_BATCH / (bound_us * 1e-6),
        "measured_capacity_rps": float(MAX_BATCH / (np.percentile(s, 50) * 1e-6)),
    }
    emit(f"serving/calibrate/{engine}", out["p50_us"],
         f"bound_us={bound_us};capacity_rps={out['capacity_rps']:.0f}")
    return out


def _tenant_weights() -> np.ndarray:
    w = 1.0 / np.arange(1, N_TENANTS + 1, dtype=np.float64) ** ZIPF_S
    return w / w.sum()


def _gen_requests(rng: np.random.Generator, n: int, gap_us: float,
                  slo_us: int):
    """Open-loop arrival plan: (arrival_us, tenant, key, deadline_us)."""
    tenants = rng.choice(N_TENANTS, size=n, p=_tenant_weights())
    # zipf-skewed per-tenant key popularity, mixed into a uint32 keyspace
    ranks = np.minimum(rng.zipf(1.2, size=n), KEYSPACE_PER_TENANT - 1)
    keys = (
        ((tenants.astype(np.uint64) << np.uint64(20)) ^ ranks.astype(np.uint64))
        * np.uint64(2654435761)
    ) & np.uint64(0xFFFFFFFF)
    # open loop: the generator never waits for responses; jittered gaps
    gaps = gap_us * rng.uniform(0.5, 1.5, size=n)
    arrivals = np.cumsum(gaps).astype(np.int64)
    return [
        (int(arrivals[i]), f"tenant-{int(tenants[i])}", int(keys[i]),
         int(arrivals[i]) + slo_us)
        for i in range(n)
    ]


def run_point(engine: str, offered_rps: float, bound_us: int, n_req: int,
              seed: int) -> dict:
    from repro.serving.lifecycle import SHED_LATE, AdmissionRejectedError
    from repro.serving.streaming import (
        StreamConfig,
        StreamingFrontEnd,
        StreamRequest,
        VirtualClockUs,
    )

    capacity_rps = MAX_BATCH / (bound_us * 1e-6)
    offered_rps = float(offered_rps)
    mult = offered_rps / capacity_rps
    gap_us = 1e6 / offered_rps
    slo_us = SLO_BOUNDS * bound_us
    if mult > 1.0:
        horizon_us = slo_us + MAX_WAIT_US
        span_floor = int(offered_rps * 1e-6 * OVERLOAD_SPAN_HORIZONS * horizon_us)
        n_req = max(n_req, span_floor)

    mgr = _fresh_stack(engine)
    clock = VirtualClockUs()
    dispatch = _MeasuredDispatch(mgr, bound_us)
    cfg = StreamConfig(
        max_batch=MAX_BATCH,
        max_wait_us=MAX_WAIT_US,
        service_bound_us=bound_us,
        tenant_rate_per_s=None,
    )
    fe = StreamingFrontEnd(
        mgr,
        config=cfg,
        clock=clock,
        dispatch_fn=dispatch,
        service_model=dispatch.service_model,
    )
    # warm the compile cache outside the measured timeline
    dispatch(np.zeros(MAX_BATCH, dtype=np.uint32))
    dispatch.samples_us.clear()
    dispatch.clamped = 0

    rng = np.random.default_rng(seed)
    plan = _gen_requests(rng, n_req, gap_us, slo_us)
    served = []
    shed = 0
    for arrival_us, tenant, key, deadline_us in plan:
        clock.advance_us(arrival_us - clock.now_us())
        served.extend(fe.pump())
        try:
            fe.submit(StreamRequest(key=key, deadline_us=deadline_us,
                                    tenant=tenant))
        except AdmissionRejectedError:
            shed += 1
    # let the pipeline run dry on the virtual timeline
    for _ in range(4 * SLO_BOUNDS):
        clock.advance_us(bound_us)
        served.extend(fe.pump())
    served.extend(fe.drain())
    shed += fe.admission.shed_by_reason.get(SHED_LATE, 0)

    assert len(served) + shed == n_req, (len(served), shed, n_req)
    lat = np.asarray([r.latency_us for r in served], dtype=np.float64)
    miss = np.asarray([r.deadline_miss_us for r in served], dtype=np.int64)
    makespan_s = max(r.t_complete_us for r in served) * 1e-6 if served else 0.0
    in_slo = int((miss == 0).sum())
    stats = fe.stats()
    row = {
        "load_mult": round(mult, 4),
        "offered_rps": offered_rps,
        "above_capacity": bool(mult > 1.0),
        "n_offered": n_req,
        "served": len(served),
        "shed": shed,
        "shed_fraction": shed / n_req,
        "shed_by_reason": dict(fe.admission.shed_by_reason),
        "p50_us": float(np.percentile(lat, 50)) if served else None,
        "p99_us": float(np.percentile(lat, 99)) if served else None,
        "deadline_miss_max_us": int(miss.max()) if served else 0,
        "served_rps": len(served) / makespan_s if makespan_s else 0.0,
        "goodput_rps": in_slo / makespan_s if makespan_s else 0.0,
        "dispatches": stats["dispatches"],
        "mean_batch": len(served) / stats["dispatches"]
        if stats["dispatches"] else 0.0,
        "clamped_dispatches": dispatch.clamped,
        "measured_dispatch_p50_us": float(np.percentile(
            np.asarray(dispatch.samples_us), 50)) if dispatch.samples_us
        else None,
    }
    emit(
        f"serving/point/{engine}/x{mult:g}",
        row["p99_us"] or 0.0,
        f"offered_rps={offered_rps:.0f};shed={row['shed_fraction']:.3f};"
        f"goodput_rps={row['goodput_rps']:.0f}",
    )
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke", action="store_true",
        help="reduced request count for CI; writes the gitignored smoke "
             "record",
    )
    ap.add_argument("--requests", type=int, default=None,
                    help="override offered requests per load point")
    args = ap.parse_args(argv)
    n_req = args.requests or (N_REQ_SMOKE if args.smoke else N_REQ_FULL)
    n_cal = CAL_SMOKE if args.smoke else CAL_FULL

    per_engine = {}
    for engine in ENGINES:
        cal = calibrate(engine, n_cal)
        bound_us = cal["service_bound_us"]
        offered = [m * cal["capacity_rps"] for m in LOAD_MULTS_BELOW]
        offered.append(OVERLOAD_X_MEASURED * cal["measured_capacity_rps"])
        points = [
            run_point(engine, rps, bound_us, n_req, seed=17 + i)
            for i, rps in enumerate(offered)
        ]
        per_engine[engine] = {
            "calibration": cal,
            "slo_us": SLO_BOUNDS * bound_us,
            "points": points,
        }

    payload = {
        "bench": "serving",
        "schema": 1,
        "smoke": args.smoke,
        "engines": list(ENGINES),
        "n_slots": N_SLOTS,
        "max_batch": MAX_BATCH,
        "max_wait_us": MAX_WAIT_US,
        "slo_bounds": SLO_BOUNDS,
        "bound_margin": BOUND_MARGIN,
        "n_tenants": N_TENANTS,
        "zipf_s": ZIPF_S,
        "requests_per_point": n_req,
        "load_mults_below": list(LOAD_MULTS_BELOW),
        "overload_x_measured": OVERLOAD_X_MEASURED,
        "per_engine": per_engine,
    }
    path = write_bench_json("serving", payload, tracked=not args.smoke)
    print(f"wrote {path}")
    rows = [
        [e, p["load_mult"], f"{p['offered_rps']:.0f}", p["served"],
         f"{p['shed_fraction']:.4f}",
         f"{p['p50_us']:.0f}" if p["p50_us"] is not None else "-",
         f"{p['p99_us']:.0f}" if p["p99_us"] is not None else "-",
         f"{p['goodput_rps']:.0f}"]
        for e in ENGINES for p in per_engine[e]["points"]
    ]
    rows_to_csv("bench_serving",
                ["engine", "load_mult", "offered_rps", "served", "shed_frac",
                 "p50_us", "p99_us", "goodput_rps"], rows)

    # self-check the record's own contract so a full run fails loudly
    rc = 0
    for e in ENGINES:
        pts = per_engine[e]["points"]
        for p in pts:
            if not p["above_capacity"] and p["shed_fraction"] > 0:
                print(f"SHED BELOW CAPACITY: {e} x{p['load_mult']}",
                      file=sys.stderr)
                rc = 1
        if pts[-1]["shed_fraction"] <= 0:
            print(f"OVERLOAD POINT DID NOT SHED: {e}", file=sys.stderr)
            rc = 1
    return rc


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
