"""Placement-tier benchmark: R-way placement throughput, migration-plan
rate, and moved-pairs-vs-theory (DESIGN.md §13).

Three measurements, both fused engines:

* **placement throughput** — keys/s through ``route_replicas_bulk`` (the
  one-pass R-way distinct placement) on a healthy fleet;
* **migration plan rate** — keys/s through ``StorePlacement.
  plan_migration`` (old AND new placement of every registered key plus the
  membership-based transfer mask, ONE device pass);
* **moved fraction vs theory** — for a grid of membership transitions
  (single/multi scale-up, single/mass failure), the measured moved-PAIR
  fraction of the migration plan must stay within the consistent-hashing
  bound.  Per replica column the paper/JumpHash bound is ``delta / n``
  keys moved; the R-way tier adds re-salt collision churn (a key whose
  later column collided re-resolves when the alive set changes), bounded
  by ``(R-1) / min(n0, n1)``.  The gate is
  ``SLACK * (delta / max(n0, n1) + (R-1) / min(n0, n1))`` — loose enough
  for hash noise, far below the ~1.0 of a full reshuffle.

Full runs write the tracked ``BENCH_placement.json`` at the repo root;
``--smoke`` (CI) writes ``benchmarks/out/BENCH_placement_smoke.json`` —
the two-name discipline of the router bench.  ``check_router_regression.py
--placement-current`` gates ``within_bound`` (hard) on either record.
"""
from __future__ import annotations

import argparse
import sys

import numpy as np

from benchmarks.common import emit, enable_compile_cache, rows_to_csv, time_loop, write_bench_json

ENGINES = ("binomial", "jump")
R = 3
SLACK = 1.5

N_FULL = 1 << 20
N_SMOKE = 1 << 14

#: (label, n0, capacity, events) — events drive a journaled
#: LifecycleManager; the moved fraction is measured on the registered
#: keys' migration plan across the whole event group
TRANSITIONS = (
    ("scale_up_1", 64, 128, (("scale_up", None),)),
    ("scale_up_8", 64, 128, tuple(("scale_up", None) for _ in range(8))),
    ("fail_1", 64, 64, (("fail", 13),)),
    ("fail_4", 64, 64, (("fail", 3), ("fail", 17), ("fail", 29), ("fail", 41))),
    ("scale_up_small", 16, 32, (("scale_up", None),)),
    ("fail_small", 8, 8, (("fail", 2), ("fail", 5))),
)


def movement_bound(n0: int, n1: int, r: int) -> float:
    """SLACK * (per-column minimal-disruption bound + re-salt churn)."""
    delta = abs(n1 - n0)
    return SLACK * (delta / max(n0, n1) + (r - 1) / min(n0, n1))


def _store(engine: str, n: int, capacity: int, keys: np.ndarray):
    from repro.placement.store import StorePlacement
    from repro.serving.batch_router import BatchRouter
    from repro.serving.lifecycle import LifecycleConfig, LifecycleManager

    router = BatchRouter(n, engine=engine, capacity=capacity)
    mgr = LifecycleManager(router, LifecycleConfig(min_alive_floor=1))
    store = StorePlacement(router, r=R)
    store.register(keys)
    return router, mgr, store


def measure_throughput(engine: str, n_keys: int, iters: int) -> dict:
    import jax

    from repro.kernels import ops

    keys = np.random.default_rng(7).integers(
        0, 1 << 32, size=n_keys, dtype=np.uint32
    )
    router, _mgr, store = _store(engine, 64, 64, keys[:1])
    fleet = store._fleet_dev()
    ku = router._coerce_keys(keys)

    def call():
        jax.block_until_ready(ops.route_replicas_bulk(ku, fleet, store.spec))

    call()  # compile
    us = time_loop(call, iters)
    out = {"us_per_call": us, "keys_per_s": n_keys / (us * 1e-6)}
    emit(f"placement/route_replicas/{engine}", us,
         f"n={n_keys};r={R};keys_per_s={out['keys_per_s']:.3e}")
    return out


def measure_transition(engine: str, label: str, n0: int, capacity: int,
                       events, n_keys: int, iters: int) -> dict:
    keys = np.random.default_rng(11).integers(
        0, 1 << 32, size=n_keys, dtype=np.uint32
    )
    _router, mgr, store = _store(engine, n0, capacity, keys)
    for kind, slot in events:
        if kind == "scale_up":
            mgr.scale_up()
        else:
            mgr.fail(slot)
    plan = store.plan_migration()  # compile + the measured artifact
    us = time_loop(lambda: store.plan_migration(), iters)
    n1 = mgr.n_alive
    bound = movement_bound(n0, n1, R)
    frac = plan.moved_fraction
    row = {
        "engine": engine,
        "label": label,
        "n0": n0,
        "n1": n1,
        "moved_pairs": plan.moved_pairs,
        "total_pairs": plan.total_pairs,
        "moved_fraction": frac,
        "bound": bound,
        "within_bound": bool(frac <= bound),
        "plan_us_per_call": us,
        "plan_keys_per_s": n_keys / (us * 1e-6),
    }
    emit(f"placement/migrate/{engine}/{label}", us,
         f"moved={frac:.4f};bound={bound:.4f};within={row['within_bound']}")
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke", action="store_true",
        help="reduced key count for CI; writes the gitignored smoke record",
    )
    ap.add_argument("--keys", type=int, default=None,
                    help="override keys per measurement")
    args = ap.parse_args(argv)
    n_keys = args.keys or (N_SMOKE if args.smoke else N_FULL)
    iters = 3 if not args.smoke else 2

    throughput = {e: measure_throughput(e, n_keys, iters) for e in ENGINES}
    transitions = [
        measure_transition(e, label, n0, cap, events, n_keys, iters)
        for e in ENGINES
        for (label, n0, cap, events) in TRANSITIONS
    ]
    all_within = all(t["within_bound"] for t in transitions)

    payload = {
        "bench": "placement",
        "schema": 1,
        "smoke": args.smoke,
        "r": R,
        "slack": SLACK,
        "n_keys": n_keys,
        "engines": list(ENGINES),
        "throughput": throughput,
        "transitions": transitions,
        "all_within_bound": all_within,
    }
    path = write_bench_json("placement", payload, tracked=not args.smoke)
    print(f"wrote {path}")
    rows = [
        [t["engine"], t["label"], t["n0"], t["n1"],
         f"{t['moved_fraction']:.4f}", f"{t['bound']:.4f}",
         t["within_bound"]]
        for t in transitions
    ]
    rows_to_csv("bench_placement",
                ["engine", "label", "n0", "n1", "moved_frac", "bound",
                 "within"], rows)
    if not all_within:
        print("MOVED FRACTION OUT OF BOUND", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
