"""Chaos harness at scale: seeded failure scenarios, invariants, recovery.

Drives the scenario library (``tests/chaos.py``) over a seed grid — mass
failure storms, flapping replicas through the heartbeat detector, cascades
down to an empty fleet, crash-and-recover mid-stream, mixed churn, and the
placement tier's replica-loss and repair-race storylines — against BOTH
fused engines, counting invariant violations (alive-only routing, minimal
disruption, typed unavailability, journal replay parity, replica
durability, repair convergence, bounded repair bandwidth) and measuring:

* **recovery latency** — detector clock seconds from each emitted "fail" to
  the matching "recover" (flap scenarios; hysteresis + flap backoff means
  the tail reflects the quarantine policy, not just the thresholds);
* **availability** — fraction of probe routes answered (an all-failed fleet
  answering with the *typed* ``FleetUnavailableError`` counts as
  unavailable-but-correct; anything else is a violation);
* **scenario throughput** — wall time per scenario, dominated by the fused
  route dispatches each scenario fires after every membership step.

Full runs (>= 1000 scenarios; ``run.py`` / the perf record) write
``BENCH_chaos.json`` at the repo root; ``--smoke`` (CI) writes
``benchmarks/out/BENCH_chaos_smoke.json`` — same two-name discipline as the
router bench.  ``benchmarks/check_router_regression.py --chaos-current``
gates on the record: zero violations is a hard gate, availability has a
floor.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

from benchmarks.common import REPO_ROOT, emit, enable_compile_cache, rows_to_csv, write_bench_json

sys.path.insert(0, os.path.join(REPO_ROOT, "tests"))

from chaos import KINDS, run_scenario  # noqa: E402

ENGINES = ("binomial", "jump")
#: full grid: 2 engines x 5 kinds x SEEDS_FULL seeds = 1000+ scenarios
SEEDS_FULL = 100
SEEDS_SMOKE = 3


def _pct(values: list, q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def run_grid(n_seeds: int) -> dict:
    per_kind: dict[str, dict] = {
        k: {"scenarios": 0, "events": 0, "violations": 0,
            "route_attempts": 0, "route_unavailable": 0}
        for k in KINDS
    }
    per_engine: dict[str, dict] = {
        e: {"scenarios": 0, "events": 0, "violations": 0,
            "route_attempts": 0, "route_unavailable": 0}
        for e in ENGINES
    }
    latencies: list[float] = []
    violations: list[str] = []
    replay_checks = 0
    repair_copies = 0
    t0 = time.perf_counter()
    for engine in ENGINES:
        for kind in KINDS:
            for seed in range(n_seeds):
                res = run_scenario(kind, engine, seed)
                for acc in (per_kind[kind], per_engine[engine]):
                    acc["scenarios"] += 1
                    acc["events"] += res.events
                    acc["violations"] += len(res.violations)
                    acc["route_attempts"] += res.route_attempts
                    acc["route_unavailable"] += res.route_unavailable
                latencies.extend(res.recovery_latencies)
                violations.extend(res.violations)
                replay_checks += res.replay_checks
                repair_copies += res.repair_copies
    wall = time.perf_counter() - t0
    total_att = total_unav = 0
    for acc in list(per_kind.values()) + list(per_engine.values()):
        att = acc.pop("route_attempts")
        unav = acc.pop("route_unavailable")
        acc["availability"] = 1.0 if att == 0 else 1.0 - unav / att
        total_att += att
        total_unav += unav
    total_att //= 2  # every scenario was accumulated into a kind AND an engine
    total_unav //= 2
    n_scen = sum(a["scenarios"] for a in per_engine.values())
    return {
        "scenarios": n_scen,
        "events": sum(a["events"] for a in per_engine.values()),
        "invariant_violations": len(violations),
        "violation_samples": violations[:20],
        "replay_checks": replay_checks,
        "repair_copies": repair_copies,
        "availability": 1.0 if total_att == 0 else 1.0 - total_unav / total_att,
        "recovery_latency_s": {
            "samples": len(latencies),
            "mean": float(np.mean(latencies)) if latencies else None,
            "p50": _pct(latencies, 50) if latencies else None,
            "p99": _pct(latencies, 99) if latencies else None,
            "max": float(np.max(latencies)) if latencies else None,
        },
        "per_kind": per_kind,
        "per_engine": per_engine,
        "wall_s": round(wall, 3),
        "us_per_scenario": wall / n_scen * 1e6,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke", action="store_true",
        help="reduced seed grid for CI; writes the gitignored smoke record",
    )
    ap.add_argument(
        "--seeds", type=int, default=None,
        help="override seeds per (engine, kind) cell",
    )
    args = ap.parse_args(argv)
    n_seeds = args.seeds or (SEEDS_SMOKE if args.smoke else SEEDS_FULL)

    summary = run_grid(n_seeds)
    emit("chaos.scenario", summary["us_per_scenario"],
         f"n={summary['scenarios']} violations={summary['invariant_violations']}")
    lat = summary["recovery_latency_s"]
    if lat["samples"]:
        emit("chaos.recovery_latency_p50", lat["p50"] * 1e6,
             f"samples={lat['samples']}")
        emit("chaos.recovery_latency_p99", lat["p99"] * 1e6, "")

    payload = {
        "bench": "chaos",
        "schema": 1,
        "smoke": args.smoke,
        "seeds_per_cell": n_seeds,
        "engines": list(ENGINES),
        "kinds": list(KINDS),
        **summary,
    }
    path = write_bench_json("chaos", payload, tracked=not args.smoke)
    print(f"wrote {path}")
    rows = [
        [k, a["scenarios"], a["events"], a["violations"],
         f"{a['availability']:.4f}"]
        for k, a in list(summary["per_kind"].items())
        + list(summary["per_engine"].items())
    ]
    rows_to_csv("bench_chaos", ["group", "scenarios", "events", "violations",
                                "availability"], rows)
    if summary["invariant_violations"]:
        print(f"INVARIANT VIOLATIONS: {summary['invariant_violations']}",
              file=sys.stderr)
        for v in summary["violation_samples"]:
            print("  " + v, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
