"""Benchmark aggregator — one bench per paper table/figure + framework-level
benches. Prints ``name,us_per_call,derived`` CSV rows; per-bench CSVs land in
benchmarks/out/.

Full-size runs through here write the CANONICAL tracked perf records
(``BENCH_<name>.json`` at the repo root, e.g. the router bench's record the
CI regression guard compares against); smoke runs write distinct
``benchmarks/out/BENCH_<name>_smoke.json`` files instead — one name, one
place each."""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from benchmarks import (
        bench_balance,
        bench_disruption,
        bench_elastic,
        bench_kernel,
        bench_lookup,
        bench_moe_routing,
        bench_observability,
        bench_placement,
        bench_roofline,
        bench_router,
        bench_serving,
        bench_theory,
    )

    benches = [
        ("lookup (paper Fig. 5)", bench_lookup),
        ("balance (paper Figs. 6-8)", bench_balance),
        ("disruption (paper §5.2/5.3)", bench_disruption),
        ("theory (paper §5.4 Eqs. 1/3/5/6)", bench_theory),
        ("kernel (bulk lookup)", bench_kernel),
        ("moe routing (hash vs topk)", bench_moe_routing),
        ("session routing (scalar vs batched)", bench_router),
        ("elastic placement", bench_elastic),
        ("replicated store placement (R-way tier)", bench_placement),
        ("streaming serving tier (micro-batch + admission)", bench_serving),
        ("observability tier (instrumented route overhead)", bench_observability),
        ("roofline table (from dry-run)", bench_roofline),
    ]
    failures = 0
    for title, mod in benches:
        print(f"# === {title} ===", flush=True)
        t0 = time.time()
        try:
            mod.main()
        except Exception:
            traceback.print_exc()
            failures += 1
        print(f"# --- done in {time.time()-t0:.1f}s", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from benchmarks.common import enable_compile_cache

    enable_compile_cache()
    main()
