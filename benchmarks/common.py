"""Shared helpers for the benchmark suite."""
from __future__ import annotations

import csv
import json
import os
import random
import time

OUT_DIR = os.path.join(os.path.dirname(__file__), "out")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: where compiled executables persist when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: one fixed path in the checkout (gitignored), because a cache
#: directory that moves between runs is never found again
DEFAULT_COMPILE_CACHE = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for a script that drives
    the device, and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other directory; otherwise the cache goes to
    ``DEFAULT_COMPILE_CACHE``.  Call it from a script's entry point, before
    the first compile — never from library code or tests.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        path = DEFAULT_COMPILE_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def rows_to_csv(name: str, header: list[str], rows: list[list]) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{name}.csv")
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)
    return path


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    """The run.py contract: ``name,us_per_call,derived`` lines."""
    print(f"{name},{us_per_call:.4f},{derived}")


def write_bench_json(name: str, payload: dict, tracked: bool = True) -> str:
    """Write the machine-readable perf record for one bench.

    ``tracked=True`` (full-size runs, e.g. via run.py) writes the CANONICAL
    ``BENCH_<name>.json`` at the repo root, kept under version control so
    the perf trajectory is tracked PR over PR.  ``tracked=False`` (smoke /
    reduced-size runs) writes ``BENCH_<name>_smoke.json`` into the
    gitignored benchmarks/out/ instead — a different name in a different
    place, so a CI or verify smoke run can never clobber or shadow the
    tracked record (``check_router_regression.py`` compares the two).
    """
    root = REPO_ROOT if tracked else OUT_DIR
    os.makedirs(root, exist_ok=True)
    fname = f"BENCH_{name}.json" if tracked else f"BENCH_{name}_smoke.json"
    path = os.path.join(root, fname)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def time_loop(fn, iters: int, warmup: int = 3) -> float:
    """Median-of-3 wall time per call, in microseconds."""
    for _ in range(warmup):
        fn()
    best = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        best.append((time.perf_counter() - t0) / iters * 1e6)
    best.sort()
    return best[1]


def keyset(n: int, seed: int = 42) -> list[int]:
    rng = random.Random(seed)
    return [rng.getrandbits(64) for _ in range(n)]
