"""Measure-once ``block_rows`` autotuner for the bulk routing kernels.

``block_rows`` is the VMEM tiling knob of the Pallas datapath (rows per
grid step, x128 lanes).  The right value depends on backend generation,
batch size and fleet capacity; a hardcoded 512 leaves double-buffering
headroom on the table (PR 2) but is not optimal everywhere.  This module
replaces the constant with a tiny persistent autotuner (DESIGN.md §7):

* the FIRST time a (backend, rows, capacity) combination is routed, each
  candidate block size is timed once on the live datapath (compile excluded
  via a warmup call) and the winner is persisted to a JSON cache file;
* every later construction — including future processes — reads the cache
  and never measures again, so serving startup stays measurement-free.

The cache lives at ``~/.cache/repro-binomialhash/block_rows.json`` (override
with ``REPRO_AUTOTUNE_CACHE``; useful for tests and hermetic CI).  Callers
that pass an explicit ``block_rows`` bypass the autotuner entirely, and the
pure-jnp CPU/GPU fallback ignores the knob, so tuning only ever runs where
it matters: on a real Pallas backend.
"""
from __future__ import annotations

import json
import os
import time

#: fallback when the autotuner is bypassed (explicit value, interpret mode,
#: or the jnp fallback path, which has no block tiling at all) — the ONE
#: definition; ``RouterSpec.resolved_block_rows`` resolves through it too
from repro.core.bulk import DEFAULT_BLOCK_ROWS  # noqa: F401,E402

#: candidate VMEM tilings: 64 KiB .. 256 KiB per in/out block at 4B x 128
#: lanes.  The kernel bodies keep every unrolled intermediate block-sized,
#: so larger tiles stop paying: on a TPU v5e at capacity 1024, 2048 rows
#: need 17 MiB of scoped VMEM (the limit is 16 MiB) and the compiler
#: refuses them, and 1024 rows take ~3x the compile time of 512.
CANDIDATES = (128, 256, 512)


def cache_path() -> str:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "repro-binomialhash", "block_rows.json"
    )


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _store(path: str, cache: dict) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)  # atomic: concurrent routers never see half a file


#: bump to invalidate every persisted verdict when the kernels change shape
CACHE_SCHEMA = "v2"


def tuned_block_rows(
    backend: str,
    rows: int,
    capacity: int,
    measure,
    candidates: tuple[int, ...] = CANDIDATES,
    path: str | None = None,
    repeats: int = 3,
    variant: str = "fused",
) -> int:
    """Best ``block_rows`` for (backend, variant, rows, capacity) — measured
    once.

    ``measure(block_rows) -> None`` runs the live datapath once with that
    tiling (the caller closes over its real operands); it is invoked
    ``repeats + 1`` times per candidate on a cache miss (first call warms
    up/compiles, the rest are timed, best-of wins) and never on a hit.
    ``variant`` names the datapath being measured (e.g. ``fused`` vs
    ``two_pass``) so verdicts are never reused across kernels with
    different cost profiles; ``CACHE_SCHEMA`` in the key invalidates stale
    verdicts when the kernels themselves change shape.
    """
    path = path or cache_path()
    key = f"{CACHE_SCHEMA}/{backend}/{variant}/rows={rows}/capacity={capacity}"
    cache = _load(path)
    hit = cache.get(key)
    if hit:
        return int(hit["block_rows"])
    timed: dict[int, float] = {}
    for c in candidates:
        if c > max(rows, candidates[0]):
            continue  # bigger blocks than the batch just pad dead lanes
        measure(c)  # compile + warm
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            measure(c)
            best = min(best, time.perf_counter() - t0)
        timed[c] = best
    winner = min(timed, key=timed.get)
    # re-load and merge just before storing: measuring takes long enough
    # that a concurrent process may have written other keys meanwhile, and
    # os.replace only prevents torn files, not lost updates
    cache = _load(path)
    cache[key] = {
        "block_rows": winner,
        "us": {str(c): round(t * 1e6, 2) for c, t in sorted(timed.items())},
    }
    _store(path, cache)
    return winner
