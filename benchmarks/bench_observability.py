"""Observability overhead benchmark: instrumented vs bare fused route.

The load monitor's claim (DESIGN.md §15) is that per-shard load telemetry
is FREE at the dispatch level: the bincount rides inside the router's own
fused device pass (``observability/load_pass``), counting every key up to
``LoadConfig.exact_cutoff`` and a deterministic ``1/2**sample_shift``
stride sample above it (exact counting of a 1M-key batch costs more than
the whole overhead budget on a single-core host — see ``LoadConfig``), so
an instrumented ``BatchRouter.route_keys`` must run within a few percent
of the bare one AT ITS DEFAULT CONFIG.  This bench measures exactly that,
per engine:

* **bare**          — ``route_keys`` with no monitor attached;
* **instrumented**  — the same router + batch with a ``LoadMonitor``
  attached (default sampling config; drain cadence pushed out of the
  timed region, like production's large drain windows);
* **overhead_ratio** — instrumented / bare µs per batch, the gated
  number (hard cap in ``check_router_regression.py --observability-
  current``: 1.03 at full 1M-key batches).  Measured as the median of
  per-round ratios over ROUNDS alternating bare/instrumented rounds —
  pairing cancels the clock-speed drift a shared single-core host shows
  between back-to-back runs, which is the same order as the cap;
* **drain_us**      — one accumulator drain (device->host transfer +
  registry update + envelope checks), amortised over ``drain_every``
  batches in production, reported so the cadence can be chosen from data.

Full runs write the tracked ``BENCH_observability.json`` at the repo
root; ``--smoke`` (CI) writes ``benchmarks/out/
BENCH_observability_smoke.json`` — the two-name discipline of the router
bench.
"""
from __future__ import annotations

import argparse
import statistics
import sys

import numpy as np

from benchmarks.common import emit, enable_compile_cache, time_loop, write_bench_json

ENGINES = ("binomial", "jump")
N_REPLICAS = 48
CAPACITY = 64

N_FULL = 1 << 20
N_SMOKE = 1 << 16
ITERS_FULL = 10
ITERS_SMOKE = 10
ROUNDS_FULL = 5
ROUNDS_SMOKE = 3


def measure_engine(engine: str, n_keys: int, iters: int, rounds: int) -> dict:
    import jax

    from repro.observability import LoadConfig, LoadMonitor
    from repro.serving.batch_router import BatchRouter

    keys = np.random.default_rng(7).integers(
        0, 1 << 32, size=n_keys, dtype=np.uint32
    )
    router = BatchRouter(N_REPLICAS, engine=engine, capacity=CAPACITY)
    # a healthy-fleet steady stream, like bench_router's steady tier; the
    # monitor is attached/detached around the timed rounds so BOTH sides
    # run the same router instance (same compiled executables, same tiling)
    router.fail(5)
    router.recover(5)
    ku = router._coerce_keys(keys)

    def call():
        jax.block_until_ready(router.route_keys(ku))

    call()  # compile the bare path
    monitor = LoadMonitor(router, config=LoadConfig(drain_every=1 << 30))
    call()  # compile the instrumented path
    monitor.detach()

    # paired rounds: alternate bare/instrumented so slow clock drift hits
    # both sides of each ratio equally
    bare_rounds, inst_rounds, ratios = [], [], []
    for _ in range(rounds):
        b = time_loop(call, iters, warmup=1)
        router.attach_load_monitor(monitor)
        i = time_loop(call, iters, warmup=1)
        monitor.detach()
        bare_rounds.append(b)
        inst_rounds.append(i)
        ratios.append(i / b)
    bare_us = statistics.median(bare_rounds)
    inst_us = statistics.median(inst_rounds)
    ratio = statistics.median(ratios)
    drain_us = time_loop(monitor.drain, max(3, iters // 3))

    out = {
        "bare": {"us_per_batch": bare_us, "keys_per_sec": n_keys / (bare_us * 1e-6)},
        "instrumented": {
            "us_per_batch": inst_us,
            "keys_per_sec": n_keys / (inst_us * 1e-6),
        },
        "overhead_ratio": ratio,
        "drain_us": drain_us,
        "sample_shift": monitor.effective_shift(n_keys),
    }
    emit(
        f"observability/route/{engine}/bare", bare_us,
        f"n={n_keys};keys_per_s={out['bare']['keys_per_sec']:.3e}",
    )
    emit(
        f"observability/route/{engine}/instrumented", inst_us,
        f"n={n_keys};overhead_ratio={ratio:.4f};"
        f"sample_shift={out['sample_shift']}",
    )
    emit(f"observability/drain/{engine}", drain_us, f"capacity={CAPACITY}")
    return out


def self_check(engine: str) -> None:
    """Instrumentation must never change routing, and the accumulator must
    agree with a host bincount: exactly below the sampling cutoff, as the
    deterministic scaled stride-sample bincount above it."""
    from repro.observability import LoadConfig, LoadMonitor
    from repro.serving.batch_router import BatchRouter

    bare = BatchRouter(N_REPLICAS, engine=engine, capacity=CAPACITY)
    inst = BatchRouter(N_REPLICAS, engine=engine, capacity=CAPACITY)
    mon = LoadMonitor(inst, config=LoadConfig(drain_every=1 << 30))

    # exact tier (n <= exact_cutoff)
    n_exact = 1 << 12
    keys = np.random.default_rng(3).integers(
        0, 1 << 32, size=n_exact, dtype=np.uint32
    )
    expect = np.asarray(bare.route_keys(keys))
    got = np.asarray(inst.route_keys(keys))
    if not np.array_equal(got, expect):
        raise AssertionError(
            f"instrumented route diverged from bare route ({engine})"
        )
    window = mon.drain()
    counts = np.bincount(expect, minlength=CAPACITY).astype(np.uint32)
    if not np.array_equal(window, counts):
        raise AssertionError(
            f"drained load counts disagree with bincount ({engine})"
        )

    # sampled tier (n > exact_cutoff)
    n_bulk = 1 << 16
    shift = mon.effective_shift(n_bulk)
    if shift == 0:
        raise AssertionError("bulk self-check batch did not trigger sampling")
    keys = np.random.default_rng(5).integers(
        0, 1 << 32, size=n_bulk, dtype=np.uint32
    )
    expect = np.asarray(bare.route_keys(keys))
    got = np.asarray(inst.route_keys(keys))
    if not np.array_equal(got, expect):
        raise AssertionError(
            f"sampled instrumented route diverged from bare route ({engine})"
        )
    window = mon.drain()
    stride = 1 << shift
    scaled = np.bincount(expect[::stride], minlength=CAPACITY) * stride
    if not np.array_equal(window.astype(np.int64), scaled):
        raise AssertionError(
            f"sampled load counts disagree with scaled stride bincount "
            f"({engine})"
        )
    if int(window.sum()) != (-(-n_bulk // stride)) * stride:
        raise AssertionError(f"sampled count total off ({engine})")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke", action="store_true",
        help="reduced sizes; writes the untracked smoke record",
    )
    args = ap.parse_args(argv)
    n_keys = N_SMOKE if args.smoke else N_FULL
    iters = ITERS_SMOKE if args.smoke else ITERS_FULL
    rounds = ROUNDS_SMOKE if args.smoke else ROUNDS_FULL

    from repro.observability import LoadConfig

    cfg = LoadConfig()
    payload: dict = {
        "batch_keys": n_keys,
        "load_config": {
            "sample_shift": cfg.sample_shift,
            "exact_cutoff": cfg.exact_cutoff,
        },
        "per_engine": {},
    }
    for engine in ENGINES:
        self_check(engine)
        payload["per_engine"][engine] = measure_engine(
            engine, n_keys, iters, rounds
        )
    path = write_bench_json("observability", payload, tracked=not args.smoke)
    print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
