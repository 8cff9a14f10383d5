#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate at which the
front end sheds nothing and its backlog does not grow.

One process, one set-up, then one window per rate, each with fresh arrivals
from the seed.  Prints one line per rate; the knee is read from these and
fixed as ``rate_rps`` in the mix's file, at about four fifths of it.

    python3 chipbench/sweep.py --workload <open-loop cell> --seed <n> \\
        --seconds <s> --rates 500,1000,2000
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


class Timers:
    """Wall time of named calls: count, mean and max, in ms."""

    def __init__(self):
        self.stats: dict[str, list[float]] = {}

    def wrap(self, owner, attr: str, name: str) -> None:
        fn = getattr(owner, attr)

        def timed(*a, **kw):
            t0 = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                self.stats.setdefault(name, []).append(time.perf_counter() - t0)

        setattr(owner, attr, timed)

    def report(self) -> dict:
        out = {k: {"n": len(v), "mean": 1e3 * sum(v) / len(v), "max": 1e3 * max(v)}
               for k, v in sorted(self.stats.items()) if v}
        self.stats = {}
        return out


def host_timers(driver) -> Timers:
    """Time the front end's host calls, one level down, and GC pauses."""
    t = Timers()
    fe, mgr = driver.fe, driver.mgr
    t.wrap(fe.breakers, "observe", "breakers.observe")
    t.wrap(fe.batcher, "_close", "batcher.close")
    t.wrap(fe.batcher, "_collect", "batcher.collect")
    t.wrap(mgr, "tick", "lifecycle.tick")
    t.wrap(mgr, "route_keys", "lifecycle.route_keys")
    t.wrap(fe, "submit", "frontend.submit")
    t.wrap(fe, "pump", "frontend.pump")
    started: list[float] = []

    def on_gc(phase, info):
        if phase == "start":
            started.append(time.perf_counter())
        elif started:
            t.stats.setdefault(f"gc.gen{info['generation']}", []).append(
                time.perf_counter() - started.pop())

    gc.callbacks.append(on_gc)
    return t


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--host-timing", action="store_true",
                    help="time the front end's host calls and Python's GC "
                         "(a diagnostic: it adds its own cost)")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"), HERE]
    import harness

    harness.prepare_environment(rehearse=args.rehearse, workload=args.workload)
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    config = harness.load_part("configs", cell["config"], args.rehearse)
    mix = harness.load_part("traffic", cell["traffic"], args.rehearse)
    import jax

    harness.enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        print("no TPU: a knee is only measured on the chip", file=sys.stderr)
        return 2
    p95 = harness.load_reader("p95_ms.served")
    driver = harness.make_driver(config, mix, args.seed, devices[:1])
    driver.setup(args.seconds)
    driver.warm()
    timers = host_timers(driver) if args.host_timing else None
    for rate in (float(r) for r in args.rates.split(",")):
        driver.schedule(args.seconds, rate)
        gc.collect()  # as a run starts its window: the last window's garbage gone
        gc.freeze()
        w = driver.window(args.seconds, annotate=False)
        run = harness.Run(cell=cell, config=config, mix=mix, setup_s=0.0, window=w["facts"], spans=w["spans"],
                          counters=w["counters"], latencies_ms=w["latencies_ms"],
                          trace=None, peaks=None)
        served = w["counters"]["stream_served_total"]
        line = dict(rate_rps=rate, p95_ms=p95(run),
                    p50_ms=float(sorted(w["latencies_ms"])[len(w["latencies_ms"]) // 2]),
                    keys_per_dispatch=served / max(w["counters"]["stream_dispatches_total"], 1),
                    shed_by_reason=driver.fe.admission.shed_by_reason,
                    **w["facts"], checks=driver.check())
        if timers is not None:
            line["host_ms"] = timers.report()
        print("sweep " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
