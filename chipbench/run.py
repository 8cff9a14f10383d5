#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix and metric readers are the files that entry
names under ``chipbench/``.  The run loads, warms up every shape the cell
uses, measures for ``--seconds``, checks what the timed path produced
against the plain reference, and prints one JSON line.  It needs the TPU
chips the cell asks for: without them it exits 2 and prints no result.

``--rehearse`` runs the same cell on the CPU at the tiny sizes each
configuration and mix file gives under ``rehearse``, with interpret-mode
kernels, and prints no result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, interpret-mode kernels; no result")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    import harness

    harness.prepare_environment(rehearse=args.rehearse,
                                workload=args.workload)
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), rehearse=args.rehearse,
                             t_start=T_START)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    return harness.emit(result, rehearse=args.rehearse)


if __name__ == "__main__":
    sys.exit(main())
