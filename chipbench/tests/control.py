#!/usr/bin/env python3
"""Read the numbers ``correct`` compares, for the program and for the
control, on several seeds of one cell in one process (one set-up's worth of
compiles).  This is what each limit in PERF.md was set from; the benchmark's
own runs never run it.

    python3 chipbench/tests/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds 3 [--program] [--rehearse]

Prints one line per run: ``reading <cell> <program|control> <seed>`` and
the compared numbers.  On the chip it needs the chips the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program", action="store_true",
                    help="also read the program itself on every seed")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH, HERE]
    import harness

    harness.prepare_environment(rehearse=args.rehearse, workload=args.workload)
    import faults

    sides = (("program", None),) if args.program else ()
    sides += (("control", faults.control),)
    for seed in (int(s) for s in args.seeds.split(",")):
        for side, patch in sides:
            try:
                result = harness.run(args.workload, seed, args.seconds, False,
                                     rehearse=args.rehearse, patch=patch)
            except harness.NoChip as e:
                print(f"no reading: {e}", file=sys.stderr)
                return 2
            checks = {k: v["value"] for k, v in result["checks"].items()}
            print(f"reading {args.workload} {side} {seed} correct={result['correct']} "
                  + json.dumps(checks), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
