#!/usr/bin/env python3
"""The control of ``fleet1k.healthy`` and the stale-zone fault of
``kv3-az.zone-loss``, put under a built cell like ``faults.py``'s, and a
reader of their compared numbers on the chip.

* ``truncated_hash``: the plain reference in the program's place, hashing
  16 of the key's 32 bits, the precision below the one the configuration
  states.  With no node failed, ``faults.control`` (the divert without its
  second redirect) answers as the program does; this one does not.
* ``pre_loss_zone_state``: the zoned placement with the zone tables from
  before the zone was lost: the zone state never reaches the device.

    python3 chipbench/tests/zoned_controls.py --workload fleet1k.healthy \\
        --patch truncated_hash --seeds 1,2,3 --seconds 5 [--rehearse]

Prints one line per run: ``reading <cell> <patch> <seed>`` and the compared
numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def truncated_hash(driver) -> None:
    import jax.numpy as jnp

    import common
    import reference

    fleet = common.reference_fleet(driver.config, driver.failed)
    omega = driver.config["omega"]

    def route_keys(keys):
        keys = np.asarray(keys)
        out = reference.route(keys & np.uint32(0xFFFF), fleet, omega)
        return jnp.asarray(out.astype(np.int32).reshape(keys.shape))

    driver.router.route_keys = route_keys


def pre_loss_zone_state(driver) -> None:
    from repro.placement.store import StorePlacement
    from repro.serving.batch_router import BatchRouter

    config = driver.config
    before = BatchRouter(config["nodes"], omega=config["omega"],
                         zones=config["zones"], **config["router"])
    stale = StorePlacement(before, r=config["replication"],
                           zones=config["zones"])._zone_state_dev()
    driver.store._zone_state_dev = lambda: stale


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--patch", required=True,
                    choices=("truncated_hash", "pre_loss_zone_state"))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH, HERE]
    import harness

    harness.prepare_environment(rehearse=args.rehearse, workload=args.workload)
    patch = globals()[args.patch]
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            result = harness.run(args.workload, seed, args.seconds, False,
                                 rehearse=args.rehearse, patch=patch)
        except harness.NoChip as e:
            print(f"no reading: {e}", file=sys.stderr)
            return 2
        checks = {k: v["value"] for k, v in result["checks"].items()}
        print(f"reading {args.workload} {args.patch} {seed} "
              f"correct={result['correct']} " + json.dumps(checks), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
