"""Render the §Roofline table from the dry-run artifact (benchmarks/out/dryrun.json).

Requires ``python -m repro.launch.dryrun`` to have been run (any subset);
skips gracefully otherwise.
"""
from __future__ import annotations

import json
import os

from benchmarks.common import OUT_DIR, emit, enable_compile_cache, rows_to_csv

DRYRUN_JSON = os.path.join(OUT_DIR, "dryrun.json")


def main() -> list[list]:
    if not os.path.exists(DRYRUN_JSON):
        emit("roofline/missing", 0.0, "run repro.launch.dryrun first")
        return []
    with open(DRYRUN_JSON) as f:
        results = json.load(f)
    rows = []
    for key in sorted(results):
        r = results[key]
        if r.get("status") != "ok":
            if r.get("status") == "skipped":
                arch, shape, mesh = key.split("|")
                rows.append([arch, shape, mesh, "skipped", "", "", "", "", "", ""])
            continue
        roof = r["roofline"]
        rows.append(
            [
                r["arch"], r["shape"], r["mesh"], roof["dominant"],
                f"{roof['compute_s']:.4f}", f"{roof['memory_s']:.4f}",
                f"{roof['collective_s']:.4f}", f"{roof['useful_ratio']:.3f}",
                f"{roof['flops']:.3e}", f"{roof['coll_bytes']:.3e}",
            ]
        )
        emit(
            f"roofline/{key}", 0.0,
            f"dominant={roof['dominant']};compute_s={roof['compute_s']:.4f};"
            f"memory_s={roof['memory_s']:.4f};coll_s={roof['collective_s']:.4f};"
            f"useful={roof['useful_ratio']:.3f}",
        )
    rows_to_csv(
        "bench_roofline",
        ["arch", "shape", "mesh", "dominant", "compute_s", "memory_s", "collective_s",
         "useful_ratio", "flops_per_dev", "coll_bytes_per_dev"],
        rows,
    )
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
