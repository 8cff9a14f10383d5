"""Framework-level elasticity: data-shard / expert / checkpoint movement on
fleet resizes and failure storms (the system-level face of the paper)."""
from __future__ import annotations

from benchmarks.common import emit, enable_compile_cache, rows_to_csv
from repro.placement.assignment import Assignment
from repro.placement.elastic import FailureDomain, plan_expert_migration


def main() -> list[list]:
    rows = []
    # data-shard reassignment across fleet transitions
    for old, new in ((64, 65), (64, 80), (256, 512), (512, 256), (256, 255)):
        a = Assignment(list(range(8192)), old)
        plan = a.resize(new)
        ideal = abs(new - old) / max(new, old)
        rows.append(["shards", old, new, round(plan.moved_fraction, 4), round(ideal, 4)])
        emit(
            f"elastic/shards/{old}->{new}", 0.0,
            f"moved={plan.moved_fraction:.4f};ideal~{ideal:.4f}",
        )
    # expert migration for EP-group rescales
    for old, new in ((8, 16), (16, 24), (16, 12)):
        m = plan_expert_migration(256, old, new)
        rows.append(["experts", old, new, round(m.plan.moved_fraction, 4), ""])
        emit(f"elastic/experts/{old}->{new}", 0.0, f"moved={m.plan.moved_fraction:.4f}")
    # failure storm: kill 10% of a 100-node serving fleet one by one
    fd = FailureDomain(100)
    keys = list(range(20000))
    base = {k: fd.locate(k) for k in keys}
    cumulative_moved = set()
    for victim in range(0, 10):
        before = {k: fd.locate(k) for k in keys}
        fd.fail(victim)
        moved = {k for k in keys if fd.locate(k) != before[k]}
        assert all(before[k] == victim for k in moved), "only victim's keys move"
        cumulative_moved |= moved
    frac = len(cumulative_moved) / len(keys)
    rows.append(["failure-storm", 100, 90, round(frac, 4), "0.10"])
    emit("elastic/failure-storm/100->90", 0.0, f"cumulative_moved={frac:.4f};ideal~0.10")
    rows_to_csv("bench_elastic", ["kind", "old", "new", "moved_frac", "ideal"], rows)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
