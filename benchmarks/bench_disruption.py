"""Paper §5.2/5.3 table: monotonicity + minimal-disruption movement
fractions, including the power-of-two boundary where the tree changes depth
(the regime BinomialHash's minor-tree fold exists for).

Each engine's moved fraction is also checked against the theoretical
``delta / n1`` bound with slack (``within_bound``) — the same
moved-keys-vs-theory gate ``bench_placement`` applies to the R-way
migration diff.  The bound HARD-GATES (raises) only for the engines that
guarantee minimal disruption at every transition (binomial, jump, the
LIFO anchors); the ``*-recon`` reference engines deliberately reshuffle
~1/2 the keys when a transition crosses a power-of-two regime boundary,
so their column is informational, and ``modulo`` is the intentional straw
man (a full reshuffle) whose column reads ``n/a``.
"""
from __future__ import annotations

from benchmarks.common import emit, enable_compile_cache, keyset, rows_to_csv
from repro.core import make

ENGINES = ["binomial", "jump", "anchor-lifo", "dx-lifo", "fliphash-recon", "jumpback-recon", "modulo"]
TRANSITIONS = [(7, 8), (8, 9), (11, 12), (15, 16), (16, 17), (100, 101), (1000, 1001)]

#: moved_frac <= SLACK * ideal + ABS_SLACK for every minimal-disruption
#: engine: multiplicative room for hash noise plus an absolute term so the
#: tiny ideals (1/1001) don't gate on a handful of keys
SLACK = 1.5
ABS_SLACK = 0.003

#: engines whose every transition must satisfy the bound (a breach raises)
STRICT_ENGINES = {"binomial", "jump", "anchor-lifo", "dx-lifo"}


def main() -> list[list]:
    keys = keyset(20000)
    rows = []
    out_of_bound = []
    for name in ENGINES:
        for n0, n1 in TRANSITIONS:
            eng = make(name, n0)
            before = [eng.get_bucket(k) for k in keys]
            while eng.size < n1:
                eng.add_bucket()
            after = [eng.get_bucket(k) for k in keys]
            moved = sum(b != a for b, a in zip(before, after))
            clean = sum(b != a and a >= n0 for b, a in zip(before, after))
            frac = moved / len(keys)
            ideal = (n1 - n0) / n1
            monotone = moved == clean
            if name == "modulo":
                within = "n/a"
            else:
                within = frac <= SLACK * ideal + ABS_SLACK
                if not within and name in STRICT_ENGINES:
                    out_of_bound.append(f"{name}/{n0}->{n1}: {frac:.4f}")
            rows.append([
                name, n0, n1, round(frac, 4), round(ideal, 4), monotone,
                within,
            ])
            emit(
                f"disruption/{name}/{n0}->{n1}", 0.0,
                f"moved={frac:.4f};ideal={ideal:.4f};monotone={monotone};"
                f"within={within}",
            )
    rows_to_csv(
        "bench_disruption",
        ["engine", "n0", "n1", "moved_frac", "ideal_frac", "monotone",
         "within_bound"],
        rows,
    )
    if out_of_bound:
        raise AssertionError(
            "moved fraction breaches the delta/n bound: "
            + "; ".join(out_of_bound)
        )
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
