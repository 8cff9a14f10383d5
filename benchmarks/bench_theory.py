"""Paper §5.4 validation: Eq. (1) lowest-level mass, Eq. (3) imbalance bound
and Eq. (5)/(6) std-dev, predicted vs simulated."""
from __future__ import annotations

import collections

import numpy as np

from benchmarks.common import emit, enable_compile_cache, keyset, rows_to_csv
from repro.core import analysis, binomial_lookup64


def main() -> list[list]:
    rows = []
    keys = keyset(200000)
    for omega in (2, 4, 6, 8):
        for n in (9, 11, 13, 15, 24, 48):
            E, M = analysis.tree_bounds(n)
            cnt = collections.Counter(binomial_lookup64(k, n, omega=omega) for k in keys)
            counts = np.array([cnt.get(i, 0) for i in range(n)], dtype=np.float64)
            # Eq. (1): probability mass on the lowest level
            p_emp = counts[M:].sum() / len(keys)
            p_pred = analysis.p_lowest_level(n, omega)
            # Eq. (3): relative imbalance between minor-tree and lowest level
            gap_emp = (counts[:M].mean() - counts[M:].mean()) / (len(keys) / n)
            gap_pred = analysis.relative_imbalance(n, omega)
            # Eq. (5): std dev
            sd_emp = counts.std()
            sd_pred = analysis.sigma(n, len(keys), omega)
            rows.append(
                [omega, n, round(p_emp, 5), round(p_pred, 5), round(gap_emp, 5),
                 round(gap_pred, 5), round(sd_emp, 2), round(sd_pred, 2)]
            )
            emit(
                f"theory/omega={omega}/n={n}", 0.0,
                f"P_low emp={p_emp:.4f} pred={p_pred:.4f};gap emp={gap_emp:.4f} pred={gap_pred:.4f}",
            )
    # Eq. (6): sigma_max curve
    for omega in (2, 4, 5, 6, 8):
        emit(f"theory/sigma_max/omega={omega}", 0.0, f"{analysis.sigma_max(1.0, omega):.5f}q")
    rows_to_csv(
        "bench_theory",
        ["omega", "n", "p_low_emp", "p_low_pred", "gap_emp", "gap_pred", "std_emp", "std_pred"],
        rows,
    )
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
