"""Paper Figs. 6-8: balance — least/most loaded relative difference and
relative std-dev of keys per node (mean = 1000 keys/node)."""
from __future__ import annotations

import collections

import numpy as np

from benchmarks.common import emit, enable_compile_cache, keyset, rows_to_csv
from repro.core import make

ENGINES = ["binomial", "jump", "fliphash-recon", "powerch-recon", "jumpback-recon"]


def _counts(name: str, n: int, mean: int = 1000):
    eng = make(name, n)
    keys = keyset(mean * n, seed=n)
    cnt = collections.Counter(eng.get_bucket(k) for k in keys)
    return np.array([cnt.get(i, 0) for i in range(n)], dtype=np.float64)


def main() -> list[list]:
    rows = []
    # Fig. 6/7: relative min/max difference and std at n = 10 / 100 / 1000
    for name in ENGINES:
        for n in (10, 100, 1000):
            c = _counts(name, n)
            rel_diff = (c.max() - c.min()) / c.mean()
            rel_std = c.std() / c.mean()
            rows.append([name, n, round(rel_diff, 4), round(rel_std, 4)])
            emit(f"balance/{name}/n={n}", 0.0, f"rel_diff={rel_diff:.4f};rel_std={rel_std:.4f}")
    # Fig. 8: scaling 2..64 nodes (binomial, fine grid over the tree boundary)
    for n in (2, 4, 8, 12, 16, 24, 32, 48, 64):
        c = _counts("binomial", n)
        rows.append(["binomial-scaling", n, round((c.max() - c.min()) / c.mean(), 4), round(c.std() / c.mean(), 4)])
        emit(f"balance-scaling/binomial/n={n}", 0.0, f"rel_std={c.std()/c.mean():.4f}")
    rows_to_csv("bench_balance", ["engine", "n", "rel_diff", "rel_std"], rows)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
