"""Paper Fig. 5 analogue: lookup time vs cluster size, per algorithm.

Scalar host-side ns/lookup for every constant-time engine (the paper's
comparison set), plus the vectorised device-path throughput (keys/s) of the
u32 BinomialHash.  Absolute numbers are CPython, not Java — the paper-
relevant signal is the SHAPE (flat in n) and the integer-vs-float ordering.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, enable_compile_cache, keyset, rows_to_csv, time_loop
from repro.core import make
from repro.core.binomial_jax import binomial_lookup_vec

ENGINES = ["binomial", "jump", "fliphash-recon", "powerch-recon", "jumpback-recon", "anchor-lifo", "dx-lifo"]
SIZES = [10, 100, 1000, 10_000, 100_000]


def main() -> list[list]:
    keys = keyset(2000)
    rows = []
    for name in ENGINES:
        for n in SIZES:
            eng = make(name, n)
            it = iter(range(10**9))

            def call(eng=eng, keys=keys, it=it):
                k = keys[next(it) % len(keys)]
                eng.get_bucket(k)

            us = time_loop(call, iters=2000)
            rows.append([name, n, round(us * 1000, 1)])  # ns per lookup
            emit(f"lookup/{name}/n={n}", us, "ns_scalar_lookup")

    # vectorised u32 path (the MoE-router datapath)
    kv = np.random.default_rng(0).integers(0, 2**32, size=(1 << 16,), dtype=np.uint32)
    for n in SIZES:
        f = lambda kv=kv, n=n: binomial_lookup_vec(kv, n, omega=16).block_until_ready()
        us = time_loop(f, iters=20)
        keys_per_s = (1 << 16) / (us * 1e-6)
        rows.append(["binomial-vec-u32", n, round(us, 1)])
        emit(f"lookup-vec/binomial/n={n}", us, f"{keys_per_s:.3e}_keys_per_s")
    rows_to_csv("bench_lookup", ["engine", "n", "ns_or_us"], rows)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
