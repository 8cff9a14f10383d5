"""Bulk-lookup kernel benchmark: vectorised u32 JAX path vs scalar python,
Pallas-interpret correctness, and the kernel's analytic TPU roofline.

Wall-clock Pallas timing on CPU interpret mode is meaningless; the TPU story
is the analytic roofline: ~8 bytes/key HBM traffic (u32 in, i32 out) vs
~obs_int_ops integer VPU ops/key — the kernel is firmly memory-bound on
v5e, so the right metric is fraction of HBM bandwidth."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, enable_compile_cache, rows_to_csv, time_loop
from repro.core.binomial import binomial_lookup32
from repro.core.binomial_jax import binomial_lookup_vec
from repro.kernels.binomial_hash import binomial_bulk_lookup_pallas
from repro.kernels.ref import binomial_bulk_lookup_ref
from repro.roofline import hw


def main() -> list[list]:
    rows = []
    rng = np.random.default_rng(0)
    kv = rng.integers(0, 2**32, size=(1 << 18,), dtype=np.uint32)

    # scalar python baseline
    it = iter(range(10**9))
    us_scalar = time_loop(lambda: binomial_lookup32(int(kv[next(it) % len(kv)]), 1000), 3000)
    emit("kernel/scalar-py/n=1000", us_scalar, "per_key")

    # vectorised u32 (the ref / CPU path)
    for n in (16, 1000, 100_000):
        f = lambda n=n: binomial_lookup_vec(kv, n, omega=16).block_until_ready()
        us = time_loop(f, 10)
        kps = len(kv) / (us * 1e-6)
        rows.append(["vec-u32", n, round(us, 1), f"{kps:.3e}"])
        emit(f"kernel/vec-u32/n={n}", us, f"{kps:.3e}_keys_per_s")

    # pallas interpret: correctness at benchmark scale
    out = binomial_bulk_lookup_pallas(kv[: 1 << 16], 1000, interpret=True)
    ref = binomial_bulk_lookup_ref(kv[: 1 << 16], 1000)
    ok = bool((np.asarray(out) == np.asarray(ref)).all())
    emit("kernel/pallas-interpret/n=1000", 0.0, f"matches_ref={ok}")
    assert ok

    # analytic TPU roofline for the kernel (per key, omega=16)
    bytes_per_key = 8.0  # u32 in + i32 out
    int_ops_per_key = 16 * 40 + 60  # ~40 VPU int ops per unrolled iter + fold
    t_mem = bytes_per_key / hw.HBM_BW
    t_cmp = int_ops_per_key / hw.PEAK_FLOPS_BF16  # VPU int throughput ~ flops peak proxy
    bound = "memory" if t_mem > t_cmp else "compute"
    keys_per_s_roof = 1.0 / max(t_mem, t_cmp)
    rows.append(["pallas-roofline", 0, 0, f"{keys_per_s_roof:.3e}"])
    emit(
        "kernel/pallas-tpu-roofline", 0.0,
        f"bound={bound};roof={keys_per_s_roof:.3e}_keys_per_s_per_chip",
    )
    rows_to_csv("bench_kernel", ["impl", "n", "us_per_call", "keys_per_s"], rows)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
