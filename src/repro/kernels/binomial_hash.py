"""Pallas TPU kernels: bulk BinomialHash routing (keys[N] -> replicas[N]).

TPU adaptation of the paper's scalar hot loop (DESIGN.md §3):
* u32 integer arithmetic only (murmur3 fmix32 mixers) — the VPU has no
  integer divide and no 64-bit datapath; the paper's modulo-free power-of-two
  mask design maps 1:1 onto AND/shift/mul ops;
* the early-exit rejection loop becomes an ω-unrolled masked blend — on an
  8×128 lane grid divergent exits buy nothing;
* keys are laid out (rows, 128) so each block is a native VREG tile; the
  block row count is the VMEM tiling knob.

The kernels are instantiated from the generic factory
(``repro.kernels.fused.make_fused_kernels``) with the binomial lookup body
— the SAME machinery every other ``BULK_ENGINES`` entry uses, so the
constant-time certifier (``repro.analysis``) checks one uniform kernel
shape per engine:

* **fused** (``binomial_route_fused_2d`` / ``binomial_route_pallas_fused``)
  — the dynamic-n lookup *and* the replacement-table failure divert in one
  kernel (DESIGN.md §3, §7): ``[n_total, n_alive]`` scalar-prefetch SMEM
  (``E``/``M`` derived in-kernel, so elastic scale-up/down and replica
  failures never retrace), packed removed-slot mask + (1, C) slots
  permutation as whole-block VMEM operands, replica ids written in a single
  pass — no intermediate ``buckets[N]`` HBM round-trip, ONE device dispatch
  per batch, storm-time cost equal to steady-time cost;
* **fused ingest** (``binomial_ingest_fused_2d`` /
  ``binomial_ingest_pallas_fused``) — the fused kernel with the session-key
  hash pulled inside too: raw u64 session ids ride in as (lo, hi) u32
  halves, the limb-wise splitmix64 (``binomial_jax.mix64_lo32``) derives
  the u32 routing key in-register — id -> replica in ONE dispatch with no
  ``keys[N]`` array anywhere (DESIGN.md §9; ``BatchRouter.route_ids``).

The kernel bodies reuse the exact jnp math from ``repro.core.binomial_jax``,
so kernel == ref == scalar-u32-oracle is enforced transitively by tests.
"""
from __future__ import annotations

from repro.core.memento_jax import _binomial_lookup_body
from repro.kernels.fused import make_fused_kernels

_KERNELS = make_fused_kernels(_binomial_lookup_body, "binomial")

#: fused lookup + replacement-table divert, (rows, 128) layout (DESIGN §3, §7)
binomial_route_fused_2d = _KERNELS.route_2d
#: any-shape fused routing entry point (pad/reshape wrapper)
binomial_route_pallas_fused = _KERNELS.route_pallas
#: fused u64-id ingest twins — splitmix64 limb mix + lookup + divert (DESIGN §9)
binomial_ingest_fused_2d = _KERNELS.ingest_2d
binomial_ingest_pallas_fused = _KERNELS.ingest_pallas
