"""Pallas TPU kernels for the jump bulk engine (DESIGN.md §10).

The JumpHash device datapath, instantiated from the generic fused machinery
(``repro.kernels.fused``): the ω-unrolled, f32-step, u32-limb jump chain
(``repro.core.jump_jax.jump_unrolled_body``) replaces the binomial lookup
body; the scalar-prefetch fleet state, the whole-block mask/table VMEM
operands and the replacement-table divert are shared with the binomial
kernels verbatim, so every retrace-free / storm-proof guarantee carries
over by construction.

Bit-exactness chain (tests enforce each link): Pallas kernel == jnp mirror
(``jump_memento_route``) == scalar ``jump32`` oracle.
"""
from __future__ import annotations

from repro.core.jump_jax import jump_unrolled_body
from repro.kernels.fused import make_fused_kernels

_KERNELS = make_fused_kernels(jump_unrolled_body, "jump")

#: fused lookup + divert, (rows, 128) layout — the jump twin of
#: ``binomial_hash.binomial_route_fused_2d``
jump_route_fused_2d = _KERNELS.route_2d
#: any-shape fused routing entry point (pad/reshape wrapper)
jump_route_pallas_fused = _KERNELS.route_pallas
#: fused u64-id ingest twins
jump_ingest_fused_2d = _KERNELS.ingest_2d
jump_ingest_pallas_fused = _KERNELS.ingest_pallas
