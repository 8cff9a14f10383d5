"""JumpHash in the device word sizes — the second fused bulk engine.

Jump consistent hash (Lamping & Veach, 2014) walks a chain of candidate
buckets ``j <- floor((b+1) * 2^31 / ((k >> 33) + 1))`` driven by a 64-bit
LCG; the expected chain length is ln(n), and every step strictly increases
the candidate, so a bounded unroll loses only an astronomically rare tail.
That makes it the natural second engine for the fused single-dispatch
datapath (DESIGN.md §10): the same replacement-table divert, the same fleet
state, a different base lookup body.

``jump32`` is the device-word flavour (the ``binomial32`` counterpart):

* the LCG state rides as (lo, hi) u32 limbs — the TPU VPU has no 64-bit
  integer datapath — stepped with the same limb-multiply helpers as the
  splitmix64 ingest mix (``binomial_jax._mul64`` + an add-with-carry);
* the original's double-precision step is replaced by an f32 step
  (``f32(b+1) * (f32(2^31) / f32(r))``): IEEE-754 single arithmetic, done
  identically by numpy on the host and XLA on CPU/interpret-mode Pallas, so
  the scalar oracle and the vectorised mirror are bit-exact by construction
  (tests enforce; a real-TPU deployment should re-verify its VPU divide
  rounds IEEE-correctly).  ``b+1`` must be exact in an f32 mantissa, which
  bounds the slot space at 2^24 (``repro.core.bulk.MAX_CAPACITY``);
* the rejection loop is unrolled ``omega`` times with a masked blend —
  lanes that exhaust the budget keep their latest (always-valid) candidate,
  and the scalar oracle stops at the identical bound, so scalar == batch
  holds even on the tail.

The bounded flavour keeps JumpHash's full consistency: growing n to n+1
moves a key only onto the new bucket n (tests pin the monotone-remap
property alongside the other ``FULLY_CONSISTENT`` engines).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.binomial_jax import _mul64, mix64_lo32
from repro.core.memento_jax import fused_route_impl, gather_read

#: the 64-bit LCG multiplier from the paper (Lamping & Veach, 2014)
JUMP_LCG = 2862933555777941757

_F_TOP = np.float32(2.0**31)


# ---------------------------------------------------------------------------
# scalar reference — the control-plane oracle (mirrors the unrolled device
# body operation for operation; np.float32 is IEEE single like XLA's f32)
# ---------------------------------------------------------------------------


def jump_lookup32(key: int, n: int, omega: int = 16) -> int:
    """u32-key, ω-bounded, f32-step jump lookup — the ``jump32`` scalar."""
    if n <= 1:
        return 0
    k = key & 0xFFFFFFFF
    b = 0
    fn = np.float32(n)
    for _ in range(omega):
        k = (k * JUMP_LCG + 1) & ((1 << 64) - 1)
        r = (k >> 33) + 1  # uniform in [1, 2^31]
        fj = np.float32(np.float32(b + 1) * np.float32(_F_TOP / np.float32(r)))
        if fj >= fn:
            return b
        b = int(fj)
    return b  # budget exhausted: the latest candidate is always < n


@dataclass
class JumpHash32:
    """Scalar ``jump32`` engine — the oracle of the jump device datapath.

    Same facade as the other engines (``get_bucket`` / LIFO add / remove);
    ``omega`` is the unroll bound shared with the kernels (the engine-
    protocol contract: oracle and device agree on every constant).
    """

    n: int
    omega: int = 16
    name = "jump32"
    exact = False  # device-word flavour of the published algorithm

    def get_bucket(self, key: int) -> int:
        return jump_lookup32(key, self.n, self.omega)

    def add_bucket(self) -> int:
        self.n += 1
        return self.n - 1

    def remove_bucket(self) -> int:
        if self.n <= 1:
            raise ValueError("cannot remove the last bucket")
        self.n -= 1
        return self.n

    @property
    def size(self) -> int:
        return self.n


# ---------------------------------------------------------------------------
# vectorised body — shared by the jnp mirrors below and the Pallas kernels
# (repro.kernels.jump_hash), so kernel == mirror == scalar transitively.
# ---------------------------------------------------------------------------


def _bits_f32(bits: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(bits.astype(jnp.uint32), jnp.float32)


def rn_top_quotient(fr: jax.Array, q0: jax.Array) -> jax.Array:
    """``RN(2^31 / fr)`` for integer-valued f32 ``fr`` in [1, 2^31], given
    any estimate ``q0`` of it within a few ulps.

    The scalar oracle divides in numpy, which rounds correctly; a TPU v5e's
    f32 divide misses the correctly rounded quotient by an ulp for about a
    quarter of these divisors.  So the device rounds the quotient itself.
    With ``fr = m * 2^(E-23)`` (``m`` the 24-bit mantissa), the quotient is
    ``2^47/m * 2^(7-E)`` and its mantissa ``Q = RN(2^47/m)`` lies in
    (2^23, 2^24].  For the estimate's mantissa ``Q0``,
    ``t = 2^48 + m - 2m*Q0`` satisfies ``Q - Q0 = floor(t / 2m)``; ``t`` is
    small, so wrapping u32 products give it exactly, and three compares
    each way find the floor.  ``2^47/m`` is never a tie: ``2^48 = (2Q+1)m``
    has no solution.
    """
    bits = jax.lax.bitcast_convert_type(fr, jnp.uint32)
    e = bits >> np.uint32(23)
    m2 = ((bits & np.uint32(0x7FFFFF)) | np.uint32(0x800000)) << np.uint32(1)
    # 2^(E-7) and 2^(7-E) from exponent fields (E = e - 127, 0 <= E <= 31)
    down = _bits_f32((e - np.uint32(7)) << np.uint32(23))
    up = _bits_f32((np.uint32(261) - e) << np.uint32(23))
    big_q0 = (q0 * down).astype(jnp.int32)  # integer-valued: exact
    t = jax.lax.bitcast_convert_type(
        (m2 >> np.uint32(1)) - big_q0.astype(jnp.uint32) * m2, jnp.int32
    )
    step = m2.astype(jnp.int32)
    k = jnp.zeros_like(t)
    for i in range(1, 4):
        k = k + (t >= i * step).astype(jnp.int32) - (t < (1 - i) * step).astype(jnp.int32)
    return (big_q0 + k).astype(jnp.float32) * up


def jump_unrolled_body(keys_u32: jax.Array, n_u32: jax.Array, omega: int) -> jax.Array:
    """ω-unrolled jump chain: u32 keys + traced n -> u32 buckets in [0, n).

    Every lane runs all ω LCG steps (divergent exits buy nothing on a VREG
    grid); ``done`` freezes each lane's bucket at its first exiting step.
    The f32 product can reach ~2^51 on exited lanes — their (out-of-range)
    integer cast is masked off by ``done``, and continuing lanes satisfy
    ``fj < n <= 2^24`` so their cast is exact.

    Every int <-> f32 conversion goes through int32: the TPU has no
    u32 <-> f32 cast.  ``n`` and ``b + 1`` are at most 2^24, so int32 holds
    them exactly; ``r`` reaches 2^31, whose bits read as int32 -2^31, and
    ``abs`` of its (exact) f32 image restores 2^31.  The quotient goes
    through ``rn_top_quotient``, so every backend rounds it as numpy does.
    """
    lo = keys_u32.astype(jnp.uint32)
    hi = jnp.zeros_like(lo)
    b = jnp.zeros_like(lo)
    done = jnp.zeros(lo.shape, dtype=bool)
    fn = n_u32.astype(jnp.int32).astype(jnp.float32)
    for _ in range(omega):
        # k = k * LCG + 1 mod 2^64, in u32 limbs (add-with-carry on the +1)
        lo, hi = _mul64(lo, hi, JUMP_LCG)
        lo = lo + np.uint32(1)
        hi = hi + jnp.where(lo == 0, np.uint32(1), np.uint32(0))
        r = (hi >> np.uint32(1)) + np.uint32(1)  # (k >> 33) + 1
        fr = jnp.abs(jax.lax.bitcast_convert_type(r, jnp.int32).astype(jnp.float32))
        fb = (b + np.uint32(1)).astype(jnp.int32).astype(jnp.float32)
        fj = fb * rn_top_quotient(fr, _F_TOP / fr)
        exits = fj >= fn
        b = jnp.where(~done & ~exits, fj.astype(jnp.int32).astype(jnp.uint32), b)
        done = done | exits
    return jnp.where(n_u32 <= np.uint32(1), np.uint32(0), b)


@functools.partial(jax.jit, static_argnames=("n", "omega"))
def jump_lookup_vec(keys: jax.Array, n: int, omega: int = 16) -> jax.Array:
    """Bulk jump lookup, n static: keys (any int dtype) -> int32 buckets."""
    if n <= 1:
        return jnp.zeros(keys.shape, dtype=jnp.int32)
    out = jump_unrolled_body(
        keys.reshape(-1).astype(jnp.uint32), np.uint32(n), omega
    )
    return out.astype(jnp.int32).reshape(keys.shape)


@functools.partial(jax.jit, static_argnames=("omega",))
def jump_lookup_dyn(keys: jax.Array, n: jax.Array, omega: int = 16) -> jax.Array:
    """Bulk jump lookup with traced n (elastic resize, no recompile)."""
    out = jump_unrolled_body(
        keys.reshape(-1).astype(jnp.uint32), jnp.asarray(n, jnp.uint32), omega
    )
    return out.astype(jnp.int32).reshape(keys.shape)


# ---------------------------------------------------------------------------
# fused mirrors: jump lookup + the engine-agnostic replacement-table divert
# under one jit — the CPU/GPU flavour of the jump device datapath.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("omega", "n_words", "read"))
def jump_memento_route(
    keys: jax.Array,
    packed_mask: jax.Array,
    table: jax.Array,
    state: jax.Array,
    omega: int = 16,
    *,
    n_words: int,
    read=gather_read,
) -> jax.Array:
    """Fused jump lookup + replacement-table divert — one dispatch.

    The pure-jnp mirror of ``repro.kernels.jump_hash.jump_route_fused_2d``;
    operand contract and fleet-state semantics identical to
    ``binomial_memento_route`` (only the base lookup body differs).
    Bit-exact against the scalar ``SessionRouter(jump32, chain_bits=32,
    resolve="table")`` oracle (tests enforce).  ``read`` (static) is the
    divert's read of the slots table, the XLA gather by default.
    """
    return fused_route_impl(
        keys, packed_mask, table, state, omega, n_words,
        lookup=jump_unrolled_body, read=read,
    )


@functools.partial(jax.jit, static_argnames=("omega", "n_words"))
def jump_ingest_route(
    ids_lo: jax.Array,
    ids_hi: jax.Array,
    packed_mask: jax.Array,
    table: jax.Array,
    state: jax.Array,
    omega: int = 16,
    *,
    n_words: int,
) -> jax.Array:
    """Fused u64-id ingest + jump lookup + divert — one dispatch, no key
    array (the jump twin of ``binomial_ingest_route``): the limb-wise
    splitmix64 derives the u32 routing key in-trace and feeds the same
    fused body."""
    keys = mix64_lo32(ids_lo, ids_hi)
    return fused_route_impl(
        keys, packed_mask, table, state, omega, n_words, lookup=jump_unrolled_body
    )
