"""Vectorised u32 BinomialHash in JAX — the on-device bulk lookup.

This is the datapath flavour (DESIGN.md §3): murmur3 fmix32 mixers, the
scalar early-exit rejection loop replaced by an ω-unrolled masked blend
(every lane runs all ω iterations; ``where`` masks select the first accepting
one).  Bit-exact against ``repro.core.binomial.binomial_lookup32`` — tests
enforce this for all shapes/dtypes/n.

Two entry points:
* ``binomial_lookup_vec(keys, n, omega)``   — n static (constant-folded masks)
* ``binomial_lookup_dyn(keys, n, omega)``   — n traced (elastic clusters
  without recompilation; masks derived with a shift-or cascade)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GOLDEN32 = np.uint32(0x9E3779B9)


def mix32(h: jax.Array) -> jax.Array:
    """murmur3 fmix32, elementwise on uint32."""
    h = h.astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def hash_iter(key: jax.Array, i) -> jax.Array:
    """hash^i(key) — i may be a python int or a traced uint32 scalar."""
    i32 = jnp.asarray(i, dtype=jnp.uint32)
    return mix32(key.astype(jnp.uint32) + i32 * GOLDEN32)


def hash_pair(h: jax.Array, f: jax.Array) -> jax.Array:
    return mix32(h.astype(jnp.uint32) ^ mix32(f.astype(jnp.uint32) + GOLDEN32))


def _or_cascade(m: jax.Array) -> jax.Array:
    """Smear the highest set bit downward: m -> 2^(floor(log2 m)+1) - 1."""
    m = m | (m >> 1)
    m = m | (m >> 2)
    m = m | (m >> 4)
    m = m | (m >> 8)
    m = m | (m >> 16)
    return m


def next_pow2_u32(n: jax.Array) -> jax.Array:
    """Smallest power of two >= n, elementwise on uint32 (shift-or cascade).

    Pure u32 shift/or ops — usable both in a jit trace and inside a Pallas
    kernel body, so the fused kernel's lookup and ``binomial_lookup_dyn`` share
    one E/M derivation (the bit that must stay identical for kernel == ref).
    """
    return _or_cascade(jnp.asarray(n, jnp.uint32) - np.uint32(1)) + np.uint32(1)


def umod32(x: jax.Array, n: jax.Array) -> jax.Array:
    """Bit-exact ``x % n`` for uint32 vectors and a scalar 1 <= n < 2**31.

    Restoring long division — shift/compare/subtract only, no integer divide,
    so it lowers on the TPU VPU (which has none).  Library building block for
    in-kernel chain-style modulo (the table divert uses the far cheaper
    ``mulhi32`` Lemire reduction instead); the pure-jnp chain remap uses
    native ``%`` (XLA has integer remainder on CPU/GPU) and tests pin the
    two equal.
    """
    x = x.astype(jnp.uint32)
    n = jnp.asarray(n, jnp.uint32)
    r = jnp.zeros_like(x)
    for k in range(31, -1, -1):
        r = (r << 1) | ((x >> np.uint32(k)) & np.uint32(1))
        r = jnp.where(r >= n, r - n, r)
    return r


def mulhi32(a: jax.Array, b: jax.Array) -> jax.Array:
    """High 32 bits of the u32xu32 product, in pure u32 ops (no u64 path).

    ``(a * b) >> 32`` via 16-bit limb decomposition — exact for all inputs.
    This is the Lemire range reduction used by the replacement-table divert:
    ``mulhi32(H, p)`` maps a uniform u32 hash onto ``[0, p)`` with four
    multiplies and a few adds/shifts, instead of an integer divide (absent
    on the TPU VPU; a *vector*-divisor ``%`` is also ~10x the cost of these
    ~11 ops on XLA:CPU, measured at 1M lanes).
    """
    a = a.astype(jnp.uint32)
    b = jnp.asarray(b, jnp.uint32)
    al, ah = a & np.uint32(0xFFFF), a >> 16
    bl, bh = b & np.uint32(0xFFFF), b >> 16
    ll = al * bl
    lh = al * bh
    hl = ah * bl
    mid = (ll >> 16) + (lh & np.uint32(0xFFFF)) + (hl & np.uint32(0xFFFF))
    return ah * bh + (lh >> 16) + (hl >> 16) + (mid >> 16)


# ---------------------------------------------------------------------------
# u64 session-key mixing in u32 limb arithmetic — the device half of the
# batched ingest path (DESIGN.md §9).  The TPU VPU has no 64-bit integer
# datapath, so raw u64 session ids ride in as (lo, hi) u32 pairs and
# splitmix64 is evaluated limb-wise; the router only ever consumes the LOW
# 32 bits of the mixed key (``_coerce_keys`` truncates u64 -> u32), so the
# final xor-shift needs just the low word.
# ---------------------------------------------------------------------------


def _xorshr64(lo: jax.Array, hi: jax.Array, s: int) -> tuple[jax.Array, jax.Array]:
    """(lo, hi) ^= (lo, hi) >> s for 0 < s < 32, in u32 limbs."""
    return lo ^ ((lo >> s) | (hi << (32 - s))), hi ^ (hi >> s)


def _mul64(lo: jax.Array, hi: jax.Array, c: int) -> tuple[jax.Array, jax.Array]:
    """(lo, hi) *= c mod 2**64 for a 64-bit constant c, in u32 limbs."""
    cl, ch = np.uint32(c & 0xFFFFFFFF), np.uint32(c >> 32)
    new_hi = mulhi32(lo, cl) + lo * ch + hi * cl
    return lo * cl, new_hi


def mix64_lo32(lo: jax.Array, hi: jax.Array) -> jax.Array:
    """Low 32 bits of ``splitmix64(hi << 32 | lo)`` in pure u32 ops.

    Bit-exact with ``uint32(repro.core.bits.mix64(id))`` per lane — the
    device-word truncation of the scalar int-session-key oracle
    (``SessionRouter.session_key``).  ~30 VPU ops per lane; usable both in a
    jit trace and inside a Pallas kernel body, which is what lets the fused
    ingest kernel hash raw u64 ids and route them in the SAME dispatch.
    """
    lo, hi = lo.astype(jnp.uint32), hi.astype(jnp.uint32)
    lo, hi = _xorshr64(lo, hi, 30)
    lo, hi = _mul64(lo, hi, 0xBF58476D1CE4E5B9)
    lo, hi = _xorshr64(lo, hi, 27)
    lo, hi = _mul64(lo, hi, 0x94D049BB133111EB)
    return lo ^ ((lo >> 31) | (hi << 1))


def highest_one_bit_index(b: jax.Array) -> jax.Array:
    """floor(log2 b) for b >= 1, exact for all u32 (shift-or + popcount)."""
    b = b.astype(jnp.uint32)
    b = b | (b >> 1)
    b = b | (b >> 2)
    b = b | (b >> 4)
    b = b | (b >> 8)
    b = b | (b >> 16)
    v = b - ((b >> 1) & np.uint32(0x55555555))
    v = (v & np.uint32(0x33333333)) + ((v >> 2) & np.uint32(0x33333333))
    v = (v + (v >> 4)) & np.uint32(0x0F0F0F0F)
    v = (v * np.uint32(0x01010101)) >> 24
    return v - np.uint32(1)


def relocate_within_level(b: jax.Array, h: jax.Array) -> jax.Array:
    """Alg. 2 vectorised: uniform relocation of b within its tree level.

    The level extent is read straight off the shift-or cascade —
    ``cascade(b) = 2^(d+1)-1`` so ``f = cascade >> 1 = 2^d-1`` and
    ``top = f+1 = 2^d`` — skipping the popcount multiply and variable shift
    of ``highest_one_bit_index`` (same values, fewer VPU ops per call, and
    this is called ω+1 times per lookup).  ``b = 0`` needs no clamp to 1:
    ``cascade(0) >> 1 == cascade(1) >> 1 == 0``, and lanes with ``b < 2``
    return ``b`` anyway (an unsigned max does not lower on the TPU).
    """
    b = b.astype(jnp.uint32)
    f = _or_cascade(b) >> 1
    top = f + np.uint32(1)
    i = hash_pair(h, f) & f
    return jnp.where(b < 2, b, top + i)


def _unrolled_body(keys_u32: jax.Array, E: jax.Array, M: jax.Array, n_u32: jax.Array, omega: int):
    """Shared ω-unrolled core. E/M/n may be python ints or traced scalars."""
    # hash_iter(key, i) == mix32(key + i*GOLDEN32): hoist the per-iteration
    # index multiply into a running accumulator (one u32 add per iteration,
    # exact in mod-2^32 arithmetic).
    kacc = keys_u32.astype(jnp.uint32)
    h0 = mix32(kacc)
    # Blocks A and C share the same expression over the ORIGINAL hash h0:
    # relocate(h0 & (M-1), h0) — compute once.
    fold = relocate_within_level(h0 & (M - np.uint32(1)), h0)
    result = jnp.zeros_like(keys_u32)
    found = jnp.zeros(keys_u32.shape, dtype=bool)
    hi = h0
    for i in range(omega):
        b = hi & (E - np.uint32(1))
        c = relocate_within_level(b, hi)
        in_a = c < M
        in_b = c < n_u32
        newly = (~found) & (in_a | in_b)
        val = jnp.where(in_a, fold, c)
        result = jnp.where(newly, val, result)
        found = found | in_a | in_b
        if i + 1 < omega:
            kacc = kacc + GOLDEN32
            hi = mix32(kacc)
    # Block C for lanes that never accepted.
    return jnp.where(found, result, fold)


@functools.partial(jax.jit, static_argnames=("n", "omega"))
def binomial_lookup_vec(keys: jax.Array, n: int, omega: int = 16) -> jax.Array:
    """Bulk lookup, n static: keys[..] (any int dtype) -> int32 buckets in [0, n)."""
    keys_u32 = keys.astype(jnp.uint32)
    if n <= 1:
        return jnp.zeros(keys.shape, dtype=jnp.int32)
    l = (n - 1).bit_length()  # ct: host-ok — n is static (static_argnames)
    E = np.uint32(1 << l)
    M = np.uint32(1 << (l - 1))
    out = _unrolled_body(keys_u32, E, M, np.uint32(n), omega)
    return out.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("omega",))
def binomial_lookup_dyn(keys: jax.Array, n: jax.Array, omega: int = 16) -> jax.Array:
    """Bulk lookup with traced n (elastic cluster size, no recompile)."""
    keys_u32 = keys.astype(jnp.uint32)
    n_u32 = jnp.asarray(n, dtype=jnp.uint32)
    E = next_pow2_u32(n_u32)
    M = E >> 1
    out = _unrolled_body(keys_u32, E, M, n_u32, omega)
    out = jnp.where(n_u32 <= 1, np.uint32(0), out)
    return out.astype(jnp.int32)
