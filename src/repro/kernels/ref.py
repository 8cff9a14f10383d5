"""Pure-jnp oracles for the bulk BinomialHash lookup / fused routing kernels.

These are the references the Pallas kernels are tested against (and
themselves bit-exact against the scalar u32 implementations in
``repro.core.binomial`` / ``repro.core.memento``).  Unjitted on purpose —
tests call them eagerly; the production jit'd flavours live in
``repro.core.binomial_jax`` and ``repro.core.memento_jax``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.binomial_jax import _unrolled_body, mix64_lo32
from repro.core.memento_jax import fused_route_impl


def binomial_bulk_lookup_ref(keys: jax.Array, n: int, omega: int = 16) -> jax.Array:
    """keys (any shape, any int dtype) -> int32 buckets in [0, n)."""
    keys_u32 = keys.astype(jnp.uint32)
    if n <= 1:
        return jnp.zeros(keys.shape, dtype=jnp.int32)
    l = (n - 1).bit_length()
    E = np.uint32(1 << l)
    M = np.uint32(1 << (l - 1))
    return _unrolled_body(keys_u32, E, M, np.uint32(n), omega).astype(jnp.int32)


def binomial_route_ref(
    keys: jax.Array,
    packed_mask: jax.Array,
    table: jax.Array,
    state: jax.Array,
    omega: int = 16,
    n_words: int | None = None,
) -> jax.Array:
    """Fused lookup + table divert oracle (same math as the fused kernel).

    keys         any int shape; packed_mask (1, W) u32 bit-words;
    table        (1, C) i32 slots permutation; state (2,) u32 [n_total, n_alive];
    n_words      static mask word count (defaults to the full padded width —
                 slower cascade, fine for an eager test oracle).
    """
    packed_mask = jnp.asarray(packed_mask, jnp.uint32)
    return fused_route_impl(
        jnp.asarray(keys),
        packed_mask,
        jnp.asarray(table, jnp.int32),
        jnp.asarray(state, jnp.uint32),
        omega,
        int(packed_mask.shape[1]) if n_words is None else n_words,
    )


def binomial_ingest_route_ref(
    ids_lo: jax.Array,
    ids_hi: jax.Array,
    packed_mask: jax.Array,
    table: jax.Array,
    state: jax.Array,
    omega: int = 16,
    n_words: int | None = None,
) -> jax.Array:
    """Fused u64-id ingest + lookup + divert oracle (same math as the ingest
    kernel): the id halves are mixed with the limb-wise splitmix64 and the
    resulting u32 key routed exactly like ``binomial_route_ref``."""
    keys = mix64_lo32(jnp.asarray(ids_lo), jnp.asarray(ids_hi))
    return binomial_route_ref(
        keys, packed_mask, table, state, omega=omega, n_words=n_words
    )
