"""Vectorised Memento-style failure resolution — the device half of the
serving datapath.

Two device-side resolutions of keys that land on removed slots, mirroring
the two host flavours in ``repro.core.memento``:

* **table** (``resolve="table"`` — the serving datapath, DESIGN.md §7):
  the ``ReplacementTable`` slots permutation rides on device as a
  ``(1, C)`` i32 array, uploaded at fleet-event time.  A removed bucket is
  resolved by at most two u32 hash rounds and EXACTLY ONE read of the
  table — no data-dependent loop, so storm-time batch cost equals
  steady-time cost.
  ``binomial_memento_route`` fuses base lookup + table divert under one jit
  (the pure-jnp mirror of the fused Pallas kernel).

* **chain** (``resolve="chain"`` — paper-faithful library flavour):
  ``memento_remap`` applies the deterministic rejection chain to a whole
  batch of buckets via a ``lax.while_loop`` over the batch.  Each round is
  one gather + one mix over all lanes; the loop exits when every lane has
  settled, so the expected cost is O(n_total / n_alive) rounds — but the
  number of rounds is data-dependent (max over the batch), which is exactly
  the storm-time cliff the table flavour removes.  Bit-exact against
  ``MementoWrapper(chain_bits=32)`` (tests enforce).

Both keep every fleet-state operand fixed-shape and traced (``capacity`` is
a static upper bound on the fleet size), so the compiled executables are
invariant across arbitrary scale/fail/recover event streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.markers import constant_time_waiver
from repro.core.binomial_jax import (
    GOLDEN32,
    _unrolled_body,
    hash_iter,
    hash_pair,
    mix32,
    mix64_lo32,
    mulhi32,
    next_pow2_u32,
)

#: lanes the packed removed-mask is padded to — one native TPU VREG row, so
#: the fused kernel can take it as a whole-block VMEM operand without layout
#: surprises (capacity/32 words of real payload, zero-padded to a multiple).
MASK_LANES = 128


def mask_words(capacity: int) -> int:
    """Number of u32 bit-words holding a ``capacity``-slot removed mask."""
    return max(1, -(-capacity // 32))


def pack_removed_mask(removed, capacity: int, lanes: int = MASK_LANES) -> np.ndarray:
    """Removed-slot ids -> ``(1, W)`` uint32 bit-words (bit b = slot b removed).

    ``W`` is ``mask_words(capacity)`` rounded up to a multiple of ``lanes``;
    the padding words are zero (never-removed).  This is the host-side mirror
    of the fused kernel's VMEM mask operand: O(capacity/32) words, shape
    fixed across arbitrary fleet-event streams.
    """
    words = -(-mask_words(capacity) // lanes) * lanes
    packed = np.zeros((1, words), dtype=np.uint32)
    for b in removed:
        if not 0 <= b < capacity:
            raise ValueError(f"removed slot {b} outside capacity {capacity}")
        packed[0, b >> 5] |= np.uint32(1) << np.uint32(b & 31)
    return packed


def table_width(capacity: int, lanes: int = MASK_LANES) -> int:
    """Lane-padded width of the device replacement table for ``capacity``."""
    return -(-capacity // lanes) * lanes


def pack_table(table, capacity: int, lanes: int = MASK_LANES) -> np.ndarray:
    """``ReplacementTable`` -> ``(1, C)`` int32 device operand.

    The ``slots`` permutation (alive prefix first); ``pos`` stays host-side
    (it exists to make the event-time swaps O(1), the device lookup never
    reads it).  ``C`` is ``capacity`` rounded up to a multiple of ``lanes``
    so the operand is a whole native VMEM block; the padding entries are
    never gathered (every index is < n_total <= capacity).  Shape is fixed
    across arbitrary fleet-event streams — this is the host-side mirror the
    incremental event-time uploads re-pin.
    """
    n = table.n_total
    if n > capacity:
        raise ValueError(f"table spans {n} slots, exceeding capacity {capacity}")
    packed = np.zeros((1, table_width(capacity, lanes)), dtype=np.int32)
    packed[0, :n] = table.slots
    return packed


@functools.partial(jax.jit, static_argnames=("max_chain",))
@constant_time_waiver(
    "paper-faithful chain-mode baseline: the Memento rejection walk is a "
    "lax.while_loop by design, bounded by the static max_chain operand; "
    "serving datapaths use the while-free table-mode engines instead"
)
def memento_remap(
    keys: jax.Array,
    buckets: jax.Array,
    removed_mask: jax.Array,
    n_total: jax.Array,
    first_alive: jax.Array,
    max_chain: int = 4096,
) -> jax.Array:
    """Divert buckets that landed on removed slots onto alive ones.

    keys         any int shape S (uint32 key space)
    buckets      shape S, base-engine buckets in [0, n_total)
    removed_mask (capacity,) bool, capacity >= n_total (fixed across events)
    n_total      traced uint32 scalar — total slot space of the base engine
    first_alive  traced uint32 scalar — fallback after max_chain rejections
    """
    shape = buckets.shape
    keys_u32 = keys.reshape(-1).astype(jnp.uint32)
    b = buckets.reshape(-1).astype(jnp.uint32)
    total = jnp.asarray(n_total, jnp.uint32)
    active = removed_mask[b]

    def cond(state):
        i, b, active = state
        return (i < np.uint32(max_chain)) & jnp.any(active)

    def body(state):
        i, b, active = state
        nb = hash_pair(hash_iter(keys_u32, i + np.uint32(1)), b) % total
        b = jnp.where(active, nb, b)
        return i + np.uint32(1), b, active & removed_mask[b]

    _, b, active = jax.lax.while_loop(cond, body, (jnp.uint32(0), b, active))
    # lanes that exhausted the chain fall back to the first alive slot,
    # mirroring MementoWrapper.first_alive().
    b = jnp.where(active, jnp.asarray(first_alive, jnp.uint32), b)
    return b.astype(jnp.int32).reshape(shape)


# ---------------------------------------------------------------------------
# table-based resolution: storm-time cost == steady-time cost.
# ---------------------------------------------------------------------------


def gather_read(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` by one XLA gather, for indices in bounds by
    construction: ``promise_in_bounds`` skips XLA's clamp logic (~30%
    cheaper gathers on XLA:CPU at 1M lanes)."""
    return table.at[idx].get(mode="promise_in_bounds")


def _table_divert(
    keys_u32: jax.Array,
    b: jax.Array,
    packed_mask: jax.Array,
    table: jax.Array,
    state: jax.Array,
    n_words: int,
    read=gather_read,
) -> jax.Array:
    """Divert buckets off removed slots — EXACTLY ONE table read, no loop.

    Mirrors ``ReplacementTable.resolve`` lane-wise (DESIGN.md §7):

    1. ``q = mulhi32(h, n_total)`` with ``h = hash_pair(key, b)`` —
       Lemire reduction to a position in the permutation; alive iff
       ``q < n_alive`` (probability n_alive / n_total);
    2. else ``q = mulhi32(mix32(h ^ q*GOLDEN32), n_alive)`` — a position in
       the alive prefix, alive by construction (chained off ``h`` and seeded
       by the *position*, so no gather is needed between the rounds; ``h``
       is already avalanched, so one fmix32 replaces a full pair-mix and
       keeps the storm divert ~20% cheaper per lane).

    Membership is a select cascade over the ``n_words`` packed mask words —
    pure elementwise ops that fuse into the hash pass, unlike a per-lane
    LUT gather.  The whole divert is therefore one fused elementwise pass +
    one final read of ``slots`` per lane — data-independent and one memory
    pass short of the two-gather variant, which is what keeps an event
    storm off the batch critical path on memory-bound hosts.

    ``read(slots, q) -> slots[q]`` takes that final read: ``gather_read``,
    one XLA gather, by default; the placement pass hands in its
    ``TableRead`` so that on a TPU the read is the ``table_read`` VMEM
    kernel (DESIGN.md §13.6).  Either way the answer is ``slots[q]`` bit for
    bit.
    """
    total = state[0].astype(jnp.uint32)
    n_alive = state[1].astype(jnp.uint32)
    slots = table[0].astype(jnp.uint32)
    words = packed_mask.reshape(-1)
    w = b >> np.uint32(5)
    word = jnp.zeros_like(b)
    for s in range(n_words):
        word = jnp.where(w == np.uint32(s), words[s], word)
    hit = ((word >> (b & np.uint32(31))) & np.uint32(1)) != 0
    h = hash_pair(keys_u32, b)
    q = mulhi32(h, total)
    deep = q >= n_alive  # a removed position: one more redirect settles it
    # second hash chains off the first (h is avalanched; one fmix32 over q)
    q = jnp.where(deep, mulhi32(mix32(h ^ (q * GOLDEN32)), n_alive), q)
    # q is in-bounds by construction (q < n_total <= C)
    return jnp.where(hit, read(slots, q), b)


def _binomial_lookup_body(keys_u32: jax.Array, total: jax.Array, omega: int) -> jax.Array:
    """The BinomialHash base-lookup body of the fused route: u32 keys +
    traced n -> u32 buckets (n <= 1 collapses to bucket 0)."""
    E = next_pow2_u32(total)
    M = E >> 1
    b = _unrolled_body(keys_u32, E, M, total, omega)
    return jnp.where(total <= np.uint32(1), np.uint32(0), b)


def fused_route_impl(
    keys: jax.Array,
    packed_mask: jax.Array,
    table: jax.Array,
    state: jax.Array,
    omega: int,
    n_words: int,
    lookup=_binomial_lookup_body,
    read=gather_read,
) -> jax.Array:
    """Traceable fused lookup + table-divert body, generic over the base
    engine: ``lookup(keys_u32, n_total, omega) -> u32 buckets`` is the only
    engine-specific piece (DESIGN.md §10); the replacement-table divert is
    engine-agnostic.  Shared by the jit'd jnp mirrors (CPU/GPU fallback of
    every ``BULK_ENGINES`` entry) and the unjitted test oracles in
    ``repro.kernels.ref``.

    keys         any int shape S (uint32 key space)
    packed_mask  (1, W) uint32 bit-words — bit b set iff slot b removed
    table        (1, C) int32 — the slots permutation (``pack_table``)
    state        (2,) uint32 — [n_total, n_alive]
    n_words      static mask word count (= ceil(capacity/32)), bounding the
                 membership select cascade
    read         the divert's read of the slots table, ``read(slots, q) ->
                 slots[q]``: ``gather_read`` or a ``TableRead``
    """
    shape = keys.shape
    keys_u32 = keys.reshape(-1).astype(jnp.uint32)
    total = state[0].astype(jnp.uint32)
    n_alive = state[1].astype(jnp.uint32)
    b = lookup(keys_u32, total, omega)

    # Healthy-fleet fast path: one scalar compare skips the divert entirely,
    # so the steady-state fused cost degenerates to the base lookup alone.
    # The cond boundary also keeps the ω-unrolled producer of ``b`` at
    # exactly ONE consumer — XLA:CPU's fusion pass happily duplicates the
    # ~850-op producer into each additional elementwise consumer otherwise
    # (measured at 2x batch latency in the PR 2 chain implementation).
    b = jax.lax.cond(
        n_alive != total,
        lambda bb: _table_divert(
            keys_u32, bb, packed_mask, table, state, n_words, read
        ),
        lambda bb: bb,
        b,
    )
    return b.astype(jnp.int32).reshape(shape)


@functools.partial(jax.jit, static_argnames=("omega", "n_words", "read"))
def binomial_memento_route(
    keys: jax.Array,
    packed_mask: jax.Array,
    table: jax.Array,
    state: jax.Array,
    omega: int = 16,
    *,
    n_words: int,
    read=gather_read,
) -> jax.Array:
    """Fused BinomialHash lookup + replacement-table divert — one dispatch.

    The pure-jnp mirror of the fused Pallas kernel
    (``repro.kernels.binomial_hash.binomial_route_fused_2d``): the ω-unrolled
    base lookup feeds the one-gather table divert in-trace, so no
    intermediate ``buckets[N]`` array ever round-trips through HBM and a
    ``BatchRouter.route_keys`` call costs exactly one dispatch.  All fleet
    state is traced and fixed-shape (``packed_mask``, ``table``, the state
    2-vector), so scale/fail/recover streams never retrace.  Bit-exact
    against the scalar ``SessionRouter(binomial32, chain_bits=32,
    resolve="table")`` oracle (tests enforce).  ``read`` (static) is the
    divert's read of the slots table, the XLA gather by default
    (``fused_route_impl``).
    """
    return fused_route_impl(
        keys, packed_mask, table, state, omega, n_words, read=read
    )


@functools.partial(jax.jit, static_argnames=("omega", "n_words"))
def binomial_ingest_route(
    ids_lo: jax.Array,
    ids_hi: jax.Array,
    packed_mask: jax.Array,
    table: jax.Array,
    state: jax.Array,
    omega: int = 16,
    *,
    n_words: int,
) -> jax.Array:
    """Fused u64-id ingest + lookup + divert — ONE dispatch, no key array.

    The pure-jnp mirror of the fused ingest Pallas kernel
    (``repro.kernels.binomial_hash.binomial_ingest_fused_2d``): raw u64
    session ids arrive as (lo, hi) u32 halves, ``mix64_lo32`` derives the
    u32 routing key in-trace, and the key feeds the same ω-unrolled
    lookup + table divert as ``binomial_memento_route`` — all inside one
    jit, so XLA fuses the ~30-op splitmix64 limb mix into the lookup's
    elementwise pass and no intermediate ``keys[N]`` array is ever
    materialised in memory (DESIGN.md §9).  Bit-exact with hashing on the
    host (``bits.np_mix64`` then truncate) and routing the keys.
    """
    keys = mix64_lo32(ids_lo, ids_hi)
    return fused_route_impl(keys, packed_mask, table, state, omega, n_words)

