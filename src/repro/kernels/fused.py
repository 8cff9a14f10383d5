"""Generic fused-routing Pallas kernels — any ``BulkEngine`` lookup body +
the replacement-table divert under ONE ``pallas_call``.

This module is the machinery EVERY ``BULK_ENGINES`` entry gets its device
kernels from (DESIGN.md §10) — the binomial paper engine included
(``repro.kernels.binomial_hash`` instantiates it alongside its static-n
extras): hand ``make_fused_kernels`` an unrolled jnp lookup body
``lookup(keys_u32, n_u32, omega) -> u32 buckets`` (usable inside a kernel:
u32/f32 elementwise ops only, n <= 1 handled) and it returns the full
kernel set —

* ``route_2d`` / ``route_pallas``   — fused lookup + divert, pre-hashed keys
  (``route_2d`` takes keys of whole tiles in any shape and does the layout
  in and out inside its one executable);
* ``ingest_2d`` / ``ingest_pallas`` — the u64-id ingest twins (limb-wise
  splitmix64 mixed in-register, then the same body);
* ``lookup_dyn_2d`` / ``lookup_dyn_pallas`` — the plain dynamic-n bulk
  lookup (the two-pass baseline's first dispatch).

All flavours keep the fleet state traced (scalar-prefetch ``[n_total,
n_alive]``, whole-block VMEM mask + table), so fleet events never retrace;
the divert body is the one ``_fused_route_body`` below with the lookup
swapped, so every engine presents the SAME kernel shape — which is also
what lets the constant-time certifier (``repro.analysis``) check one
uniform structure per engine instead of per-engine plumbing.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.binomial_jax import (
    GOLDEN32,
    hash_pair,
    mix32,
    mix64_lo32,
    mulhi32,
)
from repro.core.memento_jax import _binomial_lookup_body
from repro.observability.trace import NULL_SPAN, span

LANES = 128  # TPU minor-dim tile


def _fused_route_body(
    keys, state_ref, mask_ref, table_ref, *, omega: int, n_words: int,
    n_slots: int, lookup=_binomial_lookup_body,
):
    """Shared fused lookup+divert body: u32 keys -> u32 replica ids.

    Factored out so the plain fused kernel (pre-hashed keys) and the ingest
    kernel (u64 ids mixed in-kernel) run the exact same routing math — and
    generic over the base engine: ``lookup(keys_u32, n_u32, omega)`` is the
    only engine-specific piece (``make_fused_kernels`` instantiates every
    ``BULK_ENGINES`` entry's kernels from this same body).
    """
    n = state_ref[0].astype(jnp.uint32)
    n_alive = state_ref[1].astype(jnp.uint32)
    b = lookup(keys, n, omega)

    def removed(bv):
        # select-cascade membership test over the packed bit-words: W scalar
        # broadcasts + selects, no vector gather needed.  Cheaper than the
        # n_slots-wide table cascade — this is why the kernel keeps the mask
        # operand: the steady-state skip test touches W words, not C slots.
        w = bv >> np.uint32(5)
        word = jnp.zeros_like(bv)
        for s in range(n_words):
            word = jnp.where(w == np.uint32(s), mask_ref[0, s], word)
        return ((word >> (bv & np.uint32(31))) & np.uint32(1)) != 0

    def gather(idx):
        # select-cascade "gather" from the slots permutation: C scalar
        # broadcasts + selects per read (idx is always < n_total <= C).
        out = jnp.zeros_like(idx)
        for s in range(n_slots):
            out = jnp.where(
                idx == np.uint32(s), table_ref[0, s].astype(jnp.uint32), out
            )
        return out

    hit = removed(b)

    def divert(bb):
        # ReplacementTable.resolve, lane-wise: two bounded redirects, the
        # Lemire mulhi32 reduction in place of a modulo (the VPU has no
        # integer divide, and mulhi32 is ~11 mul/shift/add ops), then ONE
        # table read.
        h = hash_pair(keys, bb)
        q = mulhi32(h, n)
        deep = q >= n_alive  # a removed position: one more redirect settles it
        # second hash chains off the first (h is avalanched; one fmix32)
        q = jnp.where(deep, mulhi32(mix32(h ^ (q * GOLDEN32)), n_alive), q)
        return jnp.where(hit, gather(q), bb)

    return jax.lax.cond(jnp.any(hit), divert, lambda bb: bb, b)


class FusedKernels(NamedTuple):
    """The per-engine Pallas kernel set ``make_fused_kernels`` returns."""

    route_2d: Callable
    route_pallas: Callable
    ingest_2d: Callable
    ingest_pallas: Callable
    lookup_dyn_2d: Callable
    lookup_dyn_pallas: Callable


def _check_2d(rows: int, lanes: int, block_rows: int) -> None:
    if lanes != LANES:
        raise ValueError(f"minor dim must be {LANES}, got {lanes}")
    if rows % block_rows != 0:
        raise ValueError(
            f"rows ({rows}) must be a multiple of block_rows ({block_rows})"
        )


def _tile_rows(shape: tuple[int, ...], block_rows: int) -> int:
    """Rows of the ``(rows, 128)`` view of keys shaped ``shape``.

    A ``(rows, 128)`` shape is checked as ``_check_2d`` checks it; any other
    shape must hold a whole number of ``block_rows * 128`` tiles.
    """
    if len(shape) == 2 and shape[1] == LANES:
        _check_2d(shape[0], LANES, block_rows)
        return shape[0]
    size, tile = int(np.prod(shape)), block_rows * LANES
    if size % tile:
        raise ValueError(
            f"key count ({size}) must be a multiple of block_rows * {LANES} ({tile})"
        )
    return size // LANES


def _check_state_extents(packed_mask, table, n_words: int, n_slots: int) -> None:
    if not 1 <= n_words <= packed_mask.shape[1]:
        raise ValueError(f"n_words ({n_words}) must be in [1, {packed_mask.shape[1]}]")
    if not 1 <= n_slots <= table.shape[1]:
        raise ValueError(f"n_slots ({n_slots}) must be in [1, {table.shape[1]}]")


def _host_span(name: str, eager: bool):
    """``span(name)`` around eager work; nothing while tracing."""
    return span(name) if eager else NULL_SPAN


def _pad_flat(flat: jax.Array, block_rows: int) -> tuple[jax.Array, int]:
    total = flat.shape[0]
    tile = block_rows * LANES
    padded = (total + tile - 1) // tile * tile
    if padded != total:
        flat = jnp.pad(flat, (0, padded - total))
    return flat, total


def make_fused_kernels(lookup, name: str) -> FusedKernels:
    """Build the device kernel set for one engine's lookup body.

    ``lookup(keys_u32, n_u32, omega) -> u32`` must be traceable inside a
    Pallas TPU kernel body (elementwise u32/f32 ops, no gathers) and map
    n <= 1 to bucket 0 itself.  ``name`` brands the jitted wrappers for
    debuggability.
    """

    def _kernel_route(
        state_ref, mask_ref, table_ref, keys_ref, out_ref, *, omega, n_words, n_slots
    ):
        keys = keys_ref[...].astype(jnp.uint32)
        b = _fused_route_body(
            keys, state_ref, mask_ref, table_ref, omega=omega,
            n_words=n_words, n_slots=n_slots, lookup=lookup,
        )
        out_ref[...] = b.astype(jnp.int32)

    def _kernel_ingest(
        state_ref, mask_ref, table_ref, lo_ref, hi_ref, out_ref, *, omega,
        n_words, n_slots,
    ):
        keys = mix64_lo32(lo_ref[...], hi_ref[...])
        b = _fused_route_body(
            keys, state_ref, mask_ref, table_ref, omega=omega,
            n_words=n_words, n_slots=n_slots, lookup=lookup,
        )
        out_ref[...] = b.astype(jnp.int32)

    def _kernel_lookup_dyn(n_ref, keys_ref, out_ref, *, omega):
        keys = keys_ref[...].astype(jnp.uint32)
        out_ref[...] = lookup(keys, n_ref[0].astype(jnp.uint32), omega).astype(
            jnp.int32
        )

    def _route_grid_spec(block_rows, mask_shape, table_shape, n_blocks):
        return pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=[
                # whole-block mask/table: same small blocks every grid step
                pl.BlockSpec(mask_shape, lambda i, s: (0, 0)),
                pl.BlockSpec(table_shape, lambda i, s: (0, 0)),
                pl.BlockSpec((block_rows, LANES), lambda i, s: (i, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, LANES), lambda i, s: (i, 0)),
        )

    @functools.partial(
        jax.jit,
        static_argnames=("n_words", "n_slots", "omega", "block_rows", "interpret"),
    )
    def route_2d(
        keys, packed_mask, table, state, n_words, n_slots,
        omega=16, block_rows=512, interpret=False,
    ):
        """Int keys of whole tiles + fleet state -> i32 replica ids, same shape.

        The keys may have any shape and int dtype whose element count is a
        multiple of ``block_rows * 128``.  The cast to u32, the reshape to
        ``(rows, 128)`` and the reshape of the result back to the keys'
        shape run inside this one executable, where the compiler makes them
        bitcasts: a 1-D buffer and its ``(rows, 128)`` view hold the same
        bytes in the same order, so the chip runs the kernel and no copy.
        """
        rows = _tile_rows(keys.shape, block_rows)
        _check_state_extents(packed_mask, table, n_words, n_slots)
        grid_spec = _route_grid_spec(
            block_rows, packed_mask.shape, table.shape, rows // block_rows
        )
        out = pl.pallas_call(
            functools.partial(
                _kernel_route, omega=omega, n_words=n_words, n_slots=n_slots
            ),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            interpret=interpret,
        )(
            jnp.asarray(state, jnp.uint32).reshape(2),
            packed_mask.astype(jnp.uint32),
            table.astype(jnp.int32),
            keys.astype(jnp.uint32).reshape(rows, LANES),
        )
        return out.reshape(keys.shape)

    def route_pallas(
        keys, packed_mask, table, state, n_words, n_slots,
        omega=16, block_rows=512, interpret=False,
    ):
        """Any-shape int keys + fleet state -> i32 replica ids, fused kernel.

        The keys' element count decides the path.  Aligned, a multiple of
        the ``block_rows * 128`` tile (every 2^20-key batch), the keys go to
        ``route_2d`` as given: one executable, opened as one
        ``route.launch`` span, with no layout span.  Ragged (the served
        path's few keys), an eager pad to whole tiles runs before it and an
        eager slice after it, each under a ``route.layout`` span.  The pad
        and slice stay outside the jit on purpose: inside it, every distinct
        ragged length would lower the Pallas kernel anew, where outside it
        only each padded row count does.  Traced (inside a jit or a
        shard_map) the same rule holds and no span opens, since a span
        there would time the tracing.
        """
        eager = not isinstance(keys, jax.core.Tracer)
        shape, total = keys.shape, keys.size
        ragged = total % (block_rows * LANES) != 0
        if ragged:
            with _host_span("route.layout", eager):
                keys, _ = _pad_flat(keys.reshape(-1).astype(jnp.uint32), block_rows)
        with _host_span("route.launch", eager) as s:
            if s:
                s.tag(rows=keys.size // LANES, block_rows=block_rows)
            out = route_2d(
                keys, packed_mask, table, state, n_words,
                n_slots, omega=omega, block_rows=block_rows, interpret=interpret,
            )
        if not ragged:
            return out
        with _host_span("route.layout", eager):
            return out[:total].reshape(shape)

    @functools.partial(
        jax.jit,
        static_argnames=("n_words", "n_slots", "omega", "block_rows", "interpret"),
    )
    def ingest_2d(
        ids_lo, ids_hi, packed_mask, table, state, n_words, n_slots,
        omega=16, block_rows=512, interpret=False,
    ):
        """(rows, 128) u32 id halves + fleet state -> (rows, 128) i32 ids."""
        rows, lanes = ids_lo.shape
        if ids_hi.shape != ids_lo.shape:
            raise ValueError(
                f"id halves must agree in shape, got {ids_lo.shape} vs {ids_hi.shape}"
            )
        _check_2d(rows, lanes, block_rows)
        _check_state_extents(packed_mask, table, n_words, n_slots)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // block_rows,),
            in_specs=[
                pl.BlockSpec(packed_mask.shape, lambda i, s: (0, 0)),
                pl.BlockSpec(table.shape, lambda i, s: (0, 0)),
                pl.BlockSpec((block_rows, LANES), lambda i, s: (i, 0)),
                pl.BlockSpec((block_rows, LANES), lambda i, s: (i, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, LANES), lambda i, s: (i, 0)),
        )
        return pl.pallas_call(
            functools.partial(
                _kernel_ingest, omega=omega, n_words=n_words, n_slots=n_slots
            ),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            interpret=interpret,
        )(
            jnp.asarray(state, jnp.uint32).reshape(2),
            packed_mask.astype(jnp.uint32),
            table.astype(jnp.int32),
            ids_lo.astype(jnp.uint32),
            ids_hi.astype(jnp.uint32),
        )

    def ingest_pallas(
        ids_lo, ids_hi, packed_mask, table, state, n_words, n_slots,
        omega=16, block_rows=512, interpret=False,
    ):
        """Any-shape u32 id halves + fleet state -> i32 ids, fused ingest."""
        lo, total = _pad_flat(ids_lo.reshape(-1).astype(jnp.uint32), block_rows)
        hi, _ = _pad_flat(ids_hi.reshape(-1).astype(jnp.uint32), block_rows)
        out = ingest_2d(
            lo.reshape(-1, LANES), hi.reshape(-1, LANES), packed_mask, table,
            state, n_words, n_slots, omega=omega, block_rows=block_rows,
            interpret=interpret,
        )
        return out.reshape(-1)[:total].reshape(ids_lo.shape)

    @functools.partial(jax.jit, static_argnames=("omega", "block_rows", "interpret"))
    def lookup_dyn_2d(keys, n, omega=16, block_rows=512, interpret=False):
        """(rows, 128) u32 keys + traced n -> (rows, 128) i32 buckets."""
        rows, lanes = keys.shape
        _check_2d(rows, lanes, block_rows)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // block_rows,),
            in_specs=[pl.BlockSpec((block_rows, LANES), lambda i, n_ref: (i, 0))],
            out_specs=pl.BlockSpec((block_rows, LANES), lambda i, n_ref: (i, 0)),
        )
        return pl.pallas_call(
            functools.partial(_kernel_lookup_dyn, omega=omega),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            interpret=interpret,
        )(jnp.asarray(n, jnp.uint32).reshape(1), keys.astype(jnp.uint32))

    def lookup_dyn_pallas(keys, n, omega=16, block_rows=512, interpret=False):
        """Any-shape int keys + traced n -> i32 buckets (recompile-free)."""
        flat, total = _pad_flat(keys.reshape(-1).astype(jnp.uint32), block_rows)
        out = lookup_dyn_2d(
            flat.reshape(-1, LANES), n, omega=omega, block_rows=block_rows,
            interpret=interpret,
        )
        return out.reshape(-1)[:total].reshape(keys.shape)

    for fn, suffix in (
        (route_2d, "route_fused_2d"),
        (route_pallas, "route_pallas_fused"),
        (ingest_2d, "ingest_fused_2d"),
        (ingest_pallas, "ingest_pallas_fused"),
        (lookup_dyn_2d, "bulk_lookup_dyn_2d"),
        (lookup_dyn_pallas, "bulk_lookup_pallas_dyn"),
    ):
        try:
            fn.__name__ = f"{name}_{suffix}"
        except AttributeError:  # jitted wrappers may refuse the rebrand
            pass
    return FusedKernels(
        route_2d, route_pallas, ingest_2d, ingest_pallas,
        lookup_dyn_2d, lookup_dyn_pallas,
    )
