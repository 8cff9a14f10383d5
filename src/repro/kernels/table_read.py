"""Reads of a small table at many lane indices: ``TableRead``.

The placement pass (``placement/store.py``) reads small tables at every
key's lane: the slots permutation (1,024 entries at capacity 1024) in each
re-salt probe, and the zone table (1,152 entries at three zones) in the
zone fallback.  As an XLA gather over 2^20 lanes such a read takes ~7 ms
on a TPU v5e, far more than the few vector ops a key costs elsewhere.

On a TPU the read here is one Pallas kernel.  The table sits in VMEM as
``ceil(W / 128)`` rows of 128 lanes; each lane takes ``row[idx & 127]`` of
every row by an in-vreg lane gather and keeps the one whose row is
``idx >> 7`` by a select: ``ceil(W / 128)`` gathers and selects a lane,
not the ``W`` selects of a cascade.  Past ``MAX_VMEM_ROWS`` rows that cost
grows with the table, so wider tables keep the XLA gather; off the TPU
(and not in interpret mode) every read is the XLA gather.  Either way the
answer is ``table[idx]``, bit for bit (DESIGN.md §13.6).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.bulk import DEFAULT_BLOCK_ROWS, RouterSpec
from repro.kernels.fused import LANES

#: widest table, in rows of 128 entries, read from VMEM (8,192 entries);
#: past it the per-lane cost of the row selects outgrows the XLA gather's
MAX_VMEM_ROWS = 64


def _table_read_kernel(table_ref, idx_ref, out_ref, *, n_rows: int):
    block = idx_ref.shape[0] // LANES
    idx = idx_ref[...].reshape(block, LANES)
    lane = (idx & np.uint32(LANES - 1)).astype(jnp.int32)
    row = idx >> np.uint32(LANES.bit_length() - 1)
    out = jnp.zeros_like(idx)
    for k in range(n_rows):
        vals = jnp.broadcast_to(table_ref[k : k + 1, :], idx.shape)
        got = jnp.take_along_axis(vals, lane, axis=-1, mode="promise_in_bounds")
        out = jnp.where(row == np.uint32(k), got, out)
    out_ref[...] = out.reshape(block * LANES)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _vmem_read(table, idx, *, block_rows: int, interpret: bool):
    """(W,) u32 table, W a multiple of 128 as every lane-padded table is,
    (N,) u32 indices < W -> (N,) u32 ``table[idx]``.

    Defined once, so a process traces and lowers the kernel once per shape
    however many reads a pass makes.  The indices and the answer keep the
    pass's flat layout: each grid step takes ``block`` rows of 128 of them
    as one 1-D block, so no reshape or copy of a batch-sized operand runs
    outside the kernel.
    """
    n, n_rows = idx.shape[0], table.shape[0] // LANES
    rows = -(-n // LANES)
    block = -(-min(block_rows, rows) // 8) * 8
    padded = -(-rows // block) * block * LANES
    if padded != n:
        idx = jnp.pad(idx, (0, padded - n))
    out = pl.pallas_call(
        functools.partial(_table_read_kernel, n_rows=n_rows),
        grid=(padded // (block * LANES),),
        in_specs=[
            pl.BlockSpec((n_rows, LANES), lambda i: (0, 0)),
            pl.BlockSpec((block * LANES,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block * LANES,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((padded,), jnp.uint32),
        interpret=interpret,
        name="table_read",
    )(table.reshape(n_rows, LANES), idx)
    return out if padded == n else out[:n]


@dataclasses.dataclass(frozen=True)
class TableRead:
    """How a pass reads its small tables: ``read(table, idx) -> table[idx]``.

    vmem        read tables of at most ``MAX_VMEM_ROWS`` rows by the Pallas
                kernel; False keeps every read the XLA gather
    block_rows  the kernel's tile in rows of 128 indices, rounded up to
                whole (8, 128) tiles (smaller for batches of fewer rows)
    interpret   run the kernel in Pallas interpret mode

    Hashable, so a jitted pass takes it as a static argument.
    """

    vmem: bool = False
    block_rows: int = DEFAULT_BLOCK_ROWS
    interpret: bool = False

    @classmethod
    def for_spec(cls, spec: RouterSpec) -> "TableRead":
        """The read a spec's backend takes: the kernel wherever the route's
        own kernel runs (a TPU, or interpret mode), at the spec's tile."""
        return cls(
            vmem=spec.pallas_selected() or spec.interpret,
            block_rows=spec.resolved_block_rows(),
            interpret=spec.interpret,
        )

    def path(self, width: int) -> str:
        """``"vmem"`` or ``"gather"``: how a table of ``width`` is read."""
        vmem = self.vmem and -(-width // LANES) <= MAX_VMEM_ROWS
        return "vmem" if vmem else "gather"

    def __call__(self, table: jax.Array, idx: jax.Array) -> jax.Array:
        """(W,) u32 table, (N,) u32 indices below W -> (N,) u32."""
        if self.path(table.shape[0]) == "gather":
            return table.at[idx].get(mode="promise_in_bounds")
        return _vmem_read(
            table, idx, block_rows=self.block_rows, interpret=self.interpret
        )
