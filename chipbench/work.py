"""What a kernel's work is, counted from the work and not from the code.

A bulk route reads one u32 key and writes one i32 replica id per key; the
u64 ingest reads the id's two u32 halves instead.  These are the fewest
bytes any implementation must move through HBM, so bytes over the chip's
HBM bandwidth is the least time the route can take.  No integer peak of
the TPU v5e's vector unit is published, so no operation bound is kept: a
roofline share here is of the HBM bound only.
"""
from __future__ import annotations

#: the fused route kernel as the profiler names it on the chip's ``XLA Ops``
#: line (``%route_2d.1 = ... custom-call``, the ``route_2d`` jit of
#: ``kernels/fused.py``)
ROUTE_KERNEL = "route_2d"
#: the placement pass's program, on the chip's ``XLA Modules`` line
PLACE_PROGRAM = "route_replicas"


def route_bytes(keys: int) -> int:
    """4 B key in, 4 B replica id out."""
    return 8 * keys


def ingest_bytes(ids: int) -> int:
    """8 B u64 id in, 4 B replica id out."""
    return 12 * ids
