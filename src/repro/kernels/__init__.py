"""Pallas TPU kernels for the compute hot spot: bulk consistent-hash
routing.  Engine-specific kernels live in binomial_hash.py / jump_hash.py
(both instantiated from the generic machinery in fused.py); ops.py is the
spec dispatcher every caller goes through; table_read.py reads the
placement pass's small tables from VMEM; ref.py holds the pure-jnp test
oracles.  Validated in interpret mode on CPU; TPU is the target.

``route_bulk(keys, fleet, spec)`` is the fused single-dispatch serving hot
path for any registered ``BULK_ENGINES`` engine (DESIGN.md §10): fleet
state rides in as traced operands, so elastic resize / failure events
never recompile, and on a healthy fleet it is the plain lookup."""
from repro.kernels.ops import (  # noqa: F401
    make_sharded_route,
    route_bulk,
    route_ingest_bulk,
)
