"""Name -> engine registries for the consistent-hash suite.

Two tables:

* ``ENGINES`` — the scalar engines (the paper's Fig. 5 comparison set plus
  the device-word flavours): ``make(name, n)`` builds one.
* ``BULK_ENGINES`` — the pluggable *device* engines (DESIGN.md §10): each
  ``BulkEngine`` bundles its scalar oracle with the fused jnp mirrors, the
  optional Pallas kernels and the plain bulk-lookup flavours; the generic
  dispatcher (``repro.kernels.ops``) and ``BatchRouter(engine=...)``
  resolve entries from here *per call*, so tests can swap an entry in to
  intercept dispatches.
"""
from __future__ import annotations

from typing import Callable

from repro.core.baselines import (
    AnchorHashLIFO,
    DxHashLIFO,
    FlipHashRecon,
    JumpBackHashRecon,
    JumpHash,
    ModuloHash,
    PowerCHRecon,
    RendezvousHash,
    RingHash,
)
from repro.core.binomial import BinomialHash, BinomialHash32
from repro.core.binomial_jax import binomial_lookup_dyn, binomial_lookup_vec
from repro.core.bulk import BulkEngine
from repro.core.jump_jax import (
    JumpHash32,
    jump_ingest_route,
    jump_lookup_dyn,
    jump_lookup_vec,
    jump_memento_route,
)
from repro.core.memento_jax import binomial_ingest_route, binomial_memento_route
from repro.kernels.binomial_hash import (
    binomial_ingest_pallas_fused,
    binomial_route_pallas_fused,
)
from repro.kernels.jump_hash import (
    jump_ingest_pallas_fused,
    jump_route_pallas_fused,
)

ENGINES: dict[str, Callable[[int], object]] = {
    "binomial": lambda n: BinomialHash(n),
    "binomial32": lambda n: BinomialHash32(n),
    "jump": lambda n: JumpHash(n),
    "jump32": lambda n: JumpHash32(n),
    "fliphash-recon": lambda n: FlipHashRecon(n),
    "powerch-recon": lambda n: PowerCHRecon(n),
    "jumpback-recon": lambda n: JumpBackHashRecon(n),
    "anchor-lifo": lambda n: AnchorHashLIFO(n),
    "dx-lifo": lambda n: DxHashLIFO(n),
    "rendezvous": lambda n: RendezvousHash(n),
    "ring": lambda n: RingHash(n),
    "modulo": lambda n: ModuloHash(n),
}

#: constant-time engines compared in the paper's Fig. 5
CONSTANT_TIME = ["binomial", "jump", "fliphash-recon", "powerch-recon", "jumpback-recon"]

#: engines whose cross-power-of-two monotonicity is guaranteed (see DESIGN §6)
FULLY_CONSISTENT = [
    "binomial", "binomial32", "jump", "jump32", "rendezvous", "ring",
    "anchor-lifo", "dx-lifo",
]


def make(name: str, n: int):
    if name not in ENGINES:
        raise KeyError(f"unknown engine '{name}'; have {sorted(ENGINES)}")
    return ENGINES[name](n)


#: the pluggable device routing engines (DESIGN.md §10).  Every entry is
#: bit-exact against its ``scalar_engine`` oracle under table-mode failure
#: resolution across arbitrary fleet-event streams — tests enforce this for
#: each registered engine, so a new entry inherits the whole parity suite.
BULK_ENGINES: dict[str, BulkEngine] = {
    "binomial": BulkEngine(
        name="binomial",
        scalar_engine="binomial32",
        route=binomial_memento_route,
        ingest=binomial_ingest_route,
        route_pallas=binomial_route_pallas_fused,
        ingest_pallas=binomial_ingest_pallas_fused,
        lookup_dyn=binomial_lookup_dyn,
        lookup_vec=binomial_lookup_vec,
    ),
    "jump": BulkEngine(
        name="jump",
        scalar_engine="jump32",
        route=jump_memento_route,
        ingest=jump_ingest_route,
        route_pallas=jump_route_pallas_fused,
        ingest_pallas=jump_ingest_pallas_fused,
        lookup_dyn=jump_lookup_dyn,
        lookup_vec=jump_lookup_vec,
    ),
}


def make_bulk(name: str) -> BulkEngine:
    """Resolve a device engine bundle by name (the ``BatchRouter(engine=)``
    / ``RouterSpec.engine`` lookup)."""
    if name not in BULK_ENGINES:
        raise KeyError(
            f"unknown bulk engine '{name}'; have {sorted(BULK_ENGINES)} "
            f"(scalar-only engines live in ENGINES)"
        )
    return BULK_ENGINES[name]
