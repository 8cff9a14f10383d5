"""Core consistent-hashing library — the paper's contribution.

Exact scalar BinomialHash (u64 + u32), vectorised JAX u32 flavour, the
comparison suite, the Memento-style failure wrapper and the closed-form
balance theory.
"""
from repro.core.binomial import (  # noqa: F401
    BinomialHash,
    BinomialHash32,
    binomial_lookup32,
    binomial_lookup64,
)
from repro.core.binomial_jax import (  # noqa: F401
    binomial_lookup_dyn,
    binomial_lookup_vec,
)
from repro.core.bulk import BulkEngine, FleetState, RouterSpec  # noqa: F401
from repro.core.jump_jax import JumpHash32, jump_lookup_dyn, jump_lookup_vec  # noqa: F401
from repro.core.memento import MementoWrapper, ReplacementTable  # noqa: F401
from repro.core.memento_jax import memento_remap  # noqa: F401
from repro.core.registry import (  # noqa: F401
    BULK_ENGINES,
    CONSTANT_TIME,
    ENGINES,
    FULLY_CONSISTENT,
    make,
    make_bulk,
)
