"""repro — BinomialHash consistent hashing as the placement/routing substrate
of a multi-pod JAX training/inference framework. See DESIGN.md for the
architecture notes and ``examples/`` for runnable entry points.

The curated public surface (``__all__``):

* ``BatchRouter`` / ``ServingTier`` — the batched serving datapath and the
  replicated tier built on it;
* ``RouterSpec`` / ``FleetState`` / ``BulkEngine`` — the engine-agnostic
  bulk-routing protocol (DESIGN.md §10);
* ``route_bulk`` / ``route_ingest_bulk`` / ``make_sharded_route`` — the
  jit'd bulk routing entry points;
* ``make`` / ``make_bulk`` + the ``ENGINES`` / ``BULK_ENGINES`` registries —
  the scalar comparison suite and the pluggable device engines;
* ``SessionRouter`` / ``hash_session_ids`` — the scalar control plane and
  the vectorised session-id ingest;
* ``StorePlacement`` / ``PlacementSpec`` / ``PlacementRepairer`` +
  ``route_replicas_bulk`` / ``placement_diff_bulk`` — the R-way replicated
  placement tier (DESIGN.md §13);
* ``MetricsRegistry`` / ``LoadMonitor`` / ``SpanTrace`` +
  ``route_load_bulk`` and the ``BalanceDriftAlarm`` /
  ``DisruptionBoundAlarm`` types — the observability tier (DESIGN.md §15).

Attributes resolve lazily (PEP 562): ``import repro`` stays light, and the
serving stack (models, configs) only loads when actually touched.
"""
from __future__ import annotations

import importlib

#: export name -> defining module (resolved on first attribute access);
#: ``__all__`` derives from this, so the two can never drift
_EXPORTS = {
    "BatchRouter": "repro.serving.batch_router",
    "ServingTier": "repro.serving.engine",
    "SessionRouter": "repro.serving.router",
    "hash_session_ids": "repro.serving.router",
    "RouterSpec": "repro.core.bulk",
    "FleetState": "repro.core.bulk",
    "BulkEngine": "repro.core.bulk",
    "ENGINES": "repro.core.registry",
    "BULK_ENGINES": "repro.core.registry",
    "make": "repro.core.registry",
    "make_bulk": "repro.core.registry",
    "route_bulk": "repro.kernels.ops",
    "route_ingest_bulk": "repro.kernels.ops",
    "make_sharded_route": "repro.kernels.ops",
    "PlacementSpec": "repro.core.bulk",
    "StorePlacement": "repro.placement.store",
    "PlacementRepairer": "repro.serving.lifecycle",
    "route_replicas_bulk": "repro.kernels.ops",
    "placement_diff_bulk": "repro.kernels.ops",
    "MetricsRegistry": "repro.observability",
    "LoadMonitor": "repro.observability",
    "LoadConfig": "repro.observability",
    "SpanTrace": "repro.observability",
    "BalanceDriftAlarm": "repro.observability",
    "DisruptionBoundAlarm": "repro.observability",
    "route_load_bulk": "repro.kernels.ops",
}

__all__ = list(_EXPORTS)


def __getattr__(name: str):
    module = _EXPORTS.get(name)
    if module is None:
        raise AttributeError(f"module 'repro' has no attribute '{name}'")
    value = getattr(importlib.import_module(module), name)
    globals()[name] = value  # cache: subsequent accesses skip the import
    return value


def __dir__():
    return sorted(set(globals()) | set(__all__))
