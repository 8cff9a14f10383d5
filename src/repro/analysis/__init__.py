"""Constant-time certifier — static analysis that machine-checks the
paper's O(1) guarantee for every registered device engine (DESIGN.md §11).

Three layers, one CLI (``python -m repro.analysis``), one CI gate:

* **jaxpr certifier** (``repro.analysis.certify``) — traces every
  ``BULK_ENGINES`` entry's fused route / ingest (jnp mirrors AND Pallas
  kernel bodies via ``interpret=True`` lowering) and jnp dynamic-n lookup to
  closed jaxprs and walks them recursively, enforcing: no ``while_loop``
  (waivable for paper-faithful baselines via
  ``repro.analysis.markers.constant_time_waiver``), equation count affine
  in the ω unroll bound, dtypes closed over the u32-limb arithmetic set
  (traced under x64 so f64 leaks surface), no host callbacks, and exactly
  the declared number of device transfers.
* **AST lint** (``repro.analysis.lint``) — repo-specific source checks
  over ``src/repro/{core,kernels,serving}``: host-sync calls in hot-path
  functions, bare out-of-int32-range literals in limb arithmetic, and
  ``jax.config`` mutation outside tests.
* **HLO gate** (``repro.analysis.hlo_gate``) — compiles the fused route
  per engine and, via the trip-count-aware walker in
  ``repro.roofline.hlo_parse``, asserts every lowered ``while`` has a
  recoverable static trip count and that the compiled program is identical
  across fleet-event severity.

The package ``__init__`` stays import-light (PEP 562 lazy exports) so
``repro.core`` modules can import ``repro.analysis.markers`` without
pulling the engine registry in — the certifier itself imports the registry,
not the other way around.
"""
from __future__ import annotations

_EXPORTS = {
    "certify_all": "repro.analysis.certify",
    "certify_engine": "repro.analysis.certify",
    "certify_callable": "repro.analysis.certify",
    "EngineContract": "repro.analysis.certify",
    "lint_paths": "repro.analysis.lint",
    "lint_source": "repro.analysis.lint",
    "gate_all": "repro.analysis.hlo_gate",
    "gate_engine": "repro.analysis.hlo_gate",
    "constant_time_waiver": "repro.analysis.markers",
    "waivers_of": "repro.analysis.markers",
    "Report": "repro.analysis.report",
    "CheckResult": "repro.analysis.report",
    "TargetReport": "repro.analysis.report",
    "LintFinding": "repro.analysis.report",
    "HloGateResult": "repro.analysis.report",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    if name in _EXPORTS:
        import importlib

        return getattr(importlib.import_module(_EXPORTS[name]), name)
    raise AttributeError(f"module 'repro.analysis' has no attribute '{name}'")


def __dir__():
    return sorted(set(globals()) | set(__all__))
