"""What every kind of traffic shares: the storm, the router, the reference
fleet and the benchmark's profiler spans.

A mix file (``traffic/<name>.json``) holds only parameters.  Its ``kind``
names the generator that reads them, ``kinds/<kind>.py``: a module with one
class ``Driver(config, mix, seed, devices)`` and four steps,
``setup(seconds)``, ``warm()``, ``window(seconds, annotate)`` and
``check()``.  A new kind is a new file there; the harness finds it by name.
"""
from __future__ import annotations

import contextlib

import reference


def annotation(enabled: bool):
    """A profiler span named for what the benchmark is doing, or nothing."""
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def storm(rng, config: dict, mix: dict) -> list[int]:
    """The failed nodes, in the order they fail.  The last slot is never
    among them: failing it would shrink the slot space, which is a resize."""
    picked = rng.choice(config["nodes"] - 1, mix["failed_nodes"], replace=False)
    return [int(x) for x in picked]


def build_router(config: dict, devices):
    """The deployment's ``BatchRouter``; on more than one chip, keys are
    split along a mesh of them."""
    import jax

    from repro.serving.batch_router import BatchRouter

    mesh = None
    if len(devices) > 1:
        mesh = jax.make_mesh((len(devices),), ("data",), devices=devices)
    router = BatchRouter(config["nodes"], mesh=mesh, omega=config["omega"],
                         **config["router"])
    return router, mesh


def reference_fleet(config: dict, failed: list[int]) -> reference.Fleet:
    fleet = reference.Fleet(config["nodes"])
    for node in failed:
        fleet.fail(node)
    return fleet
