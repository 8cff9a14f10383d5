"""Broken paths put under a built cell, for the control and the fault tests.

Each is ``patch(driver)``: it replaces the program's entry the cell's
window calls -- ``BatchRouter.route_keys`` (the front end reaches it through
``LifecycleManager``) or ``StorePlacement.place_keys`` -- on the built
instance, after set-up and before the warm-up, so the rest of the run is the
benchmark's own.

* ``control`` is the plain reference in the program's place, breaking one
  guarantee the configuration states: for a route, without the redirect
  over the alive prefix (a diverted key may land on a failed node); for a
  placement, without the re-salt (a key may hold fewer than three distinct
  shards).  It is what a later change would be tempted to skip.
* the faults are those a cell can have: the fleet state never reaching the
  device (the storm is not seen), half of each batch left unrouted, one
  answer of each batch altered, and one chip's share of a sharded batch
  left unrouted.
"""
from __future__ import annotations

import numpy as np

import common
import reference


def _entry(driver):
    """(object, attribute name) of the entry the cell's window calls."""
    store = getattr(driver, "store", None)
    return (store, "place_keys") if store is not None else (driver.router, "route_keys")


def _wrap_host(driver, edit) -> None:
    """Route as the program does, then ``edit`` the numpy answer in place."""
    import jax.numpy as jnp

    owner, name = _entry(driver)
    routed = getattr(owner, name)

    def broken(keys):
        out = routed(keys)
        if name == "place_keys":
            replicas, exhausted = out
            host = np.array(replicas)
            edit(host, driver.config)
            return jnp.asarray(host), exhausted
        host = np.array(out)
        edit(host, driver.config)
        return jnp.asarray(host)

    setattr(owner, name, broken)


def control(driver) -> None:
    import jax.numpy as jnp

    fleet = common.reference_fleet(driver.config, driver.failed)
    omega = driver.config["omega"]
    r = driver.config["replication"]
    owner, name = _entry(driver)
    if name == "place_keys":
        def place_keys(keys):
            keys = np.asarray(keys)
            held = reference.place(keys, fleet, r, omega, resalt=False)
            return jnp.asarray(held.astype(np.int32)), jnp.zeros(keys.shape, bool)

        owner.place_keys = place_keys
    else:
        def route_keys(keys):
            keys = np.asarray(keys)
            out = reference.route(keys, fleet, omega, second_redirect=False)
            return jnp.asarray(out.astype(np.int32).reshape(keys.shape))

        owner.route_keys = route_keys


def stale_state(driver) -> None:
    """Route with a fleet that never saw the storm."""
    healthy, _ = common.build_router(driver.config, driver.devices)
    driver.router.route_keys = healthy.route_keys


def half_batch(driver) -> None:
    def edit(out, _config):
        out[out.shape[0] // 2:] = 0

    _wrap_host(driver, edit)


def altered_answer(driver) -> None:
    def edit(out, config):
        out[0] = (out[0] + 1) % config["nodes"]

    _wrap_host(driver, edit)


def chip_share_left_out(driver) -> None:
    n_chips = len(driver.devices)

    def edit(out, _config):
        out[out.shape[0] * (n_chips - 1) // n_chips:] = 0

    _wrap_host(driver, edit)
