"""``bulk``: a closed loop of bulk calls over device-resident key batches.

After ``failed_nodes`` failures drawn from the seed, the window loops the
program's bulk entry over a ring of ``ring`` batches of
``keys_per_device`` keys on each of the cell's chips, keeping at most
``pipeline_depth`` calls in flight.  The deployment's ``replication`` picks
the entry: ``BatchRouter.route_keys`` for 1, ``StorePlacement.place_keys``
for more.  ``checked_outputs`` answers of the window, drawn from the seed,
are compared key by key with the plain reference.
"""
from __future__ import annotations

import collections
import time

import numpy as np

import common
import reference


class Driver:
    def __init__(self, config, mix, seed, devices):
        self.config, self.mix, self.devices = config, mix, devices
        self.rng = np.random.default_rng(seed)
        #: draws which answers of the window the check compares
        self.sample_rng = np.random.default_rng([seed, 1])

    def setup(self, seconds: float) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        config, mix = self.config, self.mix
        self.failed = common.storm(self.rng, config, mix)
        self.keys_per_call = mix["keys_per_device"] * len(self.devices)
        self.host_keys = self.rng.integers(
            0, 1 << 32, size=(mix["ring"], self.keys_per_call), dtype=np.uint32)
        self.router, mesh = common.build_router(config, self.devices)
        with self.router.coalesced_events():
            for node in self.failed:
                self.router.fail(node)
        self.store = None
        if config["replication"] > 1:
            from repro.placement.store import StorePlacement

            self.store = StorePlacement(self.router, r=config["replication"])
        where = (self.devices[0] if mesh is None
                 else NamedSharding(mesh, P("data")))
        self.ring = [jax.device_put(k, where) for k in self.host_keys]
        jax.block_until_ready(self.ring)

    def call(self):
        """The program's bulk entry this deployment uses."""
        return self.router.route_keys if self.store is None else self.store.place_keys

    def warm(self) -> None:
        import jax

        call = self.call()
        for batch in self.ring:
            jax.block_until_ready(call(batch))

    def window(self, seconds: float, annotate: bool) -> dict:
        import jax

        span = common.annotation(annotate)
        depth = self.mix["pipeline_depth"]
        keep = self.mix["checked_outputs"]
        pending: collections.deque = collections.deque()
        kept: list[tuple[int, object]] = []
        dispatch_s: list[float] = []
        calls = 0
        call = self.call()
        with span("chipbench.window"):
            t0 = time.perf_counter()
            t_stop = t0 + seconds
            while True:
                slot = calls % len(self.ring)
                a = time.perf_counter()
                with span("chipbench.dispatch"):
                    out = call(self.ring[slot])
                b = time.perf_counter()
                dispatch_s.append(b - a)
                pending.append(out)
                # reservoir sample, drawn from the seed, of the answers due
                if calls < keep:
                    kept.append((slot, out))
                else:
                    j = int(self.sample_rng.integers(0, calls + 1))
                    if j < keep:
                        kept[j] = (slot, out)
                calls += 1
                if len(pending) > depth:
                    with span("chipbench.wait"):
                        jax.block_until_ready(pending.popleft())
                if b >= t_stop:
                    break
            with span("chipbench.wait"):
                jax.block_until_ready(list(pending))
            t_end = time.perf_counter()
        self.kept = kept
        keys = calls * self.keys_per_call
        return {
            "facts": {"seconds": t_end - t0, "keys": keys, "calls": calls,
                      "keys_per_call": self.keys_per_call,
                      "keys_per_device_call": self.mix["keys_per_device"],
                      "dispatches_over_50ms": sum(1 for s in dispatch_s if s > 0.05)},
            "spans": {"dispatch": dispatch_s},
            "counters": {},
            "attempted": keys,
            "failed": 0,
        }

    def check(self) -> dict:
        """Every key of each sampled answer against the plain reference."""
        fleet = common.reference_fleet(self.config, self.failed)
        failed = fleet.failed()
        omega = self.config["omega"]
        r = self.config["replication"]
        expected: dict[int, np.ndarray] = {}
        wrong = dead = not_distinct = exhausted = checked = 0
        for slot, out in self.kept:
            if slot not in expected:
                keys = self.host_keys[slot]
                expected[slot] = (reference.route(keys, fleet, omega) if r == 1
                                  else reference.place(keys, fleet, r, omega))
            want = expected[slot]
            if r == 1:
                got = np.asarray(out).reshape(want.shape)
            else:
                got = np.asarray(out[0]).reshape(want.shape)
                exhausted += int(np.asarray(out[1]).sum())
            checked += got.shape[0]
            valid = (got >= 0) & (got < fleet.n_total)
            dead += int((~valid | failed[np.where(valid, got, 0)]).sum())
            if r == 1:
                wrong += int((got != want).sum())
            else:
                wrong += int((got != want).any(axis=1).sum())
                same = got[:, :, None] == got[:, None, :]
                not_distinct += int((same.sum(axis=(1, 2)) > r).sum())
        checks = {"wrong_keys" if r == 1 else "wrong_rows": (wrong, 0),
                  "answers_on_failed_nodes": (dead, 0)}
        if r > 1:
            checks["rows_not_distinct"] = (not_distinct, 0)
            checks["rows_exhausted"] = (exhausted, 0)
        # every sampled answer was compared: none may go unchecked
        checks["unchecked_answers"] = (len(self.kept) * self.keys_per_call
                                       - checked, 0)
        return checks
