"""``bulk_zoned``: the ``bulk`` closed loop over a placement spread across
zones, after a whole zone is lost.

Set-up fails every node of zone ``lost_zone`` (slot ``i`` lies in zone
``i mod zones``) and ``failed_nodes`` more, drawn from the seed in the
other zones and never the last slot, in one coalesced burst, then places
through ``StorePlacement(r=replication, zones=zones)``.  The window is
``bulk``'s.  The program's zone-fallback counters are read on either side
of it, so the cell sees their change over the window alone.  The check
compares every row of ``checked_outputs`` sampled answers with the plain
zoned reference (``reference_zoned.py``), and counts rows whose holders
span fewer zones than ``min(replication, alive zones)``.
"""
from __future__ import annotations

import numpy as np

import harness
import reference_zoned

#: the program's counters the cell reads over its window
COUNTERS = ("placement_zone_fallback_columns_total",
            "placement_shard_fallback_columns_total",
            "placement_columns_total")

bulk = harness.load_module("kinds", "bulk")


class Driver(bulk.Driver):
    def setup(self, seconds: float) -> None:
        import jax

        from repro.placement.store import StorePlacement
        from repro.serving.batch_router import BatchRouter

        config, mix = self.config, self.mix
        nodes, zones = config["nodes"], config["zones"]
        lost = list(range(mix["lost_zone"], nodes, zones))
        if nodes - 1 in lost:
            raise ValueError("the lost zone holds the last slot: losing it "
                             "would be a resize, not a failure")
        others = [s for s in range(nodes - 1) if s % zones != mix["lost_zone"]]
        scattered = self.rng.choice(others, mix["failed_nodes"], replace=False)
        self.failed = lost + [int(s) for s in scattered]
        self.keys_per_call = mix["keys_per_device"] * len(self.devices)
        self.host_keys = self.rng.integers(
            0, 1 << 32, size=(mix["ring"], self.keys_per_call), dtype=np.uint32)
        # the fleet keeps its zones from genesis, as a deployment's does
        self.router = BatchRouter(nodes, omega=config["omega"], zones=zones,
                                  **config["router"])
        with self.router.coalesced_events():
            for node in self.failed:
                self.router.fail(node)
        self.store = StorePlacement(self.router, r=config["replication"],
                                    zones=zones)
        self.ring = [jax.device_put(k, self.devices[0]) for k in self.host_keys]
        jax.block_until_ready(self.ring)

    def _counters(self) -> dict:
        metrics = getattr(self.store, "metrics", None)
        if metrics is None:  # a placement with no zone counters
            return {}
        return {name: metrics.total(name) for name in COUNTERS}

    def window(self, seconds: float, annotate: bool) -> dict:
        before = self._counters()
        result = super().window(seconds, annotate)
        after = self._counters()
        result["counters"] = {k: after[k] - before[k] for k in after}
        return result

    def check(self) -> dict:
        """Every row of each sampled answer against the plain zoned
        reference."""
        config = self.config
        zones, r = config["zones"], config["replication"]
        zoned = reference_zoned.Zoned(config["nodes"], zones)
        for node in self.failed:
            zoned.fail(node)
        failed = zoned.failed()
        spread = min(r, zoned.alive_zones())
        expected: dict[int, np.ndarray] = {}
        wrong = dead = not_distinct = exhausted = not_spread = checked = 0
        for slot, out in self.kept:
            if slot not in expected:
                expected[slot] = reference_zoned.place(
                    self.host_keys[slot], zoned, r, config["omega"])
            want = expected[slot]
            got = np.asarray(out[0]).reshape(want.shape)
            exhausted += int(np.asarray(out[1]).sum())
            checked += got.shape[0]
            valid = (got >= 0) & (got < zoned.fleet.n_total)
            dead += int((~valid | failed[np.where(valid, got, 0)]).sum())
            wrong += int((got != want).any(axis=1).sum())
            same = got[:, :, None] == got[:, None, :]
            not_distinct += int((same.sum(axis=(1, 2)) > r).sum())
            not_spread += int((reference_zoned.zones_spanned(got, zones)
                               < spread).sum())
        return {
            "wrong_rows": (wrong, 0),
            "answers_on_failed_nodes": (dead, 0),
            "rows_not_distinct": (not_distinct, 0),
            "rows_exhausted": (exhausted, 0),
            "rows_not_zone_spread": (not_spread, 0),
            # every sampled answer was compared: none may go unchecked
            "unchecked_answers": (len(self.kept) * self.keys_per_call
                                  - checked, 0),
        }
