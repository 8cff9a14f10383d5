"""The two cells of the zoned deployment's change, on the CPU: both rehearse
through ``run.py``; the zoned cell's check fails a zone-blind placement, one
altered holder and zone tables from before the loss; the healthy cell's
fails a 16-bit hash; and the zone-fallback reader reads a recorded window."""
import os
import subprocess
import sys

import numpy as np
import pytest

import faults
import harness
import reference
import zoned_controls

SEED = 4_100_000_007
RUN = os.path.join(harness.HERE, "run.py")


@pytest.mark.parametrize("cell", ["kv3-az.zone-loss", "fleet1k.healthy"])
def test_cell_rehearses(cell):
    done = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", str(SEED),
         "--seconds", "1", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    assert '"correct": true' in done.stdout


def zone_blind(driver) -> None:
    """The plain zone-free reference in the program's place: distinct alive
    shards, any zones."""
    import jax.numpy as jnp

    fleet = reference.Fleet(driver.config["nodes"])
    for node in driver.failed:
        fleet.fail(node)

    def place_keys(keys):
        keys = np.asarray(keys)
        held = reference.place(keys, fleet, driver.config["replication"],
                               driver.config["omega"])
        return jnp.asarray(held.astype(np.int32)), jnp.zeros(keys.shape, bool)

    driver.store.place_keys = place_keys


def one_holder_altered(driver) -> None:
    import jax.numpy as jnp

    placed = driver.store.place_keys

    def place_keys(keys):
        replicas, exhausted = placed(keys)
        host = np.array(replicas)
        host[0, 1] = (host[0, 1] + 3) % driver.config["nodes"]  # same zone
        return jnp.asarray(host), exhausted

    driver.store.place_keys = place_keys


def _run(cell, patch):
    result = harness.run(cell, SEED, 0.5, False, rehearse=True, patch=patch)
    return result["correct"], {k: c["value"] for k, c in result["checks"].items()}


def test_zone_blind_placement_is_not_zone_spread():
    correct, checks = _run("kv3-az.zone-loss", zone_blind)
    assert not correct
    assert checks["rows_not_zone_spread"] > 0 and checks["wrong_rows"] > 0
    assert checks["rows_not_distinct"] == 0  # it fails on zones alone


def test_one_altered_holder_is_a_wrong_row():
    correct, checks = _run("kv3-az.zone-loss", one_holder_altered)
    assert not correct and checks["wrong_rows"] > 0


@pytest.mark.parametrize("cell,fault", [
    ("kv3-az.zone-loss", "control"), ("kv3-az.zone-loss", "half_batch"),
    ("kv3-az.zone-loss", "altered_answer"),
    ("fleet1k.healthy", "half_batch"), ("fleet1k.healthy", "altered_answer"),
    ("fleet1k.healthy", "truncated_hash"),
    ("kv3-az.zone-loss", "pre_loss_zone_state"),
])
def test_faults_turn_correct_false(cell, fault):
    patch = getattr(faults, fault, None) or getattr(zoned_controls, fault)
    correct, checks = _run(cell, patch)
    assert not correct, checks


@pytest.mark.parametrize("cell,patch,failing", [
    # a 16-bit hash answers with other alive nodes: wrong, never failed
    ("fleet1k.healthy", "truncated_hash", {"wrong_keys"}),
    # stale zone tables send diverted columns into the lost zone
    ("kv3-az.zone-loss", "pre_loss_zone_state",
     {"wrong_rows", "answers_on_failed_nodes"}),
])
def test_zoned_controls_fail_their_own_checks(cell, patch, failing):
    correct, checks = _run(cell, getattr(zoned_controls, patch))
    assert not correct
    assert {k for k, v in checks.items() if v > 0} >= failing
    if cell == "fleet1k.healthy":
        assert checks["answers_on_failed_nodes"] == 0


def test_zone_fallback_reader_reads_a_recorded_window():
    read = harness.load_reader("zone_fallback_pct.zoned")
    window = {"placement_zone_fallback_columns_total": 2_796_203,
              "placement_shard_fallback_columns_total": 17_011,
              "placement_columns_total": 16_777_216}
    run = harness.Run(cell={}, config={}, mix={}, setup_s=0.0, window={},
                      spans={}, counters=window, latencies_ms=None,
                      trace=None, peaks=None)
    assert read(run) == pytest.approx(100 * 2_796_203 / 16_777_216)
    run.counters = {}  # a program without the counters
    assert read(run) is None
    run.counters = {"placement_zone_fallback_columns_total": 0,
                    "placement_columns_total": 0}
    assert read(run) is None
