"""Every cell, run through the harness at its rehearsal size on the CPU:
sound, it is correct; with the control or a fault it can have under the
timed path, ``correct`` comes out false."""
import pytest

import faults
import harness

SEED = 4_100_000_007

CASES = [
    ("fleet1k.storm", None), ("fleet1k.storm", "control"),
    ("fleet1k.storm", "stale_state"), ("fleet1k.storm", "half_batch"),
    ("fleet1k.storm", "altered_answer"),
    ("kv3.place", None), ("kv3.place", "control"),
    ("kv3.place", "half_batch"), ("kv3.place", "altered_answer"),
    ("fleet1k.served", None), ("fleet1k.served", "control"),
    ("fleet1k.served", "stale_state"), ("fleet1k.served", "half_batch"),
    ("fleet1k.served", "altered_answer"),
    ("fleet1k.storm-large", None), ("fleet1k.storm-large", "control"),
    ("fleet1k.storm-large", "half_batch"),
    ("fleet1k-x4.storm", None), ("fleet1k-x4.storm", "control"),
    ("fleet1k-x4.storm", "stale_state"), ("fleet1k-x4.storm", "half_batch"),
    ("fleet1k-x4.storm", "altered_answer"),
    ("fleet1k-x4.storm", "chip_share_left_out"),
]


@pytest.mark.parametrize("cell,fault", CASES)
def test_correct_is_decided_by_the_comparison(cell, fault):
    patch = None if fault is None else getattr(faults, fault)
    result = harness.run(cell, SEED, 1.0, False, rehearse=True, patch=patch)
    failing = [k for k, c in result["checks"].items() if c["value"] > c["limit"]]
    if fault is None:
        assert result["correct"] is True, result["checks"]
        assert result["attempted"] > 0
    else:
        assert result["correct"] is False
        assert failing, result["checks"]


def test_result_line_ends_with_the_compared_numbers(capsys):
    result = harness.run("fleet1k.storm", SEED, 0.5, False, rehearse=True)
    assert list(result)[-1] == "checks"
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    harness.emit(result, rehearse=True)
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-len(result["checks"]):] == [
        f"check {k} {c['value']} limit {c['limit']}" for k, c in result["checks"].items()]


def test_no_chip_no_result():
    with pytest.raises(harness.NoChip):
        harness.run("fleet1k.storm", SEED, 0.5, False)
