"""The trace reduction, checked on a trace recorded on a TPU v5e
(``record_trace.py``: 12 fused routes of 2^16 keys after a 60-node storm)
against a second witness, the same trace as Perfetto JSON, reduced here by
hand; and on small made-up intervals."""
import gzip
import json
import os

import pytest

import reduction
import work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_clip_and_gaps():
    assert reduction.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]
    assert reduction.clip([(0, 3), (5, 10)], 2, 7) == [(2, 3), (5, 7)]
    chip = reduction.Chip(0, [("a", 2, 4), ("b", 3, 6), ("a", 8, 9)], [])
    red = reduction.Reduced((0, 10), [chip], [("chipbench.wait", 5, 9),
                                              ("chipbench.dispatch", 0, 2)])
    assert red.busy_each_s() == [5e-9]
    assert red.gaps(chip) == [(0, 2), (6, 8), (9, 10)]
    assert red.seconds_matching(chip, "a") == 3e-9
    assert red.label(6, 8) == "wait" and red.label(0, 2) == "dispatch"
    assert red.idle_pct_max() == pytest.approx(50.0)


def _perfetto():
    """Window, busy union and route-kernel events of TPU 0, from the JSON."""
    with gzip.open(os.path.join(DATA, "route12.perfetto.json.gz")) as f:
        events = json.load(f)["traceEvents"]
    pid = next(e["pid"] for e in events if e.get("ph") == "M"
               and e["name"] == "process_name" and e["args"]["name"] == "/device:TPU:0")
    tid = next(e["tid"] for e in events if e.get("ph") == "M" and e["pid"] == pid
               and e["name"] == "thread_name" and e["args"]["name"] == "XLA Ops")
    win = next(e for e in events if e.get("name") == "chipbench.window")
    lo, hi = win["ts"], win["ts"] + win["dur"]
    ops = sorted((e["ts"], e["ts"] + e["dur"], e["name"]) for e in events
                 if e.get("ph") == "X" and e["pid"] == pid and e["tid"] == tid)
    busy, end = 0.0, lo
    for s, e, _ in ops:
        s, e = max(s, end), min(e, hi)
        if e > s:
            busy += e - s
            end = e
    kernel = [(min(e, hi) - max(s, lo)) for s, e, name in ops
              if work.ROUTE_KERNEL in name and e > lo and s < hi]
    total = sum(1 for _, _, name in ops if work.ROUTE_KERNEL in name)
    return (hi - lo) / 1e6, busy / 1e6, kernel, total


def test_reduction_agrees_with_the_perfetto_witness():
    red = reduction.reduce_file(os.path.join(DATA, "route12.xplane.pb"), 1)
    window_s, busy_s, kernel, total = _perfetto()
    chip = red.chips[0]
    assert total == 12 == sum(1 for name, _, _ in chip.ops if work.ROUTE_KERNEL in name)
    assert red.window_s == pytest.approx(window_s, abs=2e-6)
    assert red.busy_s() == pytest.approx(busy_s, abs=1e-6 * 60)
    # the chip's clock runs ~0.7 ms ahead of the host's spans in this trace,
    # so the first kernel falls just before the window: it is not counted
    lo, hi = red.window
    inside = [name for name, s, e in chip.ops
              if work.ROUTE_KERNEL in name and e > lo and s < hi]
    assert len(inside) == len(kernel) == 11
    assert red.seconds_matching(chip, work.ROUTE_KERNEL) == pytest.approx(
        sum(kernel) / 1e6, abs=1e-6 * 12)
    assert red.slowest_per_call_s(work.ROUTE_KERNEL, 11) == pytest.approx(
        sum(kernel) / 1e6 / 11, abs=1e-6)
    assert red.slowest_per_call_s("no such kernel", 11) is None
    assert 0 < red.busy_s() < red.window_s
    labels = {label for label, _ in red.breakdown()["idle_gaps"]}
    assert labels <= {"dispatch", "wait"}
