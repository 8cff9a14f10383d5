"""The program-span reader, checked on a trace recorded on a TPU v5e
(``record_spans.py``: twelve fused routes of 2^20 keys after a 60-node
storm, four in flight) against a second witness, the same trace as Perfetto
JSON, read here by hand; and on small made-up intervals."""
import gzip
import json
import os
import shutil

import pytest

import harness
import program_spans
import reduction

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
XPLANE = os.path.join(DATA, "spans12.xplane.pb")
CALLS = 12


def _run(red, calls):
    return harness.Run(cell={}, config={}, mix={}, setup_s=0.0,
                       window={"calls": calls}, spans={}, counters={},
                       latencies_ms=None, trace=red, peaks=None)


def _reader(name):
    return harness.load_reader(name)


def test_offset_clip_and_idle_on_made_up_intervals():
    # the chip runs the route program 3 ns after each launch at best
    chip = reduction.Chip(0, [("op", 10, 20), ("op", 40, 50)],
                          [("jit_route_2d(1)", 10, 20), ("jit_route_2d(1)", 40, 50),
                           ("jit_reshape(2)", 21, 22)])
    red = reduction.Reduced((0, 100), [chip], [])
    events = [("route.launch", 5, 7, {"rows": 8}), ("route.launch", 37, 39, {}),
              ("route.layout", 22, 30, {}), ("route.layout", 95, 110, {}),
              ("route.call", -5, 2, {})]
    found = program_spans.from_events(events, red)
    assert found.offset_ns == 3  # min(10 - 5, 40 - 37)
    assert found.intervals("route.layout") == [(22, 30), (95, 100)]  # clipped
    assert found.intervals("route.call") == [(0, 2)]
    assert found.intervals("route.layout", shifted=True) == [(25, 33), (98, 103)]
    assert found.total_us("route.launch") == pytest.approx(4e-3)
    assert found.of("route.launch")[0][2] == {"rows": 8}
    # idle gaps of the chip: [0, 10), [20, 40), [50, 100); the shifted layout
    # spans overlap them over [25, 33) and [98, 100): 10 ns of 100
    assert program_spans.idle_in_pct(red, found, "route.layout") == pytest.approx(10.0)
    assert red.idle_pct_max() == pytest.approx(80.0)


def test_no_offset_without_route_programs():
    assert program_spans.clock_offset_ns([1, 2], []) is None
    red = reduction.Reduced((0, 10), [reduction.Chip(0, [], [])], [])
    found = program_spans.from_events([("route.layout", 1, 2, {})], red)
    assert found.offset_ns is None
    assert program_spans.idle_in_pct(red, found, "route.layout") is None


def test_readers_are_silent_without_program_spans(tmp_path, monkeypatch):
    """On a program without spans every new reader returns None."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    red = reduction.Reduced((0, 10), [reduction.Chip(0, [("op", 1, 2)], [])], [])
    trace = tmp_path / "trace"
    trace.mkdir()
    shutil.copy(os.path.join(DATA, "route12.xplane.pb"), trace / "old.xplane.pb")
    for name in ("launch_us.bulk", "layout_us.bulk", "launch_us.mesh",
                 "layout_us.mesh", "idle_in_layout_pct.bulk", "queue_us.served",
                 "tick_us.served", "route_us.served", "collect_us.served",
                 "observe_us.served"):
        assert _reader(name)(_run(red, 12)) is None, name
        assert _reader(name)(_run(None, 12)) is None, name


def _perfetto():
    """From the JSON witness: the k-th launch span's start and the k-th route
    program's start on TPU 0, in µs, and the window."""
    with gzip.open(os.path.join(DATA, "spans12.perfetto.json.gz")) as f:
        events = json.load(f)["traceEvents"]
    pid = next(e["pid"] for e in events if e.get("ph") == "M"
               and e["name"] == "process_name" and e["args"]["name"] == "/device:TPU:0")
    tid = next(e["tid"] for e in events if e.get("ph") == "M" and e["pid"] == pid
               and e["name"] == "thread_name" and e["args"]["name"] == "XLA Modules")
    programs = sorted(e["ts"] for e in events if e.get("ph") == "X" and e["pid"] == pid
                      and e["tid"] == tid and e["name"].startswith("jit_route_2d("))
    launches = sorted(e["ts"] for e in events if e.get("name") == "repro.route.launch")
    win = next(e for e in events if e.get("name") == "chipbench.window")
    return launches, programs, (win["ts"], win["ts"] + win["dur"])


@pytest.fixture
def chip_run(tmp_path, monkeypatch):
    """The recorded trace as a traced run of the benchmark would leave it."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    trace = tmp_path / "trace" / "plugins" / "profile" / "1"
    trace.mkdir(parents=True)
    shutil.copy(XPLANE, trace / "host.xplane.pb")
    return _run(reduction.reduce_file(XPLANE, 1), CALLS)


def test_offset_agrees_with_the_perfetto_witness(chip_run):
    launches, programs, _ = _perfetto()
    assert len(launches) == len(programs) == CALLS
    found = program_spans.of_run(chip_run)
    witness = min(p - h for h, p in zip(launches, programs))  # µs
    assert found.offset_ns / 1e3 == pytest.approx(witness, abs=1e-3)
    # shifted by the offset, every launch precedes its program on the chip
    shifted = sorted(s for s, _ in found.intervals("route.launch", shifted=True))
    ran = sorted(s for name, s, _ in chip_run.trace.chips[0].modules
                 if program_spans.ROUTE_PROGRAM in name)
    assert all(h <= p for h, p in zip(shifted, ran))


def test_split_of_the_recorded_storm_calls(chip_run):
    found = program_spans.of_run(chip_run)
    assert len(found.of("route.call")) == CALLS
    assert len(found.of("route.launch")) == CALLS
    assert len(found.of("route.layout")) == 2 * CALLS  # reshape in and out
    assert {t["rows"] for _, _, t in found.of("route.launch")} == {8192}
    launch = _reader("launch_us.bulk")(chip_run)
    layout = _reader("layout_us.bulk")(chip_run)
    dispatch = sum(e - s for name, s, e in chip_run.trace.host
                   if name == "chipbench.dispatch") / 1e3 / CALLS
    assert 0 < launch and 0 < layout
    assert launch + layout <= dispatch
    idle_in_layout = _reader("idle_in_layout_pct.bulk")(chip_run)
    assert 0 <= idle_in_layout <= _reader("idle_pct.bulk")(chip_run)
