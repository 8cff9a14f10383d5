"""block_rows autotuner: argmin selection, measure-once persistence across
processes, candidate filtering, and the BatchRouter wiring rules (explicit
value wins; jnp fallback and interpret mode never tune)."""
import json

import numpy as np
import pytest

from repro.kernels import autotune
from repro.serving.batch_router import BatchRouter


def _fake_measure(times: dict, calls: list):
    def measure(block_rows: int) -> None:
        calls.append(block_rows)
        measure.clock = getattr(measure, "clock", 0.0) + times[block_rows]

    return measure


def test_tuner_picks_fastest_candidate_and_persists(tmp_path, monkeypatch):
    path = str(tmp_path / "cache.json")
    # fake timer: pretend 256 is the fastest tiling
    times = {128: 5e-4, 256: 1e-4, 512: 3e-4}
    ticker = {"t": 0.0}

    def fake_clock():
        return ticker["t"]

    calls = []

    def measure(c):
        calls.append(c)
        ticker["t"] += times[c]

    monkeypatch.setattr(autotune.time, "perf_counter", fake_clock)
    got = autotune.tuned_block_rows("tpu", rows=8192, capacity=64,
                                    measure=measure, path=path)
    assert got == 256
    # warmup + repeats per candidate, every candidate tried exactly once
    assert sorted(set(calls)) == sorted(autotune.CANDIDATES)
    with open(path) as f:
        cache = json.load(f)
    key = f"{autotune.CACHE_SCHEMA}/tpu/fused/rows=8192/capacity=64"
    assert cache[key]["block_rows"] == 256

    # second call: pure cache hit — measure must NOT run again
    calls.clear()
    got2 = autotune.tuned_block_rows("tpu", rows=8192, capacity=64,
                                     measure=measure, path=path)
    assert got2 == 256 and calls == []


def test_tuner_filters_candidates_larger_than_the_batch(tmp_path, monkeypatch):
    path = str(tmp_path / "cache.json")
    ticker = {"t": 0.0}
    monkeypatch.setattr(autotune.time, "perf_counter", lambda: ticker["t"])
    tried = []

    def measure(c):
        tried.append(c)
        ticker["t"] += 1e-4

    autotune.tuned_block_rows("tpu", rows=200, capacity=64,
                              measure=measure, path=path)
    assert max(tried) <= 256  # 512+ row blocks only pad dead lanes at 200 rows


def test_tuner_distinguishes_cache_keys(tmp_path, monkeypatch):
    path = str(tmp_path / "cache.json")
    ticker = {"t": 0.0}
    monkeypatch.setattr(autotune.time, "perf_counter", lambda: ticker["t"])

    def measure(c):
        ticker["t"] += (1e-4 if c == 128 else 5e-4)

    a = autotune.tuned_block_rows("tpu", rows=4096, capacity=64,
                                  measure=measure, path=path)

    def measure2(c):
        ticker["t"] += (1e-4 if c == 512 else 5e-4)

    b = autotune.tuned_block_rows("tpu", rows=4096, capacity=256,
                                  measure=measure2, path=path)
    assert a == 128 and b == 512
    # a different datapath variant must NOT inherit the fused verdict
    c = autotune.tuned_block_rows("tpu", rows=4096, capacity=64,
                                  measure=measure2, path=path,
                                  variant="two_pass")
    assert c == 512
    with open(path) as f:
        assert len(json.load(f)) == 3


def test_batch_router_block_rows_resolution(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "cache.json"))
    # explicit value wins, no tuning
    r = BatchRouter(8, block_rows=256)
    assert r._resolve_block_rows(4096) == 256
    # jnp fallback (CPU backend): default, no tuning
    r = BatchRouter(8)
    assert r._resolve_block_rows(4096) == autotune.DEFAULT_BLOCK_ROWS
    # interpret mode is a test harness: default, no tuning
    r = BatchRouter(8, interpret=True)
    assert r._resolve_block_rows(4096) == autotune.DEFAULT_BLOCK_ROWS
    # Pallas path selected -> the tuner runs (stubbed) and is memoised per rows
    r = BatchRouter(8, use_pallas=True)
    seen = []

    def fake_tuned(backend, rows, capacity, measure, **kw):
        seen.append((backend, rows, capacity))
        return 8

    monkeypatch.setattr(autotune, "tuned_block_rows", fake_tuned)
    assert r._resolve_block_rows(4096) == 8
    assert r._resolve_block_rows(4096) == 8  # memoised: tuner ran once
    assert len(seen) == 1 and seen[0][1:] == (4096, 64)


def test_tuner_survives_corrupt_cache(tmp_path, monkeypatch):
    path = tmp_path / "cache.json"
    path.write_text("{ not json")
    ticker = {"t": 0.0}
    monkeypatch.setattr(autotune.time, "perf_counter", lambda: ticker["t"])

    def measure(c):
        ticker["t"] += 1e-4

    got = autotune.tuned_block_rows("tpu", rows=1024, capacity=64,
                                    measure=measure, path=str(path))
    assert got in autotune.CANDIDATES
    with open(path) as f:
        json.load(f)  # rewritten as valid json


def test_default_cache_path_is_env_overridable(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "/tmp/somewhere.json")
    assert autotune.cache_path() == "/tmp/somewhere.json"
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE")
    assert autotune.cache_path().endswith("block_rows.json")
