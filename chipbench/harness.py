"""The benchmark's harness: find a cell's files by name, run it, reduce, check.

Nothing here names a configuration, a traffic mix, a kind of traffic or a
metric.  A cell of ``BENCHMARK.json`` names its configuration
(``configs/<config>.json``) and its mix (``traffic/<traffic>.json``); the
mix's ``kind`` names its generator, ``kinds/<kind>.py``, a module with one
class ``Driver``; each metric is read by ``metrics/<name>.py``, a module
with one function ``read(run) -> float | None``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: JAX's persistent compilation cache, at one fixed path in the checkout:
#: the path is part of the cache key, so only the first run compiles
CACHE_DIR = os.path.join(HERE, ".jax_cache")
#: the router autotuner's verdicts, kept so later runs reuse the tiling
AUTOTUNE_FILE = os.path.join(HERE, ".autotune", "block_rows.json")
#: profiler output and TPU runtime logs of the latest run
OUT_DIR = os.path.join(HERE, ".out")


class NoChip(RuntimeError):
    """The cell needs TPU chips that this machine does not have."""


# -- the cell's files ---------------------------------------------------------
def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")


def load_part(kind: str, name: str, rehearse: bool) -> dict:
    """``configs/<name>.json`` or ``traffic/<name>.json``; the tiny sizes
    under ``rehearse`` replace the real ones in a rehearsal."""
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        part = json.load(f)
    tiny = part.pop("rehearse", {})
    if rehearse:
        part.update(tiny)
    return part


def load_module(directory: str, name: str):
    """``<directory>/<name>.py`` under the benchmark, imported by its path."""
    path = os.path.join(HERE, directory, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_{directory}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(name: str):
    return load_module("metrics", name).read


def make_driver(config: dict, mix: dict, seed: int, devices):
    """The generator of the mix's ``kind``, built for one run."""
    return load_module("kinds", mix["kind"]).Driver(config, mix, seed, devices)


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` this cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    reported = {m["name"] for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])}
    return [m for m in bench[section]
            if (cell in m["workloads"] if "workloads" in m
                else m.get("moves", m["name"]) in reported)]


# -- environment -------------------------------------------------------------
def prepare_environment(*, rehearse: bool, workload: str) -> None:
    """Set what must be set before JAX is imported."""
    os.environ["REPRO_AUTOTUNE_CACHE"] = AUTOTUNE_FILE
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(OUT_DIR, "tpu_logs"))
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        chips = find_cell(load_benchmark(), workload)["chips"]
        flag = f"--xla_force_host_platform_device_count={chips}"
        if chips > 1 and flag not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()


def enable_compile_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs lowered (compiled or fetched from the cache) while
    it is installed."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw) -> None:
        if name == self.EVENT:
            self.count += 1

    def stop(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


class GcPauses:
    """Python's garbage-collection pauses while it is installed."""

    def __init__(self):
        self.pauses: list[float] = []
        self._t0: float | None = None
        gc.callbacks.append(self._on)

    def _on(self, phase, _info) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append(time.perf_counter() - self._t0)
            self._t0 = None

    def stop(self) -> None:
        gc.callbacks.remove(self._on)

    def facts(self) -> dict:
        return {"gc_collections": len(self.pauses),
                "gc_pause_ms_max": 1e3 * max(self.pauses, default=0.0)}


# -- what a run hands the metric readers ---------------------------------------
@dataclasses.dataclass
class Run:
    """Everything a metric reader may read of one run."""

    cell: dict
    config: dict
    mix: dict
    setup_s: float
    #: host-clock facts of the measured window (seconds, keys, requests, ...)
    window: dict
    #: the benchmark's own host spans: name -> seconds of each
    spans: dict
    #: deltas of the program's own counters over the window
    counters: dict
    #: per-request latency in ms, due to handed back (served cells)
    latencies_ms: np.ndarray | None
    #: the reduced profiler trace (``--trace 1`` on a chip), else None
    trace: object | None
    #: the device's row of ``peaks.json`` (None in a rehearsal)
    peaks: dict | None


def autotune_verdicts() -> str:
    """The autotuner's persisted tilings, as the run found or made them."""
    try:
        with open(AUTOTUNE_FILE) as f:
            table = json.load(f)
    except (OSError, ValueError):
        return "no autotuner verdict (explicit tiling or none needed)"
    return json.dumps({k: v["block_rows"] for k, v in sorted(table.items())})


def device_peaks(kind: str, rehearse: bool) -> dict | None:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind in table:
        return table[kind]
    if rehearse:
        return None
    raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


@contextlib.contextmanager
def profiled(enabled: bool):
    """The JAX profiler around the window; yields the trace directory."""
    if not enabled:
        yield None
        return
    import jax

    path = os.path.join(OUT_DIR, "trace")
    shutil.rmtree(path, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # a span per Python call would slow the host
    jax.profiler.start_trace(path, profiler_options=options)
    try:
        yield path
    finally:
        jax.profiler.stop_trace()


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        rehearse: bool = False, t_start: float | None = None,
        patch=None) -> dict:
    """One run of one cell; returns the result object the run prints.

    ``patch(driver)`` may replace parts of the built system before the
    warm-up: the control and the fault tests put a broken path there.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    bench = load_benchmark()
    cell = find_cell(bench, workload)
    config = load_part("configs", cell["config"], rehearse)
    mix = load_part("traffic", cell["traffic"], rehearse)

    import jax

    enable_compile_cache()
    devices = jax.devices()
    if not rehearse:
        if devices[0].platform != "tpu":
            raise NoChip(f"JAX finds no TPU (platform {devices[0].platform})")
        if len(devices) < cell["chips"]:
            raise NoChip(f"{workload} needs {cell['chips']} chips, "
                         f"JAX finds {len(devices)}")
    devices = devices[: cell["chips"]]
    peaks = device_peaks(devices[0].device_kind, rehearse)

    driver = make_driver(config, mix, seed, devices)
    driver.setup(seconds)
    if patch is not None:
        patch(driver)
    driver.warm()
    print(f"tiling: {autotune_verdicts()}", file=sys.stderr, flush=True)
    # what set-up left alive is never garbage: keep Python's collector to
    # the window's own objects, so a full collection does not walk JAX
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    compiles = CompileCounter()
    pauses = GcPauses()
    with profiled(trace) as trace_dir:
        window = driver.window(seconds, annotate=trace)
    pauses.stop()
    compiles.stop()
    window["facts"].update(pauses.facts())
    compiles_in_window = compiles.count
    peak = memory_peak(devices)
    checks = driver.check()
    correct = all(value <= limit for value, limit in checks.values())

    reduced = None
    if trace_dir is not None:
        import reduction

        reduced = reduction.reduce_dir(trace_dir, len(devices))
    record = Run(
        cell=cell, config=config, mix=mix, setup_s=setup_s,
        window=window["facts"], spans=window["spans"],
        counters=window["counters"], latencies_ms=window.get("latencies_ms"),
        trace=reduced, peaks=peaks,
    )
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, workload, section):
        value = load_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {
        "correct": bool(correct),
        "attempted": int(window["attempted"]),
        "failed": int(window["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["compiles_in_window"] = compiles_in_window
    result["seed"] = seed
    result["window"] = window["facts"]
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, (value, limit) in checks.items()}
    return result


def emit(result: dict, *, rehearse: bool) -> int:
    """Print the result: the compared numbers as the last lines of standard
    error, the result object as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    if rehearse:
        shown = {k: result[k] for k in ("correct", "attempted", "failed",
                                        "metrics", "compiles_in_window")}
        print("rehearsal (CPU, interpret mode), no result line: "
              + json.dumps(shown), flush=True)
        return 0 if result["correct"] else 1
    print(json.dumps(result), flush=True)
    return 0
