"""Reduction of a JAX profiler trace to what the metric readers read.

The trace (``.xplane.pb``, read with ``jax.profiler.ProfileData``) holds a
plane per TPU chip (``/device:TPU:<i>``) whose ``XLA Ops`` line has one
event per operation that ran on the chip, and whose ``XLA Modules`` line
has one event per program; and host planes whose thread lines carry the
benchmark's own spans (``chipbench.*``, written by
``jax.profiler.TraceAnnotation``) on the same clock.  From these:

* the traced window: the ``chipbench.window`` span;
* busy time of a chip: the union of its operation intervals inside the
  window, so overlapping operations count once;
* the time of a kernel or a program: the summed durations of the events
  whose name contains a given pattern, inside the window;
* idle gaps: the holes in that union, each labelled with the benchmark span
  that overlaps it most -- what the host was doing while the chip waited.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"


def union(intervals) -> list[tuple[int, int]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclasses.dataclass
class Chip:
    """One chip's events: (name, start ns, end ns)."""

    index: int
    ops: list[tuple[str, int, int]]
    modules: list[tuple[str, int, int]]


@dataclasses.dataclass
class Reduced:
    window: tuple[int, int]
    chips: list[Chip]
    #: the benchmark's host spans: (name, start ns, end ns)
    host: list[tuple[str, int, int]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _busy(self, chip: Chip) -> list[tuple[int, int]]:
        return union(clip([(s, e) for _, s, e in chip.ops], *self.window))

    def busy_each_s(self) -> list[float]:
        return [sum(e - s for s, e in self._busy(c)) / 1e9 for c in self.chips]

    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips."""
        each = self.busy_each_s()
        return sum(each) / len(each) if each else 0.0

    def idle_pct_max(self) -> float | None:
        """The idle share of the idlest chip, in percent."""
        each = self.busy_each_s()
        if not each or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - min(each) / self.window_s)

    def seconds_matching(self, chip: Chip, pattern: str, line: str = OPS_LINE) -> float:
        events = chip.ops if line == OPS_LINE else chip.modules
        picked = [(s, e) for name, s, e in events if pattern in name]
        return sum(e - s for s, e in clip(picked, *self.window)) / 1e9

    def slowest_per_call_s(self, pattern: str, calls: int, line: str = OPS_LINE
                           ) -> float | None:
        """Seconds of the events matching ``pattern`` per call, on the chip
        where they took longest; None where no chip ran any."""
        each = [self.seconds_matching(c, pattern, line) for c in self.chips]
        if not calls or not any(each):
            return None
        return max(each) / calls

    def gaps(self, chip: Chip) -> list[tuple[int, int]]:
        """Idle holes of one chip inside the window, longest first."""
        lo, hi = self.window
        edges = [lo] + [x for iv in self._busy(chip) for x in iv] + [hi]
        holes = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        return sorted(holes, key=lambda h: h[0] - h[1])

    def label(self, start: int, end: int) -> str:
        """The benchmark span (not the window) overlapping [start, end) most."""
        best, best_overlap = "no benchmark span", 0
        for name, s, e in self.host:
            if name == WINDOW_SPAN:
                continue
            overlap = min(e, end) - max(s, start)
            if overlap > best_overlap:
                best, best_overlap = name[len(HOST_SPAN_PREFIX):], overlap
        return best

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (seconds summed over the
        chips, divided by their count) and the longest idle gaps (of every
        chip), each gap named by what the host was doing in it."""
        totals: dict[str, float] = {}
        lo, hi = self.window
        for chip in self.chips:
            for name, s, e in chip.ops:
                if e > lo and s < hi:
                    totals[name] = totals.get(name, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
        n = max(len(self.chips), 1)
        ops = sorted(((k, v / n) for k, v in totals.items()), key=lambda kv: -kv[1])
        holes = sorted(((h, c.index) for c in self.chips for h in self.gaps(c)),
                       key=lambda hc: hc[0][0] - hc[0][1])[:top]
        return {
            "device_ops": [[k, v] for k, v in ops[:top]],
            "idle_gaps": [[self.label(*h), (h[1] - h[0]) / 1e9] for h, _ in holes],
        }


def from_profile(profile, n_chips: int) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` to the first ``n_chips`` chips."""
    chips: dict[int, Chip] = {}
    host: list[tuple[str, int, int]] = []
    for plane in profile.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            chip = Chip(int(match.group(1)), [], [])
            for line in plane.lines:
                target = {OPS_LINE: chip.ops, MODULES_LINE: chip.modules}.get(line.name)
                if target is not None:
                    target.extend((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                                  for e in line.events)
            chips[chip.index] = chip
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                            for e in line.events if e.name.startswith(HOST_SPAN_PREFIX))
    used = [chips[i] for i in sorted(chips)[:n_chips]]
    windows = [(s, e) for name, s, e in host if name == WINDOW_SPAN]
    if windows:
        window = max(windows, key=lambda w: w[1] - w[0])
    else:
        ends = [x for c in used for _, s, e in c.ops for x in (s, e)]
        window = (min(ends), max(ends)) if ends else (0, 0)
    return Reduced(window=window, chips=used, host=host)


def reduce_file(path: str, n_chips: int) -> Reduced:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path), n_chips)


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str, n_chips: int) -> Reduced:
    return reduce_file(newest_xplane(trace_dir), n_chips)
