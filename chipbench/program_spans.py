"""The program's own spans, read from a run's profiler trace.

The program opens its spans through ``repro.observability.trace.span``.
While the profiler traces, each span is a ``repro.<name>`` event on a host
thread line of the same ``.xplane.pb`` that holds the chip's operations.
Its tags are the event's stats.  This module reads those events from the
run's newest trace under ``harness.OUT_DIR/trace`` and clips them to the
traced window.  The parse is cached, because several readers use it.

Host spans and the chip's events are stamped by different clocks.  The
offset between them is estimated from the route program.  The k-th
``route.launch`` span is paired with the k-th ``ROUTE_PROGRAM`` module on
the chip.  A chip cannot start a program before the host asks for it, so
the offset is the smallest (device start - launch start) over the pairs.
A host span shifted by the offset can be laid against the chip's idle
gaps.  The estimate is late by the least enqueue latency of any pair.

On a program that opens no such span, as before the spans existed, every
reader of this module returns None.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import warnings

import harness
import reduction

PREFIX = "repro."
#: the fused route's program as the chip's ``XLA Modules`` line names it
#: (the ``route_2d`` jit of ``kernels/fused.py``)
ROUTE_PROGRAM = "jit_route_2d("


@dataclasses.dataclass
class ProgramSpans:
    #: span name (without the prefix) -> (start ns, end ns, tags), clipped
    #: to the window, in the order they started
    spans: dict[str, list[tuple[int, int, dict]]]
    #: chip clock minus host clock, in ns; None without route programs
    offset_ns: int | None

    def of(self, name: str) -> list[tuple[int, int, dict]]:
        return self.spans.get(name, [])

    def total_us(self, name: str) -> float:
        return sum(e - s for s, e, _ in self.of(name)) / 1e3

    def intervals(self, name: str, shifted: bool = False) -> list[tuple[int, int]]:
        """The spans' intervals; ``shifted`` puts them on the chip's clock."""
        d = self.offset_ns if shifted else 0
        return [(s + d, e + d) for s, e, _ in self.of(name)]

    def served_dispatches(self) -> list[dict]:
        """The tags of each ``dispatch`` span that dispatched a batch."""
        return [tags for _, _, tags in self.of("dispatch") if tags.get("size")]


def host_events(profile) -> list[tuple[str, int, int, dict]]:
    """Every ``repro.*`` host event: (name, start ns, end ns, tags)."""
    out = []
    # the binding type of an event's stats warns once, when first built
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in profile.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        start = int(e.start_ns)
                        out.append((e.name[len(PREFIX):], start,
                                    start + int(e.duration_ns), dict(e.stats)))
    return sorted(out, key=lambda ev: ev[1])


def clock_offset_ns(launch_starts: list[int], program_starts: list[int]) -> int | None:
    """Smallest (program start - launch start) over the k-th pairs."""
    pairs = list(zip(sorted(launch_starts), sorted(program_starts)))
    return min(p - h for h, p in pairs) if pairs else None


def from_events(events, red: reduction.Reduced) -> ProgramSpans:
    """Clip the host events to the window of ``red``, and find the offset
    from its first chip's route programs."""
    lo, hi = red.window
    spans: dict[str, list[tuple[int, int, dict]]] = {}
    for name, s, e, tags in events:
        if e > lo and s < hi:
            spans.setdefault(name, []).append((max(s, lo), min(e, hi), tags))
    launches = [s for name, s, _, _ in events if name == "route.launch"]
    programs = ([s for name, s, _ in red.chips[0].modules if ROUTE_PROGRAM in name]
                if red.chips else [])
    return ProgramSpans(spans, clock_offset_ns(launches, programs))


@functools.lru_cache(maxsize=2)
def _host_events_of(path: str) -> list[tuple[str, int, int, dict]]:
    from jax.profiler import ProfileData

    return host_events(ProfileData.from_file(path))


def of_run(run) -> ProgramSpans | None:
    """The program's spans in a traced run; None where it opened none."""
    if run.trace is None:
        return None
    try:
        path = reduction.newest_xplane(os.path.join(harness.OUT_DIR, "trace"))
    except FileNotFoundError:
        return None
    found = from_events(_host_events_of(path), run.trace)
    return found if found.spans else None


def per_call_us(run, name: str) -> float | None:
    """µs of the ``name`` spans per bulk call of the window: 0 where the
    program opened other spans but none of these."""
    found = of_run(run)
    calls = run.window.get("calls")
    if found is None or not calls:
        return None
    return found.total_us(name) / calls


def per_dispatch_us(run, *names: str) -> float | None:
    """µs of the ``names`` spans per served dispatch."""
    found = of_run(run)
    if found is None:
        return None
    dispatches = len(found.served_dispatches())
    if not dispatches:
        return None
    return sum(found.total_us(n) for n in names) / dispatches


def idle_in_pct(red: reduction.Reduced, found: ProgramSpans, name: str) -> float | None:
    """Share of the window, in percent, in which the idlest chip sat idle
    while the host was inside a ``name`` span, the spans shifted onto the
    chip's clock."""
    if found.offset_ns is None or not red.chips or red.window_s <= 0:
        return None
    busy = red.busy_each_s()
    chip = red.chips[busy.index(min(busy))]
    inside = reduction.union(found.intervals(name, shifted=True))
    overlap = 0
    for gs, ge in red.gaps(chip):
        for s, e in reduction.clip(inside, gs, ge):
            overlap += e - s
    return 100.0 * overlap / 1e9 / red.window_s
