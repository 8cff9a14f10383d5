"""Span tracing: one ``span`` API over two sinks.

``span(name, tracer=, clock=, **tags)`` bounds a stretch of host code.  It
writes to whichever sinks are live:

* **the profiler** -- while ``jax.profiler`` is tracing, the span opens
  ``TraceAnnotation("repro." + name)``, so it lands on the host plane of
  the same ``.xplane.pb`` as the device's operations;
* **the ring** -- given a ``SpanTrace`` and the component's injected µs
  clock, the span records ``[start, end]`` into the ring on that clock
  (virtual spans are deterministic, wall spans are production traces).

With neither live, ``span`` returns the shared ``NULL_SPAN`` after one
check of the profiler, and builds nothing.  Tags whose values cost
anything to build are set after the fact, under ``if s:``, through
``s.tag(...)``; the null span is false.  Spans belong in host code only:
inside a traced or jitted function a span would time the tracing.

The spans the program opens (DESIGN.md §15.1), and their sinks (P the
profiler, R the ring):

* ``route.call`` (P): ``BatchRouter.route_keys``, ``StorePlacement.place_keys``;
* ``route.layout`` (P): each eager executable around the route program
  (the pad and the slice of a ragged batch; in the sharded route the pad,
  the upload, the donation copy and the output slice);
* ``route.launch`` (P): the call that enqueues the route program, tagged
  ``rows`` and ``block_rows``;
* ``dispatch`` (R, P): ``MicroBatcher._close``, from the gate to the
  handle, tagged ``size``, ``shed``, ``bound_us`` and ``wait_us_sum``;
* ``upload`` (P): ``LifecycleDispatch``'s ``device_put`` of a batch;
* ``lifecycle_tick`` (R, P): ``LifecycleManager.tick``, with the child
  ``detector.poll`` (P);
* ``collect`` (R, P): ``MicroBatcher._collect``'s wait on the result;
* ``breakers.observe`` (P): ``BreakerBoard.observe``, once per pump;
* ``request`` (R): one per served request, arrival to completion;
* ``read`` (R): one per hedged read.

The ring is fixed-capacity: recording is O(1) and allocation-bounded
forever (old spans are overwritten, never accumulated), which is what
lets it stay on in production.  Per-name record totals are kept
monotonically alongside, so invariants like "one ``request`` span per
served request" hold regardless of how many spans the ring has since
recycled (``count`` reads the totals; ``spans`` reads what is retained).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

from jax.profiler import TraceAnnotation

#: names of the spans the ring receives
SPAN_DISPATCH = "dispatch"
SPAN_COLLECT = "collect"
SPAN_READ = "read"
SPAN_REQUEST = "request"
SPAN_LIFECYCLE_TICK = "lifecycle_tick"

#: prefix of every span the program writes into the profiler's trace
PROFILER_PREFIX = "repro."

_profiling = TraceAnnotation.is_enabled


@dataclasses.dataclass(frozen=True)
class Span:
    """One completed stage: a named µs interval with tenant + tags."""

    name: str
    t_start_us: int
    t_end_us: int
    tenant: str | None = None
    tags: tuple = ()

    @property
    def duration_us(self) -> int:
        return self.t_end_us - self.t_start_us

    def tag(self, key: str, default=None):
        for k, v in self.tags:
            if k == key:
                return v
        return default


class SpanTrace:
    """Fixed-capacity span ring + monotone per-name record totals."""

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._ring: list[Span | None] = [None] * self.capacity
        self._next = 0
        self._recorded: dict[str, int] = {}
        self.total = 0

    def record(
        self,
        name: str,
        t_start_us: int,
        t_end_us: int,
        *,
        tenant: str | None = None,
        **tags,
    ) -> Span:
        span = Span(
            name=name,
            t_start_us=int(t_start_us),
            t_end_us=int(t_end_us),
            tenant=tenant,
            tags=tuple(sorted(tags.items())),
        )
        self._ring[self._next % self.capacity] = span
        self._next += 1
        self.total += 1
        self._recorded[name] = self._recorded.get(name, 0) + 1
        return span

    # -- read side -----------------------------------------------------------
    @property
    def dropped(self) -> int:
        """Spans recycled out of the ring (recorded minus retained)."""
        return max(0, self.total - self.capacity)

    def count(self, name: str | None = None) -> int:
        """Monotone record total — survives ring recycling."""
        if name is None:
            return self.total
        return self._recorded.get(name, 0)

    def spans(
        self, name: str | None = None, tenant: str | None = None
    ) -> list[Span]:
        """Retained spans, oldest first, optionally filtered."""
        start = max(0, self._next - self.capacity)
        out = []
        for i in range(start, self._next):
            span = self._ring[i % self.capacity]
            if span is None:
                continue
            if name is not None and span.name != name:
                continue
            if tenant is not None and span.tenant != tenant:
                continue
            out.append(span)
        return out


class _NullSpan:
    """What ``span`` returns when no sink is live: does nothing, is false."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None

    def __bool__(self) -> bool:
        return False

    def tag(self, **tags) -> None:
        return None


NULL_SPAN = _NullSpan()


class _LiveSpan:
    """A span with at least one live sink."""

    __slots__ = ("_name", "_tracer", "_clock", "_tags", "_annotation", "_t0")

    def __init__(self, name, tracer, clock, annotate: bool, tags: dict):
        self._name = name
        self._tracer = tracer
        self._clock = clock
        self._tags = tags
        self._annotation = (
            TraceAnnotation(PROFILER_PREFIX + name, **tags) if annotate else None
        )

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        if self._tracer is not None:
            self._t0 = self._clock()
        return self

    def tag(self, **tags) -> None:
        """Add tags to the span before it ends."""
        self._tags.update(tags)
        if self._annotation is not None:
            self._annotation.set_metadata(**tags)

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._tracer is not None:
            self._tracer.record(self._name, self._t0, self._clock(), **self._tags)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)


def span(
    name: str,
    tracer: SpanTrace | None = None,
    clock: Callable[[], int] | None = None,
    **tags,
):
    """A context manager bounding host work named ``name``.

    ``tracer``: the ring to record into, on ``clock`` (a callable giving
    the component's time in integer µs; required with ``tracer``).  The
    profiler sink is live while ``jax.profiler`` traces.  Returns
    ``NULL_SPAN`` when neither sink is live.
    """
    annotate = _profiling()
    if tracer is None and not annotate:
        return NULL_SPAN
    if tracer is not None and clock is None:
        raise ValueError(f"span {name!r}: a ring needs the component's clock")
    return _LiveSpan(name, tracer, clock, annotate, tags)
