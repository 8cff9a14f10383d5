"""Device-side per-shard load accumulation + theory-bound drift tracking.

The paper's balance and minimal-disruption claims, measured on LIVE
traffic (DESIGN.md §15).  Three pieces:

* ``route_with_load_impl`` — the instrumented route: the engine's fused
  lookup+divert pass PLUS a per-shard bincount of the replica vector,
  folded into the SAME traced dispatch over a capacity-length u32
  accumulator that rides along as a traced device operand.  Same
  dispatch-count discipline as the bare router — no host loop over
  replicas, no extra transfer, no second dispatch.  At serving batch
  sizes every key is counted; at bulk-analytics sizes the pass counts a
  deterministic 1/2^``sample_shift`` stride sample and accumulates
  ``2^sample_shift`` per sampled key, so exact and sampled batches mix
  coherently in one accumulator (see ``LoadConfig.exact_cutoff`` for why
  sampling is load-bearing: counting every key of a 1M-key batch costs
  more than the 3 % overhead budget on a single-core host no matter how
  the histogram is phrased).  The instrumented route is pure-jnp on
  every backend (elementwise + one reduction — no Pallas twin needed).  While-free, affine in ω, dtype-closed, zero
  transfers — certified as ``observability/load_pass``.

* ``LoadMonitor`` — the host control plane: attaches to a ``BatchRouter``
  (``router.attach_load_monitor``), holds the device accumulator across
  batches, and drains it to host on a configurable batch cadence — ONE
  device->host transfer per window, zero host->device uploads (the reset
  re-uses a zeros array pinned once at construction; ``.at[].add`` is
  functional, so the pinned buffer is never clobbered).  Each drain
  updates registry gauges (per-shard counts, peak/mean) and evaluates the
  balance envelope: for m keys over n alive shards the expected peak/mean
  is ≈ 1 + sqrt(2·n·ln n / m), and observed ratios past a configurable
  multiple of that raise (or emit) a ``BalanceDriftAlarm``.

* ``DisruptionTracker`` — moved-fraction telemetry keyed to
  ``routing_epoch``: a fixed probe key set is re-routed whenever a drain
  observes the epoch advanced, and the fraction of probes whose shard
  changed is compared against the minimal-disruption bound
  ``slack · delta / max(n_before, n_after)`` (the paper's ``delta/n``
  per-event guarantee; ``bench_placement.movement_bound``'s r=1 shape).
  A breach raises (or emits) a ``DisruptionBoundAlarm``.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.observability.alarms import (
    BalanceDriftAlarm,
    DisruptionBoundAlarm,
    deliver,
)
from repro.observability.metrics import MetricsRegistry


# ---------------------------------------------------------------------------
# the instrumented device pass
# ---------------------------------------------------------------------------

#: accumulate via the vectorised one-hot comparison-sum up to this many
#: (sampled) replicas — its (capacity, m) intermediate stays cache-resident
#: and XLA CPU runs it several times faster than its serial scatter loop;
#: past it the intermediate blows the cache and the scatter wins
_ONEHOT_MAX = 1 << 17


def route_with_load_impl(
    keys: jax.Array,
    packed_mask: jax.Array,
    table: jax.Array,
    state: jax.Array,
    counts: jax.Array,
    *,
    omega: int,
    n_words: int,
    route,
    sample_shift: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Route one batch AND accumulate its per-shard load — ONE traced pass.

    keys          (N,) u32 key space (any int dtype; truncated like the
                  oracle)
    counts        (capacity,) u32 running per-shard key counts (traced
                  device operand — stays resident, never re-uploaded)
    route         the engine's fused jnp route
                  ``(keys, packed, table, state, omega=, n_words=)``
    sample_shift  log2 of the count-sampling stride: 0 counts every key;
                  s > 0 counts replicas ``[::2**s]`` with weight ``2**s``
                  (an unbiased stride estimate in the same key units, so
                  exact and sampled batches share one accumulator)

    Returns ``(replicas, new_counts)``: the same int32 replica ids the
    bare route produces (bit-exact — instrumentation must never change
    routing) and the accumulator advanced by this batch's (possibly
    sampled) bincount.  Replica ids are always in
    ``[0, n_total) ⊆ [0, capacity)``, so the scatter form carries
    ``promise_in_bounds`` and costs no clamp.
    """
    keys_u32 = keys.reshape(-1).astype(jnp.uint32)
    replicas = route(
        keys_u32, packed_mask, table, state, omega=omega, n_words=n_words
    )
    stride = 1 << sample_shift
    sampled = replicas[::stride] if sample_shift else replicas
    weight = np.uint32(stride)
    if sampled.shape[0] <= _ONEHOT_MAX:
        bins = jnp.arange(counts.shape[0], dtype=sampled.dtype)
        hist = jnp.sum(
            sampled[None, :] == bins[:, None], axis=1, dtype=jnp.uint32
        )
        new_counts = counts + hist * weight
    else:
        new_counts = counts.at[sampled].add(weight, mode="promise_in_bounds")
    return replicas.reshape(keys.shape), new_counts


@functools.partial(
    jax.jit, static_argnames=("omega", "n_words", "route", "sample_shift")
)
def _route_with_load_jit(keys, packed, table, state, counts, *, omega,
                         n_words, route, sample_shift=0):
    return route_with_load_impl(
        keys, packed, table, state, counts,
        omega=omega, n_words=n_words, route=route,
        sample_shift=sample_shift,
    )


# ---------------------------------------------------------------------------
# theory envelopes
# ---------------------------------------------------------------------------


def expected_peak_over_mean(total_keys: int, n_alive: int) -> float:
    """Expected max/mean shard load for ``total_keys`` uniform keys over
    ``n_alive`` shards: ≈ 1 + sqrt(2·n·ln n / m) (balls-into-bins maximum
    in the m >> n regime — the envelope ``bench_balance`` plots against)."""
    if n_alive <= 1 or total_keys <= 0:
        return 1.0
    return 1.0 + math.sqrt(
        2.0 * n_alive * math.log(n_alive) / float(total_keys)
    )


def disruption_bound(
    delta_events: int, n_before: int, n_after: int, slack: float
) -> float:
    """Allowed moved fraction for ``delta_events`` membership events: each
    event relocates one shard's share ≈ 1/n of the keys, so the window
    bound is ``slack · delta / max(n_before, n_after)`` capped at 1.  The
    slack absorbs hash-balance deviation of the affected shards' actual
    shares around 1/n (finite probe sets, small fleets)."""
    n = max(1, n_before, n_after)
    return min(1.0, slack * delta_events / n)


# ---------------------------------------------------------------------------
# host control plane
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LoadConfig:
    """Knobs for the load monitor."""

    #: drain the device accumulator to host every this many batches
    drain_every: int = 64
    #: batches of at most this many keys are counted exactly; bigger
    #: batches are stride-sampled (see ``sample_shift``).  The cutoff
    #: exists because exact counting is O(keys): on a single-core host a
    #: 1M-key histogram costs ~2 ms however it is phrased (scatter,
    #: one-hot reduce, even hand-written C) while the 3 % overhead budget
    #: against the fused route is < 1 ms — so at bulk sizes the counts
    #: must be estimated, and at serving sizes (≤ tens of thousands of
    #: keys per batch) they stay exact
    exact_cutoff: int = 1 << 15
    #: log2 stride for sampled batches: count replicas ``[::2**shift]``
    #: with weight ``2**shift``.  At the default 6, a 1M-key batch is
    #: estimated from 16 384 keys — per-shard relative stderr
    #: ≈ sqrt(2**shift · n / N) (~6 % for 64 shards), far inside the 2×
    #: balance-alarm threshold — for < 1 ms of accumulate work
    sample_shift: int = 6
    #: alarm when observed peak/mean exceeds this multiple of the expected
    #: peak/mean envelope
    balance_mult: float = 2.0
    #: skip the balance alarm below this many drained keys (the envelope
    #: is asymptotic; tiny samples are all noise)
    min_alarm_keys: int = 1_024
    #: slack on the delta/n disruption bound (see ``disruption_bound``)
    disruption_slack: float = 2.0
    #: probe keys the disruption tracker re-routes on epoch advance
    n_probe: int = 512
    probe_seed: int = 0x0B5E11

    def __post_init__(self):
        if self.drain_every < 1:
            raise ValueError(f"drain_every must be >= 1, got {self.drain_every}")
        if self.balance_mult <= 0 or self.disruption_slack <= 0:
            raise ValueError(
                f"need positive balance_mult / disruption_slack, got "
                f"{self.balance_mult} / {self.disruption_slack}"
            )
        if self.n_probe < 1:
            raise ValueError(f"n_probe must be >= 1, got {self.n_probe}")
        if self.sample_shift < 0:
            raise ValueError(
                f"sample_shift must be >= 0, got {self.sample_shift}"
            )
        if self.exact_cutoff < 0:
            raise ValueError(
                f"exact_cutoff must be >= 0, got {self.exact_cutoff}"
            )


class DisruptionTracker:
    """Moved-fraction-vs-bound telemetry, keyed to ``routing_epoch``."""

    def __init__(
        self,
        router,
        config: LoadConfig,
        metrics: MetricsRegistry,
        on_alarm=None,
    ):
        self.router = router
        self.config = config
        self.metrics = metrics
        self.on_alarm = on_alarm
        rng = np.random.default_rng(config.probe_seed)
        self._probe_host = rng.integers(
            0, 1 << 32, size=config.n_probe, dtype=np.uint32
        )
        self._probe_dev = jax.device_put(self._probe_host)
        self._epoch: int | None = None
        self._alive = 0
        self._routes: np.ndarray | None = None

    def _route_probes(self) -> np.ndarray:
        # straight through the dispatcher — bypasses the router's monitored
        # path so probe traffic never pollutes the load accumulator
        from repro.kernels import ops

        return np.asarray(
            ops.route_bulk(self._probe_dev, self.router._fleet_dev,
                           self.router.spec)
        )

    def observe(
        self,
        prev: np.ndarray,
        now: np.ndarray,
        delta_events: int,
        n_before: int,
        n_after: int,
        *,
        epoch: int | None = None,
    ) -> float:
        """Score one membership window: moved fraction vs the bound.

        Factored out of ``check`` so a pathological remap can be scored
        directly (the chaos suite seeds one to prove the alarm fires).
        """
        moved = float(np.mean(prev != now)) if len(prev) else 0.0
        bound = disruption_bound(
            delta_events, n_before, n_after, self.config.disruption_slack
        )
        self.metrics.gauge("load_moved_fraction").set(moved)
        self.metrics.gauge("load_moved_bound").set(bound)
        if moved > bound:
            self.metrics.counter("disruption_alarms_total").inc()
            deliver(
                DisruptionBoundAlarm(
                    moved,
                    bound,
                    delta_events=delta_events,
                    n_before=n_before,
                    n_after=n_after,
                    epoch=epoch,
                ),
                self.on_alarm,
            )
        return moved

    def check(self) -> float | None:
        """Re-route the probes if ``routing_epoch`` advanced since the last
        look; returns the moved fraction (None = no epoch advance).  Called
        on every drain — event-cadence work, never per batch."""
        epoch = self.router.routing_epoch
        alive = self.router.alive
        if self._epoch is None:
            if alive == 0:
                return None  # nothing routable yet; baseline on next check
            self._epoch, self._alive = epoch, alive
            self._routes = self._route_probes()
            return None
        if epoch == self._epoch or alive == 0:
            return None
        now = self._route_probes()
        moved = self.observe(
            self._routes,
            now,
            epoch - self._epoch,
            self._alive,
            alive,
            epoch=epoch,
        )
        self._epoch, self._alive, self._routes = epoch, alive, now
        return moved


class LoadMonitor:
    """Per-shard load telemetry over a ``BatchRouter``'s routed batches.

    Attaching flips the router's fused dispatch to the instrumented pass
    (``ops.route_load_bulk``): every batch advances a device-resident
    accumulator in the same dispatch that routes it — exactly for batches
    up to ``config.exact_cutoff`` keys, by deterministic stride sample
    (weight ``2**config.sample_shift``, same key units) above it, so
    ``totals`` reads as per-shard key counts either way (exact counts
    when every batch fit under the cutoff, unbiased estimates otherwise).
    ``drain()`` runs on the configured batch cadence (or on demand): one
    host transfer, registry updates, balance-envelope evaluation and a
    disruption-bound check — see the module docstring for the full
    protocol.
    """

    def __init__(
        self,
        router,
        metrics: MetricsRegistry | None = None,
        config: LoadConfig | None = None,
        on_alarm=None,
    ):
        self.router = router
        self.config = config or LoadConfig()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.on_alarm = on_alarm
        self.tracker = DisruptionTracker(
            router, self.config, self.metrics, on_alarm=on_alarm
        )
        #: pinned once; drains re-point the accumulator here (zero uploads)
        self._zeros_dev = jax.device_put(
            np.zeros((router.capacity,), np.uint32)
        )
        self._counts_dev = self._zeros_dev
        self._window_batches = 0
        self._window_keys = 0
        #: host-side cumulative per-shard totals across drains
        self.totals = np.zeros((router.capacity,), np.uint64)
        self.total_keys = 0
        self.drains = 0
        router.attach_load_monitor(self)

    # -- router-facing surface ----------------------------------------------
    @property
    def counts_dev(self) -> jax.Array:
        """The live device accumulator (the instrumented dispatch operand)."""
        return self._counts_dev

    def effective_shift(self, n_keys: int) -> int:
        """Count-sampling shift for a batch of ``n_keys``: 0 (exact) at or
        below ``config.exact_cutoff``, ``config.sample_shift`` above it."""
        return 0 if n_keys <= self.config.exact_cutoff else \
            self.config.sample_shift

    def note_dispatch(self, new_counts: jax.Array, n_keys: int) -> None:
        """Called by the router after each instrumented dispatch with the
        advanced accumulator; drains when the window cadence is reached."""
        self._counts_dev = new_counts
        self._window_batches += 1
        self._window_keys += int(n_keys)
        if self._window_batches >= self.config.drain_every:
            self.drain()

    def detach(self) -> None:
        self.router.detach_load_monitor()

    # -- drain protocol ------------------------------------------------------
    def _alive_slots(self) -> list[int]:
        removed = self.router.domain.removed
        return [
            s for s in range(self.router.domain.total_count)
            if s not in removed
        ]

    def drain(self) -> np.ndarray:
        """Pull the window's per-shard counts to host; evaluate envelopes.

        Returns the window counts (capacity-length).  The device
        accumulator is reset by re-pointing at the pinned zeros array —
        no upload.
        """
        window = np.asarray(self._counts_dev)
        self._counts_dev = self._zeros_dev
        n_batches, self._window_batches = self._window_batches, 0
        self._window_keys = 0
        self.totals += window.astype(np.uint64)
        self.total_keys = int(self.totals.sum())
        self.drains += 1

        m = self.metrics
        m.counter("load_drains_total").inc()
        m.counter("load_keys_total").inc(int(window.sum()))
        alive = self._alive_slots()
        for s in alive:
            m.gauge("load_shard_keys", shard=str(s)).set(int(self.totals[s]))
        ratio = self.peak_over_mean(alive)
        if ratio is not None:
            m.gauge("load_peak_over_mean").set(ratio)
            self._check_balance(ratio, alive)
        self.tracker.check()
        return window

    def peak_over_mean(self, alive: list[int] | None = None) -> float | None:
        """Peak/mean cumulative load over the currently-alive shards
        (None when nothing routed yet or fewer than two shards live)."""
        if alive is None:
            alive = self._alive_slots()
        if len(alive) < 2:
            return None
        loads = self.totals[alive].astype(np.float64)
        total = loads.sum()
        if total == 0:
            return None
        return float(loads.max() / (total / len(alive)))

    def _check_balance(self, ratio: float, alive: list[int]) -> None:
        alive_keys = int(self.totals[alive].sum())
        if alive_keys < self.config.min_alarm_keys:
            return
        expected = expected_peak_over_mean(alive_keys, len(alive))
        threshold = self.config.balance_mult * expected
        if ratio > threshold:
            self.metrics.counter("balance_alarms_total").inc()
            deliver(
                BalanceDriftAlarm(
                    ratio,
                    expected,
                    threshold,
                    n_alive=len(alive),
                    total_keys=alive_keys,
                    epoch=self.router.routing_epoch,
                ),
                self.on_alarm,
            )

    def reset(self) -> None:
        """Zero every accumulator (device window + host totals)."""
        self._counts_dev = self._zeros_dev
        self._window_batches = self._window_keys = 0
        self.totals = np.zeros_like(self.totals)
        self.total_keys = 0
