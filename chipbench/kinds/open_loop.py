"""``open_loop``: requests of one key each through the streaming front end.

After ``failed_nodes`` failures drawn from the seed, requests fall due at
``rate_rps`` uniform arrivals over the window (a Poisson process
conditioned on its count), with keys drawn YCSB scrambled-zipfian
(``zipf_constant``) over ``items`` ids, through ``StreamingFrontEnd`` over
``LifecycleManager`` on the wall clock.  Each request carries the
client's timeout, ``client_timeout_ms`` past when it is due, as its
deadline; whether it was answered in time is judged apart, against the
configuration's ``latency_limit_us``.  So a request the front end could not
start in time is answered late, not shed.  Every alive node's heartbeat is
delivered once each ``heartbeat_interval_s``, the fleet spread evenly, so
the failure detector sees no silence.  Every served request is compared
with the plain reference.
"""
from __future__ import annotations

import time

import numpy as np

import common
import reference


def ycsb_keys(rng, n: int, items: int, constant: float) -> np.ndarray:
    """``n`` u32 request keys, YCSB scrambled-zipfian over ``items`` ids.

    Ranks are drawn by the exact inverse CDF of P(rank) ~ 1/(rank+1)^c and
    scrambled as YCSB does (FNV-1a 64 of the rank's eight bytes, mod the
    item count); the client then hashes the id with splitmix64 and keeps
    the low 32 bits as the routing key.
    """
    weights = np.arange(1, items + 1, dtype=np.float64) ** -constant
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    rank = np.minimum(np.searchsorted(cdf, rng.random(n)), items - 1)
    h = np.full(n, 0xCBF29CE484222325, np.uint64)
    v = rank.astype(np.uint64)
    for _ in range(8):
        h = (h ^ (v & np.uint64(0xFF))) * np.uint64(0x100000001B3)
        v >>= np.uint64(8)
    ids = (h.view(np.int64) & np.int64(0x7FFFFFFFFFFFFFFF)).astype(np.uint64)
    z = ids % np.uint64(items)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return (z ^ (z >> np.uint64(31))).astype(np.uint32)


class Driver:
    def __init__(self, config, mix, seed, devices):
        self.config, self.mix, self.devices = config, mix, devices
        self.rng = np.random.default_rng(seed)

    def setup(self, seconds: float) -> None:
        from repro.serving.lifecycle import LifecycleManager
        from repro.serving.streaming import StreamConfig, StreamingFrontEnd

        config, mix = self.config, self.mix
        self.failed = common.storm(self.rng, config, mix)
        self.schedule(seconds, mix["rate_rps"])
        self.router, _ = common.build_router(config, self.devices)
        self.mgr = LifecycleManager(self.router)
        self.fe = StreamingFrontEnd(self.mgr, config=StreamConfig(**config["stream"]))
        for node in self.failed:
            self.mgr.fail(node)
        gone = set(self.failed)
        self.alive = [s for s in range(config["nodes"]) if s not in gone]
        # each alive node beats once an interval, the fleet spread evenly
        interval = mix["heartbeat_interval_s"]
        rounds = int(np.ceil(seconds / interval)) + 1
        phase = np.arange(len(self.alive)) * (interval / len(self.alive))
        beats = (np.arange(rounds)[:, None] * interval + phase[None, :]).ravel()
        self.beat_ns = (beats * 1e9).astype(np.int64)
        self.beat_node = np.tile(np.asarray(self.alive), rounds)
        self.limit_us = config["latency_limit_us"]

    def schedule(self, seconds: float, rate_rps: float) -> None:
        """The window's requests: when each is due, and its key."""
        n = int(round(rate_rps * seconds))
        self.due_ns = np.sort((self.rng.random(n) * seconds * 1e9).astype(np.int64))
        self.keys = ycsb_keys(self.rng, n, self.mix["items"], self.mix["zipf_constant"])

    def beat_all(self) -> None:
        for node in self.alive:
            self.mgr.heartbeat(node)

    def warm(self) -> None:
        """Route every batch size the window can close, 1 to ``max_batch``,
        through the front end's own dispatch."""
        dispatch = self.fe.batcher.dispatch_fn
        for size in range(1, self.config["stream"]["max_batch"] + 1):
            self.beat_all()  # compile time is not silence
            dispatch(np.zeros(size, np.uint32)).result()

    def window(self, seconds: float, annotate: bool) -> dict:
        from repro.serving.lifecycle.errors import SHED_LATE, AdmissionRejectedError
        from repro.serving.streaming import StreamRequest

        span = common.annotation(annotate)
        fe, batcher = self.fe, self.fe.batcher
        n = self.due_ns.size
        back_ns = np.full(n, -1, np.int64)
        replica = np.full(n, -1, np.int64)
        epoch = np.full(n, -1, np.int64)
        late_ns = np.zeros(n, np.int64)
        shed = np.zeros(n, bool)
        index: dict[int, int] = {}
        submit_ns: list[int] = []
        pump_ns: list[int] = []
        served0, dispatched0 = batcher.served, batcher.dispatches
        late0 = fe.admission.shed_by_reason.get(SHED_LATE, 0)
        epoch0 = self.mgr.epoch
        limit_ns = self.limit_us * 1000
        timeout_ns = int(self.mix["client_timeout_ms"]) * 10**6

        def take(results, now):
            for res in results:
                i = index.pop(id(res.request))
                back_ns[i] = now
                replica[i] = res.replica
                epoch[i] = res.epoch

        with span("chipbench.window"):
            self.beat_all()
            t0 = time.monotonic_ns()
            due = self.due_ns + t0
            beat = self.beat_ns + t0
            t_stop = t0 + int(seconds * 1e9)
            i = b = 0
            stalls = 0
            last = t0
            while True:
                now = time.monotonic_ns()
                if now - last > 50_000_000:
                    stalls += 1  # the loop froze, wherever it was
                last = now
                if b < beat.size and beat[b] <= now:
                    with span("chipbench.heartbeat"):
                        while b < beat.size and beat[b] <= now:
                            self.mgr.heartbeat(int(self.beat_node[b]))
                            b += 1
                while i < n and due[i] <= now:
                    req = StreamRequest(key=int(self.keys[i]),
                                        deadline_us=int(due[i] + timeout_ns) // 1000)
                    index[id(req)] = i
                    late_ns[i] = now - due[i]
                    a = time.perf_counter_ns()
                    with span("chipbench.submit"):
                        try:
                            fe.submit(req)
                        except AdmissionRejectedError:
                            shed[i] = True
                            index.pop(id(req))
                    submit_ns.append(time.perf_counter_ns() - a)
                    i += 1
                a = time.perf_counter_ns()
                d = batcher.dispatches
                with span("chipbench.pump"):
                    results = fe.pump()
                took = time.perf_counter_ns() - a
                if results or batcher.dispatches != d:
                    pump_ns.append(took)
                if results:
                    take(results, time.monotonic_ns())
                if i == n and now >= t_stop:
                    break
            # the last requests are pumped out as the loop would have
            give_up = time.monotonic_ns() + 60 * 10**9
            while (batcher.open_depth or batcher.inflight_depth) and \
                    time.monotonic_ns() < give_up:
                with span("chipbench.pump"):
                    results = fe.pump()
                if results:
                    take(results, time.monotonic_ns())
            take(fe.drain(), time.monotonic_ns())
            t_end = time.monotonic_ns()

        answered = back_ns >= 0
        # a request never answered counts as answered at the end of the run
        latency_ns = np.where(answered, back_ns, t_end) - due
        self.served = answered
        self.replica, self.epoch, self.epoch0 = replica, epoch, epoch0
        self.late_shed = fe.admission.shed_by_reason.get(SHED_LATE, 0) - late0
        self.shed = shed
        on_time = answered & (back_ns <= due + limit_ns)
        return {
            "facts": {"seconds": seconds, "requests": n,
                      "answered": int(answered.sum()),
                      "on_time": int(on_time.sum()),
                      "shed": int(shed.sum()) + self.late_shed,
                      "generator_late_ms_p99": float(np.percentile(late_ns, 99) / 1e6),
                      "generator_late_ms_max": float(late_ns.max() / 1e6) if n else 0.0,
                      "loop_turns_over_50ms": stalls,
                      "p99_ms": float(np.percentile(latency_ns, 99) / 1e6)},
            "spans": {"submit": np.asarray(submit_ns) / 1e9,
                      "pump_working": np.asarray(pump_ns) / 1e9},
            "counters": {
                "stream_served_total": batcher.served - served0,
                "stream_dispatches_total": batcher.dispatches - dispatched0,
            },
            "latencies_ms": latency_ns / 1e6,
            "attempted": n,
            "failed": n - int(answered.sum()),
        }

    def check(self) -> dict:
        """Every served request's replica against the plain reference."""
        fleet = common.reference_fleet(self.config, self.failed)
        served = self.served
        want = reference.route(self.keys[served], fleet, self.config["omega"])
        got = self.replica[served]
        valid = (got >= 0) & (got < fleet.n_total)
        dead = ~valid | fleet.failed()[np.where(valid, got, 0)]
        accounted = int(served.sum()) + int(self.shed.sum()) + self.late_shed
        return {
            "wrong_replicas": (int((got != want).sum()), 0),
            "answers_on_failed_nodes": (int(dead.sum()), 0),
            "answers_from_another_epoch": (int((self.epoch[served] != self.epoch0).sum()), 0),
            "requests_unaccounted": (abs(self.due_ns.size - accounted), 0),
        }
