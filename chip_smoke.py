#!/usr/bin/env python3
"""Smoke run of the fused routing path on a TPU, checked against references.

Drives the deployment at the paper's benchmark point — 1,000 nodes x 1,000
keys per node (``PAPER_BENCH``): ``BatchRouter(1000, capacity=1024)`` at the
default omega, 2^20 device-resident u32 keys per batch made from ``--seed``,
automatic kernel selection and the autotuner's tiling.  Phases, for both
bulk engines unless noted:

  a  healthy fleet: ``route_keys`` == jnp mirror on every key, == the scalar
     oracle on sampled keys
  b  failure storm (60 nodes, 6%), then one ``scale_up`` and one
     ``scale_down``: the same comparisons after each, and only keys of the
     changed nodes move (onto the new node, after the scale-up)
  c  u64 ingest: ``route_ids`` == ``route_keys(hash_session_ids(ids))``
  d  placement (binomial): ``StorePlacement(r=3).place`` gives 3 distinct
     alive shards, == a plain reference on sampled keys; the migration diff
     after one failure stays within the delta/n bound
  e  instrumented route: a ``LoadMonitor``'s drained counts == a host
     bincount of the routed replicas
  f  streaming: a ``StreamingFrontEnd`` over a ``LifecycleManager`` on the
     wall clock answers 512 requests, each == the oracle's replica

``--chips 4`` runs only the mesh-sharded route: ``BatchRouter(1000,
mesh=<4 devices>)`` on 4 x 2^20 keys, healthy and after the storm, == a
single-device route key for key, with the output spread over all 4 devices.

Phase lines report compile and steady wall seconds: smoke timings, not
metrics.  The last line of a passing run is one JSON object naming the
device; any mismatch or error exits non-zero without it, and so does a run
that finds no TPU.  ``--rehearse`` runs the same phases on the CPU at a
tiny size with the kernels in interpret mode, and prints no ``ok`` line.

    python chip_smoke.py [--seed N] [--chips 1|4] [--rehearse]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import enable_compile_cache  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Size:
    nodes: int
    capacity: int
    keys: int
    storm: int
    sample: int
    requests: int


FULL = Size(nodes=1000, capacity=1024, keys=1 << 20, storm=60, sample=4096,
            requests=512)
TINY = Size(nodes=100, capacity=128, keys=1 << 12, storm=6, sample=256,
            requests=128)
ENGINES = ("binomial", "jump")


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def same(a, b, what: str) -> None:
    a, b = np.asarray(a), np.asarray(b)
    check(a.shape == b.shape, f"{what}: shape {a.shape} != {b.shape}")
    bad = int(np.count_nonzero(a != b))
    check(bad == 0, f"{what}: {bad} of {a.size} differ")


def report(phase: str, engine: str, keys: int, compile_s, steady_s,
           block_rows) -> None:
    print("smoke-timing " + json.dumps({
        "phase": phase, "engine": engine, "keys": keys,
        "compile_s": compile_s, "steady_s": steady_s,
        "block_rows": block_rows,
    }), flush=True)


def report_moves(phase: str, engine: str, moved, to_node) -> None:
    """How many keys a resize moved, and how many of them are keys of the
    added or removed node (the rest are re-resolved diverted keys)."""
    print("smoke-moves " + json.dumps({
        "phase": phase, "engine": engine, "moved": int(moved.sum()),
        "of_changed_node": int((moved & to_node).sum()),
    }), flush=True)


def timed(fn, repeats: int = 3):
    """(result, first-call seconds, best steady seconds), each call blocked."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return out, first, best


class Smoke:
    def __init__(self, size: Size, seed: int, rehearse: bool):
        self.size = size
        self.rng = np.random.default_rng(seed)
        self.rehearse = rehearse
        # on the chip: automatic kernel selection and the autotuner's tiling;
        # the CPU rehearsal forces the interpret-mode kernel at a small tile
        self.extra = dict(interpret=True, block_rows=8) if rehearse else {}
        self.keys_np = self.rng.integers(0, 1 << 32, size=size.keys,
                                         dtype=np.uint64).astype(np.uint32)
        self.keys = jax.device_put(self.keys_np)
        self.idx = np.sort(self.rng.choice(size.keys, size.sample, replace=False))
        self.storm_nodes = [int(x) for x in
                            self.rng.choice(size.nodes - 1, size.storm,
                                            replace=False)]

    def router(self, engine: str, **kw):
        from repro.serving.batch_router import BatchRouter

        return BatchRouter(self.size.nodes, engine=engine,
                           capacity=self.size.capacity, **self.extra, **kw)

    def block_rows(self, router, n_keys: int):
        from repro.kernels.fused import LANES

        rows = -(-n_keys // LANES)
        return router._resolve_block_rows(-(-rows // router._n_shards))

    # -- references -----------------------------------------------------------
    def mirror(self, router, keys):
        """The pure-jnp fused route on the same device and fleet state."""
        from repro.kernels import ops

        spec = dataclasses.replace(router.spec, use_pallas=False,
                                   interpret=False)
        return ops.route_bulk(keys, router._fleet_dev, spec)

    def oracle_sample(self, router, out, what: str) -> None:
        locate = router.scalar.domain.locate
        expect = [locate(int(self.keys_np[i])) for i in self.idx]
        same(np.asarray(out)[self.idx], expect, f"{what}: scalar oracle")

    def routed(self, router, phase: str, what: str):
        out, first, best = timed(lambda: router.route_keys(self.keys))
        out = np.asarray(out)
        same(out, self.mirror(router, self.keys), f"{what}: jnp mirror")
        self.oracle_sample(router, out, what)
        report(phase, router.engine, self.size.keys, first, best,
               self.block_rows(router, self.size.keys))
        return out

    def base(self, router):
        """Base-engine buckets without the divert (the jnp ``lookup_dyn``)."""
        from repro.core.registry import BULK_ENGINES

        n = np.uint32(router.domain.total_count)
        return np.asarray(BULK_ENGINES[router.engine].lookup_dyn(
            self.keys, n, omega=router.omega))

    # -- phases ---------------------------------------------------------------
    def check_kernels(self, engine: str, router) -> None:
        from repro.core.registry import BULK_ENGINES

        eng = BULK_ENGINES[engine]
        for name in ("route_pallas", "ingest_pallas"):
            check(getattr(eng, name) is not None, f"{engine}: no {name}")
        check(router.spec.pallas_selected() or self.rehearse,
              f"{engine}: the Pallas kernels are not selected on this device")

    def bulk(self, engine: str) -> None:
        from repro.serving.router import SessionRouter, hash_session_ids

        router = self.router(engine)
        self.check_kernels(engine, router)
        healthy = self.routed(router, "a_healthy", f"{engine} healthy")

        for node in self.storm_nodes:
            router.fail(node)
        storm = self.routed(router, "b_storm", f"{engine} storm")
        failed = np.asarray(self.storm_nodes)
        moved = storm != healthy
        check(np.isin(healthy[moved], failed).all(),
              f"{engine} storm: keys of surviving nodes moved")
        check(not np.isin(storm, failed).any(),
              f"{engine} storm: keys still routed to failed nodes")

        # a diverted key (its base bucket failed) re-resolves through the
        # replacement table, which reduces by n_total: a resize may move it
        # to any alive node.  Every other key moves only to a new node or
        # off a removed one.
        diverted = np.isin(self.base(router), failed)
        new = router.scale_up()
        up = self.routed(router, "b_scale_up", f"{engine} scale_up")
        moved = up != storm
        check((diverted[moved] | (up[moved] == new)).all(),
              f"{engine} scale_up: an undiverted key missed the new node {new}")
        report_moves("b_scale_up", engine, moved, up == new)
        diverted = np.isin(self.base(router), failed)
        gone = router.scale_down()
        down = self.routed(router, "b_scale_down", f"{engine} scale_down")
        moved = down != up
        check((diverted[moved] | (up[moved] == gone)).all(),
              f"{engine} scale_down: keys of surviving nodes moved")
        report_moves("b_scale_down", engine, moved, up == gone)

        ids = self.rng.integers(0, 1 << 64, size=self.size.keys,
                                dtype=np.uint64)
        out, first, best = timed(lambda: router.route_ids(ids))
        out = np.asarray(out)
        same(out, router.route_keys_np(hash_session_ids(ids)),
             f"{engine} route_ids vs hash_session_ids + route_keys")
        locate = router.scalar.domain.locate
        expect = [locate(SessionRouter.session_key(int(ids[i])))
                  for i in self.idx]
        same(out[self.idx], expect, f"{engine} route_ids: scalar oracle")
        report("c_ingest_u64", engine, self.size.keys, first, best,
               self.block_rows(router, self.size.keys))

    def placement(self) -> None:
        from benchmarks.bench_placement import movement_bound
        from repro.core.bits import np_mix32
        from repro.placement.store import (
            RESALT_SALT, StorePlacement, family_salts,
        )

        router = self.router("binomial")
        store = StorePlacement(router, r=3)
        placed, first, best = timed(lambda: store.place_keys(self.keys)[0])
        placed = np.asarray(placed)
        check(placed.shape == (self.size.keys, 3), "placement: shape")
        check((placed[:, 0] != placed[:, 1]).all()
              and (placed[:, 0] != placed[:, 2]).all()
              and (placed[:, 1] != placed[:, 2]).all(),
              "placement: replicas not distinct")
        check(((placed >= 0) & (placed < self.size.nodes)).all(),
              "placement: a replica is not an alive shard")
        # plain reference: the r salted families through the scalar oracle,
        # collisions re-salted into the alive-prefix positions of the table
        dom = router.domain
        slots = np.asarray(router._fleet_host.table[0], np.int64)
        salts = family_salts(3)
        keys = self.keys_np[self.idx]
        fam = [np_mix32(keys ^ s) for s in salts]
        resalt = [np_mix32(f ^ RESALT_SALT) for f in fam]
        n_alive = dom.alive_count
        for row, i in enumerate(self.idx):
            used: list[int] = []
            for j in range(3):
                b = dom.locate(int(fam[j][row]))
                q = (int(resalt[j][row]) * n_alive) >> 32
                while b in used:
                    b = int(slots[q])
                    q = (q + 1) % n_alive
                used.append(b)
            same(placed[i], used, f"placement key {i}: plain reference")
        report("d_placement", "binomial", self.size.keys, first, best, None)

        batch = store.register(self.keys_np)
        same(batch.replicas, placed, "placement: register vs place")
        router.fail(self.storm_nodes[0])
        plan, first, best = timed(store.plan_migration)
        same(plan.old, placed, "migration diff: old placement")
        same(plan.new, store.place_keys(self.keys)[0],
             "migration diff: new placement")
        n = self.size.nodes
        bound = movement_bound(n, n - 1, 3)
        check(plan.moved_fraction <= bound,
              f"migration moved {plan.moved_fraction} > bound {bound}")
        report("d_migration_diff", "binomial", self.size.keys, first, best,
               None)

    def instrumented(self, engine: str) -> None:
        from repro.observability.load import LoadConfig, LoadMonitor

        router = self.router(engine)
        bare = np.asarray(router.route_keys(self.keys))
        # count every key exactly: the comparison is with an exact bincount
        monitor = LoadMonitor(router, config=LoadConfig(
            exact_cutoff=self.size.keys, drain_every=1 << 30))
        out, first, best = timed(lambda: router.route_keys(self.keys))
        monitor.reset()
        out = np.asarray(router.route_keys(self.keys))
        counts = monitor.drain()
        monitor.detach()
        same(out, bare, f"{engine} instrumented route vs plain route")
        same(counts, np.bincount(out, minlength=router.capacity),
             f"{engine} load counts vs host bincount")
        report("e_instrumented", engine, self.size.keys, first, best, None)

    def streaming(self, engine: str) -> None:
        from repro.serving.lifecycle import LifecycleManager
        from repro.serving.streaming import (
            StreamConfig, StreamingFrontEnd, StreamRequest,
        )

        router = self.router(engine)
        mgr = LifecycleManager(router)
        fe = StreamingFrontEnd(mgr, config=StreamConfig(max_batch=64))
        keys = self.rng.integers(0, 1 << 32, size=self.size.requests,
                                 dtype=np.uint64).astype(np.uint32)
        t0 = time.perf_counter()
        jax.block_until_ready(router.route_keys(jax.device_put(keys[:64])))
        first = time.perf_counter() - t0
        for s in mgr.detector.slots:  # compile time is not silence
            mgr.detector.heartbeat(s)
        epoch = mgr.epoch
        served = []
        t0 = time.perf_counter()
        for k in keys:
            deadline = fe.clock.now_us() + 10_000_000
            fe.submit(StreamRequest(key=int(k), deadline_us=deadline))
            served.extend(fe.pump())
        served.extend(fe.drain())
        wall = time.perf_counter() - t0
        check(len(served) == len(keys),
              f"{engine} streaming: served {len(served)} of {len(keys)}")
        locate = router.scalar.domain.locate
        for res in served:
            check(res.epoch == epoch, f"{engine} streaming: fleet changed")
            check(res.replica == locate(res.request.key),
                  f"{engine} streaming: key {res.request.key} -> "
                  f"{res.replica}, oracle {locate(res.request.key)}")
        report("f_streaming", engine, len(keys), first, wall,
               self.block_rows(router, 64))

    def sharded(self, n_dev: int) -> None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = jax.make_mesh((n_dev,), ("data",))
        keys_np = self.rng.integers(0, 1 << 32, size=n_dev * self.size.keys,
                                    dtype=np.uint64).astype(np.uint32)
        keys = jax.device_put(keys_np, NamedSharding(mesh, P("data")))
        sharded = self.router("binomial", mesh=mesh)
        single = self.router("binomial")
        single_keys = jax.device_put(keys_np, jax.devices()[0])
        idx = self.rng.choice(keys_np.size, self.size.sample, replace=False)
        for phase in ("mesh_healthy", "mesh_storm"):
            if phase == "mesh_storm":
                for node in self.storm_nodes:
                    sharded.fail(node)
                    single.fail(node)
            out, first, best = timed(lambda: sharded.route_keys(keys))
            devices = {s.device for s in out.addressable_shards}
            check(len(out.sharding.device_set) == n_dev
                  and len(devices) == n_dev,
                  f"{phase}: output spans {len(devices)} devices")
            check(all(s.data.shape == (self.size.keys,)
                      for s in out.addressable_shards),
                  f"{phase}: uneven output shards")
            ref = np.asarray(single.route_keys(single_keys))
            out = np.asarray(out)
            same(out, ref, f"{phase}: sharded vs single-device route")
            locate = single.scalar.domain.locate
            same(out[idx], [locate(int(keys_np[i])) for i in idx],
                 f"{phase}: scalar oracle")
            report(phase, "binomial", keys_np.size, first, best,
                   self.block_rows(sharded, keys_np.size))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny size, interpret-mode kernels; no ok line")
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    cache_events: dict[str, int] = {}
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_events.update(
            {event: cache_events.get(event, 0) + 1})
        if event.startswith("/jax/compilation_cache/") else None)
    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    print(f"device: {dev.platform} / {dev.device_kind} x {n_dev}", flush=True)
    if dev.platform != "tpu" and not args.rehearse:
        print("no TPU found: chip_smoke.py runs on a TPU (or pass --rehearse)",
              file=sys.stderr)
        return 2
    if n_dev < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found {n_dev}",
              file=sys.stderr)
        return 2
    smoke = Smoke(TINY if args.rehearse else FULL, args.seed, args.rehearse)
    print("smoke timings below are wall seconds of one run, not metrics",
          flush=True)
    if args.chips == 4:
        smoke.sharded(4)
    else:
        for engine in ENGINES:
            smoke.bulk(engine)
        smoke.placement()
        for engine in ENGINES:
            smoke.instrumented(engine)
            smoke.streaming(engine)
    print("compile-cache " + json.dumps({"dir": cache_dir, **{
        e.rsplit("/", 1)[1]: n for e, n in sorted(cache_events.items())}}),
        flush=True)
    if args.rehearse:
        print("rehearsal passed (CPU, interpret mode): no device result",
              flush=True)
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
