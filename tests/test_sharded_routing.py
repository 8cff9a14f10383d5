"""Mesh-sharded routing datapath (DESIGN.md §8): a subprocess with 8 fake
host devices checks that the shard_map'd ``BatchRouter`` is bit-exact with
the single-device path and the scalar oracle across fleet events, never
retraces, pads non-divisible batches correctly, and honours key-buffer
donation semantics."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import json
import jax
import numpy as np

from repro.serving.batch_router import BatchRouter
from repro.serving.router import SessionRouter

assert len(jax.devices()) == 8
mesh = jax.make_mesh((8,), ("data",))

rng = np.random.default_rng(9)
keys = rng.integers(0, 2**64, size=(1 << 16,), dtype=np.uint64)

sharded = BatchRouter(16, mesh=mesh)
single = BatchRouter(16)
oracle = SessionRouter(16, engine="binomial32", chain_bits=32, resolve="table")

results = {"parity": True, "sharding_ok": True}

# compile once, then count retraces across the event stream
out0 = sharded.route_keys(keys)
shard_sizes = {s.data.shape for s in out0.addressable_shards}
results["n_output_shards"] = len(out0.addressable_shards)
results["shard_sizes"] = sorted(str(s) for s in shard_sizes)
route_fn = sharded._sharded_route
assert len(route_fn) == 1
jitted = next(iter(route_fn.values()))
traces_before = jitted._cache_size()

EVENTS = [("fail", 3), ("scale_up", None), ("fail", 7), ("scale_down", None),
          ("recover", 3), ("scale_up", None), ("fail", 0), ("recover", 7)]
sample = rng.choice(len(keys), size=256, replace=False)
for ev, arg in EVENTS:
    for r in (sharded, single, oracle):
        getattr(r, ev)(*(() if arg is None else (arg,)))
    a = np.asarray(sharded.route_keys(keys))
    b = single.route_keys_np(keys)
    if not np.array_equal(a, b):
        results["parity"] = False
    expect = [oracle.domain.locate(int(keys[j])) for j in sample]
    if not np.array_equal(a[sample], expect):
        results["parity"] = False
results["retraces"] = jitted._cache_size() - traces_before

# non-divisible batch: 10_001 keys over 8 shards takes the padding path
odd = keys[:10_001]
results["pad_parity"] = bool(
    np.array_equal(np.asarray(sharded.route_keys(odd)), single.route_keys_np(odd))
)

# donation: numpy input buffers are uploaded (and owned) by the router, so
# donation must not break reuse of the caller's numpy array; jax.Array
# inputs are defensively copied before donation.
donating = BatchRouter(16, mesh=mesh, donate_keys=True)
first = np.asarray(donating.route_keys(keys))
second = np.asarray(donating.route_keys(keys))  # same numpy buffer again
results["donate_np_reuse"] = bool(np.array_equal(first, second))
kdev = jax.device_put(keys.astype(np.uint32))
third = np.asarray(donating.route_keys(kdev))
fourth = np.asarray(donating.route_keys(kdev))  # caller buffer must survive
results["donate_jax_reuse"] = bool(np.array_equal(third, fourth))
fresh = BatchRouter(16)  # healthy-fleet reference (no events applied)
results["donate_parity"] = bool(np.array_equal(first, fresh.route_keys_np(keys)))

print("RESULTS " + json.dumps(results))
"""


@pytest.mark.slow
def test_sharded_routing_matches_single_device_and_oracle():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True, text=True,
        timeout=900,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULTS ")]
    assert line, out.stdout
    results = json.loads(line[0][len("RESULTS "):])
    assert results["parity"], results
    assert results["retraces"] == 0, results  # fleet events never retrace
    assert results["n_output_shards"] == 8, results  # keys really split 8 ways
    assert results["pad_parity"], results
    assert results["donate_np_reuse"], results
    assert results["donate_jax_reuse"], results
    assert results["donate_parity"], results
