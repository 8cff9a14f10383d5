"""Quickstart: the paper's algorithm in 30 seconds, host-side and in-graph.

    PYTHONPATH=src python examples/quickstart.py
"""
import collections

import jax.numpy as jnp
import numpy as np

from repro.core import BinomialHash, binomial_lookup_vec
from repro.serving.batch_router import BatchRouter

# -- host-side: route 100k data units onto 11 nodes ---------------------------
engine = BinomialHash(n=11)
keys = [hash(f"object-{i}") & (2**63 - 1) for i in range(100_000)]
table = {k: engine.get_bucket(k) for k in keys}
load = collections.Counter(table.values())
print("load per node   :", [load[i] for i in range(11)])

# -- scale up: node 11 joins; only ~1/12 of keys move, all onto node 11 -------
engine.add_bucket()
moves = {k: engine.get_bucket(k) for k in keys if engine.get_bucket(k) != table[k]}
print(f"scale 11->12    : moved {len(moves)/len(keys):.4f} (ideal {1/12:.4f}), "
      f"targets={set(moves.values())}")

# -- scale down: LIFO removal; only node 11's keys move -----------------------
engine.remove_bucket()
back = {k: engine.get_bucket(k) for k in keys}
print("scale back 12->11: restored exactly =", back == table)

# -- in-graph: the vectorised u32 device path (MoE router datapath) ----------
tokens = jnp.asarray(np.random.default_rng(0).integers(0, 2**31, 1 << 16), jnp.uint32)
experts = binomial_lookup_vec(tokens, 256, omega=16)
counts = np.bincount(np.asarray(experts), minlength=256)
print(f"in-graph routing: 64k tokens -> 256 experts, max/mean load "
      f"{counts.max()/counts.mean():.3f}")

# -- the fused Pallas TPU kernel (interpret mode on CPU) ----------------------
# a healthy fleet skips the failure divert, so the route is the plain lookup
router = BatchRouter(256, interpret=True, block_rows=8)
buckets = router.route_keys(tokens[:8192])
print("pallas kernel   : matches jnp path =",
      bool((np.asarray(buckets) == np.asarray(experts)[:8192]).all()))
