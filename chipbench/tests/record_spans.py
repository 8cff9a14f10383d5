#!/usr/bin/env python3
"""Record the small chip trace that ``test_program_spans.py`` checks the
program-span reader against: a 1,000-node fleet after a 60-node storm routes
twelve batches of 2^20 keys through the fused kernel, four in flight as in
``fleet1k.storm``, inside the benchmark's window, dispatch and wait spans.
The program's own ``repro.*`` spans land in the same trace.  Writes the
``.xplane.pb`` and the same trace as Perfetto JSON (the test's second
witness) to the directory given.

    python3 chipbench/tests/record_spans.py <out dir>
"""
from __future__ import annotations

import collections
import glob
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CALLS = 12
DEPTH = 4


def main(out: str) -> int:
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.dirname(HERE)]
    import harness

    harness.prepare_environment(rehearse=False, workload="fleet1k.storm")
    import jax
    import numpy as np

    from repro.serving.batch_router import BatchRouter

    harness.enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("no TPU: the recorded trace must come from the chip", file=sys.stderr)
        return 2
    router = BatchRouter(1000, capacity=1024, block_rows=128)
    rng = np.random.default_rng(7)
    for node in rng.choice(999, 60, replace=False):
        router.fail(int(node))
    ring = [jax.device_put(rng.integers(0, 1 << 32, 1 << 20, dtype=np.uint32))
            for _ in range(DEPTH)]
    jax.block_until_ready([router.route_keys(k) for k in ring])
    tmp = os.path.join(harness.OUT_DIR, "record_spans")
    shutil.rmtree(tmp, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, create_perfetto_trace=True,
                             profiler_options=options)
    pending: collections.deque = collections.deque()
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for i in range(CALLS):
            with jax.profiler.TraceAnnotation("chipbench.dispatch"):
                pending.append(router.route_keys(ring[i % DEPTH]))
            if len(pending) > DEPTH:
                with jax.profiler.TraceAnnotation("chipbench.wait"):
                    jax.block_until_ready(pending.popleft())
        with jax.profiler.TraceAnnotation("chipbench.wait"):
            jax.block_until_ready(list(pending))
    jax.profiler.stop_trace()
    os.makedirs(out, exist_ok=True)
    for pattern, name in (("*.xplane.pb", "spans12.xplane.pb"),
                          ("perfetto_trace.json.gz", "spans12.perfetto.json.gz")):
        found = glob.glob(os.path.join(tmp, "**", pattern), recursive=True)
        shutil.copy(found[0], os.path.join(out, name))
        print(name, os.path.getsize(os.path.join(out, name)), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
