#!/usr/bin/env python3
"""Record the small chip trace that ``test_reduction.py`` checks the
reduction against: a 1,000-node fleet after a 60-node storm routes twelve
batches of 2^16 keys through the fused kernel, inside the benchmark's own
window and dispatch spans.  Writes the ``.xplane.pb`` and the same trace as
Perfetto JSON (the test's second witness) to the directory given.

    python3 chipbench/tests/record_trace.py <out dir>
"""
from __future__ import annotations

import glob
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


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
    router = BatchRouter(1000, capacity=1024)
    rng = np.random.default_rng(7)
    for node in rng.choice(999, 60, replace=False):
        router.fail(int(node))
    keys = jax.device_put(rng.integers(0, 1 << 32, 1 << 16, dtype=np.uint32))
    jax.block_until_ready(router.route_keys(keys))
    tmp = os.path.join(harness.OUT_DIR, "record")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp, create_perfetto_trace=True)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(12):
            with jax.profiler.TraceAnnotation("chipbench.dispatch"):
                routed = router.route_keys(keys)
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                jax.block_until_ready(routed)
    jax.profiler.stop_trace()
    os.makedirs(out, exist_ok=True)
    for pattern, name in (("*.xplane.pb", "route12.xplane.pb"),
                          ("perfetto_trace.json.gz", "route12.perfetto.json.gz")):
        found = glob.glob(os.path.join(tmp, "**", pattern), recursive=True)
        shutil.copy(found[0], os.path.join(out, name))
        print(name, os.path.getsize(os.path.join(out, name)), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
