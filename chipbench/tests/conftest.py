"""The benchmark's own tests run on the CPU, with four virtual devices for
the sharded cell, and import the benchmark's modules by name.

    PYTHONPATH=src python -m pytest chipbench/tests -q
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_FLAG = "--xla_force_host_platform_device_count=4"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH, HERE]
