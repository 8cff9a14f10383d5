"""``kernel_ms.route`` in the mesh cells: the slowest chip's kernel time
per call."""
import harness

read = harness.load_reader("kernel_ms.route")
