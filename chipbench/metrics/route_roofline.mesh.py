"""``route_roofline`` in the mesh cells: one chip's share of the keys over
the slowest chip's kernel time."""
import harness

read = harness.load_reader("route_roofline")
