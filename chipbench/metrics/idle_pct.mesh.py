"""``idle_pct.bulk`` in the mesh cells: the idlest chip's idle share."""
import harness

read = harness.load_reader("idle_pct.bulk")
