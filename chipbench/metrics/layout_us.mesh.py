"""``layout_us.bulk`` in the mesh cells: 0 where the sharded route runs no
layout executable (keys already on the mesh, no pad, no donation)."""
import harness

read = harness.load_reader("layout_us.bulk")
