"""``launch_us.bulk`` in the mesh cells: the shard_map executable's enqueue."""
import harness

read = harness.load_reader("launch_us.bulk")
