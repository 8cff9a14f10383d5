"""``dispatch_us.bulk`` in the mesh cells: host time of one sharded call."""
import harness

read = harness.load_reader("dispatch_us.bulk")
