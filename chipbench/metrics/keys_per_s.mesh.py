"""``keys_per_s`` in the cells whose keys are split over a mesh of chips:
the same count, under a bound of its own (a four-chip host does not freeze
as the one-chip host does)."""
import harness

read = harness.load_reader("keys_per_s")
