"""``idle_pct.bulk`` in the served cells, which report ``goodput_rps``."""
import harness

read = harness.load_reader("idle_pct.bulk")
