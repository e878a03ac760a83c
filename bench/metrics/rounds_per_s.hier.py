"""`rounds_per_s` of the hierarchical cells, which report `rounds_per_s.hier`:
rounds completed in the timed window over its whole length."""
import harness

read = harness.metric_reader("rounds_per_s")
