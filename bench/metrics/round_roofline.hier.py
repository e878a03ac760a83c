"""`round_roofline` of the hierarchical cells, which report `rounds_per_s.hier`:
share of the rounds' device time the chip would need at its peak."""
import harness

read = harness.metric_reader("round_roofline")
