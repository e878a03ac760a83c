"""`round_device_ms` of the hierarchical cells, which report `rounds_per_s.hier`:
device busy time per round in the traced window."""
import harness

read = harness.metric_reader("round_device_ms")
