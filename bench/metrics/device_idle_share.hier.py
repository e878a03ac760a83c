"""`device_idle_share` of the hierarchical cells, which report `rounds_per_s.hier`:
share of the traced window in which no op ran on the device."""
import harness

read = harness.metric_reader("device_idle_share")
