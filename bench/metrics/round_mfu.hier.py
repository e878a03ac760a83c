"""`round_mfu` of the hierarchical cells, which report `rounds_per_s.hier`:
the whole window's share of the chip's bf16 peak."""
import harness

read = harness.metric_reader("round_mfu")
