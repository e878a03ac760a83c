"""`rows_per_needed` of the hierarchical cells, which report `rounds_per_s.hier`:
rows the round program read over the rows the window's rounds needed."""
import harness

read = harness.metric_reader("rows_per_needed")
