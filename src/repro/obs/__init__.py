"""Run telemetry: span timers, per-round event journal, straggler attribution.

Everything here is observational — enabling telemetry never touches an RNG
stream or changes a trajectory (pinned by tests/test_obs.py).
"""
from repro.obs import spans
from repro.obs.attribution import (
    Attribution,
    attribution_from_blocks,
    compute_attribution,
)
from repro.obs.events import (
    EVENTS_NAME,
    RunJournal,
    histories_equal,
    history_from_journal,
    load_events,
)
from repro.obs.spans import SPANS_NAME, collecting, disable, enable, enabled, span, totals

__all__ = [
    "spans",
    "span",
    "enable",
    "disable",
    "enabled",
    "totals",
    "collecting",
    "SPANS_NAME",
    "RunJournal",
    "EVENTS_NAME",
    "load_events",
    "history_from_journal",
    "histories_equal",
    "Attribution",
    "attribution_from_blocks",
    "compute_attribution",
]
