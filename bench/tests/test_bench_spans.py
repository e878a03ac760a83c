"""The idle split by program span (``bench/trace_reduce.py``) and the
readers of the program's spans and counters in the traced window."""
import os

import numpy as np
import pytest

import harness
import trace_reduce
from trace_reduce import OUTSIDE, split_idle

TRACE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
#: the readers of the window's spans and counters, and their cell
READERS = ("prepare_idle_ms", "fetch_idle_ms", "rows_per_needed",
           "rows_per_needed.hier", "h2d_gb_per_round.hier")


def _sums_to_gaps(split, gaps):
    total = float(np.sum(np.diff(np.asarray(gaps, float), axis=1))) * 1e-9
    assert sum(split.values()) == pytest.approx(total, rel=1e-12)


@pytest.mark.parametrize("case", ["three_spans", "nested", "outside"])
def test_split_by_overlap(case):
    if case == "three_spans":
        # one gap across the tail of a, all of b, the head of c, and the
        # host time between b and c under no span
        spans = [(0, 100, "a"), (100, 200, "b"), (250, 400, "c")]
        gaps = [(50, 300)]
        want = {"a": 50, "b": 100, "c": 50, OUTSIDE: 50}
    elif case == "nested":
        # the parent takes only what no child holds
        spans = [(0, 1000, "run"), (100, 300, "prepare"),
                 (300, 320, "execute"), (600, 900, "fetch")]
        gaps = [(50, 310), (700, 950)]
        want = {"run": 50 + 50, "prepare": 200, "execute": 10,
                "fetch": 200, OUTSIDE: 0}
    else:
        spans = [(100, 200, "a")]
        gaps = [(0, 50), (300, 400)]
        want = {OUTSIDE: 150}
    split = split_idle(np.asarray(gaps), spans)
    assert split == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    _sums_to_gaps(split, gaps)


def test_innermost_pieces_are_disjoint():
    spans = [(0, 10, "p"), (2, 4, "c1"), (4, 8, "c2"), (5, 6, "g")]
    assert trace_reduce.innermost(spans) == [
        (0, 2, "p"), (2, 4, "c1"), (4, 5, "c2"), (5, 6, "g"), (6, 8, "c2"),
        (8, 10, "p")]


@pytest.mark.parametrize("names", [[], ["bench/host"],
                                   ["bench/host", "bench/run_block"]])
def test_split_of_recorded_trace_sums_to_idle(names):
    red = trace_reduce.reduce(TRACE, names)
    split = red["idle_by_span"]
    assert set(split) == set(names) | {OUTSIDE}
    idle = red["window_s"] - red["busy_s"]
    assert sum(split.values()) == pytest.approx(idle, rel=1e-6)
    if names:
        # the window is the run_block calls' extent, so the host's sleeps
        # between them fall under bench/host or under no span at all
        assert split["bench/host"] > 0


def _ctx(**over):
    ctx = {"rounds": 20, "rows": 1000, "blocks_s": [0.01, 0.01],
           "spans": {}, "trace": None, "window_spans": {},
           "window_counters": {}}
    ctx.update(over)
    return ctx


def test_readers_give_none_without_their_input():
    for name in READERS + ("encode_s",):
        assert harness.metric_reader(name)(_ctx()) is None, name


def test_readers_on_synthetic_ctx():
    ctx = _ctx(trace={"idle_by_span": {"block/prepare": 0.004,
                                       "block/fetch": 0.006, OUTSIDE: 0.1}},
               window_counters={"round/rows": {"events": 2, "total": 3100},
                                "hier/h2d_bytes": {"events": 8,
                                                   "total": 6.62e10}},
               spans={"encode/parity": {"count": 1, "total_s": 0.25,
                                        "min_s": 0.25, "max_s": 0.25}})
    got = {k: harness.metric_reader(k)(ctx) for k in READERS}
    assert got == pytest.approx({"prepare_idle_ms": 2.0, "fetch_idle_ms": 3.0,
                                 "rows_per_needed": 3.1,
                                 "rows_per_needed.hier": 3.1,
                                 "h2d_gb_per_round.hier": 3.31})
    assert harness.metric_reader("encode_s")(ctx) == 0.25
