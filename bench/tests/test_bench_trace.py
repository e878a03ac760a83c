"""The trace reduction on a small trace recorded on a TPU v5e: a jitted
matmul and a scan under ``bench/run_block``, a host transfer and a sleep
under ``bench/host``, five times."""
import os

import pytest

import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_busy_and_window(reduced):
    assert reduced["n_devices"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # the five matmul+scan pairs took ~20 us each on the device; the
    # window spans the first run_block to the last, sleeps included
    assert 5e-5 < reduced["busy_s"] < 5e-4
    assert 0.02 < reduced["window_s"] < 0.05
    assert reduced["idle_share"] == pytest.approx(
        100 * (1 - reduced["busy_s"] / reduced["window_s"]))


def test_breakdown(reduced):
    ops = reduced["breakdown"]["device_ops"]
    gaps = reduced["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert all(name.startswith("jit__lambda/%") for name, _ in ops)
    assert sum(s for _, s in ops) >= reduced["busy_s"] * 0.99
    labels = {name.split(" > ")[0] for name, _ in gaps}
    assert labels <= {"bench/run_block", "bench/host"}
    assert "bench/host" in labels
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(s for _, s in gaps) <= idle * (1 + 1e-9)


def test_missing_trace(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_reduce.find_xplane(str(tmp_path))
