"""A kind of cell comes as new files only, and the kind the two cells
share reads as the harness read before kinds existed."""
import json
import os

import pytest

import harness
import trace_reduce
from tiny import TINY

TRACE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")

SEED = 2 ** 31 + 4242
#: rounds of the one window call in each tiny cell
ROUNDS = {"femnist_hier_coded": 1, "paper_coded_static": 10}

#: the tiny cells' checks at SEED with a zero-second window (one call),
#: from the harness of commit 89ab225 (before kinds), on the CPU
PARENT = {
    "femnist_hier_coded": {
        "t_star": 1.8211601263074334e-15, "loads_off": 0,
        "parity_x": 2.655428723198854e-07,
        "parity_y": 1.2947947834884412e-07,
        "loss_1": 3.044390115087543e-14, "loss_2": 8.655069780505582e-14,
        "loss_3": 1.1542876706087247e-13, "grad_1": 3.1829812450110424e-08,
        "change_3": 2.979564719983952e-08,
        "tail_change": 4.5913742901666427e-07, "returns_off": 0.0},
    "paper_coded_static": {
        "t_star": 1.509315142583686e-07, "loads_off": 0,
        "parity_x": 1.61462559154677e-05, "parity_y": 1.5991457939198287e-05,
        "loss_1": 1.3060671514359172e-07, "loss_2": 4.3483627133249386e-07,
        "loss_3": 7.141595185475855e-07, "grad_1": 8.129985661801368e-07,
        "change_3": 1.7858534066242433e-06, "returns_off": 0.0},
}


@pytest.mark.parametrize("cell", sorted(TINY))
def test_tiny_checks_match_parent(cell):
    r = harness.run(cell, SEED, 0, False, require_accelerator=False,
                    overrides=TINY[cell], log=lambda s: None)
    assert r["correct"] and r["attempted"] == ROUNDS[cell]
    assert {k: c["value"] for k, c in r["checks"].items()} == PARENT[cell]


TOY_KIND = '''
"""A running sum of a vector made from the seed, one add a call."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import spans

VARIANTS = {"reference": {}}


def make_inputs(cfg, traffic, seed):
    key = jax.random.PRNGKey(seed % 2 ** 32)
    return {"v": jax.random.normal(key, (cfg["n"],))}


@jax.jit
def _add(s, v):
    return s + v


class System:
    def __init__(self, cfg, traffic, inputs):
        self.v, self.fault = inputs["v"], cfg["fault"]
        self.s = jnp.zeros_like(self.v)
        self.calls = 0

    def step(self):
        with spans.span("toy/step"):
            if self.fault != "state_unchanged":
                self.s = _add(self.s, self.v)
            self.calls += 1
            spans.count("toy/items", int(self.v.shape[0]))
            jax.block_until_ready(self.s)
        return 1

    def skipped(self):
        return 0

    def snapshot(self):
        return np.asarray(self.s)

    def handoff(self):
        return {"s": np.asarray(self.s), "calls": self.calls}


def check(cfg, traffic, inputs, ran, limits, variants=()):
    v = np.asarray(inputs["v"], np.float64)
    gap = lambda s, k: float(np.max(np.abs(s - k * v)) / np.max(np.abs(k * v)))
    nums = {"warm_gap": max(gap(s, k + 1) for k, s in enumerate(ran["warm"])),
            "sum_gap": gap(ran["tail"]["s"], ran["tail"]["calls"])}
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks, {name: nums for name in variants}, {"items": v.size}
'''

READERS = {
    "toy_sums_per_s": "def read(ctx):\n"
                      "    return ctx['rounds'] / ctx['window_s']\n",
    "toy_items": "def read(ctx):\n"
                 "    rec = ctx['window_counters'].get('toy/items')\n"
                 "    return None if rec is None else rec['total']\n",
    "toy_op_s": "def read(ctx):\n"
                "    ops = (ctx['trace'] or {}).get('op_s')\n"
                "    return sum(ops.values()) if ops else None\n",
}


@pytest.fixture
def toy_root(tmp_path):
    """A checkout with one cell of kind ``toy`` and nothing else: its
    kind, configuration, traffic, limits and per-layer readers are new
    files; `setup_s` is the benchmark's own reader."""
    bench = tmp_path / "bench"
    for sub in ("kinds", "configs", "traffic", "limits", "metrics"):
        (bench / sub).mkdir(parents=True)
    (bench / "kinds" / "toy.py").write_text(TOY_KIND)
    (bench / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "kind": "toy", "n": 4096, "fault": None}))
    (bench / "traffic" / "sums.json").write_text(json.dumps(
        {"name": "sums", "steps_compared": 3}))
    (bench / "limits" / "toy_sums.json").write_text(json.dumps(
        {"warm_gap": 1e-5, "sum_gap": 1e-4}))
    for name, src in READERS.items():
        (bench / "metrics" / f"{name}.py").write_text(src)
    metric = {"better": "higher", "source": "host_clock"}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy_sums", "config": "toy",
                       "traffic": "sums", "chips": 1}],
        "end_to_end": [
            dict(metric, name="toy_sums_per_s", unit="sums/s"),
            dict(metric, name="setup_s", unit="s", better="lower")],
        "per_layer": [
            dict(metric, name="toy_items", unit="items",
                 source="program_counter", moves="toy_sums_per_s"),
            dict(metric, name="toy_op_s", unit="s",
                 source="device_trace", moves="toy_sums_per_s")]}))
    return str(tmp_path)


def _run(root, trace=False, **overrides):
    return harness.run("toy_sums", SEED, 0.05, trace, root=root,
                       require_accelerator=False, overrides=overrides,
                       log=lambda s: None)


@pytest.mark.parametrize("fault", [None, "state_unchanged"])
def test_toy_kind_runs_from_new_files(toy_root, fault):
    r = _run(toy_root, fault=fault)
    assert r["correct"] == (fault is None), harness.check_lines(r)
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"toy_sums_per_s", "setup_s"}
    assert list(r)[-1] == "checks"


def test_toy_kind_traced_window(toy_root, monkeypatch):
    """The window's spans and counters reach the readers.  No device op
    lands in a CPU trace, so the recorded v5e trace stands in for the
    run's own for `op_s`."""
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: TRACE)
    r = _run(toy_root, trace=True)
    assert r["correct"], harness.check_lines(r)
    assert r["metrics"]["toy_items"]["value"] == 4096 * r["attempted"]
    assert r["metrics"]["toy_op_s"]["value"] > 0
    assert r["breakdown"]["device_ops"]
