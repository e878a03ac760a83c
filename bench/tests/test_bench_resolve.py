"""Every cell and metric named in BENCHMARK.json resolves to its files,
and only through those names."""
import json
import os
import shutil

import pytest

import harness

ROOT = harness.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    BENCH = json.load(fh)
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    got = harness.load_cell(cell)
    wl = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert got["config"]["name"] == wl["config"]
    assert got["traffic"]["name"] == wl["traffic"]
    e2e = {m for m, _, _ in got["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert got["per_layer"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        named = cell in m.get("workloads", [cell])
        have = m["name"] in e2e | {n for n, _, _ in got["per_layer"]}
        assert named == have, m["name"]
    for _, _, read in got["end_to_end"] + got["per_layer"]:
        assert callable(read)
    # every cell compares set-up, the first calls and the window's
    # streams; `tail_change` only where it separates (PERF.md)
    assert {"t_star", "loads_off", "parity_x", "parity_y", "loss_1",
            "loss_2", "loss_3", "grad_1", "change_3", "returns_off"} \
        <= set(got["limits"]) <= {
        "t_star", "loads_off", "parity_x", "parity_y", "loss_1", "loss_2",
        "loss_3", "grad_1", "change_3", "tail_change", "returns_off"}


def test_config_files_match_entries():
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as fh:
            cfg = json.load(fh)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]


def test_cell_resolves_only_by_name(tmp_path):
    """A copy of the checkout whose cell names a traffic mix under another
    name fails to resolve; restoring the name resolves again."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench", "configs"),
                    tmp_path / "bench" / "configs")
    cell = CELLS[0]
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    wl = next(w for w in bench["workloads"] if w["name"] == cell)
    wl["traffic"] = wl["traffic"] + "_renamed"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(FileNotFoundError):
        harness.load_cell(cell, root=str(tmp_path))
    with pytest.raises(KeyError):
        harness.load_cell("no_such_cell", root=str(tmp_path))
