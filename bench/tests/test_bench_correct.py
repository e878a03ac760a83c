"""What decides ``correct``, at a size the CPU holds: the program agrees
with the reference, and the control (the reference with fp8 matmul
operands and a float32 solver in the program's place) fails the cell's
limits."""
import pytest

import compare
import harness
from tiny import TINY

SEED = 2 ** 31 + 4242


@pytest.mark.parametrize("cell", sorted(TINY))
def test_program_agrees_with_reference(cell):
    r = harness.run(cell, SEED, 0.2, False, require_accelerator=False,
                    overrides=TINY[cell], log=lambda s: None)
    assert r["correct"], harness.check_lines(r)
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {m for m, _, _ in
                                 harness.load_cell(cell)["end_to_end"]}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_fails(cell):
    r = harness.run(cell, SEED, 0.2, False, require_accelerator=False,
                    overrides=TINY[cell], variants=("reference", "control"),
                    log=lambda s: None)
    limits = harness.load_cell(cell)["limits"]
    ok, checks = compare.judge(r["variants"]["control"], limits)
    assert not ok, checks
    # the reference against itself reads nothing
    same = r["variants"]["reference"]
    assert all(v == 0 for v in same.values()), same
