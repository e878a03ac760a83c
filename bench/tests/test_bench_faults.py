"""A run whose timed path is broken underneath comes out not correct:
once for each fault a cell can have (one chip: no exchange between
chips; no tokens), and once for a stream cursor that drifts only after
the first compared calls, which only the numbers reckoned after the
window see."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import harness
from repro.core import aggregation
from repro.core.fed_runtime import Experiment
from repro.hier.topology import HierExperiment
from tiny import TINY


def _unchanged(self, state, n_rounds=None, **kw):
    """A step that returns its state unchanged (the cursor still moves)."""
    k = n_rounds or self.checkpoint_every or 1
    return dataclasses.replace(state, rounds_done=state.rounds_done + k)


def _half_batch(client_grads, returned_mask):
    """Half of the batch left out, the mean taken over the rest."""
    half = client_grads.shape[0] // 2
    mask = jnp.asarray(returned_mask, client_grads.dtype)[:half, None, None]
    return 2.0 * jnp.sum(client_grads[:half] * mask, axis=0)


def _drifting(run_block):
    """run_block that skips one delay draw in every call after the third."""
    def drift(self, state, *a, **kw):
        if state.rounds_done >= 3 * (self.checkpoint_every or 1):
            rng = np.random.default_rng()
            rng.bit_generator.state = state.rng_state
            rng.random()
            state = dataclasses.replace(state,
                                        rng_state=rng.bit_generator.state)
        return run_block(self, state, *a, **kw)
    return drift


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "cursor_drift"])
@pytest.mark.parametrize("cell", sorted(TINY))
def test_fault_is_not_correct(cell, fault, monkeypatch):
    if fault == "state_unchanged":
        monkeypatch.setattr(Experiment, "run_block", _unchanged)
        monkeypatch.setattr(HierExperiment, "run_block", _unchanged)
    elif fault == "half_batch":
        monkeypatch.setattr(aggregation, "masked_gradient_sum", _half_batch)
    else:
        for cls in (Experiment, HierExperiment):
            monkeypatch.setattr(cls, "run_block", _drifting(cls.run_block))
    r = harness.run(cell, 2 ** 31 + 99, 0.2, False,
                    require_accelerator=False, overrides=TINY[cell],
                    log=lambda s: None)
    assert not r["correct"], harness.check_lines(r)
    if fault == "cursor_drift":
        # the first calls still agree: only the numbers reckoned after
        # the window see the drift
        assert all(c["value"] <= c["limit"] for k, c in r["checks"].items()
                   if k not in ("tail_change", "returns_off")), \
            harness.check_lines(r)
