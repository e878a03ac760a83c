"""The system under test, driven the way a user drives it.

`System` builds the deployment through the program's own entry points
and exposes the three things the harness needs: one `step` (one
``run_block`` call, ending in host values), the iterate, and the set-up
layer's answers (deadlines, loads, parity sets) for the comparison.

Every ``ExperimentSpec`` field that only picks a code path (engine,
kernel_backend, alloc_backend, fused_coded, fused_embed) keeps its
default, so the benchmark measures the path users get.  A hierarchical
configuration is built as ``repro.hier.HierExperiment`` directly, which
is what ``repro.api.build_experiment`` returns for such a spec, and the
only way to pass ``encode_block``.
"""
from __future__ import annotations

import numpy as np

#: a horizon no run reaches: the window stops on the clock
HORIZON = 10 ** 9


def make_spec(cfg: dict, traffic: dict, fl_seed: int):
    from repro.config import ExperimentSpec, FLConfig, TrainConfig

    net = cfg["network"]
    fl = FLConfig(
        n_clients=cfg["clients"], scheme=traffic["scheme"],
        delta=cfg["delta"], max_rate_bps=net["max_rate_bps"],
        rate_decay=net["rate_decay"], max_mac_rate=net["max_mac_rate"],
        mac_decay=net["mac_decay"], alpha=net["alpha"],
        p_erasure=net["p_erasure"], overhead=net["overhead"],
        bits_per_scalar=net["bits_per_scalar"], seed=fl_seed)
    train = TrainConfig(learning_rate=cfg["train"]["learning_rate"],
                        l2_reg=cfg["train"]["l2_reg"], lr_decay_epochs=())
    return ExperimentSpec(
        fl=fl, train=train, scheme=traffic["scheme"],
        channel_profile=traffic["channel_profile"],
        checkpoint_every=traffic["rounds_per_block"],
        hier_shards=cfg["hier_shards"],
        sample_fraction=cfg["sample_fraction"])


class System:
    """One built deployment and its run state."""

    def __init__(self, cfg: dict, traffic: dict, fl_seed: int, x, y):
        spec = make_spec(cfg, traffic, fl_seed)
        self.hier = spec.hier_active
        if self.hier:
            from repro.hier import HierExperiment
            self.exp = HierExperiment(spec, x, y,
                                      encode_block=cfg["encode_block"])
        else:
            from repro.api import build_experiment
            self.exp = build_experiment(spec, x, y)
        self.state = self.exp.init_state(HORIZON)

    def step(self) -> int:
        """One ``run_block`` call; returns the rounds it completed."""
        import jax
        r0 = self.state.rounds_done
        self.state = self.exp.run_block(self.state)
        jax.block_until_ready(self.state.theta)
        return self.state.rounds_done - r0

    def theta(self) -> np.ndarray:
        return np.asarray(self.state.theta, np.float64)

    def returned(self) -> np.ndarray:
        """Per-round count of clients back by the deadline (in the
        cohort), over every round played so far."""
        return np.asarray(self.state.n_ret)

    def skipped(self) -> int:
        """Rounds the program's divergence guard skipped so far."""
        sk = getattr(self.state, "skipped", None)
        return 0 if sk is None else int(np.sum(sk))

    def answers(self) -> list[dict]:
        """Per edge aggregator (one for the flat engine): t*, the integer
        loads, and the parity set (u, q) / (u, c) as device arrays."""
        if self.hier:
            return [{"t_star": p.t_star, "loads": np.asarray(p.loads),
                     "px": p.parity_x, "py": p.parity_y}
                    for p in self.exp.plans]
        e = self.exp
        return [{"t_star": e.t_star, "loads": np.asarray(e.loads),
                 "px": e.parity.x, "py": e.parity.y}]
