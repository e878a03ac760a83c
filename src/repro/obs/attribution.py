"""Post-hoc straggler attribution from the run's materialized delay tensors.

The engine pre-samples every round's per-client delays into host arrays
before compiling the scan (`fed_runtime._block_single`,
`hier.topology.HierExperiment.run_block`).  With telemetry enabled
(`repro.obs.spans.enable`) those already-materialized blocks are kept —
a numpy reference per block, no RNG touched, no extra draws — and this
module turns them into the paper's delay analysis, per client:

  * **deadline-miss rate** — fraction of rounds a client exceeded the
    round deadline: the deadline the compiled round played
    (`Scheme.round_rule`, fetched with the block's outputs) in the flat
    runtime, each shard's t* in the hierarchical tier;
  * **slowest-k contributions** — how often the client was among the k
    slowest present that round (who *drives* the tail, not just who
    misses);
  * **coded-compensation share** — per round, the fraction of the data
    mass the parity gradient stood in for: ``1 - sum_j l_j r_j / m``
    (a data-mass proxy for the parity share of the update, exact for
    the uniform-weight limit; 0 for schemes with no parity).

Exposed as ``Experiment.attribution()`` and, per shard, as
``HierExperiment.attribution()``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Attribution", "attribution_from_blocks", "compute_attribution"]


@dataclasses.dataclass
class Attribution:
    """Straggler attribution over one run's captured rounds."""
    rounds: int                  # rounds covered
    k: int                       # slowest-k window
    miss_rate: np.ndarray        # (n,) deadline-miss rate per client
    miss_counts: np.ndarray      # (n,) rounds missed
    active_rounds: np.ndarray    # (n,) rounds the client was present
    slowest_k_counts: np.ndarray  # (n,) rounds among the k slowest present
    comp_share: np.ndarray       # (rounds,) coded-compensation data share
    deadline: np.ndarray         # (rounds,) the round's deadline
    on_time: np.ndarray          # (rounds,) clients present and on time

    def top_stragglers(self, count: int = 5) -> "list[tuple[int, float]]":
        """[(client, miss_rate)] sorted worst-first, ties by client id."""
        order = np.lexsort((np.arange(len(self.miss_rate)),
                            -self.miss_rate))
        return [(int(j), float(self.miss_rate[j]))
                for j in order[:count]]

    def to_dict(self) -> dict:
        return {
            "rounds": int(self.rounds),
            "k": int(self.k),
            "miss_rate": [float(v) for v in self.miss_rate],
            "miss_counts": [int(v) for v in self.miss_counts],
            "active_rounds": [int(v) for v in self.active_rounds],
            "slowest_k_counts": [int(v) for v in self.slowest_k_counts],
            "comp_share_mean": float(self.comp_share.mean())
            if len(self.comp_share) else 0.0,
            "top_stragglers": [[j, r] for j, r in self.top_stragglers()],
        }


def compute_attribution(times: np.ndarray, active, deadline: np.ndarray,
                        *, loads=None, m=None, coded: bool = False,
                        k: int = 3) -> Attribution:
    """Attribution over (T, n) delay samples against (T,) deadlines.

    `active` is the (T, n) presence mask (churn / sampled cohorts), or
    None for all-present runs.  `loads`/`m` feed the coded-compensation
    data share when `coded`.
    """
    times = np.asarray(times, np.float64)
    T, n = times.shape
    if k < 1:
        raise ValueError(f"k={k} must be >= 1")
    active = (np.ones((T, n), bool) if active is None
              else np.asarray(active) > 0)
    deadline = np.asarray(deadline, np.float64)
    miss = (times > deadline[:, None]) & active
    active_rounds = active.sum(axis=0)
    miss_counts = miss.sum(axis=0)
    miss_rate = miss_counts / np.maximum(active_rounds, 1)
    # slowest-k among clients PRESENT each round: absent clients sort
    # first at -inf, so the tail of the argsort is the live tail — but
    # guard rounds with fewer than k present
    order = np.argsort(np.where(active, times, -np.inf), axis=1,
                       kind="stable")
    tail = order[:, -min(k, n):]
    tail_live = np.take_along_axis(active, tail, axis=1)
    slowest = np.zeros(n, np.int64)
    np.add.at(slowest, tail[tail_live], 1)
    ret = (~miss) & active
    if coded:
        mass = (np.asarray(loads, np.float64)[None, :] * ret).sum(axis=1)
        comp_share = np.clip(1.0 - mass / float(m), 0.0, 1.0)
    else:
        comp_share = np.zeros(T, np.float64)
    return Attribution(rounds=T, k=int(min(k, n)), miss_rate=miss_rate,
                       miss_counts=miss_counts,
                       active_rounds=active_rounds,
                       slowest_k_counts=slowest, comp_share=comp_share,
                       deadline=deadline, on_time=ret.sum(axis=1))


def attribution_from_blocks(blocks: "list[dict]", *, loads=None, m=None,
                            coded: bool = False, k: int = 3) -> Attribution:
    """Concatenate per-block captures (`fed_runtime._block_single`) and
    attribute.  Each block dict: ``times`` (K, n), optional ``active``
    (K, n), and ``deadline`` (K,), the deadlines the program played."""
    if not blocks:
        raise RuntimeError(
            "no telemetry captured for this run: call "
            "repro.obs.spans.enable() before running, then attribution()")
    times = np.concatenate([np.asarray(b["times"], np.float64)
                            for b in blocks])
    active = np.concatenate(
        [np.asarray(b["active"], np.float64) if b.get("active") is not None
         else np.ones_like(np.asarray(b["times"], np.float64))
         for b in blocks])
    deadline = np.concatenate([np.asarray(b["deadline"], np.float64)
                               for b in blocks])
    return compute_attribution(times, active, deadline, loads=loads, m=m,
                               coded=coded, k=k)
