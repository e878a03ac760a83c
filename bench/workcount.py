"""The work a round needs, and the chip's peaks to hold it against.

The work is reckoned from the allocation and each round's draws, never
from padded tensors: a round needs the rows whose gradient reaches the
update, the whole-point loads of the clients that are in the round's
cohort and return by their aggregator's deadline, plus the parity rows u
of every edge aggregator.  A gradient that misses the deadline, or of a
client outside the cohort, changes nothing (the delays are drawn before
the round is played), so it is not work the round needs.  One round
reads each needed row once (f32, q features and c labels) and multiplies
it twice, X theta and X^T r:

    F = 4 rows q c        B = 4 rows (q + c)

So a change that removes padding, streaming or unneeded gradients raises
the shares built from these counts, and no path can push them past 100%.
"""
from __future__ import annotations

import json
import os

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def needed_rows(reached, loads, parity_rows: int) -> int:
    """Rows a set of rounds needs: `reached` (rounds, n) marks the clients
    whose gradient reaches each round's update (back by the deadline, in
    the cohort), `loads` (n,) their whole-point loads (0 for a client
    with none), `parity_rows` the sum of u over aggregators."""
    reached = np.asarray(reached, np.int64)
    per_round = reached @ np.asarray(loads, np.int64)
    return int(per_round.sum()) + reached.shape[0] * int(parity_rows)


def round_work(rows: int, q: int, c: int) -> tuple[float, float]:
    """(FLOPs, bytes) that `rows` needed rows take."""
    return 4.0 * rows * q * c, 4.0 * rows * (q + c)


def peaks(kind: str, path: str = PEAKS) -> dict:
    """Peaks of a device kind as JAX names it; an unknown kind raises."""
    with open(path) as fh:
        table = json.load(fh)["kinds"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path} "
                       f"(known: {sorted(table)})")
    return table[kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> float:
    """100 x the least time the chip could take over `seconds`."""
    bound = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * bound / seconds


def mfu(flops: float, seconds: float, peak: dict) -> float:
    """100 x FLOPs done over what the chip's bf16 peak allows."""
    return 100.0 * flops / (seconds * peak["bf16_flops_per_s"])
