"""The comparison that decides ``correct``.

A side is what is compared of one run: the program's, or a reference
variant's put in the program's place (see `side`).  `numbers` reduces a
side and the reference's to the compared numbers; `judge` holds each
against its limit from ``bench/limits/<cell>.json``.  Every number is a
gap that reads 0 when the two sides agree:

``t_star``      max over edge aggregators of |t*_p - t*_r| / (1 + t*_r)
``loads_off``   clients whose whole-point load differs from the floor
                of the reference's optimum by more than one point
``parity_x``    max over aggregators of ||(P_p - P_r) V|| / ||P_r V||,
                V a fixed (q, 16) Gaussian probe
``parity_y``    max over aggregators of ||Y~_p - Y~_r|| / ||Y~_r||
``loss_k``      |L(theta_p,k) - L(theta_r,k)| / L(theta_r,k), the squared
                loss over every client point after run_block call k
``grad_1``      gap of norms of the first call's update, relative to the
                reference's: | ||theta_1p|| - ||theta_1r|| | / ||theta_1r||
                (theta_0 = 0, so this is the first gradient's norm times
                the step size when a call plays one round)
``change_3``    the same gap for the change after the third call
``tail_change`` after the window: both sides start from the program's
                iterate theta_w and the stream positions the window left,
                and play the same number of calls again;
                ||D_p - D_r|| / ||D_r||, D the change from theta_w
``returns_off`` share of all rounds played (the first calls, the window,
                the tail) whose count of clients back by the deadline
                differs between the sides: the delay and cohort streams
                over the whole window

A cell's limits name the numbers it compares; the others are reckoned
and left unjudged (PERF.md gives the readings of each).
"""
from __future__ import annotations

import math

import numpy as np

#: rows of the parity probe
PROBE = 16


def probe(q: int):
    """The fixed (q, PROBE) Gaussian probe the parity features are
    projected on before they are compared."""
    import jax
    return jax.random.normal(jax.random.PRNGKey(20110623), (q, PROBE))


def probed(shards: list[dict], mm, v) -> list[dict]:
    """Host copies of what is compared of each edge aggregator's set-up:
    t_star, loads (and the reference's ``real`` optimum where present),
    the parity features times the probe `v`, and the parity labels."""
    out = []
    for s in shards:
        d = {"t_star": s["t_star"], "loads": np.asarray(s["loads"]),
             "px_v": np.asarray(mm(s["px"], v)), "py": np.asarray(s["py"])}
        if "real" in s:
            d["real"] = s["real"]
        out.append(d)
    return out


def side(setup: list[dict], thetas: list, tail, returned) -> dict:
    """What is compared of one run: the probed set-up answers, the
    iterate after each of the first calls, the iterate after the calls
    played again from the window's end, and every round's count of
    clients back by the deadline."""
    return {"setup": setup, "thetas": list(thetas), "tail": tail,
            "returned": np.asarray(returned)}


def _rel(a, b) -> float:
    """||a - b|| / ||b|| in float64 (device or host arrays)."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def _norm_gap(a, b) -> float:
    nb = float(np.linalg.norm(b))
    return abs(float(np.linalg.norm(a)) - nb) / max(nb, 1e-300)


def parity_numbers(prog: list[dict], ref: list[dict]) -> dict:
    """Set-up layer numbers, per edge aggregator on each side: t_star,
    loads (whole points; the reference's ``real`` optimum), px_v (the
    parity features times the probe) and py (the parity labels)."""
    t_gap, off, px_gap, py_gap = 0.0, 0, 0.0, 0.0
    for p, r in zip(prog, ref, strict=True):
        t_gap = max(t_gap, abs(p["t_star"] - r["t_star"]) / (1 + r["t_star"]))
        off += int(np.sum(np.abs(np.asarray(p["loads"], np.int64)
                                 - np.floor(r["real"]).astype(np.int64)) > 1))
        if p["px_v"].shape != r["px_v"].shape:
            return {"t_star": math.inf, "loads_off": off,
                    "parity_x": math.inf, "parity_y": math.inf}
        px_gap = max(px_gap, _rel(p["px_v"], r["px_v"]))
        py_gap = max(py_gap, _rel(p["py"], r["py"]))
    return {"t_star": t_gap, "loads_off": off, "parity_x": px_gap,
            "parity_y": py_gap}


def step_numbers(thetas_p: list, thetas_r: list, losses_p: list,
                 losses_r: list) -> dict:
    out = {f"loss_{k + 1}": abs(lp - lr) / lr
           for k, (lp, lr) in enumerate(zip(losses_p, losses_r, strict=True))}
    out["grad_1"] = _norm_gap(thetas_p[0], thetas_r[0])
    out["change_3"] = _norm_gap(thetas_p[2], thetas_r[2])
    return out


def numbers(sp: dict, sr: dict, theta_w, losses) -> dict:
    """Every compared number of side `sp` against the reference's side
    `sr`; `theta_w` is the iterate both tails start from, and `losses`
    the reference's loss over a list of iterates."""
    k = len(sp["thetas"])
    ls = losses(sp["thetas"] + sr["thetas"])
    nums = parity_numbers(sp["setup"], sr["setup"])
    nums.update(step_numbers(sp["thetas"], sr["thetas"], ls[:k], ls[k:]))
    nums["tail_change"] = _rel(np.asarray(sp["tail"]) - theta_w,
                               np.asarray(sr["tail"]) - theta_w)
    rp, rr = sp["returned"], sr["returned"]
    nums["returns_off"] = float(np.mean(rp != rr)) \
        if rp.shape == rr.shape else math.inf
    return nums


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): correct when every number
    that has a limit is finite and at most it, and no limit is left
    unread."""
    if not set(limits) <= set(nums):
        raise ValueError(f"limits {sorted(limits)} name numbers that are "
                         f"not reckoned: {sorted(nums)}")
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
