"""Benchmark: paper Fig. 3 — properties of the expected return.

(a) piece-wise concavity of E[R_j(t; l)] in l at the paper's illustration
    parameters (p=0.9, tau=sqrt(3), mu=2, alpha=20, t=10);
(b) monotonicity of the optimized return E[R_j(t; l*_j(t))] in t.
Emits summary rows; full curves land in artifacts/fig3.json.
"""
from __future__ import annotations

import json
import math
import os
import time

import numpy as np

from repro.core import load_allocation as la
from repro.core.delay_model import NodeDelayParams


def run():
    nd = NodeDelayParams(mu=2.0, alpha=20.0, tau=math.sqrt(3.0), p=0.9)
    t0 = time.perf_counter()
    ls = np.linspace(0.05, nd.mu * 10.0, 300)
    curve_a = [la.expected_return(nd, 10.0, float(l)) for l in ls]
    ts = np.linspace(0.5, 40.0, 120)
    curve_b = [la.optimal_load(nd, float(t), cap=25.0)[1] for t in ts]
    us = (time.perf_counter() - t0) * 1e6
    os.makedirs("artifacts", exist_ok=True)
    with open("artifacts/fig3.json", "w") as f:
        json.dump({"l": ls.tolist(), "ER": curve_a,
                   "t": ts.tolist(), "ER_opt": curve_b}, f)
    mono = bool(np.all(np.diff(curve_b) >= -1e-9))
    return [("fig3_expected_return_curves", us,
             f"peak_ER={max(curve_a):.3f};opt_return_monotone={mono}")]


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(",".join(str(x) for x in r))
