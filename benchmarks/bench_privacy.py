"""Benchmark: privacy budget computation (paper Appendix F / eq. 62) across
coding redundancy levels — the paper's privacy-vs-redundancy trade-off."""
from __future__ import annotations

import time

import numpy as np

from repro.core import privacy


def run(l=400, q=2000, deltas=(0.05, 0.1, 0.2, 0.5)):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(l, q)) * np.sqrt(2.0 / q)
    m = 12000
    rows = []
    for delta in deltas:
        u = int(delta * m)
        t0 = time.perf_counter()
        eps = privacy.mi_dp_budget(x, u)
        us = (time.perf_counter() - t0) * 1e6
        rows.append((f"privacy_eps_delta_{delta}", us, f"eps={eps:.3f}bits"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(",".join(str(x) for x in r))
