"""Benchmark: load-allocation optimizer (paper §V footnote 2 — the paper's
MATLAB fminbnd two-step solve takes <2 min; this measures ours)."""
from __future__ import annotations

import time

from repro.config import FLConfig
from repro.core import load_allocation as la
from repro.core.delay_model import mec_network, packet_bits, scale_tau


def run(n_clients=30, minibatch=400, q=2000, c=10, deltas=(0.05, 0.1, 0.2)):
    fl = FLConfig(n_clients=n_clients)
    nodes = [scale_tau(nd, packet_bits(fl, q * c))
             for nd in mec_network(fl, d_scalars_per_point=q * c)]
    m = n_clients * minibatch
    rows = []
    for delta in deltas:
        t0 = time.perf_counter()
        alloc = la.two_step_allocate(nodes, [float(minibatch)] * n_clients,
                                     None, u_max=delta * m, m=float(m))
        us = (time.perf_counter() - t0) * 1e6
        rows.append((f"load_alloc_delta_{delta}", us,
                     f"t_star={alloc.t_star:.3f}s"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(",".join(str(x) for x in r))
