"""Benchmark: the paper's headline experiment (Fig 4/5, Tables II/III).

Runs naive-uncoded / greedy-uncoded / CodedFedL on the synthetic MNIST
stand-in with the paper's §V-A MEC network, and reports:
  * per-iteration accuracy parity (coded vs naive)      — Fig 4b/5b
  * simulated wall-clock per scheme + time-to-accuracy  — Fig 4c, Tables II/III
  * host wall-clock speedup of the batched scan engine over the legacy
    per-client Python loop (coded scheme, n=32 clients)
  * multi-realization wall-clock bands (mean ± std over independent delay
    realizations, one vmapped call) — the Fig 4/5 confidence bands
Scale is reduced by default so `python -m benchmarks.run` stays fast; pass
--full for the paper-scale (m=12000, q=2000) run.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.api import build_experiment
from repro.config import ExperimentSpec, FLConfig, RFFConfig, TrainConfig
from repro.core import rff
from repro.core.delay_model import mec_network
from repro.data import sharding, synthetic


def engine_speedup(n_clients=32, l=64, q=128, c=10, iters=150, seed=0):
    """Host wall-clock: batched scan engine vs. legacy per-client loop.

    Coded scheme at n_clients (>= 32 by default, the regime stochastic-coded
    follow-ups sweep).  The batched timing includes jit compilation, i.e.
    this is the end-to-end cost of one cold `run()` call.
    """
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(n_clients, l, q)).astype(np.float32) * 0.2
    ys = rng.normal(size=(n_clients, l, c)).astype(np.float32)
    fl = FLConfig(n_clients=n_clients, delta=0.2, psi=0.2, seed=seed)
    tcfg = TrainConfig(learning_rate=0.5, l2_reg=1e-5)
    timings = {}
    for engine in ("batched", "legacy"):
        exp = build_experiment(ExperimentSpec(fl=fl, train=tcfg,
                                              scheme="coded", engine=engine),
                               xs, ys)
        t0 = time.perf_counter()
        exp.run(iters)
        timings[engine] = time.perf_counter() - t0
    speed = timings["legacy"] / timings["batched"]
    return [(f"fed_engine_speedup_coded_n{n_clients}",
             timings["batched"] * 1e6,
             f"legacy_us={timings['legacy'] * 1e6:.0f};speedup={speed:.1f}x")]


def run(m_train=3000, q=256, d=64, n_clients=30, iters=200,
        delta=0.2, psi=0.2, seed=0, return_histories=False):
    fl = FLConfig(n_clients=n_clients, delta=delta, psi=psi, seed=seed)
    ds = synthetic.synthetic_classification(m_train=m_train,
                                            m_test=max(500, m_train // 6),
                                            d=d, seed=seed)
    rcfg = RFFConfig(q=q, sigma=2.0 if d < 256 else 5.0)
    om, de = rff.rff_params(rcfg, d)
    xh_tr = np.asarray(rff.rff_transform(jnp.asarray(ds.x_train), om, de))
    xh_te = np.asarray(rff.rff_transform(jnp.asarray(ds.x_test), om, de))
    lr = rff.suggest_lr(xh_tr)
    nodes = mec_network(fl, d_scalars_per_point=q * ds.n_classes)
    shards = sharding.sort_and_shard(xh_tr, ds.y_train, n_clients)
    minibatch = xh_tr.shape[0] // n_clients
    per_client = sharding.assign_shards_by_speed(shards, nodes, minibatch)
    xs = np.stack([c[0] for c in per_client])
    ys = np.stack([ds.one_hot(c[1]) for c in per_client])
    tcfg = TrainConfig(learning_rate=lr,
                       lr_decay_epochs=(int(iters * 0.55), int(iters * 0.8)))

    def eval_fn(theta):
        th = np.asarray(theta)
        return 0.0, float(((xh_te @ th).argmax(1) == ds.y_test).mean())

    results, sims, rows = {}, {}, []
    for scheme in ("naive", "greedy", "coded"):
        t0 = time.perf_counter()
        sim = build_experiment(ExperimentSpec(fl=fl, train=tcfg,
                                              rff=rcfg, scheme=scheme),
                               xs, ys)
        res = sim.run(iters, eval_fn=eval_fn, eval_every=5)
        us = (time.perf_counter() - t0) * 1e6
        results[scheme] = res
        sims[scheme] = sim
        final = res.history[-1]
        rows.append((f"fed_{scheme}_sim", us,
                     f"acc={final.accuracy:.3f};wall={final.wall_clock:.0f}s"))

    # time-to-accuracy speedups (Tables II/III analog)
    target = 0.95 * results["naive"].history[-1].accuracy

    def t_gamma(res):
        for h in res.history:
            if not np.isnan(h.accuracy) and h.accuracy >= target:
                return h.wall_clock
        return float("inf")

    tU, tG, tC = (t_gamma(results[s]) for s in ("naive", "greedy", "coded"))
    rows.append(("fed_speedup_vs_naive", 0.0,
                 f"gamma={target:.3f};tU/tC={tU / tC:.2f}x"))
    rows.append(("fed_speedup_vs_greedy", 0.0,
                 f"tG/tC={tG / tC if np.isfinite(tG) else float('inf'):.2f}x"
                 if np.isfinite(tG) else "greedy_never_reaches_target"))
    acc_gap = (results["naive"].history[-1].accuracy
               - results["greedy"].history[-1].accuracy)
    rows.append(("fed_noniid_acc_gap_naive_minus_greedy", 0.0,
                 f"{acc_gap:.3f}"))

    # Fig 4/5 confidence bands: R independent delay realizations, vmapped
    # (reuses the sims above — parity setup and scan cache are already warm)
    for scheme in ("naive", "coded"):
        t0 = time.perf_counter()
        multi = sims[scheme].run_multi(iters, 8)
        us = (time.perf_counter() - t0) * 1e6
        mean, std = multi.wall_clock_bands()
        rows.append((f"fed_{scheme}_wall_bands_r8", us,
                     f"final={mean[-1]:.0f}s±{std[-1]:.1f}s"))

    rows += engine_speedup()
    if return_histories:
        return rows, results
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import sys
    full = "--full" in sys.argv
    kw = dict(m_train=12000, q=2000, d=784, iters=350) if full else {}
    for r in run(**kw):
        print(",".join(str(x) for x in r))
