#!/usr/bin/env python3
"""Drive the coded federated round on a TPU once and check what comes out.

    python3 chip_smoke.py               # one chip: every phase below
    python3 chip_smoke.py --chips 4     # the client mesh on four chips only

The main path is the one a user calls — ``ExperimentSpec`` ->
``repro.api.build_experiment`` -> ``Experiment.run`` — on the paper's
§V-A deployment at its published size (n=30 clients, l=400 points each,
d=784, q=2000, c=10; ``repro.configs.mnist_rff``), with synthetic
MNIST-shaped data made from ``--seed``.  One process; one line of
findings per phase:

  device  platform, device kind and count; anything but a TPU exits 1.
  paper   schemes coded and naive, kernel_backend pallas and xla, 20
          rounds in blocks of 10: setup, compile, first- and warm-run
          seconds.  The pallas round program must hold a Mosaic kernel
          (``tpu_custom_call``).  Pallas vs xla theta, and naive vs a
          NumPy float64 replay of the same 20 gradient steps.
  fused   coded with ``fused_embed=True`` on the raw (n, l, d) features
          vs the two-pass pallas run.
  solver  the vectorized float64 allocation solver, run on the chip, vs
          the scalar NumPy oracle at n=30 and n=1000 (the README's 1e-6).
          At n=30 the oracle solves the whole two-step problem; at
          n=1000 (minutes of host time for the full oracle) it checks
          that the scalar total return brackets the target at
          t* (1 -/+ 1e-6).  Both sizes compare every node's load with the
          scalar optimum at the same deadline.
  hier    the hierarchical tier at n=1e4 (4 shards, sample_fraction
          0.25, 3 rounds): chunked solver and shard kernels, pallas vs
          xla.

With ``--chips 4`` only the paper cell runs: coded, pallas, with mesh=4
and mesh=None.  The compiled mesh program must split the client tensor
over 4 devices, and the two trajectories must agree.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed
only when every phase passed; a failed check exits non-zero.  Times are
single-run smoke timings on the host clock, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the paper's §V-A deployment (repro.configs.mnist_rff)
N, L, D, Q, C = 30, 400, 784, 2000, 10
ROUNDS, BLOCK = 20, 10

#: largest max|a - b| / max|b| allowed between two runs of one deployment
#: whose gradient code differs (pallas vs xla, fused vs two-pass, mesh vs
#: one device, either vs the float64 replay).  On a TPU both XLA's and
#: Mosaic's f32 dots round their operands to bfloat16 at default
#: precision (~4e-3 per product), so 20 rounds drift ~2e-4 from the
#: float64 replay; runs that differ only in kernel agree far closer
THETA_TOL = 1e-2
#: the README's claim for the vectorized solver against the scalar oracle
SOLVER_TOL = 1e-6
#: what marks a Mosaic-compiled Pallas kernel in compiled HLO text
KERNEL_MARK = "tpu_custom_call"


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAIL: {what}")


def rel_diff(a, b) -> float:
    """max|a - b| / max|b| in float64."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def paper_data(seed: int, n: int = N, l: int = L, d: int = D, q: int = Q,
               c: int = C) -> dict:
    """The §V-A cell's client tensors from `seed`.

    Label-sorted shards go to clients by expected speed, as in
    ``benchmarks/bench_fed_training.py``.  The RFF features are embedded
    on the host in float64 from the shared-seed (Omega, delta), so every
    backend starts from the same float32 inputs and the fused run embeds
    the same raw rows itself.
    """
    from repro.config import FLConfig, RFFConfig
    from repro.configs import mnist_rff
    from repro.core import rff
    from repro.core.delay_model import mec_network
    from repro.data import sharding, synthetic

    fl = FLConfig(n_clients=n, seed=seed)
    rcfg = RFFConfig(q=q, sigma=mnist_rff.RFF.sigma)
    ds = synthetic.synthetic_classification(
        m_train=n * l, m_test=2000, d=d, n_classes=c, seed=seed)
    omega, delta = (np.asarray(a, np.float64)
                    for a in rff.rff_params(rcfg, d))

    def embed(x):
        return (np.sqrt(2.0 / q) * np.cos(x.astype(np.float64) @ omega
                                          + delta)).astype(np.float32)

    nodes = mec_network(fl, d_scalars_per_point=q * c)
    shards = sharding.sort_and_shard(ds.x_train, ds.y_train, n)
    per_client = sharding.assign_shards_by_speed(shards, nodes, l)
    xs_raw = np.stack([x for x, _ in per_client])
    ys = np.stack([ds.one_hot(y) for _, y in per_client])
    xs = embed(xs_raw.reshape(n * l, d)).reshape(n, l, q)
    return {"fl": fl, "rff": rcfg, "xs_raw": xs_raw, "xs": xs, "ys": ys,
            "x_test": embed(ds.x_test), "y_test": ds.y_test,
            "lr": rff.suggest_lr(xs.reshape(n * l, q))}


def paper_spec(cell: dict, **kw):
    from repro.config import ExperimentSpec, TrainConfig
    return ExperimentSpec(
        fl=cell["fl"], rff=cell["rff"],
        train=TrainConfig(learning_rate=cell["lr"], lr_decay_epochs=()),
        checkpoint_every=BLOCK, **kw)


def run_cell(spec, xs, ys) -> dict:
    """Build, compile and run one deployment twice through the user
    entry points; every time ends in `block_until_ready`."""
    import jax
    import jax.numpy as jnp
    from repro.api import build_experiment

    t0 = time.perf_counter()
    exp = build_experiment(spec, xs, ys)
    consts = jax.block_until_ready(exp._get_consts())
    setup_s = time.perf_counter() - t0
    # the round program exactly as `run_block` calls it: one block of
    # `checkpoint_every` rounds through the cached jitted scan
    k, rounds = spec.checkpoint_every, ROUNDS
    t0 = time.perf_counter()
    compiled = exp._get_scan(False).lower(
        consts, exp._carry0(jnp.zeros((exp.q, exp.c), jnp.float32), 1.0),
        exp._scan_xs(np.zeros((k, exp.n)), np.zeros(k))).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = exp.run(rounds)
    theta = np.asarray(jax.block_until_ready(res.theta))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(exp.run(rounds).theta)
    warm_s = time.perf_counter() - t0
    check(theta.shape == (exp.q, exp.c) and np.all(np.isfinite(theta)),
          f"{spec.scheme}/{spec.kernel_backend}: theta not finite "
          f"{exp.q}x{exp.c}")
    return {"exp": exp, "theta": theta, "compiled": compiled,
            "kernel": KERNEL_MARK in compiled.as_text(),
            "line": (f"rounds={rounds} block={k} setup_s={setup_s:.3f} "
                     f"compile_s={compile_s:.3f} first_run_s={first_s:.3f} "
                     f"warm_run_s={warm_s:.3f}")}


def naive_reference(xs, ys, lr: float, l2: float, rounds: int):
    """The naive scheme's rounds in NumPy float64: every client returns
    with its whole local set, theta <- theta - lr (X^T (X theta - Y) / m
    + l2 theta)."""
    q, c = xs.shape[-1], ys.shape[-1]
    x = xs.reshape(-1, q).astype(np.float64)
    y = ys.reshape(-1, c).astype(np.float64)
    theta = np.zeros((q, c))
    for _ in range(rounds):
        theta = theta - lr * (x.T @ (x @ theta - y) / x.shape[0]
                              + l2 * theta)
    return theta


def paper_phase(cell: dict) -> dict:
    thetas = {}
    for scheme in ("coded", "naive"):
        for backend in ("pallas", "xla"):
            r = run_cell(paper_spec(cell, scheme=scheme,
                                    kernel_backend=backend),
                         cell["xs"], cell["ys"])
            acc = float(np.mean((cell["x_test"] @ r["theta"]).argmax(1)
                                == cell["y_test"]))
            t_star = r["exp"].t_star
            print(f"paper scheme={scheme} backend={backend} {r['line']} "
                  f"{KERNEL_MARK}={r['kernel']} test_acc={acc:.4f} "
                  f"t_star={t_star}", flush=True)
            if backend == "pallas":
                check(r["kernel"], f"{scheme}/pallas round program has no "
                      f"{KERNEL_MARK}: the kernel was not compiled")
            thetas[scheme, backend] = r["theta"]
        d = rel_diff(thetas[scheme, "pallas"], thetas[scheme, "xla"])
        print(f"paper scheme={scheme} pallas_vs_xla_rel={d:.3e} "
              f"tol={THETA_TOL}", flush=True)
        check(d <= THETA_TOL, f"{scheme}: pallas vs xla {d:.3e}")
    ref = naive_reference(cell["xs"], cell["ys"], cell["lr"],
                          paper_spec(cell).train.l2_reg, ROUNDS)
    for backend in ("pallas", "xla"):
        d = rel_diff(thetas["naive", backend], ref)
        print(f"paper scheme=naive backend={backend} vs_f64_reference_rel="
              f"{d:.3e} tol={THETA_TOL}", flush=True)
        check(d <= THETA_TOL, f"naive/{backend} vs float64 replay {d:.3e}")
    return thetas


def fused_phase(cell: dict, two_pass) -> None:
    r = run_cell(paper_spec(cell, scheme="coded", kernel_backend="pallas",
                            fused_embed=True), cell["xs_raw"], cell["ys"])
    d = rel_diff(r["theta"], two_pass)
    print(f"fused scheme=coded backend=pallas {r['line']} "
          f"{KERNEL_MARK}={r['kernel']} vs_two_pass_rel={d:.3e} "
          f"tol={THETA_TOL}", flush=True)
    check(r["kernel"], f"fused round program has no {KERNEL_MARK}")
    check(d <= THETA_TOL, f"fused vs two-pass {d:.3e}")


def network(n: int, seed: int, q: int = Q, c: int = C):
    """(FLConfig, scaled nodes) of the §V-A network at n clients.  Past
    the paper's n=30 the geometric rate/MAC decays are re-exponentiated
    to span the same range at any n, as ``repro.launch.scale`` does."""
    from repro.config import FLConfig
    from repro.core.delay_model import mec_network, packet_bits, scale_tau
    fl = FLConfig(n_clients=n, seed=seed)
    if n != N:
        fl = FLConfig(n_clients=n, seed=seed, rate_decay=0.95 ** (12.0 / n),
                      mac_decay=0.8 ** (12.0 / n))
    payload = packet_bits(fl, q * c)
    return fl, [scale_tau(nd, payload)
                for nd in mec_network(fl, d_scalars_per_point=q * c)]


def solver_phase(seed: int, sizes=(30, 1000), l: int = L) -> None:
    from repro.core import load_allocation as la
    for n in sizes:
        fl, nodes = network(n, seed)
        caps = [float(l)] * n
        m = float(n * l)
        u = float(max(1, round(fl.delta * m)))
        t0 = time.perf_counter()
        alloc = la.two_step_allocate_vectorized(nodes, caps, None, u, m)
        solve_s = time.perf_counter() - t0
        t = alloc.t_star
        oracle = np.array([la.optimal_load(nd, t, cap)[0]
                           for nd, cap in zip(nodes, caps)])
        load_rel = float(np.abs(alloc.loads - oracle).max() / (1.0 + l))
        if n <= 100:
            exact = la.two_step_allocate(nodes, caps, None, u, m, tol=1e-9)
            t_rel = abs(t - exact.t_star) / (1.0 + exact.t_star)
            t_line = f"t_star_oracle={exact.t_star!r} t_star_rel={t_rel:.3e}"
            t_ok = t_rel <= SOLVER_TOL
        else:
            target = m - u
            lo = float(np.sum(la.max_total_return(
                nodes, caps, t * (1.0 - SOLVER_TOL))[1]))
            hi = float(np.sum(la.max_total_return(
                nodes, caps, t * (1.0 + SOLVER_TOL))[1]))
            t_ok = lo < target <= hi
            t_line = (f"oracle_return_at_t(1-tol)={lo!r} target={target!r} "
                      f"oracle_return_at_t(1+tol)={hi!r} bracketed={t_ok}")
        print(f"solver n={n} t_star={t!r} solve_s={solve_s:.3f} {t_line} "
              f"max_load_diff_rel={load_rel:.3e} tol={SOLVER_TOL}",
              flush=True)
        check(t_ok, f"solver n={n}: t* off the scalar oracle by > "
              f"{SOLVER_TOL}")
        check(load_rel <= SOLVER_TOL,
              f"solver n={n}: load off the scalar oracle by {load_rel:.3e}")


def hier_phase(seed: int, n: int = 10_000, shards: int = 4,
               fraction: float = 0.25, l: int = 16, q: int = 256,
               c: int = C, rounds: int = 3) -> None:
    import jax
    from repro.api import build_experiment
    from repro.config import ExperimentSpec, TrainConfig
    from repro.launch.scale import synthetic_block

    fl, _ = network(n, seed, q, c)
    thetas = {}
    for backend in ("pallas", "xla"):
        spec = ExperimentSpec(
            fl=fl, train=TrainConfig(learning_rate=0.5, l2_reg=1e-5),
            scheme="coded", kernel_backend=backend, hier_shards=shards,
            sample_fraction=fraction)
        t0 = time.perf_counter()
        exp = build_experiment(
            spec, data_fn=lambda lo, hi: synthetic_block(lo, hi, l, q, c))
        setup_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = exp.run(rounds)
        theta = np.asarray(jax.block_until_ready(res.theta))
        run_s = time.perf_counter() - t0
        print(f"hier n={n} shards={shards} sample_fraction={fraction} "
              f"l={l} q={q} c={c} backend={backend} rounds={rounds} "
              f"setup_s={setup_s:.3f} run_s={run_s:.3f} "
              f"t_round={res.t_round!r} "
              f"mean_returned={float(np.mean(res.n_ret)):.1f}", flush=True)
        check(np.all(np.isfinite(theta)), f"hier/{backend}: theta not finite")
        check(np.all(res.n_ret > 0), f"hier/{backend}: no client returned")
        thetas[backend] = theta
    d = rel_diff(thetas["pallas"], thetas["xla"])
    print(f"hier pallas_vs_xla_rel={d:.3e} tol={THETA_TOL}", flush=True)
    check(d <= THETA_TOL, f"hier pallas vs xla {d:.3e}")


def mesh_phase(cell: dict, chips: int) -> None:
    runs = {}
    for mesh in (chips, None):
        r = run_cell(paper_spec(cell, scheme="coded", kernel_backend="pallas",
                                mesh=mesh), cell["xs"], cell["ys"])
        gx = r["compiled"].input_shardings[0][0]["gx"]
        spread = len(gx.device_set)
        print(f"mesh={mesh} scheme=coded backend=pallas {r['line']} "
              f"{KERNEL_MARK}={r['kernel']} gx_sharding={gx} "
              f"gx_devices={spread}", flush=True)
        check(r["kernel"], f"mesh={mesh}: round program has no {KERNEL_MARK}")
        if mesh is not None:
            check(spread == chips, f"client tensor on {spread} devices, "
                  f"not {chips}")
        runs[mesh] = r["theta"]
    d = rel_diff(runs[chips], runs[None])
    print(f"mesh={chips} vs_single_device_rel={d:.3e} tol={THETA_TOL}",
          flush=True)
    check(d <= THETA_TOL, f"mesh vs single device {d:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the client-mesh paper cell")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)} devices={devs} compile_cache={cache}",
          flush=True)
    check(dev.platform == "tpu", f"no TPU: JAX's device is {dev.platform}")
    check(len(devs) >= args.chips,
          f"--chips {args.chips} but {len(devs)} device(s)")

    cell = paper_data(args.seed)
    if args.chips > 1:
        mesh_phase(cell, args.chips)
    else:
        thetas = paper_phase(cell)
        fused_phase(cell, thetas["coded", "pallas"])
        solver_phase(args.seed)
        hier_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
