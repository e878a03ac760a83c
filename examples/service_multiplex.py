"""ExperimentService: many concurrent CodedFedL runs in one process.

Submits three heterogeneous jobs — a static coded run, a greedy run with
a different block size, and an adaptive run over a drifting channel — to
one `ExperimentService`, which round-robins one block per job per step
and checkpoints every run under ``root/<run_id>/``.  Midway through, the
service is "killed" (dropped) and a fresh one pointed at the same root
resumes every run from its latest checkpoint; the final models are
bit-identical to an uninterrupted service.

    PYTHONPATH=src python examples/service_multiplex.py
"""
import dataclasses
import tempfile

import numpy as np

from repro.api import ExperimentService, build_experiment
from repro.config import ExperimentSpec, FLConfig, TrainConfig


def make_data(n=8, l=64, q=128, c=4, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(n, l, q)).astype(np.float32) * 0.2
    ys = rng.normal(size=(n, l, c)).astype(np.float32)
    return xs, ys


def main():
    xs, ys = make_data()
    base = ExperimentSpec(
        fl=FLConfig(n_clients=8, delta=0.25, psi=0.25, seed=11),
        train=TrainConfig(learning_rate=0.3),
        scheme="coded", checkpoint_every=20)
    jobs = {
        "coded-static": base,
        "greedy-static": dataclasses.replace(base, scheme="greedy",
                                             checkpoint_every=25),
        "adaptive-drift": dataclasses.replace(
            base, scheme="adaptive_coded", channel_profile="drift_churn",
            adapt_every=10, checkpoint_every=20),
    }
    iterations = 100
    root = tempfile.mkdtemp(prefix="service_runs_")
    print(f"checkpoint root: {root}\n")

    # uninterrupted service = the reference
    control = ExperimentService(root + "_control")
    for rid, spec in jobs.items():
        control.submit(spec, xs, ys, iterations, run_id=rid)
    expect = control.run_until_complete()

    # interleave blocks, then kill the service mid-flight
    svc = ExperimentService(root)
    for rid, spec in jobs.items():
        svc.submit(spec, xs, ys, iterations, run_id=rid)
    for k in range(7):
        rid = svc.step()
        run = svc.runs[rid]
        print(f"step {k}: advanced {rid!r:18s} -> "
              f"{run.state.rounds_done:3d}/{iterations} rounds")
    print("\n-- service killed --\n")
    del svc

    # a fresh service on the same root picks every run back up
    svc2 = ExperimentService(root)
    for rid, spec in jobs.items():
        run = svc2.submit(spec, xs, ys, iterations, run_id=rid)
        print(f"resubmitted {rid!r:18s} resumed={run.resumed} "
              f"at {run.state.rounds_done} rounds")
    results = svc2.run_until_complete()

    print()
    for rid in jobs:
        same = bool(np.array_equal(np.asarray(expect[rid].theta),
                                   np.asarray(results[rid].theta)))
        wall = results[rid].history[-1].wall_clock
        print(f"{rid:18s} final wall-clock {wall:8.1f}s   "
              f"bit-identical to uninterrupted = {same}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
