"""Run telemetry end to end: spans, journal, attribution, text report.

One CodedFedL run with the `repro.obs` subsystem switched on:

  * ``obs_spans.collecting()`` — span timers over setup, the two-step
    allocation solve, parity encode, trace generation, scan
    compile-vs-execute, checkpoint save, and journal appends.  Zero
    overhead when disabled; bit-identical trajectories either way (the
    collector never touches an RNG stream).
  * ``journal_dir=...`` — an append-only ``events.jsonl``, one event per
    round (wall clock, returned count, guard counters, lr scale, loss),
    deterministic to the byte given (spec, seed), and replayable into
    the exact ``FedResult.history`` via `history_from_journal`.
  * ``Experiment.attribution()`` — post-hoc straggler attribution from
    the realized delay tensors: per-client deadline-miss rates, the
    per-round slowest-k counts, and the coded-compensation share.

Everything lands in one run directory, and the same text report the CI
telemetry job prints is rendered from those files alone:

    PYTHONPATH=src python examples/run_report.py
    PYTHONPATH=src python -m benchmarks.obs_report --report /tmp/obs_demo
"""
import json
import os

import numpy as np

from repro.api import (ExperimentSpec, build_experiment, histories_equal,
                       history_from_journal, obs_spans)
from repro.config import FLConfig, TrainConfig
from repro.launch.report import ATTR_NAME, render_report

RUN_DIR = "/tmp/obs_demo"
ITERS = 24


def main():
    rng = np.random.default_rng(0)
    n, l, q, c = 10, 24, 32, 3
    theta_true = rng.normal(size=(q, c)).astype(np.float32)
    xs = rng.normal(size=(n, l, q)).astype(np.float32) * 0.3
    ys = (np.einsum("nlq,qc->nlc", xs, theta_true)
          + 0.005 * rng.normal(size=(n, l, c)).astype(np.float32))
    spec = ExperimentSpec(
        fl=FLConfig(n_clients=n, delta=0.25, psi=0.2, seed=0),
        train=TrainConfig(learning_rate=1.0, l2_reg=0.0),
        scheme="coded", checkpoint_every=6)

    def eval_fn(theta):
        pred = np.einsum("nlq,qc->nlc", xs, np.asarray(theta))
        return float(np.mean((pred - ys) ** 2)), 0.0

    # reference run with telemetry OFF — the invariant under test below
    ref = build_experiment(spec, xs, ys).run(ITERS, eval_fn=eval_fn,
                                             eval_every=1)

    with obs_spans.collecting():
        exp = build_experiment(spec, xs, ys)
        res = exp.run(ITERS, eval_fn=eval_fn, eval_every=1,
                      journal_dir=RUN_DIR)
        attr = exp.attribution()
        obs_spans.write_json(os.path.join(RUN_DIR, obs_spans.SPANS_NAME))
    with open(os.path.join(RUN_DIR, ATTR_NAME), "w") as fh:
        json.dump(attr.to_dict(), fh, indent=2, sort_keys=True)
        fh.write("\n")

    assert np.array_equal(np.asarray(ref.theta), np.asarray(res.theta)), \
        "telemetry must never perturb a trajectory"
    assert histories_equal(history_from_journal(RUN_DIR), res.history), \
        "journal replay must reconstruct the exact history"

    print(render_report(RUN_DIR))
    print(f"run dir: {RUN_DIR} (events.jsonl, spans.json, "
          f"{ATTR_NAME})")
    print("telemetry-on trajectory == telemetry-off trajectory: OK")
    print("journal replay == FedResult.history: OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
