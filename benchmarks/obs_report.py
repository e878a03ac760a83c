"""Telemetry smoke benchmark + run-report CLI (`repro.obs` surface).

Two jobs in one front-end:

* ``--smoke`` / ``--full`` — probe the telemetry subsystem end to end:
  run a journaled + span-timed + attributed demo run into
  ``<out-dir>/demo_run/`` (events.jsonl, spans.json, attribution.json),
  render its text report, then run the schema-v9 ``telemetry`` benchmark
  section (`repro.launch.report.run_telemetry`) and write it to
  ``<out-dir>/telemetry.json``.  With ``--validate`` the section is
  checked against the strict invariants (trajectory bit-identity,
  journal determinism/replay, overhead ratio < 1.05) and the process
  exits 1 on any problem — the CI telemetry job's contract.

* ``--report DIR`` — render the text report for an existing run
  directory (one written by ``Experiment.run(journal_dir=...)`` or an
  `ExperimentService` with telemetry enabled) and exit.

  PYTHONPATH=src python -m benchmarks.obs_report --smoke --validate \
      --out-dir obs_smoke
  PYTHONPATH=src python -m benchmarks.obs_report --report runs/myrun
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from repro.launch import report as report_mod
from repro.obs import spans as obs_spans

# telemetry-probe sizes: "smoke" IS `run_telemetry`'s compute-dominated
# default (CI-sized, seconds); "full" lengthens the horizon so the ratio
# is averaged over more rounds
SCALES = {
    "smoke": dict(),
    "full": dict(iters=400, repeats=5),
}

# demo-run size (report rendering only — invariants are pinned by the
# probe and tests/test_obs.py, so this just needs to be fast)
_DEMO = dict(n_clients=8, l=32, q=32, c=4, iters=16, block=4, seed=0)


def _demo_run(out_dir: str, kernel_backend: str) -> str:
    """One journaled, span-timed, attributed coded run -> its run dir."""
    from repro.config import ExperimentSpec, FLConfig, TrainConfig
    from repro.core.fed_runtime import Experiment

    run_dir = os.path.join(out_dir, "demo_run")
    rng = np.random.default_rng(_DEMO["seed"])
    xs = rng.normal(size=(_DEMO["n_clients"], _DEMO["l"],
                          _DEMO["q"])).astype(np.float32) * 0.2
    ys = rng.normal(size=(_DEMO["n_clients"], _DEMO["l"],
                          _DEMO["c"])).astype(np.float32)
    spec = ExperimentSpec(
        fl=FLConfig(n_clients=_DEMO["n_clients"], delta=0.2, psi=0.2,
                    seed=_DEMO["seed"]),
        train=TrainConfig(learning_rate=0.5, l2_reg=1e-5),
        scheme="coded", kernel_backend=kernel_backend,
        checkpoint_every=_DEMO["block"])
    with obs_spans.collecting():
        exp = Experiment(spec, xs, ys)
        exp.run(_DEMO["iters"], journal_dir=run_dir)
        attr = exp.attribution()
        obs_spans.write_json(os.path.join(run_dir, obs_spans.SPANS_NAME))
    with open(os.path.join(run_dir, report_mod.ATTR_NAME), "w") as fh:
        json.dump(attr.to_dict(), fh, indent=2, sort_keys=True)
        fh.write("\n")
    return run_dir


def run(out_dir: str, scale: str = "smoke", kernel_backend: str = "xla",
        validate: bool = False) -> int:
    os.makedirs(out_dir, exist_ok=True)
    run_dir = _demo_run(out_dir, kernel_backend)
    print(report_mod.render_report(run_dir))

    telemetry = report_mod.run_telemetry(kernel_backend=kernel_backend,
                                         **SCALES[scale])
    out_path = os.path.join(out_dir, "telemetry.json")
    with open(out_path, "w") as fh:
        json.dump(telemetry, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"telemetry section -> {out_path}")
    print(f"  overhead_ratio={telemetry['overhead_ratio']:.4f} "
          f"(enabled {telemetry['enabled_seconds']:.3f}s / "
          f"disabled {telemetry['disabled_seconds']:.3f}s)")
    print(f"  trajectory_bit_identical="
          f"{telemetry['trajectory_bit_identical']} "
          f"journal_deterministic={telemetry['journal_deterministic']} "
          f"journal_replay_matches={telemetry['journal_replay_matches']}")
    if validate:
        problems = report_mod.validate_telemetry(telemetry)
        if problems:
            print("telemetry section FAILED validation:", file=sys.stderr)
            for p in problems:
                print(f"  - {p}", file=sys.stderr)
            return 1
        print("telemetry section validates (strict ceiling "
              f"{report_mod.MAX_OVERHEAD_RATIO})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out-dir", default="obs_smoke",
                    help="where the demo run dir + telemetry.json land")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized probe (the default scale)")
    ap.add_argument("--full", action="store_true",
                    help="longer-horizon probe")
    ap.add_argument("--validate", action="store_true",
                    help="enforce the strict telemetry invariants; "
                         "exit 1 on any problem")
    ap.add_argument("--kernel-backend", default="xla",
                    choices=("xla", "pallas"))
    ap.add_argument("--report", metavar="DIR",
                    help="render the text report for an existing run "
                         "directory and exit")
    args = ap.parse_args(argv)
    if args.report:
        print(report_mod.render_report(args.report))
        return 0
    scale = "full" if args.full else "smoke"
    return run(args.out_dir, scale=scale,
               kernel_backend=args.kernel_backend, validate=args.validate)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
