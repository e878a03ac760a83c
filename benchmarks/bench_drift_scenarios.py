"""Benchmark: static vs adaptive load allocation under network drift.

Thin CLI over `repro.launch.scenarios`: runs the drift-scenario
comparison (same deployment, same realized channel trace, static round-0
allocation vs the adaptive controller) and writes the standalone
``BENCH_drift_scenarios.json`` artifact the CI `scenarios` smoke step
uploads.  The same section also rides inside ``BENCH_fed_training.json``
(schema v4) via ``benchmarks.bench_scheme_compare``.

  PYTHONPATH=src python -m benchmarks.bench_drift_scenarios [--smoke|--full]
      [--out BENCH_drift_scenarios.json]
  PYTHONPATH=src python -m benchmarks.bench_drift_scenarios \
      --validate BENCH_drift_scenarios.json    # exit 1 on malformed artifact
"""
from __future__ import annotations

import argparse
import datetime
import json
import sys

from repro.launch import scenarios as launch_scenarios

ARTIFACT_NAME = "BENCH_drift_scenarios.json"

_SCALES = {
    "smoke": dict(n_clients=6, l=16, q=16, c=3, iters=50, adapt_every=5),
    "default": dict(),          # repro.launch.scenarios defaults
    "full": dict(n_clients=20, l=48, q=64, c=5, iters=120, adapt_every=8),
}


def run(out_path: str = ARTIFACT_NAME, scale: str = "default",
        kernel_backend: str = "xla"):
    """Run the comparison, write the artifact, return CSV rows."""
    section = launch_scenarios.run_scenarios(
        kernel_backend=kernel_backend, **_SCALES[scale])
    problems = launch_scenarios.validate_scenarios(section)
    if problems:
        raise RuntimeError(f"scenario section failed validation: {problems}")
    artifact = {
        "benchmark": "fed_drift_scenarios",
        "generated": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
        "scenarios": section,
    }
    with open(out_path, "w") as fh:
        json.dump(artifact, fh, indent=2, sort_keys=True)
        fh.write("\n")
    rows = []
    for name, case in section["cases"].items():
        rows.append((
            f"fed_scenario_{name}", case["host_seconds"] * 1e6,
            f"adaptive_speedup={case['adaptive_speedup']:.2f}x;"
            f"tt_static={case['static']['time_to_target']:.2f}s;"
            f"tt_adaptive={case['adaptive']['time_to_target']:.2f}s"))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=ARTIFACT_NAME)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-sized run (seconds, not minutes)")
    ap.add_argument("--full", action="store_true", help="larger run")
    ap.add_argument("--kernel-backend", default="xla",
                    choices=("xla", "pallas"))
    ap.add_argument("--validate", metavar="PATH",
                    help="validate an existing artifact and exit")
    args = ap.parse_args(argv)

    if args.validate:
        try:
            with open(args.validate) as fh:
                artifact = json.load(fh)
        except (OSError, json.JSONDecodeError) as exc:
            print(f"INVALID: cannot load artifact: {exc}", file=sys.stderr)
            return 1
        problems = launch_scenarios.validate_scenarios(
            artifact.get("scenarios"))
        if artifact.get("benchmark") != "fed_drift_scenarios":
            problems.append(
                f"bad benchmark id: {artifact.get('benchmark')!r}")
        if problems:
            for pr in problems:
                print(f"INVALID: {pr}", file=sys.stderr)
            return 1
        print(f"{args.validate}: OK")
        return 0

    scale = "full" if args.full else ("smoke" if args.smoke else "default")
    for name, us, derived in run(args.out, scale=scale,
                                 kernel_backend=args.kernel_backend):
        print(f"{name},{us:.1f},{derived}")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
