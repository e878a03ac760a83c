"""Benchmark: coded vs uncoded vs ideal-no-straggler scheme comparison.

Thin CLI/CSV front-end over `repro.launch.bench`: runs the comparison
across heterogeneity profiles with `run_multi`, writes the
``BENCH_fed_training.json`` artifact (the recorded perf trajectory; CI
asserts it exists and is well-formed every push) and emits the usual
``name,us_per_call,derived`` rows for `benchmarks.run`.

  PYTHONPATH=src python -m benchmarks.bench_scheme_compare [--smoke|--full]
      [--out BENCH_fed_training.json]
  PYTHONPATH=src python -m benchmarks.bench_scheme_compare \
      --validate BENCH_fed_training.json     # exit 1 on malformed artifact
"""
from __future__ import annotations

import argparse
import sys

from repro.launch import bench as launch_bench
from repro.launch import kernel_bench

# (n_clients, l, q, c, iters, realizations) for the profile grid, plus
# the drift-scenario (static vs adaptive) comparison's, the RunState
# service benchmark's, the per-kernel microbenchmark's, the
# fault-injection resilience benchmark's, and the hierarchical
# population-scaling benchmark's own sizes
_SCALES = {
    "smoke": dict(n_clients=5, l=12, q=16, c=3, iters=8, realizations=3,
                  scenario_kwargs=dict(n_clients=6, l=16, q=16, c=3,
                                       iters=50, adapt_every=5),
                  service_kwargs=dict(n_clients=6, l=16, q=16, c=3,
                                      iters=24, block=6),
                  kernel_kwargs=dict(kernel_bench.SCALES["smoke"], iters=10),
                  resilience_kwargs=dict(iters=24),
                  # the full REQUIRED_NS ladder (the artifact validator
                  # pins it) at the shallowest deterministic solver depth
                  scale_kwargs=dict(rounds=2, trace_rounds=2,
                                    solver_kwargs=dict(n_golden_search=12,
                                                       n_bisect=20))),
    "default": dict(n_clients=12, l=32, q=64, c=5, iters=40,
                    realizations=6, scenario_kwargs=None,
                    service_kwargs=None,
                    kernel_kwargs=dict(kernel_bench.SCALES["default"],
                                       iters=20),
                    resilience_kwargs=None, scale_kwargs=None),
    "full": dict(n_clients=30, l=100, q=256, c=10, iters=150,
                 realizations=8,
                 scenario_kwargs=dict(n_clients=20, l=48, q=64, c=5,
                                      iters=120, adapt_every=8),
                 service_kwargs=None,
                 kernel_kwargs=dict(kernel_bench.SCALES["full"], iters=20),
                 resilience_kwargs=dict(iters=80),
                 scale_kwargs=dict(
                     ns=(1_000, 10_000, 100_000, 1_000_000))),
}


def run(out_path: str = launch_bench.ARTIFACT_NAME, scale: str = "default",
        kernel_backend: str = "xla", engine: str = "sweep",
        measure_loop: bool = True):
    """Run the comparison, write the artifact, return CSV rows."""
    result = launch_bench.run_schemes(kernel_backend=kernel_backend,
                                      engine=engine,
                                      measure_loop=measure_loop,
                                      **_SCALES[scale])
    launch_bench.write_artifact(result, out_path)
    problems = launch_bench.validate_artifact(out_path)
    if problems:
        raise RuntimeError(f"benchmark artifact failed validation: {problems}")
    rows = []
    for pname, prof in result["profiles"].items():
        for scheme, entry in prof["schemes"].items():
            rows.append((
                f"fed_compare_{pname}_{scheme}",
                entry["host_seconds"] * 1e6,
                f"wall={entry['final_wall_clock_mean']:.1f}s"
                f"±{entry['final_wall_clock_std']:.1f}"))
        rows.append((f"fed_compare_{pname}_speedup", 0.0,
                     f"vs_naive={prof['coded_speedup_vs_naive']:.2f}x;"
                     f"vs_ideal={prof['coded_overhead_vs_ideal']:.2f}x"))
    sweep = result.get("sweep")
    if sweep:
        derived = (f"loop={sweep['loop_host_seconds']:.2f}s;"
                   f"speedup={sweep['speedup']:.2f}x"
                   if sweep.get("speedup") else "loop=unmeasured")
        rows.append(("fed_sweep_grid", sweep["host_seconds"] * 1e6, derived))
    service = result.get("service")
    if service:
        rows.append((
            "fed_service_block_overhead",
            service["blocked_seconds"] * 1e6,
            f"oneshot={service['oneshot_seconds']:.3f}s;"
            f"ratio={service['overhead_ratio']:.3f};"
            f"resumed_ok={service['resumed_bit_identical']}"))
    kernels = result.get("kernels")
    if kernels:
        for kname, entry in kernels["entries"].items():
            rows.append((f"kernel_{kname}", entry["us_per_call"],
                         f"backend={kernels['backend']}"))
        rows.append(("kernel_fused_vs_two_pass", 0.0,
                     f"ratio={kernels['fused_vs_two_pass_ratio']:.3f}"))
    for name, case in result.get("scenarios", {}).get("cases", {}).items():
        rows.append((
            f"fed_scenario_{name}", case["host_seconds"] * 1e6,
            f"adaptive_speedup={case['adaptive_speedup']:.2f}x;"
            f"tt_static={case['static']['time_to_target']:.2f}s;"
            f"tt_adaptive={case['adaptive']['time_to_target']:.2f}s"))
    resilience = result.get("resilience")
    if resilience:
        for name, case in resilience["cases"].items():
            rows.append((
                f"fed_resilience_{name}", case["host_seconds"] * 1e6,
                f"masked={case['coded']['health']['returns_masked']};"
                f"naive_skipped="
                f"{case['naive_unguarded']['health']['rounds_skipped']};"
                f"graceful={case['coded']['degraded_gracefully']}"))
        chaos = resilience["service"]
        rows.append((
            "fed_resilience_chaos", chaos["host_seconds"] * 1e6,
            f"crash_retries={chaos['crash_retries']};"
            f"chaos_ok={chaos['chaos_bit_identical']};"
            f"fallback_ok={chaos['fallback_recovery_bit_identical']}"))
    telemetry = result.get("telemetry")
    if telemetry:
        rows.append((
            "fed_telemetry_overhead", telemetry["enabled_seconds"] * 1e6,
            f"ratio={telemetry['overhead_ratio']:.3f};"
            f"bit_identical={telemetry['trajectory_bit_identical']};"
            f"journal_ok={telemetry['journal_deterministic']}"))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=launch_bench.ARTIFACT_NAME)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-sized run (seconds, not minutes)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale run")
    ap.add_argument("--kernel-backend", default="xla",
                    choices=("xla", "pallas"))
    ap.add_argument("--engine", default="sweep", choices=("sweep", "loop"),
                    help="compiled (profile x realization) sweep per scheme "
                         "(default) or the pre-sweep per-profile loop")
    ap.add_argument("--no-loop-baseline", action="store_true",
                    help="skip timing the looped path (sweep engine only); "
                         "the artifact then omits the measured speedup")
    ap.add_argument("--validate", metavar="PATH",
                    help="validate an existing artifact and exit")
    args = ap.parse_args(argv)

    if args.validate:
        problems = launch_bench.validate_artifact(args.validate)
        if problems:
            for pr in problems:
                print(f"INVALID: {pr}", file=sys.stderr)
            return 1
        print(f"{args.validate}: OK")
        return 0

    scale = "full" if args.full else ("smoke" if args.smoke else "default")
    for name, us, derived in run(args.out, scale=scale,
                                 kernel_backend=args.kernel_backend,
                                 engine=args.engine,
                                 measure_loop=not args.no_loop_baseline):
        print(f"{name},{us:.1f},{derived}")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
