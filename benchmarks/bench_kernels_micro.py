"""Per-kernel microbenchmark CLI with a committed-artifact regression gate.

Thin front-end over `repro.launch.kernel_bench`: times every kernel the
federated round path is built from (`rff_embed`, `linreg_grad_masked`,
`parity_encode_batched`, the fused embed->gradient kernel, and its
two-pass equivalent), prints the usual ``name,us_per_call,derived`` rows,
and — when given a committed ``BENCH_fed_training.json`` — fails if any
kernel regressed past the threshold.

  PYTHONPATH=src python -m benchmarks.bench_kernels_micro [--smoke|--full]
      [--kernel-backend {xla,pallas}] [--iters N] [--out fresh.json]
  PYTHONPATH=src python -m benchmarks.bench_kernels_micro --smoke \
      --compare BENCH_fed_training.json [--threshold 3.0] \
      [--out fresh_kernels.json]        # exit 1 on regression
  PYTHONPATH=src python -m benchmarks.bench_kernels_micro \
      --validate BENCH_fed_training.json  # exit 1 on malformed section

``--compare`` writes the fresh section to ``--out`` BEFORE judging it, so
a failing CI run can upload the fresh numbers for inspection.  The gate
is one-sided (speedups always pass) and its threshold is generous
(`kernel_bench.DEFAULT_THRESHOLD`) — it exists to catch wrapper-level
regressions (accidental de-jitting, shape blowups), not host jitter.
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.launch import kernel_bench


def _load_section(path: str) -> dict:
    """The ``kernels`` section of an artifact, or a bare section file."""
    with open(path) as fh:
        obj = json.load(fh)
    if isinstance(obj, dict) and "kernels" in obj:
        return obj["kernels"]
    return obj


def run(scale: str = "default", kernel_backend: str = "xla",
        iters: int | None = None, seed: int = 0) -> dict:
    """Run the microbenchmark at a named scale; return the section dict."""
    kwargs = dict(kernel_bench.SCALES[scale])
    if iters is not None:
        kwargs["iters"] = iters
    return kernel_bench.run_kernel_bench(kernel_backend=kernel_backend,
                                         seed=seed, **kwargs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-sized shapes")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale shapes (784-dim features, q=2000)")
    ap.add_argument("--kernel-backend", default="xla",
                    choices=("xla", "pallas"))
    ap.add_argument("--iters", type=int, default=None,
                    help="timed iterations per kernel (default: per-scale)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", metavar="PATH",
                    help="write the fresh kernels section as JSON")
    ap.add_argument("--compare", metavar="PATH",
                    help="committed artifact (or bare kernels section) to "
                         "gate against; exit 1 on regression")
    ap.add_argument("--threshold", type=float,
                    default=kernel_bench.DEFAULT_THRESHOLD,
                    help="regression factor: fresh us_per_call may not "
                         "exceed threshold x committed (default %(default)s)")
    ap.add_argument("--validate", metavar="PATH",
                    help="validate an artifact's kernels section and exit")
    args = ap.parse_args(argv)

    if args.validate:
        problems = kernel_bench.validate_kernels(_load_section(args.validate))
        if problems:
            for pr in problems:
                print(f"INVALID: {pr}", file=sys.stderr)
            return 1
        print(f"{args.validate}: kernels section OK")
        return 0

    scale = "full" if args.full else ("smoke" if args.smoke else "default")
    fresh = run(scale=scale, kernel_backend=args.kernel_backend,
                iters=args.iters, seed=args.seed)
    for name in kernel_bench.KERNEL_NAMES:
        print(f"kernel_{name},{fresh['entries'][name]['us_per_call']:.1f},"
              f"backend={fresh['backend']}")
    print(f"kernel_fused_vs_two_pass,0.0,"
          f"ratio={fresh['fused_vs_two_pass_ratio']:.3f}")

    if args.out:
        with open(args.out, "w") as fh:
            json.dump(fresh, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"wrote {args.out}")

    if args.compare:
        problems = kernel_bench.compare_kernels(
            fresh, _load_section(args.compare), threshold=args.threshold)
        if problems:
            for pr in problems:
                print(f"REGRESSION: {pr}", file=sys.stderr)
            return 1
        print(f"{args.compare}: within {args.threshold:.2f}x of committed")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
