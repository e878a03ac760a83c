#!/usr/bin/env python3
"""Readings the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds <n> \
        [--variants <k>] [--seconds <s>]

For each of `n` seeds, one run of the cell with a window of `s` seconds
(the benchmark's ``run_seconds`` by default, so the tail is compared
where a run compares it): the program's readings.  For the first `k`
seeds the same run also puts each variant of the cell's kind
(``VARIANTS`` of ``bench/kinds/<kind>.py``; for ``rff_coded`` the
control, fp8 matmul operands and a float32 solver, and the planted
faults, half the batch and a drifting stream cursor) in the program's
place, compared with the reference exactly as the program is.  One
process; one JSON line per run on stdout.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--variants", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--first-seed", type=int, default=4_000_000_000)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import harness

    if args.seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
            args.seconds = json.load(fh)["run_seconds"]
    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    kind = harness.load_cell(args.workload)["kind"]
    others = tuple(k for k in kind.VARIANTS if k != "reference")
    for i in range(args.seeds):
        seed = args.first_seed + i
        r = harness.run(args.workload, seed, args.seconds, False, log=log,
                        variants=others if i < args.variants else ())
        print(json.dumps({
            "seed": seed, "correct": r["correct"],
            "numbers": {k: c["value"] for k, c in r["checks"].items()},
            "variants": r.get("variants", {}),
            "metrics": {k: m["value"] for k, m in r["metrics"].items()},
            "memory_peak_bytes": r["device"]["memory_peak_bytes"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
