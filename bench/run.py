#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n>
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of stdout is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` with ``--trace 1``, and ``checks``: every compared number
beside its limit); the last lines of stderr repeat the checks.  Without
an accelerator, or with fewer chips than the cell asks for, it exits 3
and prints no result; without the program (``src/repro``) it exits 2.
JAX's compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``, else in
``.jax_cache/`` in the checkout.
"""
import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _clean(obj):
    """JSON-safe copy: non-finite floats become their names."""
    if isinstance(obj, dict):
        return {k: _clean(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_clean(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return str(obj)
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"error: no program under {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [src, BENCH]
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    # every program, however quick to compile, is found again next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    import harness
    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), start=START, log=log)
    except harness.NoAccelerator as err:
        print(f"error: {err}", file=sys.stderr)
        return 3
    print(json.dumps(_clean(result)), flush=True)
    for line in harness.check_lines(result):
        print(line, file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
