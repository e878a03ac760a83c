"""Benchmark: hierarchical-tier population scaling (n = 1e3 .. 1e6).

Thin CLI over `repro.launch.scale.run_scale`: runs the edge-aggregator
tier with sampled cohorts and streamed synthetic client blocks across a
ladder of population sizes, writes a standalone ``BENCH_hier_scale.json``
(the same section `repro.launch.bench` embeds into
``BENCH_fed_training.json`` under schema v8), and emits the usual
``name,us_per_call,derived`` rows for `benchmarks.run`.

  PYTHONPATH=src python -m benchmarks.bench_hier_scale [--smoke|--full]
      [--out BENCH_hier_scale.json]
  PYTHONPATH=src python -m benchmarks.bench_hier_scale \
      --validate BENCH_hier_scale.json     # exit 1 on malformed artifact

--smoke covers n in {1e3, 1e4} (the CI ``scale`` job's budget), the
default ladder is the committed-artifact n in {1e3, 1e4, 1e5}, and
--full adds the 1e6 rung.  Validation of a standalone artifact pins the
ladder the run itself recorded (``ns``); the committed
BENCH_fed_training.json ladder is pinned to `scale.REQUIRED_NS` by
`repro.launch.bench.validate_artifact`.
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.launch import scale as scale_mod

_NS = {
    "smoke": (1_000, 10_000),
    "default": scale_mod.REQUIRED_NS,
    "full": (1_000, 10_000, 100_000, 1_000_000),
}


def run(out_path: str = "BENCH_hier_scale.json", ladder: str = "default",
        rounds: int = 2):
    """Run the ladder, write the artifact, return CSV rows."""
    ns = _NS[ladder]
    section = scale_mod.run_scale(ns=ns, rounds=rounds)
    with open(out_path, "w") as fh:
        json.dump(section, fh, indent=2, sort_keys=True)
        fh.write("\n")
    problems = scale_mod.validate_scale(section, required_ns=ns)
    if problems:
        raise RuntimeError(f"scale artifact failed validation: {problems}")
    rows = []
    for entry in section["entries"]:
        n = entry["n"]
        rows.append((
            f"hier_scale_n{n}", entry["wall_seconds"] * 1e6,
            f"setup={entry['setup_seconds']:.2f}s;"
            f"rounds={entry['round_seconds']:.2f}s;"
            f"shards={entry['shards']};"
            f"peak_bytes={entry['peak_client_tensor_bytes']};"
            f"dense_bytes={entry['dense_client_tensor_bytes']}"))
        rows.append((
            f"hier_trace_n{n}", entry["trace_seconds"] * 1e6,
            f"rounds={entry['trace_rounds']}"))
    ident = section["identity"]
    rows.append(("hier_identity", 0.0,
                 f"routes_flat={ident['routes_flat_engine']};"
                 f"bit_identical={ident['bit_identical']}"))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_hier_scale.json")
    ap.add_argument("--smoke", action="store_true",
                    help="n in {1e3, 1e4} (the CI scale job's budget)")
    ap.add_argument("--full", action="store_true",
                    help="adds the 1e6 rung to the default ladder")
    ap.add_argument("--rounds", type=int, default=2,
                    help="federated rounds per rung")
    ap.add_argument("--validate", metavar="PATH",
                    help="validate an existing artifact and exit")
    args = ap.parse_args(argv)

    if args.validate:
        try:
            with open(args.validate) as fh:
                section = json.load(fh)
        except (OSError, json.JSONDecodeError) as exc:
            print(f"INVALID: cannot load artifact: {exc}", file=sys.stderr)
            return 1
        ns = section.get("ns") if isinstance(section, dict) else None
        problems = scale_mod.validate_scale(
            section, required_ns=tuple(ns) if ns else scale_mod.REQUIRED_NS)
        if not isinstance(ns, list) or not ns:
            problems = [f"missing/empty 'ns' ladder: {ns!r}"] + problems
        if problems:
            for pr in problems:
                print(f"INVALID: {pr}", file=sys.stderr)
            return 1
        print(f"{args.validate}: OK")
        return 0

    ladder = "full" if args.full else ("smoke" if args.smoke else "default")
    for name, us, derived in run(args.out, ladder=ladder,
                                 rounds=args.rounds):
        print(f"{name},{us:.1f},{derived}")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
