"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  bench_fed_training    -- Fig 4/5 + Tables II/III (scheme accuracy/
                           wall-clock, time-to-accuracy speedups, non-IID
                           accuracy gap)
  bench_scheme_compare  -- coded vs uncoded vs ideal-no-straggler across
                           heterogeneity profiles; writes the
                           BENCH_fed_training.json perf-trajectory artifact
  bench_load_alloc      -- SV footnote 2 (two-step optimizer solve time)
  bench_kernels         -- compute hot-spot kernels (RFF / gradient / parity)
  bench_privacy         -- Appendix F privacy budget vs redundancy
Roofline terms (SRoofline) are produced by benchmarks.roofline from the
dry-run artifacts.
"""
from __future__ import annotations


def main() -> None:
    from benchmarks import (bench_fed_training, bench_fig3, bench_kernels,
                            bench_load_alloc, bench_privacy,
                            bench_scheme_compare)
    print("name,us_per_call,derived")
    rows = []
    rows += bench_load_alloc.run()
    rows += bench_fig3.run()
    rows += bench_kernels.run()
    rows += bench_privacy.run()
    rows += bench_fed_training.run()
    rows += bench_scheme_compare.run()
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
