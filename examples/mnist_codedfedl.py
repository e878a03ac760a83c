"""Paper reproduction driver (Fig 4 / Tables II-III, MNIST-scale).

Trains the paper's exact workload — RFF kernel regression, (sigma, q) =
(5, 2000)-style embedding, 30 clients, LTE-parameterized delay network,
non-IID sort-and-shard split — under all three schemes, and reports
time-to-accuracy speedups.  MNIST itself is not downloadable in this
container; a statistically matched synthetic task stands in (DESIGN.md §7).
Wall-clock numbers are simulated seconds from the paper's delay models.

    PYTHONPATH=src python examples/mnist_codedfedl.py             # reduced
    PYTHONPATH=src python examples/mnist_codedfedl.py --full      # paper scale
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks import bench_fed_training  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper scale: m=12000, q=2000, d=784, 350 iters")
    ap.add_argument("--delta", type=float, default=0.2,
                    help="coding redundancy u_max/m (paper: 0.1 / 0.2)")
    ap.add_argument("--psi", type=float, default=0.2,
                    help="greedy drop fraction (paper: 0.1 / 0.2)")
    args = ap.parse_args()
    kw = dict(delta=args.delta, psi=args.psi)
    if args.full:
        kw.update(m_train=12000, q=2000, d=784, iters=350)
    rows, results = bench_fed_training.run(return_histories=True, **kw)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    print("\naccuracy vs iteration (coded should track naive, greedy lag):")
    hist = {s: results[s].history for s in results}
    for i in range(0, len(hist["naive"]), max(1, len(hist["naive"]) // 12)):
        row = {s: hist[s][i].accuracy for s in hist}
        print(f"  iter {i:4d}  naive={row['naive']:.3f} "
              f"greedy={row['greedy']:.3f} coded={row['coded']:.3f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
