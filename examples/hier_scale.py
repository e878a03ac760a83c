"""Hierarchical population scale: 20,000 clients on a laptop-class CPU.

The flat engine materializes a dense ``(n, l, q)`` client tensor and
solves the two-step allocation over all n nodes at once — fine at the
paper's n <= 1000, hopeless at a population.  The hierarchical tier
(`repro.hier`) partitions the population into edge-aggregator shards,
runs the static coded round per shard (chunked O(block)-memory solver),
samples a Bernoulli(f) cohort per round from a dedicated RNG stream, and
reweights each shard's parity gradient so the update stays an unbiased
SGD step at every f.  Client tensors exist one shard at a time, streamed
through ``data_fn(lo, hi)`` — nothing O(n * l * q) is ever resident.

This example builds a 10k-client deployment (10 shards of 1k, 25%%
cohorts), runs a few rounds, shows the O(active cohort) memory contract
and the kill/resume round-trip, then prints a tiny scaling curve.  The
real curve (n = 1e3..1e5, recorded in the schema-v8 ``scale`` section of
`BENCH_fed_training.json`) is produced by
``python -m benchmarks.bench_hier_scale``.  Hier-active specs also
build through the usual ``repro.api.build_experiment(spec,
data_fn=...)`` — this example constructs `HierExperiment` directly only
to pass ``solver_kwargs`` (shallower deterministic solver iterations,
the knob the scale bench uses on its largest rungs).

    PYTHONPATH=src python examples/hier_scale.py
"""
import time

from repro.config import ExperimentSpec, FLConfig, TrainConfig
from repro.hier import HierExperiment
from repro.launch import scale as launch_scale

N, SHARDS, L, Q, C = 10_000, 10, 8, 16, 3


def main():
    # heterogeneity knobs re-exponentiated so the population spans the
    # same rate/compute range as the paper's 12-client cell at any n
    fl = FLConfig(n_clients=N, delta=0.2, seed=0,
                  rate_decay=0.95 ** (12.0 / N),
                  mac_decay=0.8 ** (12.0 / N))
    spec = ExperimentSpec(
        fl=fl, train=TrainConfig(learning_rate=0.5, l2_reg=1e-5),
        scheme="coded", hier_shards=SHARDS, sample_fraction=0.25)

    # streamed client blocks: deterministic synthetic data generated per
    # (lo, hi) range on demand — the dense (N, L, Q) tensor never exists
    def data_fn(lo, hi):
        return launch_scale.synthetic_block(lo, hi, L, Q, C)

    t0 = time.perf_counter()
    exp = HierExperiment(spec, data_fn=data_fn,
                         solver_kwargs=dict(n_golden_search=12, n_bisect=20))
    print(f"setup: {SHARDS} edge aggregators over n={N} clients in "
          f"{time.perf_counter() - t0:.1f}s host time "
          f"(simulated parity-upload overhead {exp.setup_time:.2f}s)")
    peak, dense = exp.peak_client_tensor_bytes(), 4 * N * L * (Q + C)
    print(f"peak client-tensor memory: {peak / 1e6:.2f} MB "
          f"(dense flat engine would hold {dense / 1e6:.2f} MB; "
          f"{dense / peak:.0f}x less — O(active cohort))")

    t0 = time.perf_counter()
    state = exp.run_block(exp.init_state(4), 2)     # two rounds...
    mid = exp.save_state("/tmp/hier_example_ckpt_000002.npz", state)
    state = exp.run_block(exp.restore_state(mid), 2)   # ...kill/resume
    res = exp.finish(state)
    print(f"4 rounds in {time.perf_counter() - t0:.1f}s host time; "
          f"server deadline t_round={res.t_round:.4f}s, "
          f"mean in-cohort returns/round "
          f"{res.n_ret.mean():.0f}/{N} (f=0.25)")
    w = max(p.parity_weight for p in res.plans)
    print(f"coded compensation: max shard parity reweight w(f)={w:.3f} "
          f"(unbiased update; w=1 exactly at f=1)\n")

    print("tiny scaling curve (the bench records n=1e3..1e5):")
    section = launch_scale.run_scale(
        ns=(1_000, 4_000), l=4, q=8, c=2, rounds=2, trace_rounds=1,
        solver_kwargs=dict(n_golden_search=12, n_bisect=20))
    for e in section["entries"]:
        print(f"  n={e['n']:>6d}: setup {e['setup_seconds']:6.1f}s  "
              f"rounds {e['round_seconds']:5.2f}s  "
              f"peak {e['peak_client_tensor_bytes'] / 1e6:6.2f} MB  "
              f"(dense {e['dense_client_tensor_bytes'] / 1e6:6.2f} MB)")
    ident = section["identity"]
    print(f"identity config (shards=1, f=1.0) routes to the flat engine "
          f"bit-identically: {ident['bit_identical']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
