"""Client-mesh sharding + compiled profile sweep via the experiment API.

1. Builds one frozen `ExperimentSpec` (scheme, delay profile, mesh, all
   declarative) and runs CodedFedL with its client axis sharded over every
   available device: per-shard gradients are computed locally and
   psum-aggregated, mirroring the MEC server reduction of paper §III.
2. Sweeps EVERY registered scheme over the heterogeneity profile grid in
   ONE compiled call per scheme (`Experiment.sweep` — the
   `repro.launch.sweep.run_sweep` engine replaying the same spec).

Fake a multi-device host before running (must be set before jax starts):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/mesh_sweep.py
"""
import numpy as np
import jax

from repro.api import ExperimentSpec, build_experiment, grid_names
from repro.config import FLConfig, TrainConfig
from repro.core.delay_model import HETEROGENEITY_PROFILES
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

N, L, Q, C = 12, 32, 64, 5
ITERS, REALIZATIONS = 30, 4

rng = np.random.default_rng(0)
xs = rng.normal(size=(N, L, Q)).astype(np.float32) * 0.2
ys = rng.normal(size=(N, L, C)).astype(np.float32)

# --- 1. sharded single deployment: everything in one frozen spec ----------
ndev = jax.device_count()
spec = ExperimentSpec(
    fl=FLConfig(n_clients=N, delta=0.2, psi=0.2, seed=0),
    train=TrainConfig(learning_rate=0.5, l2_reg=1e-5, lr_decay_epochs=(15,)),
    scheme="coded",
    mesh=ndev,
)
print(f"[mesh] sharding {N} clients over {ndev} device(s); "
      f"spec round-trips JSON: "
      f"{ExperimentSpec.from_dict(spec.to_dict()) == spec}")
exp = build_experiment(spec, xs, ys)
res = exp.run(ITERS)
print(f"[mesh] coded: t*={res.t_star:.3f}s  "
      f"finished {ITERS} rounds at {res.history[-1].wall_clock:.1f} "
      f"simulated seconds")

# --- 2. compiled (profile x realization) sweep over the registry ----------
print(f"[sweep] {len(HETEROGENEITY_PROFILES)} profiles x "
      f"{REALIZATIONS} realizations x schemes {grid_names()}, "
      f"one compiled call per scheme")
unsharded = build_experiment(ExperimentSpec(
    fl=spec.fl, train=spec.train, scheme="coded"), xs, ys)
sw = unsharded.sweep(profiles=HETEROGENEITY_PROFILES, iterations=ITERS,
                     realizations=REALIZATIONS, schemes=grid_names())
for scheme, per_profile in sw.results.items():
    print(f"[sweep] {scheme}: compiled grid call took "
          f"{sw.host_seconds[scheme]:.2f}s host-side")
    for pname, multi in per_profile.items():
        mean, std = multi.wall_clock_bands()
        print(f"    {pname:>10}: {mean[-1]:8.1f} ± {std[-1]:5.1f} "
              f"simulated s")
