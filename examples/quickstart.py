"""Quickstart: CodedFedL end-to-end in ~30 seconds on CPU.

Builds a small federated deployment (10 clients over a simulated wireless
MEC network) and runs every registered straggler-mitigation scheme through
the declarative experiment API: one frozen `ExperimentSpec` per scheme,
`repro.api.build_experiment(spec, xs, ys)` for the runnable deployment.
Prints the headline comparison (per-iteration accuracy parity + wall-clock
speedup), demonstrates the kill/resume round-trip of the block-structured
runtime (save a RunState checkpoint mid-run, rebuild the experiment from
scratch, resume — bit-identical result), then finishes with a
multi-realization run (8 independent delay draws, one vmapped call)
showing the wall-clock confidence band.

    PYTHONPATH=src python examples/quickstart.py
"""
import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.api import ExperimentSpec, build_experiment
from repro.config import FLConfig, RFFConfig, TrainConfig
from repro.core import rff
from repro.core.delay_model import mec_network
from repro.data import sharding, synthetic


def main():
    fl = FLConfig(n_clients=10, delta=0.2, psi=0.2)
    ds = synthetic.synthetic_classification(m_train=2000, m_test=500, d=64)

    # 1. distributed kernel embedding (shared-seed RFF, paper §III-A)
    rcfg = RFFConfig(q=256, sigma=2.0)
    omega, delta = rff.rff_params(rcfg, d=64)
    xh_tr = np.asarray(rff.rff_transform(jnp.asarray(ds.x_train), omega, delta))
    xh_te = np.asarray(rff.rff_transform(jnp.asarray(ds.x_test), omega, delta))

    # 2. non-IID partition over the simulated MEC network (paper §V-A)
    nodes = mec_network(fl, d_scalars_per_point=rcfg.q * ds.n_classes)
    shards = sharding.sort_and_shard(xh_tr, ds.y_train, fl.n_clients)
    per_client = sharding.assign_shards_by_speed(shards, nodes, minibatch=200)
    xs = np.stack([c[0] for c in per_client])
    ys = np.stack([ds.one_hot(c[1]) for c in per_client])

    tcfg = TrainConfig(learning_rate=rff.suggest_lr(xh_tr))

    def eval_fn(theta):
        acc = ((xh_te @ np.asarray(theta)).argmax(1) == ds.y_test).mean()
        return 0.0, float(acc)

    # 3. one frozen spec per scheme (the declarative experiment API); the
    # base spec is JSON-serializable — log it next to the results
    base_spec = ExperimentSpec(fl=fl, train=tcfg, rff=rcfg)
    print(f"base spec: {base_spec.to_dict()}\n")
    print(f"{'scheme':14s} {'accuracy':>9s} {'wall-clock':>11s}"
          f" {'deadline':>9s} {'eps(bits)':>10s}")
    base_wall = None
    for scheme in ("naive", "greedy", "ideal", "coded", "partial_coded"):
        spec = dataclasses.replace(base_spec, scheme=scheme)
        res = build_experiment(spec, xs, ys).run(100, eval_fn=eval_fn,
                                                 eval_every=25)
        h = res.history[-1]
        if scheme == "naive":
            base_wall = h.wall_clock
        speed = f"({base_wall / h.wall_clock:.1f}x)" if scheme != "naive" else ""
        t_star = f"{res.t_star:.2f}s" if res.t_star else "-"
        eps = f"{res.privacy_eps:.2f}" if res.privacy_eps else "-"
        print(f"{scheme:14s} {h.accuracy:9.3f} {h.wall_clock:9.0f}s "
              f"{speed:>6s} {t_star:>9s} {eps:>10s}")

    # 4. kill/resume round-trip: checkpoint_every=25 makes the run a chain
    # of 4 blocks, each saving a RunState checkpoint; we "kill" after one
    # block and resume in a FRESH experiment — the final model is
    # bit-identical to the uninterrupted run
    import tempfile
    ckpt_spec = dataclasses.replace(base_spec, scheme="coded",
                                    checkpoint_every=25)
    control = build_experiment(ckpt_spec, xs, ys).run(100)
    ckpt_dir = tempfile.mkdtemp(prefix="quickstart_ckpt_")
    interrupted = build_experiment(ckpt_spec, xs, ys)
    state = interrupted.run_block(interrupted.init_state(100))  # 25 rounds
    interrupted.save_state(f"{ckpt_dir}/ckpt_{state.rounds_done:06d}.npz",
                           state)
    del interrupted, state                                      # the kill
    resumed = build_experiment(ckpt_spec, xs, ys).run(
        100, checkpoint_dir=ckpt_dir, resume=True)
    identical = bool(np.array_equal(np.asarray(control.theta),
                                    np.asarray(resumed.theta)))
    print(f"\nkill at round 25 -> resume from {ckpt_dir}: "
          f"bit-identical = {identical}")

    # 5. confidence bands: 8 independent delay realizations, one vmapped call
    print("\nwall-clock over 8 delay realizations (mean ± std, final round):")
    for scheme in ("naive", "coded"):
        exp = build_experiment(dataclasses.replace(base_spec, scheme=scheme),
                               xs, ys)
        mean, std = exp.run_multi(100, 8).wall_clock_bands()
        print(f"  {scheme:6s} {mean[-1]:8.0f}s ± {std[-1]:.1f}s")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
