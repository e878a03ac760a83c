"""Training driver.

Two modes:
  * plain      — single-host (reduced-config) LM training on synthetic
                 tokens; used by smoke tests and examples.
  * federated  — CodedFedL-style deadline aggregation generalized to deep
                 models, through the system's normal path:
                 ``repro.api.build_experiment`` with a model spec
                 (``ExperimentSpec.model``).  Each client is a simulated MEC
                 client with the paper's delay model holding one token
                 sequence; the coded scheme's allocation sets the deadline
                 t* and each client's load (a prefix of its sequence);
                 gradients that miss t* are dropped and the survivors are
                 reweighted by 1/P(T_j <= t*) inside the compiled round
                 (`repro.core.lm_round`; parity coding itself is
                 linear-model-only, paper Sec. III).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ExperimentSpec, FLConfig, LMSpec, TrainConfig
from repro.configs import ARCH_IDS, get_config, smoke_variant
from repro.data.pipeline import PackedLMDataset, PipelineConfig
from repro.models.model_zoo import build
from repro.optim import optimizers
from repro.optim.schedule import cosine


def make_batch(cfg, batch: int, seq: int, seed: int, shard_id: int = 0):
    """Training batch from the packed-LM pipeline (+ modality stubs)."""
    rng = np.random.default_rng(seed)
    out = {}
    ntok = seq
    if cfg.is_encdec:
        out["frames"] = jnp.asarray(
            rng.normal(size=(batch, cfg.encoder_seq, cfg.d_model)),
            jnp.dtype(cfg.dtype))
    elif cfg.n_prefix_patches:
        out["patch_embeds"] = jnp.asarray(
            rng.normal(size=(batch, cfg.n_prefix_patches, cfg.d_model)),
            jnp.dtype(cfg.dtype))
        ntok = seq - cfg.n_prefix_patches
    ds = PackedLMDataset(PipelineConfig(
        vocab=cfg.vocab, seq_len=ntok, batch=batch, seed=seed * 1000003,
        n_shards=max(shard_id + 1, 1), shard_id=shard_id))
    b = ds.batch_at(0)
    out["tokens"] = jnp.asarray(b["tokens"])
    out["labels"] = jnp.asarray(b["labels"])
    return out


def train(cfg, steps: int = 20, batch: int = 4, seq: int = 64,
          lr: float = 3e-3, optimizer: str = "adam", *,
          federated: bool = False, fl_cfg: FLConfig | None = None,
          log_every: int = 5, seed: int = 0):
    """Returns (params, losses, wall_clock_sim).  Federated runs hold one
    sequence of `seq` targets per client (`batch` is not used) and train
    with AdamW at a constant `lr`."""
    if federated:
        return train_federated(cfg, steps, seq, lr, fl_cfg or FLConfig(
            n_clients=8), log_every=log_every, seed=seed)
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    opt_init, opt_update = optimizers.get(optimizer)
    opt_state = opt_init(params)
    lr_fn = cosine(lr, steps, warmup=min(10, steps // 10))

    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, b: model.loss_fn(p, b, remat=False)))

    losses = []
    for step in range(steps):
        b = make_batch(cfg, batch, seq, seed + step)
        loss, grads = grad_fn(params, b)
        params, opt_state = opt_update(params, grads, opt_state, lr_fn(step))
        losses.append(float(loss))
        if log_every and step % log_every == 0:
            print(f"step {step:4d} loss {float(loss):.4f}")
    return params, losses, 0.0


def train_federated(cfg, steps: int, seq: int, lr: float, fl: FLConfig, *,
                    log_every: int = 5, seed: int = 0):
    """`steps` coded-deadline rounds of `cfg` over ``fl.n_clients``
    clients, each holding one packed sequence of `seq` next-token targets,
    through ``build_experiment``.  Returns (params, per-round losses,
    simulated MEC wall-clock)."""
    from repro.api import build_experiment
    tokens = np.concatenate([
        make_batch(cfg, 1, seq + 1, seed, shard_id=j)["tokens"]
        for j in range(fl.n_clients)])
    spec = ExperimentSpec(
        fl=fl, scheme="coded",
        train=TrainConfig(optimizer="adamw", learning_rate=lr,
                          lr_decay_epochs=()),
        model=LMSpec(model=cfg, init_seed=seed))
    res = build_experiment(spec, tokens).run(steps)
    losses = [h.loss for h in res.history]
    if log_every:
        for h in res.history[::log_every]:
            print(f"step {h.iteration:4d} loss {h.loss:.4f} "
                  f"returned {h.returned}/{fl.n_clients}")
    return res.theta["params"], losses, res.history[-1].wall_clock


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b", choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--federated", action="store_true")
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (non-smoke) config — not for CPU")
    args = ap.parse_args()
    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = smoke_variant(cfg)
    t0 = time.time()
    _, losses, sim_wall = train(cfg, steps=args.steps, batch=args.batch,
                                seq=args.seq, federated=args.federated)
    print(f"final loss {losses[-1]:.4f}  ({time.time() - t0:.1f}s"
          + (f", simulated FL wall-clock {sim_wall:.1f}s" if args.federated
               else "") + ")")


if __name__ == "__main__":
    main()
