"""End-to-end driver: federated training of a transformer LM with the
paper's deadline-based aggregation (DESIGN.md §4 generalization).

Each simulated MEC client owns a data shard; per round, client round-trip
delays are sampled from the paper's §II-B models, the load allocator picks
the deadline t*, stragglers are dropped, and surviving gradients are
reweighted by 1/P(T_j <= t*) so the aggregate stays unbiased.

Default is a ~20M-param qwen3-family model for a quick CPU run; use
--params 100m --steps 300 for the full deliverable-scale run.

    PYTHONPATH=src python examples/train_llm_federated.py
    PYTHONPATH=src python examples/train_llm_federated.py --params 100m --steps 300
"""
import argparse
import dataclasses
import time

from repro.config import FLConfig
from repro.configs import get_config, smoke_variant
from repro.launch.train import train


def model_cfg(size: str):
    base = smoke_variant(get_config("qwen3-4b"))
    if size == "20m":
        return dataclasses.replace(base, n_layers=4, d_model=256, d_ff=1024,
                                   vocab=8192, n_heads=4, n_kv_heads=2)
    if size == "100m":
        return dataclasses.replace(base, n_layers=8, d_model=512, d_ff=2048,
                                   vocab=32768, n_heads=8, n_kv_heads=4)
    raise ValueError(size)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--params", default="20m", choices=["20m", "100m"])
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()
    cfg = model_cfg(args.params)
    n_param_est = (cfg.n_layers * (4 * cfg.d_model * cfg.d_ff
                                   + 4 * cfg.d_model * cfg.d_model)
                   + 2 * cfg.vocab * cfg.d_model)
    print(f"arch=qwen3-family ~{n_param_est / 1e6:.0f}M params, "
          f"{args.clients} federated clients, deadline aggregation")
    t0 = time.time()
    _, losses, sim_wall = train(
        cfg, steps=args.steps, batch=args.batch, seq=args.seq,
        federated=True, fl_cfg=FLConfig(n_clients=args.clients),
        log_every=max(1, args.steps // 10))
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} over {args.steps} steps "
          f"({time.time() - t0:.0f}s real, {sim_wall:.0f}s simulated "
          f"MEC wall-clock)")
    assert losses[-1] < losses[0], "training must reduce loss"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
