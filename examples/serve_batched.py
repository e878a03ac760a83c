"""Batched serving example: prefill + decode loop across architectures.

Exercises the same serve_step the decode dry-run shapes lower — full KV
cache for dense archs, rolling window for SWA, latent cache for MLA,
recurrent state for RWKV6 — at reduced config on CPU.

    PYTHONPATH=src python examples/serve_batched.py [--arch rwkv6-1.6b]
"""
import argparse

from repro.configs import ARCH_IDS, get_config, smoke_variant
from repro.launch.serve import serve


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    args = ap.parse_args()
    archs = [args.arch] if args.arch else ["qwen3-4b", "mixtral-8x7b",
                                           "rwkv6-1.6b",
                                           "deepseek-v2-lite-16b"]
    for arch in archs:
        print(f"--- {arch}")
        cfg = smoke_variant(get_config(arch))
        serve(cfg, batch=args.batch, prompt_len=args.prompt_len,
              gen_len=args.gen_len)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
