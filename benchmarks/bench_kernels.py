"""Benchmark: per-kernel timings (jnp reference path on CPU; the Pallas
kernels are validated in interpret mode — wall time there is not meaningful
for the TPU target, so the jit'd jnp path is what's timed)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref


def _time(f, *args, iters=5):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else \
        jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def run(m=12000, d=784, q=2000, c=10, u=1200):
    rng = np.random.default_rng(0)
    x_raw = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    omega = jnp.asarray(rng.normal(size=(d, q)), jnp.float32)
    delta = jnp.asarray(rng.uniform(0, 6.28, size=(q,)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(m, q)) * 0.03, jnp.float32)
    theta = jnp.zeros((q, c), jnp.float32)
    y = jnp.asarray(rng.normal(size=(m, c)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(u, m)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 1, size=(m,)), jnp.float32)

    rff = jax.jit(ref.rff_embed)
    grad = jax.jit(ref.linreg_grad)
    par = jax.jit(ref.parity_encode)
    rows = [
        ("kernel_rff_embed_12kx784x2000", _time(rff, x_raw, omega, delta),
         f"flops={2 * m * d * q:.2e}"),
        ("kernel_linreg_grad_12kx2000x10", _time(grad, x, theta, y),
         f"flops={4 * m * q * c:.2e}"),
        ("kernel_parity_encode_1200x12k", _time(par, g, w, x),
         f"flops={2 * u * m * q:.2e}"),
    ]
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(",".join(str(x) for x in r))
