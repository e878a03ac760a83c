"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each kernel's test sweeps shapes and
dtypes and asserts allclose against the function of the same name here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def embed_precision(dtype):
    """Precision of the RFF argument x @ omega, here and in both embedding
    kernels.  cos turns the argument's absolute error into feature error,
    and at a TPU's default precision f32 operands are rounded to bf16: at
    the paper's widths that moved the trained model by ~1% against a
    float64 embedding.  f32 operands are contracted at full f32 (the CPU
    does so anyway); bf16 operands have nothing more to keep."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def rff_embed(x, omega, delta):
    """Random Fourier feature map (paper eq. 18).

    x: (m, d), omega: (d, q), delta: (q,) -> (m, q)
      phi(x) = sqrt(2/q) * cos(x @ omega + delta)
    """
    q = omega.shape[1]
    arg = jnp.dot(x, omega, precision=embed_precision(x.dtype))
    return jnp.sqrt(2.0 / q) * jnp.cos(arg + delta[None, :])


def linreg_grad(x, theta, y):
    """Unnormalized squared-loss linear-regression gradient (paper eq. 7/10).

    x: (m, q), theta: (q, c), y: (m, c) -> (q, c)
      g = x^T (x @ theta - y)
    Callers divide by the load l (or u) themselves.
    """
    return x.T @ (x @ theta - y)


def linreg_grad_masked(x, theta, y, mask):
    """Row-masked gradient (batched-engine form of eq. 7/10).

    x: (l, q), theta: (q, c), y: (l, c), mask: (l,) per-row weights ->
      g = x^T diag(mask) (x @ theta - y)
    Rows with mask 0 contribute exactly zero, so callers may hand over
    mask-padded dense subsets without pre-zeroing the padding; fractional
    entries scale a row's gradient (the fused coded round's 1/u factor).
    """
    r = (x @ theta - y) * mask[:, None].astype(x.dtype)
    return x.T @ r


def parity_encode(g, w, x):
    """Local parity dataset encoding (paper eq. 19).

    g: (u, l) generator, w: (l,) diagonal weights, x: (l, q) data -> (u, q)
      parity = G @ diag(w) @ X
    """
    return (g * w[None, :]) @ x


def gqa_decode(q, k, v, k_pos, q_pos, window: int = 0):
    """One-token GQA decode attention oracle.

    q: (B, H, hd); k/v: (B, T, K, hd/hd_v); k_pos: (T,); q_pos: ().
    """
    import numpy as np
    B, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qr = q.reshape(B, K, G, hd).astype(jnp.float32) / np.sqrt(hd)
    s = jnp.einsum("bkgd,btkd->bkgt", qr, k.astype(jnp.float32))
    valid = (k_pos >= 0) & (k_pos <= q_pos)
    if window > 0:
        valid = valid & (k_pos > q_pos - window)
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(B, H, v.shape[-1]).astype(q.dtype)
