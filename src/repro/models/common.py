"""Shared model building blocks (functional, explicit param pytrees)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(cfg):
    return jnp.dtype(cfg.dtype)


# ----------------------------------------------------------------- init utils
def dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    if len(shape) == 3:            # (D, H, hd) style
        fan_in = shape[0]
    s = scale if scale is not None else 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ----------------------------------------------------------------- norms
def rms_norm(x, weight, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight + bias).astype(x.dtype)


# ----------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention-temperature factor 0.1 mscale ln(scale) + 1."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_freqs(head_dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN inverse frequencies as DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding`` makes them: interpolated (divided by
    ``yarn.factor``) below the correction dim of ``beta_slow`` rotations,
    original above that of ``beta_fast``, and a linear ramp between, over
    ``original_max_position_embeddings`` positions."""
    def corr_dim(rotations):
        return (head_dim * math.log(yarn.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    extra = rope_freqs(head_dim, theta)
    keep = 1.0 - ramp                       # weight of the original freqs
    return (extra / yarn.factor * (1.0 - keep) + extra * keep) \
        .astype(np.float32)


def apply_rope(x, positions, theta: float, yarn=None):
    """x: (..., S, H, hd) or (..., S, hd); positions: (..., S) int.  With
    `yarn` (a ``YarnConfig``) the frequencies are YaRN's and cos/sin are
    scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta) if yarn is None
                        else yarn_freqs(hd, theta, yarn))   # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    if x.ndim == ang.ndim + 1:                              # head axis present
        ang = ang[..., None, :]                             # (..., S, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        amp = (yarn_mscale(yarn.factor, yarn.mscale)
               / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if amp != 1.0:
            cos, sin = cos * amp, sin * amp
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d: int):
    pos = np.arange(seq)[:, None]
    i = np.arange(d // 2)[None, :]
    ang = pos / np.power(10000.0, 2.0 * i / d)
    out = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(out, jnp.float32)


# ----------------------------------------------------------------- losses
def softmax_cross_entropy(logits, labels, mask=None):
    """logits: (..., V) any float dtype; labels int (...).

    The label log-prob is extracted with an iota==label masked sum instead
    of take_along_axis: a vocab-dim gather on vocab-SHARDED logits makes
    XLA's partitioner replicate the full-batch f32 logits (2x37 GB of
    collectives measured on internvl2 train_4k), while the masked sum stays
    local per shard and psums only the (B, S) partials.
    """
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    picked = jnp.where(ids == labels[..., None], logits, 0.0)
    ll = jnp.sum(picked, axis=-1)
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def swiglu(x, w1, w3, w2):
    """SwiGLU FFN: (.., D) @ (D,F) gates -> (.., D)."""
    h = jax.nn.silu(x @ w1) * (x @ w3)
    return h @ w2
