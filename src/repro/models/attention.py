"""GQA attention: full / sliding-window, train+prefill+decode, flash-style.

The seq x seq score matrix is never materialized: we lax.scan over KV chunks
with a running (max, denom, acc) online softmax — the TPU-native equivalent
of flash attention, expressed in XLA ops so the multi-pod dry-run lowers
without a custom kernel.  Sliding-window attention uses a rolling cache of
`window` slots for decode (sub-quadratic long-context path).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import apply_rope, dense_init, rms_norm

NEG_INF = -1e30


def init_attn(key, cfg, dtype):
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, H, hd), dtype),
        "wk": dense_init(ks[1], (D, K, hd), dtype),
        "wv": dense_init(ks[2], (D, K, hd), dtype),
        "wo": dense_init(ks[3], (H, hd, D), dtype),
    }
    if cfg.qk_norm:
        p["qn"] = jnp.ones((hd,), dtype)
        p["kn"] = jnp.ones((hd,), dtype)
    return p


def _project_q(p, x, positions, cfg):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qk_norm:
        q = rms_norm(q, p["qn"], cfg.norm_eps)
    return apply_rope(q, positions, cfg.rope_theta)


def _project_kv(p, x, positions, cfg, rope: bool = True):
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        k = rms_norm(k, p["kn"], cfg.norm_eps)
    if rope:
        k = apply_rope(k, positions, cfg.rope_theta)
    return k, v


def _attend_single(q, k, v, q_pos, k_pos, window: int, causal: bool = True,
                   scale: float | None = None):
    """One-shot attention for q_len == 1 (decode).

    §Perf: the kv-chunk lax.scan forces XLA to materialize (all-gather) a
    seq-sharded KV cache chunk by chunk — 8.7 GB/device/token on the 104B
    decode dry-run.  Written as a single einsum + masked softmax over the
    (sharded) cache length, the partitioner instead replicates the 2 MB
    query, keeps every cache shard local, and all-reduces the small
    softmax partials (EXPERIMENTS.md §Perf iteration 2).
    """
    B, S, H, hd = q.shape
    K = k.shape[2]
    hd_v = v.shape[-1]
    G = H // K
    if scale is None:
        scale = 1.0 / np.sqrt(hd)
    qr = q.reshape(B, S, K, G, hd).astype(jnp.float32) * scale
    s = jnp.einsum("bskgd,btkd->bskgt", qr, k.astype(jnp.float32))
    valid = jnp.broadcast_to(k_pos[None, :] >= 0, (S, k_pos.shape[0]))
    if causal:
        valid = valid & (k_pos[None, :] <= q_pos[:, None])
    if window > 0:
        valid = valid & (k_pos[None, :] > q_pos[:, None] - window)
    s = jnp.where(valid[None, :, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bskgt,btkd->bskgd", p / jnp.maximum(l, 1e-30),
                     v.astype(jnp.float32))
    return out.reshape(B, S, H, hd_v).astype(q.dtype)


def _flash(q, k, v, q_pos, k_pos, window: int, chunk: int = 512,
           causal: bool = True, scale: float | None = None,
           remat_chunks: bool = False):
    """Online-softmax attention.

    q: (B, S, H, hd); k, v: (B, T, K, hd); *_pos: (S,), (T,) global positions
    (k_pos may contain -1 for invalid rolling-cache slots).  `scale` is
    the softmax scale (default hd^-0.5).  With `remat_chunks` the
    backward pass keeps each chunk's carry and recomputes that chunk's
    probabilities, never all chunks' (B, S, H, chunk) at once.
    Returns (B, S, H, hd).
    """
    B, S, H, hd = q.shape
    if S == 1:                          # decode: one-shot path (see above)
        return _attend_single(q, k, v, q_pos, k_pos, window, causal, scale)
    T, K = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]                  # may differ from hd (MLA)
    G = H // K
    if scale is None:
        scale = 1.0 / np.sqrt(hd)
    qr = q.reshape(B, S, K, G, hd).astype(jnp.float32) * scale
    chunk = min(chunk, T)
    n_chunks = T // chunk if T % chunk == 0 else -(-T // chunk)
    pad = n_chunks * chunk - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=-1)
    kc = k.reshape(B, n_chunks, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, K, hd_v).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(n_chunks, chunk)

    def step(carry, xs):
        m, l, acc = carry
        k_i, v_i, p_i = xs                     # (B, C, K, hd), (C,)
        s = jnp.einsum("bskgd,bckd->bskgc", qr, k_i.astype(jnp.float32))
        valid = jnp.broadcast_to(p_i[None, :] >= 0, (S, p_i.shape[0]))
        if causal:
            valid = valid & (p_i[None, :] <= q_pos[:, None])
        if window > 0:
            valid = valid & (p_i[None, :] > q_pos[:, None] - window)
        # valid: (S, C) -> broadcast over (B, S, K, G, C)
        s = jnp.where(valid[None, :, None, None, :], s, NEG_INF)
        m_i = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_i[..., None])
        corr = jnp.exp(m - m_i)
        l_i = l * corr + jnp.sum(p, axis=-1)
        acc_i = acc * corr[..., None] + jnp.einsum(
            "bskgc,bckd->bskgd", p, v_i.astype(jnp.float32))
        return (m_i, l_i, acc_i), None

    m0 = jnp.full((B, S, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, K, G), jnp.float32)
    a0 = jnp.zeros((B, S, K, G, hd_v), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step) if remat_chunks else step, (m0, l0, a0),
        (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, S, H, hd_v).astype(q.dtype)


def attn_train(p, x, positions, cfg, window: int = 0):
    """Full-sequence causal attention.  x: (B, S, D), positions: (S,)."""
    q = _project_q(p, x, positions[None, :], cfg)
    k, v = _project_kv(p, x, positions[None, :], cfg)
    win = window if window else cfg.swa_window
    # §Perf: flash-style backward — recompute the kv loop instead of saving
    # the per-chunk online-softmax carries (EXPERIMENTS.md §Perf iter 1b).
    flash = jax.checkpoint(
        lambda q_, k_, v_: _flash(q_, k_, v_, positions, positions, win))
    out = flash(q, k, v)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def init_cache(cfg, batch: int, max_seq: int, dtype, window: int = 0):
    """KV cache; rolling when window>0 (sub-quadratic decode)."""
    slots = min(max_seq, window) if window > 0 else max_seq
    K, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, slots, K, hd), dtype),
        "v": jnp.zeros((batch, slots, K, hd), dtype),
        "pos": jnp.full((slots,), -1, jnp.int32),
    }


def attn_prefill(p, x, positions, cfg, cache, window: int = 0):
    """Full forward over the prompt; fills the cache. Returns (out, cache)."""
    q = _project_q(p, x, positions[None, :], cfg)
    k, v = _project_kv(p, x, positions[None, :], cfg)
    win = window if window else cfg.swa_window
    out = _flash(q, k, v, positions, positions, win)
    S = x.shape[1]
    slots = cache["k"].shape[1]
    if slots >= S:
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0))
        cp = jax.lax.dynamic_update_slice(
            cache["pos"], positions.astype(jnp.int32), (0,))
    else:                                     # rolling window: keep the tail
        ck = k[:, S - slots:]
        cv = v[:, S - slots:]
        cp = positions[S - slots:].astype(jnp.int32)
    new_cache = {"k": ck, "v": cv, "pos": cp}
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache


def attn_decode(p, x, pos, cfg, cache, window: int = 0):
    """One-token step.  x: (B, 1, D); pos: scalar int32 position."""
    positions = jnp.full((1, 1), pos, jnp.int32)
    q = _project_q(p, x, positions, cfg)
    k, v = _project_kv(p, x, positions, cfg)
    slots = cache["k"].shape[1]
    win = window if window else cfg.swa_window
    slot = jnp.where(win > 0, pos % slots, jnp.minimum(pos, slots - 1))
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    cp = jax.lax.dynamic_update_slice(
        cache["pos"], jnp.full((1,), pos, jnp.int32), (slot,))
    out = _flash(q, ck, cv, jnp.full((1,), pos, jnp.int32), cp, win)
    new_cache = {"k": ck, "v": cv, "pos": cp}
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache
