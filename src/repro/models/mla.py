"""DeepSeek-V2 Multi-head Latent Attention (MLA).

The KV cache stores only the compressed latent c_kv (rank `kv_lora_rank`)
plus the shared RoPE key — ~10x smaller than a GQA cache.  With
``cfg.yarn`` the rope dims take YaRN's frequencies and the softmax scale
grows by mscale(factor, mscale_all_dim)^2, as DeepSeek-V2 does.  RoPE
rotates split halves of the rope dims, where DeepSeek-V2 first
de-interleaves pairs: a fixed permutation of the rope columns of
``wq`` and ``w_dkv``.  The baseline
decode path decompresses K/V from the latent each step (matches the paper's
formulation); absorbing W_uk into the query is a §Perf optimization measured
in EXPERIMENTS.md.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import apply_rope, dense_init, rms_norm, yarn_mscale
from repro.models.attention import _flash, _flash_causal


def softmax_scale(cfg) -> float:
    """(qk_nope + qk_rope)^-0.5, times mscale(factor, mscale_all_dim)^2
    under YaRN."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = cfg.yarn
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def init_mla(key, cfg, dtype):
    D, H = cfg.d_model, cfg.n_heads
    m = cfg.mla
    ks = jax.random.split(key, 5)
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq": dense_init(ks[0], (D, H, qk), dtype),
        "w_dkv": dense_init(ks[1], (D, m.kv_lora_rank + m.qk_rope_head_dim), dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "w_uk": dense_init(ks[2], (m.kv_lora_rank, H, m.qk_nope_head_dim), dtype),
        "w_uv": dense_init(ks[3], (m.kv_lora_rank, H, m.v_head_dim), dtype),
        "wo": dense_init(ks[4], (H, m.v_head_dim, D), dtype),
    }


def _q_proj(p, x, positions, cfg):
    m = cfg.mla
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_pe = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta, cfg.yarn)
    return jnp.concatenate([q_nope, q_pe], axis=-1)


def _latent(p, x, positions, cfg):
    m = cfg.mla
    ckv = x @ p["w_dkv"]                                    # (B,S,lora+rope)
    c, k_pe = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    c = rms_norm(c, p["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope(k_pe, positions, cfg.rope_theta, cfg.yarn)  # (B,S,rope)
    return c, k_pe


def _decompress(p, c, k_pe, cfg):
    """latent -> per-head K (nope+rope) and V."""
    H = cfg.n_heads
    k_nope = jnp.einsum("bsl,lhk->bshk", c, p["w_uk"])
    v = jnp.einsum("bsl,lhk->bshk", c, p["w_uv"])
    k_pe_b = jnp.broadcast_to(k_pe[:, :, None, :],
                              k_nope.shape[:3] + (k_pe.shape[-1],))
    k = jnp.concatenate([k_nope, k_pe_b], axis=-1)
    return k, v


def mla_train(p, x, positions, cfg, window: int = 0):
    with jax.named_scope("mla"):
        q = _q_proj(p, x, positions[None, :], cfg)
        c, k_pe = _latent(p, x, positions[None, :], cfg)
        k, v = _decompress(p, c, k_pe, cfg)
        win = window if window else cfg.swa_window
        # the backward pass recomputes one kv chunk at a time
        out = _flash_causal(q, k, v, positions, win,
                            scale=softmax_scale(cfg), remat_chunks=True)
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"])    # kv heads == H


def init_mla_cache(cfg, batch: int, max_seq: int, dtype, window: int = 0):
    m = cfg.mla
    slots = min(max_seq, window) if window > 0 else max_seq
    return {"c": jnp.zeros((batch, slots, m.kv_lora_rank), dtype),
            "kpe": jnp.zeros((batch, slots, m.qk_rope_head_dim), dtype),
            "pos": jnp.full((slots,), -1, jnp.int32)}


def mla_prefill(p, x, positions, cfg, cache, window: int = 0):
    q = _q_proj(p, x, positions[None, :], cfg)
    c, k_pe = _latent(p, x, positions[None, :], cfg)
    k, v = _decompress(p, c, k_pe, cfg)
    win = window if window else cfg.swa_window
    out = _flash_causal(q, k, v, positions, win, scale=softmax_scale(cfg))
    S = x.shape[1]
    slots = cache["c"].shape[1]
    if slots >= S:
        cc = jax.lax.dynamic_update_slice(cache["c"], c, (0, 0, 0))
        ck = jax.lax.dynamic_update_slice(cache["kpe"], k_pe, (0, 0, 0))
        cp = jax.lax.dynamic_update_slice(
            cache["pos"], positions.astype(jnp.int32), (0,))
    else:
        cc, ck = c[:, S - slots:], k_pe[:, S - slots:]
        cp = positions[S - slots:].astype(jnp.int32)
    return (jnp.einsum("bshk,hkd->bsd", out, p["wo"]),
            {"c": cc, "kpe": ck, "pos": cp})


def mla_decode(p, x, pos, cfg, cache, window: int = 0):
    positions = jnp.full((1, 1), pos, jnp.int32)
    q = _q_proj(p, x, positions, cfg)
    c, k_pe = _latent(p, x, positions, cfg)
    slots = cache["c"].shape[1]
    win = window if window else cfg.swa_window
    slot = jnp.where(win > 0, pos % slots, jnp.minimum(pos, slots - 1))
    cc = jax.lax.dynamic_update_slice(cache["c"], c, (0, slot, 0))
    ck = jax.lax.dynamic_update_slice(cache["kpe"], k_pe, (0, slot, 0))
    cp = jax.lax.dynamic_update_slice(
        cache["pos"], jnp.full((1,), pos, jnp.int32), (slot,))
    k, v = _decompress(p, cc, ck, cfg)                      # baseline path
    out = _flash(q, k, v, jnp.full((1,), pos, jnp.int32), cp, win,
                 scale=softmax_scale(cfg))
    return (jnp.einsum("bshk,hkd->bsd", out, p["wo"]),
            {"c": cc, "kpe": ck, "pos": cp})
