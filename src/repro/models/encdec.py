"""Whisper-style encoder-decoder transformer (audio carve-out: the conv/mel
frontend is a stub — `frames` are precomputed frame embeddings (B, T_enc, D)).

Encoder: bidirectional self-attention, GELU FFN, sinusoidal positions.
Decoder: causal self-attention (+ optional sliding window for the long-
context variant) and cross-attention to the encoder output; the decode cache
holds the rolling self-attn KV plus the cross-attn KV computed once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import (dense_init, dtype_of, embed_init,
                                 rms_norm, sinusoidal_positions,
                                 softmax_cross_entropy)
from repro.models.attention import _flash, _flash_causal


def _init_qkvo(key, cfg, dtype):
    D, H, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {"wq": dense_init(ks[0], (D, H, hd), dtype),
            "wk": dense_init(ks[1], (D, H, hd), dtype),
            "wv": dense_init(ks[2], (D, H, hd), dtype),
            "wo": dense_init(ks[3], (H, hd, D), dtype)}


def _qkv(p, x):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    return q, k, v


def _init_ffn(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {"w1": dense_init(k1, (cfg.d_model, cfg.d_ff), dtype),
            "w2": dense_init(k2, (cfg.d_ff, cfg.d_model), dtype)}


def _ffn(p, x):
    return jax.nn.gelu(x @ p["w1"]) @ p["w2"]


def init_params(cfg, key):
    dtype = dtype_of(cfg)
    ks = jax.random.split(key, 6)

    def enc_layer(k):
        ka, kf = jax.random.split(k)
        return {"ln1": jnp.ones((cfg.d_model,), dtype),
                "ln2": jnp.ones((cfg.d_model,), dtype),
                "attn": _init_qkvo(ka, cfg, dtype),
                "ffn": _init_ffn(kf, cfg, dtype)}

    def dec_layer(k):
        ka, kc, kf = jax.random.split(k, 3)
        return {"ln1": jnp.ones((cfg.d_model,), dtype),
                "ln_x": jnp.ones((cfg.d_model,), dtype),
                "ln2": jnp.ones((cfg.d_model,), dtype),
                "self": _init_qkvo(ka, cfg, dtype),
                "cross": _init_qkvo(kc, cfg, dtype),
                "ffn": _init_ffn(kf, cfg, dtype)}

    return {
        "embed": embed_init(ks[0], (cfg.vocab, cfg.d_model), dtype),
        "enc_in_proj": dense_init(ks[1], (cfg.d_model, cfg.d_model), dtype),
        "enc_norm": jnp.ones((cfg.d_model,), dtype),
        "dec_norm": jnp.ones((cfg.d_model,), dtype),
        "lm_head": dense_init(ks[2], (cfg.d_model, cfg.vocab), dtype),
        "enc": jax.vmap(enc_layer)(
            jax.random.split(ks[3], cfg.n_encoder_layers)),
        "dec": jax.vmap(dec_layer)(jax.random.split(ks[4], cfg.n_layers)),
    }


def abstract_params(cfg):
    import functools
    return jax.eval_shape(functools.partial(init_params, cfg),
                          jax.random.PRNGKey(0))


def encode(cfg, params, frames):
    """frames: (B, T_enc, D) stub embeddings -> (B, T_enc, D)."""
    B, T, D = frames.shape
    x = frames.astype(dtype_of(cfg)) @ params["enc_in_proj"]
    x = x + sinusoidal_positions(T, D).astype(x.dtype)[None]
    pos = jnp.arange(T, dtype=jnp.int32)

    def body(x, p):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _qkv(p["attn"], h)
        o = _flash(q, k, v, pos, pos, 0, causal=False)
        x = x + jnp.einsum("bshk,hkd->bsd", o, p["attn"]["wo"])
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        return x + _ffn(p["ffn"], h), None

    x, _ = jax.lax.scan(body, x, params["enc"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def _dec_layer(cfg, p, x, enc_out, pos_q, mode, cache=None, window=0):
    """Decoder layer in train/prefill ('full') or decode mode."""
    new_cache = {}
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = _qkv(p["self"], h)
    if mode in ("train", "prefill"):
        o = _flash_causal(q, k, v, pos_q, window)
        if mode == "prefill":
            S = x.shape[1]
            slots = cache["k"].shape[1]
            if slots >= S:
                ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0))
                cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0))
                cp = jax.lax.dynamic_update_slice(
                    cache["pos"], pos_q.astype(jnp.int32), (0,))
            else:
                ck, cv = k[:, S - slots:], v[:, S - slots:]
                cp = pos_q[S - slots:].astype(jnp.int32)
            new_cache.update({"k": ck, "v": cv, "pos": cp})
    else:                                           # decode: single position
        slots = cache["k"].shape[1]
        p_scalar = pos_q
        slot = jnp.where(window > 0, p_scalar % slots,
                         jnp.minimum(p_scalar, slots - 1))
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
        cp = jax.lax.dynamic_update_slice(
            cache["pos"], jnp.full((1,), p_scalar, jnp.int32), (slot,))
        o = _flash(q, ck, cv, jnp.full((1,), p_scalar, jnp.int32), cp, window)
        new_cache.update({"k": ck, "v": cv, "pos": cp})
    x = x + jnp.einsum("bshk,hkd->bsd", o, p["self"]["wo"])

    # cross attention (encoder output fixed; never causal)
    h = rms_norm(x, p["ln_x"], cfg.norm_eps)
    qx = jnp.einsum("bsd,dhk->bshk", h, p["cross"]["wq"])
    if mode == "decode":
        kx, vx = cache["xk"], cache["xv"]
    else:
        kx = jnp.einsum("btd,dhk->bthk", enc_out, p["cross"]["wk"])
        vx = jnp.einsum("btd,dhk->bthk", enc_out, p["cross"]["wv"])
        if mode == "prefill":
            new_cache.update({"xk": kx, "xv": vx})
    t_pos = jnp.arange(kx.shape[1], dtype=jnp.int32)
    q_pos = (jnp.zeros((qx.shape[1],), jnp.int32) if mode != "decode"
             else jnp.zeros((1,), jnp.int32))
    ox = _flash(qx, kx, vx, q_pos, t_pos, 0, causal=False)
    x = x + jnp.einsum("bshk,hkd->bsd", ox, p["cross"]["wo"])
    if mode == "decode":
        new_cache.update({"xk": kx, "xv": vx})

    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    return x + _ffn(p["ffn"], h), new_cache


def loss_fn(cfg, params, batch, window: int = 0, remat: bool = True,
            chunked: bool = True):
    """batch: frames (B,T_enc,D), tokens (B,S), labels (B,S)."""
    enc_out = encode(cfg, params, batch["frames"])
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    S = x.shape[1]
    x = x + sinusoidal_positions(S, cfg.d_model).astype(x.dtype)[None]
    pos = jnp.arange(S, dtype=jnp.int32)

    def body(x, p):
        out, _ = _dec_layer(cfg, p, x, enc_out, pos, "train", window=window)
        return out, None

    body_fn = jax.checkpoint(body) if remat else body
    x, _ = jax.lax.scan(body_fn, x, params["dec"])
    x = rms_norm(x, params["dec_norm"], cfg.norm_eps)
    logits = x @ params["lm_head"]
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    return softmax_cross_entropy(logits, jnp.maximum(labels, 0), mask)


def init_cache(cfg, batch: int, max_seq: int, window: int = 0):
    dtype = dtype_of(cfg)
    slots = min(max_seq, window) if window > 0 else max_seq
    H, hd = cfg.n_heads, cfg.head_dim
    T = cfg.encoder_seq
    one = {"k": jnp.zeros((batch, slots, H, hd), dtype),
           "v": jnp.zeros((batch, slots, H, hd), dtype),
           "pos": jnp.full((slots,), -1, jnp.int32),
           "xk": jnp.zeros((batch, T, H, hd), dtype),
           "xv": jnp.zeros((batch, T, H, hd), dtype)}
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros((cfg.n_layers,) + a.shape, a.dtype)
        if a.dtype != jnp.int32
        else jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), one)


def prefill(cfg, params, batch, window: int = 0, chunked: bool = True):
    enc_out = encode(cfg, params, batch["frames"])
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    B, S = x.shape[0], x.shape[1]
    x = x + sinusoidal_positions(S, cfg.d_model).astype(x.dtype)[None]
    pos = jnp.arange(S, dtype=jnp.int32)
    cache = init_cache(cfg, B, S, window)

    def body(x, xs):
        p, c = xs
        out, nc = _dec_layer(cfg, p, x, enc_out, pos, "prefill", cache=c,
                             window=window)
        return out, nc

    x, cache = jax.lax.scan(body, x, (params["dec"], cache))
    x = rms_norm(x, params["dec_norm"], cfg.norm_eps)
    return x[:, -1, :] @ params["lm_head"], cache


def decode_step(cfg, params, cache, tokens, pos, window: int = 0):
    x = jnp.take(params["embed"], tokens, axis=0)       # (B,1,D)
    x = x + sinusoidal_positions(1, cfg.d_model).astype(x.dtype)[None] * 0 \
        + _pos_embed_at(cfg, pos).astype(x.dtype)

    def body(x, xs):
        p, c = xs
        out, nc = _dec_layer(cfg, p, x, None, pos, "decode", cache=c,
                             window=window)
        return out, nc

    x, cache = jax.lax.scan(body, x, (params["dec"], cache))
    x = rms_norm(x, params["dec_norm"], cfg.norm_eps)
    return x[:, -1, :] @ params["lm_head"], cache


def _pos_embed_at(cfg, pos):
    """Sinusoidal position embedding at a traced position (1, 1, D)."""
    D = cfg.d_model
    i = jnp.arange(D // 2, dtype=jnp.float32)
    ang = pos.astype(jnp.float32) / jnp.power(10000.0, 2.0 * i / D)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])[None, None, :]
