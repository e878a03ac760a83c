"""Unified decoder-only model covering dense GQA / MoE / MLA / RWKV6 /
Mamba-hybrid / VLM-prefix architectures.

Layers are organized into *stages*: a stage is a repeating pattern of
(mixer, ffn) layer specs scanned over its repeat count with stacked params —
jax.lax.scan keeps the HLO size O(pattern) instead of O(n_layers), which is
what makes 64-72 layer 100-400B configs compile quickly in the dry-run.
Heterogeneous architectures (jamba's 1:7 attn:mamba interleave with
alternating MoE) become a pattern of length 8 scanned 9 times.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention, mla, moe, ssm_mamba, ssm_rwkv
from repro.models.common import (dense_init, dtype_of, embed_init, rms_norm,
                                 softmax_cross_entropy, swiglu)

# ------------------------------------------------------------------- stages

def stages(cfg) -> list[tuple[tuple[tuple[str, str], ...], int]]:
    """Returns [(pattern, count)] with pattern = ((mixer, ffn), ...)."""
    L = cfg.n_layers
    if cfg.arch_type == "ssm":                      # rwkv6
        return [((("rwkv", "rwkv_ffn"),), L)]
    if cfg.arch_type == "hybrid":                   # jamba: 1:7, alt MoE
        n = cfg.ssm.attn_every_n
        assert L % n == 0
        pattern = []
        for i in range(n):
            mixer = "attn" if i == 0 else "mamba"
            ffn = "moe" if (cfg.moe is not None and i % 2 == 1) else "dense"
            pattern.append((mixer, ffn))
        return [(tuple(pattern), L // n)]
    if cfg.mla is not None:                         # deepseek: first dense FFN
        return [((("mla", "dense"),), 1), ((("mla", "moe"),), L - 1)]
    if cfg.moe is not None:                         # mixtral
        return [((("attn", "moe"),), L)]
    return [((("attn", "dense"),), L)]              # dense / vlm


# ------------------------------------------------------------------- params

def _init_ffn(key, cfg, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    D, F = cfg.d_model, cfg.d_ff
    return {"w1": dense_init(k1, (D, F), dtype),
            "w3": dense_init(k2, (D, F), dtype),
            "w2": dense_init(k3, (F, D), dtype)}


def _init_layer(key, spec, cfg, dtype):
    mixer, ffn = spec
    km, kf = jax.random.split(key)
    p: dict[str, Any] = {"ln1": jnp.ones((cfg.d_model,), dtype),
                         "ln2": jnp.ones((cfg.d_model,), dtype)}
    if mixer == "attn":
        p["attn"] = attention.init_attn(km, cfg, dtype)
    elif mixer == "mla":
        p["mla"] = mla.init_mla(km, cfg, dtype)
    elif mixer == "rwkv":
        p["rwkv"] = ssm_rwkv.init_rwkv_mix(km, cfg, dtype)
    elif mixer == "mamba":
        p["mamba"] = ssm_mamba.init_mamba(km, cfg, dtype)
    if ffn == "dense":
        p["ffn"] = _init_ffn(kf, cfg, dtype)
    elif ffn == "moe":
        p["moe"] = moe.init_moe(kf, cfg, dtype)
    elif ffn == "rwkv_ffn":
        p["ffn"] = ssm_rwkv.init_rwkv_ffn(kf, cfg, dtype)
    return p


def _init_superblock(key, pattern, cfg, dtype):
    keys = jax.random.split(key, len(pattern))
    return {f"l{i}": _init_layer(keys[i], spec, cfg, dtype)
            for i, spec in enumerate(pattern)}


def init_params(cfg, key):
    dtype = dtype_of(cfg)
    ks = jax.random.split(key, 4 + len(stages(cfg)))
    V = cfg.vocab_padded
    params: dict[str, Any] = {
        "embed": embed_init(ks[0], (V, cfg.d_model), dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], (cfg.d_model, V), dtype)
    if cfg.n_prefix_patches:
        params["patch_proj"] = dense_init(
            ks[2], (cfg.d_model, cfg.d_model), dtype)
    for si, (pattern, count) in enumerate(stages(cfg)):
        keys = jax.random.split(ks[3 + si], count)
        params[f"stage{si}"] = jax.vmap(
            lambda k: _init_superblock(k, pattern, cfg, dtype))(keys)
    return params


def abstract_params(cfg, policy_fn=None):
    """ShapeDtypeStruct param tree (no allocation) for the dry-run."""
    return jax.eval_shape(functools.partial(init_params, cfg),
                          jax.random.PRNGKey(0))


# ------------------------------------------------------------------- caches

def _init_layer_cache(spec, cfg, batch, max_seq, dtype, window):
    mixer, _ = spec
    if mixer == "attn":
        return {"attn": attention.init_cache(cfg, batch, max_seq, dtype, window)}
    if mixer == "mla":
        return {"mla": mla.init_mla_cache(cfg, batch, max_seq, dtype, window)}
    if mixer == "rwkv":
        return {"rwkv": ssm_rwkv.init_rwkv_state(cfg, batch, dtype)}
    if mixer == "mamba":
        return {"mamba": ssm_mamba.init_mamba_state(cfg, batch, dtype)}
    return {}


def init_cache(cfg, batch: int, max_seq: int, window: int = 0):
    dtype = dtype_of(cfg)
    cache = {}
    for si, (pattern, count) in enumerate(stages(cfg)):
        one = {f"l{i}": _init_layer_cache(spec, cfg, batch, max_seq, dtype,
                                          window)
               for i, spec in enumerate(pattern)}
        cache[f"stage{si}"] = jax.tree_util.tree_map(
            lambda a: jnp.zeros((count,) + a.shape, a.dtype)
            if a.dtype != jnp.int32
            else jnp.broadcast_to(a, (count,) + a.shape).copy(), one)
    return cache


# ------------------------------------------------------------------- layers

def _zero_aux(cfg, batch: int) -> dict:
    """Per-layer statistics summed over the layers: the balance loss
    (per sequence (B,) for DeepSeek's, else a scalar), and the held
    experts' routed assignments and expert-matmul rows (`moe_ffn_held`)."""
    seq = cfg.moe is not None and cfg.moe.dropless
    return {"balance": jnp.zeros((batch,) if seq else (), jnp.float32),
            "routed": jnp.zeros((), jnp.int32),
            "rows": jnp.zeros((), jnp.int32)}


def _layer_apply(spec, p, x, cfg, mode, positions=None, pos=None,
                 cache=None, window=0, chunked=True, token_mask=None):
    """One (mixer, ffn) layer.  Returns (x, new_cache, aux) with aux as
    `_zero_aux` lays it out; `token_mask` (B, S) marks the tokens that
    route to experts (dropless MoE only; None: all)."""
    mixer, ffn = spec
    aux = _zero_aux(cfg, x.shape[0])
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    new_cache = {}
    if mixer == "attn":
        if mode == "train":
            out = attention.attn_train(p["attn"], h, positions, cfg, window)
        elif mode == "prefill":
            out, c = attention.attn_prefill(p["attn"], h, positions, cfg,
                                            cache["attn"], window)
            new_cache["attn"] = c
        else:
            out, c = attention.attn_decode(p["attn"], h, pos, cfg,
                                           cache["attn"], window)
            new_cache["attn"] = c
    elif mixer == "mla":
        if mode == "train":
            out = mla.mla_train(p["mla"], h, positions, cfg, window)
        elif mode == "prefill":
            out, c = mla.mla_prefill(p["mla"], h, positions, cfg,
                                     cache["mla"], window)
            new_cache["mla"] = c
        else:
            out, c = mla.mla_decode(p["mla"], h, pos, cfg, cache["mla"], window)
            new_cache["mla"] = c
    elif mixer == "rwkv":
        st = cache["rwkv"] if cache else ssm_rwkv.init_rwkv_state(
            cfg, x.shape[0], x.dtype)
        out, (x_last, wkv) = ssm_rwkv.rwkv_mix_train(
            p["rwkv"], h, st["x_prev_mix"], st["wkv"], cfg,
            chunked=(mode == "train" or mode == "prefill") and chunked)
        new_cache["rwkv"] = {"x_prev_mix": x_last, "wkv": wkv,
                             "x_prev_ffn": st["x_prev_ffn"]}
    elif mixer == "mamba":
        st = cache["mamba"] if cache else ssm_mamba.init_mamba_state(
            cfg, x.shape[0], x.dtype)
        out, st2 = ssm_mamba.mamba_block(p["mamba"], h, st, cfg,
                                         chunked=chunked and mode != "decode")
        new_cache["mamba"] = st2
    x = x + out

    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if ffn == "dense":
        with jax.named_scope("dense_ffn"):
            x = x + swiglu(h, p["ffn"]["w1"], p["ffn"]["w3"], p["ffn"]["w2"])
    elif ffn == "moe":
        if cfg.moe.dropless:
            out, aux = moe.moe_ffn_held(p["moe"], h, cfg, token_mask)
        else:
            out, aux["balance"] = moe.moe_ffn(p["moe"], h, cfg)
        x = x + out
    elif ffn == "rwkv_ffn":
        st = new_cache.get("rwkv") or (cache["rwkv"] if cache else
                                       ssm_rwkv.init_rwkv_state(cfg, x.shape[0], x.dtype))
        out, x_last = ssm_rwkv.rwkv_ffn(p["ffn"], h, st["x_prev_ffn"], cfg)
        if "rwkv" in new_cache:
            new_cache["rwkv"]["x_prev_ffn"] = x_last
        x = x + out
    return x, new_cache, aux


def _run_stages(cfg, params, x, mode, positions=None, pos=None, cache=None,
                window=0, remat=False, chunked=True, token_mask=None):
    """Scan over every stage.  Returns (x, new_cache, aux), aux the
    layers' `_zero_aux` statistics summed."""
    total_aux = _zero_aux(cfg, x.shape[0])
    new_cache = {}
    for si, (pattern, count) in enumerate(stages(cfg)):
        sp = params[f"stage{si}"]
        sc = cache[f"stage{si}"] if cache is not None else None

        def body(carry, xs):
            x, aux = carry
            layer_p, layer_c = xs
            lc_out = {}
            for i, spec in enumerate(pattern):
                x, c, a = _layer_apply(
                    spec, layer_p[f"l{i}"], x, cfg, mode,
                    positions=positions, pos=pos,
                    cache=None if layer_c is None else layer_c[f"l{i}"],
                    window=window, chunked=chunked, token_mask=token_mask)
                lc_out[f"l{i}"] = c
                aux = jax.tree_util.tree_map(jnp.add, aux, a)
            return (x, aux), lc_out

        body_fn = jax.checkpoint(body) if remat else body
        if sc is None:
            # scan needs a pytree for xs; pass params only
            def body_np(carry, layer_p):
                return body_fn(carry, (layer_p, None))
            (x, total_aux), _ = jax.lax.scan(body_np, (x, total_aux), sp)
        else:
            (x, total_aux), cache_out = jax.lax.scan(
                body_fn, (x, total_aux), (sp, sc))
            new_cache[f"stage{si}"] = cache_out
    return x, new_cache, total_aux


# ------------------------------------------------------------------- embeds

def _embed_tokens(cfg, params, tokens):
    return jnp.take(params["embed"], tokens, axis=0)


def _inputs_embeds(cfg, params, batch):
    """Token embeddings, with VLM patch prefix when configured."""
    emb = _embed_tokens(cfg, params, batch["tokens"])
    if cfg.n_prefix_patches:
        patches = batch["patch_embeds"].astype(emb.dtype) @ params["patch_proj"]
        emb = jnp.concatenate([patches, emb], axis=1)
    return emb


def _logits(cfg, params, x):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    if cfg.vocab_padded != cfg.vocab:
        ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
        logits = jnp.where(ids < cfg.vocab, logits,
                           jnp.asarray(-1e9, logits.dtype))
    return logits


# ------------------------------------------------------------------- public

def loss_fn(cfg, params, batch, window: int = 0, remat: bool = True,
            chunked: bool = True):
    """batch: tokens (B,S), labels (B,S) [, patch_embeds (B,P,D)].

    Labels are next-token targets aligned with the *token* positions;
    label -100 masks a position out.
    """
    x = _inputs_embeds(cfg, params, batch)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    x, _, aux = _run_stages(cfg, params, x, "train", positions=positions,
                            window=window, remat=remat, chunked=chunked)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.n_prefix_patches:
        x = x[:, cfg.n_prefix_patches:, :]
    logits = _logits(cfg, params, x)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    loss = softmax_cross_entropy(logits, jnp.maximum(labels, 0), mask)
    return loss + jnp.mean(aux["balance"])


def client_losses(cfg, params, tokens, loads, remat: bool = True):
    """Per-client objectives of the federated language-model round.

    tokens: (B, S + 1) ids, one sequence per client; loads: (B,) ints,
    client b's load being its first ``loads[b]`` next-token targets.  A
    target past the load is masked out of the loss and routes to no
    expert; under causal attention that is the sequence truncated to its
    load.  Returns ``(L, stats)``: ``L[b] = sum_{t < l_b} nll_{b,t} +
    l_b * balance_b`` (the per-token loss plus the balance loss, summed
    over the load), ``stats`` the per-client ``nll`` sums and the summed
    ``routed`` / ``rows`` counts of the held experts.
    """
    inp, labels = tokens[:, :-1], tokens[:, 1:]
    S = inp.shape[1]
    mask = (jnp.arange(S)[None, :] < loads[:, None]).astype(jnp.float32)
    x = _embed_tokens(cfg, params, inp)
    positions = jnp.arange(S, dtype=jnp.int32)
    x, _, aux = _run_stages(cfg, params, x, "train", positions=positions,
                            remat=remat, token_mask=mask)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        logits = _logits(cfg, params, x).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        picked = jnp.sum(jnp.where(ids == labels[..., None], logits, 0.0),
                         axis=-1)
        nll = jnp.sum((lse - picked) * mask, axis=-1)
    balance = jnp.broadcast_to(aux["balance"], nll.shape)
    L = nll + loads.astype(jnp.float32) * balance
    return L, {"nll": nll, "routed": aux["routed"], "rows": aux["rows"]}


def hidden_states(cfg, params, batch, window: int = 0, chunked: bool = True):
    """Final-norm hidden states (B, S, D) — feature extractor for the
    CodedFedL coded linear-probe head (core/coded_probe.py)."""
    x = _inputs_embeds(cfg, params, batch)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    x, _, _ = _run_stages(cfg, params, x, "train", positions=positions,
                          window=window, remat=False, chunked=chunked)
    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def prefill(cfg, params, batch, window: int = 0, chunked: bool = True):
    """Returns (last-position logits (B, V), cache)."""
    x = _inputs_embeds(cfg, params, batch)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    cache = init_cache(cfg, B, S, window)
    x, cache, _ = _run_stages(cfg, params, x, "prefill", positions=positions,
                              cache=cache, window=window, chunked=chunked)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _logits(cfg, params, x[:, -1, :]), cache


def decode_step(cfg, params, cache, tokens, pos, window: int = 0):
    """One-token decode.  tokens: (B, 1); pos: scalar int32.

    Returns (logits (B, V), new_cache)."""
    x = _embed_tokens(cfg, params, tokens)
    x, cache, _ = _run_stages(cfg, params, x, "decode", pos=pos, cache=cache,
                              window=window)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _logits(cfg, params, x[:, -1, :]), cache
