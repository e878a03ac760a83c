"""Mixture-of-Experts FFN: GShard/Switch-style capacity dispatch, or
dropless grouped matrix products over the experts this device holds.

Capacity-based dispatch keeps the compiled FLOPs equal to the *active*
FLOPs (tokens x top_k x expert FFN), which is what the roofline analysis
must see — a dense all-experts einsum would overstate MoE compute by
E/top_k.  Experts are tensor-parallel over the `model` axis within each
expert (uniform across 8/16/64-expert configs), dispatch is batch-sharded.

``cfg.moe.dropless`` (DeepSeek-V2) takes `moe_ffn_held` instead: the
router scores all ``num_experts``, every token's top-k assignments that
land in the held range ``cfg.moe.held`` = (first, count) are sorted by
expert and computed as grouped matrix products
(``jax.lax.ragged_dot``), and no token is dropped.  A device holding one
share of the experts computes that share's part of the layer; the shares'
parts, with the shared experts counted once, add up to the whole layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import dense_init, swiglu

#: rows of one tile of the TPU's grouped-matmul kernel at the shapes the
#: held-expert layer uses: its compiled metadata lists rows / 512 + groups
#: - 1 tile slots, and a group's rows are computed in whole tiles
GMM_ROW_TILE = 512


def init_moe(key, cfg, dtype):
    """Router over all experts.  Dropless layers hold routed weights for
    the held experts only, each from its own key at its own fan-in (so a
    share is the slice of the whole layer); capacity layers all E."""
    m = cfg.moe
    D, F, E = cfg.d_model, (m.d_ff_expert or cfg.d_ff), m.num_experts
    first, count = m.held
    ks = jax.random.split(key, 5)

    def experts(k, shape):
        if not m.dropless:
            return dense_init(k, (E,) + shape, dtype)
        keys = jax.random.split(k, E)[first:first + count]
        return jax.vmap(lambda kk: dense_init(kk, shape, dtype))(keys)

    p = {
        "router": dense_init(ks[0], (D, E), jnp.float32, scale=0.02),
        "w1": experts(ks[1], (D, F)),
        "w3": experts(ks[2], (D, F)),
        "w2": experts(ks[3], (F, D)),
    }
    if m.num_shared_experts:
        Fs = F * m.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["ws1"] = dense_init(k1, (D, Fs), dtype)
        p["ws3"] = dense_init(k2, (D, Fs), dtype)
        p["ws2"] = dense_init(k3, (Fs, D), dtype)
    return p


def moe_ffn(p, x, cfg):
    """x: (B, S, D) -> (out, aux_loss)."""
    m = cfg.moe
    B, S, D = x.shape
    E, k = m.num_experts, m.top_k
    N = B * S
    xt = x.reshape(N, D)
    logits = (xt.astype(jnp.float32) @ p["router"])            # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)              # (N, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # ---- capacity dispatch (position within each expert's buffer)
    cap = int(m.capacity_factor * N * k / E)
    cap = max(cap, 1)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)      # (N, k, E)
    flat = onehot.reshape(N * k, E)
    pos_in_expert = jnp.cumsum(flat, axis=0) * flat - 1        # (N*k, E)
    pos = jnp.max(pos_in_expert, axis=-1).reshape(N, k)        # (N, k)
    expert = gate_idx
    keep = pos < cap                                           # token dropping
    gate_vals = gate_vals * keep

    # dispatch (N, k) slots -> (E, cap, D) via scatter
    flat_idx = expert * cap + jnp.minimum(pos, cap - 1)        # (N, k)
    buf = jnp.zeros((E * cap, D), x.dtype)
    src = jnp.repeat(xt[:, None, :], k, axis=1)                # (N, k, D)
    buf = buf.at[flat_idx.reshape(-1)].add(
        (src * keep[..., None]).reshape(N * k, D))
    buf = buf.reshape(E, cap, D)

    # expert computation — active FLOPs only
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w1"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["w3"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w2"]).reshape(E * cap, D)

    # combine
    gathered = out_buf[flat_idx.reshape(-1)].reshape(N, k, D)
    out = jnp.sum(gathered * gate_vals[..., None].astype(x.dtype), axis=1)

    # shared experts (DeepSeek-style) always active
    if "ws1" in p:
        h = jax.nn.silu(xt @ p["ws1"]) * (xt @ p["ws3"])
        out = out + h @ p["ws2"]

    # GShard load-balance aux loss
    me = jnp.mean(probs, axis=0)                               # (E,)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce) * m.aux_loss_weight
    return out.reshape(B, S, D), aux


def gmm_rows(sizes):
    """Rows a grouped matmul over groups of `sizes` rows (laid end to end)
    computes on the TPU: each non-empty group's rows in whole
    `GMM_ROW_TILE` tiles, a tile two groups share computed once for
    each."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    tiles = jnp.where(sizes > 0,
                      (ends - 1) // GMM_ROW_TILE - starts // GMM_ROW_TILE + 1,
                      0)
    return jnp.sum(tiles).astype(jnp.int32) * GMM_ROW_TILE


def _balance(probs, idx, live, B, S, m):
    """DeepSeek-V2's per-sequence balance loss (arXiv:2405.04434 Sec.
    3.2.3) over the live tokens of each sequence: alpha sum_i f_i P_i with
    f_i = E / (K T) (tokens choosing expert i) and P_i the mean router
    probability of expert i; (B,)."""
    E, k = m.num_experts, m.top_k
    chosen = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1)
    w = live.astype(jnp.float32)[:, None]
    T = jnp.maximum(jnp.sum(live.reshape(B, S), axis=1), 1)[:, None]
    f = jnp.sum((chosen * w).reshape(B, S, E), axis=1) * (E / k) / T
    P = jnp.sum((probs * w).reshape(B, S, E), axis=1) / T
    return m.aux_loss_weight * jnp.sum(f * P, axis=-1)


def moe_ffn_held(p, x, cfg, mask=None):
    """Dropless MoE over the held experts.  x: (B, S, D); `mask` (B, S)
    marks the tokens that route (None: all; the rest take the shared
    experts only).  Returns (out, stats): ``balance`` (see `_balance`),
    ``routed`` (token-expert assignments to held experts) and ``rows``
    (rows the expert matmuls compute, `gmm_rows`)."""
    m = cfg.moe
    B, S, D = x.shape
    E, k = m.num_experts, m.top_k
    first, count = m.held
    N = B * S
    xt = x.reshape(N, D)
    live = jnp.ones((N,), bool) if mask is None else mask.reshape(N) > 0
    with jax.named_scope("moe/router"):
        # the gate in f32, as DeepSeek-V2 computes it
        logits = jnp.dot(xt.astype(jnp.float32), p["router"],
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)                # (N, E)
        gate, idx = jax.lax.top_k(probs, k)                    # (N, k)
        if m.norm_topk_prob:
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        gate = gate * m.routed_scaling_factor
        balance = _balance(probs, idx, live, B, S, m)
    with jax.named_scope("moe/experts"):
        local = (idx - first).reshape(N * k)
        held = (local >= 0) & (local < count) & jnp.repeat(live, k)
        group = jnp.where(held, local, count)
        order = jnp.argsort(group, stable=True)        # held rows first
        sizes = jnp.zeros((count + 1,), jnp.int32).at[group].add(1)[:count]
        on = held[order][:, None]
        # rows past the groups are not computed by the kernel: every
        # operand and result is masked there, so nothing of them flows
        xs = jnp.where(on, xt[order // k], 0.0)

        def gmm(a, w):
            return jax.lax.ragged_dot(a, w, sizes,
                                      preferred_element_type=jnp.float32)

        h = jnp.where(on, jax.nn.silu(gmm(xs, p["w1"])) * gmm(xs, p["w3"]),
                      0.0)
        y = jnp.where(on, gmm(h, p["w2"]), 0.0) * gate.reshape(N * k)[order,
                                                                      None]
        inv = jnp.zeros((N * k,), jnp.int32).at[order].set(
            jnp.arange(N * k, dtype=jnp.int32))
        out = jnp.sum(y[inv].reshape(N, k, D), axis=1).astype(x.dtype)
    if "ws1" in p:
        with jax.named_scope("moe/shared"):
            out = out + swiglu(xt, p["ws1"], p["ws3"], p["ws2"])
    stats = {"balance": balance, "routed": jnp.sum(held).astype(jnp.int32),
             "rows": gmm_rows(sizes)}
    return out.reshape(B, S, D), stats
