"""GQA attention: full / sliding-window, train+prefill+decode, flash-style.

The seq x seq score matrix is never materialized: we lax.scan over KV chunks
with a running (max, denom, acc) online softmax — the TPU-native equivalent
of flash attention, expressed in XLA ops so the multi-pod dry-run lowers
without a custom kernel.  Causal self-attention over a sequence's own
positions (training and prefill, `_flash_causal`) visits only the query x
key blocks on or below the diagonal.  Sliding-window attention uses a
rolling cache of `window` slots for decode (sub-quadratic long-context
path).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import apply_rope, dense_init, rms_norm

NEG_INF = -1e30
#: kv chunk (and causal query block) width of the flash loops
CHUNK = 512


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


def _online_step(qr, q_pos, carry, k_i, v_i, p_i, window: int,
                 causal: bool):
    """One kv chunk of the online softmax for the queries `qr`.

    qr: (B, Sq, K, G, hd) f32, already scaled; k_i, v_i: (B, C, K, hd);
    p_i: (C,) key positions (-1: padding); carry (m, l, acc) of the
    queries, (B, Sq, K, G) twice and (B, Sq, K, G, hd_v)."""
    m, l, acc = carry
    s = jnp.einsum("bskgd,bckd->bskgc", qr, k_i.astype(jnp.float32))
    valid = jnp.broadcast_to(p_i[None, :] >= 0,
                             (q_pos.shape[0], p_i.shape[0]))
    if causal:
        valid = valid & (p_i[None, :] <= q_pos[:, None])
    if window > 0:
        valid = valid & (p_i[None, :] > q_pos[:, None] - window)
    # valid: (Sq, C) -> broadcast over (B, Sq, K, G, C)
    s = jnp.where(valid[None, :, None, None, :], s, NEG_INF)
    m_i = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_i[..., None])
    corr = jnp.exp(m - m_i)
    l_i = l * corr + jnp.sum(p, axis=-1)
    acc_i = acc * corr[..., None] + jnp.einsum(
        "bskgc,bckd->bskgd", p, v_i.astype(jnp.float32))
    return m_i, l_i, acc_i


def _prepare(q, k, v, k_pos, chunk: int, scale: float | None):
    """Scaled f32 queries (B, S, K, G, hd) and the keys, values and key
    positions cut into chunks, the tail padded with position -1."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]                  # may differ from hd (MLA)
    if scale is None:
        scale = 1.0 / np.sqrt(hd)
    qr = q.reshape(B, S, K, H // K, hd).astype(jnp.float32) * scale
    n_chunks = -(-T // chunk)
    pad = n_chunks * chunk - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=-1)
    kc = k.reshape(B, n_chunks, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, K, hd_v).transpose(1, 0, 2, 3, 4)
    return qr, kc, vc, k_pos.reshape(n_chunks, chunk)


def _carry0(B, S, K, G, hd_v):
    return (jnp.full((B, S, K, G), NEG_INF, jnp.float32),
            jnp.zeros((B, S, K, G), jnp.float32),
            jnp.zeros((B, S, K, G, hd_v), jnp.float32))


def _flash(q, k, v, q_pos, k_pos, window: int, chunk: int = CHUNK,
           causal: bool = True, scale: float | None = None):
    """Online-softmax attention over every (query, kv chunk) pair.

    q: (B, S, H, hd); k, v: (B, T, K, hd); *_pos: (S,), (T,) global positions
    (k_pos may contain -1 for invalid rolling-cache slots).  `scale` is
    the softmax scale (default hd^-0.5).  Causal self-attention over a
    sequence's own positions takes `_flash_causal`, which skips the
    chunks above the diagonal.  Returns (B, S, H, hd).
    """
    B, S, H, hd = q.shape
    if S == 1:                          # decode: one-shot path (see above)
        return _attend_single(q, k, v, q_pos, k_pos, window, causal, scale)
    K, hd_v = k.shape[2], v.shape[-1]
    qr, kc, vc, pc = _prepare(q, k, v, k_pos, min(chunk, k.shape[1]), scale)

    def step(carry, xs):
        return _online_step(qr, q_pos, carry, *xs, window, causal), None

    (m, l, acc), _ = jax.lax.scan(step, _carry0(B, S, K, H // K, hd_v),
                                  (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, S, H, hd_v).astype(q.dtype)


def _causal_pairs(n: int, chunk: int, window: int):
    """The (query block, kv chunk) pairs of one sequence that
    `_flash_causal` visits, `n` blocks of `chunk`: those on or below the
    diagonal and, with a window, not wholly before the band of every
    query of the block; row by row, each row's kv chunks in ascending
    order and ending on the diagonal."""
    rows, cols = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    near = cols <= rows
    if window > 0:                      # nearest pair: i*C - (j*C + C - 1)
        near &= (rows - cols - 1) * chunk + 1 < window
    return np.nonzero(near)


def causal_block_scores(S: int, window: int = 0, chunk: int = CHUNK) -> int:
    """Query-key scores `_flash_causal` computes for one sequence of S
    positions and one head: the visited blocks, padding included."""
    c = min(chunk, S)
    return len(_causal_pairs(-(-S // c), c, window)[0]) * c * c


def _flash_causal(q, k, v, positions, window: int, chunk: int = CHUNK,
                  scale: float | None = None, remat_chunks: bool = False):
    """Causal self-attention of one sequence: `_flash` with the queries
    and the keys at the same `positions` (S,), in increasing order.

    The queries are cut into blocks as wide as the kv chunks, and query
    block i runs the online softmax over kv chunks 0..i only, in the
    same ascending order and arithmetic as `_flash`: a chunk above the
    diagonal (or, with a window, wholly before the band) would add
    exp(NEG_INF - m) = 0 to rows that have seen their own key, so it is
    left out and the numbers are unchanged.  One `lax.scan` runs over
    the visited pairs (`_causal_pairs`), its carry one query block's,
    started afresh at each row's first pair and read out at its last.
    With `remat_chunks` the backward pass keeps one block carry per
    pair and recomputes that pair's probabilities, never all pairs'
    (B, chunk, H, chunk) at once.  Returns (B, S, H, hd_v).
    """
    B, S, H, hd = q.shape
    K, hd_v = k.shape[2], v.shape[-1]
    G = H // K
    c = min(chunk, S)
    qr, kc, vc, pc = _prepare(q, k, v, positions, c, scale)
    n = pc.shape[0]
    pad = n * c - S
    if pad:                             # padded queries: discarded below
        qr = jnp.pad(qr, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    qb = qr.reshape(B, n, c, K, G, hd).transpose(1, 0, 2, 3, 4, 5)
    rows, cols = _causal_pairs(n, c, window)
    first = np.r_[True, rows[1:] != rows[:-1]]
    init = _carry0(B, c, K, G, hd_v)

    def step(carry, xs):
        i, j, fresh = xs
        carry = [jnp.where(fresh, a, b) for a, b in zip(init, carry)]
        m, l, acc = _online_step(qb[i], pc[i], carry, kc[j], vc[j], pc[j],
                                 window, True)
        return (m, l, acc), (l, acc)

    _, (l, acc) = jax.lax.scan(
        jax.checkpoint(step) if remat_chunks else step, init,
        (rows, cols, first))
    # each row closes on its diagonal: static slices, not a gather
    last = np.flatnonzero(np.r_[first[1:], True])
    out = jnp.stack([acc[t] / jnp.maximum(l[t], 1e-30)[..., None]
                     for t in last])
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, n * c, H, hd_v)
    return out[:, :S].astype(q.dtype)


def attn_train(p, x, positions, cfg, window: int = 0):
    """Full-sequence causal attention.  x: (B, S, D), positions: (S,)."""
    q = _project_q(p, x, positions[None, :], cfg)
    k, v = _project_kv(p, x, positions[None, :], cfg)
    win = window if window else cfg.swa_window
    # §Perf: flash-style backward — recompute the kv loop instead of saving
    # the per-chunk online-softmax carries (EXPERIMENTS.md §Perf iter 1b).
    flash = jax.checkpoint(
        lambda q_, k_, v_: _flash_causal(q_, k_, v_, positions, win))
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
    out = _flash_causal(q, k, v, positions, win)
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
