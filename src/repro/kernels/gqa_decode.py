"""Pallas TPU kernel: flash-decode GQA attention (one token vs KV cache).

§Perf iteration 2 made single-token decode attention the framework's
serving hot-spot expression (one-shot einsum + masked softmax); this kernel
is its TPU-native tiling: the cache streams through VMEM in (bt, K, hd)
chunks with an online-softmax accumulator in VMEM scratch, so the (T,)-long
score row never materializes in HBM.  Grid (B, T/bt), sequential on the
chunk axis; the accumulator re-initializes at chunk 0 and the output block
is written at the final chunk.

Masking: slot positions `k_pos` (rolling caches store -1 for empty slots)
must be <= q_pos and, for sliding-window decode, > q_pos - window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, kpos_ref, qpos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, nt: int, G: int, window: int,
            scale: float):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (H, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bt, K, hd)
    v = v_ref[0].astype(jnp.float32)                  # (bt, K, hd_v)
    K = k.shape[1]
    qg = q.reshape(K, G, q.shape[-1])
    s = jnp.einsum("kgd,tkd->kgt", qg, k)             # (K, G, bt)
    kp = kpos_ref[0]                                  # (bt,)
    qp = qpos_ref[0, 0]
    valid = (kp >= 0) & (kp <= qp)
    if window > 0:
        valid = valid & (kp > qp - window)
    s = jnp.where(valid[None, None, :], s, NEG_INF)

    m_prev = m_ref[...]                               # (K, G)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[..., None] + jnp.einsum(
        "kgt,tkd->kgd", p, v)
    m_ref[...] = m_new

    @pl.when(t == nt - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bt", "window", "interpret"))
def gqa_decode(q, k, v, k_pos, q_pos, *, bt: int = 512, window: int = 0,
               interpret: bool | None = None):
    """q: (B, H, hd); k: (B, T, K, hd); v: (B, T, K, hd_v);
    k_pos: (T,) int32 slot positions; q_pos: () int32.
    Returns (B, H, hd_v).  Requires T % bt == 0 and H % K == 0."""
    B, H, hd = q.shape
    T, K, hd_v = k.shape[1], k.shape[2], v.shape[-1]
    assert T % bt == 0, (T, bt)
    G = H // K
    nt = T // bt
    kpos2 = jnp.broadcast_to(k_pos.reshape(1, T), (1, T))
    qpos2 = jnp.reshape(q_pos.astype(jnp.int32), (1, 1))
    scale = 1.0 / (hd ** 0.5)
    return pl.pallas_call(
        functools.partial(_kernel, nt=nt, G=G, window=window, scale=scale),
        grid=(B, nt),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, bt, K, hd), lambda b, t: (b, t, 0, 0)),
            pl.BlockSpec((1, bt, K, hd_v), lambda b, t: (b, t, 0, 0)),
            pl.BlockSpec((1, bt), lambda b, t: (0, t)),
            pl.BlockSpec((1, 1), lambda b, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, hd_v), lambda b, t: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, hd_v), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((K, G), jnp.float32),
            pltpu.VMEM((K, G), jnp.float32),
            pltpu.VMEM((K, G, hd_v), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v, kpos2, qpos2)
