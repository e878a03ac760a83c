"""Causal self-attention over the query x key blocks on or below the
diagonal (`attention._flash_causal`) against the full-square masked
loop every query block used to run over every kv chunk, in f32 at
HIGHEST: output and gradients agree, the skipped blocks are the ones
the counters count, and every other call of `_flash` keeps the square."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lm_round
from repro.models import attention
from repro.models.attention import NEG_INF, _flash, _flash_causal

TOL = dict(rtol=2e-5, atol=2e-5)


def _square_oracle(q, k, v, q_pos, k_pos, window, chunk, causal=True,
                   scale=None):
    """The full-square loop: every kv chunk scored against every query,
    masked, through one (B, S, H) online-softmax carry."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]
    G = H // K
    if scale is None:
        scale = 1.0 / np.sqrt(hd)
    qr = q.reshape(B, S, K, G, hd).astype(jnp.float32) * scale
    chunk = min(chunk, T)
    n_chunks = -(-T // chunk)
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
        k_i, v_i, p_i = xs
        s = jnp.einsum("bskgd,bckd->bskgc", qr, k_i.astype(jnp.float32))
        valid = jnp.broadcast_to(p_i[None, :] >= 0, (S, p_i.shape[0]))
        if causal:
            valid = valid & (p_i[None, :] <= q_pos[:, None])
        if window > 0:
            valid = valid & (p_i[None, :] > q_pos[:, None] - window)
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
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, S, H, hd_v).astype(q.dtype)


def _qkv(S, T=None, H=4, K=4, hd=12, hd_v=12, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    T = S if T is None else T
    return (jax.random.normal(kq, (2, S, H, hd), jnp.float32),
            jax.random.normal(kk, (2, T, K, hd), jnp.float32),
            jax.random.normal(kv, (2, T, K, hd_v), jnp.float32))


def _assert_same_with_grads(f, g, q, k, v):
    np.testing.assert_allclose(jax.jit(f)(q, k, v), jax.jit(g)(q, k, v),
                               **TOL)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a)) * a[0][..., :1]),
            argnums=(0, 1, 2)))(q, k, v)
    for got, want in zip(grads(f), grads(g)):
        np.testing.assert_allclose(got, want, **TOL)


CASES = {                         # S, chunk, hd_v, kv heads, window
    "multiple_of_chunk": (32, 8, 12, 4, 0),
    "padded_tail": (27, 8, 12, 4, 0),
    "shorter_than_chunk": (5, 8, 12, 4, 0),
    "mla_value_width": (24, 8, 8, 4, 0),         # hd 12, hd_v 8 (192/128)
    "grouped_kv_heads": (24, 8, 12, 2, 0),
    "window_band_skip": (40, 8, 12, 4, 10),
    "window_padded_tail": (37, 8, 8, 2, 9),
}


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("case", CASES)
def test_triangle_matches_full_square(case, remat):
    S, chunk, hd_v, K, window = CASES[case]
    q, k, v = _qkv(S, K=K, hd_v=hd_v)
    pos = jnp.arange(S, dtype=jnp.int32) + 3       # any offset: relative
    with jax.default_matmul_precision("highest"):
        _assert_same_with_grads(
            lambda q, k, v: _flash_causal(q, k, v, pos, window, chunk=chunk,
                                          scale=0.3, remat_chunks=remat),
            lambda q, k, v: _square_oracle(q, k, v, pos, pos, window, chunk,
                                           scale=0.3),
            q, k, v)


@pytest.mark.parametrize("case", ["multiple_of_chunk", "padded_tail",
                                  "shorter_than_chunk", "window_band_skip"])
def test_only_counted_blocks_are_computed(case, monkeypatch):
    """Run eagerly, the chunk step runs once per visited block, and that
    is what `causal_block_scores` counts."""
    S, chunk, hd_v, K, window = CASES[case]
    calls = []
    step = attention._online_step

    def counted(qr, *args):
        calls.append(qr.shape[1])
        return step(qr, *args)

    monkeypatch.setattr(attention, "_online_step", counted)
    q, k, v = _qkv(S, K=K, hd_v=hd_v)
    with jax.disable_jit():
        _flash_causal(q, k, v, jnp.arange(S, dtype=jnp.int32), window,
                      chunk=chunk)
    c = min(chunk, S)
    assert sum(calls) == attention.causal_block_scores(S, window, chunk) // c
    assert set(calls) == {c}


def test_square_stays_for_non_causal_and_rolling_caches():
    """Encoder and cross attention (causal=False) and a cache with -1
    slots keep the full-square loop, with the oracle's numbers."""
    with jax.default_matmul_precision("highest"):
        q, k, v = _qkv(12, T=20)
        q_pos = jnp.zeros((12,), jnp.int32)
        t_pos = jnp.arange(20, dtype=jnp.int32)
        _assert_same_with_grads(
            lambda q, k, v: _flash(q, k, v, q_pos, t_pos, 0, chunk=8,
                                   causal=False),
            lambda q, k, v: _square_oracle(q, k, v, q_pos, t_pos, 0, 8,
                                           causal=False),
            q, k, v)
        q, k, v = _qkv(6, T=16, seed=1)
        q_pos = jnp.arange(20, 26, dtype=jnp.int32)
        slots = jnp.where(jnp.arange(16) < 11, jnp.arange(9, 25), -1)
        _assert_same_with_grads(
            lambda q, k, v: _flash(q, k, v, q_pos, slots, 12, chunk=8),
            lambda q, k, v: _square_oracle(q, k, v, q_pos, slots, 12, 8),
            q, k, v)


@pytest.mark.parametrize("S, chunk, blocks", [
    (512, 512, 1), (4096, 512, 36), (700, 512, 3), (32, 512, 1),
    (40, 8, 15)])
def test_block_scores_count_the_visited_triangle(S, chunk, blocks):
    c = min(chunk, S)
    assert attention.causal_block_scores(S, 0, chunk) == blocks * c * c
    if S % c == 0:                 # n whole blocks: (n + 1) c / (S + 1)
        ratio = attention.causal_block_scores(S, 0, chunk) / (S * (S + 1) / 2)
        assert ratio == pytest.approx((S // c + 1) * c / (S + 1))


def test_round_scores_cover_every_attention_layer():
    from repro.configs import get_config
    cfg = get_config("deepseek-v2-lite-16b")
    scores, causal = lm_round.attn_scores(cfg, 4096)
    assert scores == cfg.n_layers * 36 * 512 * 512
    assert causal == cfg.n_layers * 4096 * 4097 // 2
    assert scores / causal == pytest.approx(36 * 512 ** 2 / (4096 * 4097 / 2))
