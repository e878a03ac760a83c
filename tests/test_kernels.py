"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles in ref.py.

Every Pallas kernel (interpret mode on CPU) is asserted allclose against
the jnp oracle of the same name across shapes, dtypes (f32/bf16), padded
q_true, and ragged validity masks.  Marked `kernels` so CI can run the
kernel/property job separately from the system suite.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

pytestmark = pytest.mark.kernels

RNG = np.random.default_rng(42)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


SHAPES_RFF = [(128, 128, 128), (256, 384, 128), (200, 300, 100),
              (64, 512, 96), (130, 257, 70)]


@pytest.mark.parametrize("m,q,d", SHAPES_RFF)
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_rff_embed(m, q, d, dtype):
    x = _arr((m, d), dtype)
    omega = _arr((d, q), dtype)
    delta = jnp.asarray(RNG.uniform(0, 2 * np.pi, size=(q,)), dtype)
    got = ops.rff_embed(x, omega, delta, use_pallas=True)
    want = ref.rff_embed(x, omega, delta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


SHAPES_GRAD = [(128, 128, 8), (256, 256, 10), (200, 260, 3), (384, 128, 1),
               (130, 70, 5)]


@pytest.mark.parametrize("m,q,c", SHAPES_GRAD)
def test_linreg_grad(m, q, c):
    x = _arr((m, q), scale=0.3)
    theta = _arr((q, c), scale=0.3)
    y = _arr((m, c))
    got = ops.linreg_grad(x, theta, y, use_pallas=True)
    want = ref.linreg_grad(x, theta, y)
    denom = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(np.asarray(got) / denom,
                               np.asarray(want) / denom, atol=3e-5)


SHAPES_PAR = [(128, 128, 128), (96, 200, 260), (256, 130, 64), (64, 64, 500)]


@pytest.mark.parametrize("u,l,q", SHAPES_PAR)
def test_parity_encode(u, l, q):
    g = _arr((u, l))
    w = jnp.asarray(RNG.uniform(0.2, 1.0, size=(l,)), jnp.float32)
    x = _arr((l, q), scale=0.5)
    got = ops.parity_encode(g, w, x, use_pallas=True)
    want = ref.parity_encode(g, w, x)
    denom = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(np.asarray(got) / denom,
                               np.asarray(want) / denom, atol=3e-5)


SHAPES_PAR_BATCHED = [(1, 128, 128, 128), (4, 96, 200, 130), (7, 13, 20, 24),
                      (3, 64, 64, 500)]


@pytest.mark.parametrize("n,u,l,q", SHAPES_PAR_BATCHED)
def test_parity_encode_batched(n, u, l, q):
    """All-clients kernel (client axis = outer grid dim) vs the vmapped
    oracle AND the per-client single kernel (bit-equal: same dots, same
    accumulation order per client)."""
    g = _arr((n, u, l))
    w = jnp.asarray(RNG.uniform(0.2, 1.0, size=(n, l)), jnp.float32)
    x = _arr((n, l, q), scale=0.5)
    got = ops.parity_encode_batched(g, w, x, use_pallas=True)
    want = jax.vmap(ref.parity_encode)(g, w, x)
    denom = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(np.asarray(got) / denom,
                               np.asarray(want) / denom, atol=3e-5)
    per_client = jnp.stack([
        ops.parity_encode(g[j], w[j], x[j], use_pallas=True)
        for j in range(n)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(per_client))


# n, l, q, c — deliberately non-divisible shapes to exercise the padding
SHAPES_MASKED = [(4, 128, 128, 8), (3, 100, 70, 3), (6, 257, 130, 1),
                 (1, 64, 300, 5)]


@pytest.mark.parametrize("n,l,q,c", SHAPES_MASKED)
def test_linreg_grad_masked(n, l, q, c):
    """Batched masked kernel == per-client masked jnp oracle, ragged masks."""
    x = _arr((n, l, q), scale=0.3)
    theta = _arr((q, c), scale=0.3)
    y = _arr((n, l, c))
    # ragged validity: client j keeps a random prefix-free subset of rows
    mask = jnp.asarray((RNG.uniform(size=(n, l)) < 0.6).astype(np.float32))
    got = ops.linreg_grad_masked(x, theta, y, mask, use_pallas=True)
    want = ops.linreg_grad_masked(x, theta, y, mask)
    denom = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(np.asarray(got) / denom,
                               np.asarray(want) / denom, atol=3e-5)
    # and the jnp fallback against the scalar oracle, client by client
    for j in range(n):
        single = ref.linreg_grad_masked(x[j], theta, y[j], mask[j])
        np.testing.assert_allclose(np.asarray(want[j]), np.asarray(single),
                                   rtol=1e-5, atol=1e-5)


def test_linreg_grad_masked_ignores_unzeroed_padding():
    """Rows with mask 0 contribute nothing even when x/y are NOT pre-zeroed."""
    n, l, q, c = 3, 40, 24, 2
    x = _arr((n, l, q), scale=0.5)
    theta = _arr((q, c), scale=0.5)
    y = _arr((n, l, c))
    keep = np.zeros((n, l), np.float32)
    keep[:, : l // 2] = 1.0
    mask = jnp.asarray(keep)
    for use_pallas in (False, True):
        got = ops.linreg_grad_masked(x, theta, y, mask,
                                     use_pallas=use_pallas)
        want = jnp.stack([ref.linreg_grad(x[j, : l // 2], theta,
                                          y[j, : l // 2])
                          for j in range(n)])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_linreg_grad_masked_matches_batched_all_ones():
    """All-ones mask reduces the masked kernel to the plain batched path."""
    n, l, q, c = 4, 60, 40, 4
    x = _arr((n, l, q), scale=0.3)
    theta = _arr((q, c), scale=0.3)
    y = _arr((n, l, c))
    ones = jnp.ones((n, l), jnp.float32)
    a = ops.linreg_grad_masked(x, theta, y, ones, use_pallas=True)
    b = ops.linreg_grad_batched(x, theta, y, use_pallas=True)
    cpl = ops.linreg_grad_batched(x, theta, y)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    np.testing.assert_allclose(np.asarray(a), np.asarray(cpl),
                               rtol=1e-4, atol=1e-4)


def test_linreg_grad_masked_bf16():
    n, l, q, c = 2, 128, 128, 4
    x = _arr((n, l, q), jnp.bfloat16, scale=0.3)
    theta = _arr((q, c), jnp.bfloat16, scale=0.3)
    y = _arr((n, l, c), jnp.bfloat16)
    mask = jnp.asarray((RNG.uniform(size=(n, l)) < 0.5), jnp.bfloat16)
    got = ops.linreg_grad_masked(x, theta, y, mask, use_pallas=True)
    want = ops.linreg_grad_masked(x, theta, y, mask)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=0.5, rtol=0.1)


def test_linreg_grad_c_too_wide_raises_clear_error():
    """Satellite: c that cannot fit a VMEM tile must raise a clear error,
    not an opaque Pallas shape assert."""
    x = jnp.zeros((128, 128), jnp.float32)
    wide = 300_000
    theta = jnp.zeros((128, wide), jnp.float32)
    y = jnp.zeros((128, wide), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        ops.linreg_grad(x, theta, y, use_pallas=True)
    with pytest.raises(ValueError, match="VMEM"):
        ops.linreg_grad_masked(x[None], theta, y[None],
                               jnp.ones((1, 128), jnp.float32),
                               use_pallas=True)


def test_rff_embed_padded_q_true():
    """Zero-padding q must keep the sqrt(2/q_true) scale of the real q."""
    from repro.kernels.rff_embed import rff_embed as kernel
    m, d, q = 128, 128, 100
    x = _arr((m, d))
    omega = _arr((d, q))
    delta = jnp.asarray(RNG.uniform(0, 2 * np.pi, size=(q,)), jnp.float32)
    want = ref.rff_embed(x, omega, delta)
    # ops-level padding path (pads q 100 -> 128 and passes q_true=100)
    got = ops.rff_embed(x, omega, delta, use_pallas=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # direct kernel call on hand-padded operands
    op = jnp.pad(omega, ((0, 0), (0, 28)))
    dp = jnp.pad(delta, (0, 28))
    direct = kernel(x, op, dp, q_true=q)[:, :q]
    np.testing.assert_allclose(np.asarray(direct), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # omitting q_true silently rescales by sqrt(q_true/q_pad) — make sure
    # the guard actually matters
    wrong = kernel(x, op, dp)[:, :q]
    assert not np.allclose(np.asarray(wrong), np.asarray(want), atol=1e-3)


def test_rff_embed_batched_matches_vmapped_oracle():
    n, l, d, q = 3, 50, 33, 70
    x = _arr((n, l, d))
    omega = _arr((d, q))
    delta = jnp.asarray(RNG.uniform(0, 2 * np.pi, size=(q,)), jnp.float32)
    got = ops.rff_embed_batched(x, omega, delta, use_pallas=True)
    want = jax.vmap(lambda xj: ref.rff_embed(xj, omega, delta))(x)
    assert got.shape == (n, l, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_parity_encode_bf16():
    u, l, q = 128, 128, 128
    g = _arr((u, l), jnp.bfloat16)
    w = jnp.asarray(RNG.uniform(0.2, 1.0, size=(l,)), jnp.bfloat16)
    x = _arr((l, q), jnp.bfloat16, scale=0.5)
    got = ops.parity_encode(g, w, x, use_pallas=True)
    want = ref.parity_encode(g, w, x)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=0.6, rtol=0.1)


DECODE_SHAPES = [
    # B, H, K, hd, hd_v, T, window
    (2, 8, 2, 64, 64, 256, 0),      # GQA
    (2, 8, 8, 64, 64, 300, 0),      # MHA, non-divisible T (padded)
    (1, 16, 4, 32, 32, 128, 48),    # sliding window
    (2, 4, 4, 16, 8, 64, 0),        # MLA-style hd_v != hd
]


@pytest.mark.parametrize("B,H,K,hd,hdv,T,win", DECODE_SHAPES)
def test_gqa_decode(B, H, K, hd, hdv, T, win):
    q = _arr((B, H, hd))
    k = _arr((B, T, K, hd), scale=0.3)
    v = _arr((B, T, K, hdv))
    kp = jnp.asarray(np.where(RNG.uniform(size=T) < 0.9,
                              np.arange(T), -1), jnp.int32)
    qp = jnp.int32(T - 1)
    got = ops.gqa_decode(q, k, v, kp, qp, window=win, use_pallas=True, bt=64)
    want = ref.gqa_decode(q, k, v, kp, qp, window=win)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_gqa_decode_matches_model_attention():
    """Kernel oracle agrees with the model's _attend_single decode path."""
    from repro.models.attention import _attend_single
    B, H, K, hd, T = 2, 8, 4, 32, 96
    q = _arr((B, 1, H, hd))
    k = _arr((B, T, K, hd), scale=0.3)
    v = _arr((B, T, K, hd))
    kp = jnp.arange(T, dtype=jnp.int32)
    qp = jnp.full((1,), T - 1, jnp.int32)
    want = _attend_single(q, k, v, qp, kp, 0)[:, 0]
    got = ref.gqa_decode(q[:, 0], k, v, kp, jnp.int32(T - 1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_bf16_support():
    x = _arr((128, 128), jnp.bfloat16)
    omega = _arr((128, 128), jnp.bfloat16)
    delta = jnp.zeros((128,), jnp.bfloat16)
    got = ops.rff_embed(x, omega, delta, use_pallas=True)
    want = ref.rff_embed(x, omega, delta)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.15)


def test_block_shape_sweep():
    """Kernel must be numerically invariant to BlockSpec tiling choices."""
    x = _arr((256, 256), scale=0.3)
    theta = _arr((256, 4), scale=0.3)
    y = _arr((256, 4))
    base = np.asarray(ref.linreg_grad(x, theta, y))
    for bm, bq in [(64, 64), (128, 256), (256, 128)]:
        got = np.asarray(ops.linreg_grad(x, theta, y, use_pallas=True,
                                         bm=bm, bq=bq))
        np.testing.assert_allclose(got, base, atol=1e-3)


# --- fused RFF-embed -> masked gradient kernel (raw features in, grads out) ---

# n, l, d, q, c — mixed divisible and ragged shapes
SHAPES_FUSED = [(3, 128, 16, 128, 4), (2, 100, 33, 70, 3),
                (4, 257, 20, 130, 1), (1, 64, 128, 256, 5)]


@pytest.mark.parametrize("n,l,d,q,c", SHAPES_FUSED)
def test_rff_linreg_grad_fused(n, l, d, q, c):
    """Fused kernel == its jnp fallback == the explicit two-pass path."""
    x = _arr((n, l, d), scale=0.3)
    omega = _arr((d, q), scale=0.3)
    delta = jnp.asarray(RNG.uniform(0, 2 * np.pi, size=(q,)), jnp.float32)
    theta = _arr((q, c), scale=0.3)
    y = _arr((n, l, c))
    mask = jnp.asarray((RNG.uniform(size=(n, l)) < 0.7).astype(np.float32))
    got = ops.rff_linreg_grad_masked(x, omega, delta, theta, y, mask,
                                     use_pallas=True)
    want = ops.rff_linreg_grad_masked(x, omega, delta, theta, y, mask)
    assert got.shape == (n, q, c) and got.dtype == jnp.float32
    denom = max(float(jnp.abs(want).max()), 1.0)
    np.testing.assert_allclose(np.asarray(got) / denom,
                               np.asarray(want) / denom, atol=3e-5)
    # the fallback IS the two-pass composition, bit for bit — the fused
    # path replaces it without changing what is computed
    phi = ops.rff_embed_batched(x, omega, delta)
    two_pass = ops.linreg_grad_masked(phi, theta, y, mask)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(two_pass))


def test_rff_linreg_grad_fused_parity_row():
    """The coded parity pseudo-client rides the same grid: pre-embedded
    (l, q) parity features substitute for the in-kernel embed on the last
    row, and its mask carries the coded 1/u scale."""
    n, l, d, q, c, u = 3, 64, 16, 64, 3, 24
    x = _arr((n, l, d), scale=0.3)
    omega = _arr((d, q), scale=0.3)
    delta = jnp.asarray(RNG.uniform(0, 2 * np.pi, size=(q,)), jnp.float32)
    theta = _arr((q, c), scale=0.3)
    y = _arr((n + 1, l, c))
    parity_phi = jnp.zeros((l, q), jnp.float32).at[:u].set(
        _arr((u, q), scale=0.5))
    mask = np.zeros((n + 1, l), np.float32)
    mask[:n] = (RNG.uniform(size=(n, l)) < 0.7)
    mask[n, :u] = 1.0 / u
    mask = jnp.asarray(mask)
    got = ops.rff_linreg_grad_masked(x, omega, delta, theta, y, mask,
                                     parity_phi=parity_phi, use_pallas=True)
    want = ops.rff_linreg_grad_masked(x, omega, delta, theta, y, mask,
                                      parity_phi=parity_phi)
    assert got.shape == (n + 1, q, c)
    # single-block shapes: the padded contraction contributes exact zeros,
    # so pallas and jnp differ only in XLA's reduction order
    _assert_f32_close(got, want)
    # the parity row must equal the plain masked gradient on parity_phi
    par = ref.linreg_grad_masked(parity_phi, theta, y[n], mask[n])
    np.testing.assert_allclose(np.asarray(got[n]), np.asarray(par),
                               rtol=1e-6, atol=1e-6)


def test_rff_linreg_grad_fused_bf16():
    """bf16 inputs accumulate in f32 and the output stays f32."""
    n, l, d, q, c = 2, 128, 16, 128, 4
    x = _arr((n, l, d), jnp.bfloat16, scale=0.3)
    omega = _arr((d, q), jnp.bfloat16, scale=0.3)
    delta = jnp.asarray(RNG.uniform(0, 2 * np.pi, size=(q,)), jnp.bfloat16)
    theta = _arr((q, c), jnp.bfloat16, scale=0.3)
    y = _arr((n, l, c), jnp.bfloat16)
    mask = jnp.asarray((RNG.uniform(size=(n, l)) < 0.7), jnp.bfloat16)
    got = ops.rff_linreg_grad_masked(x, omega, delta, theta, y, mask,
                                     use_pallas=True)
    want = ops.rff_linreg_grad_masked(x, omega, delta, theta, y, mask)
    assert got.dtype == jnp.float32 and want.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=0.1, rtol=0.1)


def test_rff_linreg_grad_fused_block_sweep():
    """Numerically stable across BlockSpec tiling choices."""
    n, l, d, q, c = 2, 256, 16, 256, 4
    x = _arr((n, l, d), scale=0.3)
    omega = _arr((d, q), scale=0.3)
    delta = jnp.asarray(RNG.uniform(0, 2 * np.pi, size=(q,)), jnp.float32)
    theta = _arr((q, c), scale=0.3)
    y = _arr((n, l, c))
    mask = jnp.ones((n, l), jnp.float32)
    base = np.asarray(ops.rff_linreg_grad_masked(x, omega, delta, theta, y,
                                                 mask))
    for bm, bq in [(64, 64), (128, 256), (256, 128)]:
        got = np.asarray(ops.rff_linreg_grad_masked(
            x, omega, delta, theta, y, mask, use_pallas=True, bm=bm, bq=bq))
        np.testing.assert_allclose(got, base, atol=1e-3)


def test_rff_linreg_grad_fused_rejects_bad_args():
    from repro.kernels.rff_linreg_grad import (
        rff_linreg_grad_masked as kernel)
    rows, l, d, q, c = 2, 128, 128, 128, 4
    x = jnp.zeros((rows, l, d), jnp.float32)
    omega = jnp.zeros((d, q), jnp.float32)
    delta = jnp.zeros((q,), jnp.float32)
    theta = jnp.zeros((q, c), jnp.float32)
    y = jnp.zeros((rows, l, c), jnp.float32)
    mask = jnp.ones((rows, l), jnp.float32)
    pphi = jnp.zeros((1, l, q), jnp.float32)
    with pytest.raises(ValueError, match="q_true"):
        kernel(x, omega, delta, theta, y, mask, pphi, n_real=rows, q_true=0)
    with pytest.raises(ValueError, match="n_real"):
        kernel(x, omega, delta, theta, y, mask, pphi, n_real=rows + 1)
    # resident Omega/theta past the VMEM budget must raise a clear error
    wide = 300_000
    with pytest.raises(ValueError, match="VMEM"):
        ops.rff_linreg_grad_masked(
            x, jnp.zeros((d, wide), jnp.float32),
            jnp.zeros((wide,), jnp.float32),
            jnp.zeros((wide, c), jnp.float32), y, mask, use_pallas=True)


# --- wrapper padding edges: one below / at / one above the block size ---
#
# Where the zero-padding stays inside a single contraction block, the
# padded terms are exact +0.0 contributions and the Pallas (interpret)
# result must be BIT-EQUAL to the jnp reference — any `_pad_to` /
# `_clamp_block` regression (wrong scale, garbage in the pad, off-by-one
# slicing) breaks exact equality loudly.  One past the block multiple the
# contraction legitimately splits into two accumulation steps, so those
# cases assert tight allclose instead.  The masked gradient contracts
# twice (over q, then over the rows), and XLA associates the reference's
# two dots differently from the kernel's blocked ones, so its in-block
# cases hold to a few f32 ulp of the output's scale rather than bit for
# bit; garbage in the pad or a wrong mask is still orders larger.

_EDGE = (127, 128, 129)   # around the 128-lane block


def _assert_edge(got, want, exact):
    got, want = np.asarray(got), np.asarray(want)
    if exact:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def _assert_f32_close(got, want):
    """Agreement to ~8 f32 ulp of the output's largest magnitude."""
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1.0)
    np.testing.assert_allclose(got / scale, want / scale, rtol=0,
                               atol=8 * np.finfo(np.float32).eps)


@pytest.mark.parametrize("d", _EDGE)
@pytest.mark.parametrize("q", _EDGE)
def test_rff_embed_batched_padding_edges(d, q):
    n, l = 2, 9
    x = _arr((n, l, d))
    omega = _arr((d, q))
    delta = jnp.asarray(RNG.uniform(0, 2 * np.pi, size=(q,)), jnp.float32)
    got = ops.rff_embed_batched(x, omega, delta, use_pallas=True)
    want = jax.vmap(lambda xj: ref.rff_embed(xj, omega, delta))(x)
    _assert_edge(got, want, exact=(d <= 128 and q <= 128))


@pytest.mark.parametrize("l", _EDGE)
@pytest.mark.parametrize("q", _EDGE)
def test_linreg_grad_masked_padding_edges(l, q):
    n, c = 2, 3
    x = _arr((n, l, q), scale=0.3)
    theta = _arr((q, c), scale=0.3)
    y = _arr((n, l, c))
    mask = jnp.asarray((RNG.uniform(size=(n, l)) < 0.7).astype(np.float32))
    got = ops.linreg_grad_masked(x, theta, y, mask, use_pallas=True)
    want = jnp.stack([ref.linreg_grad_masked(x[j], theta, y[j], mask[j])
                      for j in range(n)])
    if l <= 128:
        _assert_f32_close(got, want)
    else:
        _assert_edge(got, want, exact=False)


@pytest.mark.parametrize("l", _EDGE)
@pytest.mark.parametrize("q", (63, 64, 65))
def test_parity_encode_batched_padding_edges(l, q):
    n, u = 2, 24
    g = _arr((n, u, l))
    w = jnp.asarray(RNG.uniform(0.2, 1.0, size=(n, l)), jnp.float32)
    x = _arr((n, l, q), scale=0.5)
    got = ops.parity_encode_batched(g, w, x, use_pallas=True)
    want = jax.vmap(ref.parity_encode)(g, w, x)
    _assert_edge(got, want, exact=(l <= 128))


# --- satellite bugfix pins ---


def test_rff_embed_q_true_guard():
    """q_true=0 must raise, not silently fall back to the padded q."""
    from repro.kernels.rff_embed import rff_embed as kernel
    m, d, q = 128, 128, 128
    x = _arr((m, d))
    omega = _arr((d, q))
    delta = jnp.zeros((q,), jnp.float32)
    with pytest.raises(ValueError, match="q_true"):
        kernel(x, omega, delta, q_true=0)
    with pytest.raises(ValueError, match="q_true"):
        kernel(x, omega, delta, q_true=-3)
    # None still defaults to the (padded) q
    got = kernel(x, omega, delta, q_true=None)
    want = ref.rff_embed(x, omega, delta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_gqa_decode_block_clamp():
    """T=500 with the default bt=512 must clamp to an 8-aligned block (a
    bare min(bt, T) left bt=500, which only interpret mode tolerates)."""
    from repro.kernels.ops import _clamp_block
    assert _clamp_block(512, 500, True) == 504
    assert _clamp_block(512, 500, True) % 8 == 0
    assert _clamp_block(512, 500, False) == 512   # compiled path untouched
    B, H, K, hd, T = 2, 8, 4, 32, 500
    q = _arr((B, H, hd))
    k = _arr((B, T, K, hd), scale=0.3)
    v = _arr((B, T, K, hd))
    kp = jnp.asarray(np.where(RNG.uniform(size=T) < 0.9,
                              np.arange(T), -1), jnp.int32)
    qp = jnp.int32(T - 1)
    got = ops.gqa_decode(q, k, v, kp, qp, use_pallas=True)
    want = ref.gqa_decode(q, k, v, kp, qp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
