"""jit'd public wrappers around the Pallas kernels with automatic padding
and a pure-jnp fallback.

`use_pallas=True` runs the Pallas kernels: compiled on a TPU and in
interpret mode elsewhere, unless the caller names `interpret`
(`repro.kernels.backend.resolve_interpret`).  The default entry points
pad inputs up to block multiples, run the kernel, and slice the result
back, so arbitrary shapes are accepted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.backend import resolve_interpret
from repro.kernels.linreg_grad import linreg_grad as _linreg_grad_kernel
from repro.kernels.linreg_grad import \
    linreg_grad_masked as _linreg_grad_masked_kernel
from repro.kernels.parity_encode import parity_encode as _parity_encode_kernel
from repro.kernels.parity_encode import \
    parity_encode_batched as _parity_encode_batched_kernel
from repro.kernels.rff_embed import rff_embed as _rff_embed_kernel
from repro.kernels.rff_linreg_grad import \
    rff_linreg_grad_masked as _rff_linreg_grad_masked_kernel
from repro.kernels.gqa_decode import gqa_decode as _gqa_decode_kernel


# TPU vector lanes: the last (minor) dim of every VMEM tile is 128 wide, so
# narrow label widths c are zero-padded up to a lane multiple before hitting
# the kernel (the hardware pads implicitly anyway; doing it explicitly keeps
# Mosaic from asserting on unsupported minor dims) and sliced back after.
_LANE = 128


def _pad_to(x, mults):
    """Zero-pad each dim of x up to the next multiple of mults[i]."""
    pads = []
    for dim, mult in zip(x.shape, mults):
        rem = (-dim) % mult
        pads.append((0, rem))
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def _clamp_block(block: int, dim: int, interpret: bool | None,
                 mult: int = 8) -> int:
    """Shrink a block size down to the (mult-rounded) dim — interpret only.

    The federated runtime's fused client+parity tensor often has a point
    axis far below the default 128-row block (e.g. l_max = 24); tiling it
    at the default would zero-pad every client row 5x, which interpret mode
    (CPU CI) pays for in real host FLOPs.  On a compiled TPU the defaults
    stay untouched: Mosaic requires 128-multiple lane dims there and the
    hardware pads implicitly anyway.
    """
    if not resolve_interpret(interpret):
        return block
    return max(mult, min(block, -(-dim // mult) * mult))


def rff_embed(x, omega, delta, *, use_pallas: bool = False,
              bm: int = 128, bq: int = 128, bk: int = 128,
              interpret: bool | None = None):
    if not use_pallas:
        return ref.rff_embed(x, omega, delta)
    m, d = x.shape
    q = omega.shape[1]
    xp = _pad_to(x, (bm, bk))
    op = _pad_to(omega, (bk, bq))
    dp = _pad_to(delta, (bq,))
    out = _rff_embed_kernel(xp, op, dp, bm=bm, bq=bq, bk=bk,
                            interpret=interpret, q_true=q)
    return out[:m, :q]


def rff_embed_batched(x_stack, omega, delta, *, use_pallas: bool = False,
                      bm: int = 128, bq: int = 128, bk: int = 128,
                      interpret: bool | None = None):
    """vmap-compatible RFF embedding over a client axis.

    x_stack: (n, l, d), omega: (d, q), delta: (q,) -> (n, l, q).  The jnp
    path vmaps the reference map; the Pallas path flattens the client axis
    into the row dimension so the whole stack is ONE tiled kernel call (one
    padding round instead of n).
    """
    if not use_pallas:
        return jax.vmap(lambda x: ref.rff_embed(x, omega, delta))(x_stack)
    n, l, d = x_stack.shape
    q = omega.shape[1]
    flat = rff_embed(x_stack.reshape(n * l, d), omega, delta,
                     use_pallas=True, bm=bm, bq=bq, bk=bk,
                     interpret=interpret)
    return flat.reshape(n, l, q)


def linreg_grad(x, theta, y, *, use_pallas: bool = False,
                bm: int = 128, bq: int = 128, interpret: bool | None = None):
    if not use_pallas:
        return ref.linreg_grad(x, theta, y)
    m, q = x.shape
    c = theta.shape[1]
    xp = _pad_to(x, (bm, bq))
    tp = _pad_to(theta, (bq, _LANE))
    yp = _pad_to(y, (bm, _LANE))
    out = _linreg_grad_kernel(xp, tp, yp, bm=bm, bq=bq, interpret=interpret)
    return out[:q, :c]


def linreg_grad_masked(x_stack, theta, y_stack, mask, *,
                       use_pallas: bool = False, bm: int = 128, bq: int = 128,
                       interpret: bool | None = None):
    """Per-client row-masked gradients over a dense padded client axis.

    x_stack: (n, l, q), theta: (q, c), y_stack: (n, l, c), mask: (n, l)
    -> (n, q, c) with  g_j = X_j^T diag(mask_j) (X_j theta - Y_j).

    This is the batched engine's hot path: the federated runtime hands over
    its dense mask-padded client tensor — with the global parity set fused
    in as an extra pseudo-client row in the coded scheme — and the whole
    round's gradients come from ONE kernel call (client axis = outermost
    grid dim).  Padding rows carry mask 0, so the caller need not pre-zero
    them; mask entries may be arbitrary per-row *weights* (not just 0/1),
    which is how the coded-gradient 1/u scale rides along.  In interpret
    mode the row block is clamped down to the point axis so short fused
    layouts tile without 5x zero-padding.
    """
    if not use_pallas:
        return jax.vmap(
            lambda x, y, w: ref.linreg_grad_masked(x, theta, y, w))(
                x_stack, y_stack, mask)
    n, l, q = x_stack.shape
    c = theta.shape[1]
    bm = _clamp_block(bm, l, interpret)
    xp = _pad_to(x_stack, (1, bm, bq))
    tp = _pad_to(theta, (bq, _LANE))
    yp = _pad_to(y_stack, (1, bm, _LANE))
    mp = _pad_to(mask, (1, bm))
    out = _linreg_grad_masked_kernel(xp, tp, yp, mp, bm=bm, bq=bq,
                                     interpret=interpret)
    return out[:, :q, :c]


def rff_linreg_grad_masked(x_raw, omega, delta, theta, y_stack, mask, *,
                           parity_phi=None, use_pallas: bool = False,
                           bm: int = 128, bq: int = 128,
                           interpret: bool | None = None):
    """Fused RFF-embed -> per-client masked gradients from RAW features.

    x_raw: (n, l, d) raw client features, omega: (d, q), delta: (q,),
    theta: (q, c), y_stack: (rows, l, c), mask: (rows, l) -> (rows, q, c)
    float32 with  g_j = phi_j^T diag(mask_j) (phi_j theta - Y_j)  and
    phi_j = sqrt(2/q) cos(X_j omega + delta).

    When `parity_phi` (l, q) is given, the coded parity pseudo-client rides
    along as one extra grid row (rows = n + 1): it is already embedded (a
    generator-weighted sum of embedded points lives in q-space), so the
    kernel substitutes it for the in-kernel embed and its mask entries carry
    the coded 1/u scale.  The (n, l, q) embedded tensor is never
    materialized in HBM — this replaces the two-pass rff_embed_batched +
    linreg_grad_masked round path.  bf16 inputs accumulate in f32 and the
    output is float32 either way; the jnp fallback upcasts to f32 up front
    to match.
    """
    n, l, d = x_raw.shape
    q = omega.shape[1]
    c = theta.shape[1]
    rows = n + (1 if parity_phi is not None else 0)
    assert y_stack.shape == (rows, l, c), (y_stack.shape, rows, l, c)
    assert mask.shape == (rows, l), (mask.shape, rows, l)
    if not use_pallas:
        f32 = jnp.float32
        phi = jax.vmap(lambda x: ref.rff_embed(
            x.astype(f32), omega.astype(f32), delta.astype(f32)))(x_raw)
        if parity_phi is not None:
            phi = jnp.concatenate([phi, parity_phi[None].astype(f32)], axis=0)
        return jax.vmap(lambda x, y, w: ref.linreg_grad_masked(
            x, theta.astype(f32), y.astype(f32), w.astype(f32)))(
                phi, y_stack, mask)
    bm = _clamp_block(bm, l, interpret)
    xp = _pad_to(x_raw, (1, bm, _LANE))
    if parity_phi is not None:
        # the parity grid row never embeds, but its raw-x block is still
        # fetched by the BlockSpec — give it a zero dummy row
        xp = jnp.concatenate([xp, jnp.zeros_like(xp[:1])], axis=0)
    op = _pad_to(omega, (_LANE, bq))
    dp = _pad_to(delta, (bq,))
    tp = _pad_to(theta, (bq, _LANE))
    yp = _pad_to(y_stack, (1, bm, _LANE))
    mp = _pad_to(mask, (1, bm))
    lp, qp = xp.shape[1], op.shape[1]
    if parity_phi is not None:
        pp = _pad_to(parity_phi, (bm, bq))[None]
    else:
        pp = jnp.zeros((1, lp, qp), x_raw.dtype)
    out = _rff_linreg_grad_masked_kernel(xp, op, dp, tp, yp, mp, pp,
                                         n_real=n, bm=bm, bq=bq,
                                         interpret=interpret, q_true=q)
    return out[:, :q, :c]


def linreg_grad_batched(x_stack, theta, y_stack, *, use_pallas: bool = False,
                        bm: int = 128, bq: int = 128, interpret: bool | None = None):
    """Per-client gradients over a dense client axis.

    x_stack: (n, l, q), theta: (q, c), y_stack: (n, l, c) -> (n, q, c).
    The jnp path vmaps the reference kernel (one fused batched matmul); the
    Pallas path is the masked batched kernel with an all-ones mask, i.e. one
    tiled kernel call for all n clients.
    """
    if not use_pallas:
        return jax.vmap(lambda x, y: ref.linreg_grad(x, theta, y))(
            x_stack, y_stack)
    mask = jnp.ones(x_stack.shape[:2], x_stack.dtype)
    return linreg_grad_masked(x_stack, theta, y_stack, mask, use_pallas=True,
                              bm=bm, bq=bq, interpret=interpret)


def parity_encode(g, w, x, *, use_pallas: bool = False,
                  bu: int = 128, bq: int = 128, bl: int = 128,
                  interpret: bool | None = None):
    if not use_pallas:
        return ref.parity_encode(g, w, x)
    u, l = g.shape
    q = x.shape[1]
    gp = _pad_to(g, (bu, bl))
    wp = _pad_to(w, (bl,))
    xp = _pad_to(x, (bl, bq))
    out = _parity_encode_kernel(gp, wp, xp, bu=bu, bq=bq, bl=bl,
                                interpret=interpret)
    return out[:u, :q]


def parity_encode_batched(g_stack, w_stack, x_stack, *,
                          use_pallas: bool = False, bu: int = 128,
                          bq: int = 128, bl: int = 128,
                          interpret: bool | None = None):
    """All-clients parity encode over a dense client axis.

    g_stack: (n, u, l), w_stack: (n, l), x_stack: (n, l, q) -> (n, u, q)
    with  parity_j = G_j diag(w_j) X_j.  The jnp path vmaps the reference
    kernel; the Pallas path is ONE tiled kernel launch with the client axis
    as the outermost grid dimension (in interpret mode, row blocks are
    clamped to the true u so small populations don't pad up to 128).
    """
    if not use_pallas:
        return jax.vmap(ref.parity_encode)(g_stack, w_stack, x_stack)
    n, u, l = g_stack.shape
    q = x_stack.shape[2]
    bu = _clamp_block(bu, u, interpret)
    gp = _pad_to(g_stack, (1, bu, bl))
    wp = _pad_to(w_stack, (1, bl))
    xp = _pad_to(x_stack, (1, bl, bq))
    out = _parity_encode_batched_kernel(gp, wp, xp, bu=bu, bq=bq, bl=bl,
                                        interpret=interpret)
    return out[:, :u, :q]


def gqa_decode(q, k, v, k_pos, q_pos, *, window: int = 0,
               use_pallas: bool = False, bt: int = 512,
               interpret: bool | None = None):
    if not use_pallas:
        return ref.gqa_decode(q, k, v, k_pos, q_pos, window)
    T = k.shape[1]
    # clamp through the 8-multiple helper: a bare min(bt, T) can leave a
    # non-multiple-of-8 block (T=500 -> bt=500) that only interpret mode
    # tolerates; _clamp_block rounds to an aligned tile and leaves the
    # compiled path's block untouched
    bt = _clamp_block(bt, T, interpret)
    rem = (-T) % bt
    if rem:
        k = jnp.pad(k, ((0, 0), (0, rem), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, rem), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, rem), constant_values=-1)
    return _gqa_decode_kernel(q, k, v, k_pos, q_pos, bt=bt, window=window,
                              interpret=interpret)
