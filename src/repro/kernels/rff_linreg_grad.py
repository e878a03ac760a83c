"""Pallas TPU kernel: fused RFF-embed -> masked linear-regression gradient.

    phi_b   = sqrt(2/q) * cos(X_b @ Omega + delta)          (paper eq. 18)
    g_b     = phi_b^T diag(mask_b) (phi_b @ theta - Y_b)    (paper eq. 7/10)

This fuses the two passes the round path used to launch separately
(`rff_embed` then `linreg_grad_masked`): the RAW client features
(rows, L, d) stay resident in HBM and the embedded (rows, L, q) tensor is
never materialized there — each (bm, q) row-block of phi is computed
in-kernel into VMEM scratch, consumed for the residual and the q-block
transposed accumulations, and discarded.

Grid (rows, L/bm, Q/bq) with the q-block axis innermost, mirroring
`linreg_grad_masked`: at j == 0 the kernel embeds the (bm, d) raw row
block against the resident Omega (one MXU contraction over the full d
axis — Mosaic tiles the K loop internally), adds delta, applies the
cos + sqrt(2/q) finalization, and forms the masked residual
R = phi @ theta - Y in scratch; each j-step then accumulates
phi[:, j-block]^T @ R into the revisited (q, c) output block.

The coded parity pseudo-client row rides along in the SAME grid: parity
rows live in embedded q-space already (they are generator-weighted sums
of embedded points), so a pre-embedded (L, q) `pphi` input substitutes
for the in-kernel embed on grid rows b >= n_real.  Its mask entries carry
the 1/(u (1-pnr_C)) coded-gradient scale exactly as in the two-pass fused
layout, so one launch still produces the whole round's gradients.

Dtypes: with float32 inputs everything runs in f32.  With bfloat16
inputs (x/omega/delta/theta/y), the embed matmul, cosine, residual and
output accumulate in float32 (`preferred_element_type`) and the output
is float32 in both variants — the bf16 variant halves the streamed-input
HBM traffic without giving up gradient accumulation precision.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.kernels.linreg_grad import _LANE, _VMEM_BUDGET_BYTES
from repro.kernels.ref import embed_precision

_ACC = jnp.float32


def _check_fused_vmem(d: int, q: int, c: int, bm: int, bq: int,
                      in_dtype) -> None:
    """Clear error when the VMEM working set cannot fit the budget.

    Blocks whose index moves over the grid are double-buffered: the raw
    row block (bm, d), labels (bm, c), the weight column (bm, 1) padded
    to a lane, the parity row block (bm, q) and the f32 (bq, c) output
    tile.  Grid-invariant Omega (d, q), delta (padded to 8 sublanes) and
    theta (q, c) are held once, as are the f32 phi (bm, q) and residual
    (bm, c) scratch.  With f32 inputs the embed matmul runs at full f32
    (`ref.embed_precision`), and Mosaic's scratch for splitting its
    operands into bf16 parts grows like four f32 copies of the (bm, d)
    row block.  Checked against the TPU compiler for v5e at bm in
    {128, 256, 512} and q in {512, ..., 4096}: the estimate never passes
    a shape the compiler refuses, and at bm=128, q=2048 (the paper's
    round) it refuses exactly what the compiler refuses.
    """
    in_size = jnp.dtype(in_dtype).itemsize
    acc_size = jnp.dtype(_ACC).itemsize
    streamed_in = bm * d + bm * c + bm * _LANE + bm * q
    split = 4 * bm * d if embed_precision(in_dtype) is not None else 0
    nbytes = ((2 * streamed_in + d * q + 8 * q + q * c) * in_size
              + (2 * bq * c + bm * q + bm * c + split) * acc_size)
    if nbytes > _VMEM_BUDGET_BYTES:
        raise ValueError(
            f"rff_linreg_grad: working set for d={d}, q={q}, "
            f"c={c}, bm={bm}, bq={bq} ({jnp.dtype(in_dtype).name} inputs) "
            f"needs ~{nbytes / 2**20:.1f} MiB of VMEM (Omega + theta + phi "
            f"scratch + double-buffered row blocks), over the "
            f"{_VMEM_BUDGET_BYTES / 2**20:.0f} MiB scoped kernel budget. "
            "Shrink q/d or fall back to the two-pass "
            "rff_embed + linreg_grad_masked path.")


def _kernel(x_ref, omega_ref, delta_ref, theta_ref, y_ref, mask_ref,
            pphi_ref, o_ref, phi_ref, r_ref, *, n_real: int, q_true: int,
            bq: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _embed_and_residual():
        # phi row block for this (client, row-block): embedded on the fly
        # for real clients, read pre-embedded for the parity pseudo-row
        @pl.when(b < n_real)
        def _embed():
            acc = jnp.dot(x_ref[0], omega_ref[...],
                          precision=embed_precision(x_ref.dtype),
                          preferred_element_type=_ACC)
            scale = jnp.array(math.sqrt(2.0 / q_true), _ACC)
            phi_ref[...] = scale * jnp.cos(acc + delta_ref[...].astype(_ACC))

        @pl.when(b >= n_real)
        def _parity():
            phi_ref[...] = pphi_ref[0].astype(_ACC)

        r = (jnp.dot(phi_ref[...], theta_ref[...].astype(_ACC),
                     preferred_element_type=_ACC)
             - y_ref[0].astype(_ACC))
        r_ref[...] = r * mask_ref[0].astype(_ACC)             # (bm, 1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    phi_blk = phi_ref[:, pl.ds(j * bq, bq)]
    o_ref[...] += jnp.dot(phi_blk.T, r_ref[...],
                          preferred_element_type=o_ref.dtype)[None]


@functools.partial(jax.jit, static_argnames=("bm", "bq", "interpret",
                                             "n_real", "q_true"))
def rff_linreg_grad_masked(x_raw, omega, delta, theta, y, mask, pphi, *,
                           n_real: int, bm: int = 128, bq: int = 128,
                           interpret: bool | None = None,
                           q_true: int | None = None):
    """Fused embed->gradient over a dense padded client axis.

    x_raw: (rows, L, d) raw features (rows beyond n_real are dummies whose
    blocks are fetched but never read), omega: (d, q), delta: (q,),
    theta: (q, c), y: (rows, L, c), mask: (rows, L), pphi: (1, L, q)
    -> (rows, q, c) float32 with

      g_b = phi_b^T diag(mask_b) (phi_b theta - Y_b),
      phi_b = sqrt(2/q_true) cos(X_b omega + delta)   for b <  n_real,
      phi_b = pphi[0]                                 for b >= n_real.

    Requires block divisibility on L/q (ops.rff_linreg_grad_masked pads);
    `q_true` is the unpadded feature count feeding the sqrt(2/q) scale.
    Mask entries are per-row weights (the parity row carries the coded
    1/u scale); rows with mask 0 contribute exactly zero, so padded rows
    need not be pre-zeroed.  Output is float32 for bf16 inputs too (the
    accumulator dtype).
    """
    rows, L, d = x_raw.shape
    d2, q = omega.shape
    q2, c = theta.shape
    assert d == d2 and q == q2 and delta.shape == (q,)
    assert y.shape == (rows, L, c) and mask.shape == (rows, L)
    assert pphi.shape == (1, L, q)
    assert L % bm == 0 and q % bq == 0, (rows, L, q, bm, bq)
    if q_true is None:
        q_true = q
    if q_true <= 0:
        raise ValueError(f"q_true must be positive, got {q_true}")
    if not 0 <= n_real <= rows:
        raise ValueError(f"n_real={n_real} out of range for rows={rows}")
    _check_fused_vmem(d, q, c, bm, bq, x_raw.dtype)
    delta2 = delta.reshape(1, q)
    return pl.pallas_call(
        functools.partial(_kernel, n_real=n_real, q_true=q_true, bq=bq),
        grid=(rows, L // bm, q // bq),
        in_specs=[
            pl.BlockSpec((1, bm, d), lambda b, i, j: (b, i, 0)),   # raw rows
            pl.BlockSpec((d, q), lambda b, i, j: (0, 0)),          # Omega
            pl.BlockSpec((1, q), lambda b, i, j: (0, 0)),          # delta
            pl.BlockSpec((q, c), lambda b, i, j: (0, 0)),          # theta
            pl.BlockSpec((1, bm, c), lambda b, i, j: (b, i, 0)),   # labels
            pl.BlockSpec((1, bm, 1), lambda b, i, j: (b, i, 0)),   # weights
            pl.BlockSpec((1, bm, q), lambda b, i, j: (0, i, 0)),   # parity phi
        ],
        out_specs=pl.BlockSpec((1, bq, c), lambda b, i, j: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, q, c), _ACC),
        scratch_shapes=[pltpu.VMEM((bm, q), _ACC),
                        pltpu.VMEM((bm, c), _ACC)],
        interpret=resolve_interpret(interpret),
    )(x_raw, omega, delta2, theta, y, mask.reshape(rows, L, 1), pphi)
