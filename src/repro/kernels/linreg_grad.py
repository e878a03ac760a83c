"""Pallas TPU kernel: fused linear-regression gradient (paper eq. 7/10/28).

    g = X^T (X @ theta - Y)        X: (m, q), theta: (q, c), Y: (m, c)

This is the compute hot-spot of every CodedFedL training round (client
partial gradients AND the server's coded gradient share this form).  The
kernel streams row-blocks of X through VMEM once: for each M-block it forms
the residual R = X_blk @ theta - Y_blk in VMEM scratch, then accumulates
X_blk^T @ R into the (q, c) output without materializing the (m, c) residual
in HBM.  Grid (M/bm, Q/bq); the residual is computed once per M-block (at
j == 0) using a full-q view of the X row-block, and the output accumulates
across M steps (revisited output block).

`linreg_grad_masked` is the batched variant the federated runtime's scan
engine feeds with its dense mask-padded (n, l_max, q) client tensor: the
client axis becomes the outermost grid dimension and the mask is fused
into the residual, so padded rows contribute exactly zero even when the
caller did not pre-zero them.  Mask entries are general per-row *weights*,
not just 0/1 validity bits — the runtime's fused coded round appends the
global parity set as an (n+1)-th pseudo-client row whose mask carries the
coded-gradient 1/(u (1-pnr_C)) scale, so the whole round (client gradients
+ coded gradient) is ONE launch of this kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.backend import resolve_interpret

# A v5e core has 128 MiB of VMEM, but Mosaic gives one kernel a scoped
# 16 MiB of it unless the call raises `vmem_limit_bytes`; these kernels
# keep that default, so their working set must fit 16 MiB.
_VMEM_BUDGET_BYTES = 16 * 1024 * 1024

#: lane width a (rows, 1) VMEM column is padded out to
_LANE = 128


def _check_c_fits_vmem(q: int, c: int, bm: int, bq: int, dtype) -> None:
    """Validate that the label width `c` leaves the kernel's VMEM working
    set inside the budget.

    Pallas double-buffers every block whose index moves over the grid —
    the full-q X row block (bm, q), the labels (bm, c), the masked
    variant's weight column (bm, 1) padded to a lane, the X^T side tile
    (bm, bq) and the output tile (bq, c) — and holds grid-invariant
    theta (q, c) and the residual scratch (bm, c) once.  This matches
    the scoped VMEM the TPU compiler reports for these kernels to within
    a few tiles, so a large c (or q) gets a clear, actionable error here
    instead of an opaque compiler one.
    """
    itemsize = jnp.dtype(dtype).itemsize
    streamed = bm * q + bm * c + bm * _LANE + bm * bq + bq * c
    resident = q * c + bm * c
    nbytes = (2 * streamed + resident) * itemsize
    if nbytes > _VMEM_BUDGET_BYTES:
        raise ValueError(
            f"linreg_grad: label width c={c} with q={q}, bm={bm}, bq={bq} "
            f"({jnp.dtype(dtype).name}) needs ~{nbytes / 2**20:.1f} MiB of "
            f"VMEM (double-buffered X/label/output tiles + theta), over the "
            f"{_VMEM_BUDGET_BYTES / 2**20:.0f} MiB scoped kernel budget. "
            "Split the label columns into <=128-wide chunks or shrink "
            "bm/bq.")


def _kernel(xfull_ref, theta_ref, y_ref, xblk_ref, o_ref, r_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _residual():
        r_ref[...] = (jnp.dot(xfull_ref[...], theta_ref[...],
                              preferred_element_type=r_ref.dtype)
                      - y_ref[...])

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(xblk_ref[...].T, r_ref[...],
                          preferred_element_type=o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bq", "interpret"))
def linreg_grad(x, theta, y, *, bm: int = 128, bq: int = 128,
                interpret: bool | None = None):
    """g = X^T (X theta - Y): (m, q), (q, c), (m, c) -> (q, c)."""
    m, q = x.shape
    q2, c = theta.shape
    assert q == q2 and y.shape == (m, c)
    assert m % bm == 0 and q % bq == 0, (m, q, bm, bq)
    _check_c_fits_vmem(q, c, bm, bq, x.dtype)
    return pl.pallas_call(
        _kernel,
        grid=(m // bm, q // bq),
        in_specs=[
            pl.BlockSpec((bm, q), lambda i, j: (i, 0)),     # full-q row block
            pl.BlockSpec((q, c), lambda i, j: (0, 0)),      # theta resident
            pl.BlockSpec((bm, c), lambda i, j: (i, 0)),     # labels row block
            pl.BlockSpec((bm, bq), lambda i, j: (i, j)),    # X^T side tile
        ],
        out_specs=pl.BlockSpec((bq, c), lambda i, j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((q, c), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, c), x.dtype)],
        interpret=resolve_interpret(interpret),
    )(x, theta, y, x)


def _masked_kernel(xfull_ref, theta_ref, y_ref, mask_ref, xblk_ref, o_ref,
                   r_ref):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _residual():
        r = (jnp.dot(xfull_ref[0], theta_ref[...],
                     preferred_element_type=r_ref.dtype)
             - y_ref[0])
        r_ref[...] = r * mask_ref[0].astype(r_ref.dtype)     # (bm, 1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(xblk_ref[0].T, r_ref[...],
                          preferred_element_type=o_ref.dtype)[None]


@functools.partial(jax.jit, static_argnames=("bm", "bq", "interpret"))
def linreg_grad_masked(x, theta, y, mask, *, bm: int = 128, bq: int = 128,
                       interpret: bool | None = None):
    """Per-client masked gradients:  g_j = X_j^T diag(mask_j) (X_j theta - Y_j).

    x: (n, l, q), theta: (q, c), y: (n, l, c), mask: (n, l) -> (n, q, c).
    Grid (n, L/bm, Q/bq): the client axis is outermost, so one kernel call
    covers the whole dense mask-padded client tensor of the batched engine
    (including the fused parity pseudo-client row in the coded scheme).
    The mask multiplies the residual — rows with mask 0 contribute exactly
    zero regardless of the padded x/y contents, and fractional entries act
    as per-row gradient weights (the coded 1/u scale).
    """
    n, l, q = x.shape
    q2, c = theta.shape
    assert q == q2 and y.shape == (n, l, c) and mask.shape == (n, l)
    assert l % bm == 0 and q % bq == 0, (n, l, q, bm, bq)
    _check_c_fits_vmem(q, c, bm, bq, x.dtype)
    return pl.pallas_call(
        _masked_kernel,
        grid=(n, l // bm, q // bq),
        in_specs=[
            pl.BlockSpec((1, bm, q), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((q, c), lambda b, i, j: (0, 0)),
            pl.BlockSpec((1, bm, c), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bm, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bm, bq), lambda b, i, j: (b, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bq, c), lambda b, i, j: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, q, c), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, c), x.dtype)],
        interpret=resolve_interpret(interpret),
    )(x, theta, y, mask.reshape(n, l, 1), x)
