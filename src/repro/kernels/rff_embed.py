"""Pallas TPU kernel: fused random-Fourier-feature embedding (paper eq. 18).

    out[i, s] = sqrt(2/q) * cos( sum_k x[i, k] * omega[k, s] + delta[s] )

The matmul runs on the MXU with (bm, bk) x (bk, bq) VMEM tiles; the bias add,
cosine and scale are fused into the final K-step so the (m, q) intermediate
x @ omega never round-trips to HBM.  Grid is (M/bm, Q/bq, D/bk) with the
contraction dimension innermost; the output block is revisited across K steps
and used as the accumulator.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret
from repro.kernels.ref import embed_precision


def _kernel(x_ref, omega_ref, delta_ref, o_ref, *, nk: int, q_true: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(x_ref[...], omega_ref[...],
                          precision=embed_precision(x_ref.dtype),
                          preferred_element_type=o_ref.dtype)

    @pl.when(k == nk - 1)
    def _finalize():
        scale = jnp.array(math.sqrt(2.0 / q_true), dtype=o_ref.dtype)
        o_ref[...] = scale * jnp.cos(o_ref[...] + delta_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("bm", "bq", "bk", "interpret", "q_true"))
def rff_embed(x, omega, delta, *, bm: int = 128, bq: int = 128, bk: int = 128,
              interpret: bool | None = None, q_true: int | None = None):
    """x: (m, d), omega: (d, q), delta: (q,) -> (m, q).  Requires divisibility.

    q_true: the unpadded feature count used in the sqrt(2/q) scale (defaults
    to omega's column count; callers that zero-pad q must pass the original).
    """
    m, d = x.shape
    d2, q = omega.shape
    assert d == d2 and delta.shape == (q,)
    assert m % bm == 0 and q % bq == 0 and d % bk == 0, (m, q, d, bm, bq, bk)
    # explicit None check: `q_true or q` would silently substitute the
    # padded q when a caller passes q_true=0
    if q_true is None:
        q_true = q
    if q_true <= 0:
        raise ValueError(f"q_true must be positive, got {q_true}")
    nk = d // bk
    delta2 = delta.reshape(1, q)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, q_true=q_true),
        grid=(m // bm, q // bq, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bq), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bq), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bq), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, q), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, omega, delta2)
