"""Pallas TPU kernel: fused local parity encoding (paper eq. 19).

    parity = G @ diag(w) @ X       G: (u, l), w: (l,), X: (l, q)

Each client runs this once over its (transformed) local dataset to produce
its parity set.  The diagonal weighting is fused into the generator tile in
VMEM (G_tile * w_tile) so diag(w) @ X is never materialized.  Grid
(U/bu, Q/bq, L/bl) with the contraction dim innermost; the output block
accumulates across L steps.

`parity_encode_batched` is the all-clients variant the federated runtime's
coded setup feeds: the client axis becomes the outermost grid dimension
(like `linreg_grad_masked`), so all n local parity sets come from ONE tiled
kernel launch instead of a per-client Python loop.  Block-for-block it runs
the same dots in the same order as n single-client calls, so the two are
bit-identical.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret


def _kernel(g_ref, w_ref, x_ref, o_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    gw = g_ref[...] * w_ref[...]                 # (bu, bl) * (1, bl)
    o_ref[...] += jnp.dot(gw, x_ref[...], preferred_element_type=o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bu", "bq", "bl", "interpret"))
def parity_encode(g, w, x, *, bu: int = 128, bq: int = 128, bl: int = 128,
                  interpret: bool | None = None):
    """(u, l), (l,), (l, q) -> (u, q).  Requires block divisibility."""
    u, l = g.shape
    l2, q = x.shape
    assert l == l2 and w.shape == (l,)
    assert u % bu == 0 and q % bq == 0 and l % bl == 0, (u, l, q, bu, bq, bl)
    nk = l // bl
    w2 = w.reshape(1, l)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(u // bu, q // bq, nk),
        in_specs=[
            pl.BlockSpec((bu, bl), lambda i, j, k: (i, k)),
            pl.BlockSpec((1, bl), lambda i, j, k: (0, k)),
            pl.BlockSpec((bl, bq), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bu, bq), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((u, q), g.dtype),
        interpret=resolve_interpret(interpret),
    )(g, w2, x)


def _batched_kernel(g_ref, w_ref, x_ref, o_ref):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    gw = g_ref[0] * w_ref[0]                     # (bu, bl) * (1, bl)
    o_ref[...] += jnp.dot(gw, x_ref[0],
                          preferred_element_type=o_ref.dtype)[None]


@functools.partial(jax.jit, static_argnames=("bu", "bq", "bl", "interpret"))
def parity_encode_batched(g, w, x, *, bu: int = 128, bq: int = 128,
                          bl: int = 128, interpret: bool | None = None):
    """All-clients parity encode: (n, u, l), (n, l), (n, l, q) -> (n, u, q).

    Grid (n, U/bu, Q/bq, L/bl): the client axis is outermost, so the whole
    population's parity sets come from one kernel launch.  Requires block
    divisibility on u/q/l (ops.parity_encode_batched pads).
    """
    n, u, l = g.shape
    n2, l2, q = x.shape
    assert n == n2 and l == l2 and w.shape == (n, l)
    assert u % bu == 0 and q % bq == 0 and l % bl == 0, (u, l, q, bu, bq, bl)
    w3 = w.reshape(n, 1, l)
    return pl.pallas_call(
        _batched_kernel,
        grid=(n, u // bu, q // bq, l // bl),
        in_specs=[
            pl.BlockSpec((1, bu, bl), lambda b, i, j, k: (b, i, k)),
            pl.BlockSpec((1, 1, bl), lambda b, i, j, k: (b, 0, k)),
            pl.BlockSpec((1, bl, bq), lambda b, i, j, k: (b, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bu, bq), lambda b, i, j, k: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((n, u, q), g.dtype),
        interpret=resolve_interpret(interpret),
    )(g, w3, x)
