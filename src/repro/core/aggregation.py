"""Coded federated aggregation (paper §III-E).

Per round r+1:
  - client j (if it returns by t*) contributes the unnormalized partial
    gradient over its l*_j processed points:  X~_j^T (X~_j theta - Y~_j)
  - the MEC compute unit contributes the coded gradient over the global
    parity set, weighted by 1/(1 - pnr_C):
        g_C = 1/(1-pnr_C) * Xv^T (Xv theta - Yv)           (eq. 28)
  - the server aggregates  g_M = (g_C + g_U) / m            (eq. 30)

E[g_M] ~= g, the full gradient over the entire decentralized dataset
(eq. 31/32), because the W_j weighting built the parity data to carry
exactly the *expected missing mass* of each data point.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import ops


def batched_client_gradients(x_stack, y_stack, theta, *, mask=None,
                             use_pallas: bool = False,
                             interpret: bool | None = None):
    """All-client unnormalized gradients in one call.

    x_stack: (n, l, q), y_stack: (n, l, c), theta: (q, c) -> (n, q, c).
    Rows padded with zeros contribute exactly zero (x_k = 0 makes the
    per-point gradient x_k (x_k theta - y_k)^T vanish), so callers may pass
    dense mask-padded subsets.  Passing the (n, l) validity `mask` instead
    routes through the fused masked kernel, which also tolerates un-zeroed
    padding; with `use_pallas` the whole stack is one tiled Pallas call
    (interpret mode on CPU, compiled on TPU).
    """
    if mask is not None:
        return ops.linreg_grad_masked(x_stack, theta, y_stack, mask,
                                      use_pallas=use_pallas,
                                      interpret=interpret)
    return ops.linreg_grad_batched(x_stack, theta, y_stack,
                                   use_pallas=use_pallas,
                                   interpret=interpret)


def masked_gradient_sum(client_grads, returned_mask):
    """sum_j 1{T_j<=t*} g_j over a dense (n, q, c) gradient stack.

    returned_mask: (n,) bool/float — fused multiply-add, no Python loop.
    """
    mask = jnp.asarray(returned_mask, client_grads.dtype)[:, None, None]
    return jnp.sum(client_grads * mask, axis=0)


def fused_client_parity_tensors(sub_x, sub_y, mask, parity_x, parity_y, *,
                                pnr_c: float = 0.0,
                                l_target: int | None = None):
    """Append the global parity set as an (n+1)-th pseudo-client row.

    sub_x: (n, l_max, q), sub_y: (n, l_max, c), mask: (n, l_max) validity;
    parity_x: (u, q), parity_y: (u, c).  Returns (fx, fy, fmask) of shapes
    ((n+1, L, q), (n+1, L, c), (n+1, L)) with L = max(l_max, u, l_target).

    The coded-gradient scale 1/(u (1-pnr_C)) (eq. 28, incl. the G^T G / u
    concentration of eq. 31) is folded into the parity row's mask entries:
    `linreg_grad_masked` multiplies the residual by the mask, so the parity
    row yields  Xv^T ((Xv theta - Yv) / (u (1-pnr_C)))  — exactly the coded
    gradient — from the SAME kernel call that produces the n client
    gradients.  Zero-mask padding contributes exactly nothing, so rows of
    different true lengths tile together.  `l_target` pads L further so
    deployments with different loads stack along a sweep axis.
    """
    n, l_max, q = sub_x.shape
    c = sub_y.shape[-1]
    u = parity_x.shape[0]
    L = max(l_max, u, l_target or 1)
    fx = jnp.zeros((n + 1, L, q), sub_x.dtype)
    fy = jnp.zeros((n + 1, L, c), sub_y.dtype)
    # the mask must be floating so the fractional parity scale survives —
    # a bool/int validity mask would truncate 1/u to 1 or 0
    mask = jnp.asarray(mask, sub_x.dtype)
    fmask = jnp.zeros((n + 1, L), mask.dtype)
    fx = fx.at[:n, :l_max].set(sub_x).at[n, :u].set(parity_x)
    fy = fy.at[:n, :l_max].set(sub_y).at[n, :u].set(parity_y)
    scale = 1.0 / (u * (1.0 - pnr_c))
    fmask = fmask.at[:n, :l_max].set(mask).at[n, :u].set(scale)
    return fx, fy, fmask


def fused_embed_client_gradients(x_raw, y_stack, omega, delta, theta, *,
                                 mask, parity_phi=None,
                                 use_pallas: bool = False,
                                 interpret: bool | None = None):
    """All-client gradients straight from RAW features in one fused call.

    x_raw: (n, l, d) raw features, y_stack: (rows, l, c), mask: (rows, l)
    -> (rows, q, c): the RFF embedding phi(X) = sqrt(2/q) cos(X Omega +
    delta) is computed inside the gradient kernel, so the (n, l, q)
    embedded tensor is never materialized.  With `parity_phi` (l, q) the
    coded parity pseudo-client (already in embedded q-space) rides along
    as row n (rows = n + 1); its mask entries must carry the coded
    1/(u (1-pnr_C)) scale, exactly like `fused_client_parity_tensors`.
    """
    return ops.rff_linreg_grad_masked(
        x_raw, omega, delta, theta, y_stack, mask, parity_phi=parity_phi,
        use_pallas=use_pallas, interpret=interpret)


def fused_embed_client_parity_tensors(sub_x_raw, sub_y, mask, parity_x,
                                      parity_y, *, pnr_c: float = 0.0,
                                      l_target: int | None = None):
    """Raw-space analogue of `fused_client_parity_tensors`.

    sub_x_raw: (n, l_max, d) RAW features, sub_y: (n, l_max, c), mask:
    (n, l_max); parity_x: (u, q) EMBEDDED parity rows, parity_y: (u, c).
    Returns (fx, fy, fmask, pphi) with fx: (n, L, d) raw client rows only
    (the fused kernel appends the parity grid row itself), fy/fmask:
    (n+1, L, ·) carrying the parity labels and its 1/(u (1-pnr_C))-scaled
    mask row, and pphi: (L, q) the pre-embedded parity block the kernel
    substitutes for the in-kernel embed on the parity row.
    L = max(l_max, u, l_target).
    """
    n, l_max, d = sub_x_raw.shape
    c = sub_y.shape[-1]
    u, q = parity_x.shape
    L = max(l_max, u, l_target or 1)
    fx = jnp.zeros((n, L, d), sub_x_raw.dtype).at[:, :l_max].set(sub_x_raw)
    fy = jnp.zeros((n + 1, L, c), sub_y.dtype)
    mask = jnp.asarray(mask, fy.dtype)
    fmask = jnp.zeros((n + 1, L), mask.dtype)
    fy = fy.at[:n, :l_max].set(sub_y).at[n, :u].set(parity_y)
    scale = 1.0 / (u * (1.0 - pnr_c))
    fmask = fmask.at[:n, :l_max].set(mask).at[n, :u].set(scale)
    pphi = jnp.zeros((L, q), parity_x.dtype).at[:u].set(parity_x)
    return fx, fy, fmask, pphi


def client_gradient(x, y, theta, *, use_pallas: bool = False):
    """Unnormalized partial gradient X^T (X theta - Y) over processed points."""
    return ops.linreg_grad(x, theta, y, use_pallas=use_pallas)


def coded_gradient(parity_x, parity_y, theta, pnr_c: float = 0.0,
                   *, use_pallas: bool = False, interpret: bool | None = None):
    """g_C over the global parity set (eq. 28).

        g_C = 1/(1-pnr_C) * (1/u) * Xv^T (Xv theta - Yv)

    The 1/u factor realizes the G^T G / u -> I concentration (eq. 31):
    E[(1/u) Xv^T(Xv theta - Yv)] = X^^T W^T W (X^ theta - Y), i.e. the SUM
    over data points of the expected-missing-mass-weighted per-point
    gradients — commensurate with the clients' unnormalized sums.
    """
    u = parity_x.shape[0]
    g = ops.linreg_grad(parity_x, theta, parity_y, use_pallas=use_pallas,
                        interpret=interpret)
    return g / (u * (1.0 - pnr_c))


def federated_gradient(coded_g, client_grads, returned_mask, m: int,
                       l2_reg: float = 0.0, theta=None):
    """g_M = (g_C + sum_j 1{T_j<=t*} g_j) / m  (+ optional L2 term).

    coded_g: (q, c) or None (coded unit straggled this round / uncoded run)
    client_grads: list of (q, c) unnormalized client gradients
    returned_mask: bool per client — whether it arrived by the deadline
    """
    total = jnp.zeros_like(client_grads[0] if client_grads else coded_g)
    for g, ret in zip(client_grads, returned_mask):
        total = total + jnp.where(ret, g, jnp.zeros_like(g))
    if coded_g is not None:
        total = total + coded_g
    g_m = total / m
    if l2_reg and theta is not None:
        g_m = g_m + l2_reg * theta
    return g_m
