"""Distributed encoding of local datasets into parity data (paper §III-B/D).

Client j:
  - draws a PRIVATE generator G_j in R^{u x l_j}, entries iid mean-0 var-1
    (normal or Rademacher);
  - builds the diagonal weight matrix W_j from the no-return probabilities:
      w_{j,k} = sqrt(1 - P(T_j <= t*))  if point k is in the processed subset
      w_{j,k} = 1                        otherwise (never evaluated locally)
    (paper §III-D: pnr_{j,2} = 1 for unprocessed points);
  - ships (X~_j, Y~_j) = (G_j W_j X^_j, G_j W_j Y_j) to the server.

Server: sums the n local parity sets -> global parity dataset (eq. 20/21).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def generator_matrix(key, u: int, l: int, kind: str = "normal"):
    """Private random generator G_j with iid mean-0 var-1 entries."""
    if kind == "normal":
        return jax.random.normal(key, (u, l), jnp.float32)
    if kind == "rademacher":
        return (2.0 * jax.random.bernoulli(key, 0.5, (u, l)) - 1.0).astype(jnp.float32)
    raise ValueError(kind)


def weight_vector(l: int, processed_idx: np.ndarray, p_return: float) -> np.ndarray:
    """Diagonal of W_j (paper §III-D).

    processed_idx: indices of the l*_j points the client will process.
    p_return: P(T_j <= t*) for this client.
    """
    w = np.ones(l, dtype=np.float32)                    # sqrt(pnr=1) = 1
    w[processed_idx] = np.sqrt(1.0 - p_return)          # sqrt(pnr_{j,1})
    return w


@dataclasses.dataclass
class LocalParity:
    x: jnp.ndarray    # (u, q)
    y: jnp.ndarray    # (u, c)


def encode_local(key, x_hat, y, w, u: int, *, kind: str = "normal",
                 use_pallas: bool = False) -> LocalParity:
    """Local parity dataset (X~_j, Y~_j) = (G_j W_j X^_j, G_j W_j Y_j)."""
    l = x_hat.shape[0]
    g = generator_matrix(key, u, l, kind)
    w = jnp.asarray(w)
    px = ops.parity_encode(g, w, x_hat, use_pallas=use_pallas)
    py = ops.parity_encode(g, w, y, use_pallas=use_pallas)
    return LocalParity(x=px, y=py)


def encode_local_batched(keys, x_stack, y_stack, w_stack, u: int, *,
                         kind: str = "normal",
                         use_pallas: bool = False,
                         interpret: bool | None = None) -> LocalParity:
    """All-clients parity encode in one batched call.

    keys: (n,) stacked PRNG keys (one per client, identical to what a
    sequential `jax.random.split` chain would hand each client, so the
    parity sets match `encode_local` exactly);
    x_stack: (n, l, q); y_stack: (n, l, c); w_stack: (n, l).
    Returns stacked LocalParity with x: (n, u, q), y: (n, u, c).

    The jnp path vmaps the reference encode; `use_pallas` runs the whole
    population through ONE tiled `parity_encode_batched` kernel launch per
    array (client axis = outermost grid dim) — bit-identical to a
    per-client `encode_local` loop, without its n Python-level kernel
    launches and padding rounds.
    """
    l = x_stack.shape[1]
    if use_pallas:
        g_stack = jax.vmap(
            lambda k: generator_matrix(k, u, l, kind))(keys)
        w_stack = jnp.asarray(w_stack)
        px = ops.parity_encode_batched(g_stack, w_stack,
                                       jnp.asarray(x_stack),
                                       use_pallas=True, interpret=interpret)
        py = ops.parity_encode_batched(g_stack, w_stack,
                                       jnp.asarray(y_stack),
                                       use_pallas=True, interpret=interpret)
        return LocalParity(x=px, y=py)

    def one(key, x, y, w):
        g = generator_matrix(key, u, x.shape[0], kind)
        return ref.parity_encode(g, w, x), ref.parity_encode(g, w, y)

    px, py = jax.vmap(one)(keys, jnp.asarray(x_stack), jnp.asarray(y_stack),
                           jnp.asarray(w_stack))
    return LocalParity(x=px, y=py)


def aggregate_parity_stacked(parity: LocalParity) -> LocalParity:
    """Global parity set from a stacked (n, u, ·) LocalParity (eq. 20)."""
    return LocalParity(x=jnp.sum(parity.x, axis=0), y=jnp.sum(parity.y, axis=0))


def aggregate_parity(parities: list[LocalParity]) -> LocalParity:
    """Global parity set = sum over clients (paper eq. 20).

    On a pod this is a psum over the `data` axis; here (host simulation of
    the MEC server) it is a tree-sum.
    """
    x = jnp.sum(jnp.stack([p.x for p in parities]), axis=0)
    y = jnp.sum(jnp.stack([p.y for p in parities]), axis=0)
    return LocalParity(x=x, y=y)
