"""CodedFedL load allocation and coding-redundancy optimizer (paper §III-C, §IV).

Two-step scheme:
  Step 1 (fixed deadline t): for every node j in [n+1] (clients + MEC
    compute unit), maximize the expected return
        E[R_j(t; l)] = l * P(T_j <= t)
    over 0 <= l <= cap_j.  By Theorem 1 the objective is piece-wise concave
    in l with concavity-piece boundaries at l = mu_j (t - v tau_j); we run a
    golden-section search per piece (no SciPy dependency).
  Step 2: bisection over t (the maximized total expected return is monotone
    increasing in t, Appendix C) until it equals m.

Two solver backends share this structure:

  * the original scalar NumPy path (``two_step_allocate``) — a Python loop
    over nodes and concavity pieces; exact, but O(n) Python-level work per
    bisection step, so it cannot scale past a few hundred clients;
  * ``two_step_allocate_vectorized`` — the same two-step scheme as ONE
    fixed-iteration jitted JAX program: golden-section over every
    (node, concavity-piece) pair simultaneously, bracketing + bisection as
    ``lax.fori_loop``s.  All n clients (plus the optional MEC server node,
    i.e. the paper's n+1 nodes) are solved in a single call; n >= 1000 is a
    single device program.  Asymmetric tau_up/p_up links (footnote 1) ride
    the same program through a flattened per-direction transmission grid.
    The scalar path stays as the numerical oracle (tests assert
    node-for-node agreement).

Special case p_j = 0 (AWGN links): closed form via the Lambert-W minor
branch (paper eq. 34/35, Appendix D), used both as a fast path and as an
oracle in tests (the vectorized solver must reproduce it at p = 0).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.delay_model import NodeDelayParams, stack_node_params

_INV_PHI = (math.sqrt(5.0) - 1.0) / 2.0


# --------------------------------------------------------------------------
# Lambert W, minor branch W_{-1}:  w e^w = x  for x in (-1/e, 0), w <= -1.
# --------------------------------------------------------------------------
def lambert_w_minus1(x: float) -> float:
    if not (-1.0 / math.e < x < 0.0):
        raise ValueError(f"W_-1 defined on (-1/e, 0); got {x}")
    # initial guess (Corless et al. 1996 asymptotics)
    l1 = math.log(-x)
    l2 = math.log(-l1)
    w = l1 - l2 + l2 / l1
    for _ in range(100):
        ew = math.exp(w)
        f = w * ew - x
        # Halley's method
        denom = ew * (w + 1.0) - (w + 2.0) * f / (2.0 * w + 2.0)
        w_new = w - f / denom
        if abs(w_new - w) < 1e-14 * (1.0 + abs(w_new)):
            return w_new
        w = w_new
    return w


def awgn_slope(node: NodeDelayParams) -> float:
    """s_j = -alpha*mu / (W_{-1}(-e^{-(1+alpha)}) + 1)   (paper eq. 34)."""
    w = lambert_w_minus1(-math.exp(-(1.0 + node.alpha)))
    return -node.alpha * node.mu / (w + 1.0)


def awgn_optimal_load(node: NodeDelayParams, t: float, cap: float) -> float:
    """Closed-form l*_j(t) for p=0 (paper eq. 34)."""
    if t <= 2.0 * node.tau:
        return 0.0
    s = awgn_slope(node)
    return min(s * (t - 2.0 * node.tau), cap)


def awgn_optimal_return(node: NodeDelayParams, t: float, cap: float) -> float:
    """Closed-form E[R_j(t; l*_j(t))] for p=0 (paper eq. 35)."""
    if t <= 2.0 * node.tau:
        return 0.0
    s = awgn_slope(node)
    zeta = cap / s + 2.0 * node.tau
    if t <= zeta:
        s_tilde = s * (1.0 - math.exp(-node.alpha * (node.mu / s - 1.0)))
        return s_tilde * (t - 2.0 * node.tau)
    return cap * (1.0 - math.exp(
        -node.alpha * node.mu / cap * (t - cap / node.mu - 2.0 * node.tau)))


# --------------------------------------------------------------------------
# General case: E[R_j(t; l)] = l * cdf_j(t; l), piece-wise concave in l.
# --------------------------------------------------------------------------
def expected_return(node: NodeDelayParams, t: float, load: float) -> float:
    if load <= 0:
        return 0.0
    return load * node.cdf(t, load)


def _golden_max(f, lo: float, hi: float, tol: float = 1e-9):
    """Golden-section maximization of unimodal f on [lo, hi]."""
    a, b = lo, hi
    c = b - _INV_PHI * (b - a)
    d = a + _INV_PHI * (b - a)
    fc, fd = f(c), f(d)
    while (b - a) > tol * (1.0 + abs(a) + abs(b)):
        if fc >= fd:
            b, d, fd = d, c, fc
            c = b - _INV_PHI * (b - a)
            fc = f(c)
        else:
            a, c, fc = c, d, fd
            d = a + _INV_PHI * (b - a)
            fd = f(d)
    x = (a + b) / 2.0
    return x, f(x)


def optimal_load(node: NodeDelayParams, t: float, cap: float) -> tuple[float, float]:
    """Maximize E[R_j(t; l)] over 0 <= l <= cap.

    Returns (l*, E[R_j(t; l*)]).  Handles the general p>0 case by searching
    each concavity piece; for p==0 uses the closed form.
    """
    symmetric = node.tau_up is None and node.p_up is None
    if cap <= 0 or (symmetric and t <= 2.0 * node.tau) or \
            (not symmetric and t <= node.tau + node._tau_up):
        return 0.0, 0.0
    if node.p == 0.0 and symmetric:
        l = awgn_optimal_load(node, t, cap)
        return l, expected_return(node, t, l)
    # piece boundaries: l = mu (t - v tau) for v = 2..v_m, clipped to (0, cap]
    # (v capped where the NB tail is numerically zero — see NodeDelayParams)
    v_m = node._v_cap(t)
    if v_m < 2:
        return 0.0, 0.0
    bounds = sorted({min(max(node.mu * (t - v * node.tau), 0.0), cap)
                     for v in range(2, v_m + 1)} | {0.0, cap})
    best_l, best_r = 0.0, 0.0
    f = lambda l: expected_return(node, t, l)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        if hi - lo < 1e-15:
            continue
        x, fx = _golden_max(f, lo + 1e-12, hi)
        # also test the piece endpoints
        for cand, fc in ((x, fx), (hi, f(hi))):
            if fc > best_r:
                best_l, best_r = cand, fc
    return best_l, best_r


@dataclasses.dataclass(frozen=True)
class Allocation:
    t_star: float                 # optimal epoch deadline (seconds)
    loads: np.ndarray             # l*_j for clients j in [n]
    u_star: float                 # coded redundancy processed at the server
    returns: np.ndarray           # E[R_j(t*; l*_j)] per client
    coded_return: float           # E[R_C(t*; u*)]

    @property
    def total_return(self) -> float:
        return float(np.sum(self.returns) + self.coded_return)


def max_total_return(nodes: Sequence[NodeDelayParams], caps: Sequence[float],
                     t: float) -> tuple[np.ndarray, np.ndarray]:
    loads = np.zeros(len(nodes))
    rets = np.zeros(len(nodes))
    for j, (node, cap) in enumerate(zip(nodes, caps)):
        loads[j], rets[j] = optimal_load(node, t, cap)
    return loads, rets


def two_step_allocate(clients: Sequence[NodeDelayParams],
                      client_caps: Sequence[float],
                      server: NodeDelayParams | None,
                      u_max: float,
                      m: float,
                      tol: float = 1e-6,
                      t_hi: float | None = None) -> Allocation:
    """Solve paper eq. (23) via the two-step approach (eq. 24-27).

    `server=None` models the paper's §V assumption P(T_C <= t) = 1 (dedicated
    reliable MEC resources => u* = u_max contributes fully for any t>0).
    """
    nodes = list(clients)
    caps = list(client_caps)

    def total(t: float) -> float:
        _, rets = max_total_return(nodes, caps, t)
        tot = float(np.sum(rets))
        if server is None:
            tot += u_max
        else:
            _, r = optimal_load(server, t, u_max)
            tot += r
        return tot

    target = float(m)
    # the maximal possible return is sum(caps) + u_max; demand feasibility
    if sum(caps) + u_max < target - 1e-9:
        raise ValueError("infeasible: sum of caps + u_max < m")
    # bracket
    lo = 0.0
    hi = t_hi if t_hi is not None else 1.0
    for _ in range(200):
        if total(hi) >= target:
            break
        hi *= 2.0
    else:
        raise RuntimeError("could not bracket deadline time")
    # bisection (total return monotone increasing in t, Appendix C)
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if total(mid) >= target:
            hi = mid
        else:
            lo = mid
        if hi - lo < tol * (1.0 + hi):
            break
    t_star = hi
    loads, rets = max_total_return(nodes, caps, t_star)
    if server is None:
        u_star, coded_ret = float(u_max), float(u_max)
    else:
        u_star, coded_ret = optimal_load(server, t_star, u_max)
    return Allocation(t_star=t_star, loads=loads, u_star=u_star,
                      returns=rets, coded_return=coded_ret)


# --------------------------------------------------------------------------
# Vectorized fixed-iteration JAX solver (all n+1 nodes simultaneously).
# --------------------------------------------------------------------------
def _tail_v_cap(p_max: float) -> int:
    """Static truncation of the NB transmission-count tail.

    Mirrors NodeDelayParams._v_cap's tail rule at the population's largest
    erasure probability; the per-t floor(t/tau) part of the scalar cap is
    subsumed by the slack > 0 masking inside the vectorized cdf.  Rounded up
    to a multiple of 8 so nearby populations share one compiled program
    (v_cap is a static jit argument; the extra tail terms are < 1e-13).
    """
    if p_max <= 0.0:
        return 2
    exact = 2 + int(np.ceil(-14.0 / np.log10(p_max))) + 10
    return int(-(-exact // 8) * 8)


def _geo_tail_cap(p_max: float) -> int:
    """Static per-direction geometric tail cap: NodeDelayParams._geo_cap
    (the scalar oracle's truncation rule — one source of truth) at the
    population's largest erasure prob, rounded up to a multiple of 8 so
    nearby populations share one compiled program."""
    return int(-(-NodeDelayParams._geo_cap(p_max) // 8) * 8)


def vectorized_grid_width(nodes: Sequence[NodeDelayParams]) -> int:
    """Transmission-grid columns K the vectorized solver would build.

    Symmetric populations collapse to the NB(2) grid (K = V - 1);
    asymmetric ones pay the per-direction pair grid (K = Vd * Vu), which
    grows as O(log^2 p) toward p -> 1.  The runtime's auto backend pick
    consults this to keep high-erasure asymmetric populations on the
    scalar solver instead of materializing (n, pieces, K) intermediates.
    """
    prm = stack_node_params(nodes)
    if np.array_equal(prm["p_down"], prm["p_up"]) \
            and np.array_equal(prm["tau_down"], prm["tau_up"]):
        return _tail_v_cap(float(prm["p_down"].max())) - 1
    return (_geo_tail_cap(float(prm["p_down"].max()))
            * _geo_tail_cap(float(prm["p_up"].max())))


def _transmission_grids(prm: dict) -> tuple[np.ndarray, np.ndarray]:
    """Per-node transmission-count weights/offsets (h, comm), each (n, K).

    The cdf inside the vectorized objective is a weighted sum over
    transmission counts: P(T <= t) = sum_k h_k (1 - exp(-rate (t - l/mu -
    comm_k))) over terms with positive slack.  Symmetric (reciprocal)
    links collapse the two geometric directions into the NB(2, 1-p) pmf
    over the round-trip count (K = V-1 terms, exactly the pre-asym
    layout); asymmetric links keep the full (n_down, n_up) pair grid with
    per-direction tail caps — mirroring `NodeDelayParams._cdf_asym`'s
    nested sum, flattened so the same jitted program serves both.
    """
    p_d, p_u = prm["p_down"], prm["p_up"]
    tau_d, tau_u = prm["tau_down"], prm["tau_up"]
    if np.array_equal(p_d, p_u) and np.array_equal(tau_d, tau_u):
        v_cap = _tail_v_cap(float(p_d.max()))
        v = np.arange(2, v_cap + 1, dtype=np.float64)
        h = ((v - 1.0) * (1.0 - p_d[:, None]) ** 2
             * p_d[:, None] ** (v - 2.0))
        return h, tau_d[:, None] * v
    vd = np.arange(1, _geo_tail_cap(float(p_d.max())) + 1, dtype=np.float64)
    vu = np.arange(1, _geo_tail_cap(float(p_u.max())) + 1, dtype=np.float64)
    n = p_d.shape[0]
    h_d = (1.0 - p_d[:, None]) * p_d[:, None] ** (vd - 1.0)    # (n, Vd)
    h_u = (1.0 - p_u[:, None]) * p_u[:, None] ** (vu - 1.0)    # (n, Vu)
    h = (h_d[:, :, None] * h_u[:, None, :]).reshape(n, -1)
    comm = ((tau_d[:, None] * vd)[:, :, None]
            + (tau_u[:, None] * vu)[:, None, :]).reshape(n, -1)
    return h, comm


def _vec_expected_return(mu, alpha, h, comm, t, loads):
    """E[R(t; l)] = l * P(T <= t), element-wise (paper eq. 42 / Theorem 1).

    mu/alpha and the precomputed transmission grids `h`/`comm` must
    broadcast against `loads[..., None]`.  Terms with non-positive slack
    are masked, so the result is exact for any t without a data-dependent
    transmission-count cap.
    """
    lo = loads[..., None]
    slack = t - lo / mu[..., None] - comm
    safe = jnp.where(loads > 0, loads, 1.0)
    rate = (alpha * mu / safe)[..., None]
    term = jnp.where(slack > 0, h * (1.0 - jnp.exp(-rate * slack)), 0.0)
    cdf = jnp.minimum(jnp.sum(term, axis=-1), 1.0)
    return jnp.where(loads > 0, loads * cdf, 0.0)


def _vec_optimal_loads(mu, alpha, tau, h, comm, caps, t, *, v_cap: int,
                       n_golden: int):
    """Step 1 for every node at once: argmax_l E[R(t; l)], 0 <= l <= cap.

    Runs a fixed-iteration golden-section search on every (node, concavity
    piece) pair simultaneously — piece boundaries at l = mu (t - v tau)
    (Theorem 1; asymmetric links keep the downlink-tau boundary grid, the
    same heuristic piece placement the scalar solver uses) — then keeps
    the best of the piece interior and piece upper endpoint, mirroring the
    scalar solver's candidate order.
    Returns (loads, returns), each shaped like caps.
    """
    v = jnp.arange(2, v_cap + 1, dtype=caps.dtype)

    def f(l):                                   # l: (n, P) piece-grid loads
        return _vec_expected_return(mu[:, None], alpha[:, None],
                                    h[:, None, :], comm[:, None, :], t, l)

    # sorted piece boundaries: clip(mu (t - v tau), [0, cap]) ∪ {0, cap}
    b = jnp.clip(mu[:, None] * (t - v * tau[:, None]), 0.0, caps[:, None])
    zeros = jnp.zeros_like(caps)[:, None]
    bounds = jnp.sort(jnp.concatenate([zeros, b, caps[:, None]], axis=1),
                      axis=1)                   # (n, V + 1)
    lo, hi = bounds[:, :-1], bounds[:, 1:]      # (n, V) pieces

    # classic golden section with one objective eval per iteration: carry
    # (a, b, c, d, fc, fd) and probe only the one new interior point
    a, bb = lo + 1e-12, hi
    c = bb - _INV_PHI * (bb - a)
    d = a + _INV_PHI * (bb - a)
    fc, fd = f(c), f(d)

    def body(_, st):
        a, bb, c, d, fc, fd = st
        left = fc >= fd
        a2 = jnp.where(left, a, c)
        b2 = jnp.where(left, d, bb)
        probe = jnp.where(left, b2 - _INV_PHI * (b2 - a2),
                          a2 + _INV_PHI * (b2 - a2))
        fp = f(probe)
        c2 = jnp.where(left, probe, d)
        d2 = jnp.where(left, c, probe)
        fc2 = jnp.where(left, fp, fd)
        fd2 = jnp.where(left, fc, fp)
        return (a2, b2, c2, d2, fc2, fd2)

    a, bb, *_ = jax.lax.fori_loop(0, n_golden, body, (a, bb, c, d, fc, fd))
    x = 0.5 * (a + bb)

    # candidate order matches the scalar loop: per piece (ascending), the
    # golden interior point first, then the piece's upper endpoint
    cands = jnp.stack([x, hi], axis=-1).reshape(caps.shape[0], -1)
    rets = jnp.stack([f(x), f(hi)], axis=-1).reshape(caps.shape[0], -1)
    best = jnp.argmax(rets, axis=1)
    best_ret = jnp.take_along_axis(rets, best[:, None], axis=1)[:, 0]
    best_load = jnp.take_along_axis(cands, best[:, None], axis=1)[:, 0]
    ok = best_ret > 0.0
    return jnp.where(ok, best_load, 0.0), jnp.where(ok, best_ret, 0.0)


@functools.partial(jax.jit, static_argnames=("v_cap", "n_golden",
                                             "n_golden_search",
                                             "n_bracket", "n_bisect"))
def _vec_two_step(mu, alpha, tau, h, comm, caps, target, t_hi0, *,
                  v_cap: int, n_golden: int, n_golden_search: int,
                  n_bracket: int, n_bisect: int):
    """Step 2: bracket + bisection over t, entirely on device.

    The bracket doubles t until the maximized total return reaches the
    target (lax.while_loop, capped at n_bracket doublings); the bisection is
    a fixed n_bisect-iteration lax.fori_loop, so one compiled program solves
    the whole population regardless of n.  During the search only the
    objective VALUE matters, and golden-section value error is quadratic in
    the interval width, so a coarser n_golden_search is used inside the
    bracket/bisection and the full n_golden only for the final load
    extraction at t*.  `h`/`comm` are the `_transmission_grids` weights —
    symmetric NB(2) or the asymmetric pair grid, transparently.
    """
    def total(t):
        _, rets = _vec_optimal_loads(mu, alpha, tau, h, comm, caps, t,
                                     v_cap=v_cap, n_golden=n_golden_search)
        return jnp.sum(rets)

    def need_more(state):
        hi, k = state
        return (total(hi) < target) & (k < n_bracket)
    hi, _ = jax.lax.while_loop(need_more, lambda s: (s[0] * 2.0, s[1] + 1),
                               (t_hi0, 0))

    def bisect(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        ge = total(mid) >= target
        return (jnp.where(ge, lo, mid), jnp.where(ge, mid, hi))
    _, t_star = jax.lax.fori_loop(0, n_bisect, bisect,
                                  (jnp.zeros_like(hi), hi))

    loads, rets = _vec_optimal_loads(mu, alpha, tau, h, comm, caps, t_star,
                                     v_cap=v_cap, n_golden=n_golden)
    return t_star, loads, rets


def vectorized_optimal_loads(nodes: Sequence[NodeDelayParams], t: float,
                             caps: Sequence[float], *, n_golden: int = 52
                             ) -> tuple[np.ndarray, np.ndarray]:
    """Step-1 optimal loads for all nodes in one jitted call (float64).

    Node-for-node equivalent of looping `optimal_load` — including
    asymmetric tau_up/p_up links (footnote 1 generalization), which flow
    through the flattened per-direction transmission grid; the scalar
    path is the oracle the property tests compare against.
    """
    prm = stack_node_params(nodes)
    v_cap = _tail_v_cap(float(prm["p_down"].max()))
    h, comm = _transmission_grids(prm)
    with jax.enable_x64(True):
        loads, rets = jax.jit(_vec_optimal_loads,
                              static_argnames=("v_cap", "n_golden"))(
            jnp.asarray(prm["mu"]), jnp.asarray(prm["alpha"]),
            jnp.asarray(prm["tau_down"]), jnp.asarray(h), jnp.asarray(comm),
            jnp.asarray(np.asarray(caps, np.float64)), float(t),
            v_cap=v_cap, n_golden=n_golden)
        return np.asarray(loads), np.asarray(rets)


def two_step_allocate_vectorized(clients: Sequence[NodeDelayParams],
                                 client_caps: Sequence[float],
                                 server: NodeDelayParams | None,
                                 u_max: float,
                                 m: float,
                                 tol: float = 1e-6,
                                 t_hi: float | None = None,
                                 n_golden: int = 52,
                                 n_golden_search: int = 28,
                                 n_bracket: int = 60,
                                 n_bisect: int = 48) -> Allocation:
    """Vectorized counterpart of `two_step_allocate` (paper eq. 23-27).

    One fixed-iteration jitted JAX program solves step 1 for all n clients
    (plus the MEC server compute node when given — the paper's n+1 nodes)
    and runs the step-2 bracket/bisection on device; n >= 1000 nodes is a
    single call.  Asymmetric tau_up/p_up links are supported through the
    flattened per-direction transmission grid (`_transmission_grids`).
    Matches the scalar solver within its bisection tolerance (`tol` only
    documents that contract — iteration counts are fixed and exceed it).
    Float64 throughout via a local x64 scope.
    """
    nodes = list(clients)
    caps = [float(cp) for cp in client_caps]
    target = float(m)
    if server is not None:
        nodes.append(server)
        caps.append(float(u_max))
    else:
        target -= float(u_max)          # P(T_C <= t) = 1: u_max always returns
    if sum(client_caps) + u_max < m - 1e-9:
        raise ValueError("infeasible: sum of caps + u_max < m")
    prm = stack_node_params(nodes)
    v_cap = _tail_v_cap(float(prm["p_down"].max()))
    h, comm = _transmission_grids(prm)
    with jax.enable_x64(True):
        t_star, loads, rets = _vec_two_step(
            jnp.asarray(prm["mu"]), jnp.asarray(prm["alpha"]),
            jnp.asarray(prm["tau_down"]), jnp.asarray(h), jnp.asarray(comm),
            jnp.asarray(np.asarray(caps, np.float64)), target,
            float(t_hi if t_hi is not None else 1.0),
            v_cap=v_cap, n_golden=n_golden,
            n_golden_search=n_golden_search, n_bracket=n_bracket,
            n_bisect=n_bisect)
        t_star = float(t_star)
        loads = np.asarray(loads)
        rets = np.asarray(rets)
    if server is None:
        u_star, coded_ret = float(u_max), float(u_max)
    else:
        loads, u_star = loads[:-1], float(loads[-1])
        rets, coded_ret = rets[:-1], float(rets[-1])
    return Allocation(t_star=t_star, loads=loads, u_star=u_star,
                      returns=rets, coded_return=coded_ret)
