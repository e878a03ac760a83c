"""Plain reference of the coded federated round (paper Sec. III), written
from the paper and the program's documented conventions; it imports
nothing of the program and takes nothing the program made.

What a run of the system does, restated plainly:

1. Allocation (eq. 23-27): node j returns load L by deadline t with
   P(T_j <= t) = sum_v (v-1)(1-p)^2 p^(v-2) (1 - exp(-a mu/L (t - L/mu
   - tau v))) over v >= 2 with positive slack (Theorem 1).  Each node
   takes the load that maximizes L P(T_j <= t) up to its l points; t* is
   the least t at which those returns plus the u parity rows reach m.
   Loads are floored to whole points.
2. Processed points: the first l*_j of a random permutation of each
   client's points (the flat engine; permutation drawn from the
   ``seed + 17`` stream) or the first l*_j points (edge aggregators).
3. Parity (eq. 19-21): client j draws G_j (u x l) i.i.d. N(0, 1) from the
   j-th key of the split chain started at PRNGKey(seed + 99) (folded with
   the shard index for edge aggregators), weights point k by
   sqrt(1 - P(T_j <= t*)) if processed and 1 otherwise, and the server
   sums G_j W_j X_j and G_j W_j Y_j.
4. Round (eq. 28-30): delays T_j are drawn from the ``seed + 17`` stream
   (geometric down, geometric up, unit exponential, per block of rounds);
   sampled cohorts from ``(seed + 5557,)``; client j contributes
   X_j^T (X_j theta - Y_j) over its processed points if T_j <= t* (and it
   is in the cohort); the parity set adds w P_x^T (P_x theta - P_y) / u,
   w = (m_s - f R_s) / (m_s - R_s) for a sampled cohort; theta <- theta -
   lr (g / m + l2 theta).

Arithmetic: matrix products run at full f32 (HIGHEST) and the allocation
in float64.  `Reference(..., control=True)` is the control: the same
computation with fp8 (e4m3, one scale per tensor) matmul operands and
the allocation in float32, the precision step below the configuration's
bf16-operand products and float64 solver.  ``operands="bf16"`` rounds
the matmul operands to bf16, as the configuration states, and serves as
a witness of what rounding alone moves.  Planted faults:
``fault="half_batch"`` keeps only the first half of each aggregator's
clients and doubles their sum; ``fault="cursor_drift"`` draws one extra
uniform from the delay stream in every call after the first
``steps_compared``, as a driver that lost its stream position would.
"""
from __future__ import annotations

import functools
import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: transmission counts summed in the delay cdf (the tail past v = 12
#: weighs below 1e-9 at the configurations' erasure probability 0.1)
V_MAX = 12
#: golden-section iterations per concavity piece while searching t*
#: (returns to ~1e-12), and for the loads at t* (to ~1e-12 of l: a
#: coarser load would land on the other side of a whole point now and
#: then, and move one point's parity weight)
GOLDEN = 28
GOLDEN_FINAL = 60
_INV_PHI = (math.sqrt(5.0) - 1.0) / 2.0
#: bytes of generator matrices per parity block
PARITY_BLOCK_BYTES = 6e8
#: clients per jitted gradient call (a short block is padded)
CLIENT_BLOCK = 32
#: faults that can be planted in the reference put in the program's place
FAULTS = (None, "half_batch", "cursor_drift")


# ------------------------------------------------------------- arithmetic
class Arith:
    """Matrix products at full f32, with bf16 operands, or with fp8
    operands and a float32 solver (the control)."""

    def __init__(self, operands: str = "f32"):
        if operands not in ("f32", "bf16", "fp8"):
            raise ValueError(f"unknown operands {operands!r}")
        self.operands = operands
        self.solver_dtype = np.float32 if operands == "fp8" else np.float64

    def q(self, a):
        import jax.numpy as jnp
        if self.operands == "f32":
            return a
        if self.operands == "bf16":
            return a.astype(jnp.bfloat16).astype(jnp.float32)
        s = jnp.max(jnp.abs(a)) / 448.0
        s = jnp.where(s > 0, s, 1.0)
        return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    def mm(self, a, b):
        import jax
        import jax.numpy as jnp
        return jnp.matmul(self.q(a), self.q(b),
                          precision=jax.lax.Precision.HIGHEST)


# ------------------------------------------------------------- allocation
def _nb_weights(p, dtype):
    v = np.arange(2, V_MAX + 1, dtype=dtype)
    h = (v - 1) * (1 - p[:, None]) ** 2 * p[:, None] ** (v - 2)
    return v, h.astype(dtype)


def return_prob(net: dict, t: float, loads, dtype=np.float64) -> np.ndarray:
    """P(T_j <= t) at per-node loads (0 where the load is 0)."""
    mu, al, tau = (np.asarray(net[k], dtype) for k in ("mu", "alpha", "tau"))
    v, h = _nb_weights(np.asarray(net["p"], dtype), dtype)
    L = np.asarray(loads, dtype)
    safe = np.where(L > 0, L, 1)
    slack = t - safe[:, None] / mu[:, None] - tau[:, None] * v
    term = np.where(slack > 0, h * (1 - np.exp(
        -(al * mu / safe)[:, None] * np.maximum(slack, 0))), 0)
    return np.where(L > 0, np.minimum(term.sum(-1), 1), 0).astype(dtype)


def _returns(mu, al, tau, v, h, t, L):
    """L * P(T <= t) for loads L of shape (n, P)."""
    safe = np.where(L > 0, L, 1)
    slack = (t - safe[..., None] / mu[:, None, None]
             - tau[:, None, None] * v)
    rate = (al[:, None] * mu[:, None] / safe)[..., None]
    term = np.where(slack > 0,
                    h[:, None, :] * (1 - np.exp(-rate * np.maximum(slack, 0))),
                    0)
    return np.where(L > 0, L * np.minimum(term.sum(-1), 1), 0)


def optimal_loads(net: dict, cap: float, t: float, dtype=np.float64,
                  iters: int = GOLDEN):
    """argmax over 0 <= L <= cap of L P(T_j <= t), every node at once:
    golden section on each concavity piece (boundaries mu (t - v tau)),
    then the best of each piece's interior optimum and upper end."""
    mu, al, tau = (np.asarray(net[k], dtype) for k in ("mu", "alpha", "tau"))
    v, h = _nb_weights(np.asarray(net["p"], dtype), dtype)
    t = dtype(t)
    caps = np.full(mu.shape, cap, dtype)
    b = np.clip(mu[:, None] * (t - v * tau[:, None]), 0, caps[:, None])
    bounds = np.sort(np.concatenate(
        [np.zeros_like(caps)[:, None], b, caps[:, None]], axis=1), axis=1)
    lo, hi = bounds[:, :-1], bounds[:, 1:]
    f = lambda L: _returns(mu, al, tau, v, h, t, L)  # noqa: E731
    a, bb = lo + dtype(1e-12), hi
    c = bb - dtype(_INV_PHI) * (bb - a)
    d = a + dtype(_INV_PHI) * (bb - a)
    fc, fd = f(c), f(d)
    for _ in range(iters):
        left = fc >= fd
        a, bb = np.where(left, a, c), np.where(left, d, bb)
        probe = np.where(left, bb - dtype(_INV_PHI) * (bb - a),
                         a + dtype(_INV_PHI) * (bb - a))
        fp = f(probe)
        c, d, fc, fd = (np.where(left, probe, d), np.where(left, c, probe),
                        np.where(left, fp, fd), np.where(left, fc, fp))
    x = 0.5 * (a + bb)
    cands = np.stack([x, hi], -1).reshape(len(mu), -1)
    rets = np.stack([f(x), f(hi)], -1).reshape(len(mu), -1)
    best = np.argmax(rets, axis=1)
    load = cands[np.arange(len(mu)), best]
    ret = rets[np.arange(len(mu)), best]
    return np.where(ret > 0, load, 0), np.where(ret > 0, ret, 0)


def allocate(net: dict, cap: float, u: int, m: float, dtype=np.float64):
    """(t*, real-valued loads): the least t whose maximized total return
    plus u reaches m, by a bracketed Illinois root search."""
    target = float(m) - float(u)
    rtol = 1e-13 if dtype == np.float64 else 1e-7

    def g(t):
        return float(np.sum(optimal_loads(net, cap, t, dtype)[1],
                            dtype=np.float64)) - target

    lo, g_lo, hi = 0.0, -target, 1.0
    g_hi = g(hi)
    while g_hi < 0:
        lo, g_lo, hi = hi, g_hi, 2.0 * hi
        g_hi = g(hi)
        if hi > 1e30:
            raise ValueError("no deadline reaches the target return")
    side = 0
    for _ in range(200):
        if hi - lo <= rtol * hi:
            break
        t = hi - g_hi * (hi - lo) / (g_hi - g_lo)
        if not lo < t < hi:
            t = 0.5 * (lo + hi)
        gt = g(t)
        if gt >= 0:
            hi, g_hi = t, gt
            if side == 1:
                g_lo *= 0.5
            side = 1
        else:
            lo, g_lo = t, gt
            if side == -1:
                g_hi *= 0.5
            side = -1
    return hi, optimal_loads(net, cap, hi, dtype, GOLDEN_FINAL)[0]


# ------------------------------------------------------------ data access
class Data:
    """Client blocks of the benchmark's own inputs (device or host), always
    of a fixed number of clients so every jitted reference call keeps one
    shape: a short block repeats its last client, and callers give the
    repeats zero weight."""

    def __init__(self, x, y):
        self.x, self.y = x, y
        self.host = isinstance(x, np.ndarray)

    def take(self, idx: np.ndarray):
        import jax.numpy as jnp
        if self.host:
            return jnp.asarray(self.x[idx]), jnp.asarray(self.y[idx])
        idx = jnp.asarray(idx)
        return self.x[idx], self.y[idx]

    def block(self, lo: int, hi: int, rows: int):
        """(x, y, valid) for clients [lo, hi) padded to `rows` clients."""
        import jax.numpy as jnp
        valid = np.arange(lo, lo + rows) < hi
        if hi - lo == rows and self.host:
            return jnp.asarray(self.x[lo:hi]), jnp.asarray(self.y[lo:hi]), \
                valid
        return (*self.take(np.minimum(np.arange(lo, lo + rows), hi - 1)),
                valid)


@functools.lru_cache(maxsize=None)
def _kernels(operands: str):
    """The reference's jitted products at the given operand precision."""
    import jax
    import jax.numpy as jnp
    ar = Arith(operands)

    @functools.partial(jax.jit, static_argnames=("u",))
    def encode(keys, x, y, w, u):
        b, l = x.shape[0], x.shape[1]
        g = jax.vmap(lambda k: jax.random.normal(k, (u, l), jnp.float32))(keys)
        g = jnp.transpose(g, (1, 0, 2)).reshape(u, b * l)
        wx = (x * w[:, :, None]).reshape(b * l, -1)
        wy = (y * w[:, :, None]).reshape(b * l, -1)
        return ar.mm(g, wx), ar.mm(g, wy)

    @jax.jit
    def client_sum(x, y, wm, theta):
        xf = x.reshape(-1, x.shape[-1])
        r = (ar.mm(xf, theta) - y.reshape(-1, y.shape[-1])) \
            * wm.reshape(-1, 1)
        return ar.mm(xf.T, r)

    @jax.jit
    def parity_grad(px, py, theta):
        return ar.mm(px.T, ar.mm(px, theta) - py)

    @jax.jit
    def sq_loss(x, y, valid, th):
        c = y.shape[-1]
        res = ar.mm(x.reshape(-1, x.shape[-1]), th).reshape(
            -1, th.shape[1] // c, c) - y.reshape(-1, 1, c)
        rows = jnp.repeat(valid.astype(res.dtype), x.shape[1])
        return jnp.sum(res * res * rows[:, None, None], axis=(0, 2))

    return {"encode": encode, "client_sum": client_sum,
            "parity_grad": parity_grad, "sq_loss": sq_loss}


def _key_chain(key0, count: int):
    import jax
    def step(key, _):
        key, sub = jax.random.split(key)
        return key, sub
    return jax.lax.scan(step, key0, None, length=count)[1]


def _shard_ranges(n: int, shards: int):
    base, rem = divmod(n, shards)
    out, lo = [], 0
    for s in range(shards):
        hi = lo + base + (1 if s < rem else 0)
        out.append((lo, hi))
        lo = hi
    return out


# -------------------------------------------------------------- reference
class Reference:
    """The reference run of one configuration and traffic mix."""

    def __init__(self, cfg: dict, traffic: dict, fl_seed: int, net: dict,
                 data: Data, *, control: bool = False, operands: str = "f32",
                 fault: str | None = None):
        if traffic["scheme"] != "coded" or traffic["channel_profile"]:
            raise ValueError("the reference covers the static coded round")
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.cfg, self.traffic, self.seed = cfg, traffic, fl_seed
        self.net, self.data = net, data
        if control:
            operands = "fp8"
        self.ar = Arith(operands)
        self.k = _kernels(operands)
        self.fault = fault
        self.n, self.l = cfg["clients"], cfg["points_per_client"]
        self.q, self.c = cfg["q"], cfg["classes"]
        self.m = self.n * self.l
        self.f = float(cfg["sample_fraction"])
        self.hier = cfg["hier_shards"] > 1 or self.f < 1.0
        self.ranges = _shard_ranges(self.n, cfg["hier_shards"])
        self.rng = np.random.default_rng(fl_seed + 17)
        self.srng = np.random.default_rng((fl_seed + 5557,))
        self.calls = 0
        #: per-round count of clients back by their deadline (in the
        #: cohort), over every call drawn so far
        self.returned: list[np.ndarray] = []
        # edge aggregators solve independently; NumPy releases the GIL
        with ThreadPoolExecutor(len(self.ranges)) as pool:
            allocs = list(pool.map(self._allocate, self.ranges))
        self.shards = [self._setup(s, lo, hi, *allocs[s])
                       for s, (lo, hi) in enumerate(self.ranges)]
        self.loads = np.concatenate([sh["loads"] for sh in self.shards])
        self.mask = np.concatenate([sh["mask"] for sh in self.shards])

    # ---------------------------------------------------------- set-up
    def _allocate(self, rng: tuple[int, int]):
        lo, hi = rng
        m_s = (hi - lo) * self.l
        u = max(1, int(round(self.cfg["delta"] * m_s)))
        net = {k: np.asarray(v[lo:hi]) for k, v in self.net.items()}
        return u, allocate(net, float(self.l), u, float(m_s),
                           self.ar.solver_dtype)

    def _setup(self, s: int, lo: int, hi: int, u: int, alloc) -> dict:
        import jax
        import jax.numpy as jnp

        n_s, l = hi - lo, self.l
        m_s = n_s * l
        net = {k: np.asarray(v[lo:hi]) for k, v in self.net.items()}
        dt = self.ar.solver_dtype
        t_star, real = alloc
        loads = np.minimum(np.floor(real).astype(np.int64), l)
        p_ret = np.where(loads > 0, return_prob(net, t_star, loads, dt), 0.0)
        if not self.hier:
            perm = self.rng.permuted(np.tile(np.arange(l), (n_s, 1)), axis=1)
            mask = np.zeros((n_s, l), np.float32)
            take = np.arange(l)[None, :] < loads[:, None]
            mask[np.broadcast_to(np.arange(n_s)[:, None], (n_s, l))[take],
                 perm[take]] = 1.0
        else:
            mask = (np.arange(l)[None, :] < loads[:, None]).astype(np.float32)
        w = np.where(mask > 0, np.sqrt(1.0 - p_ret)[:, None],
                     1.0).astype(np.float32)
        key0 = jax.random.PRNGKey(self.seed + 99)
        if self.hier:
            key0 = jax.random.fold_in(key0, s)
        keys = np.asarray(_key_chain(key0, n_s))
        blk = max(1, min(n_s, int(PARITY_BLOCK_BYTES // (4 * u * l))))
        n_pad = -(-n_s // blk) * blk
        keys = np.concatenate([keys, np.repeat(keys[-1:], n_pad - n_s, 0)])
        w_pad = np.zeros((n_pad, l), np.float32)
        w_pad[:n_s] = w
        px = jnp.zeros((u, self.q), jnp.float32)
        py = jnp.zeros((u, self.c), jnp.float32)
        for a in range(0, n_s, blk):
            x, y, _ = self.data.block(lo + a, min(lo + a + blk, hi), blk)
            dpx, dpy = self.k["encode"](jnp.asarray(keys[a:a + blk]), x, y,
                                        jnp.asarray(w_pad[a:a + blk]), u=u)
            px, py = px + dpx, py + dpy
        r_mass = float(np.sum(loads * p_ret))
        r = min(r_mass, m_s * (1.0 - 1e-9))
        w_par = 1.0 if self.f == 1.0 else (m_s - self.f * r) / (m_s - r)
        return {"lo": lo, "hi": hi, "t_star": float(t_star), "real": real,
                "loads": loads, "mask": mask, "u": u, "px": px, "py": py,
                "w_par": float(w_par)}

    # ---------------------------------------------------------- rounds
    def _delays(self, rounds: int) -> np.ndarray:
        net = self.net
        p = np.asarray(net["p"], np.float64)
        loads = self.loads.astype(np.float64)
        active = loads > 0.0
        n_down = self.rng.geometric(1.0 - p, size=(rounds, self.n))
        n_up = self.rng.geometric(1.0 - p, size=(rounds, self.n))
        t = net["tau"] * n_down + net["tau"] * n_up
        scale = np.where(active, loads / (net["alpha"] * net["mu"]), 0.0)
        stoch = self.rng.exponential(1.0, size=(rounds, self.n)) * scale
        return t + np.where(active, loads / net["mu"], 0.0) + stoch

    def _client_sum(self, idx: np.ndarray, weights: np.ndarray, theta):
        """sum_j weights_j X_j^T (mask_j (X_j theta - Y_j)) over `idx`."""
        import jax.numpy as jnp
        g = jnp.zeros((self.q, self.c), jnp.float32)
        step = min(self.n, CLIENT_BLOCK)
        for a in range(0, len(idx), step):
            sel = idx[a:a + step]
            x, y = self.data.take(np.concatenate(
                [sel, np.full(step - len(sel), sel[-1])]))
            wm = np.zeros((step, self.l), np.float32)
            wm[:len(sel)] = self.mask[sel] * weights[a:a + step, None]
            g = g + self.k["client_sum"](x, y, jnp.asarray(wm), theta)
        return g

    def _draws(self) -> np.ndarray:
        """The next run_block call's per-round (rounds, n) mask of clients
        back by their aggregator's deadline (and in the cohort), drawn
        from the delay and sampling streams in the program's order."""
        k = int(self.traffic["rounds_per_block"])
        if self.fault == "cursor_drift" \
                and self.calls >= int(self.traffic["steps_compared"]):
            self.rng.random()
        self.calls += 1
        if self.hier:
            times = np.concatenate([self._delays(1) for _ in range(k)])
            cohort = self.srng.random((k, self.n)) < self.f
        else:
            times = self._delays(k)
            cohort = np.ones((k, self.n), bool)
        t_star = np.concatenate([np.full(sh["hi"] - sh["lo"], sh["t_star"])
                                 for sh in self.shards])
        ret = (times <= t_star) & cohort
        self.returned.append(ret.sum(axis=1))
        return ret

    def skip(self, calls: int) -> np.ndarray:
        """Draw `calls` run_block calls without playing them; returns
        their (rounds, n) masks of clients back by the deadline."""
        out = [self._draws() for _ in range(calls)]
        return np.concatenate(out) if out else np.zeros((0, self.n), bool)

    def run(self, steps: int, theta0=None) -> list[np.ndarray]:
        """theta after each of the next `steps` run_block calls, from
        `theta0` (zeros by default)."""
        import jax.numpy as jnp
        lr = float(self.cfg["train"]["learning_rate"])
        l2 = float(self.cfg["train"]["l2_reg"])
        theta = jnp.zeros((self.q, self.c), jnp.float32) if theta0 is None \
            else jnp.asarray(theta0, jnp.float32)
        out = []
        for _ in range(steps):
            for ret in self._draws():
                g = jnp.zeros((self.q, self.c), jnp.float32)
                for sh in self.shards:
                    lo, hi = sh["lo"], sh["hi"]
                    wts = np.ones(hi - lo)
                    if self.fault == "half_batch":
                        wts = np.where(np.arange(hi - lo) < (hi - lo) // 2,
                                       2.0, 0.0)
                    idx = np.nonzero(ret[lo:hi] & (wts > 0))[0]
                    g = g + self._client_sum(lo + idx, wts[idx], theta)
                    g = g + (sh["w_par"] / sh["u"]) * self.k["parity_grad"](
                        sh["px"], sh["py"], theta)
                theta = theta - lr * (g / self.m + l2 * theta)
            out.append(np.asarray(theta, np.float64))
        return out

    # ------------------------------------------------------------ loss
    def losses(self, thetas: list[np.ndarray]) -> list[float]:
        """Squared loss over every client point plus the l2 term, for
        each theta: sum ||x theta - y||^2 / (2 m) + l2 ||theta||^2 / 2."""
        import jax.numpy as jnp
        k = len(thetas)
        th = jnp.asarray(np.concatenate(thetas, axis=1), jnp.float32)
        tot = np.zeros(k)
        step = min(self.n, 4 * CLIENT_BLOCK)
        for a in range(0, self.n, step):
            x, y, valid = self.data.block(a, min(a + step, self.n), step)
            tot += np.asarray(self.k["sq_loss"](x, y, jnp.asarray(valid), th),
                              np.float64)
        l2 = float(self.cfg["train"]["l2_reg"])
        return [float(tot[i] / (2.0 * self.m)
                      + 0.5 * l2 * np.sum(np.square(thetas[i])))
                for i in range(k)]
