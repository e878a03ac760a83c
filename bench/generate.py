"""Inputs of a cell, made from the run's seed: the network and the data.

One general generator serves every configuration; a configuration file
names its data kind and sizes, and nothing here knows a cell by name.

* `seeds` turns the benchmark's seed (any whole number) into the two
  31-bit seeds the run uses: the program's ``FLConfig.seed`` and the
  data seed.
* `network` builds the paper's Sec. V-A MEC network as stacked arrays:
  link rate ``max_rate * k1^i`` and MAC rate ``max_mac * k2^i`` over a
  random permutation drawn from the network seed, one packet of q*c
  scalars per transmission.  The reference uses it, and the generator
  uses it to hand label-sorted shards out by speed.
* `make_data` makes the clients' RFF-embedded features and one-hot
  labels on the device, one jitted call per chunk of clients, with the
  RFF argument X @ Omega at full f32 (HIGHEST).  ``host=True`` copies
  each chunk into one host array as soon as it is made (the hierarchical
  tier streams its clients from the host).

Data kinds (``cfg["data"]["kind"]``):

``label_sorted_by_speed``
    Balanced labels, sorted, cut into n shards of l points; shard r goes
    to the client with the r-th smallest expected full-load delay (the
    paper's non-IID split).
``writer_skew``
    Each writer draws its labels from a writer-specific categorical and
    adds a writer-specific style offset to every point.

Points are class prototypes in [0, 1]^d plus the writer's style and
pixel noise, clipped to [0, 1].
"""
from __future__ import annotations

import functools
import math

import numpy as np

#: writers per jitted data call (the last chunk is padded and trimmed)
CHUNK = 128


def seeds(seed: int) -> tuple[int, int]:
    """(program seed, data seed), both below 2**31 - 2**16 so the
    program's fixed seed offsets stay 32-bit."""
    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    top = 2 ** 31 - 2 ** 16
    return int(state[0]) % top, int(state[1]) % top


def hierarchical(cfg: dict) -> bool:
    """Whether a configuration runs on the hierarchical tier (edge
    aggregators or sampled cohorts), whose clients stream from the host."""
    return cfg["hier_shards"] > 1 or cfg["sample_fraction"] < 1.0


def network(cfg: dict, fl_seed: int) -> dict:
    """Per-client delay parameters, each an (n,) float64 array:
    mu (points/s), alpha, tau (s per transmission), p (erasure)."""
    net = cfg["network"]
    n = cfg["clients"]
    payload = (cfg["q"] * cfg["classes"] * net["bits_per_scalar"]
               * (1.0 + net["overhead"]))
    rng = np.random.default_rng(fl_seed)
    rate_f = net["rate_decay"] ** np.arange(n)
    mac_f = net["mac_decay"] ** np.arange(n)
    rng.shuffle(rate_f)
    rng.shuffle(mac_f)
    rates = net["max_rate_bps"] * rate_f
    macs = net["max_mac_rate"] * mac_f
    return {
        "mu": macs / float(cfg["q"] * cfg["classes"]),
        "alpha": np.full(n, float(net["alpha"])),
        "tau": (1.0 / rates) * payload,
        "p": np.full(n, float(net["p_erasure"])),
    }


def speed_rank(cfg: dict, net: dict) -> np.ndarray:
    """Rank of each client by expected full-load delay (0 = fastest)."""
    l = cfg["points_per_client"]
    delay = (l / net["mu"] * (1.0 + 1.0 / net["alpha"])
             + 2.0 * net["tau"] / (1.0 - net["p"]))
    rank = np.empty(len(delay), np.int64)
    rank[np.argsort(delay, kind="stable")] = np.arange(len(delay))
    return rank


@functools.lru_cache(maxsize=None)
def _chunk_fn(l: int, d: int, q: int, c: int, noise: float, style: float,
              kind: str):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def make(key, proto, omega, delta, labels):
        """labels: (B, l) int32 ('label_sorted_by_speed') or ignored."""
        b = labels.shape[0]
        k_lab, k_lab2, k_sty, k_pix = jax.random.split(key, 4)
        if kind == "writer_skew":
            logits = 2.0 * jax.random.normal(k_lab, (b, c))
            labels = jax.random.categorical(
                k_lab2, logits[:, None, :], axis=-1, shape=(b, l))
        sty = style * jax.random.normal(k_sty, (b, 1, d))
        pix = noise * jax.random.normal(k_pix, (b, l, d))
        x = jnp.clip(proto[labels] + sty + pix, 0.0, 1.0)
        arg = jnp.dot(x.reshape(b * l, d), omega, precision=hi)
        phi = math.sqrt(2.0 / q) * jnp.cos(arg + delta[None, :])
        return (phi.reshape(b, l, q),
                jax.nn.one_hot(labels, c, dtype=jnp.float32))

    return make


def make_data(cfg: dict, data_seed: int, fl_seed: int, *,
              host: bool = False):
    """(x (n, l, q) f32, y (n, l, c) f32) for `cfg` from the seeds:
    device arrays, or NumPy arrays with ``host=True``."""
    import jax
    import jax.numpy as jnp

    n, l, d = cfg["clients"], cfg["points_per_client"], cfg["d"]
    q, c = cfg["q"], cfg["classes"]
    kind = cfg["data"]["kind"]
    if kind not in ("label_sorted_by_speed", "writer_skew"):
        raise ValueError(f"unknown data kind {kind!r}")
    key = jax.random.PRNGKey(data_seed)
    k_proto, k_omega, k_delta, k_pts = jax.random.split(key, 4)
    proto = jnp.clip(0.3 + 0.35 * jax.random.normal(k_proto, (c, d)),
                     0.0, 1.0)
    omega = jax.random.normal(k_omega, (d, q)) / cfg["sigma"]
    delta = jax.random.uniform(k_delta, (q,), maxval=2.0 * math.pi)
    make = _chunk_fn(l, d, q, c, float(cfg["data"]["pixel_noise"]),
                     float(cfg["data"]["writer_style"]), kind)
    if kind == "label_sorted_by_speed":
        shard_labels = (np.arange(n * l) * c // (n * l)).reshape(n, l)
        labels = shard_labels[speed_rank(cfg, network(cfg, fl_seed))]
    else:
        labels = np.zeros((n, l), np.int64)
    chunk = min(n, CHUNK)
    starts = list(range(0, n, chunk))

    def call(i):
        lo = starts[i]
        lab = np.zeros((chunk, l), np.int32)
        lab[:min(chunk, n - lo)] = labels[lo:lo + chunk]
        return make(jax.random.fold_in(k_pts, i), proto, omega, delta,
                    jnp.asarray(lab))

    if not host:
        parts = [call(i) for i in range(len(starts))]
        x = jnp.concatenate([p[0] for p in parts])[:n]
        y = jnp.concatenate([p[1] for p in parts])[:n]
        return x, y
    x = np.empty((n, l, q), np.float32)
    y = np.empty((n, l, c), np.float32)
    nxt = call(0)
    for i, lo in enumerate(starts):
        cur = nxt
        if i + 1 < len(starts):
            nxt = call(i + 1)
        hi_ = min(lo + chunk, n)
        x[lo:hi_] = np.asarray(cur[0])[:hi_ - lo]
        y[lo:hi_] = np.asarray(cur[1])[:hi_ - lo]
    return x, y
