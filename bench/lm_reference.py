"""Plain reference of the federated DeepSeek-V2 language-model round,
written from arXiv:2405.04434, DeepSeek-V2's published ``config.json``
and ``modeling_deepseek.py``; it imports nothing of the program.

The model, one sequence at a time, in float32 with every product at
``jax.lax.Precision.HIGHEST``:

* embedding; then per layer x += MLA(RMSNorm(x)), x += FFN(RMSNorm(x)),
  the first ``first_k_dense_replace`` FFNs dense SwiGLU of width
  ``intermediate_size``, the rest MoE; final RMSNorm; untied head.
* MLA with no q LoRA: q = h Wq split into 128 nope + 64 rope dims per
  head; [c, k_pe] = h W_dkv, c RMS-normed; k_nope = c W_uk, v = c W_uv;
  k_pe shared by the heads; dense causal softmax attention at scale
  192^-0.5 mscale(40, 0.707)^2; o W_o.
* YaRN RoPE on the 64 rope dims, in closed form: inverse frequencies
  blend theta^(-2i/64) / factor and theta^(-2i/64) over the linear ramp
  between floor(d(beta_fast)) and ceil(d(beta_slow)),
  d(r) = 64 ln(4096 / (2 pi r)) / (2 ln theta); cos/sin times
  mscale(factor, mscale) / mscale(factor, mscale_all_dim).
* MoE: softmax over all ``router_experts`` router outputs, greedy top-6,
  gates not renormalised, times ``routed_scaling_factor``; the routed
  sum runs over the held experts only, one expert at a time over every
  token (weight 0 where the token did not choose it); plus the shared
  experts, one SwiGLU of width n_shared x 1408.
* The per-sequence balance loss (Sec. 3.2.3) over all router outputs:
  alpha sum_i f_i P_i, f_i = E / (K T) (tokens choosing i), P_i the mean
  probability, over the sequence's loaded tokens.

The round: client j's objective is L_j = sum of the next-token losses
over its first l_j targets + l_j sum over layers of its balance loss; the
gradient is sum over clients back by the deadline of (1 / p_j) grad L_j
/ (n S), clipped to global norm ``clip_norm``, then AdamW (decoupled
weight decay, bias-corrected moments).

Departures from ``modeling_deepseek.py``, each shared with the program:
RoPE rotates split halves of the rope dims where DeepSeek first
de-interleaves pairs (with random weights, a fixed permutation of the
rope columns of Wq and W_dkv); one chip's share of the experts is
computed and the absent experts' part left out; the vocabulary is a
slice; the balance loss enters each client's summed objective weighted by
its load, so that a client's loss is its mean-token loss plus the balance
loss, times its load.

Weights are one dict per layer (``ln1``, ``ln2``, ``mla``: wq, w_dkv,
kv_norm, w_uk, w_uv, wo; ``ffn``: w1, w3, w2, or ``moe``: router, w1,
w3, w2 (held experts), ws1, ws3, ws2), and ``embed``, ``final_norm``,
``lm_head``; `init_weights` makes them from a key at the configuration's
widths, and `Model` refuses weights of any other shape.  Layers run one at a time: a
client's forward keeps each layer's input, and its backward recomputes
each layer under ``jax.vjp``, so the reference fits beside the weights,
the gradient and AdamW's state.

Variants put in the program's place (``VARIANTS``): ``control`` runs
every product with fp8 (e4m3, one scale per tensor) operands; planted
faults: ``renorm_topk`` renormalises the top-6 gates, ``capacity_drop``
drops a held expert's tokens past capacity 1.25 S K / E in token order,
``no_yarn_mscale`` leaves mscale^2 out of the softmax scale,
``half_batch`` keeps the first half of the clients and doubles their
weight, ``wrong_share`` routes the tokens that choose experts 0..7 into
the held experts' weights.
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
VARIANTS = {"reference": {}, "control": {"operands": "fp8"},
            "renorm_topk": {"fault": "renorm_topk"},
            "capacity_drop": {"fault": "capacity_drop"},
            "no_yarn_mscale": {"fault": "no_yarn_mscale"},
            "half_batch": {"fault": "half_batch"},
            "wrong_share": {"fault": "wrong_share"}}
CAPACITY_FACTOR = 1.25


def _mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn(hp: dict) -> tuple[np.ndarray, float, float]:
    """(inverse frequencies (rope/2,), cos/sin amplitude, softmax scale)
    of DeepSeek-V2's YaRN, in closed form."""
    dim = hp["qk_rope_head_dim"]
    base = float(hp["rope_theta"])
    rs = hp["rope_scaling"]
    factor = float(rs["factor"])

    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2, dtype=np.float64)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    orig = base ** (-2.0 * i / dim)
    inv = orig / factor * ramp + orig * (1.0 - ramp)
    amp = _mscale(factor, rs["mscale"]) / _mscale(factor, rs["mscale_all_dim"])
    scale = (hp["qk_nope_head_dim"] + dim) ** -0.5 \
        * _mscale(factor, rs["mscale_all_dim"]) ** 2
    return inv.astype(np.float32), float(amp), float(scale)


def shapes(hp: dict) -> dict:
    """{tensor: shape} of one layer of each kind and of the rest, from
    the configuration's widths (``n_routed_experts`` the experts held)."""
    D, H = hp["hidden_size"], hp["num_attention_heads"]
    nope, rope = hp["qk_nope_head_dim"], hp["qk_rope_head_dim"]
    lora, hv = hp["kv_lora_rank"], hp["v_head_dim"]
    F, Fe = hp["intermediate_size"], hp["moe_intermediate_size"]
    Fs = Fe * hp["n_shared_experts"]
    held, V = hp["n_routed_experts"], hp["vocab_size"]
    mla = {"wq": (D, H, nope + rope), "w_dkv": (D, lora + rope),
           "kv_norm": (lora,), "w_uk": (lora, H, nope),
           "w_uv": (lora, H, hv), "wo": (H, hv, D)}
    norms = {"ln1": (D,), "ln2": (D,)}
    return {
        "top": {"embed": (V, D), "final_norm": (D,), "lm_head": (D, V)},
        "dense": dict(norms, mla=mla, ffn={"w1": (D, F), "w3": (D, F),
                                           "w2": (F, D)}),
        "moe": dict(norms, mla=mla, moe={
            "router": (D, hp["router_experts"]), "w1": (held, D, Fe),
            "w3": (held, D, Fe), "w2": (held, Fe, D), "ws1": (D, Fs),
            "ws3": (D, Fs), "ws2": (Fs, D)})}


def _layer_kinds(hp: dict) -> list:
    dense = int(hp["first_k_dense_replace"])
    return ["dense" if i < dense else "moe"
            for i in range(hp["num_hidden_layers"])]


def init_weights(hp: dict, key) -> dict:
    """Initial weights from `key` at the configuration's widths: each
    tensor normal from its own key (folded from its name), with standard
    deviation 1 / sqrt(its first axis) (for wo, (heads, v_head_dim,
    hidden), the head count), 0.02 for the router and the embedding, and
    norms 1.  Each routed expert draws from its global index (the held
    ones are ``experts_held_first`` on), so a share of the experts is a
    slice of the whole layer's."""
    sh = shapes(hp)
    first = int(hp["experts_held_first"])

    def draw(name, shape):
        k = jax.random.fold_in(key, zlib.crc32(name.encode()))
        leaf = name.rsplit("/", 1)[-1]
        if len(shape) == 1:                                    # the norms
            return jnp.ones(shape, jnp.float32)
        if leaf in ("router", "embed"):
            return 0.02 * jax.random.normal(k, shape, jnp.float32)
        if "/moe/" in name and leaf in ("w1", "w3", "w2"):
            return jnp.stack([jax.random.normal(
                jax.random.fold_in(k, first + e), shape[1:], jnp.float32)
                for e in range(shape[0])]) * shape[1] ** -0.5
        return jax.random.normal(k, shape, jnp.float32) * shape[0] ** -0.5

    def tree(prefix, spec):
        return {k: tree(f"{prefix}/{k}", v) if isinstance(v, dict)
                else draw(f"{prefix}/{k}", v) for k, v in spec.items()}

    out = tree("", sh["top"])
    out["layers"] = [tree(f"/layers/{i}", sh[kind])
                     for i, kind in enumerate(_layer_kinds(hp))]
    return out


def check_shapes(hp: dict, params: dict) -> None:
    """Raise unless every tensor of `params` has the shape the
    configuration's widths give it."""
    sh = shapes(hp)
    want = dict(sh["top"], layers=[sh[k] for k in _layer_kinds(hp)])
    got = jax.tree_util.tree_map(lambda a: tuple(a.shape), params)
    flat_w = jax.tree_util.tree_flatten_with_path(
        want, is_leaf=lambda x: isinstance(x, tuple))[0]
    flat_g = jax.tree_util.tree_flatten_with_path(
        got, is_leaf=lambda x: isinstance(x, tuple))[0]
    if [p for p, _ in flat_w] != [p for p, _ in flat_g]:
        raise ValueError("weights are not laid out as the configuration's")
    for (path, w), (_, g) in zip(flat_w, flat_g):
        if w != g:
            raise ValueError(f"weights {jax.tree_util.keystr(path)} are "
                             f"{g}; the configuration's widths give {w}")


class Model:
    """The layer functions of one variant, jitted once each."""

    def __init__(self, hp: dict, first: int, operands: str = "f32",
                 fault: str | None = None):
        if fault not in (None,) + tuple(v["fault"] for v in VARIANTS.values()
                                        if "fault" in v):
            raise ValueError(f"unknown fault {fault!r}")
        self.hp, self.first, self.fault = hp, first, fault
        self.operands = operands
        inv, self.amp, scale = yarn(hp)
        self.inv = jnp.asarray(inv)
        if fault == "no_yarn_mscale":
            scale = (hp["qk_nope_head_dim"] + hp["qk_rope_head_dim"]) ** -0.5
        self.scale = scale
        self.dense_fwd = jax.jit(lambda p, x, live: self.layer(p, x, live,
                                                               False))
        self.moe_fwd = jax.jit(lambda p, x, live: self.layer(p, x, live,
                                                             True))
        self.dense_bwd = jax.jit(lambda p, x, live, dy, db: self._pull(
            p, x, live, dy, db, False))
        self.moe_bwd = jax.jit(lambda p, x, live, dy, db: self._pull(
            p, x, live, dy, db, True))
        self.head_fwd = jax.jit(self.head)
        self.head_bwd = jax.jit(lambda p, x, lab, live, c: jax.vjp(
            lambda p_, x_: self.head(p_, x_, lab, live), p, x)[1](c))

    # ------------------------------------------------------- arithmetic
    def q(self, a):
        if self.operands == "f32":
            return a
        s = jnp.max(jnp.abs(a)) / 448.0
        s = jnp.where(s > 0, s, 1.0)
        return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    def mm(self, eq, a, b):
        return jnp.einsum(eq, self.q(a), self.q(b), precision=HIGHEST)

    def norm(self, x, w):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.hp["rms_norm_eps"]) * w

    def swiglu(self, h, w1, w3, w2):
        a = self.mm("sd,df->sf", h, w1)
        return self.mm("sf,fd->sd", jax.nn.silu(a) * self.mm(
            "sd,df->sf", h, w3), w2)

    def rope(self, x, pos):
        ang = pos[:, None].astype(jnp.float32) * self.inv      # (S, r/2)
        if x.ndim == 3:
            ang = ang[:, None, :]
        cos, sin = jnp.cos(ang) * self.amp, jnp.sin(ang) * self.amp
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1)

    # ------------------------------------------------------- the layer
    def mla(self, p, h):
        hp = self.hp
        S = h.shape[0]
        pos = jnp.arange(S)
        nope, lora = hp["qk_nope_head_dim"], hp["kv_lora_rank"]
        q = self.mm("sd,dhk->shk", h, p["wq"])
        q = jnp.concatenate([q[..., :nope], self.rope(q[..., nope:], pos)],
                            axis=-1)
        ckv = self.mm("sd,dl->sl", h, p["w_dkv"])
        c = self.norm(ckv[:, :lora], p["kv_norm"])
        k_pe = self.rope(ckv[:, lora:], pos)
        k_nope = self.mm("sl,lhk->shk", c, p["w_uk"])
        v = self.mm("sl,lhk->shk", c, p["w_uv"])
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_pe[:, None, :], k_nope.shape[:2] + k_pe.shape[-1:])], axis=-1)
        s = self.mm("shk,thk->hst", q, k) * self.scale
        causal = pos[None, :] <= pos[:, None]
        s = jnp.where(causal[None], s, -jnp.inf)
        o = self.mm("hst,thk->shk", jax.nn.softmax(s, axis=-1), v)
        return self.mm("shk,hkd->sd", o, p["wo"])

    def moe(self, p, h, live):
        hp = self.hp
        E, K = hp["router_experts"], hp["num_experts_per_tok"]
        count = p["w1"].shape[0]
        S = h.shape[0]
        probs = jax.nn.softmax(jnp.dot(h, p["router"], precision=HIGHEST),
                               axis=-1)
        gate, idx = jax.lax.top_k(probs, K)
        if self.fault == "renorm_topk":
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        gate = gate * hp["routed_scaling_factor"] * live[:, None]
        chosen = jax.nn.one_hot(idx, E) * live[:, None, None]  # (S, K, E)
        if self.fault == "capacity_drop":
            cap = max(int(CAPACITY_FACTOR * S * K / E), 1)
            pos = jnp.cumsum(chosen.reshape(S * K, E), axis=0).reshape(
                S, K, E) * chosen - 1
            chosen = chosen * (pos < cap)
        out = self.swiglu(h, p["ws1"], p["ws3"], p["ws2"])
        first = 0 if self.fault == "wrong_share" else self.first
        for e in range(count):
            g = jnp.sum(gate * chosen[:, :, first + e], axis=-1)
            out = out + g[:, None] * self.swiglu(h, p["w1"][e], p["w3"][e],
                                                 p["w2"][e])
        T = jnp.maximum(jnp.sum(live), 1.0)
        f = jnp.sum(jax.nn.one_hot(idx, E) * live[:, None, None],
                    axis=(0, 1)) * E / (K * T)
        P = jnp.sum(probs * live[:, None], axis=0) / T
        return out, hp["aux_loss_alpha"] * jnp.sum(f * P)

    def layer(self, p, x, live, is_moe: bool):
        """One sequence's layer: (x, balance loss)."""
        x = x + self.mla(p["mla"], self.norm(x, p["ln1"]))
        h = self.norm(x, p["ln2"])
        if is_moe:
            out, bal = self.moe(p["moe"], h, live)
        else:
            f = p["ffn"]
            out, bal = self.swiglu(h, f["w1"], f["w3"], f["w2"]), 0.0
        return x + out, jnp.float32(bal)

    def _pull(self, p, x, live, dy, db, is_moe):
        _, pull = jax.vjp(lambda p_, x_: self.layer(p_, x_, live, is_moe),
                          p, x)
        return pull((dy, db))

    def head(self, p, x, labels, live):
        """Next-token loss summed over the live targets."""
        logits = self.mm("sd,dv->sv", self.norm(x, p["final_norm"]),
                         p["lm_head"])
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, labels[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * live)


class Round:
    """Rounds of the federated model, from given weights and AdamW state.

    `hp` holds the configuration's keys (DeepSeek-V2's ``config.json``
    names, with ``router_experts``, the router's width, and
    ``aux_loss_alpha``); `first` is the first held expert; `train` the
    optimiser: ``learning_rate``, ``weight_decay``, ``adam_b1``,
    ``adam_b2``, ``adam_eps``, ``clip_norm``.
    """

    def __init__(self, hp: dict, first: int, train: dict, *,
                 operands: str = "f32", fault: str | None = None):
        self.model = Model(hp, first, operands, fault)
        self.hp, self.train, self.fault = hp, train, fault
        self.dense = int(hp["first_k_dense_replace"])

    def client(self, params, tokens, load: int, coef: float | None, grad):
        """Client j's loss summed over its load and, when `coef` is given,
        `grad` + grad of coef * L_j (added layer by layer)."""
        md = self.model
        S = tokens.shape[0] - 1
        inp, lab = tokens[:-1], tokens[1:]
        live = (jnp.arange(S) < load).astype(jnp.float32)
        x = params["embed"][inp]
        xs = []
        for i, p in enumerate(params["layers"]):
            xs.append(x)
            x, _ = (md.dense_fwd if i < self.dense else md.moe_fwd)(p, x, live)
        head = {k: params[k] for k in ("final_norm", "lm_head")}
        nll = float(md.head_fwd(head, x, lab, live))
        if coef is None:
            return nll, grad
        dhead, dx = md.head_bwd(head, x, lab, live, jnp.float32(coef))
        grad = dict(grad, **{k: _add(grad[k], dhead[k]) for k in dhead})
        layers = list(grad["layers"])
        db = jnp.float32(coef * load)
        for i in reversed(range(len(xs))):
            pull = md.dense_bwd if i < self.dense else md.moe_bwd
            dp, dx = pull(params["layers"][i], xs[i], live, dx, db)
            layers[i] = _add(layers[i], dp)
        grad["layers"] = layers
        grad["embed"] = _scatter_add(grad["embed"], inp, dx)
        return nll, grad

    def play(self, state: dict, tokens, loads, returned, p_return,
             lr: float) -> tuple[dict, float]:
        """One round from `state` ``{"params", "m", "v", "t"}`` (its
        arrays are consumed): returns (the new state, the mean next-token
        loss over every loaded target at the round's start)."""
        check_shapes(self.hp, state["params"])
        tr = self.train
        n = len(loads)
        m_pts = n * (tokens.shape[1] - 1)
        w = np.where(returned & (loads > 0), 1.0 / np.where(
            p_return > 0, p_return, 1.0), 0.0)
        if self.fault == "half_batch":
            w = np.where(np.arange(n) < n // 2, 2.0 * w, 0.0)
        params = state["params"]
        grad = jax.tree_util.tree_map(jnp.zeros_like, params)
        nll = 0.0
        for j in range(n):
            if loads[j] <= 0:
                continue
            coef = float(w[j]) / m_pts if w[j] > 0 else None
            nll_j, grad = self.client(params, tokens[j], int(loads[j]), coef,
                                      grad)
            nll += nll_j
        loss = nll / max(int(np.sum(loads)), 1)
        leaves = jax.tree_util.tree_leaves(grad)
        gnorm = float(np.sqrt(sum(float(jnp.sum(x * x)) for x in leaves)))
        t = state["t"] + 1
        p, m, v = _adamw(params, state["m"], state["v"], grad,
                         min(1.0, tr["clip_norm"] / (gnorm + 1e-6)), lr, t,
                         tr["adam_b1"], tr["adam_b2"], tr["adam_eps"],
                         tr["weight_decay"])
        return {"params": p, "m": m, "v": v, "t": t}, loss


@functools.partial(jax.jit, donate_argnums=0)
def _add(acc, g):
    return jax.tree_util.tree_map(jnp.add, acc, g)


@functools.partial(jax.jit, donate_argnums=0)
def _scatter_add(table, rows, d):
    return table.at[rows].add(d)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adamw(params, m, v, grad, clip, lr, t, b1, b2, eps, wd):
    """AdamW with decoupled weight decay and bias-corrected moments, on
    the gradient times `clip`."""
    tm = jax.tree_util.tree_map
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    m = tm(lambda a, g: b1 * a + (1 - b1) * clip * g, m, grad)
    v = tm(lambda a, g: b2 * a + (1 - b2) * (clip * g) ** 2, v, grad)
    params = tm(lambda x, a, b: x - lr * (a / bc1 / (jnp.sqrt(b / bc2) + eps)
                                          + wd * x), params, m, v)
    return params, m, v
