"""The federated round of a language model (``ExperimentSpec.model``).

The coded scheme's set-up is used as it is: the two-step allocation gives
the deadline t*, the loads l*_j and p_j = P(T_j <= t*) (`CodedScheme.setup`).
No parity set is built: parity coding is linear only (paper Sec. III).  A
point is a next-token target, and client j's load is the prefix of its
first l*_j targets of its one sequence; under causal attention, masking
the loss past l*_j is the sequence truncated to l*_j.  One round:

    g = sum_j (ret_j / p_j) grad L_j / m,    m = n S

with ``L_j`` the client's loss summed over its loaded prefix
(`repro.models.transformer.client_losses`) and ``ret_j`` whether it is
back by t* with a load; a client with l*_j = 0 contributes nothing.  The
gradient is taken over micro-batches of whole clients accumulated in a
scan, clipped to global norm `CLIP_NORM` and applied by AdamW.

The iterate ``theta`` is ``{"params", "opt": {"m", "v", "t"}}``; the
block driver of `repro.core.fed_runtime` carries it through the same
packed upload, scan and packed fetch as the RFF round (it donates the
iterate to the scan, so a block consumes its input state's buffers).
Guards: a returned client whose loss is non-finite is masked out of the
sum (``n_masked``); a round whose gradient norm is non-finite is skipped
and the learning rate backs off, as the RFF round's divergence guard does
(with a finite gradient, AdamW on a finite state stays finite).

Per round the scan emits, after the four outputs every round has
(`EXTRAS`): the loss (mean next-token loss over every loaded target,
before the update), the returned mask as bits, the token-expert
assignments to held experts and the rows the expert matmuls computed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer
from repro.obs import spans as obs_spans

#: per-round outputs of the round after t_round, n_ret, n_masked, skipped
EXTRAS = ("loss", "ret_bits", "routed", "rows")

#: AdamW's betas and epsilon, and the global-norm clip of the round
#: gradient (DeepSeek-V2's recipe, arXiv:2405.04434 Sec. 3.2)
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
CLIP_NORM = 1.0


def param_count(cfg) -> int:
    """Parameters of the model as held here (shapes only)."""
    shapes = jax.eval_shape(functools.partial(transformer.init_params, cfg),
                            jax.random.PRNGKey(0))
    return int(sum(math.prod(leaf.shape)
                   for leaf in jax.tree_util.tree_leaves(shapes)))


def validate(exp) -> None:
    """What the model round needs of a built `Experiment`."""
    lm = exp.lm
    if exp.step_kind != "coded":
        raise ValueError(
            f"a model spec trains under the coded scheme's deadline; scheme "
            f"{exp.scheme!r} runs the {exp.step_kind!r} round")
    if exp.n > 31:
        raise ValueError(f"a model round packs its returned mask in one "
                         f"int32 word: at most 31 clients, got {exp.n}")
    mb = lm.micro_batch or exp.n
    if exp.n % mb:
        raise ValueError(f"model.micro_batch={mb} does not divide the "
                         f"{exp.n} clients")
    cfg = lm.model
    if cfg.is_encdec or cfg.n_prefix_patches or cfg.arch_type in (
            "ssm", "hybrid"):
        raise ValueError(f"model {cfg.name!r}: the federated round trains "
                         "decoder-only attention models")


def _check_weights(cfg, params) -> None:
    """Raise unless `params` has the model's layout: its tree, and every
    leaf's shape and dtype, as `transformer.init_params` makes them."""
    want = jax.eval_shape(functools.partial(transformer.init_params, cfg),
                          jax.random.PRNGKey(0))
    if (jax.tree_util.tree_structure(params)
            != jax.tree_util.tree_structure(want)):
        raise ValueError(f"model {cfg.name!r}: the given weights are not "
                         "laid out as the model's parameters")
    for (path, w), g in zip(jax.tree_util.tree_flatten_with_path(want)[0],
                            jax.tree_util.tree_leaves(params)):
        if tuple(g.shape) != w.shape or g.dtype != w.dtype:
            raise ValueError(
                f"model {cfg.name!r}: weights "
                f"{jax.tree_util.keystr(path)} are {tuple(g.shape)} "
                f"{g.dtype}; the model's are {w.shape} {w.dtype}")


def init_theta(lm, params=None) -> dict:
    """The parameters and a zero AdamW state, made on the device (span
    ``model/init``, synced when spans are on).  The parameters are made
    from ``lm.init_seed`` in one jitted call, or are the caller's
    `params` (`transformer.init_params`'s layout, checked leaf by leaf),
    which the state then holds: the first block consumes them."""
    cfg = lm.model

    def zeros(tree):
        return jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), tree)

    @jax.jit
    def make(key):
        params = transformer.init_params(cfg, key)
        return params, zeros(params), zeros(params)

    with obs_spans.span("model/init"):
        if params is None:
            params, m, v = make(jax.random.PRNGKey(int(lm.init_seed)))
        else:
            _check_weights(cfg, params)
            m, v = jax.jit(lambda p: (zeros(p), zeros(p)))(params)
        theta = {"params": params,
                 "opt": {"m": m, "v": v, "t": jnp.zeros((), jnp.int32)}}
        if obs_spans.enabled():
            jax.block_until_ready(theta)
    return theta


def consts(exp) -> dict:
    """The round's per-deployment arrays: the clients' token ids, t*, the
    loads and p_j."""
    return {"tokens": exp.x,
            "t_star": jnp.float32(exp.t_star),
            "loads": jnp.asarray(exp.loads, jnp.int32),
            "p_return": jnp.asarray(exp.p_return, jnp.float32)}


def build_step(exp):
    """One round ``step(consts, (theta, lr_scale), (t_row, lr))``; see
    the module docstring for the outputs."""
    from repro.core.fed_runtime import LR_BACKOFF
    cfg = exp.lm.model
    n = exp.n
    mb = exp.lm.micro_batch or n
    groups = n // mb
    m = float(exp.m)
    wd = float(exp.train.weight_decay)
    b1, b2, eps, clip = ADAM_B1, ADAM_B2, ADAM_EPS, CLIP_NORM
    tmap = jax.tree_util.tree_map

    def step(consts, carry, inp):
        theta, lr_scale = carry
        t_row, lr = inp
        params, opt = theta["params"], theta["opt"]
        loads = consts["loads"]
        t_star = consts["t_star"]
        ret = (t_row <= t_star) & (loads > 0)
        p = consts["p_return"]
        w = jnp.where(ret, 1.0 / jnp.where(p > 0, p, 1.0), 0.0)

        def micro(acc, xs):
            tok, ld, wj = xs
            L, pull, st = jax.vjp(
                lambda pr: transformer.client_losses(cfg, pr, tok, ld),
                params, has_aux=True)
            ok = jnp.isfinite(L)
            (g,) = pull(jnp.where(ok, wj, 0.0) / m)
            return tmap(jnp.add, acc, g), (st["nll"], ok, st["routed"],
                                           st["rows"])

        def split(a):
            return a.reshape((groups, mb) + a.shape[1:])

        g, (nll, ok, routed, rows) = jax.lax.scan(
            micro, tmap(jnp.zeros_like, params),
            (split(consts["tokens"]), split(loads), split(w)))
        nll, ok = nll.reshape(n), ok.reshape(n)
        n_masked = jnp.sum(ret & ~ok).astype(jnp.int32)
        loss = (jnp.sum(jnp.where(loads > 0, nll, 0.0))
                / jnp.maximum(jnp.sum(loads), 1).astype(jnp.float32))

        leaves = jax.tree_util.tree_leaves(g)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))
        g = tmap(lambda x: x * jnp.minimum(1.0, clip / (gnorm + 1e-6)), g)
        t = opt["t"] + 1
        bc1 = 1.0 - b1 ** t.astype(jnp.float32)
        bc2 = 1.0 - b2 ** t.astype(jnp.float32)
        m1 = tmap(lambda a, b: b1 * a + (1.0 - b1) * b, opt["m"], g)
        v1 = tmap(lambda a, b: b2 * a + (1.0 - b2) * b * b, opt["v"], g)
        step_lr = lr * lr_scale
        p1 = tmap(lambda x, a, b: (x - step_lr * (
            a / bc1 / (jnp.sqrt(b / bc2) + eps) + wd * x)).astype(x.dtype),
            params, m1, v1)
        # always-on divergence guard: never commit a non-finite round
        ok_round = jnp.isfinite(gnorm)
        new = {"params": p1, "opt": {"m": m1, "v": v1, "t": t}}
        theta_new = tmap(lambda a, b: jnp.where(ok_round, a, b), new, theta)
        lr_scale_new = jnp.where(ok_round, lr_scale,
                                 lr_scale * jnp.float32(LR_BACKOFF))
        ret_bits = jnp.sum(ret.astype(jnp.int32)
                           << jnp.arange(n, dtype=jnp.int32))
        out = (t_star, jnp.sum(ret).astype(jnp.int32), n_masked,
               (~ok_round).astype(jnp.int32), loss, ret_bits,
               jnp.sum(routed), jnp.sum(rows))
        return (theta_new, lr_scale_new), out

    return step


def unpack_extras(words: np.ndarray, K: int) -> dict:
    """The `EXTRAS` of a block's packed outputs (the words after the
    four per-round outputs and the lr scale)."""
    cols = words.reshape(len(EXTRAS), K)
    return {"loss": cols[0].view(np.float32).astype(np.float64),
            "ret_bits": cols[1].astype(np.int64),
            "routed": cols[2].astype(np.int64),
            "rows": cols[3].astype(np.int64)}

