"""A DeepSeek-V2 language model trained through the program's federated
coded-deadline round, as a kind of cell.

* inputs: one sequence of token ids per client, drawn uniformly from the
  vocabulary slice on the device, and the program's seeds;
* system: ``repro.api.build_experiment`` with a model spec
  (``ExperimentSpec.model``), one ``run_block`` call a step.  Its
  snapshot is the round's loss and, per weight tensor, the norm and a
  seeded random projection <leaf, r> of the parameters and of AdamW's
  first moment; never the iterate, except at the window's end, where the
  parameters and AdamW's state are copied to the host for the tail;
* weights: made from the seed by the reference's `init_weights`, at the
  configuration's widths, and handed to the program, which checks every
  tensor's shape against its own model before it trains them;
* comparison: the plain reference (``bench/lm_reference.py``) plays the
  first ``steps_compared`` rounds from the same initial weights, draws
  the window's rounds without playing them, then plays the first tail
  round from the program's window-end state.  Set-up is compared with an
  independent allocation (``bench/reference.py``).  Compared numbers, each
  a gap that reads 0 when the sides agree:

  ``t_star``       |t*_p - t*_r| / (1 + t*_r)
  ``loads_off``    clients whose whole-token load is more than one token
                   off the floor of the reference's optimum
  ``parity_x``,    rows of a parity set the program built, where the
  ``parity_y``     reference builds none (parity coding is linear only)
  ``loss_k``       |L_p - L_r| / L_r, the round-k loss (k = 1..3)
  ``grad_1``       worst weight tensor, of AdamW's first moment after
                   round 1 ((1 - b1) times the clipped gradient): the gap
                   of norms and of projections, relative to the
                   reference's norm
  ``change_3``     worst weight tensor, of the parameters' change after
                   3 rounds: gap of projections over the reference's
                   norm of the change
  ``tail_loss``    the loss gap of the first round after the window, both
                   sides from the program's window-end state (reckoned,
                   not judged: ``tail_change`` holds that round)
  ``tail_change``  ``change_3`` of that round's update
  ``returns_off``  share of all rounds played whose mask of clients back
                   by t* differs between the sides

* work count (ctx): the tokens the window's rounds needed (the loaded
  prefixes of the clients back by t*, ``tokens_needed``), their forward
  and backward FLOPs (``flops_needed``), and the held experts' size.
"""
from __future__ import annotations

import functools
import json
import math
import zlib

import numpy as np

import generate
import lm_reference
import reference

VARIANTS = lm_reference.VARIANTS
#: key of the per-tensor projection vectors
PROJ_KEY = 20240507


# ----------------------------------------------------------- configuration
def shape_counts(cfg: dict) -> dict:
    """Per-token matrix parameters and total parameters of the model as
    held here, reckoned from the configuration's widths."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    lora, hv = cfg["kv_lora_rank"], cfg["v_head_dim"]
    F, Fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    E, K, held = cfg["router_experts"], cfg["num_experts_per_tok"], \
        cfg["n_routed_experts"]
    Fs = Fe * cfg["n_shared_experts"]
    V = cfg["vocab_size"]
    L, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    mla = D * H * (nope + rope) + D * (lora + rope) + lora * H * (nope + hv) \
        + H * hv * D
    moe_tok = 3 * D * Fs + D * E + K * held / E * 3 * D * Fe
    per_token = L * mla + dense * 3 * D * F + (L - dense) * moe_tok + D * V
    params = (2 * V * D + D + L * (mla + lora + 2 * D) + dense * 3 * D * F
              + (L - dense) * (D * E + held * 3 * D * Fe + 3 * D * Fs))
    return {"per_token": per_token, "params": params,
            "attn": L * H * (nope + rope + hv),
            "expert_bytes": held * 3 * D * Fe * 4,
            "moe_layers": L - dense}


def model_config(cfg: dict):
    """The program's ``ModelConfig`` of this configuration (f32 storage).
    The program's MoE layer is DeepSeek-V2's: softmax scores, greedy
    top-k, the per-sequence balance loss; another setting is refused."""
    from repro.config import MLAConfig, ModelConfig, MoEConfig, YarnConfig
    if (cfg["scoring_func"], cfg["topk_method"], cfg["seq_aux"],
            cfg["q_lora_rank"]) != ("softmax", "greedy", True, None):
        raise ValueError("the program's MLA and MoE layers are DeepSeek-V2-"
                         "Lite's: softmax greedy top-k, seq_aux, no q LoRA")
    rs = cfg["rope_scaling"]
    return ModelConfig(
        name=cfg["name"], arch_type="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"], dtype="float32",
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        moe=MoEConfig(num_experts=cfg["router_experts"],
                      num_shared_experts=cfg["n_shared_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      norm_topk_prob=cfg["norm_topk_prob"],
                      routed_scaling_factor=float(
                          cfg["routed_scaling_factor"]),
                      dropless=True,
                      aux_loss_weight=cfg["aux_loss_alpha"],
                      experts_held=(cfg["experts_held_first"],
                                    cfg["n_routed_experts"])),
        yarn=YarnConfig(
            factor=float(rs["factor"]), beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]),
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]))


def make_spec(cfg: dict, traffic: dict, fl_seed: int, init_seed: int):
    from repro.config import ExperimentSpec, FLConfig, LMSpec, TrainConfig
    net, tr = cfg["network"], cfg["train"]
    fl = FLConfig(
        n_clients=cfg["clients"], scheme=traffic["scheme"],
        delta=cfg["delta"], max_rate_bps=net["max_rate_bps"],
        rate_decay=net["rate_decay"], max_mac_rate=net["max_mac_rate"],
        mac_decay=net["mac_decay"], alpha=net["alpha"],
        p_erasure=net["p_erasure"], overhead=net["overhead"],
        bits_per_scalar=net["bits_per_scalar"], seed=fl_seed)
    train = TrainConfig(optimizer="adamw", learning_rate=tr["learning_rate"],
                        weight_decay=tr["weight_decay"], lr_decay_epochs=())
    lm = LMSpec(model=model_config(cfg), init_seed=init_seed,
                micro_batch=cfg["micro_batch"])
    return ExperimentSpec(fl=fl, train=train, scheme=traffic["scheme"],
                          channel_profile=traffic["channel_profile"],
                          checkpoint_every=traffic["rounds_per_block"],
                          model=lm)


def network(cfg: dict, fl_seed: int) -> dict:
    """Per-client delay parameters (the paper's Sec. V-A network): a
    token costs 3 MACs per parameter, a packet carries the parameters."""
    net = cfg["network"]
    n = cfg["clients"]
    P = shape_counts(cfg)["params"]
    payload = P * net["bits_per_scalar"] * (1.0 + net["overhead"])
    rng = np.random.default_rng(fl_seed)
    rate_f = net["rate_decay"] ** np.arange(n)
    mac_f = net["mac_decay"] ** np.arange(n)
    rng.shuffle(rate_f)
    rng.shuffle(mac_f)
    return {"mu": net["max_mac_rate"] * mac_f / float(3 * P),
            "alpha": np.full(n, float(net["alpha"])),
            "tau": payload / (net["max_rate_bps"] * rate_f),
            "p": np.full(n, float(net["p_erasure"]))}


def _delays(rng, net: dict, loads, rounds: int) -> np.ndarray:
    """One block's (rounds, n) delays: geometric down, geometric up, unit
    exponential, in the program's order."""
    p = np.asarray(net["p"], np.float64)
    loads = np.asarray(loads, np.float64)
    n = len(loads)
    down = rng.geometric(1.0 - p, size=(rounds, n))
    up = rng.geometric(1.0 - p, size=(rounds, n))
    active = loads > 0
    scale = np.where(active, loads / (net["alpha"] * net["mu"]), 0.0)
    stoch = rng.exponential(1.0, size=(rounds, n)) * scale
    return net["tau"] * (down + up) + np.where(active, loads / net["mu"],
                                               0.0) + stoch


def flops(cfg: dict, loads, reached) -> float:
    """Forward and backward FLOPs the rounds of `reached` ((rounds, n)
    clients back by t*) need: each loaded target passes the matrix
    parameters a token meets (routed experts at their expected share
    K held / E), and causal attention over its prefix."""
    c = shape_counts(cfg)
    L = np.asarray(loads, np.float64)[None, :] * np.asarray(reached)
    fwd = 2.0 * L * c["per_token"] + c["attn"] * L * (L + 1.0)
    return float(3.0 * np.sum(fwd))


# ------------------------------------------------------------- fingerprint
def ref_layout(params: dict, dense: int) -> dict:
    """The program's parameters (device or host arrays) as the reference
    lays them out: one dict per layer (the stacked stages unstacked)."""
    import jax
    layers = []
    for si, count in ((0, dense), (1, None)):
        stage = params[f"stage{si}"]["l0"]
        n = jax.tree_util.tree_leaves(stage)[0].shape[0]
        for i in range(n if count is None else count):
            layers.append(jax.tree_util.tree_map(lambda a: a[i], stage))
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": params["lm_head"], "layers": layers}


def program_layout(ref: dict, dense: int) -> dict:
    """Reference-layout weights as the program lays them out: the `dense`
    leading layers stacked as one stage, the MoE layers as another."""
    import jax
    import jax.numpy as jnp

    def stack(layers):
        return {"l0": jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                             *layers)}
    return {"embed": ref["embed"], "final_norm": ref["final_norm"],
            "lm_head": ref["lm_head"], "stage0": stack(ref["layers"][:dense]),
            "stage1": stack(ref["layers"][dense:])}


@functools.lru_cache(maxsize=None)
def _init(cfg_json: str, dense: int | None):
    """The jitted weight maker of a configuration: reference layout, or
    the program's with the count of `dense` layers."""
    import jax
    hp = json.loads(cfg_json)
    if dense is None:
        return jax.jit(lambda key: lm_reference.init_weights(hp, key))
    return jax.jit(lambda key: program_layout(
        lm_reference.init_weights(hp, key), dense))


def weights(cfg: dict, seed: int, dense: int | None = None) -> dict:
    """The initial weights from `seed`, on the device, in one jitted call
    (see `_init`)."""
    import jax
    return _init(json.dumps(cfg, sort_keys=True), dense)(
        jax.random.PRNGKey(seed))


def _named(tree) -> list:
    import jax
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.append(("/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                             for k in path), leaf))
    return out


def _values(tree, dense):
    import jax
    import jax.numpy as jnp
    if dense is not None:
        tree = ref_layout(tree, dense)
    out = []
    for name, leaf in _named(tree):
        r = jax.random.normal(jax.random.fold_in(
            jax.random.PRNGKey(PROJ_KEY), zlib.crc32(name.encode())),
            leaf.shape, jnp.float32)
        x = leaf.astype(jnp.float32)
        out.append(jnp.stack([jnp.sqrt(jnp.sum(x * x)), jnp.sum(x * r)]))
    return jnp.stack(out)


def _diff_norms(a, b):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda u, v: jnp.sqrt(jnp.sum(jnp.square(u - v))), a, b)


@functools.lru_cache(maxsize=None)
def _jitted(name: str):
    import jax
    if name == "values":
        return jax.jit(_values, static_argnums=1)
    return jax.jit(_diff_norms)


def fingerprint(tree, dense: int | None = None) -> dict:
    """{tensor name: (norm, <tensor, r>)} over a reference-layout tree (or
    a program-layout one, unstacked inside the reduction, with the count
    of `dense` layers), r a seeded standard normal tensor per name; one
    jitted reduction."""
    import jax
    shapes = tree if dense is None else jax.eval_shape(
        lambda t: ref_layout(t, dense), tree)
    names = [n for n, _ in _named(shapes)]
    vals = np.asarray(_jitted("values")(tree, dense), np.float64)
    return {n: (float(v[0]), float(v[1])) for n, v in zip(names, vals)}


def _norms(a, b) -> dict:
    """{name: ||a - b||} of two reference-layout trees."""
    return {n: float(v) for n, v in _named(_jitted("norms")(a, b))}


# ------------------------------------------------------------- the kind
def make_inputs(cfg: dict, traffic: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    fl_seed, data_seed = generate.seeds(seed)
    tokens = jax.random.randint(
        jax.random.PRNGKey(data_seed),
        (cfg["clients"], cfg["tokens_per_client"] + 1), 0,
        cfg["vocab_size"], dtype=jnp.int32)
    return {"fl_seed": fl_seed, "init_seed": data_seed + 1,
            "tokens": tokens}


class System:
    """The deployment built through ``repro.api.build_experiment``."""

    def __init__(self, cfg: dict, traffic: dict, inputs: dict):
        from repro.api import build_experiment
        spec = make_spec(cfg, traffic, inputs["fl_seed"],
                         inputs["init_seed"])
        self.exp = build_experiment(spec, inputs["tokens"])
        self.dense = cfg["first_k_dense_replace"]
        self.state = self.exp.init_state(10 ** 9, params=weights(
            cfg, inputs["init_seed"], self.dense))
        self.end_at = int(traffic["steps_compared"]) + 1
        self.snaps = 0
        self.tail1 = None
        self._tail_next = False

    def step(self) -> int:
        r0 = self.state.rounds_done
        self.state = self.exp.run_block(self.state)
        if self._tail_next:
            self._tail_next = False
            self.tail1 = fingerprint(self.state.theta["params"], self.dense)
        return self.state.rounds_done - r0

    def skipped(self) -> int:
        return int(np.sum(self.state.skipped))

    def snapshot(self) -> dict:
        import jax
        self.snaps += 1
        theta = self.state.theta
        snap = {"loss": float(self.state.lm_log["loss"][-1]),
                "params": fingerprint(theta["params"], self.dense),
                "m": fingerprint(theta["opt"]["m"], self.dense),
                "rounds": self.state.rounds_done}
        if self.snaps == self.end_at:
            # the window-end iterate to the host, leaf by leaf, unstacked
            # there (views)
            host = jax.tree_util.tree_map(np.asarray, {
                "params": theta["params"], "m": theta["opt"]["m"],
                "v": theta["opt"]["v"]})
            snap["state"] = {k: ref_layout(v, self.dense)
                             for k, v in host.items()}
            snap["state"]["t"] = int(theta["opt"]["t"])
            self._tail_next = True
        return snap

    def handoff(self) -> dict:
        e, log = self.exp, self.state.lm_log
        return {"t_star": float(e.t_star), "loads": np.asarray(e.loads),
                "parity_rows": 0 if e.parity is None
                else int(e.parity.x.shape[0]),
                "returned": _bits(log["ret_bits"], e.n),
                "losses": np.asarray(log["loss"]), "tail1": self.tail1,
                "lr": float(e.train.learning_rate)}


def _bits(ret_bits, n: int) -> np.ndarray:
    return ((np.asarray(ret_bits, np.int64)[:, None] >> np.arange(n)) & 1) \
        .astype(bool)


def _gap(p: dict, r: dict, norm_too: bool, scale: dict | None = None):
    """Worst tensor's gap of fingerprints `p` against `r`: projections
    (and norms) relative to the reference's norm, or to `scale`."""
    worst = 0.0
    for name, (nr, pr) in r.items():
        den = max((scale or {}).get(name, nr), 1e-30)
        gap = abs(p[name][1] - pr) / den
        if norm_too:
            gap = max(gap, abs(p[name][0] - nr) / den)
        worst = max(worst, gap)
    return worst


def check(cfg: dict, traffic: dict, inputs: dict, ran: dict, limits: dict,
          variants=()) -> tuple[bool, dict, dict, dict]:
    import gc

    import jax
    import jax.numpy as jnp

    import compare

    steps = len(ran["warm"])
    n, S = cfg["clients"], cfg["tokens_per_client"]
    fl_seed = inputs["fl_seed"]
    net = network(cfg, fl_seed)
    u = max(1, int(round(cfg["delta"] * n * S)))
    t_star, real = reference.allocate(net, float(S), u, float(n * S))
    loads = np.minimum(np.floor(real).astype(np.int64), S)
    p_ret = np.where(loads > 0, reference.return_prob(net, t_star, loads),
                     0.0)
    rng = np.random.default_rng(fl_seed + 17)
    rpb = int(traffic["rounds_per_block"])

    def calls(k):
        """The next `k` calls' (k rpb, n) masks of clients back by t*,
        drawn call by call as the program draws them."""
        out = [_delays(rng, net, loads, rpb) <= t_star for _ in range(k)]
        return np.concatenate(out) if out else np.zeros((0, n), bool)

    warm, window, tail = calls(steps), calls(ran["calls"]), calls(steps)
    returned = np.concatenate([warm, window, tail]) & (loads > 0)

    tail_h = ran["tail"]
    tokens = np.asarray(inputs["tokens"])
    lr = tail_h["lr"]
    train = dict(cfg["train"])
    first = cfg["experts_held_first"]
    # the initial weights, made again from the seed
    host0 = jax.tree_util.tree_map(np.asarray,
                                   weights(cfg, inputs["init_seed"]))
    gc.collect()
    end = ran["window_end"]
    toks = jnp.asarray(tokens)

    def zeros(t):
        return jax.tree_util.tree_map(jnp.zeros_like, t)

    def on_device(tree):
        return jax.tree_util.tree_map(jnp.asarray, tree)

    def replay(variant: str) -> dict:
        """The variant's warm rounds from the initial weights and its
        first tail round from the program's window-end state (each round
        consumes the state it is given; the starting points are read
        again from the host for the norms of the change)."""
        rd = lm_reference.Round(cfg, first, train, **VARIANTS[variant])
        with jax.default_matmul_precision("highest"):
            p0 = on_device(host0)
            st = {"params": p0, "m": zeros(p0), "v": zeros(p0), "t": 0}
            del p0
            losses = []
            for k in range(steps):
                st, loss = rd.play(st, toks, loads, returned[k * rpb], p_ret,
                                   lr)
                losses.append(loss)
                if k == 0:
                    fm1 = fingerprint(st["m"])
            fp3 = fingerprint(st["params"])
            p3 = st["params"]
            del st
            gc.collect()
            d3 = _norms(p3, on_device(host0))
            del p3
            st = {k: on_device(end["state"][k]) for k in ("params", "m", "v")}
            st["t"] = end["state"]["t"]
            st, tl = rd.play(st, toks, loads, returned[end["rounds"]], p_ret,
                             lr)
            fpt = fingerprint(st["params"])
            pt = st["params"]
            del st
            gc.collect()
            dt = _norms(pt, on_device(end["state"]["params"]))
            del pt
            gc.collect()
        return {"losses": losses, "m1": fm1, "p3": fp3, "d3": d3,
                "tail_loss": tl, "pt": fpt, "dt": dt}

    def numbers(side: dict, ref: dict, setup: dict, ret) -> dict:
        nums = {"t_star": abs(setup["t_star"] - t_star) / (1.0 + t_star),
                "loads_off": int(np.sum(np.abs(
                    np.asarray(setup["loads"], np.int64)
                    - np.floor(real).astype(np.int64)) > 1)),
                "parity_x": float(setup["parity_rows"]),
                "parity_y": float(setup["parity_rows"])}
        for k in range(steps):
            nums[f"loss_{k + 1}"] = abs(side["losses"][k]
                                        - ref["losses"][k]) / ref["losses"][k]
        nums["grad_1"] = _gap(side["m1"], ref["m1"], True)
        nums["change_3"] = _gap(side["p3"], ref["p3"], False, ref["d3"])
        nums["tail_loss"] = abs(side["tail_loss"] - ref["tail_loss"]) \
            / ref["tail_loss"]
        nums["tail_change"] = _gap(side["pt"], ref["pt"], False, ref["dt"])
        nums["returns_off"] = float(np.mean(np.any(ret != returned, axis=1))) \
            if ret.shape == returned.shape else math.inf
        return nums

    prog = {"losses": [w["loss"] for w in ran["warm"]],
            "m1": ran["warm"][0]["m"], "p3": ran["warm"][-1]["params"],
            "tail_loss": float(tail_h["losses"][end["rounds"]]),
            "pt": tail_h["tail1"]}
    ref = replay("reference")
    setup = {k: tail_h[k] for k in ("t_star", "loads", "parity_rows")}
    correct, checks = compare.judge(
        numbers(prog, ref, setup, tail_h["returned"]), limits)
    others = {}
    for name in variants:
        if name != "reference":
            others[name] = numbers(replay(name), ref,
                                   {"t_star": t_star, "loads": loads,
                                    "parity_rows": 0},
                                   returned)
    win = slice(steps * rpb, (steps + ran["calls"]) * rpb)
    reached = returned[win]
    counts = shape_counts(cfg)
    return correct, checks, others, {
        "tokens_needed": int(np.sum(reached @ loads)),
        "flops_needed": flops(cfg, loads, reached),
        "expert_bytes": counts["expert_bytes"],
        "moe_layers": counts["moe_layers"],
        "d_model": cfg["hidden_size"],
        "d_expert": cfg["moe_intermediate_size"]}
