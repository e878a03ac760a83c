"""The federated language-model round (`repro.core.lm_round`) against the
plain reference (``bench/lm_reference.py``), at a small size of
DeepSeek-V2-Lite's pattern: one dense layer and one MoE layer, widths
cut, 8 routed experts of which 2 are held, seeded weights."""
import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))
import lm_reference  # noqa: E402
from repro.api import build_experiment
from repro.config import (ExperimentSpec, FLConfig, LMSpec, MoEConfig,
                          TrainConfig)
from repro.configs import get_config
from repro.core import lm_round
from repro.models import mla, moe, transformer
from repro.models.common import swiglu, yarn_freqs
from repro.obs import spans as obs_spans

N_CLIENTS, S = 4, 32
FIRST, HELD = 2, 2
TRAIN = {"learning_rate": 1e-3, "weight_decay": 0.1, "adam_b1": 0.9,
         "adam_b2": 0.95, "adam_eps": 1e-8, "clip_norm": 1.0}


def small_model(held=(FIRST, HELD)):
    full = get_config("deepseek-v2-lite-16b")
    return dataclasses.replace(
        full, n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=24,
        d_ff=96, vocab=128, dtype="float32",
        mla=dataclasses.replace(full.mla, kv_lora_rank=32,
                                qk_nope_head_dim=16, qk_rope_head_dim=8,
                                v_head_dim=16),
        moe=dataclasses.replace(full.moe, num_experts=8, top_k=3,
                                d_ff_expert=32, experts_held=held))


def hp_of(cfg) -> dict:
    """The reference's configuration keys (DeepSeek-V2's names)."""
    y = cfg.yarn
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "qk_nope_head_dim": cfg.mla.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.mla.qk_rope_head_dim,
        "v_head_dim": cfg.mla.v_head_dim,
        "kv_lora_rank": cfg.mla.kv_lora_rank, "rope_theta": cfg.rope_theta,
        "rope_scaling": {
            "factor": y.factor, "beta_fast": y.beta_fast,
            "beta_slow": y.beta_slow, "mscale": y.mscale,
            "mscale_all_dim": y.mscale_all_dim,
            "original_max_position_embeddings":
                y.original_max_position_embeddings},
        "rms_norm_eps": cfg.norm_eps,
        "router_experts": cfg.moe.num_experts,
        "experts_held_first": cfg.moe.held[0],
        "n_routed_experts": cfg.moe.held[1],
        "n_shared_experts": cfg.moe.num_shared_experts,
        "num_experts_per_tok": cfg.moe.top_k,
        "routed_scaling_factor": cfg.moe.routed_scaling_factor,
        "aux_loss_alpha": cfg.moe.aux_loss_weight,
        "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": cfg.moe.d_ff_expert,
        "vocab_size": cfg.vocab, "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": 1}


def ref_layout(params):
    """The program's parameters in the reference's layout."""
    layers = []
    for si in (0, 1):
        stage = params[f"stage{si}"]["l0"]
        n = jax.tree_util.tree_leaves(stage)[0].shape[0]
        layers += [jax.tree_util.tree_map(lambda a: a[i], stage)
                   for i in range(n)]
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": params["lm_head"], "layers": layers}


def program_layout(ref):
    """Reference-layout weights in the program's layout."""
    def stack(layers):
        return {"l0": jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                             *layers)}
    return {"embed": ref["embed"], "final_norm": ref["final_norm"],
            "lm_head": ref["lm_head"], "stage0": stack(ref["layers"][:1]),
            "stage1": stack(ref["layers"][1:])}


def experiment(model=None, seed=11):
    spec = ExperimentSpec(
        fl=FLConfig(n_clients=N_CLIENTS, delta=0.1, seed=seed),
        train=TrainConfig(optimizer="adamw",
                          learning_rate=TRAIN["learning_rate"],
                          weight_decay=TRAIN["weight_decay"],
                          lr_decay_epochs=()),
        scheme="coded", checkpoint_every=1,
        model=LMSpec(model=model or small_model(), init_seed=5,
                     micro_batch=2))
    toks = jax.random.randint(jax.random.PRNGKey(seed),
                              (N_CLIENTS, S + 1), 0, 128, dtype=jnp.int32)
    return build_experiment(spec, toks), toks


def returned(ret_bits, n):
    """(rounds, n) mask of clients back by t* from the round's bits."""
    return (np.asarray(ret_bits)[:, None] >> np.arange(n)) & 1 > 0


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _worst(tree_p, tree_r):
    gaps = jax.tree_util.tree_map(_rel, tree_p, tree_r)
    return max(jax.tree_util.tree_leaves(gaps))


@pytest.fixture(scope="module")
def played():
    """Three rounds of the program, and the reference's replay of them
    from the same initial weights (the reference's own, handed to the
    program) and the program's returned masks."""
    exp, toks = experiment()
    p0 = jax.tree_util.tree_map(np.asarray, lm_reference.init_weights(
        hp_of(exp.lm.model), jax.random.PRNGKey(5)))
    state = exp.init_state(10, params=program_layout(
        jax.tree_util.tree_map(jnp.asarray, p0)))
    np.testing.assert_array_equal(
        ref_layout(state.theta["params"])["layers"][1]["moe"]["w2"],
        p0["layers"][1]["moe"]["w2"])
    assert state.lm_log["loss"].shape == (0,)
    snaps = []
    for _ in range(3):
        state = exp.run_block(state)
        th = state.theta       # donated to the next block: copy it out
        snaps.append(jax.tree_util.tree_map(np.asarray, {
            "params": ref_layout(th["params"]),
            "m": ref_layout(th["opt"]["m"])}))
    rd = lm_reference.Round(hp_of(exp.lm.model), FIRST, TRAIN)
    ret = returned(state.lm_log["ret_bits"], exp.n)
    st = {"params": jax.tree_util.tree_map(jnp.asarray, p0),
          "m": jax.tree_util.tree_map(jnp.zeros_like, p0),
          "v": jax.tree_util.tree_map(jnp.zeros_like, p0), "t": 0}
    ref = []
    with jax.default_matmul_precision("highest"):
        for k in range(3):
            st, loss = rd.play(st, toks, np.asarray(exp.loads), ret[k],
                               np.asarray(exp.p_return),
                               TRAIN["learning_rate"])
            # the next round consumes the state's arrays: copy them out
            ref.append({"loss": loss, **jax.tree_util.tree_map(
                np.asarray, {"params": st["params"], "m": st["m"]})})
    return exp, state, snaps, ref, p0


def test_one_round_loss_and_gradient_match_reference(played):
    exp, state, snaps, ref, _ = played
    assert abs(state.lm_log["loss"][0] - ref[0]["loss"]) \
        <= 1e-6 * ref[0]["loss"]
    # AdamW's first moment after one round is (1 - b1) g, g the clipped
    # round gradient: every tensor of it within f32 rounding
    assert _worst(snaps[0]["m"], ref[0]["m"]) < 1e-4


def test_three_adamw_rounds_match_reference(played):
    exp, state, snaps, ref, p0 = played
    for k in range(3):
        assert abs(state.lm_log["loss"][k] - ref[k]["loss"]) \
            <= 1e-5 * ref[k]["loss"]
    change_p = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - b,
                                      snaps[2]["params"], p0)
    change_r = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - b,
                                      ref[2]["params"], p0)
    assert _worst(change_p, change_r) < 1e-3


def test_round_reports_routed_rows_and_returns(played):
    exp, state, _, _, _ = played
    log = state.lm_log
    assert np.all(log["routed"] > 0)
    assert np.all(log["rows"] % moe.GMM_ROW_TILE == 0)
    assert np.all(log["rows"] >= log["routed"])
    ret = returned(log["ret_bits"], exp.n)
    np.testing.assert_array_equal(ret.sum(axis=1), state.n_ret)


def test_round_counts_attention_scores_of_its_shapes():
    """A model block counts the query-key scores its attention forward
    computes and the causal triangle's, per client sequence and layer
    (heads not counted): S = 32 is one block of 32^2 against 32 * 33 / 2,
    a ratio of 2 * 32 / 33."""
    exp, _ = experiment()
    state = exp.init_state(10)
    with obs_spans.collecting() as spans:
        exp.run_block(state)
        got = spans.counters()
    layers = exp.lm.model.n_layers
    assert got["attn/scores"] == {"events": 1,
                                  "total": N_CLIENTS * layers * S * S}
    assert got["attn/causal_scores"] == {
        "events": 1, "total": N_CLIENTS * layers * S * (S + 1) // 2}
    assert (got["attn/scores"]["total"] / got["attn/causal_scores"]["total"]
            == pytest.approx(2 * S / (S + 1)))


def test_yarn_frequencies_and_scale_match_closed_form():
    cfg = get_config("deepseek-v2-lite-16b")
    y = cfg.yarn
    # correction dims for 32 and 1 rotations over 4,096 positions
    d = [64 * math.log(4096 / (r * 2 * math.pi)) / (2 * math.log(1e4))
         for r in (32.0, 1.0)]
    low, high = math.floor(d[0]), math.ceil(d[1])
    assert (low, high) == (10, 23)
    i = np.arange(32, dtype=np.float64)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    orig = 1e4 ** (-2 * i / 64)
    want = orig / 40.0 * ramp + orig * (1 - ramp)
    np.testing.assert_allclose(yarn_freqs(64, 1e4, y), want, rtol=1e-6)
    inv, amp, scale = lm_reference.yarn(hp_of(cfg))
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert amp == 1.0
    ms = 0.1 * 0.707 * math.log(40) + 1.0
    assert abs(ms * ms - 1.5896) < 1e-4
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * ms * ms,
                                                   rel=1e-12)
    assert scale == pytest.approx(mla.softmax_scale(cfg), rel=1e-12)


def test_expert_shares_sum_to_uncut_layer():
    """Each of 4 devices holding 2 of the 8 experts computes its part; the
    parts, with the shared experts counted once, give the uncut layer,
    which matches the reference's layer over all experts."""
    cfg = small_model(held=None)
    p = moe.init_moe(jax.random.PRNGKey(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))
    whole, st = moe.moe_ffn_held(p, x, cfg)
    shared = swiglu(x, p["ws1"], p["ws3"], p["ws2"])
    total = shared
    routed = 0
    for s in range(4):
        cs = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, experts_held=(2 * s, 2)))
        ps = moe.init_moe(jax.random.PRNGKey(3), cs, jnp.float32)
        for k in ("w1", "w3", "w2"):
            np.testing.assert_array_equal(ps[k], p[k][2 * s:2 * s + 2])
        part, sts = moe.moe_ffn_held(ps, x, cs)
        total = total + (part - shared)
        routed += int(sts["routed"])
        np.testing.assert_allclose(sts["balance"], st["balance"], rtol=1e-6)
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-6)
    assert routed == int(st["routed"]) == 2 * 16 * cfg.moe.top_k
    model = lm_reference.Model(hp_of(cfg), 0)
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            out, bal = model.moe(p, x[b], jnp.ones(16))
            np.testing.assert_allclose(out, whole[b], rtol=1e-4, atol=1e-5)
            assert float(bal) == pytest.approx(float(st["balance"][b]),
                                               rel=1e-5)


def test_masked_loss_equals_truncated_sequence():
    cfg = small_model()
    params = transformer.init_params(cfg, jax.random.PRNGKey(7))
    toks = jax.random.randint(jax.random.PRNGKey(8), (1, S + 1), 0, 128)
    ell = 19

    def masked(p):
        return transformer.client_losses(cfg, p, toks,
                                         jnp.array([ell]))[0][0]

    def truncated(p):
        return transformer.client_losses(cfg, p, toks[:, :ell + 1],
                                         jnp.array([ell]))[0][0]

    lm_, gm = jax.value_and_grad(masked)(params)
    lt, gt = jax.value_and_grad(truncated)(params)
    assert float(lm_) == pytest.approx(float(lt), rel=1e-6)
    assert _worst(gm, gt) < 1e-5


def test_late_and_unloaded_clients_contribute_exactly_zero():
    exp, toks = experiment()
    step = jax.jit(lm_round.build_step(exp))
    consts = lm_round.consts(exp)
    consts["loads"] = consts["loads"].at[1].set(0)
    theta = exp.init_state(1).theta
    t_row = jnp.zeros(exp.n, jnp.float32).at[0].set(
        2.0 * float(exp.t_star))        # client 0 misses the deadline
    lr = jnp.float32(1e-3)
    (th_a, _), out_a = step(consts, (theta, jnp.float32(1.0)), (t_row, lr))
    junk = dict(consts, tokens=consts["tokens"].at[:2].set(
        (consts["tokens"][:2] + 17) % 128))
    (th_b, _), out_b = step(junk, (theta, jnp.float32(1.0)), (t_row, lr))
    for a, b in zip(jax.tree_util.tree_leaves(th_a["opt"]["m"]),
                    jax.tree_util.tree_leaves(th_b["opt"]["m"])):
        np.testing.assert_array_equal(a, b)
    assert int(out_a[1]) == exp.n - 2          # n_ret
    assert int(out_a[5]) == sum(1 << j for j in range(2, exp.n))


def test_model_spec_round_trips_and_rejects_what_it_does_not_combine():
    import json
    spec = ExperimentSpec(fl=FLConfig(n_clients=4), scheme="coded",
                          model=LMSpec(model=small_model(), micro_batch=2))
    assert ExperimentSpec.from_dict(json.loads(json.dumps(
        spec.to_dict()))) == spec
    for bad in ({"hier_shards": 2}, {"channel_profile": "markov_loss"},
                {"fault_profile": "flaky_clients"}, {"mesh": 2},
                {"secure_aggregation": True}):
        with pytest.raises(ValueError, match="model spec"):
            ExperimentSpec(fl=FLConfig(n_clients=4), scheme="coded",
                           model=LMSpec(model=small_model()), **bad)
    with pytest.raises(ValueError, match="experts_held"):
        MoEConfig(num_experts=8, experts_held=(6, 4), dropless=True)


@pytest.mark.parametrize("departure", [
    {"d_ff": 64},                                  # the dense layer's width
    {"moe": {"d_ff_expert": 48}},                  # an expert's width
    {"moe": {"experts_held": (FIRST, 3)}},         # the experts held
    {"mla": {"kv_lora_rank": 16}},                 # the latent's rank
])
def test_program_refuses_weights_of_other_widths(departure):
    """Weights the reference makes at the configuration's widths are
    refused by a model whose widths depart from them, and the reference
    refuses that model's own weights."""
    good = small_model()
    bad = dataclasses.replace(good, **{
        k: (dataclasses.replace(getattr(good, k), **v)
            if isinstance(v, dict) else v) for k, v in departure.items()})
    ref = lm_reference.init_weights(hp_of(good), jax.random.PRNGKey(5))
    exp, _ = experiment(bad)
    with pytest.raises(ValueError, match="weights"):
        exp.init_state(1, params=program_layout(ref))
    theirs = ref_layout(transformer.init_params(bad, jax.random.PRNGKey(5)))
    with pytest.raises(ValueError, match="weights"):
        lm_reference.check_shapes(hp_of(good), theirs)
    lm_reference.check_shapes(hp_of(good), ref)
