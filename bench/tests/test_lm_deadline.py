"""The language-model kind (``bench/kinds/lm_deadline.py``) run through
the harness at a size the CPU runs: the program agrees with the plain
reference within the cell's limits, and the control and every planted
fault break at least one of them."""
import math

import pytest

import harness
import lm_reference

CELL = "dsv2lite_deadline_4k"
SEED = 2 ** 31 + 4243
#: DeepSeek-V2-Lite's pattern (one dense, one MoE layer) with its widths
#: cut; 8 routed experts of which 2 are held, 3 per token
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_hidden_layers": 2, "router_experts": 8, "n_routed_experts": 2,
        "experts_held_first": 2, "num_experts_per_tok": 3,
        "vocab_size": 128, "clients": 4, "tokens_per_client": 64,
        "micro_batch": 2, "train": {"learning_rate": 1e-3}}
OTHERS = tuple(k for k in lm_reference.VARIANTS if k != "reference")


@pytest.fixture(scope="module")
def result():
    return harness.run(CELL, SEED, 0, False, require_accelerator=False,
                       overrides=TINY, variants=OTHERS, log=lambda s: None)


def test_tiny_cell_is_correct(result):
    assert result["correct"], harness.check_lines(result)
    assert result["attempted"] == 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"rounds_per_s", "setup_s"}


@pytest.mark.parametrize("variant", OTHERS)
def test_variant_breaks_a_limit(result, variant):
    limits = harness.load_cell(CELL)["limits"]
    nums = result["variants"][variant]
    broken = [k for k, lim in limits.items()
              if not (math.isfinite(nums[k]) and nums[k] <= lim)]
    assert broken, (variant, nums)


def test_model_readers_on_a_made_up_window():
    """The five readers of the model cell, on a window whose counters,
    spans and device ops are given: each reads its formula, and None
    where its source is absent (as in a run of a program without it)."""
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"trace": {"window_s": 50.0, "busy_s": 49.0, "op_s": {
               "jit_block/ragged-dot-none.3": 2.0,
               "jit_block/ragged-dot-none.4": 1.0,
               "jit_block/ragged-dot-metadata": 0.5,
               "jit_block/while": 40.0}},
           "peak": peak, "rounds": 50,
           "window_counters": {"round/tokens": {"total": 50 * 32768},
                               "moe/routed": {"total": 1.0e6},
                               "moe/expert_rows": {"total": 1.5e6}},
           "spans": {"model/init": {"total_s": 1.25}},
           "tokens_needed": 50 * 30000, "flops_needed": 2.0e15,
           "expert_bytes": 8 * 3 * 2048 * 1408 * 4, "moe_layers": 4,
           "d_model": 2048, "d_expert": 1408}
    read = {n: harness.metric_reader(n) for n in (
        "round_mfu.moe", "expert_roofline.moe", "tokens_per_needed.moe",
        "expert_rows_per_routed.moe", "model_init_s")}
    assert read["round_mfu.moe"](ctx) == pytest.approx(
        100 * 2.0e15 / (50.0 * 197e12))
    flops = 18.0 * 2048 * 1408 * 1.0e6
    assert read["expert_roofline.moe"](ctx) == pytest.approx(
        100 * (flops / 197e12) / 3.0)
    assert read["tokens_per_needed.moe"](ctx) == pytest.approx(32768 / 30000)
    assert read["expert_rows_per_routed.moe"](ctx) == pytest.approx(1.5)
    assert read["model_init_s"](ctx) == 1.25
    bare = dict(ctx, trace={"window_s": 50.0, "busy_s": 49.0, "op_s": {}},
                window_counters={}, spans={})
    for name in ("expert_roofline.moe", "tokens_per_needed.moe",
                 "expert_rows_per_routed.moe", "model_init_s"):
        assert read[name](bare) is None, name
