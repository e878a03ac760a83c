"""Assert every assigned architecture config matches the assignment table
exactly (layers / d_model / heads / kv / d_ff / vocab / family features)."""
import pytest

from repro.configs import ARCH_IDS, get_config

EXPECT = {
    "yi-6b": dict(arch_type="dense", n_layers=32, d_model=4096, n_heads=32,
                  n_kv_heads=4, d_ff=11008, vocab=64000),
    "command-r-plus-104b": dict(arch_type="dense", n_layers=64,
                                d_model=12288, n_heads=96, n_kv_heads=8,
                                d_ff=33792, vocab=256000),
    "internvl2-1b": dict(arch_type="vlm", n_layers=24, d_model=896,
                         n_heads=14, n_kv_heads=2, d_ff=4864, vocab=151655),
    "mixtral-8x7b": dict(arch_type="moe", n_layers=32, d_model=4096,
                         n_heads=32, n_kv_heads=8, d_ff=14336, vocab=32000),
    "rwkv6-1.6b": dict(arch_type="ssm", n_layers=24, d_model=2048,
                       d_ff=7168, vocab=65536),
    "qwen3-4b": dict(arch_type="dense", n_layers=36, d_model=2560,
                     n_heads=32, n_kv_heads=8, d_ff=9728, vocab=151936),
    "jamba-1.5-large-398b": dict(arch_type="hybrid", n_layers=72,
                                 d_model=8192, n_heads=64, n_kv_heads=8,
                                 d_ff=24576, vocab=65536),
    "deepseek-v2-lite-16b": dict(arch_type="moe", n_layers=27, d_model=2048,
                                 n_heads=16, n_kv_heads=16, d_ff=10944,
                                 vocab=102400),
    "whisper-base": dict(arch_type="audio", n_layers=6, d_model=512,
                         n_heads=8, n_kv_heads=8, d_ff=2048, vocab=51865),
    "qwen3-32b": dict(arch_type="dense", n_layers=64, d_model=5120,
                      n_heads=64, n_kv_heads=8, d_ff=25600, vocab=151936),
}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_config_matches_assignment(arch):
    cfg = get_config(arch)
    for field, value in EXPECT[arch].items():
        assert getattr(cfg, field) == value, (arch, field)
    assert cfg.source, arch      # every config cites its source


def test_family_features():
    mixtral = get_config("mixtral-8x7b")
    assert mixtral.moe.num_experts == 8 and mixtral.moe.top_k == 2
    assert mixtral.swa_window == 4096
    ds = get_config("deepseek-v2-lite-16b")
    assert ds.mla.kv_lora_rank == 512
    assert ds.moe.num_experts == 64 and ds.moe.top_k == 6
    assert ds.moe.num_shared_experts == 2
    # the published config.json's keys: dense first layer 10944 wide,
    # experts 1408 wide, unrenormalised top-6 gates scaled by 1, dropless
    # routing with the per-sequence balance loss, YaRN RoPE, eps 1e-6
    assert ds.d_ff == 10944 and ds.moe.d_ff_expert == 1408
    assert ds.moe.norm_topk_prob is False
    assert ds.moe.routed_scaling_factor == 1.0
    assert ds.moe.dropless       # with the per-sequence balance loss
    assert ds.moe.held == (0, 64)
    assert ds.norm_eps == 1e-6 and ds.rope_theta == 10000.0
    assert not ds.tie_embeddings
    y = ds.yarn
    assert (y.factor, y.beta_fast, y.beta_slow) == (40.0, 32.0, 1.0)
    assert y.original_max_position_embeddings == 4096
    assert y.mscale == y.mscale_all_dim == 0.707
    assert (ds.mla.qk_nope_head_dim, ds.mla.qk_rope_head_dim,
            ds.mla.v_head_dim) == (128, 64, 128)
    jamba = get_config("jamba-1.5-large-398b")
    assert jamba.ssm.attn_every_n == 8          # 1:7 attn:mamba
    assert jamba.moe.num_experts == 16 and jamba.moe.top_k == 2
    rwkv = get_config("rwkv6-1.6b")
    assert rwkv.attention_free and rwkv.rwkv.head_size == 64
    for a in ("qwen3-4b", "qwen3-32b"):
        assert get_config(a).qk_norm
    w = get_config("whisper-base")
    assert w.is_encdec and w.n_encoder_layers == 6 and w.encoder_seq == 1500
    ivl = get_config("internvl2-1b")
    assert ivl.n_prefix_patches == 256 and ivl.tie_embeddings
