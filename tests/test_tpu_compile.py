"""The round-path Pallas kernels compile for a described TPU v5e chip.

Interpret mode (the rest of the kernel suite) cannot see Mosaic's tiling
rules or its scoped-VMEM limit; these tests hand each kernel the shapes of
the paper's §V-A round (n=30 clients plus the coded parity row, l=400,
d=784, q=2000, c=10, u=δ·m=1200 parity rows per client;
``configs/mnist_rff.py``) and compile it with ``interpret=False`` for one
chip of a described ``v5e:2x2`` topology.  Nothing runs: no chip is needed,
only the TPU compiler.  The topology is described inside a fixture, never
at import, so only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import linreg_grad as linreg_mod
from repro.kernels import ops
from repro.kernels import rff_linreg_grad as fused_mod

N, L, D, Q, C, U = 30, 400, 784, 2000, 10, 1200
ROWS = N + 1          # the fused coded round's parity pseudo-client row


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a
    compile for an absent device is written but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


KERNELS = {
    "linreg_grad_masked": (
        lambda x, th, y, m: ops.linreg_grad_masked(
            x, th, y, m, use_pallas=True, interpret=False),
        [(ROWS, L, Q), (Q, C), (ROWS, L, C), (ROWS, L)]),
    "rff_linreg_grad_masked": (
        lambda x, om, de, th, y, m, pp: ops.rff_linreg_grad_masked(
            x, om, de, th, y, m, parity_phi=pp, use_pallas=True,
            interpret=False),
        [(N, L, D), (D, Q), (Q,), (Q, C), (ROWS, L, C), (ROWS, L), (L, Q)]),
    # bf16 inputs: the embed matmul keeps Mosaic's default precision
    "rff_linreg_grad_masked_bf16": (
        lambda x, om, de, th, y, m, pp: ops.rff_linreg_grad_masked(
            *(a.astype(jnp.bfloat16) for a in (x, om, de, th, y)), m,
            parity_phi=pp.astype(jnp.bfloat16), use_pallas=True,
            interpret=False),
        [(N, L, D), (D, Q), (Q,), (Q, C), (ROWS, L, C), (ROWS, L), (L, Q)]),
    "rff_embed": (
        lambda x, om, de: ops.rff_embed(x, om, de, use_pallas=True,
                                        interpret=False),
        [(N * L, D), (D, Q), (Q,)]),
    "parity_encode_batched": (
        lambda g, w, x: ops.parity_encode_batched(
            g, w, x, use_pallas=True, interpret=False),
        [(N, U, L), (N, L), (N, L, Q)]),
    # the hier tier's per-shard coded gradient over the (u, q) parity set
    "linreg_grad": (
        lambda x, th, y: ops.linreg_grad(x, th, y, use_pallas=True,
                                         interpret=False),
        [(U, Q), (Q, C), (U, C)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


# VMEM guards: the kernels keep Mosaic's default 16 MiB scoped VMEM, and
# each guard counts double-buffered streamed blocks.  At the paper's
# c=10 (lane-padded to 128) the guard must pass the widest shape the
# compiler accepts and refuse the next one, which the compiler refuses too.
def _masked(q):
    return (lambda x, th, y, m: linreg_mod.linreg_grad_masked(
                x, th, y, m, interpret=False),
            [(4, 512, q), (q, 128), (4, 512, 128), (4, 512)])


def _fused(d):
    return (lambda x, om, de, th, y, m, pp: fused_mod.rff_linreg_grad_masked(
                x, om, de, th, y, m, pp, n_real=ROWS - 1, interpret=False),
            [(ROWS, 512, d), (d, 2048), (2048,), (2048, 128),
             (ROWS, 512, 128), (ROWS, 512), (1, 512, 2048)])


@pytest.mark.parametrize("make,fits,refused", [
    (_masked, 10240, 11264),
    (_fused, 1024, 1152),
], ids=["linreg_grad_masked", "rff_linreg_grad_masked"])
def test_vmem_guard_matches_compiler(one_chip, monkeypatch, make, fits,
                                     refused):
    fn, shapes = make(fits)
    _compile(fn, one_chip, *shapes)
    fn, shapes = make(refused)
    with pytest.raises(ValueError, match="VMEM"):
        _compile(fn, one_chip, *shapes)
    monkeypatch.setattr(linreg_mod, "_check_c_fits_vmem",
                        lambda *a: None)
    monkeypatch.setattr(fused_mod, "_check_fused_vmem", lambda *a: None)
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile(fn, one_chip, *shapes)


def test_held_expert_layer_compiles_for_v5e(one_chip):
    """DeepSeek-V2-Lite's held-expert MoE layer at its published widths
    (D 2,048, experts 1,408 wide, 64-wide router, top-6, 8 experts held,
    2 shared), forward and backward over 2 x 512 tokens: the grouped
    matmuls lower to the TPU's ragged-dot kernel."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import moe

    full = get_config("deepseek-v2-lite-16b")
    cfg = dataclasses.replace(full, dtype="float32", moe=dataclasses.replace(
        full.moe, experts_held=(8, 8)))
    p = jax.eval_shape(lambda k: moe.init_moe(k, cfg, jnp.float32),
                       jax.random.PRNGKey(0))
    p = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), p)
    x = jax.ShapeDtypeStruct((2, 512, cfg.d_model), jnp.float32,
                             sharding=one_chip)

    def loss(p_, x_):
        out, st = moe.moe_ffn_held(p_, x_, cfg)
        return jnp.sum(out * out) + jnp.sum(st["balance"])

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).compile() \
        .as_text()
    assert "ragged-dot" in text


def test_causal_attention_triangle_compiles_for_v5e(one_chip):
    """MLA's training attention at the model cell's shapes (2 sequences
    of 4,096, 16 heads, 192-wide keys, 128-wide values, kv chunks of
    512), forward and backward with the per-chunk recompute: the loop
    over the causal triangle's 36 blocks needs no more scratch than the
    full square's 8 whole-sequence chunk steps."""
    from repro.models import attention

    B, S, H = 2, 4096, 16
    pos = jnp.arange(S, dtype=jnp.int32)

    def square(q, k, v):
        """Every kv chunk against all 4,096 queries, one carry kept per
        chunk for the backward."""
        qr, kc, vc, pc = attention._prepare(q, k, v, pos, 512, None)

        def step(carry, xs):
            return attention._online_step(qr, pos, carry, *xs, 0,
                                          True), None
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(step), attention._carry0(B, S, H, 1, 128),
            (kc, vc, pc))
        return (acc / l[..., None]).reshape(B, S, H, 128)

    forms = {"square": square,
             "triangle": lambda q, k, v: attention._flash_causal(
                 q, k, v, pos, 0, remat_chunks=True)}
    temp = {}
    for name, f in forms.items():
        def fwd_bwd(q, k, v, g, f=f):
            _, pull = jax.vjp(f, q, k, v)
            return pull(g)
        comp = _compile(fwd_bwd, one_chip, (B, S, H, 192), (B, S, H, 192),
                        (B, S, H, 128), (B, S, H, 128))
        temp[name] = comp.memory_analysis().temp_size_in_bytes
    assert temp["triangle"] <= temp["square"], temp
