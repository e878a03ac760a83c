"""Pallas TPU kernels for CodedFedL's compute hot-spots.

  rff_embed           -- fused cos(X @ Omega + delta) RFF map (paper eq. 18)
  linreg_grad         -- fused X^T (X theta - Y) gradient (eq. 7/10/28)
  linreg_grad_masked  -- batched row-masked gradient over the dense padded
                         (n, l_max, q) client tensor (the batched engine's
                         kernel_backend="pallas" hot path)
  parity_encode       -- fused G diag(w) X parity encoding (paper eq. 19)
  gqa_decode          -- flash-decode GQA attention (serving hot-spot)

Each kernel has a pure-jnp oracle in ref.py; ops.py holds jit'd wrappers
with padding + fallback (plus the vmap-compatible batched entry points
linreg_grad_batched / rff_embed_batched).  backend.py decides where they
run: Mosaic-compiled on a TPU, the Pallas interpreter elsewhere.  Kernels
target TPU v5e BlockSpec/VMEM tiling, are validated on CPU in interpret
mode (tests/test_kernels.py, marked `kernels`) and compiled for a
described v5e chip (tests/test_tpu_compile.py).
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
