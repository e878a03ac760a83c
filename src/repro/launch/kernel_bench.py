"""Per-kernel microbenchmark harness (the ``kernels`` artifact section).

Times every kernel the federated round path is built from — `rff_embed`,
`linreg_grad_masked`, `parity_encode_batched`, and the fused
embed->gradient `rff_linreg_grad_masked` against its two-pass equivalent —
and emits the required ``kernels`` section of ``BENCH_fed_training.json``
(schema v6).  The headline number is ``fused_vs_two_pass_ratio``: the
fused kernel's time over the two-pass (embed, then gradient) time at the
same shapes, i.e. the measured payoff of never materializing the
``(n, l, q)`` embedded tensor per round.

What is timed is the jit'd path of the selected ``kernel_backend``:
``"xla"`` (the CI default) times the plain-jnp reference compositions —
Pallas interpret-mode wall time on CPU measures the interpreter, not the
TPU target, so CI gates regressions on the XLA path.  ``interpret``
defaults to the platform (`repro.kernels.backend.resolve_interpret`):
``kernel_backend="pallas"`` times the compiled kernels on a TPU.

CI gate: `compare_kernels(fresh, committed, threshold)` flags any kernel
whose us_per_call regressed past ``threshold`` x the committed artifact's
(host-noise tolerant: only slowdowns fail, never speedups), and
`validate_kernels` is wired into `repro.launch.bench.validate_artifact`
so an artifact without the section (or with non-finite timings) is
malformed.  CLI front-end: ``benchmarks/bench_kernels_micro.py``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.backend import resolve_interpret

#: kernel names every ``kernels`` section must time
KERNEL_NAMES = ("rff_embed", "linreg_grad_masked", "parity_encode_batched",
                "rff_linreg_grad_fused", "two_pass_embed_grad")

#: default CI regression threshold: fresh us_per_call may not exceed
#: threshold x committed us_per_call (generous — CI hosts are noisy; the
#: gate exists to catch order-of-magnitude kernel/wrappers regressions,
#: not scheduler jitter)
DEFAULT_THRESHOLD = 3.0

# (n_clients, l, d, q, c, u) per scale; "smoke" is CI-sized (well under a
# second per kernel on a shared runner), "full" the paper's §V-A operating
# point (784-dim MNIST, q = 2000)
SCALES = {
    "smoke": dict(n_clients=4, l=64, d=16, q=128, c=4, u=32),
    "default": dict(n_clients=12, l=128, d=64, q=512, c=8, u=128),
    "full": dict(n_clients=30, l=400, d=784, q=2000, c=10, u=1200),
}


def _time(fn, *args, iters: int, warmup: int = 2) -> float:
    """Mean us/call of ``fn(*args)`` after ``warmup`` compile+cache calls."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def run_kernel_bench(n_clients: int = 12, l: int = 128, d: int = 64,
                     q: int = 512, c: int = 8, u: int = 128,
                     iters: int = 10, seed: int = 0,
                     kernel_backend: str = "xla",
                     interpret: bool | None = None) -> dict:
    """Time the round path's kernels at one shape; return the section dict.

    Shapes mirror the runtime's layouts: embedding flattens the client
    axis into (n*l, d) rows; the gradient/parity kernels run over the
    dense (n, l, ·) client tensor.  The fused and two-pass timings share
    identical inputs, so their ratio isolates the fusion itself.
    """
    if kernel_backend not in ("xla", "pallas"):
        raise ValueError(f"unknown kernel_backend {kernel_backend!r}")
    use_pallas = kernel_backend == "pallas"
    interpret = resolve_interpret(interpret)
    rng = np.random.default_rng(seed)
    x_raw = jnp.asarray(rng.normal(size=(n_clients, l, d)), jnp.float32)
    omega = jnp.asarray(rng.normal(size=(d, q)) / 5.0, jnp.float32)
    delta = jnp.asarray(rng.uniform(0, 2 * np.pi, size=(q,)), jnp.float32)
    theta = jnp.asarray(rng.normal(size=(q, c)) * 0.1, jnp.float32)
    y = jnp.asarray(rng.normal(size=(n_clients, l, c)), jnp.float32)
    mask = jnp.asarray(rng.uniform(size=(n_clients, l)) < 0.8,
                       jnp.float32)
    g = jnp.asarray(rng.normal(size=(n_clients, u, l)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 1.0, size=(n_clients, l)), jnp.float32)

    kw = dict(use_pallas=use_pallas, interpret=interpret)
    embed = jax.jit(lambda x2d: ops.rff_embed(x2d, omega, delta, **kw))
    phi = embed(x_raw.reshape(n_clients * l, d)).reshape(n_clients, l, q)
    grad = jax.jit(lambda p, th, yy, mm: ops.linreg_grad_masked(
        p, th, yy, mm, **kw))
    parity = jax.jit(lambda gg, ww, pp: ops.parity_encode_batched(
        gg, ww, pp, **kw))
    fused = jax.jit(lambda x, th: ops.rff_linreg_grad_masked(
        x, omega, delta, th, y, mask, **kw))
    two_pass = jax.jit(lambda x, th: ops.linreg_grad_masked(
        ops.rff_embed_batched(x, omega, delta, **kw), th, y, mask, **kw))

    entries = {
        "rff_embed": _time(embed, x_raw.reshape(n_clients * l, d),
                           iters=iters),
        "linreg_grad_masked": _time(grad, phi, theta, y, mask, iters=iters),
        "parity_encode_batched": _time(parity, g, w, phi, iters=iters),
        "rff_linreg_grad_fused": _time(fused, x_raw, theta, iters=iters),
        "two_pass_embed_grad": _time(two_pass, x_raw, theta, iters=iters),
    }
    return {
        "backend": kernel_backend,
        "interpret": bool(interpret),
        "iters": int(iters),
        "shapes": {"n_clients": n_clients, "l": l, "d": d, "q": q, "c": c,
                   "u": u},
        "entries": {k: {"us_per_call": float(v)}
                    for k, v in entries.items()},
        "fused_vs_two_pass_ratio": float(
            entries["rff_linreg_grad_fused"]
            / entries["two_pass_embed_grad"]),
    }


def validate_kernels(section) -> list[str]:
    """Problems with a ``kernels`` artifact section (empty == valid)."""
    errs = []
    if not isinstance(section, dict):
        return [f"kernels: must be an object, got {type(section).__name__}"]
    if section.get("backend") not in ("xla", "pallas"):
        errs.append(f"kernels/backend: bad value {section.get('backend')!r}")
    entries = section.get("entries")
    if not isinstance(entries, dict):
        return errs + ["kernels/entries: missing"]
    for name in KERNEL_NAMES:
        entry = entries.get(name)
        us = entry.get("us_per_call") if isinstance(entry, dict) else None
        if not _is_pos(us):
            errs.append(f"kernels/entries/{name}/us_per_call: "
                        f"bad value {us!r}")
    ratio = section.get("fused_vs_two_pass_ratio")
    if not _is_pos(ratio):
        errs.append(f"kernels/fused_vs_two_pass_ratio: bad value {ratio!r}")
    shapes = section.get("shapes")
    if not isinstance(shapes, dict) or not all(
            isinstance(shapes.get(k), int) and shapes.get(k) > 0
            for k in ("n_clients", "l", "d", "q", "c", "u")):
        errs.append(f"kernels/shapes: bad value {shapes!r}")
    return errs


def compare_kernels(fresh: dict, committed: dict,
                    threshold: float = DEFAULT_THRESHOLD) -> list[str]:
    """Regression gate: fresh timings vs a committed ``kernels`` section.

    Returns a problem string per kernel whose fresh us_per_call exceeds
    ``threshold`` x the committed one (one-sided: speedups always pass),
    plus one if the fused-vs-two-pass ratio regressed past the same
    factor.  Both sections must validate first; structural problems are
    reported instead of timings nonsense.
    """
    errs = [f"fresh artifact: {e}" for e in validate_kernels(fresh)]
    errs += [f"committed artifact: {e}" for e in validate_kernels(committed)]
    if errs:
        return errs
    if threshold <= 1.0:
        return [f"threshold must exceed 1.0, got {threshold}"]
    for name in KERNEL_NAMES:
        new = fresh["entries"][name]["us_per_call"]
        old = committed["entries"][name]["us_per_call"]
        if new > threshold * old:
            errs.append(
                f"{name}: {new:.1f} us/call vs committed {old:.1f} "
                f"(> {threshold:.2f}x regression threshold)")
    new_r = fresh["fused_vs_two_pass_ratio"]
    old_r = committed["fused_vs_two_pass_ratio"]
    if new_r > threshold * old_r:
        errs.append(
            f"fused_vs_two_pass_ratio: {new_r:.3f} vs committed "
            f"{old_r:.3f} (> {threshold:.2f}x regression threshold)")
    return errs


def _is_pos(val) -> bool:
    return isinstance(val, (int, float)) and np.isfinite(val) and val > 0
