"""Federated-learning runtime: the engine behind `repro.api.Experiment`.

This is the paper's system layer (§III, §V): a server loop over training
rounds in a simulated wireless MEC network.  Compute/communication delays are
*sampled from the paper's stochastic models* each round; the simulated
wall-clock is the quantity all of Fig. 4/5 and Tables II/III are measured in.

The straggler-mitigation scheme is a pluggable registry object
(``repro.core.schemes``) that owns the deployment setup, its
contributions to the compiled step, and the per-round rule — deadline,
returned mask, denominator (`Scheme.round_rule`) — that `build_step`
calls; the runtime holds no per-scheme branch.  `Experiment` is built
from a frozen `ExperimentSpec` (``repro.api.build_experiment``).  The
kwargs-era `FederatedSimulation` front-end has been removed (a stub
raising a pointed error remains).

Block-structured resumable runs
-------------------------------
Every batched-engine run is threaded through an explicit
`repro.core.run_state.RunState`: ``run(iterations)`` is a loop of
``run_block(state, n_rounds) -> state`` calls over the same cached
compiled scan, where a block is ``spec.checkpoint_every`` rounds (0 = the
whole horizon in one block, reproducing the historical one-shot
trajectories bit-exactly).  The state carries the model iterate, round
cursor, RNG bit-generator state, channel-trace state, estimator
sufficient statistics, adaptive control values, and the round-log
accumulators — so ``save_state``/``restore_state``
(`repro.checkpoint.io`) give kill/resume at any block boundary that is
bit-identical to the uninterrupted blocked run, including the loss curve
and the adaptive schedule.  `repro.launch.service.ExperimentService`
multiplexes many such runs' blocks over one process.

Engines
-------
``ExperimentSpec(engine="batched")`` (the default) runs the whole
training loop as one compiled program:

  * per-client processed subsets are padded to a dense ``(n, l_max, q)``
    tensor with a validity mask (rows with mask 0 contribute exactly zero to
    the linear-regression gradient), so all n client gradients come from a
    single call;
  * the coded scheme appends the global parity set as an (n+1)-th
    *pseudo-client row* of that tensor, with the 1/(u (1-pnr_C)) coded-
    gradient scale folded into its mask entries — client gradients AND the
    coded gradient come from ONE masked-kernel call per round
    (``fused_coded=False`` keeps the historical two-call path as the
    numerical oracle);
  * round delays for the *entire run* are pre-sampled with the vectorized
    ``delay_model.sample_round_times`` API (3 RNG draws total instead of
    ``iterations * n`` Python-level calls);
  * the per-round update runs under ``jax.lax.scan`` inside one ``jax.jit``.

``engine="legacy"`` keeps the original per-client Python loop and serves as
the numerical-equivalence oracle: both engines consume the same pre-sampled
delay matrix, so with equal seeds they produce the same ``theta`` trajectory
to fp32 tolerance (see tests/test_batched_engine.py).

Network dynamics (``ExperimentSpec.channel_profile``, ``repro.net``): the
run's delays are pre-sampled *through* a deterministic per-seed channel
trace (Gilbert–Elliott erasure bursts, shadowing/MCS rate hopping, compute
drift, churn) instead of the stationary model — still one compiled scan,
with a per-round availability row joining the scan inputs.  The static
profile reproduces the stationary engine bit-exactly.  Adaptive schemes
(``Scheme.adaptive``) additionally run the
``repro.net.estimator.AdaptiveController`` control loop on the host ahead
of the scan: online (mu, tau, p) estimation from round telemetry and a
re-plan every ``adapt_every`` rounds, whose per-round controls join the
scan inputs (`Scheme.segment_inputs`) so shapes (and the compiled step)
never change.

Robustness (``ExperimentSpec.fault_profile``, ``repro.faults``): the
compiled step carries two guards.  The non-finite guard
(``spec.nonfinite_guard``, default on) zeroes non-finite client/parity
gradient rows out of the weighted sum and counts them — for coded
schemes the parity gradient compensates the masked mass exactly as it
covers stragglers.  The always-on divergence guard never commits a
non-finite iterate: the round is skipped (model held) and the effective
lr backs off by `LR_BACKOFF` per skip.  Both are IEEE no-ops on clean
rounds, so guarded fault-free runs stay bit-identical to history.
Injected return faults (NaN/inf uploads, stale-update replay, corrupted
parity) ride the scan inputs from a dedicated RNG stream; degradation
counters thread through `RunState` and surface as `FedResult.health`.

``kernel_backend`` selects how the batched engine computes gradients:
``"xla"`` (default) is the plain-jnp vmapped path; ``"pallas"`` routes every
per-round gradient through the fused Pallas kernels
(``kernels.linreg_grad_masked`` over the dense padded client tensor —
interpret mode off-TPU, compiled on TPU).  Both backends produce the same
trajectory to fp32 tolerance.  ``alloc_backend`` picks the deadline/load
optimizer: the scalar NumPy two-step solver or the vectorized
fixed-iteration JAX solver (``"auto"`` chooses by population size).

Client-mesh mode
----------------
``ExperimentSpec(mesh=k)`` (an int device count; a concrete 1-D
``jax.sharding.Mesh`` with a single ``"clients"`` axis goes through
``build_experiment(..., mesh=...)`` instead) partitions the dense client
tensor, the
per-round returned mask, and the per-shard gradient computation over the
mesh with ``shard_map``; each device computes its local clients' gradients
and the shards are reduced with a ``psum`` — structurally mirroring the MEC
server aggregation in paper §III.  The client axis is zero-row padded up to
a multiple of the mesh size (padded rows carry an all-zero mask, so they
contribute exactly nothing).  CI-testable on CPU via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; the sharded engine
reproduces the single-device trajectory to fp32 tolerance at any device
count (tests/test_sharded_engine.py).

Multi-realization mode
----------------------
``run_multi(iterations, n_realizations)`` vmaps the compiled scan over a
stack of independent delay realizations (same deployment, fresh network
draws), producing the Fig. 4/5 wall-clock curves *with confidence bands* in
one compiled call — ``MultiFedResult.wall_clock`` is ``(R, iterations)``.
For sweeps over many deployments sharing shapes, ``repro.launch.sweep``
stacks the per-deployment constants built here and vmaps the same step over
the (profile x realization) grid in one compiled call per scheme.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.checkpoint import io as ckpt_io
from repro.config import ExperimentSpec
from repro.core import aggregation, lm_round, rff as rff_mod, schemes
from repro.kernels import ops as kernel_ops
from repro.kernels.backend import resolve_interpret
from repro.core.delay_model import (mec_network, packet_bits,
                                    sample_round_times, scale_tau)
from repro.core.run_state import RunState, pack_state, unpack_state
from repro.net.estimator import (AdaptiveSchedule, OnlineChannelEstimator,
                                 plan_segment)
from repro.faults import inject as finject
from repro.net.trace import (TraceState, generate_trace_block,
                             sample_round_times_traced)
from repro.obs import spans as obs_spans

#: name of the client-partitioned mesh axis (see `repro.launch.mesh`)
CLIENT_AXIS = "clients"

#: divergence-guard learning-rate backoff per skipped round
LR_BACKOFF = 0.5


# jitted once at module level so the legacy oracle keeps the same compiled
# gradient path the pre-batched runtime had (the batched engine compiles its
# whole scan instead)
_batched_client_grads_jit = jax.jit(aggregation.batched_client_gradients)


@dataclasses.dataclass
class RoundLog:
    iteration: int
    wall_clock: float          # cumulative simulated seconds
    returned: int              # clients that made the deadline
    loss: float
    accuracy: float
    # per-round degradation counters (batched engine; the legacy oracle
    # has no guards and leaves the zero defaults)
    n_masked: int = 0          # contributions masked by the finite guard
    skipped: int = 0           # 1 if the divergence guard skipped the round


@dataclasses.dataclass
class RunHealth:
    """Degradation counters of a completed batched-engine run.

    ``rounds_degraded`` counts rounds where the non-finite guard masked
    at least one contribution (client upload or parity row);
    ``returns_masked`` is the total masked contributions over the run;
    ``rounds_skipped`` counts divergence-guard skips (iterate kept, lr
    backed off by `LR_BACKOFF`); ``lr_scale`` is the final backoff
    multiplier — 1.0 means the divergence guard never fired (for multi
    runs: the worst realization's).
    """
    rounds_degraded: int
    returns_masked: int
    rounds_skipped: int
    lr_scale: float


@dataclasses.dataclass
class FedResult:
    theta: jnp.ndarray
    history: list[RoundLog]
    t_star: float | None = None
    loads: np.ndarray | None = None
    setup_time: float = 0.0    # parity upload overhead (coded only)
    # worst-client eps-MI-DP leakage (bits) of the shared parity rows
    # (core/privacy.py, paper Appendix F); None for schemes that share
    # nothing beyond gradients
    privacy_eps: float | None = None
    # degradation counters (batched engine only; the legacy oracle has
    # no guards and reports None)
    health: RunHealth | None = None


@dataclasses.dataclass
class MultiFedResult:
    """One deployment, R independent delay realizations (vmapped scan).

    theta: (R, q, c) final iterates; wall_clock / returned: (R, iterations)
    cumulative simulated seconds (incl. setup) and per-round return counts.
    """
    theta: jnp.ndarray
    wall_clock: np.ndarray
    returned: np.ndarray
    t_star: float | None = None
    loads: np.ndarray | None = None
    setup_time: float = 0.0
    accuracy: np.ndarray | None = None   # (R,) if an eval_fn was supplied
    privacy_eps: float | None = None     # see FedResult.privacy_eps
    health: RunHealth | None = None      # aggregated over realizations

    def wall_clock_bands(self) -> tuple[np.ndarray, np.ndarray]:
        """(mean, std) over realizations, each (iterations,) — the Fig. 4/5
        curve with its confidence band."""
        return (self.wall_clock.mean(axis=0), self.wall_clock.std(axis=0))


# ---------------------------------------------------------------------------
# Scheme step: a module-level factory so the single-run scan, run_multi, and
# the compiled sweep engine (repro.launch.sweep) all execute the *same*
# per-round math.  Per-deployment arrays live in a `consts` dict (a pytree
# vmappable over a profile axis); everything Python-static lives in `static`.
# ---------------------------------------------------------------------------

def _guard_and_sum(g, ret, bad, guard):
    """Inject per-row corruption, guard non-finite rows, and reduce.

    Returns ``(g_sum, n_masked)``.  `bad` (rows,) carries injected fault
    values: a non-finite entry replaces the whole gradient row of a
    client that RETURNED this round (a client past the deadline uploads
    nothing, corrupt or not); finite entries leave rows bit-untouched
    (the replacement is a `where`, never an add, so -0.0 entries
    survive).  With `guard` every non-finite row — injected or organic —
    is zeroed out of the weighted sum and counted; without it, poison
    flows into the iterate and the always-on divergence guard skips the
    round instead.  On an all-finite run the guard is an IEEE no-op
    (``where(True, g, 0) == g``), so guard-on clean trajectories stay
    bit-identical to historical ones.
    """
    if bad is not None:
        live_bad = jnp.where(ret > 0.0, bad, 0.0)
        g = jnp.where(jnp.isfinite(live_bad)[:, None, None], g,
                      live_bad[:, None, None])
    if not guard:
        return aggregation.masked_gradient_sum(g, ret), jnp.int32(0)
    finite = jnp.all(jnp.isfinite(g), axis=(1, 2))
    n_masked = jnp.sum((ret > 0.0) & ~finite).astype(jnp.int32)
    g = jnp.where(finite[:, None, None], g, 0.0)
    return aggregation.masked_gradient_sum(g, ret), n_masked


def _make_grad_sum(static: dict):
    """g_sum(gx, gy, gmask, ret, theta[, bad]) ->
    ((q, c) returned-masked gradient sum, n_masked int32).

    Single-device: one masked-kernel call over the whole client tensor.
    Mesh mode: the same call per client shard inside `shard_map`, the
    (sum, count) pair reduced with a psum over the `clients` axis (the
    MEC server aggregation).  With ``fused_embed`` the call signature
    becomes ``g_sum(consts, gmask, ret, theta[, bad])`` — the fused
    embed->gradient kernel needs the omega/delta (and coded pphi) consts
    alongside the raw client tensor, and never runs under a mesh.  `bad`
    (fault injection, see `_guard_and_sum`) is only ever passed on the
    non-mesh paths — return-fault injection under a mesh is rejected at
    construction.
    """
    use_pallas = static["use_pallas"]
    interpret = static["interpret"]
    mesh: Optional[Mesh] = static["mesh"]
    guard = static.get("guard", True)

    if static.get("fused_embed", False):
        def local_fused(consts, gmask, ret, theta, bad=None):
            g = aggregation.fused_embed_client_gradients(
                consts["gx"], consts["gy"], consts["omega"],
                consts["delta"], theta, mask=gmask,
                parity_phi=consts.get("pphi"), use_pallas=use_pallas,
                interpret=interpret)
            return _guard_and_sum(g, ret, bad, guard)
        return local_fused

    def local(gx, gy, gmask, ret, theta, bad=None):
        g = aggregation.batched_client_gradients(
            gx, gy, theta, mask=gmask, use_pallas=use_pallas,
            interpret=interpret)
        return _guard_and_sum(g, ret, bad, guard)

    if mesh is None:
        return local

    def shard(gx, gy, gmask, ret, theta):
        return jax.lax.psum(local(gx, gy, gmask, ret, theta), CLIENT_AXIS)

    # check_vma=False: pallas_call has no replication rule; correctness is
    # covered by the psum (out is explicitly replicated by the reduction).
    return jax.shard_map(
        shard, mesh=mesh,
        in_specs=(P(CLIENT_AXIS), P(CLIENT_AXIS), P(CLIENT_AXIS),
                  P(CLIENT_AXIS), P()),
        out_specs=(P(), P()), check_vma=False)


def build_step(static: dict):
    """One scan step ``step(consts, carry, inp)``.

    `static` (Python-level, fixed at trace time): scheme (the registered
    `repro.core.schemes.Scheme` object), n, n_wait, l2, m, l, fused,
    mesh, use_pallas, interpret, collect_theta, channel, guard, faults,
    stale.
    `consts` (arrays, vmappable): gx (rows, L, q), gy (rows, L, c), gmask
    (rows, L), ret_tail (rows - n,), plus what the scheme adds
    (`Scheme.extra_consts`, `Scheme.segment_inputs`).

    ``carry`` is ``(theta, lr_scale)`` — lr_scale is the divergence
    guard's backoff multiplier, 1.0 until a non-finite iterate is
    produced, halved (`LR_BACKOFF`) on every skipped round thereafter;
    with ``stale=True`` (stale-update fault injection) it grows the
    previous round's iterate: ``(theta, lr_scale, theta_prev)``.

    ``inp`` is ``(t_row, lr, *ctl)``: ``ctl`` is what the scheme's
    `Scheme.round_rule` reads — empty on the stationary path, the
    per-round availability row ``(active,)`` when a network trace drives
    the run (``channel=True``), and the adaptive schemes' per-round
    control values after it.  The rule gives the round's deadline, the
    returned mask and the denominator; the step keeps what every scheme
    shares.  With ``faults=True`` (`repro.faults`) two fault inputs ride
    at the very END of the tuple: ``(..., fcode, fpar)`` — per-client
    fault codes (n,) int32 and the round's corrupted-parity flag () f32.
    Under the static channel profile `active` is identically 1.0 and
    every extra operation is an IEEE no-op, so trajectories stay
    bit-identical to the stationary path; likewise guard-on fault-free
    steps compile to bit-identical trajectories (see `_guard_and_sum`).

    The scheme is static, so each compiles to a straight-line fused
    update.
    """
    scheme = static["scheme"]
    l2 = static["l2"]
    fused = static["fused"]
    fused_embed = static.get("fused_embed", False)
    guard = static.get("guard", True)
    faults = static.get("faults", False)
    stale = static.get("stale", False)
    collect_theta = static["collect_theta"]
    use_pallas = static["use_pallas"]
    interpret = static["interpret"]
    grad_sum = _make_grad_sum(static)
    # a coded scheme's parity set is the first tail row of the fused
    # tensors, or rides apart in consts (par_x / par_y) when unfused
    parity_row = fused and scheme.coded
    parity_apart = scheme.coded and not fused

    def step(consts, carry, inp):
        if stale:
            theta, lr_scale, theta_prev = carry
        else:
            theta, lr_scale = carry
        if faults:
            *inp, fcode, fpar = inp
        t_row, lr, *ctl = inp
        t_round, ret_real, n_ret, denom, gmask = scheme.round_rule(
            static, consts, t_row, tuple(ctl))
        # ret_tail covers the pseudo-client rows: the always-active parity
        # row (fused coded) and any zero-mask mesh padding rows.
        ret = jnp.concatenate([ret_real.astype(jnp.float32),
                               consts["ret_tail"]])
        bad = None
        if faults:
            # per-row injected fault values: NaN/inf garbage where the
            # fault code says so, 0.0 (= leave the row untouched) where
            # clean; the parity pseudo-row (tail[0] of the fused coded
            # tensors) corrupts on the round's fpar flag
            bad_client = jnp.where(
                fcode == finject.CODE_NAN, jnp.float32(jnp.nan),
                jnp.where(fcode == finject.CODE_INF, jnp.float32(jnp.inf),
                          jnp.float32(0.0)))
            tail_n = consts["ret_tail"].shape[0]
            tail_bad = jnp.zeros((tail_n,), jnp.float32)
            if parity_row and tail_n:
                tail_bad = tail_bad.at[0].set(
                    jnp.where(fpar > 0, jnp.float32(jnp.nan),
                              jnp.float32(0.0)))
            bad = jnp.concatenate([bad_client, tail_bad])

        def sum_at(th, ret_v):
            args = ((consts, gmask, ret_v, th) if fused_embed
                    else (consts["gx"], consts["gy"], gmask, ret_v, th))
            if faults:
                args = args + (bad,)
            return grad_sum(*args)

        if stale:
            # stale-replay clients contribute their gradient at the
            # PREVIOUS iterate: partition the returned mask into fresh
            # and stale rows and take a second masked sum at theta_prev
            # (the parity row is server-side and always fresh)
            stale_f = (fcode == finject.CODE_STALE).astype(jnp.float32)
            stale_full = jnp.concatenate(
                [stale_f, jnp.zeros_like(consts["ret_tail"])])
            g_fresh, m_fresh = sum_at(theta, ret * (1.0 - stale_full))
            g_stale, m_stale = sum_at(theta_prev, ret * stale_full)
            g_sum = g_fresh + g_stale
            n_masked = m_fresh + m_stale
        else:
            g_sum, n_masked = sum_at(theta, ret)
        if parity_apart:
            g_par = aggregation.coded_gradient(
                consts["par_x"], consts["par_y"], theta, pnr_c=0.0,
                use_pallas=use_pallas, interpret=interpret)
            if faults:
                par_bad = jnp.where(fpar > 0, jnp.float32(jnp.nan),
                                    jnp.float32(0.0))
                g_par = jnp.where(jnp.isfinite(par_bad), g_par, par_bad)
            if guard:
                par_ok = jnp.all(jnp.isfinite(g_par))
                n_masked = n_masked + (~par_ok).astype(jnp.int32)
                g_par = jnp.where(par_ok, g_par, 0.0)
            g_sum = g_sum + g_par
        theta_upd = theta - (lr * lr_scale) * (g_sum / denom + l2 * theta)
        # always-on divergence guard: a non-finite iterate is never
        # committed — the round is skipped (model held) and the lr backs
        # off.  `lr * lr_scale` with lr_scale == 1.0 is bit-identical to
        # the unguarded update, so clean runs reproduce history exactly.
        ok = jnp.all(jnp.isfinite(theta_upd))
        theta_new = jnp.where(ok, theta_upd, theta)
        lr_scale_new = jnp.where(ok, lr_scale,
                                 lr_scale * jnp.float32(LR_BACKOFF))
        skipped = (~ok).astype(jnp.int32)
        out = (t_round, n_ret, n_masked, skipped)
        if collect_theta:
            out = out + (theta_new,)
        carry_new = ((theta_new, lr_scale_new, theta) if stale
                     else (theta_new, lr_scale_new))
        return carry_new, out

    return step


def _pad_rows(arr: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Zero-pad the leading (client) axis up to `rows`."""
    extra = rows - arr.shape[0]
    if extra == 0:
        return arr
    return jnp.pad(arr, ((0, extra),) + ((0, 0),) * (arr.ndim - 1))


def _round_rows(consts: dict) -> int:
    """Rows the compiled round reads each round: the point rows of the
    step's gradient tensor (with the fused coded path, n + 1 row blocks
    of L points, the parity set the last; mesh padding included), plus
    the parity rows where they ride as separate consts."""
    gx = consts["gx"]
    rows = int(gx.shape[0]) * int(gx.shape[1])
    if "par_x" in consts:
        rows += int(consts["par_x"].shape[0])
    return rows


def _pack_inputs(leaves) -> "tuple[np.ndarray, tuple]":
    """A block's host inputs as one int32 word buffer, for one upload.

    Each leaf is canonicalised as `jnp.asarray` does with 64-bit types
    off (floats to f32, integers to i32; a bool becomes a 0/1 word) and
    laid end to end.  Returns ``(words, layout)``; `layout` holds each
    leaf's ``(shape, dtype name)`` and is what `_unpack_inputs` needs."""
    words, layout = [], []
    for leaf in leaves:
        a = np.asarray(leaf)
        if a.dtype == np.bool_:
            name, w = "bool", a.astype(np.int32)
        elif a.dtype.kind == "f":
            name, w = "float32", a.astype(np.float32).view(np.int32)
        elif a.dtype.kind == "i":
            name, w = "int32", a.astype(np.int32)
        else:
            raise TypeError(f"cannot pack a {a.dtype} scan input")
        layout.append((a.shape, name))
        words.append(w.reshape(-1))
    return np.concatenate(words), tuple(layout)


def _unpack_inputs(words, layout) -> list:
    """Device side of `_pack_inputs`: the leaves again, bit for bit,
    by static slices, bitcasts and reshapes."""
    leaves, off = [], 0
    for shape, name in layout:
        size = math.prod(shape)
        w = words[off:off + size].reshape(shape)
        off += size
        if name == "float32":
            w = jax.lax.bitcast_convert_type(w, jnp.float32)
        elif name == "bool":
            w = w != 0
        leaves.append(w)
    return leaves


def _pack_outputs(per_round, lr_scale, extra=()) -> jnp.ndarray:
    """A block's host-bound outputs as one (4K + 1 + EK,) int32 vector,
    for one fetch: the per-round t_round (f32 bits), n_ret, n_masked and
    skipped, then the carry's lr_scale (f32 bits), then the E per-round
    `extra` outputs of the model round (`lm_round.EXTRAS`; floats as
    their bits)."""
    t_round, n_ret, n_masked, skipped = per_round
    bits = jax.lax.bitcast_convert_type
    extra = [bits(a, jnp.int32) if a.dtype == jnp.float32 else a
             for a in extra]
    return jnp.concatenate([bits(t_round, jnp.int32), n_ret, n_masked,
                            skipped, bits(lr_scale, jnp.int32)[None]]
                           + extra)


def _unpack_outputs(words: np.ndarray, K: int) -> tuple:
    """Host side of `_pack_outputs`: ``(t_rounds f64, n_ret i32,
    n_masked i64, skipped i64, lr_scale float)``."""
    return (words[:K].view(np.float32).astype(np.float64),
            words[K:2 * K],
            words[2 * K:3 * K].astype(np.int64),
            words[3 * K:4 * K].astype(np.int64),
            float(words[4 * K:].view(np.float32)[0]))


def _empty_sched(n: int) -> dict:
    """Zero-length adaptive-schedule record (keys per
    `repro.core.run_state._SCHED_KEYS`); blocks append to it via
    `_append_sched`, `Experiment._assemble_schedule` turns the finished
    record back into an `AdaptiveSchedule`."""
    return {
        "times": np.zeros((0, n), np.float64),
        "active": np.zeros((0, n), np.float32),
        "block_idx": np.zeros(0, np.int32),
        "t_star_r": np.zeros(0, np.float32),
        "n_wait_r": np.zeros(0, np.int32),
        "loads_blocks": np.zeros((0, n), np.float64),
        "est_mu": np.zeros((0, n), np.float64),
        "est_tau": np.zeros((0, n), np.float64),
        "est_p": np.zeros((0, n), np.float64),
        "est_avail": np.zeros((0, n), np.float64),
        "est_rounds_seen": np.zeros(0, np.int64),
    }


def _append_sched(sched: dict, seg) -> dict:
    """Append one `SegmentPlan`'s record to a schedule dict, offsetting
    the segment-local block indices onto the run-global block axis."""
    b0 = sched["loads_blocks"].shape[0]
    est = seg.estimates
    return {
        "times": np.concatenate([sched["times"], seg.times]),
        "active": np.concatenate([sched["active"], seg.active]),
        "block_idx": np.concatenate(
            [sched["block_idx"], (seg.block_idx + b0).astype(np.int32)]),
        "t_star_r": np.concatenate([sched["t_star_r"], seg.t_star_r]),
        "n_wait_r": np.concatenate([sched["n_wait_r"], seg.n_wait_r]),
        "loads_blocks": np.concatenate([sched["loads_blocks"],
                                        seg.loads_blocks]),
        "est_mu": np.concatenate(
            [sched["est_mu"], np.stack([e["mu"] for e in est])]),
        "est_tau": np.concatenate(
            [sched["est_tau"], np.stack([e["tau"] for e in est])]),
        "est_p": np.concatenate(
            [sched["est_p"], np.stack([e["p"] for e in est])]),
        "est_avail": np.concatenate(
            [sched["est_avail"], np.stack([e["avail"] for e in est])]),
        "est_rounds_seen": np.concatenate(
            [sched["est_rounds_seen"],
             np.array([e["rounds_seen"] for e in est], np.int64)]),
    }


class Experiment:
    """One runnable FL deployment, built from a frozen `ExperimentSpec`.

    Clients hold equally sized local minibatches of RFF-transformed data
    (x_stack: (n, l, q), y_stack: (n, l, c)); the delay network follows
    paper §V-A.  The spec names a registered scheme
    (``repro.core.schemes``) that owns the deployment setup — load
    allocation, parity construction, privacy accounting — and its
    contributions to the compiled step.  ``spec.engine`` selects the
    compiled batched scan loop ("batched", default) or the per-client
    Python oracle ("legacy"); ``spec.mesh`` (a device count) or the
    ``mesh`` override (an int or a concrete 1-D "clients" Mesh) shards the
    batched engine's client axis over devices.

    Prefer the entrypoint ``repro.api.build_experiment(spec, xs, ys)``.

    Batched-engine runs are block-structured: ``run``/``run_multi`` drive
    `init_state` / `run_block` / `finish` over an explicit `RunState`,
    checkpointable at every block boundary via `save_state` /
    `restore_state` (see the module docstring).
    """

    def __init__(self, spec: ExperimentSpec, x_stack, y_stack, *,
                 nodes: Optional[list] = None,
                 rng: Optional[np.random.Generator] = None,
                 mesh: "Mesh | int | None" = None):
        if not isinstance(spec, ExperimentSpec):
            raise TypeError(
                f"spec must be an ExperimentSpec, got {type(spec).__name__}"
                " (build one with repro.config.ExperimentSpec and pass it"
                " to repro.api.build_experiment)")
        if spec.hier_active:
            raise ValueError(
                f"spec requests the hierarchical tier (hier_shards="
                f"{spec.hier_shards}, sample_fraction="
                f"{spec.sample_fraction}) but was passed to the flat "
                "engine; build it with repro.api.build_experiment, which "
                "routes hier-active specs to repro.hier.HierExperiment")
        self.spec = spec
        fl_cfg = spec.resolved_fl()      # delay-profile knobs applied
        self.engine = spec.engine
        # "pallas" routes the batched engine's gradient calls through the
        # fused Pallas kernels (compiled on a TPU, interpret mode
        # elsewhere); "xla" keeps the plain-jnp vmapped path.  The legacy
        # oracle engine always uses the jnp path.
        self.kernel_backend = spec.kernel_backend
        self.alloc_backend = spec.alloc_backend
        self._interpret = resolve_interpret()
        self.mesh = self._resolve_mesh(spec.mesh if mesh is None else mesh)
        self.fused_coded = spec.fused_coded
        self.secure_aggregation = spec.secure_aggregation
        self.scheme = spec.resolved_scheme
        self.scheme_obj = schemes.get_scheme(self.scheme)
        self.step_kind = self.scheme_obj.step_kind
        self.scheme_params = spec.scheme_params_dict
        # --- network dynamics (repro.net): channel trace + adaptation
        self.channel = spec.resolved_channel()
        self.adapt_every = spec.adapt_every
        self.adaptive = self.scheme_obj.adaptive
        if self.adaptive:
            if self.engine == "legacy":
                raise ValueError(
                    f"scheme {self.scheme!r} needs the batched engine "
                    "(the legacy oracle has no adaptive schedule path)")
            if self.mesh is not None:
                raise NotImplementedError(
                    "adaptive schemes do not support client-mesh "
                    "sharding yet")
            if self.adapt_every < 1:
                raise ValueError(
                    f"scheme {self.scheme!r} requires "
                    "ExperimentSpec.adapt_every >= 1 (the re-allocation "
                    "period in rounds)")
            if self.channel is None:
                # adaptation without declared dynamics: run on the exact
                # static profile (estimation converges to the nominal
                # network, allocation stays ~put)
                from repro.net.channel import CHANNEL_PROFILES
                self.channel = CHANNEL_PROFILES["static"]
        # --- fault injection (repro.faults): return faults compile into
        # the step via a dedicated RNG stream; service-level faults
        # (crashes, checkpoint corruption) are read by ExperimentService
        self.faults = spec.resolved_faults()
        self.nonfinite_guard = bool(spec.nonfinite_guard)
        self.return_faults = (self.faults is not None
                              and self.faults.has_return_faults)
        self.stale_faults = (self.faults is not None
                             and self.faults.stale_prob > 0.0)
        if self.return_faults and self.mesh is not None:
            # the config layer rejects spec.mesh; this catches the
            # build_experiment(..., mesh=...) override path too
            raise NotImplementedError(
                "return-fault injection does not support client-mesh "
                "sharding yet (crash/checkpoint faults are fine)")
        self._fault_seed = fl_cfg.seed + 7717
        self.checkpoint_every = spec.checkpoint_every
        if (self.checkpoint_every > 0 and self.adaptive
                and self.checkpoint_every % self.adapt_every != 0):
            raise ValueError(
                f"checkpoint_every={self.checkpoint_every} must be a "
                f"multiple of adapt_every={self.adapt_every} so checkpoint "
                "boundaries align with re-allocation blocks")
        self.run_id = spec.run_id
        self._trace_seed = fl_cfg.seed + 9973
        # trace-stream reservation cursor: each single run reserves one
        # stream index, each traced run_multi one per realization.  The
        # reserved index lives in the run's RunState (not here), so
        # replaying a restored state is hermetic — this counter only
        # hands out fresh streams to NEW runs on this instance.
        self._trace_calls = 0
        self.last_schedule = None     # AdaptiveSchedule of the latest run
        self.fl = fl_cfg
        self.train = spec.train
        self.fused_embed = spec.fused_embed
        # a model spec (repro.core.lm_round): x_stack is the clients' token
        # ids (n, S + 1), one sequence each; a point is a next-token target
        self.lm = spec.model
        if self.lm is not None:
            if y_stack is not None:
                raise ValueError("a model spec takes the clients' token ids "
                                 "(n, S + 1) alone; y_stack must be None")
            self.x = jnp.asarray(x_stack, jnp.int32)
            self.y = None
            self.n, self.l = self.x.shape[0], self.x.shape[1] - 1
            self.q = self.c = self.d = None
            self.omega = self.delta = None
        else:
            self.x = jnp.asarray(x_stack)
            self.y = jnp.asarray(y_stack)
            self.c = self.y.shape[-1]
        # fused_embed: x_stack is RAW (n, l, d); q comes from the RFF
        # config and the shared-seed (Omega, delta) are derived here so
        # the in-kernel embed matches rff.rff_transform exactly
        if self.fused_embed:
            if self.adaptive:
                raise NotImplementedError(
                    f"scheme {self.scheme!r} does not support "
                    "fused_embed yet (adaptive re-allocation assumes "
                    "embedded tensors)")
            if self.mesh is not None:
                raise NotImplementedError(
                    "fused_embed does not support client-mesh sharding "
                    "yet")
            self.n, self.l, self.d = self.x.shape
            self.q = spec.rff.q
            self.omega, self.delta = rff_mod.rff_params(spec.rff, self.d)
        elif self.lm is None:
            self.n, self.l, self.q = self.x.shape
            self.d = None
            self.omega = self.delta = None
        self.m = self.n * self.l
        self.steps_per_epoch = spec.steps_per_epoch
        self.rng = rng or np.random.default_rng(fl_cfg.seed + 17)

        # --- delay network (tau scaled to the actual gradient/model packet)
        # a model's packet is its parameters, and a token costs a forward
        # and a backward pass over them: 3 MACs per parameter
        scalars = (self.q * self.c if self.lm is None
                   else lm_round.param_count(self.lm.model))
        per_point = scalars if self.lm is None else 3 * scalars
        base_nodes = nodes or mec_network(fl_cfg,
                                          d_scalars_per_point=per_point)
        payload = packet_bits(fl_cfg, scalars)    # model == gradient size
        self.nodes = [scale_tau(nd, payload) for nd in base_nodes[:self.n]]

        self.t_star = None
        self.t_ideal = None
        self.loads = np.full(self.n, self.l, dtype=np.float64)
        self.parity = None
        self.setup_time = 0.0
        self.processed_idx = [np.arange(self.l) for _ in range(self.n)]
        self._scan_cache: dict = {}
        # telemetry capture (repro.obs): per-block delay/plan references
        # kept only while spans are enabled, feeding `attribution()`
        self._attr_blocks: "list[dict]" = []
        if self.lm is not None:
            lm_round.validate(self)
            self._attn_scores = lm_round.attn_scores(self.lm.model, self.l)
        with obs_spans.span("setup/experiment"):
            self.scheme_obj.setup(self)
        self.privacy_eps = (None if self.lm is not None
                            else self.scheme_obj.privacy_budget(self))
        self._consts = None     # built lazily on first run/run_multi

    @staticmethod
    def _resolve_mesh(mesh) -> Optional[Mesh]:
        if mesh is None:
            return None
        if isinstance(mesh, int):
            from repro.launch.mesh import make_client_mesh
            mesh = make_client_mesh(mesh)
        if tuple(mesh.axis_names) != (CLIENT_AXIS,):
            raise ValueError(
                f"mesh must have exactly one axis named {CLIENT_AXIS!r}, "
                f"got {mesh.axis_names}")
        return mesh

    @property
    def n_wait(self) -> int:
        """Greedy-family wait count: the fastest (1 - psi) * n clients.
        Single source of truth for the compiled step's static clamp, the
        legacy oracle, and the adaptive controller's block-0 plan."""
        return max(1, int(math.ceil((1.0 - self.fl.psi) * self.n)))

    def embedded_x(self) -> jnp.ndarray:
        """Transient (n, l, q) embedded stack for HOST-SIDE setup only
        (parity encoding, privacy accounting).  The fused_embed round
        path never materializes this — phi is computed tile-by-tile
        inside the gradient kernel each round."""
        if not self.fused_embed:
            raise ValueError("embedded_x() is only meaningful with "
                             "fused_embed=True (x is already embedded)")
        return kernel_ops.rff_embed_batched(
            self.x, self.omega, self.delta,
            use_pallas=self.kernel_backend == "pallas",
            interpret=self._interpret)

    # -------------------------------------------------------- scheme plumbing
    def _pick_alloc_backend(self) -> str:
        """Resolve alloc_backend="auto": the vectorized jitted solver wins at
        scale, the scalar loop has no compile cost at small n.  Asymmetric
        links ride the vectorized solver's per-direction transmission grid
        since PR 5, so symmetry no longer forces the scalar path — but the
        pair grid is O(Vd*Vu) columns, so auto keeps high-erasure
        asymmetric populations (grid wider than ~4k columns) on the scalar
        loop rather than materializing multi-GB solver intermediates.
        Explicit alloc_backend="vectorized" overrides."""
        if self.alloc_backend != "auto":
            return self.alloc_backend
        from repro.core.load_allocation import vectorized_grid_width
        return "vectorized" if (self.n >= 64 and
                                vectorized_grid_width(self.nodes) <= 4096) \
            else "scalar"

    # ------------------------------------------------------------- step consts
    def consts_point_len(self) -> int:
        """Point-axis length of `build_consts()["gx"]` — shape arithmetic
        only, so sweep callers can compute a grid-wide `l_target` without
        materializing (and discarding) the fused tensors per profile."""
        return self.scheme_obj.consts_point_len(self)

    def build_consts(self, l_target: Optional[int] = None) -> dict:
        """Per-deployment arrays consumed by `build_step`'s step function.

        The registered scheme contributes the gradient tensors and its
        scheme-specific consts (deadlines, parity, activity masks).
        `l_target` pads the point axis up to a common length so deployments
        with different per-client loads stack along a profile axis
        (repro.launch.sweep).  With a mesh, the client axis is additionally
        zero-row padded to a multiple of the mesh size.
        """
        if self.lm is not None:
            return lm_round.consts(self)
        gx, gy, gmask, tail = self.scheme_obj.grad_tensors(self, l_target)
        if self.mesh is not None:
            rows = -(-gx.shape[0] // self.mesh.size) * self.mesh.size
            tail = tail + [0.0] * (rows - gx.shape[0])
            gx, gy, gmask = (_pad_rows(gx, rows), _pad_rows(gy, rows),
                             _pad_rows(gmask, rows))
        consts = {
            "gx": gx, "gy": gy, "gmask": gmask,
            "ret_tail": jnp.asarray(tail, jnp.float32),
        }
        if self.fused_embed:
            consts["omega"] = self.omega
            consts["delta"] = self.delta
        consts.update(self.scheme_obj.extra_consts(self))
        return consts

    def step_static(self, collect_theta: bool = False) -> dict:
        """Python-static step parameters matching `build_consts`."""
        return {
            "scheme": self.scheme_obj,
            "n": self.n,
            "n_wait": self.n_wait,
            "l2": self.train.l2_reg,
            "m": float(self.m),
            "l": float(self.l),
            "fused": self.fused_coded,
            "fused_embed": self.fused_embed,
            "mesh": self.mesh,
            "use_pallas": self.kernel_backend == "pallas",
            "interpret": self._interpret,
            "collect_theta": collect_theta,
            "channel": self.channel is not None,
            "guard": self.nonfinite_guard,
            "faults": self.return_faults,
            "stale": self.stale_faults,
        }

    def scheme_params_estimator_kwargs(self) -> dict:
        """Estimator knobs riding in `scheme_params` (adaptive family)."""
        kw = {}
        if "est_beta" in self.scheme_params:
            kw["beta"] = float(self.scheme_params["est_beta"])
        if "est_window" in self.scheme_params:
            kw["window"] = int(self.scheme_params["est_window"])
        return kw

    # ------------------------------------------------------------------ round
    def _sample_round_times(self, rounds: int = 1) -> np.ndarray:
        """(rounds, n) delay samples — one vectorized draw for the whole run."""
        return sample_round_times(self.nodes, np.asarray(self.loads, float),
                                  self.rng, rounds)

    def _reserve_trace_streams(self, k: int) -> int:
        """Reserve `k` consecutive trace-stream indices for a new run and
        return the base index.  The base lives in the run's `RunState`
        (``trace_call``), so restored states replay hermetically no
        matter how many runs this instance has since started."""
        base = self._trace_calls
        self._trace_calls += k
        return base

    def _trace_rng(self, index: int) -> np.random.Generator:
        """Dedicated per-run trace generator: deterministic per (seed,
        stream index) and independent of `self.rng`, so turning the
        channel on never shifts the delay-draw stream the static engine
        consumes."""
        return np.random.default_rng((self._trace_seed, int(index)))

    def _lr(self, epoch: int) -> float:
        lr = self.train.learning_rate
        for e in self.train.lr_decay_epochs:
            if epoch >= e:
                lr *= self.train.lr_decay
        return lr

    def _lr_schedule_range(self, r0: int, r1: int) -> np.ndarray:
        """Per-round learning rates for global rounds [r0, r1) — blocks
        read their position from the global cursor, so the schedule is
        invariant to how the run is partitioned into blocks."""
        return np.array([self._lr(it // self.steps_per_epoch)
                         for it in range(r0, r1)], np.float32)

    def _lr_schedule(self, iterations: int) -> np.ndarray:
        return self._lr_schedule_range(0, iterations)

    # --------------------------------------------------------- batched engine
    @staticmethod
    def _timed_scan(fn):
        """Telemetry shim over a cached jitted scan: the first (compiling)
        call lands in span ``scan/compile``, warm calls in
        ``scan/execute``.  Disabled spans delegate straight through.  The
        compile span blocks on the output (it runs once, in warm-up); the
        execute span times the dispatch only and never syncs, so tracing
        leaves the block's schedule as it is — the device side is read
        from a profiler trace.  ``.lower`` is the jitted scan's, so the
        compiled round program can be inspected."""
        state = {"warm": False}

        def call(*args):
            if not obs_spans.enabled():
                state["warm"] = True
                return fn(*args)
            if state["warm"]:
                with obs_spans.span("scan/execute"):
                    return fn(*args)
            with obs_spans.span("scan/compile"):
                out = jax.block_until_ready(fn(*args))
            state["warm"] = True
            return out

        call.lower = fn.lower
        return call

    def _get_scan(self, collect_theta: bool, layout: tuple):
        """jit'd `lax.scan` of one flat block, cached per (scheme, collect,
        layout).  ``fn(consts, thetas, words)``: `thetas` is ``(theta,)``,
        or ``(theta, theta_prev)`` with stale replay; `words` is the
        `_pack_inputs` buffer of the xs leaves (their structure follows
        the step's static configuration, see `build_step`) and, last, the
        carry's lr_scale.  Returns ``(thetas, packed, collected)``:
        the new `thetas`, the `_pack_outputs` vector, and ``(thetas per
        round,)`` with `collect_theta`, else ``()``."""
        cache_key = (self.scheme, collect_theta, layout)
        fn = self._scan_cache.get(cache_key)
        if fn is None:
            if self.lm is not None:
                # the model's iterate is donated: a block consumes its
                # input state's parameter and optimizer buffers
                step, n_extra, donate = (lm_round.build_step(self),
                                         len(lm_round.EXTRAS), (1,))
            else:
                step, n_extra, donate = (
                    build_step(self.step_static(collect_theta)), 0, ())

            def block(consts, thetas, words):
                *xs, lr_scale = _unpack_inputs(words, layout)
                carry0 = (thetas[0], lr_scale) + tuple(thetas[1:])
                carry, per_round = jax.lax.scan(
                    lambda c, inp: step(consts, c, inp), carry0, tuple(xs))
                return ((carry[0],) + carry[2:],
                        _pack_outputs(per_round[:4], carry[1],
                                      per_round[4:4 + n_extra]),
                        per_round[4 + n_extra:])

            fn = self._timed_scan(jax.jit(block, donate_argnums=donate))
            self._scan_cache[cache_key] = fn
        return fn

    def _get_consts(self) -> dict:
        if self._consts is None:
            self._consts = self.build_consts()
        return self._consts

    def _prepare_scan(self, collect: bool, xs: tuple, lr_scale, theta,
                      theta_prev=None) -> tuple:
        """A flat block's scan and its arguments: the host `xs` leaves and
        the carry's `lr_scale` go to the device in one packed upload.
        Returns ``(scan_fn, thetas, words)``; the caller dispatches
        ``scan_fn(consts, thetas, words)`` and starts the packed output's
        copy to the host at once, so it begins the moment the scan ends."""
        words, layout = _pack_inputs(xs + (lr_scale,))
        words = jax.device_put(words)
        # the packed upload here and the packed fetch after the scan
        obs_spans.count("block/transfers", 2)
        thetas = (theta,)
        if self.stale_faults:
            thetas += (theta if theta_prev is None else theta_prev,)
        return self._get_scan(collect, layout), thetas, words

    def _get_multi_scan(self):
        """jit'd vmapped scan for the stationary multi-realization mode,
        cached once per scheme.  Takes the per-realization carry
        explicitly so blocks chain across calls.  With return faults
        enabled the per-realization fault inputs join the vmapped xs."""
        cache_key = (self.scheme, "multi")
        fn = self._scan_cache.get(cache_key)
        if fn is None:
            step = build_step(self.step_static(collect_theta=False))
            if self.return_faults:
                def multi(consts, carry0_r, times_r, lrs_r, fc_r, fp_r):
                    def one(c0, tj, fc, fp):
                        return jax.lax.scan(
                            lambda c, inp: step(consts, c, inp), c0,
                            (tj, lrs_r, fc, fp))
                    return jax.vmap(one)(carry0_r, times_r, fc_r, fp_r)
            else:
                def multi(consts, carry0_r, times_r, lrs_r):
                    def one(c0, tj):
                        return jax.lax.scan(
                            lambda c, inp: step(consts, c, inp), c0,
                            (tj, lrs_r))
                    return jax.vmap(one)(carry0_r, times_r)

            fn = self._timed_scan(jax.jit(multi))
            self._scan_cache[cache_key] = fn
        return fn

    # ------------------------------------------------------- fault plumbing
    def _fault_rows(self, state: RunState, rounds: int):
        """Draw `rounds` rows of fault inputs from the state's dedicated
        fault stream; returns ``(xs_extra, new_rng_state)``, `xs_extra`
        the host ``(codes, parity_bad)`` rows — ``((), old state)`` when
        return faults are off.  The stream is seeded off
        ``fl.seed + 7717``, independent of both the delay-draw RNG and
        the channel-trace streams, so toggling faults never shifts the
        network realization a run faces."""
        if not self.return_faults:
            return (), state.fault_rng_state
        frng = np.random.default_rng()
        frng.bit_generator.state = state.fault_rng_state
        fcodes, fpar = finject.sample_fault_rows(
            self.faults, frng, rounds, self.n)
        return (fcodes, fpar), frng.bit_generator.state

    def _carry0(self, theta, lr_scale, theta_prev=None):
        """Scan carry matching `build_step`'s static configuration."""
        carry = (jnp.asarray(theta),
                 jnp.asarray(np.asarray(lr_scale), jnp.float32))
        if self.stale_faults:
            carry = carry + (jnp.asarray(
                theta if theta_prev is None else theta_prev),)
        return carry

    # ------------------------------------------------- block-structured runs
    def init_state(self, iterations: int, *,
                   n_realizations: Optional[int] = None,
                   collect: bool = False, params=None) -> RunState:
        """Fresh `RunState` for a run of `iterations` rounds.

        ``n_realizations=None`` starts a "single" run; otherwise a
        "multi" run (stationary — blocks advance all realizations'
        cursors together through one vmapped scan call) or a
        "multi_channel" run (traced — blocks advance one full
        realization at a time, each with its own trace stream).  The
        state is seeded from this experiment's live RNG and the run's
        trace streams are reserved here, so runs launched back to back
        consume disjoint randomness exactly like the pre-RunState
        engine.  A model spec's run starts from `params` where given (the
        caller's weights, `repro.core.lm_round.init_theta`), else from
        weights made from ``spec.model.init_seed``.
        """
        if params is not None and self.lm is None:
            raise ValueError("params are a model spec's initial weights; "
                             "this experiment trains no model")
        iterations = int(iterations)
        if iterations < 1:
            raise ValueError(f"iterations={iterations} must be >= 1")
        self._attr_blocks = []   # attribution covers the new run only
        if n_realizations is None:
            mode = "single"
            R = None
        else:
            R = int(n_realizations)
            if R < 1:
                raise ValueError(f"n_realizations={R} must be >= 1")
            mode = "multi_channel" if self.channel is not None else "multi"
            collect = False
        trace_call = -1
        trace = est = controls = sched = None
        if self.channel is not None:
            if mode == "single":
                trace_call = self._reserve_trace_streams(1)
                trace = TraceState.init(self.n, self._trace_rng(trace_call))
                if self.adaptive:
                    est = OnlineChannelEstimator(
                        self.nodes,
                        **self.scheme_params_estimator_kwargs()).state_dict()
                    controls = self.scheme_obj.initial_controls(self)
                    sched = _empty_sched(self.n)
            else:
                # one stream per realization; the per-realization
                # estimator/controls are block-local (a block IS one
                # whole realization), so they never live in the state
                trace_call = self._reserve_trace_streams(R)
        lm_log = None
        if self.lm is not None:
            if mode != "single" or collect:
                raise ValueError("a model spec runs single trajectories "
                                 "(no run_multi, no eval_fn)")
            lm_log = {k: np.zeros(0, np.float64 if k == "loss" else np.int64)
                      for k in lm_round.EXTRAS}
        if mode == "single":
            theta = (lm_round.init_theta(self.lm, params)
                     if self.lm is not None
                     else jnp.zeros((self.q, self.c), jnp.float32))
            t_rounds = np.zeros(0, np.float64)
            n_ret = np.zeros(0, np.int32)
            lr_scale = 1.0
            n_masked = np.zeros(0, np.int64)
            skipped = np.zeros(0, np.int64)
        elif mode == "multi":
            theta = jnp.zeros((R, self.q, self.c), jnp.float32)
            t_rounds = np.zeros((R, 0), np.float64)
            n_ret = np.zeros((R, 0), np.int32)
            lr_scale = np.ones(R, np.float64)
            n_masked = np.zeros((R, 0), np.int64)
            skipped = np.zeros((R, 0), np.int64)
        else:
            theta = jnp.zeros((R, self.q, self.c), jnp.float32)
            t_rounds = np.zeros((0, iterations), np.float64)
            n_ret = np.zeros((0, iterations), np.int32)
            lr_scale = np.ones(R, np.float64)
            n_masked = np.zeros((0, iterations), np.int64)
            skipped = np.zeros((0, iterations), np.int64)
        losses = accs = None
        if mode == "single" and collect:
            losses = np.zeros(0, np.float64)
            accs = np.zeros(0, np.float64)
        # stale-fault replay needs the previous iterate in the carry;
        # multi_channel blocks are whole realizations, so theirs is
        # block-local and never lives in the state
        theta_prev = (theta if self.stale_faults
                      and mode != "multi_channel" else None)
        fault_rng_state = None
        if self.return_faults:
            fault_rng_state = np.random.default_rng(
                (self._fault_seed,)).bit_generator.state
        return RunState(
            mode=mode, iterations=iterations, rounds_done=0,
            realizations_done=0, n_realizations=R, collect=bool(collect),
            theta=theta, rng_state=self.rng.bit_generator.state,
            trace_call=trace_call, trace=trace, est=est, controls=controls,
            t_rounds=t_rounds, n_ret=n_ret, losses=losses, accs=accs,
            sched=sched, lr_scale=lr_scale, n_masked=n_masked,
            skipped=skipped, theta_prev=theta_prev,
            fault_rng_state=fault_rng_state, lm_log=lm_log)

    def run_block(self, state: RunState, n_rounds: Optional[int] = None, *,
                  eval_fn: Optional[Callable] = None,
                  eval_every: int = 10) -> RunState:
        """Advance a run by one block and return the NEW `RunState` (the
        input is never mutated, so replaying a block from a saved state
        is always safe; a model spec's block consumes the input state's
        parameter and optimizer buffers, `repro.core.lm_round`).

        ``n_rounds`` defaults to ``spec.checkpoint_every``, or the whole
        remaining horizon when that is 0.  "multi_channel" runs advance
        exactly one full realization per block regardless of
        ``n_rounds``.  A "single" run initialized with ``collect=True``
        must be given its ``eval_fn`` on every block — losses are
        evaluated block-locally so resumed runs rebuild the identical
        loss curve.
        """
        if state.done:
            raise ValueError(
                "run is already complete "
                f"({state.rounds_done}/{state.iterations} rounds)")
        if state.mode == "single":
            if state.collect and eval_fn is None:
                raise ValueError("state was initialized with collect=True; "
                                 "run_block needs its eval_fn")
            if not state.collect and eval_fn is not None:
                raise ValueError(
                    "state was initialized with collect=False; re-init "
                    "with collect=True to evaluate during the run")
        with obs_spans.span("block/run", cursor=state.rounds_done):
            # detached generator: the stream position lives in the state,
            # not in this Experiment, so replaying a restored block is
            # hermetic
            rng = np.random.default_rng()
            rng.bit_generator.state = state.rng_state
            if state.mode == "multi_channel":
                return self._block_multi_channel(state, rng)
            r0 = state.rounds_done
            K = int(n_rounds) if n_rounds is not None else (
                self.checkpoint_every or state.iterations)
            if K < 1:
                raise ValueError(f"n_rounds={K} must be >= 1")
            K = min(K, state.iterations - r0)
            lrs = self._lr_schedule_range(r0, r0 + K)
            if state.mode == "multi":
                return self._block_multi(state, rng, K, lrs)
            return self._block_single(state, rng, K, lrs, eval_fn,
                                      eval_every)

    def _block_single(self, state: RunState, rng, K: int, lrs, eval_fn,
                      eval_every: int) -> RunState:
        """K rounds of a single trajectory: stationary pre-sampling, or
        the traced-channel (and adaptive-controller) path chained through
        the state's `TraceState` / estimator stats / control values."""
        r0 = state.rounds_done
        with obs_spans.span("block/prepare"):
            consts = self._get_consts()
            if obs_spans.enabled() and self.lm is None:
                obs_spans.count("round/rows", K * _round_rows(consts))
            trace_new = state.trace
            est_new, controls_new = state.est, state.controls
            sched_new = state.sched
            if self.channel is None:
                times = sample_round_times(
                    self.nodes, np.asarray(self.loads, float), rng, K)
                xs = (times, lrs)
            else:
                with obs_spans.span("trace/generate"):
                    trace_block, trace_new = generate_trace_block(
                        self.nodes, self.channel, K, state.trace)
                if self.adaptive:
                    est = OnlineChannelEstimator(
                        self.nodes, **self.scheme_params_estimator_kwargs())
                    est.load_state_dict(state.est)
                    seg = plan_segment(self, est, trace_block, r0, r0 + K,
                                       state.controls, rng)
                    extra, more_consts = self.scheme_obj.segment_inputs(
                        self, seg)
                    consts = dict(consts, **more_consts)
                    xs = (seg.times, lrs, seg.active) + extra
                    est_new = est.state_dict()
                    controls_new = seg.controls
                    sched_new = _append_sched(state.sched, seg)
                else:
                    times = sample_round_times_traced(
                        self.nodes, np.asarray(self.loads, float), rng,
                        trace_block)
                    xs = (times, lrs,
                          trace_block.active.astype(np.float32))
            fault_xs, fault_rng_new = self._fault_rows(state, K)
            scan_fn, thetas, words = self._prepare_scan(
                state.collect, xs + fault_xs, state.lr_scale, state.theta,
                state.theta_prev)
        thetas, packed, collected = scan_fn(consts, thetas, words)
        packed.copy_to_host_async()
        with obs_spans.span("block/fetch"):
            out = np.asarray(packed)
            (t_rounds_b, n_ret_b, n_masked_b, skipped_b,
             lr_scale) = _unpack_outputs(out, K)
        if obs_spans.enabled():
            # the delays as the program saw them (f32) and the deadline
            # it played, so `times > deadline` is the program's decision
            self._attr_blocks.append({
                "times": np.asarray(xs[0], np.float32),
                "active": xs[2] if self.channel is not None else None,
                "deadline": t_rounds_b})
        lm_log = state.lm_log
        if self.lm is not None:
            ext = lm_round.unpack_extras(out[4 * K + 1:], K)
            obs_spans.count("round/tokens", K * self.n * self.l)
            scores, causal = self._attn_scores
            obs_spans.count("attn/scores", K * self.n * scores)
            obs_spans.count("attn/causal_scores", K * self.n * causal)
            obs_spans.count("moe/routed", int(ext["routed"].sum()))
            obs_spans.count("moe/expert_rows", int(ext["rows"].sum()))
            lm_log = {k: np.concatenate([state.lm_log[k], ext[k]])
                      for k in lm_round.EXTRAS}
        losses_new, accs_new = state.losses, state.accs
        if state.collect:
            thetas_r = collected[0]
            loss_b = np.full(K, np.nan)
            acc_b = np.full(K, np.nan)
            for k in range(K):
                it = r0 + k
                if it % eval_every == 0 or it == state.iterations - 1:
                    loss, acc = eval_fn(thetas_r[k])
                    loss_b[k] = float(loss)
                    acc_b[k] = float(acc)
            losses_new = np.concatenate([state.losses, loss_b])
            accs_new = np.concatenate([state.accs, acc_b])
        with obs_spans.span("block/state"):
            return dataclasses.replace(
                state, rounds_done=r0 + K, theta=thetas[0],
                rng_state=rng.bit_generator.state, trace=trace_new,
                est=est_new, controls=controls_new,
                t_rounds=np.concatenate([state.t_rounds, t_rounds_b]),
                n_ret=np.concatenate([state.n_ret, n_ret_b]),
                losses=losses_new, accs=accs_new, sched=sched_new,
                lr_scale=lr_scale,
                n_masked=np.concatenate([state.n_masked, n_masked_b]),
                skipped=np.concatenate([state.skipped, skipped_b]),
                theta_prev=(thetas[1] if self.stale_faults else None),
                fault_rng_state=fault_rng_new, lm_log=lm_log)

    def _block_multi(self, state: RunState, rng, K: int, lrs) -> RunState:
        """K rounds of ALL stationary realizations in one vmapped scan
        call; per-realization theta carries chain across blocks."""
        R = int(state.n_realizations)
        times = sample_round_times(
            self.nodes, np.asarray(self.loads, float), rng, R * K)
        times = times.reshape(R, K, self.n)
        multi = self._get_multi_scan()
        args = (self._get_consts(),
                self._carry0(state.theta, state.lr_scale,
                             state.theta_prev),
                jnp.asarray(times, jnp.float32), jnp.asarray(lrs))
        fault_rng_new = state.fault_rng_state
        if self.return_faults:
            frng = np.random.default_rng()
            frng.bit_generator.state = state.fault_rng_state
            fcodes, fpar = finject.sample_fault_rows(
                self.faults, frng, R * K, self.n)
            args = args + (
                jnp.asarray(fcodes.reshape(R, K, self.n)),
                jnp.asarray(fpar.reshape(R, K), jnp.float32))
            fault_rng_new = frng.bit_generator.state
        carry_out, (t_rounds, n_ret, n_masked, skipped) = multi(*args)
        return dataclasses.replace(
            state, rounds_done=state.rounds_done + K, theta=carry_out[0],
            rng_state=rng.bit_generator.state,
            t_rounds=np.concatenate(
                [state.t_rounds, np.asarray(t_rounds, np.float64)], axis=1),
            n_ret=np.concatenate(
                [state.n_ret, np.asarray(n_ret)], axis=1),
            lr_scale=np.asarray(carry_out[1], np.float64),
            n_masked=np.concatenate(
                [state.n_masked, np.asarray(n_masked, np.int64)], axis=1),
            skipped=np.concatenate(
                [state.skipped, np.asarray(skipped, np.int64)], axis=1),
            theta_prev=(carry_out[2] if self.stale_faults else None),
            fault_rng_state=fault_rng_new)

    def _block_multi_channel(self, state: RunState, rng) -> RunState:
        """One full traced realization per block: a fresh trace stream at
        index ``trace_call + r`` and (adaptive family) a fresh controller,
        exactly like the per-realization host loop of the pre-RunState
        engine."""
        r = state.realizations_done
        tstate = TraceState.init(self.n,
                                 self._trace_rng(state.trace_call + r))
        with obs_spans.span("trace/generate"):
            trace, _ = generate_trace_block(self.nodes, self.channel,
                                            state.iterations, tstate)
        consts = self._get_consts()
        lrs = self._lr_schedule(state.iterations)
        sched_new = state.sched
        if self.adaptive:
            est = OnlineChannelEstimator(
                self.nodes, **self.scheme_params_estimator_kwargs())
            seg = plan_segment(self, est, trace, 0, state.iterations,
                               self.scheme_obj.initial_controls(self), rng)
            extra, more_consts = self.scheme_obj.segment_inputs(self, seg)
            consts = dict(consts, **more_consts)
            xs = (seg.times, lrs, seg.active) + extra
            # the record kept is the LAST realization's plan, matching the
            # pre-RunState engine's `last_schedule` semantics
            sched_new = _append_sched(_empty_sched(self.n), seg)
        else:
            times = sample_round_times_traced(
                self.nodes, np.asarray(self.loads, float), rng, trace)
            xs = (times, lrs, trace.active.astype(np.float32))
        fault_xs, fault_rng_new = self._fault_rows(state,
                                                   state.iterations)
        theta0 = jnp.zeros((self.q, self.c), jnp.float32)
        scan_fn, thetas, words = self._prepare_scan(
            False, xs + fault_xs, 1.0, theta0)
        thetas, packed, _ = scan_fn(consts, thetas, words)
        packed.copy_to_host_async()
        t_rounds_r, n_ret_r, n_masked_r, skipped_r, lr_scale_r = (
            _unpack_outputs(np.asarray(packed), state.iterations))
        lr_scale_new = np.asarray(state.lr_scale, np.float64).copy()
        lr_scale_new[r] = lr_scale_r
        return dataclasses.replace(
            state, realizations_done=r + 1,
            rounds_done=(r + 1) * state.iterations,
            theta=state.theta.at[r].set(thetas[0]),
            rng_state=rng.bit_generator.state, sched=sched_new,
            t_rounds=np.concatenate([state.t_rounds, t_rounds_r[None]]),
            n_ret=np.concatenate([state.n_ret, n_ret_r[None]]),
            lr_scale=lr_scale_new,
            n_masked=np.concatenate([state.n_masked, n_masked_r[None]]),
            skipped=np.concatenate([state.skipped, skipped_r[None]]),
            fault_rng_state=fault_rng_new)

    # ---------------------------------------------------- checkpoint/restore
    def save_state(self, path: str, state: RunState) -> str:
        """Checkpoint `state` atomically (`repro.checkpoint.io`),
        embedding this experiment's `ExperimentSpec` as JSON provenance."""
        if self.lm is not None:
            raise NotImplementedError(
                "checkpoints of a model spec's run state (a parameter "
                "pytree and AdamW state) are not supported yet")
        arrays, meta = pack_state(state)
        meta["spec"] = self.spec.to_dict()
        with obs_spans.span("checkpoint/save"):
            return ckpt_io.save_state(path, arrays, meta)

    def restore_state(self, path: str) -> RunState:
        """Load a `RunState` checkpoint, verify its spec provenance
        against this experiment, and bump the trace-stream cursor past
        the restored run's reservation so new runs stay disjoint."""
        self._attr_blocks = []   # attribution covers post-restore rounds
        with obs_spans.span("checkpoint/restore"):
            arrays, meta = ckpt_io.restore_state(path)
        spec_dict = meta.get("spec")
        if spec_dict is not None:
            saved = ExperimentSpec.from_dict(spec_dict)
            if saved != self.spec:
                raise ValueError(
                    f"checkpoint provenance mismatch: {path!r} was saved "
                    "by a run of a different ExperimentSpec than this "
                    "experiment's — refusing to resume across specs")
        state = unpack_state(arrays, meta)
        if state.trace_call >= 0:
            reserved = (int(state.n_realizations)
                        if state.mode == "multi_channel" else 1)
            self._trace_calls = max(self._trace_calls,
                                    state.trace_call + reserved)
        return state

    # ------------------------------------------------------------ finalizing
    def finish(self, state: RunState,
               eval_fn: Optional[Callable] = None):
        """Turn a completed `RunState` into `FedResult` /
        `MultiFedResult` and sync this experiment's RNG to the run-end
        stream position (so back-to-back runs consume disjoint draws,
        exactly like the pre-RunState engine)."""
        if not state.done:
            raise ValueError(
                f"run is not complete ({state.rounds_done}/"
                f"{state.iterations} rounds); call run_block until "
                "state.done")
        self.rng.bit_generator.state = state.rng_state
        if state.sched is not None:
            self.last_schedule = self._assemble_schedule(state.sched)
        if state.mode == "single":
            return self._finish_single(state)
        return self._finish_multi(state, eval_fn)

    @staticmethod
    def _run_health(state: RunState) -> "RunHealth | None":
        if state.n_masked is None:
            return None
        ls = np.asarray(state.lr_scale, np.float64)
        return RunHealth(
            rounds_degraded=int(np.sum(np.asarray(state.n_masked) > 0)),
            returns_masked=int(np.sum(state.n_masked)),
            rounds_skipped=int(np.sum(state.skipped)),
            lr_scale=float(ls.min() if ls.ndim else ls))

    def _finish_single(self, state: RunState) -> FedResult:
        wall = self.setup_time + np.cumsum(state.t_rounds)
        history: list[RoundLog] = []
        # a restored format-1 checkpoint has no guard counters
        have_guards = state.n_masked is not None
        for it in range(state.iterations):
            loss = float(state.losses[it]) if state.collect else float("nan")
            if state.lm_log is not None:
                loss = float(state.lm_log["loss"][it])
            acc = float(state.accs[it]) if state.collect else float("nan")
            history.append(RoundLog(
                it, float(wall[it]), int(state.n_ret[it]), loss, acc,
                n_masked=int(state.n_masked[it]) if have_guards else 0,
                skipped=int(state.skipped[it]) if have_guards else 0))
        return FedResult(theta=state.theta, history=history,
                         t_star=self.t_star, loads=self.loads,
                         setup_time=self.setup_time,
                         privacy_eps=self.privacy_eps,
                         health=self._run_health(state))

    def _finish_multi(self, state: RunState, eval_fn) -> MultiFedResult:
        wall = self.setup_time + np.cumsum(state.t_rounds, axis=1)
        theta = state.theta
        acc = None
        if eval_fn is not None:
            if state.mode == "multi_channel":
                acc = np.array([eval_fn(theta[r])[1]
                                for r in range(theta.shape[0])])
            else:
                # vmap the eval over the realization axis when eval_fn is
                # jax-traceable (it must then be pure — it sees a batched
                # tracer, not R concrete arrays); numpy/host-side eval_fns
                # raise a tracer-conversion error and fall back to the
                # loop.  Genuine eval_fn bugs (bad shapes) propagate.
                try:
                    acc = np.asarray(jax.vmap(
                        lambda th: jnp.asarray(eval_fn(th)[1]))(theta))
                except jax.errors.JAXTypeError:
                    acc = np.array([eval_fn(theta[r])[1]
                                    for r in range(theta.shape[0])])
        return MultiFedResult(theta=theta, wall_clock=wall,
                              returned=np.asarray(state.n_ret),
                              t_star=self.t_star, loads=self.loads,
                              setup_time=self.setup_time, accuracy=acc,
                              privacy_eps=self.privacy_eps,
                              health=self._run_health(state))

    def _assemble_schedule(self, sched: dict) -> AdaptiveSchedule:
        """Rebuild the run's `AdaptiveSchedule` from the state's
        serialized record; the scheme's own fields come from
        `Scheme.schedule_fields` (a coded scheme's load masks are a pure
        function of the per-block loads)."""
        estimates = [
            {"mu": sched["est_mu"][b], "tau": sched["est_tau"][b],
             "p": sched["est_p"][b], "avail": sched["est_avail"][b],
             "rounds_seen": int(sched["est_rounds_seen"][b])}
            for b in range(sched["loads_blocks"].shape[0])]
        return AdaptiveSchedule(
            times=sched["times"], active=sched["active"],
            block_idx=sched["block_idx"],
            loads_blocks=sched["loads_blocks"], estimates=estimates,
            **self.scheme_obj.schedule_fields(
                self, sched["t_star_r"], sched["n_wait_r"],
                sched["loads_blocks"]))

    # ------------------------------------------------------------ telemetry
    def attribution(self, k: int = 3):
        """Post-hoc straggler attribution (`repro.obs.attribution`) over
        the delay blocks this experiment materialized while telemetry was
        enabled (`repro.obs.spans.enable`): per-client deadline-miss
        rate, slowest-`k` contribution counts, and the coded-compensation
        data share per round.  Covers single-trajectory rounds computed
        in this process since the last `init_state`/`restore_state`.
        Raises `RuntimeError` when nothing was captured."""
        from repro.obs.attribution import attribution_from_blocks
        return attribution_from_blocks(
            self._attr_blocks, loads=self.loads, m=self.m,
            coded=self.scheme_obj.coded, k=k)

    def _drive(self, state: RunState, checkpoint_dir: Optional[str],
               eval_fn=None, eval_every: int = 10,
               journal=None) -> RunState:
        """Advance `state` to completion block by block, checkpointing
        each block boundary when a directory is given and journaling each
        block's rounds when a `RunJournal` is given (after the
        checkpoint, so the journal never runs ahead of durable state)."""
        while not state.done:
            state = self.run_block(state, eval_fn=eval_fn,
                                   eval_every=eval_every)
            if checkpoint_dir is not None:
                self.save_state(
                    os.path.join(
                        checkpoint_dir,
                        f"{ckpt_io.CKPT_PREFIX}{state.rounds_done:06d}.npz"),
                    state)
            if journal is not None:
                journal.sync(self, state)
        return state

    # ---------------------------------------------------------- legacy engine
    def _run_legacy(self, iterations: int, times_all: np.ndarray,
                    lrs: np.ndarray, eval_fn, eval_every: int) -> FedResult:
        """Original per-client Python loop — the numerical oracle the batched
        engine is tested against (same pre-sampled delays, same trajectory)."""
        theta = jnp.zeros((self.q, self.c), jnp.float32)
        wall = self.setup_time
        history: list[RoundLog] = []
        n_wait = self.n_wait

        for it in range(iterations):
            times = times_all[it]
            if self.step_kind == "naive":
                returned = np.ones(self.n, dtype=bool)
                t_round = float(np.max(times))
                denom = self.m
            elif self.step_kind == "greedy":
                order = np.argsort(times)
                returned = np.zeros(self.n, dtype=bool)
                returned[order[:n_wait]] = True
                t_round = float(times[order[n_wait - 1]])
                denom = int(returned.sum()) * self.l
            elif self.step_kind == "coded":
                returned = times <= self.t_star
                t_round = float(self.t_star)
                denom = self.m
            elif self.step_kind == "ideal":
                returned = np.ones(self.n, dtype=bool)
                t_round = float(self.t_ideal)
                denom = self.m
            else:
                raise ValueError(self.step_kind)

            # gradients
            if self.step_kind == "coded":
                grads = []
                for j in range(self.n):
                    if returned[j] and self.loads[j] > 0:
                        grads.append(aggregation.client_gradient(
                            self._sub_x[j], self._sub_y[j], theta))
                coded_g = aggregation.coded_gradient(
                    self.parity.x, self.parity.y, theta, pnr_c=0.0)
                total = coded_g
                for g in grads:
                    total = total + g
                g_m = total / denom + self.train.l2_reg * theta
            else:
                g_all = _batched_client_grads_jit(self.x, self.y, theta)
                g_m = aggregation.masked_gradient_sum(g_all, returned) / denom \
                    + self.train.l2_reg * theta

            theta = theta - float(lrs[it]) * g_m
            wall += t_round

            if eval_fn is not None and (it % eval_every == 0 or it == iterations - 1):
                loss, acc = eval_fn(theta)
            else:
                loss, acc = float("nan"), float("nan")
            history.append(RoundLog(it, wall, int(returned.sum()), loss, acc))

        return FedResult(theta=theta, history=history, t_star=self.t_star,
                         loads=self.loads, setup_time=self.setup_time,
                         privacy_eps=self.privacy_eps)

    # ------------------------------------------------------------------- runs
    def run(self, iterations: int,
            eval_fn: Optional[Callable[[jnp.ndarray], tuple[float, float]]] = None,
            eval_every: int = 10, *, checkpoint_dir: Optional[str] = None,
            resume: bool = False,
            journal_dir: Optional[str] = None) -> FedResult:
        """Run `iterations` rounds as a chain of `run_block` calls over
        the cached compiled scan: block size = ``spec.checkpoint_every``
        rounds, or the whole horizon when 0 (which reproduces the
        historical one-shot trajectories bit-for-bit).  With a channel
        profile the delays flow through the network trace (and the
        adaptive controller's schedule) instead — still one compiled
        scan per block.

        ``checkpoint_dir`` writes an atomic `RunState` checkpoint at
        every block boundary; ``resume=True`` restores the latest one
        there (if any) and continues, bit-identical to the uninterrupted
        blocked run.  ``journal_dir`` appends one `repro.obs` event per
        round to ``<journal_dir>/events.jsonl`` at the same boundaries —
        on resume the journal is trimmed/regrown to match the restored
        state, so an interrupted run's journal is always extended, never
        corrupted.
        """
        if self.engine == "legacy" and self.channel is None:
            if checkpoint_dir is not None or resume:
                raise ValueError(
                    "checkpointing requires the batched engine; the legacy "
                    "per-client oracle has no block-structured run state")
            if journal_dir is not None:
                raise ValueError(
                    "journal_dir requires the batched engine; the legacy "
                    "per-client oracle has no RunState to journal from")
            times = self._sample_round_times(iterations)
            lrs = self._lr_schedule(iterations)
            return self._run_legacy(iterations, times, lrs, eval_fn,
                                    eval_every)
        state = None
        if resume:
            if checkpoint_dir is None:
                raise ValueError("resume=True requires checkpoint_dir")
            latest = ckpt_io.latest_checkpoint(checkpoint_dir,
                                               valid_only=True)
            if latest is not None:
                state = self.restore_state(latest)
                if state.mode != "single":
                    raise ValueError(
                        f"checkpoint {latest!r} holds a {state.mode!r} "
                        "run; resume it with run_multi")
                if state.iterations != int(iterations):
                    raise ValueError(
                        f"checkpoint {latest!r} is a {state.iterations}-"
                        f"round run; this run asked for {iterations}")
                if state.collect != (eval_fn is not None):
                    raise ValueError(
                        f"checkpoint {latest!r} was saved with collect="
                        f"{state.collect}; pass a matching eval_fn")
        if state is None:
            state = self.init_state(iterations,
                                    collect=eval_fn is not None)
        journal = None
        if journal_dir is not None:
            from repro.obs.events import RunJournal
            journal = RunJournal(journal_dir)
            # trim past the restored state (a journal ahead of a rolled-
            # back checkpoint replays from authoritative state), then
            # regrow whatever prefix the state already carries
            journal.reset_to(state.rounds_done)
            journal.sync(self, state)
        state = self._drive(state, checkpoint_dir, eval_fn, eval_every,
                            journal=journal)
        return self.finish(state)

    def run_multi(self, iterations: int, n_realizations: int,
                  eval_fn: Optional[Callable[[jnp.ndarray],
                                             tuple[float, float]]] = None,
                  *, checkpoint_dir: Optional[str] = None,
                  resume: bool = False) -> MultiFedResult:
        """R independent delay realizations of the same deployment.

        One vmapped scan call per block produces the full
        (R, iterations) wall-clock / return-count surface — mean ± std
        over axis 0 is the Fig. 4/5 curve with its confidence band
        (`MultiFedResult.wall_clock_bands`).  With
        ``spec.checkpoint_every == 0`` the whole run is one block, i.e.
        one compiled call, exactly as before.

        Always runs on the batched scan engine (the legacy oracle has no
        vmappable form); the `engine` constructor argument only selects
        the `run()` path.  The final-iterate eval is vmapped over the
        realization axis when `eval_fn` is jax-traceable, falling back
        to a per-realization Python loop otherwise.  Channel-profile
        runs advance one full realization (fresh trace stream) per block
        over one shared compiled scan instead — checkpoints then land at
        realization, not round, granularity.

        ``checkpoint_dir``/``resume`` checkpoint and restore the run at
        block boundaries exactly like `run`.
        """
        state = None
        if resume:
            if checkpoint_dir is None:
                raise ValueError("resume=True requires checkpoint_dir")
            latest = ckpt_io.latest_checkpoint(checkpoint_dir,
                                               valid_only=True)
            if latest is not None:
                state = self.restore_state(latest)
                if state.mode == "single":
                    raise ValueError(
                        f"checkpoint {latest!r} holds a single run; "
                        "resume it with run()")
                if (state.iterations != int(iterations)
                        or int(state.n_realizations)
                        != int(n_realizations)):
                    raise ValueError(
                        f"checkpoint {latest!r} is a {state.iterations}-"
                        f"round x {state.n_realizations}-realization run; "
                        f"this run asked for {iterations} x "
                        f"{n_realizations}")
        if state is None:
            state = self.init_state(iterations,
                                    n_realizations=n_realizations)
        state = self._drive(state, checkpoint_dir)
        return self.finish(state, eval_fn)

    # ------------------------------------------------------------------ sweep
    def sweep(self, *, profiles: dict, iterations: int, realizations: int,
              schemes: Optional[tuple] = None):
        """Sweep this experiment's data over heterogeneity profiles.

        Convenience front-end over `repro.launch.sweep.run_sweep` — the
        same spec (scheme, backends, training config) is replayed across
        `profiles` ({name: FLConfig-override dict}) in ONE compiled
        (profile x realization) call per scheme.  `schemes` defaults to
        just this experiment's scheme.
        """
        from repro.launch import sweep as sweep_mod
        return sweep_mod.run_sweep(
            self.x, self.y, profiles=profiles, train_cfg=self.train,
            iterations=iterations, realizations=realizations,
            schemes=schemes or (self.scheme,), base_spec=self.spec)


class FederatedSimulation:
    """Removed.  The deprecated kwargs front-end over `Experiment` was a
    shim folding its arguments into a frozen `ExperimentSpec`; the two
    entrypoints shared one code path, so nothing is lost by migrating.
    The stub survives only to point stragglers at the replacement."""

    def __init__(self, *args, **kwargs):
        raise TypeError(
            "FederatedSimulation has been removed; build a frozen "
            "repro.config.ExperimentSpec and call "
            "repro.api.build_experiment(spec, x_stack, y_stack) instead")
