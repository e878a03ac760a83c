"""Straggler-mitigation scheme registry (the paper's §V "Schemes").

Each scheme is a registered object that owns its deployment setup — load
allocation, parity construction, privacy accounting — its contributions
to the compiled step's consts and gradient tensors, and its per-round
rule: the round's deadline, who counts as returned, and the gradient
denominator (`Scheme.round_rule`).  `fed_runtime.build_step` calls the
rule and keeps only what every scheme shares (fault rows, the non-finite
guard, stale replay, the update and the divergence guard).  The runtime
(`repro.core.fed_runtime`), the compiled sweep (`repro.launch.sweep`),
and the benchmark grid (`repro.launch.bench`) all enumerate this
registry, so registering a scheme makes it runnable via
``repro.api.build_experiment`` and puts it in ``BENCH_fed_training.json``
with no edit elsewhere.

Built-in schemes:

  naive          — server waits for ALL n clients (full load).
  greedy         — server waits for the fastest (1-psi)*n clients.
  ideal          — deterministic no-straggler floor: full load, exact
                   compute, one transmission per direction.  Runnable
                   (same gradients as naive, deterministic wall-clock).
  coded          — CodedFedL: optimized loads l*_j + a global parity set
                   with redundancy u = delta * m; round time = t*.
  partial_coded  — coded with a *tunable fraction* of the redundancy
                   budget, u = u_fraction * delta * m (Prakash et al. /
                   Sun et al. style partial coding: less parity shared,
                   smaller privacy budget, weaker straggler cover).  The
                   fraction comes from ``ExperimentSpec.scheme_params``
                   ("u_fraction", default 0.5).

Registering your own — a new redundancy rule over the coded round::

    from repro.core import schemes

    class MyScheme(schemes.CodedScheme):
        name = "my_scheme"
        def u_budget(self, exp):
            return 7   # any redundancy rule

    schemes.register(MyScheme())

or a round with its own deadline, here the median of the clients
present::

    import jax.numpy as jnp

    class MedianWait(schemes.Scheme):
        name = "median_wait"

        def round_rule(self, st, consts, t_row, ctl):
            active = ctl[0] if ctl else jnp.ones_like(t_row)
            n_act = jnp.sum(active).astype(jnp.int32)
            srt = jnp.sort(jnp.where(active > 0, t_row, jnp.inf))
            k = jnp.clip((n_act + 1) // 2, 1, st["n"])
            t_round = jnp.where(n_act > 0, jnp.take(srt, k - 1), 0.0)
            ret = (t_row <= t_round).astype(t_row.dtype) * active
            n_ret = jnp.sum(ret).astype(jnp.int32)
            denom = jnp.maximum(n_ret, 1).astype(jnp.float32) * st["l"]
            return t_round, ret, n_ret, denom, consts["gmask"]

    schemes.register(MedianWait())
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encoding, load_allocation, privacy
from repro.core.delay_model import ideal_round_time, packet_bits
from repro.obs import spans as obs_spans


class Scheme:
    """Base scheme: full per-client loads, no parity, no deadline consts.

    Subclasses set ``name`` (registry key) and define `round_rule`.
    ``step_kind`` is a label naming the round a scheme plays, read only
    where a caller keeps its own copy of a rule: the per-client legacy
    oracle, the hierarchical tier and the model round, which refuse the
    kinds they do not play.  ``coded`` marks schemes that allocate loads
    and build a parity set (t_star / loads / parity / privacy budget).
    ``adaptive`` marks schemes that re-plan their controls between
    blocks (`replan`) and feed them to the step as per-round scan inputs
    (`segment_inputs`).  ``grid`` marks schemes that belong to the
    default profile-grid sweep/benchmark (`repro.launch.sweep.run_sweep`
    / `repro.launch.bench`); adaptive schemes opt out — they need a
    channel trace and a per-run control schedule, and are benched by the
    drift-scenario runner (`repro.launch.scenarios`) instead.
    """
    name: str = ""
    step_kind: str = ""
    coded: bool = False
    adaptive: bool = False
    grid: bool = True

    def round_rule(self, st, consts, t_row, ctl):
        """One round's outcome inside the compiled step (traced).

        `st` is the step's static dict (``n``, ``n_wait``, ``m``, ``l``,
        ...; `fed_runtime.Experiment.step_static`), `consts` the step's
        consts, `t_row` the round's (n,) f32 client delays.  `ctl` holds
        the round's control inputs after the learning rate: ``()`` on
        the stationary path, ``(active,)`` under a channel trace, and
        ``(active, *segment_inputs)`` for an adaptive scheme.

        Returns ``(t_round, ret_real, n_ret, denom, gmask)``: the round's
        clock, the (n,) returned weights of the real clients, the count
        the run reports as returned, the gradient-sum denominator, and
        the gradient mask of this round.
        """
        raise NotImplementedError

    def setup(self, exp) -> None:
        """Host-side deployment setup; mutates the Experiment in place."""

    def consts_point_len(self, exp) -> int:
        """Point-axis length of `grad_tensors`' gx — shape arithmetic only,
        so sweep callers can compute a grid-wide l_target cheaply."""
        return exp.l

    def grad_tensors(self, exp, l_target=None):
        """(gx, gy, gmask, ret_tail) — the dense client gradient tensors.

        ret_tail lists the returned-mask entries of any pseudo-client rows
        appended past the n real clients (mesh padding is applied by the
        caller on top).
        """
        gx, gy = exp.x, exp.y
        gmask = jnp.ones((exp.n, exp.l), exp.x.dtype)
        return gx, gy, gmask, []

    def extra_consts(self, exp) -> dict:
        """Scheme-specific entries of the step `consts` pytree."""
        return {}

    def privacy_budget(self, exp):
        """Worst-case eps-MI-DP leakage (bits) of what clients share, or
        None when nothing beyond gradients leaves the device."""
        return None

    def replan(self, exp, estimator) -> dict:
        """Adaptive-family hook: new control values from the estimated
        network (called by `repro.net.estimator.AdaptiveController`
        between blocks).  Returns a dict of updated control values
        ({"loads", "t_star"} for the coded family, {"n_wait"} for the
        greedy family); non-adaptive schemes never re-plan."""
        raise NotImplementedError(f"{self.name!r} is not adaptive")

    def segment_inputs(self, exp, seg) -> "tuple[tuple, dict]":
        """Adaptive-family hook: a planned segment's
        (`repro.net.estimator.SegmentPlan`) per-round scan inputs, in the
        order `round_rule` reads them after ``active``, and the consts
        they index."""
        raise NotImplementedError(f"{self.name!r} is not adaptive")

    def schedule_fields(self, exp, t_star_r, n_wait_r, loads_blocks) -> dict:
        """Adaptive-family hook: this scheme's fields of a run's
        `repro.net.estimator.AdaptiveSchedule`, from the planned
        per-round deadlines and wait counts and the per-block loads."""
        raise NotImplementedError(f"{self.name!r} is not adaptive")

    def initial_controls(self, exp) -> dict:
        """The scheme's control-plane contribution to a fresh `RunState`:
        the values in effect at round 0, updated by `replan` thereafter
        and carried across checkpoint boundaries.  Every scheme has a
        load vector and a wait count; the coded family additionally has
        its setup-time deadline (``t_star`` is None otherwise)."""
        return {"loads": np.asarray(exp.loads, np.float64).copy(),
                "t_star": exp.t_star, "n_wait": exp.n_wait}

    def __repr__(self):
        return f"<Scheme {self.name!r} step_kind={self.step_kind!r}>"


def _kth_present(t_row, active, k, n):
    """The deadline at the k-th fastest of the clients present (k clamped
    to [1, present]; 0.0 with nobody present) and who made it."""
    srt = jnp.sort(jnp.where(active > 0, t_row, jnp.inf))
    n_act = jnp.sum(active).astype(jnp.int32)
    k_eff = jnp.clip(jnp.minimum(k, n_act), 1, n)
    t_round = jnp.where(n_act > 0, jnp.take(srt, k_eff - 1), 0.0)
    return t_round, (t_row <= t_round).astype(t_row.dtype) * active


def _average_over_returned(ret_real, l):
    """n_ret and the denominator of a round that averages over the
    returned clients' full loads."""
    n_ret = jnp.sum(ret_real).astype(jnp.int32)
    return n_ret, jnp.maximum(n_ret, 1).astype(jnp.float32) * l


class NaiveScheme(Scheme):
    """Waits for every client present: the round lasts the slowest."""
    name = "naive"
    step_kind = "naive"

    def round_rule(self, st, consts, t_row, ctl):
        if ctl:
            active, = ctl
            ret_real = active
            n_ret = jnp.sum(active).astype(jnp.int32)
            t_round = jnp.max(jnp.where(active > 0, t_row, 0.0))
        else:
            n_ret = jnp.int32(st["n"])
            t_round = jnp.max(t_row)
            ret_real = jnp.ones_like(t_row)
        return t_round, ret_real, n_ret, st["m"], consts["gmask"]


class GreedyScheme(Scheme):
    """Waits for the fastest n_wait clients (of those present)."""
    name = "greedy"
    step_kind = "greedy"

    def round_rule(self, st, consts, t_row, ctl):
        if ctl:
            t_round, ret_real = _kth_present(
                t_row, ctl[0], jnp.int32(st["n_wait"]), st["n"])
        else:
            t_round = jnp.sort(t_row)[st["n_wait"] - 1]
            ret_real = (t_row <= t_round).astype(t_row.dtype)
        n_ret, denom = _average_over_returned(ret_real, st["l"])
        return t_round, ret_real, n_ret, denom, consts["gmask"]


class IdealScheme(Scheme):
    """Deterministic no-straggler baseline, now runnable end-to-end.

    Gradient-wise identical to naive (every client, full load); the round
    clock is the deterministic floor `delay_model.ideal_round_time` instead
    of the sampled max — so trajectories match naive's all-returned rounds
    while the wall-clock lower-bounds every full-load scheme.
    """
    name = "ideal"
    step_kind = "ideal"

    def setup(self, exp) -> None:
        exp.t_ideal = ideal_round_time(exp.nodes, float(exp.l))

    def extra_consts(self, exp) -> dict:
        return {"t_ideal": jnp.float32(exp.t_ideal)}

    def round_rule(self, st, consts, t_row, ctl):
        # every client present returns; the sampled t_row is ignored
        ret_real = ctl[0] if ctl else jnp.ones_like(t_row)
        n_ret = jnp.sum(ret_real).astype(jnp.int32)
        return consts["t_ideal"], ret_real, n_ret, st["m"], consts["gmask"]


class CodedScheme(Scheme):
    """CodedFedL (paper §III): optimized loads + global parity set.

    The parity set is also what makes the coded family *robust*: the
    MDS-style global parity gradient stands in for whatever client mass
    is missing from a round, whether that mass was lost to stragglers
    (the paper's case) or masked out by the step's non-finite guard
    (`fed_runtime._guard_and_sum` with fault injection, `repro.faults`).  A
    naive average has no such stand-in — masked returns simply shrink
    its effective batch, which is the coded-degrades-gracefully /
    naive-pays contrast the resilience benchmark records
    (`repro.launch.resilience`).
    """
    name = "coded"
    step_kind = "coded"
    coded = True

    def round_rule(self, st, consts, t_row, ctl):
        # the deadline is t*; a client returns by it if it holds a load
        # (consts["active"]), while n_ret counts every on-time client
        t_star = consts["t_star"]
        by_deadline = (t_row <= t_star).astype(t_row.dtype)
        ret_real = by_deadline * consts["active"]
        if ctl:
            active, = ctl
            by_deadline = by_deadline * active
            ret_real = ret_real * active
        n_ret = jnp.sum(by_deadline).astype(jnp.int32)
        return t_star, ret_real, n_ret, st["m"], consts["gmask"]

    # ------------------------------------------------------------ redundancy
    def u_budget(self, exp) -> int:
        """Parity rows u to build — the full paper budget delta * m."""
        return max(1, int(round(exp.fl.delta * exp.m)))

    # ----------------------------------------------------------------- setup
    def setup(self, exp) -> None:
        fl = exp.fl
        u_max = self.u_budget(exp)
        allocate = (load_allocation.two_step_allocate_vectorized
                    if exp._pick_alloc_backend() == "vectorized"
                    else load_allocation.two_step_allocate)
        with obs_spans.span("solver/two_step"):
            alloc = allocate(
                exp.nodes, [float(exp.l)] * exp.n, server=None,
                u_max=float(u_max), m=float(exp.m))
        exp.t_star = alloc.t_star
        exp.u = u_max
        # integer loads (floor, at least 0)
        exp.loads = np.minimum(np.floor(alloc.loads).astype(int), exp.l)
        # probability of return by t* per client at its optimal load
        exp.p_return = np.array([
            nd.cdf(exp.t_star, float(ld)) if ld > 0 else 0.0
            for nd, ld in zip(exp.nodes, exp.loads)])
        if exp.lm is not None:
            # a model round (repro.core.lm_round) uses the deadline and
            # loads alone: each client's load is the prefix of its
            # sequence, and no parity set is built (parity is linear only)
            return
        # Processed-subset sampling v2 (vectorized): one `rng.permuted` draw
        # over an (n, l) index matrix replaces the per-client
        # `rng.permutation` loop.  This consumes the numpy RNG stream
        # differently from v1 (so subsets differ across versions — pinned by
        # tests/test_batched_engine.py::test_vectorized_subset_sampling_spec)
        # but stays fully deterministic per seed.
        perm = exp.rng.permuted(
            np.tile(np.arange(exp.l), (exp.n, 1)), axis=1)
        # selection-priority order: point perm[j, k] is the k-th point
        # client j would process — the adaptive family re-masks prefixes
        # of this order when it re-allocates loads
        exp._select_perm = perm
        take = np.arange(exp.l)[None, :] < exp.loads[:, None]   # (n, l)
        processed = np.zeros((exp.n, exp.l), dtype=bool)
        row_ids = np.broadcast_to(np.arange(exp.n)[:, None],
                                  (exp.n, exp.l))
        processed[row_ids[take], perm[take]] = True
        exp.processed_idx = [np.nonzero(processed[j])[0]
                             for j in range(exp.n)]
        # weight matrices (paper §III-D) for the whole population at once:
        # sqrt(1 - P(return)) on processed points, 1 elsewhere
        w_stack = np.where(processed,
                           np.sqrt(1.0 - exp.p_return)[:, None],
                           1.0).astype(np.float32)
        # per-client PRNG keys: same sequential split chain the per-client
        # encode would consume, rolled up into one lax.scan
        def _chain(key, _):
            key, sub = jax.random.split(key)
            return key, sub
        _, keys = jax.lax.scan(_chain, jax.random.PRNGKey(fl.seed + 99),
                               None, length=exp.n)
        # all n local parity sets in one batched encode (paper eq. 19) —
        # one vmapped jnp call or one tiled Pallas kernel launch.  In
        # fused_embed mode the clients hold RAW features; parity encoding
        # happens over on-the-fly embeds (a transient (n, l, q) stack that
        # lives only for this setup step — the round path never sees it)
        x_enc = exp.embedded_x() if exp.fused_embed else exp.x
        with obs_spans.span("encode/parity"):
            stacked = encoding.encode_local_batched(
                keys, x_enc, exp.y, w_stack, exp.u,
                use_pallas=exp.kernel_backend == "pallas",
                interpret=exp._interpret)
            if exp.secure_aggregation:
                # paper §VI future work: the server only ever sees masked
                # uploads; pairwise masks cancel in the sum
                # (core/secure_agg.py)
                from repro.core import secure_agg
                skey = jax.random.PRNGKey(fl.seed + 1234)
                masked = [secure_agg.mask_parity(
                    skey, j, exp.n,
                    encoding.LocalParity(x=stacked.x[j], y=stacked.y[j]))
                    for j in range(exp.n)]
                exp.parity = secure_agg.secure_aggregate(masked)
            else:
                exp.parity = encoding.aggregate_parity_stacked(stacked)
            if obs_spans.enabled():
                jax.block_until_ready((exp.parity.x, exp.parity.y))
        # one-time parity upload overhead: clients upload u*(q+c) scalars in
        # parallel; expected transmissions 1/(1-p) (paper Fig 4a inset).
        # NodeDelayParams validates p < 1 at construction, so the expected
        # transmission count is finite here by contract.
        bits = packet_bits(fl, exp.u * (exp.q + exp.c))
        exp.setup_time = max(
            nd.tau / packet_bits(fl, exp.q * exp.c) * bits / (1.0 - nd.p)
            for nd in exp.nodes)
        # ragged per-client subsets: only the legacy oracle reads them
        if exp.engine == "legacy":
            exp._sub_x = [exp.x[j][exp.processed_idx[j]]
                          for j in range(exp.n)]
            exp._sub_y = [exp.y[j][exp.processed_idx[j]]
                          for j in range(exp.n)]
        # dense mask-padded (n, l_max, ·) view: the chosen indices of each
        # row, sorted ascending, with unchosen slots pushed past the end by
        # an `l` sentinel — vectorized replacement for the per-client
        # pad/gather loop
        l_max = max(1, int(exp.loads.max()))
        sorted_idx = np.sort(np.where(take, perm, exp.l), axis=1)[:, :l_max]
        pad_mask = (sorted_idx < exp.l).astype(np.float32)
        pad_idx = np.where(sorted_idx < exp.l, sorted_idx, 0).astype(np.int32)
        rows = jnp.asarray(pad_idx)
        mask = jnp.asarray(pad_mask)[:, :, None]
        gather = jax.vmap(lambda xj, ij: xj[ij])
        exp._sub_x_pad = gather(exp.x, rows) * mask
        exp._sub_y_pad = gather(exp.y, rows) * mask
        exp._grad_mask = jnp.asarray(pad_mask)       # (n, l_max) row validity

    # ------------------------------------------------------------ step consts
    def consts_point_len(self, exp) -> int:
        l_max = int(exp._sub_x_pad.shape[1])
        return max(l_max, exp.u) if exp.fused_coded else l_max

    def grad_tensors(self, exp, l_target=None):
        from repro.core import aggregation
        if exp.fused_coded:
            if exp.fused_embed:
                # raw-space client rows; the embedded parity block goes in
                # as a separate `pphi` const the fused kernel reads on the
                # parity grid row (stashed here so `extra_consts` — which
                # has no l_target — ships the matching padded view)
                gx, gy, gmask, pphi = \
                    aggregation.fused_embed_client_parity_tensors(
                        exp._sub_x_pad, exp._sub_y_pad, exp._grad_mask,
                        exp.parity.x, exp.parity.y, pnr_c=0.0,
                        l_target=l_target)
                exp._pphi_const = pphi
            else:
                gx, gy, gmask = aggregation.fused_client_parity_tensors(
                    exp._sub_x_pad, exp._sub_y_pad, exp._grad_mask,
                    exp.parity.x, exp.parity.y, pnr_c=0.0,
                    l_target=l_target)
            tail = [1.0]          # the always-active parity pseudo-row
        else:
            gx, gy, gmask = (exp._sub_x_pad, exp._sub_y_pad,
                             exp._grad_mask)
            if l_target is not None and l_target > gx.shape[1]:
                pad = ((0, 0), (0, l_target - gx.shape[1]))
                gx = jnp.pad(gx, pad + ((0, 0),))
                gy = jnp.pad(gy, pad + ((0, 0),))
                gmask = jnp.pad(gmask, pad)
            tail = []
        return gx, gy, gmask, tail

    def extra_consts(self, exp) -> dict:
        consts = {
            "t_star": jnp.float32(exp.t_star),
            "active": jnp.asarray(exp.loads > 0, jnp.float32),
        }
        if exp.fused_coded and exp.fused_embed:
            consts["pphi"] = exp._pphi_const
        if not exp.fused_coded:
            consts["par_x"] = exp.parity.x
            consts["par_y"] = exp.parity.y
        return consts

    # --------------------------------------------------------------- privacy
    def privacy_budget(self, exp) -> float:
        """Worst-client eps-MI-DP budget (bits) of sharing u parity rows
        (paper Appendix F, eq. 62).  What leaks is the EMBEDDED data the
        parity rows are built from, so fused_embed runs account over the
        same transient embeds the parity encode consumed."""
        x_src = exp.embedded_x() if exp.fused_embed else exp.x
        return float(max(
            privacy.mi_dp_budget(np.asarray(x_src[j]), exp.u)
            for j in range(exp.n)))


class PartialCodedScheme(CodedScheme):
    """Coded with a tunable fraction of the redundancy budget.

    u = u_fraction * delta * m, u_fraction in (0, 1] — the partial/
    stochastic-coding regime of Prakash et al. (*Coded Computing for
    Federated Learning at the Edge*) and Sun et al. (*Stochastic Coded
    Federated Learning*): smaller parity uploads (cheaper setup, smaller
    eps-MI-DP leakage) against a later optimal deadline t*.
    """
    name = "partial_coded"
    default_u_fraction = 0.5

    def u_fraction(self, exp) -> float:
        frac = float(exp.scheme_params.get("u_fraction",
                                           self.default_u_fraction))
        if not 0.0 < frac <= 1.0:
            raise ValueError(
                f"u_fraction must lie in (0, 1], got {frac}")
        return frac

    def u_budget(self, exp) -> int:
        return max(1, int(round(self.u_fraction(exp)
                                * exp.fl.delta * exp.m)))


class AdaptiveCodedScheme(CodedScheme):
    """CodedFedL with blockwise load re-allocation under network drift.

    Static CodedFedL solves the two-step allocation ONCE from the nominal
    (round-0) delay statistics; when the network drifts (Dhakal et al.
    2020, Sun et al. 2022 both flag this), the fixed deadline t* either
    wastes wall-clock on a network that got faster or bleeds return mass
    on one that got slower.  This scheme re-solves the allocation every
    ``ExperimentSpec.adapt_every`` rounds on the *estimated* network
    (`repro.net.estimator`), applying the new loads as prefix-mask
    re-weightings over a full-length fused client tensor — shapes (and
    the compiled step) never change.

    The parity set stays the one built at setup from the initial
    allocation: re-uploading parity every block would re-pay the setup
    cost the coding exists to amortize, so the §III-D expected-miss
    weights are an approximation away from the re-allocated loads (the
    same approximation a deployed system would make).

    ``scheme_params`` knobs: ``est_beta`` (EWMA factor, default 0.25),
    ``est_window`` (switch to windowed-MLE estimation), ``avail_min``
    (availability score below which a client gets no load, default 0.5).
    """
    name = "adaptive_coded"
    step_kind = "adaptive_coded"
    adaptive = True
    grid = False

    def round_rule(self, st, consts, t_row, ctl):
        # the block's re-planned deadline, and the index of its
        # re-allocated fused load mask
        active, t_star_r, block = ctl
        ret_real = (t_row <= t_star_r).astype(t_row.dtype) * active
        n_ret = jnp.sum(ret_real).astype(jnp.int32)
        gmask = consts["gmask_blocks"][block]
        return t_star_r, ret_real, n_ret, st["m"], gmask

    def segment_inputs(self, exp, seg):
        return ((seg.t_star_r, seg.block_idx),
                {"gmask_blocks": self._gmask_blocks(exp, seg.loads_blocks)})

    def schedule_fields(self, exp, t_star_r, n_wait_r, loads_blocks):
        return {"t_star": t_star_r,
                "gmask_blocks": self._gmask_blocks(exp, loads_blocks)}

    def setup(self, exp) -> None:
        if not exp.fused_coded:
            raise ValueError(
                "adaptive_coded requires fused_coded=True (re-allocation "
                "re-weights the fused client+parity mask)")
        if exp.fused_embed:
            raise NotImplementedError(
                "adaptive_coded does not support fused_embed yet (the "
                "per-block gmask re-weighting assumes embedded tensors)")
        super().setup(exp)
        # full-length priority view: every client's points in selection-
        # priority order, so ANY re-allocated load l_j <= l is a prefix
        # mask of the same (n, l) tensor
        perm = jnp.asarray(exp._select_perm)
        gather = jax.vmap(lambda xj, ij: xj[ij])
        exp._adapt_x = gather(exp.x, perm)
        exp._adapt_y = gather(exp.y, perm)

    # ------------------------------------------------------------ step consts
    def consts_point_len(self, exp) -> int:
        return max(exp.l, exp.u)

    def grad_tensors(self, exp, l_target=None):
        from repro.core import aggregation
        # full-length tensors; the per-block prefix mask (not baked into
        # the data) selects the processed points — linreg_grad_masked
        # tolerates un-zeroed padding by contract
        gx, gy, gmask = aggregation.fused_client_parity_tensors(
            exp._adapt_x, exp._adapt_y,
            jnp.asarray(self._prefix_mask(exp, exp.loads)),
            exp.parity.x, exp.parity.y, pnr_c=0.0, l_target=l_target)
        return gx, gy, gmask, [1.0]

    @staticmethod
    def _prefix_mask(exp, loads) -> np.ndarray:
        """(n, l) float32 prefix mask over the priority order."""
        loads = np.asarray(loads)
        return (np.arange(exp.l)[None, :]
                < loads[:, None]).astype(np.float32)

    def gmask_for_loads(self, exp, loads) -> jnp.ndarray:
        """(n+1, L) fused mask for a load vector: client prefix rows plus
        the 1/u-scaled parity pseudo-row — the mask-re-weighting unit the
        adaptive step indexes per block."""
        L = max(exp.l, exp.u)
        mask = np.zeros((exp.n + 1, L), np.float32)
        mask[:exp.n, :exp.l] = self._prefix_mask(exp, loads)
        mask[exp.n, :exp.u] = 1.0 / exp.u
        return jnp.asarray(mask)

    def _gmask_blocks(self, exp, loads_blocks) -> jnp.ndarray:
        """(B, n+1, L) fused masks, one per block's load vector."""
        return jnp.stack([self.gmask_for_loads(exp, loads)
                          for loads in loads_blocks])

    # ----------------------------------------------------------------- replan
    def replan(self, exp, estimator) -> dict:
        from repro.core import load_allocation
        est_nodes = estimator.estimated_nodes()
        avail_min = float(exp.scheme_params.get("avail_min", 0.5))
        caps = np.where(estimator.avail_hat >= avail_min, float(exp.l), 0.0)
        allocate = (load_allocation.two_step_allocate_vectorized
                    if exp._pick_alloc_backend() == "vectorized"
                    else load_allocation.two_step_allocate)
        with obs_spans.span("solver/two_step"):
            try:
                alloc = allocate(est_nodes, list(caps), server=None,
                                 u_max=float(exp.u), m=float(exp.m))
            except ValueError:
                # too many clients estimated unavailable for feasibility:
                # fall back to full caps rather than keep a stale plan
                alloc = allocate(est_nodes, [float(exp.l)] * exp.n,
                                 server=None, u_max=float(exp.u),
                                 m=float(exp.m))
        loads = np.minimum(np.floor(alloc.loads).astype(int), exp.l)
        return {"loads": loads, "t_star": float(alloc.t_star)}


class AdaptiveGreedyScheme(GreedyScheme):
    """Greedy waiting with an adaptively re-tuned wait count.

    Static greedy always waits for the fastest ``(1 - psi) n`` clients.
    Under drift/churn the right count changes: this scheme re-picks, every
    ``adapt_every`` rounds, the k maximizing expected returned data per
    second — ``argmin_k E[T]_(k) / k`` over the *estimated* per-client
    expected delays, restricted to clients whose availability score
    clears ``avail_min`` (default 0.5).
    """
    name = "adaptive_greedy"
    step_kind = "adaptive_greedy"
    adaptive = True
    grid = False

    def round_rule(self, st, consts, t_row, ctl):
        active, n_wait_r = ctl          # the block's re-tuned wait count
        t_round, ret_real = _kth_present(t_row, active, n_wait_r, st["n"])
        n_ret, denom = _average_over_returned(ret_real, st["l"])
        return t_round, ret_real, n_ret, denom, consts["gmask"]

    def segment_inputs(self, exp, seg):
        return (seg.n_wait_r,), {}

    def schedule_fields(self, exp, t_star_r, n_wait_r, loads_blocks):
        return {"n_wait": n_wait_r}

    def replan(self, exp, estimator) -> dict:
        est_nodes = estimator.estimated_nodes()
        avail_min = float(exp.scheme_params.get("avail_min", 0.5))
        avail = estimator.avail_hat >= avail_min
        if not np.any(avail):
            return {"n_wait": 1}
        exp_delay = np.array([nd.expected_delay(float(exp.l))
                              for nd in est_nodes])
        srt = np.sort(np.where(avail, exp_delay, np.inf))
        k = np.arange(1, exp.n + 1, dtype=np.float64)
        with np.errstate(invalid="ignore"):
            cost = np.where(np.isfinite(srt), srt / k, np.inf)
        return {"n_wait": int(np.argmin(cost)) + 1}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Scheme] = {}


def register(scheme: Scheme, *, overwrite: bool = False) -> Scheme:
    """Register a Scheme instance under its ``name``.

    Everything downstream — ``repro.api.build_experiment``, the compiled
    sweep, the benchmark grid/artifact — enumerates this registry.
    """
    if not scheme.name:
        raise ValueError(f"{scheme!r} has no name")
    if type(scheme).round_rule is Scheme.round_rule:
        raise ValueError(f"scheme {scheme.name!r} defines no round_rule")
    if scheme.name in _REGISTRY and not overwrite:
        raise ValueError(f"scheme {scheme.name!r} already registered "
                         "(pass overwrite=True to replace)")
    _REGISTRY[scheme.name] = scheme
    return scheme


def unregister(name: str) -> None:
    _REGISTRY.pop(name, None)


def get_scheme(name: str) -> Scheme:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown scheme {name!r} (registered: "
                         f"{registered_names()})") from None


def registered_names() -> tuple[str, ...]:
    """All registered scheme names, in registration order."""
    return tuple(_REGISTRY)


def coded_names() -> tuple[str, ...]:
    """Names of the coded-family schemes (parity + load allocation)."""
    return tuple(n for n, s in _REGISTRY.items() if s.coded)


def grid_names() -> tuple[str, ...]:
    """Schemes belonging to the default profile-grid sweep/benchmark
    (adaptive schemes opt out — see `Scheme.grid`)."""
    return tuple(n for n, s in _REGISTRY.items() if s.grid)


register(CodedScheme())
register(NaiveScheme())
register(GreedyScheme())
register(IdealScheme())
register(PartialCodedScheme())
register(AdaptiveCodedScheme())
register(AdaptiveGreedyScheme())
