"""FL runtime mechanics (scheme semantics, determinism, logging)."""
import numpy as np
import pytest

from repro import api
from repro.config import ExperimentSpec, FLConfig, TrainConfig


def _sim(scheme, n=6, l=20, q=32, c=3, **fl_kw):
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(n, l, q)).astype(np.float32) * 0.2
    ys = rng.normal(size=(n, l, c)).astype(np.float32)
    fl = FLConfig(n_clients=n, **fl_kw)
    tc = TrainConfig(learning_rate=0.5, l2_reg=0.0)
    return api.build_experiment(
        ExperimentSpec(fl=fl, train=tc, scheme=scheme), xs, ys)


def test_naive_waits_for_all():
    sim = _sim("naive")
    res = sim.run(5)
    assert all(h.returned == 6 for h in res.history)


def test_greedy_waits_for_fraction():
    sim = _sim("greedy", psi=0.5)
    res = sim.run(5)
    assert all(h.returned == 3 for h in res.history)


def test_coded_setup_builds_parity():
    sim = _sim("coded", delta=0.2)
    assert sim.parity is not None
    assert sim.parity.x.shape[0] == sim.u
    assert sim.u == int(round(0.2 * 6 * 20))
    assert sim.setup_time > 0
    assert sim.t_star > 0


def test_coded_loads_leq_capacity():
    sim = _sim("coded", delta=0.3)
    assert np.all(sim.loads <= 20)
    assert np.all(sim.loads >= 0)


def test_wallclock_accumulates():
    sim = _sim("naive")
    res = sim.run(4)
    walls = [h.wall_clock for h in res.history]
    assert all(b > a for a, b in zip(walls, walls[1:]))


def test_theta_updates():
    sim = _sim("coded", delta=0.2)
    res = sim.run(3)
    assert float(np.abs(np.asarray(res.theta)).sum()) > 0


def test_secure_aggregation_identical_parity():
    """The spec's secure_aggregation flag routes parity uploads through
    mask_parity/secure_aggregate, and the masked aggregate equals the
    plain parity sum (pairwise masks cancel exactly in the sum)."""
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(6, 20, 32)).astype(np.float32) * 0.2
    ys = rng.normal(size=(6, 20, 3)).astype(np.float32)
    fl = FLConfig(n_clients=6, delta=0.2)
    tc = TrainConfig(learning_rate=0.5, l2_reg=0.0)
    plain = api.build_experiment(
        ExperimentSpec(fl=fl, train=tc, scheme="coded"), xs, ys)
    secure = api.build_experiment(
        ExperimentSpec(fl=fl, train=tc, scheme="coded",
                       secure_aggregation=True), xs, ys)
    assert secure.secure_aggregation and not plain.secure_aggregation
    np.testing.assert_allclose(np.asarray(plain.parity.x),
                               np.asarray(secure.parity.x), atol=1e-3)
    np.testing.assert_allclose(np.asarray(plain.parity.y),
                               np.asarray(secure.parity.y), atol=1e-3)
    # identical parity + identical delay stream => identical trajectories
    res_p = plain.run(5)
    res_s = secure.run(5)
    np.testing.assert_allclose(np.asarray(res_p.theta),
                               np.asarray(res_s.theta), atol=1e-4)


def test_loss_decreases_naive():
    rng = np.random.default_rng(1)
    n, l, q, c = 4, 30, 16, 2
    theta_true = rng.normal(size=(q, c)).astype(np.float32)
    xs = rng.normal(size=(n, l, q)).astype(np.float32) * 0.3
    ys = np.einsum("nlq,qc->nlc", xs, theta_true)
    fl = FLConfig(n_clients=n)
    tc = TrainConfig(learning_rate=2.0, l2_reg=0.0)
    sim = api.build_experiment(
        ExperimentSpec(fl=fl, train=tc, scheme="naive"), xs, ys)

    def eval_fn(theta):
        pred = np.einsum("nlq,qc->nlc", xs, np.asarray(theta))
        return float(np.mean((pred - ys) ** 2)), 0.0

    res = sim.run(50, eval_fn=eval_fn, eval_every=1)
    losses = [h.loss for h in res.history]
    assert losses[-1] < 0.1 * losses[0]


# ---------------------------------------------------------------------------
# the block driver's packed transfers against an unpacked reference
# ---------------------------------------------------------------------------

def _reference_block(exp, state, K, eval_fn):
    """One flat block played without packing: the scan inputs built leaf
    by leaf through `jnp.asarray` from the state's own streams, the scan
    over `build_step` jitted with consts as an argument, and each output
    fetched on its own.  Returns the host values the block appends."""
    import jax
    import jax.numpy as jnp
    from repro.core.delay_model import sample_round_times
    from repro.core.fed_runtime import build_step
    from repro.faults import inject as finject
    from repro.net.estimator import OnlineChannelEstimator, plan_segment
    from repro.net.trace import (generate_trace_block,
                                 sample_round_times_traced)

    r0 = state.rounds_done
    rng = np.random.default_rng()
    rng.bit_generator.state = state.rng_state
    lrs = exp._lr_schedule_range(r0, r0 + K)
    loads = np.asarray(exp.loads, float)
    consts = exp._get_consts()
    if exp.channel is None:
        times = sample_round_times(exp.nodes, loads, rng, K)
        xs = (jnp.asarray(times, jnp.float32), jnp.asarray(lrs, jnp.float32))
    else:
        trace_block, _ = generate_trace_block(exp.nodes, exp.channel, K,
                                              state.trace)
        if exp.adaptive:
            est = OnlineChannelEstimator(
                exp.nodes, **exp.scheme_params_estimator_kwargs())
            est.load_state_dict(state.est)
            seg = plan_segment(exp, est, trace_block, r0, r0 + K,
                               state.controls, rng)
            xs = (jnp.asarray(seg.times, jnp.float32), jnp.asarray(lrs),
                  jnp.asarray(seg.active))
            if exp.step_kind == "adaptive_coded":
                consts = dict(consts, gmask_blocks=seg.gmask_blocks)
                xs += (jnp.asarray(seg.t_star_r, jnp.float32),
                       jnp.asarray(seg.block_idx))
            else:
                xs += (jnp.asarray(seg.n_wait_r),)
        else:
            times = sample_round_times_traced(exp.nodes, loads, rng,
                                              trace_block)
            xs = (jnp.asarray(times, jnp.float32), jnp.asarray(lrs),
                  jnp.asarray(trace_block.active, jnp.float32))
    if exp.return_faults:
        frng = np.random.default_rng()
        frng.bit_generator.state = state.fault_rng_state
        fcodes, fpar = finject.sample_fault_rows(exp.faults, frng, K, exp.n)
        xs += (jnp.asarray(fcodes), jnp.asarray(fpar, jnp.float32))
    carry0 = (jnp.asarray(state.theta),
              jnp.asarray(np.asarray(state.lr_scale), jnp.float32))
    if exp.stale_faults:
        carry0 += (jnp.asarray(state.theta_prev),)
    step = build_step(exp.step_static(state.collect))
    carry, per_round = jax.jit(
        lambda consts, c0, xs: jax.lax.scan(
            lambda c, inp: step(consts, c, inp), c0, xs))(consts, carry0, xs)
    out = {"theta": np.asarray(carry[0]),
           "t_rounds": np.asarray(per_round[0], np.float64),
           "n_ret": np.asarray(per_round[1]),
           "n_masked": np.asarray(per_round[2], np.int64),
           "skipped": np.asarray(per_round[3], np.int64),
           "lr_scale": float(carry[1])}
    if exp.stale_faults:
        out["theta_prev"] = np.asarray(carry[2])
    if state.collect:
        out["losses"] = np.array([eval_fn(th)[0] for th in per_round[4]])
    return out


PACKED_CASES = {
    "coded": (dict(scheme="coded"), 8, False),
    "naive": (dict(scheme="naive"), 8, False),
    "channel": (dict(scheme="coded", channel_profile="drift_churn"), 8,
                False),
    "adaptive_coded": (dict(scheme="adaptive_coded",
                            channel_profile="drift_churn", adapt_every=2),
                       8, False),
    "adaptive_greedy": (dict(scheme="adaptive_greedy",
                             channel_profile="drift_churn", adapt_every=2),
                        8, False),
    "stale_faults": (dict(scheme="coded", fault_profile="byzantine_lite"),
                     8, False),
    "collect": (dict(scheme="coded"), 8, True),
    "short_last_block": (dict(scheme="coded"), 10, False),
}


def _same(a, b):
    """Equal dtype, shape and bits."""
    a, b = np.asarray(a), np.asarray(b)
    assert (a.dtype, a.shape) == (b.dtype, b.shape)
    assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("case", sorted(PACKED_CASES))
def test_packed_block_matches_unpacked_reference(case):
    over, iterations, collect = PACKED_CASES[case]
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(6, 16, 24)).astype(np.float32) * 0.2
    ys = rng.normal(size=(6, 16, 3)).astype(np.float32)
    spec = ExperimentSpec(
        fl=FLConfig(n_clients=6, delta=0.25, psi=0.3, seed=3),
        train=TrainConfig(learning_rate=0.5, l2_reg=1e-5,
                          lr_decay_epochs=(1,)),
        checkpoint_every=4, **over)
    exp = api.build_experiment(spec, xs, ys)

    def eval_fn(th):
        return float(np.abs(np.asarray(th)).sum()), 0.0

    state = exp.init_state(iterations, collect=collect)
    blocks = []
    while not state.done:
        r0 = state.rounds_done
        ref = _reference_block(exp, state, min(4, iterations - r0), eval_fn)
        state = exp.run_block(state, eval_fn=eval_fn if collect else None,
                              eval_every=1)
        blocks.append(state.rounds_done - r0)
        _same(state.theta, ref["theta"])
        for key in ("t_rounds", "n_ret", "n_masked", "skipped"):
            _same(getattr(state, key)[r0:], ref[key])
        assert type(state.lr_scale) is float
        assert state.lr_scale == ref["lr_scale"]
        if exp.stale_faults:
            _same(state.theta_prev, ref["theta_prev"])
        if collect:
            _same(state.losses[r0:], ref["losses"])
    assert blocks == ([4, 4, 2] if iterations == 10 else [4, 4])


def test_pack_inputs_round_trip_as_asarray():
    import jax
    import jax.numpy as jnp
    from repro.core.fed_runtime import _pack_inputs, _unpack_inputs
    rng = np.random.default_rng(1)
    leaves = (rng.normal(size=(3, 5)) * 1e3, rng.random(4).astype(np.float32),
              rng.integers(-9, 9, size=(2, 3)),
              rng.integers(0, 5, size=7).astype(np.int32),
              rng.random((2, 2)) < 0.5, 0.3, np.float32(-0.0))
    words, layout = _pack_inputs(leaves)
    assert words.dtype == np.int32
    got = jax.jit(lambda w: _unpack_inputs(w, layout))(jax.device_put(words))
    assert len(got) == len(leaves)
    for g, leaf in zip(got, leaves):
        _same(g, jnp.asarray(leaf))
