"""Declarative experiment API: ExperimentSpec round-trip, scheme registry,
and the removal of the FederatedSimulation shim.

The contract under test: (1) a spec survives spec -> dict -> JSON -> spec
bit-exactly, equal specs build bit-equal step constants, and a revived
spec produces IDENTICAL theta trajectories on both kernel backends;
(2) the removed kwargs constructor is a stub whose error points at the
spec entrypoint; (3) every registered scheme (including the new
partial-redundancy one) runs through `repro.api.build_experiment`.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.config import ExperimentSpec, FLConfig, TrainConfig
from repro.core import fed_runtime, schemes
from repro.core.delay_model import HETEROGENEITY_PROFILES


def _data(n=6, l=16, q=24, c=3, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(n, l, q)).astype(np.float32) * 0.2
    ys = rng.normal(size=(n, l, c)).astype(np.float32)
    return xs, ys


def _spec(scheme="coded", **over):
    base = dict(
        fl=FLConfig(n_clients=6, delta=0.25, psi=0.3, seed=3),
        train=TrainConfig(learning_rate=0.5, l2_reg=1e-5,
                          lr_decay_epochs=(5,)),
        scheme=scheme)
    base.update(over)
    return ExperimentSpec(**base)


# ---------------------------------------------------------------------------
# ExperimentSpec
# ---------------------------------------------------------------------------

def test_spec_json_round_trip_equality():
    spec = _spec("partial_coded", scheme_params={"u_fraction": 0.3},
                 delay_profile="paper", kernel_backend="pallas",
                 alloc_backend="scalar", mesh=2, fused_coded=False)
    revived = ExperimentSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert revived == spec
    assert hash(revived) == hash(spec)


def test_spec_round_trip_build_consts_bit_equal():
    """spec -> dict -> spec reproduces bit-equal step constants (the arrays
    the whole compiled run is a pure function of)."""
    xs, ys = _data()
    spec = _spec("coded")
    revived = ExperimentSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    c1 = api.build_experiment(spec, xs, ys).build_consts()
    c2 = api.build_experiment(revived, xs, ys).build_consts()
    assert set(c1) == set(c2)
    for key in c1:
        np.testing.assert_array_equal(np.asarray(c1[key]),
                                      np.asarray(c2[key]), err_msg=key)


def test_spec_validation_errors():
    with pytest.raises(ValueError, match="engine"):
        _spec(engine="warp")
    with pytest.raises(ValueError, match="kernel_backend"):
        _spec(kernel_backend="cuda")
    with pytest.raises(ValueError, match="alloc_backend"):
        _spec(alloc_backend="scipy")
    with pytest.raises(ValueError, match="delay_profile"):
        _spec(delay_profile="nonexistent")
    with pytest.raises(ValueError, match="mesh"):
        _spec(mesh=0)
    with pytest.raises(ValueError, match="steps_per_epoch"):
        _spec(steps_per_epoch=0)
    with pytest.raises(ValueError, match="unknown ExperimentSpec field"):
        ExperimentSpec.from_dict({"flux_capacitor": 1})


def test_spec_scheme_params_normalized_and_hashable():
    a = _spec("partial_coded", scheme_params={"u_fraction": 0.4, "z": 1})
    b = _spec("partial_coded", scheme_params=(("z", 1), ("u_fraction", 0.4)))
    assert a == b and hash(a) == hash(b)
    assert a.scheme_params_dict == {"u_fraction": 0.4, "z": 1}


def test_spec_delay_profile_overrides_fl():
    spec = _spec(delay_profile="extreme")
    fl = spec.resolved_fl()
    assert fl.rate_decay == HETEROGENEITY_PROFILES["extreme"]["rate_decay"]
    assert fl.mac_decay == HETEROGENEITY_PROFILES["extreme"]["mac_decay"]
    # equivalent to overriding the FLConfig fields by hand
    xs, ys = _data()
    by_profile = api.build_experiment(spec, xs, ys).run(4)
    import dataclasses
    manual = _spec(fl=dataclasses.replace(
        spec.fl, **HETEROGENEITY_PROFILES["extreme"]))
    by_fl = api.build_experiment(manual, xs, ys).run(4)
    np.testing.assert_array_equal(np.asarray(by_profile.theta),
                                  np.asarray(by_fl.theta))


def test_unknown_scheme_rejected_at_build_time():
    xs, ys = _data()
    with pytest.raises(ValueError, match="unknown scheme"):
        api.build_experiment(_spec("fountain_coded"), xs, ys)


def test_experiment_rejects_non_spec():
    xs, ys = _data()
    with pytest.raises(TypeError, match="ExperimentSpec"):
        fed_runtime.Experiment({"scheme": "coded"}, xs, ys)


def test_build_experiment_accepts_dict_spec():
    xs, ys = _data()
    exp = api.build_experiment(_spec("naive").to_dict(), xs, ys)
    assert exp.scheme == "naive"


# ---------------------------------------------------------------------------
# Removed shim + spec-path equivalence (acceptance criterion)
# ---------------------------------------------------------------------------

def test_removed_shim_raises_pointed_error():
    """The kwargs constructor is gone; the stub's error names the
    replacement entrypoint."""
    xs, ys = _data()
    with pytest.raises(TypeError, match="build_experiment"):
        fed_runtime.FederatedSimulation(
            xs, ys, FLConfig(n_clients=6), TrainConfig(), scheme="naive")


@pytest.mark.parametrize("kernel_backend", ["xla", "pallas"])
@pytest.mark.parametrize("scheme", ["coded", "naive", "greedy"])
def test_revived_spec_trajectory_identical(scheme, kernel_backend):
    """A spec revived from its serialized dict == the original spec,
    bit-for-bit, on both kernel backends (the trajectory is a pure
    function of the frozen spec — the equivalence the old shim tests
    pinned, now phrased without the removed kwargs path)."""
    xs, ys = _data()
    fl = FLConfig(n_clients=6, delta=0.25, psi=0.3, seed=3)
    tc = TrainConfig(learning_rate=0.5, l2_reg=1e-5, lr_decay_epochs=(5,))
    spec = ExperimentSpec(fl=fl, train=tc, scheme=scheme,
                          kernel_backend=kernel_backend)
    revived = ExperimentSpec.from_dict(
        json.loads(json.dumps(spec.to_dict())))
    trace = lambda th: (float(np.abs(np.asarray(th)).sum()), 0.0)
    res_old = api.build_experiment(spec, xs, ys).run(
        8, eval_fn=trace, eval_every=1)
    res_new = api.build_experiment(revived, xs, ys).run(
        8, eval_fn=trace, eval_every=1)
    np.testing.assert_array_equal(np.asarray(res_old.theta),
                                  np.asarray(res_new.theta))
    for ho, hn in zip(res_old.history, res_new.history):
        assert ho.returned == hn.returned
        assert ho.wall_clock == hn.wall_clock
        assert ho.loss == hn.loss


# ---------------------------------------------------------------------------
# Scheme registry + new schemes
# ---------------------------------------------------------------------------

def test_registry_contains_builtins_in_order():
    names = schemes.registered_names()
    assert set(("coded", "naive", "greedy", "ideal",
                "partial_coded")) <= set(names)
    assert set(schemes.coded_names()) >= {"coded", "partial_coded"}


def test_register_rejects_duplicates_and_bad_kinds():
    with pytest.raises(ValueError, match="already registered"):
        schemes.register(schemes.CodedScheme())

    class Nameless(schemes.Scheme):
        step_kind = "naive"

    with pytest.raises(ValueError, match="no name"):
        schemes.register(Nameless())

    class BadKind(schemes.Scheme):
        # a kind label alone is not a round: the scheme defines no rule
        name = "bad_kind"
        step_kind = "quantum"

    with pytest.raises(ValueError, match="round_rule"):
        schemes.register(BadKind())
    assert "bad_kind" not in schemes.registered_names()


class _MedianWait(schemes.Scheme):
    """A deadline no module outside this file knows: the median of the
    clients present."""
    name = "test_median_wait"

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


@pytest.fixture
def median_wait():
    scheme = schemes.register(_MedianWait())
    try:
        yield scheme
    finally:
        schemes.unregister(scheme.name)


def test_registered_round_rule_runs_on_every_path(median_wait):
    """A scheme registered with a new round rule runs through
    build_experiment, run, run_multi, run_sweep and attribution() with no
    edit outside this file: half of the clients (rounded up) make each
    round's deadline, on every path."""
    from repro.launch.sweep import run_sweep
    from repro.obs import spans as obs_spans

    xs, ys = _data()
    half = (xs.shape[0] + 1) // 2
    spec = _spec(median_wait.name, checkpoint_every=3)
    exp = api.build_experiment(spec, xs, ys)
    res = exp.run(6)
    assert [h.returned for h in res.history] == [half] * 6
    assert np.isfinite(np.asarray(res.theta)).all()

    multi = exp.run_multi(5, 3)
    np.testing.assert_array_equal(multi.returned, np.full((3, 5), half))

    sweep = run_sweep(xs, ys, profiles={"paper": {}, "slow": {"mac_decay": 0.7}},
                      train_cfg=spec.train, iterations=4, realizations=2,
                      schemes=(median_wait.name,), base_spec=spec)
    for got in sweep.results[median_wait.name].values():
        np.testing.assert_array_equal(got.returned, np.full((2, 4), half))

    with obs_spans.collecting():
        state = exp.init_state(6)
        while not state.done:
            state = exp.run_block(state)
        attr = exp.attribution()
    assert attr.deadline.tobytes() == state.t_rounds.tobytes()
    np.testing.assert_array_equal(attr.on_time, np.full(6, half))
    np.testing.assert_array_equal(state.n_ret, np.full(6, half))


@pytest.mark.parametrize("scheme", ["coded", "naive", "greedy", "ideal",
                                    "partial_coded"])
def test_every_registered_scheme_runs_via_build_experiment(scheme):
    xs, ys = _data()
    res = api.build_experiment(_spec(scheme), xs, ys).run(5)
    assert np.isfinite(np.asarray(res.theta)).all()
    assert res.history[-1].wall_clock > 0


def test_partial_coded_uses_fraction_of_redundancy():
    xs, ys = _data()
    full = api.build_experiment(_spec("coded"), xs, ys)
    half = api.build_experiment(_spec("partial_coded"), xs, ys)
    third = api.build_experiment(
        _spec("partial_coded", scheme_params={"u_fraction": 1.0 / 3.0}),
        xs, ys)
    assert half.u == max(1, round(0.5 * full.u))
    assert third.u < half.u < full.u
    # less parity shared -> a later deadline but a smaller privacy budget
    assert half.t_star >= full.t_star
    assert half.privacy_eps < full.privacy_eps
    with pytest.raises(ValueError, match="u_fraction"):
        api.build_experiment(
            _spec("partial_coded", scheme_params={"u_fraction": 1.5}),
            xs, ys)


def test_partial_coded_batched_matches_legacy_oracle():
    """The new scheme rides the same engines: batched scan == per-client
    Python oracle on the same pre-sampled delays."""
    xs, ys = _data()
    res = {}
    for engine in ("batched", "legacy"):
        exp = api.build_experiment(_spec("partial_coded", engine=engine),
                                   xs, ys)
        res[engine] = exp.run(10)
    np.testing.assert_allclose(np.asarray(res["batched"].theta),
                               np.asarray(res["legacy"].theta), atol=1e-5)
    for hb, hl in zip(res["batched"].history, res["legacy"].history):
        assert hb.returned == hl.returned
        np.testing.assert_allclose(hb.wall_clock, hl.wall_clock, rtol=1e-5)


def test_ideal_scheme_deterministic_floor():
    xs, ys = _data()
    ideal = api.build_experiment(_spec("ideal"), xs, ys)
    naive = api.build_experiment(_spec("naive"), xs, ys)
    res_i = ideal.run(6)
    res_n = naive.run(6)
    # same gradients (all clients, full load) -> identical trajectories
    np.testing.assert_allclose(np.asarray(res_i.theta),
                               np.asarray(res_n.theta), atol=1e-6)
    # deterministic round clock at the full-load floor
    walls = np.array([h.wall_clock for h in res_i.history])
    np.testing.assert_allclose(np.diff(walls), ideal.t_ideal, rtol=1e-6)
    assert res_n.history[-1].wall_clock >= res_i.history[-1].wall_clock
    # and run_multi realizations collapse onto one curve
    multi = ideal.run_multi(5, 3)
    _, std = multi.wall_clock_bands()
    np.testing.assert_allclose(std, 0.0, atol=1e-9)


def test_privacy_eps_wired_into_results():
    xs, ys = _data()
    coded = api.build_experiment(_spec("coded"), xs, ys)
    res = coded.run(3)
    multi = coded.run_multi(3, 2)
    from repro.core import privacy
    want = max(privacy.mi_dp_budget(np.asarray(xs[j]), coded.u)
               for j in range(xs.shape[0]))
    assert res.privacy_eps == pytest.approx(want)
    assert multi.privacy_eps == pytest.approx(want)
    assert api.build_experiment(_spec("naive"), xs, ys).run(3).privacy_eps \
        is None


def test_experiment_sweep_method_matches_run_multi():
    """Experiment.sweep flows through the same build_consts/build_step
    machinery as run_multi — equal seeds, equal results."""
    xs, ys = _data()
    profiles = {"uniform": dict(rate_decay=1.0, mac_decay=1.0),
                "paper": dict(rate_decay=0.95, mac_decay=0.8)}
    exp = api.build_experiment(_spec("coded"), xs, ys)
    sw = exp.sweep(profiles=profiles, iterations=6, realizations=2)
    assert set(sw.results["coded"]) == set(profiles)
    import dataclasses
    for pname, knobs in profiles.items():
        loop = api.build_experiment(
            _spec(fl=dataclasses.replace(exp.spec.fl, **knobs)),
            xs, ys).run_multi(6, 2)
        got = sw.results["coded"][pname]
        np.testing.assert_allclose(got.wall_clock, loop.wall_clock,
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(got.theta),
                                   np.asarray(loop.theta), atol=1e-5)


def test_mesh_in_spec_shards_like_mesh_kwarg():
    """spec.mesh (serializable device count) == Experiment mesh override."""
    import jax
    if jax.device_count() < 1:
        pytest.skip("no devices")
    k = jax.device_count()
    xs, ys = _data()
    by_spec = api.build_experiment(_spec("coded", mesh=k), xs, ys).run(4)
    by_override = api.build_experiment(_spec("coded"), xs, ys,
                                       mesh=k).run(4)
    unsharded = api.build_experiment(_spec("coded"), xs, ys).run(4)
    np.testing.assert_array_equal(np.asarray(by_spec.theta),
                                  np.asarray(by_override.theta))
    np.testing.assert_allclose(np.asarray(by_spec.theta),
                               np.asarray(unsharded.theta), atol=1e-5)


def test_step_static_exposes_step_kind():
    """Coded-family schemes play the same round rule; the registered
    object decides, not string comparison on the scheme name."""
    xs, ys = _data()
    partial = api.build_experiment(_spec("partial_coded"), xs, ys)
    assert partial.step_kind == "coded"
    assert partial.step_static()["scheme"] is schemes.get_scheme(
        "partial_coded")
    assert (type(partial.step_static()["scheme"]).round_rule
            is schemes.CodedScheme.round_rule)
    ideal = api.build_experiment(_spec("ideal"), xs, ys)
    assert ideal.step_static()["scheme"] is schemes.get_scheme("ideal")
    assert "t_ideal" in ideal.build_consts()
