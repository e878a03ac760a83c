"""Client-mesh (shard_map + psum) engine vs. the single-device batched engine.

The mesh mode partitions the dense client tensor over a `clients` axis,
computes per-shard gradients locally, and psum-aggregates — the device-level
mirror of the paper's MEC server aggregation.  With equal seeds it must
reproduce the single-device trajectory to fp32 tolerance at ANY device
count; padding rows injected to make the client axis divisible carry an
all-zero mask and must contribute exactly nothing.

Runs meaningfully under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the `multidevice`
CI job); with fewer host devices the higher device counts skip.
"""
import jax
import numpy as np
import pytest

from repro import api
from repro.config import ExperimentSpec, FLConfig, TrainConfig
from repro.launch.mesh import make_client_mesh

pytestmark = pytest.mark.multidevice

DEVICE_COUNTS = (1, 2, 4, 8)


def _data(n=8, l=24, q=32, c=3, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(n, l, q)).astype(np.float32) * 0.2
    ys = rng.normal(size=(n, l, c)).astype(np.float32)
    return xs, ys


def _sim(xs, ys, scheme, mesh=None, **spec_kw):
    fl = FLConfig(n_clients=xs.shape[0], delta=0.25, psi=0.3, seed=3)
    tc = TrainConfig(learning_rate=0.5, l2_reg=1e-4, lr_decay_epochs=(10, 18))
    spec = ExperimentSpec(fl=fl, train=tc, scheme=scheme, **spec_kw)
    # mesh goes through the build_experiment override so tests can pass a
    # concrete Mesh object (not spec-serializable) as well as a count
    return api.build_experiment(spec, xs, ys, mesh=mesh)


def _skip_unless(ndev):
    if jax.device_count() < ndev:
        pytest.skip(f"needs {ndev} devices, have {jax.device_count()} "
                    "(set XLA_FLAGS=--xla_force_host_platform_device_count)")


@pytest.mark.parametrize("ndev", DEVICE_COUNTS)
@pytest.mark.parametrize("scheme", ["naive", "greedy", "coded"])
def test_mesh_matches_single_device_trajectory(scheme, ndev):
    """Same seeds => same theta trajectory and history at every mesh size.

    n=8 divides evenly at every count here; the zero-row padding path is
    covered by test_mesh_pads_indivisible_client_axis (6 clients over 4
    devices)."""
    _skip_unless(ndev)
    xs, ys = _data()
    trace = lambda th: (float(np.abs(np.asarray(th)).sum()), 0.0)
    res_1 = _sim(xs, ys, scheme).run(20, eval_fn=trace, eval_every=1)
    res_m = _sim(xs, ys, scheme, mesh=ndev).run(20, eval_fn=trace,
                                                eval_every=1)
    np.testing.assert_allclose(np.asarray(res_m.theta),
                               np.asarray(res_1.theta), atol=1e-5)
    for h1, hm in zip(res_1.history, res_m.history):
        assert h1.returned == hm.returned
        np.testing.assert_allclose(hm.wall_clock, h1.wall_clock, rtol=1e-5)
        np.testing.assert_allclose(hm.loss, h1.loss, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("scheme", ["naive", "coded"])
def test_mesh_pads_indivisible_client_axis(scheme):
    """n=6 clients (7 fused rows for coded) over 4 devices: the zero-mask
    padding rows must not perturb the trajectory."""
    _skip_unless(4)
    xs, ys = _data(n=6)
    res_1 = _sim(xs, ys, scheme).run(15)
    res_m = _sim(xs, ys, scheme, mesh=4).run(15)
    np.testing.assert_allclose(np.asarray(res_m.theta),
                               np.asarray(res_1.theta), atol=1e-5)


@pytest.mark.parametrize("ndev", [2, 8])
@pytest.mark.parametrize("scheme", ["naive", "greedy", "coded"])
def test_mesh_run_multi_matches_single_device(scheme, ndev):
    """vmapped realizations over the sharded step == single-device."""
    _skip_unless(ndev)
    xs, ys = _data()
    m1 = _sim(xs, ys, scheme).run_multi(8, 3)
    mm = _sim(xs, ys, scheme, mesh=ndev).run_multi(8, 3)
    np.testing.assert_allclose(mm.wall_clock, m1.wall_clock, rtol=1e-6)
    np.testing.assert_array_equal(mm.returned, m1.returned)
    np.testing.assert_allclose(np.asarray(mm.theta), np.asarray(m1.theta),
                               atol=1e-5)


def test_mesh_pallas_backend_matches_xla():
    """Pallas kernels inside shard_map (check_vma=False) == XLA mesh path."""
    _skip_unless(2)
    xs, ys = _data()
    res_x = _sim(xs, ys, "coded", mesh=2).run(10)
    res_p = _sim(xs, ys, "coded", mesh=2, kernel_backend="pallas").run(10)
    np.testing.assert_allclose(np.asarray(res_p.theta),
                               np.asarray(res_x.theta), atol=1e-5)


def test_mesh_accepts_mesh_object_and_rejects_bad_axes():
    _skip_unless(2)
    xs, ys = _data(n=4)
    mesh = make_client_mesh(2)
    res = _sim(xs, ys, "naive", mesh=mesh).run(3)
    assert np.isfinite(np.asarray(res.theta)).all()
    bad = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("model",))
    with pytest.raises(ValueError, match="clients"):
        _sim(xs, ys, "naive", mesh=bad)


def test_make_client_mesh_validates_device_count():
    with pytest.raises(ValueError, match="device"):
        make_client_mesh(jax.device_count() + 1)
