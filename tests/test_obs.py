"""Run-telemetry subsystem (`repro.obs`): spans, journal, attribution.

The hard invariant under test: telemetry never touches an RNG stream or
changes a trajectory — runs with spans/journal enabled are bit-identical
to runs with telemetry off, and the journal is a deterministic function
of (spec, seed).  Also covered: byte-exact journal determinism, replay
reconstructing `FedResult.history`, kill/resume appending to (not
corrupting) an existing journal, torn-tail repair, the per-round
`RoundLog.n_masked`/`skipped` counters, straggler attribution bounds,
and the `ExperimentService` per-run timing surface.
"""
import json
import os

import numpy as np
import pytest

from repro import api
from repro.config import ExperimentSpec, FLConfig, TrainConfig
from repro.launch.report import REQUIRED_SPANS
from repro.obs import (RunJournal, attribution_from_blocks,
                       histories_equal, history_from_journal, load_events,
                       round_deadlines)
from repro.obs import spans as obs_spans
from repro.obs.events import EVENTS_NAME


@pytest.fixture(autouse=True)
def _spans_off():
    """Every test starts (and leaves) with the collector disabled."""
    obs_spans.disable()
    obs_spans.reset()
    yield
    obs_spans.disable()
    obs_spans.reset()


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
        scheme=scheme, checkpoint_every=4)
    base.update(over)
    return ExperimentSpec(**base)


def _eval():
    return lambda th: (float(np.abs(np.asarray(th)).sum()), 0.0)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_records_nothing_when_disabled():
    with obs_spans.span("solver/two_step"):
        pass
    assert obs_spans.totals() == {}
    obs_spans.enable()
    with obs_spans.span("solver/two_step"):
        pass
    with obs_spans.span("solver/two_step"):
        pass
    rec = obs_spans.totals()["solver/two_step"]
    assert rec["count"] == 2
    assert rec["total_s"] >= rec["max_s"] >= rec["min_s"] >= 0.0


def test_forced_span_measures_without_recording_globally():
    with obs_spans.span("service/block", force=True) as sp:
        pass
    assert sp.elapsed_s is not None and sp.elapsed_s >= 0.0
    assert obs_spans.totals() == {}   # global collector stays untouched


def test_collecting_context_restores_prior_flag():
    assert not obs_spans.enabled()
    with obs_spans.collecting() as mod:
        assert obs_spans.enabled()
        with obs_spans.span("trace/generate"):
            pass
        assert "trace/generate" in mod.totals()
    assert not obs_spans.enabled()


def test_write_json_roundtrip(tmp_path):
    obs_spans.enable()
    with obs_spans.span("encode/parity"):
        pass
    path = tmp_path / obs_spans.SPANS_NAME
    obs_spans.write_json(str(path))
    loaded = json.loads(path.read_text())
    assert loaded["encode/parity"]["count"] == 1


class _Annotation:
    """Stands in for `jax.profiler.TraceAnnotation`, logging its use."""
    log: list = []

    def __init__(self, name, **meta):
        self.name, self.meta = name, meta

    def __enter__(self):
        self.log.append(("enter", self.name, self.meta))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.meta))


def test_enabled_span_enters_trace_annotation(monkeypatch):
    monkeypatch.setattr(obs_spans, "_annotation", _Annotation)
    monkeypatch.setattr(_Annotation, "log", [])
    with obs_spans.span("block/run", cursor=40):
        pass
    with obs_spans.span("service/block", force=True):
        pass
    assert _Annotation.log == []
    obs_spans.enable()
    with obs_spans.span("block/run", cursor=40):
        with obs_spans.span("block/prepare"):
            pass
    assert _Annotation.log == [
        ("enter", "block/run", {"cursor": 40}),
        ("enter", "block/prepare", {}), ("exit", "block/prepare", {}),
        ("exit", "block/run", {"cursor": 40})]


def test_counters_fold_and_reset_with_collecting():
    obs_spans.count("round/rows", 5)
    assert obs_spans.counters() == {}
    with obs_spans.collecting():
        obs_spans.count("round/rows", 5)
        obs_spans.count("round/rows", 7)
        obs_spans.count("hier/h2d_bytes", 4096)
        assert obs_spans.counters() == {
            "hier/h2d_bytes": {"events": 1, "total": 4096},
            "round/rows": {"events": 2, "total": 12}}
    obs_spans.count("round/rows", 1)      # disabled again: not counted
    assert obs_spans.counters()["round/rows"]["total"] == 12
    with obs_spans.collecting():
        assert obs_spans.counters() == {}
    # totals() keeps its schema: counters never show up there
    assert obs_spans.totals() == {}


def test_scan_execute_never_blocks(monkeypatch):
    import jax
    xs, ys = _data()
    obs_spans.enable()
    exp = api.build_experiment(_spec(), xs, ys)
    state = exp.run_block(exp.init_state(12))        # scan/compile
    calls = []
    sync = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or sync(x))
    state = exp.run_block(exp.run_block(state))
    assert calls == []
    rec = obs_spans.totals()
    assert rec["scan/compile"]["count"] == 1
    assert rec["scan/execute"]["count"] == 2
    for name in ("block/run", "block/prepare", "block/fetch", "block/state"):
        assert rec[name]["count"] == 3


def test_round_rows_counter_matches_shapes():
    xs, ys = _data()
    with obs_spans.collecting() as mod:
        exp = api.build_experiment(_spec(), xs, ys)
        exp.run(12)
        got = mod.counters()["round/rows"]
    # the fused coded tensor: n client row blocks and the parity set,
    # each padded to L = max(largest load, u) points
    L = max(int(exp.loads.max()), exp.u)
    assert got == {"events": 3, "total": 12 * (exp.n + 1) * L}


def test_block_transfers_counter_and_one_upload(monkeypatch):
    from repro.core import fed_runtime
    xs, ys = _data()
    with obs_spans.collecting() as mod:
        exp = api.build_experiment(_spec(), xs, ys)
        exp.run(12)
        assert mod.counters()["block/transfers"] == {"events": 3,
                                                     "total": 2 * 3}
    state = exp.run_block(exp.init_state(12))          # compiles
    puts = []
    put = fed_runtime.jax.device_put
    monkeypatch.setattr(fed_runtime.jax, "device_put",
                        lambda *a, **k: puts.append(1) or put(*a, **k))
    state = exp.run_block(exp.run_block(state))
    assert len(puts) == 2                              # one per call


# ---------------------------------------------------------------------------
# the hard invariant: telemetry never perturbs a trajectory
# ---------------------------------------------------------------------------

CASES = {
    "coded": dict(scheme="coded"),
    "adaptive_coded": dict(scheme="adaptive_coded",
                           channel_profile="drift_churn", adapt_every=2),
}


@pytest.mark.parametrize("kernel_backend", ["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_telemetry_on_off_bit_identical(case, kernel_backend, tmp_path):
    xs, ys = _data()
    spec = _spec(kernel_backend=kernel_backend, **CASES[case])
    ev = _eval()

    off = api.build_experiment(spec, xs, ys).run(12, eval_fn=ev,
                                                 eval_every=1)
    obs_spans.enable()
    on = api.build_experiment(spec, xs, ys).run(
        12, eval_fn=ev, eval_every=1, journal_dir=str(tmp_path / "j"))

    np.testing.assert_array_equal(np.asarray(off.theta),
                                  np.asarray(on.theta))
    assert histories_equal(off.history, on.history)
    # and the journal replays the exact history the run returned
    assert histories_equal(
        history_from_journal(str(tmp_path / "j")), on.history)


def test_hier_telemetry_on_off_bit_identical(tmp_path):
    xs, ys = _data(n=12, l=4, q=6, c=2)
    spec = ExperimentSpec(
        fl=FLConfig(n_clients=12, delta=0.25, seed=3),
        train=TrainConfig(learning_rate=0.5, l2_reg=1e-5),
        scheme="coded", hier_shards=2, sample_fraction=0.5,
        checkpoint_every=3)

    off = api.build_experiment(spec, xs, ys).run(9)
    obs_spans.enable()
    exp_on = api.build_experiment(spec, xs, ys)
    on = exp_on.run(9, journal_dir=str(tmp_path / "j"))

    np.testing.assert_array_equal(np.asarray(off.theta),
                                  np.asarray(on.theta))
    events = load_events(str(tmp_path / "j"))
    assert len(events) == 9
    # hier rounds journal every shard's coded deadline
    assert all(len(e["t_star_s"]) == 2 for e in events)
    attr = exp_on.attribution()
    assert set(attr) == {0, 1}
    # each round uploads every shard's f32 block and return mask:
    # 2 shards x 6 clients x (4 x (6 + 2) points + 1 mask entry) x 4 B
    counts = obs_spans.counters()
    assert counts["hier/h2d_bytes"] == {
        "events": 9 * 2, "total": 9 * 2 * 6 * (4 * 8 + 1) * 4}
    # and reads each shard's 6 x 4 client rows plus u_s = 6 parity rows
    assert counts["round/rows"] == {"events": 9 * 2,
                                    "total": 9 * 2 * (6 * 4 + 6)}
    names = set(obs_spans.totals())
    assert {"hier/round_block", "hier/sample", "hier/shard_upload",
            "hier/shard_round"} <= names


# ---------------------------------------------------------------------------
# journal determinism / replay / resume
# ---------------------------------------------------------------------------

def test_journal_byte_deterministic(tmp_path):
    xs, ys = _data()
    spec = _spec()
    obs_spans.enable()
    for d in ("a", "b"):
        api.build_experiment(spec, xs, ys).run(
            12, eval_fn=_eval(), eval_every=1,
            journal_dir=str(tmp_path / d))
    a = (tmp_path / "a" / EVENTS_NAME).read_bytes()
    assert a == (tmp_path / "b" / EVENTS_NAME).read_bytes()
    assert len(a.splitlines()) == 12


def test_journal_event_shape(tmp_path):
    xs, ys = _data()
    api.build_experiment(_spec(), xs, ys).run(
        8, eval_fn=_eval(), eval_every=1, journal_dir=str(tmp_path))
    events = load_events(str(tmp_path))
    assert [e["round"] for e in events] == list(range(8))
    wall = 0.0
    for e in events:
        assert e["t_round_s"] > 0 and e["wall_clock_s"] > wall
        wall = e["wall_clock_s"]
        assert e["returned"] >= 1
        assert e["n_masked"] == 0 and e["skipped"] == 0
        assert e["lr_scale"] == 1.0
        assert e["loss"] is not None   # collect=True, eval_every=1


def test_kill_resume_appends_to_existing_journal(tmp_path):
    """Interrupt at a block boundary, resume in a FRESH Experiment with
    the same journal dir: the final journal is byte-identical to the
    uninterrupted run's (appended, never rewritten)."""
    xs, ys = _data()
    spec = _spec()
    ev = _eval()
    ref_dir, jdir = str(tmp_path / "ref"), str(tmp_path / "resumed")
    ckpt = str(tmp_path / "ckpt")

    api.build_experiment(spec, xs, ys).run(
        12, eval_fn=ev, eval_every=1, journal_dir=ref_dir)

    # partial run: one block (4 rounds), checkpoint + journal, then "kill"
    interrupted = api.build_experiment(spec, xs, ys)
    state = interrupted.init_state(12, collect=True)
    state = interrupted.run_block(state, eval_fn=ev, eval_every=1)
    interrupted.save_state(os.path.join(ckpt, "ckpt_000004.npz"), state)
    journal = RunJournal(jdir)
    assert journal.sync(interrupted, state) == 4
    partial = (tmp_path / "resumed" / EVENTS_NAME).read_bytes()

    resumed = api.build_experiment(spec, xs, ys)
    resumed.run(12, eval_fn=ev, eval_every=1, checkpoint_dir=ckpt,
                resume=True, journal_dir=jdir)
    final = (tmp_path / "resumed" / EVENTS_NAME).read_bytes()
    assert final.startswith(partial)
    assert final == (tmp_path / "ref" / EVENTS_NAME).read_bytes()


def test_torn_tail_repaired_on_open(tmp_path):
    xs, ys = _data()
    spec = _spec()
    exp = api.build_experiment(spec, xs, ys)
    state = exp.init_state(8, collect=True)
    state = exp.run_block(state, eval_fn=_eval(), eval_every=1)
    journal = RunJournal(str(tmp_path))
    journal.sync(exp, state)
    clean = (tmp_path / EVENTS_NAME).read_bytes()

    # simulate a crash mid-append: a torn, newline-less partial record
    with open(tmp_path / EVENTS_NAME, "ab") as fh:
        fh.write(b'{"round": 99, "t_round_s"')
    # read-only loader skips the torn tail and leaves the file alone
    assert len(load_events(str(tmp_path))) == 4
    assert (tmp_path / EVENTS_NAME).read_bytes() != clean
    # the write-path journal truncates it and continues cleanly
    reopened = RunJournal(str(tmp_path))
    assert reopened.rounds_logged == 4
    assert (tmp_path / EVENTS_NAME).read_bytes() == clean
    state = exp.run_block(state, eval_fn=_eval(), eval_every=1)
    reopened.sync(exp, state)
    assert [e["round"] for e in load_events(str(tmp_path))] == \
        list(range(8))


def test_journal_dir_rejected_on_legacy_engine(tmp_path):
    xs, ys = _data()
    exp = api.build_experiment(_spec(engine="legacy", checkpoint_every=0),
                               xs, ys)
    with pytest.raises(ValueError, match="batched engine"):
        exp.run(4, journal_dir=str(tmp_path))


# ---------------------------------------------------------------------------
# RoundLog degradation counters
# ---------------------------------------------------------------------------

def test_roundlog_carries_guard_counters():
    xs, ys = _data()
    res = api.build_experiment(_spec(), xs, ys).run(6)
    for log in res.history:
        assert log.n_masked == 0 and log.skipped == 0


def test_legacy_engine_fills_zero_counters():
    xs, ys = _data()
    res = api.build_experiment(_spec(engine="legacy", checkpoint_every=0),
                               xs, ys).run(4)
    assert all(log.n_masked == 0 and log.skipped == 0
               for log in res.history)


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------

def test_attribution_requires_enabled_telemetry():
    xs, ys = _data()
    exp = api.build_experiment(_spec(), xs, ys)
    exp.run(4)
    with pytest.raises(RuntimeError, match="enable"):
        exp.attribution()


def test_attribution_bounds_and_report():
    xs, ys = _data()
    exp = api.build_experiment(_spec(), xs, ys)
    obs_spans.enable()
    exp.run(10)
    attr = exp.attribution(k=2)
    n = 6
    assert attr.rounds == 10 and attr.k == 2
    assert attr.miss_rate.shape == (n,)
    assert np.all((attr.miss_rate >= 0) & (attr.miss_rate <= 1))
    assert np.all(attr.miss_counts <= attr.active_rounds)
    assert attr.slowest_k_counts.sum() == 10 * 2
    assert np.all((attr.comp_share >= 0) & (attr.comp_share <= 1))
    top = attr.top_stragglers(3)
    assert len(top) == 3
    assert [r for _, r in top] == sorted((r for _, r in top),
                                         reverse=True)
    d = attr.to_dict()
    assert d["rounds"] == 10
    assert len(d["miss_rate"]) == n
    assert 0.0 <= d["comp_share_mean"] <= 1.0


def test_round_deadlines_per_step_kind():
    rng = np.random.default_rng(0)
    times = rng.uniform(1.0, 5.0, size=(4, 5))
    active = np.ones((4, 5), dtype=bool)
    active[2, :3] = False

    coded = round_deadlines("coded", times, active, t_star=2.5)
    np.testing.assert_array_equal(coded, np.full(4, 2.5))
    per_round = np.array([1.0, 2.0, 3.0, 4.0])
    np.testing.assert_array_equal(
        round_deadlines("adaptive_coded", times, active,
                        t_star_r=per_round), per_round)
    naive = round_deadlines("naive", times, active)
    np.testing.assert_array_equal(
        naive, np.where(active, times, 0.0).max(axis=1))
    greedy = round_deadlines("greedy", times, active, n_wait=3)
    srt = np.sort(np.where(active, times, np.inf), axis=1)
    # row 2 has only 2 active clients -> waits clamps to its live count
    expect = np.array([srt[0, 2], srt[1, 2], srt[2, 1], srt[3, 2]])
    np.testing.assert_array_equal(greedy, expect)


def test_attribution_from_blocks_concatenates():
    blocks = [{"times": np.full((3, 4), 1.0), "active": None},
              {"times": np.full((2, 4), 9.0), "active": None}]
    attr = attribution_from_blocks(
        blocks, "coded", t_star=2.0, t_ideal=1.0, n_wait=2,
        loads=np.full(4, 0.5), m=2.0, k=1)
    assert attr.rounds == 5
    # rounds in block 2 all miss the coded deadline
    np.testing.assert_array_equal(attr.miss_counts, np.full(4, 2))
    np.testing.assert_allclose(attr.miss_rate, 0.4)
    np.testing.assert_allclose(attr.comp_share[:3], 0.0)
    np.testing.assert_allclose(attr.comp_share[3:], 1.0)


# ---------------------------------------------------------------------------
# spans through a real run + service surface
# ---------------------------------------------------------------------------

def test_required_spans_recorded_by_journaled_run(tmp_path):
    xs, ys = _data()
    with obs_spans.collecting() as mod:
        api.build_experiment(_spec(), xs, ys).run(
            8, journal_dir=str(tmp_path))
        names = set(mod.totals())
    assert set(REQUIRED_SPANS) <= names
    assert "checkpoint/save" not in names   # no checkpoint_dir given


def test_service_health_timing_and_journal(tmp_path):
    xs, ys = _data()
    spec = _spec()
    svc = api.ExperimentService(str(tmp_path))
    obs_spans.enable()
    svc.submit(spec, xs, ys, 8, run_id="r0")
    while svc.step() is not None:
        pass
    timing = svc.health_report()["r0"]["timing"]
    assert timing["blocks_run"] == 2
    assert timing["block_seconds"] > 0
    assert timing["ckpt_save_seconds"] > 0
    assert timing["backoff_seconds"] == 0.0
    events = load_events(str(tmp_path / "r0"))
    assert [e["round"] for e in events] == list(range(8))
