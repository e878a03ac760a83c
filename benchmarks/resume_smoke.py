"""Kill/resume smoke: SIGKILL a checkpointing run mid-flight, resume it,
and require bit-equality with the uninterrupted control run.

Parent mode (default) never imports JAX, so that only one process at a
time holds the accelerator.  It runs three children of this same file,
one after another: ``--role control`` (the uninterrupted run), ``--role
child`` (a block-structured run that checkpoints every block and sleeps
between blocks to widen the kill window, SIGKILLed once at least two
checkpoints are on disk) and ``--role resume`` (resumes from the latest
checkpoint).  Control and resume each write their final model /
wall-clock log / returned counts to a JSON file, and the parent asserts
they match exactly.  Exit code 0 = bit-identical; anything else fails CI.

    PYTHONPATH=src python benchmarks/resume_smoke.py --ckpt-dir /tmp/ck
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

ITERATIONS = 24
BLOCK = 4           # checkpoint_every
KILL_AFTER = 8      # SIGKILL once >= this many rounds are checkpointed


def build():
    """One deterministic deployment shared by control, child, and resume."""
    from repro.api import build_experiment
    from repro.config import ExperimentSpec, FLConfig, TrainConfig
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(6, 16, 24)).astype(np.float32) * 0.2
    ys = rng.normal(size=(6, 16, 3)).astype(np.float32)
    spec = ExperimentSpec(
        fl=FLConfig(n_clients=6, delta=0.25, psi=0.3, seed=3),
        train=TrainConfig(learning_rate=0.5, l2_reg=1e-5,
                          lr_decay_epochs=(5,)),
        scheme="adaptive_coded", channel_profile="drift_churn",
        adapt_every=2, checkpoint_every=BLOCK, run_id="resume-smoke")
    return build_experiment(spec, xs, ys)


def child(ckpt_dir: str) -> None:
    """Checkpoint every block, sleeping in between so the parent can
    SIGKILL between (not during) block computations."""
    from repro.checkpoint import io as ckpt_io
    exp = build()
    state = exp.init_state(ITERATIONS)
    while not state.done:
        state = exp.run_block(state)
        exp.save_state(
            os.path.join(ckpt_dir,
                         f"{ckpt_io.CKPT_PREFIX}{state.rounds_done:06d}.npz"),
            state)
        time.sleep(0.5)


def finished_run(ckpt_dir: str, resume: bool, result: str) -> None:
    """Run to the end (fresh, or resumed from `ckpt_dir`) and write the
    final model and per-round log to `result` as JSON (floats round-trip
    exactly, so the parent's comparison stays bit-for-bit)."""
    if resume:
        res = build().run(ITERATIONS, checkpoint_dir=ckpt_dir, resume=True)
    else:
        res = build().run(ITERATIONS)
    with open(result, "w") as fh:
        json.dump({"theta": np.asarray(res.theta).tolist(),
                   "wall_clock": [h.wall_clock for h in res.history],
                   "returned": [h.returned for h in res.history],
                   "privacy_eps": res.privacy_eps}, fh)


def _child_cmd(role: str, ckpt_dir: str, result: str = "") -> list:
    cmd = [sys.executable, os.path.abspath(__file__), "--role", role,
           "--ckpt-dir", ckpt_dir]
    return cmd + (["--result", result] if result else [])


def _checkpointed_rounds(ckpt_dir: str) -> int:
    """Rounds covered by the newest ``ckpt_<rounds>.npz`` in `ckpt_dir`
    (the runtime's checkpoint naming), read without importing JAX."""
    rounds = [int(name[len("ckpt_"):-len(".npz")])
              for name in os.listdir(ckpt_dir)
              if name.startswith("ckpt_") and name.endswith(".npz")
              and ".tmp" not in name]
    return max(rounds, default=0)


def parent(ckpt_dir: str, out: str) -> int:
    os.makedirs(ckpt_dir, exist_ok=True)
    control_json = os.path.join(ckpt_dir, "control.json")
    resumed_json = os.path.join(ckpt_dir, "resumed.json")

    subprocess.run(_child_cmd("control", ckpt_dir, control_json),
                   check=True)

    proc = subprocess.Popen(_child_cmd("child", ckpt_dir))
    deadline = time.time() + 300
    killed_at = None
    try:
        while time.time() < deadline:
            rounds = _checkpointed_rounds(ckpt_dir)
            if rounds >= KILL_AFTER:
                killed_at = rounds
                break
            if proc.poll() is not None:
                print(f"FAIL: child exited early (rc={proc.returncode}) "
                      "before reaching the kill point", file=sys.stderr)
                return 2
            time.sleep(0.05)
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
            proc.wait()
    if killed_at is None:
        print("FAIL: no checkpoint appeared within the deadline",
              file=sys.stderr)
        return 2
    assert killed_at < ITERATIONS, "child finished before the kill"

    subprocess.run(_child_cmd("resume", ckpt_dir, resumed_json), check=True)
    with open(control_json) as fh:
        control = json.load(fh)
    with open(resumed_json) as fh:
        resumed = json.load(fh)

    theta_ok = bool(np.array_equal(np.asarray(control["theta"]),
                                   np.asarray(resumed["theta"])))
    wall_ok = control["wall_clock"] == resumed["wall_clock"]
    ret_ok = control["returned"] == resumed["returned"]
    eps_ok = control["privacy_eps"] == resumed["privacy_eps"]
    ok = theta_ok and wall_ok and ret_ok and eps_ok

    report = {
        "iterations": ITERATIONS, "checkpoint_every": BLOCK,
        "killed_at_round": killed_at, "theta_bit_identical": theta_ok,
        "wall_clock_identical": wall_ok, "returned_identical": ret_ok,
        "privacy_eps_identical": eps_ok, "ok": ok,
    }
    if out:
        with open(out, "w") as fh:
            json.dump(report, fh, indent=2)
    print(json.dumps(report, indent=2))
    if not ok:
        print("FAIL: resumed run diverged from control", file=sys.stderr)
        return 1
    print(f"OK: SIGKILL at round {killed_at}, resumed bit-identically")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--role", default="parent",
                    choices=("parent", "control", "child", "resume"),
                    help="parent (default) runs the other three roles "
                         "as children, one at a time")
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--result", default="",
                    help="JSON result path (control/resume roles)")
    ap.add_argument("--out", default="",
                    help="optional JSON report path (parent mode)")
    args = ap.parse_args()
    if args.role == "parent":
        return parent(args.ckpt_dir, args.out)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.role == "child":
        child(args.ckpt_dir)
    else:
        finished_run(args.ckpt_dir, args.role == "resume", args.result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
