"""Chaos smoke: SIGKILL a service mid-block AND corrupt its latest
checkpoint; the restarted service must recover bit-identically.

Extends ``benchmarks/resume_smoke.py`` from kill-tolerance to full
infrastructure-fault tolerance.  Parent mode (default) never imports
JAX, so that only one process at a time holds the accelerator; it runs
three children of this same file, one after another.  ``--role
control`` runs the never-interrupted control service.  ``--role child``
is an `ExperimentService` stepping one block at a time with sleeps to
widen the kill window; the parent SIGKILLs it once enough rounds are
checkpointed, then *corrupts the newest checkpoint on disk* (bit-flips
through `repro.faults.bitflip_file`).  ``--role restart`` restarts the
service over the same root: it must detect the corruption through
digest verification, fall back to the newest intact checkpoint
(``fallback_resume``), recompute the lost blocks, and finish with a
final theta bit-identical to the control.  Exit code 0 = recovered
bit-identically; anything else fails CI.

    PYTHONPATH=src python benchmarks/chaos_smoke.py --root /tmp/chaos
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
RUN_ID = "chaos-smoke"


def build_spec():
    from repro.config import ExperimentSpec, FLConfig, TrainConfig
    return ExperimentSpec(
        fl=FLConfig(n_clients=6, delta=0.25, psi=0.3, seed=3),
        train=TrainConfig(learning_rate=0.5, l2_reg=1e-5,
                          lr_decay_epochs=(5,)),
        scheme="coded", checkpoint_every=BLOCK, run_id=RUN_ID)


def data():
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(6, 16, 24)).astype(np.float32) * 0.2
    ys = rng.normal(size=(6, 16, 3)).astype(np.float32)
    return xs, ys


def make_service(root: str):
    from repro.launch.service import ExperimentService
    svc = ExperimentService(root)
    svc.submit(build_spec(), *data(), ITERATIONS, run_id=RUN_ID)
    return svc


def child(root: str) -> None:
    """Step the service one block at a time, sleeping in between so the
    parent can SIGKILL between (not during) block computations."""
    svc = make_service(root)
    while svc.pending:
        svc.step()
        time.sleep(0.5)


def control(root: str, result: str) -> None:
    """The never-interrupted control service; final model and wall-clock
    log go to `result` as JSON (floats round-trip exactly)."""
    res = make_service(os.path.join(root, "control")) \
        .run_until_complete()[RUN_ID]
    with open(result, "w") as fh:
        json.dump({"theta": np.asarray(res.theta).tolist(),
                   "wall_clock": [h.wall_clock for h in res.history]}, fh)


def restart(root: str, result: str) -> None:
    """Restart the service over the killed and corrupted root and write
    what the parent checks to `result`."""
    svc = make_service(root)
    run = svc.runs[RUN_ID]
    res = svc.run_until_complete()[RUN_ID]
    with open(result, "w") as fh:
        json.dump({
            "theta": None if res is None else np.asarray(res.theta).tolist(),
            "wall_clock": (None if res is None
                           else [h.wall_clock for h in res.history]),
            "fallback_resume": bool(run.fallback_resume),
            "resumed": bool(run.resumed),
            "health": svc.health_report()[RUN_ID]}, fh)


def _child_cmd(role: str, root: str, result: str = "") -> list:
    cmd = [sys.executable, os.path.abspath(__file__), "--role", role,
           "--root", root]
    return cmd + (["--result", result] if result else [])


def _newest_checkpoint(ckpt_dir: str):
    """(rounds, path) of the newest ``ckpt_<rounds>.npz`` in `ckpt_dir`
    (the runtime's checkpoint naming), read without importing JAX."""
    found = [(int(name[len("ckpt_"):-len(".npz")]),
              os.path.join(ckpt_dir, name))
             for name in os.listdir(ckpt_dir)
             if name.startswith("ckpt_") and name.endswith(".npz")
             and ".tmp" not in name]
    return max(found, default=(0, None))


def parent(root: str, out: str) -> int:
    from repro.faults import bitflip_file      # numpy only, no JAX
    ckpt_dir = os.path.join(root, RUN_ID)
    os.makedirs(ckpt_dir, exist_ok=True)
    control_json = os.path.join(root, "control.json")
    restart_json = os.path.join(root, "restart.json")

    subprocess.run(_child_cmd("control", root, control_json), check=True)

    proc = subprocess.Popen(_child_cmd("child", root))
    deadline = time.time() + 300
    killed_at = None
    try:
        while time.time() < deadline:
            rounds, _ = _newest_checkpoint(ckpt_dir)
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

    # the second fault: bit rot on the newest checkpoint the kill left
    _, corrupted = _newest_checkpoint(ckpt_dir)
    bitflip_file(corrupted)

    subprocess.run(_child_cmd("restart", root, restart_json), check=True)
    with open(control_json) as fh:
        ctrl = json.load(fh)
    with open(restart_json) as fh:
        rest = json.load(fh)

    theta_ok = rest["theta"] is not None and bool(np.array_equal(
        np.asarray(ctrl["theta"]), np.asarray(rest["theta"])))
    wall_ok = rest["wall_clock"] is not None and (
        ctrl["wall_clock"] == rest["wall_clock"])
    fallback_ok = rest["fallback_resume"]
    ok = theta_ok and wall_ok and fallback_ok

    report = {
        "iterations": ITERATIONS, "checkpoint_every": BLOCK,
        "killed_at_round": killed_at,
        "corrupted_checkpoint": os.path.basename(corrupted),
        "fallback_resume": fallback_ok,
        "resumed_at_round": (killed_at - BLOCK if rest["resumed"]
                             else None),
        "theta_bit_identical": theta_ok,
        "wall_clock_identical": wall_ok,
        "health": rest["health"], "ok": ok,
    }
    if out:
        with open(out, "w") as fh:
            json.dump(report, fh, indent=2)
    print(json.dumps(report, indent=2))
    if not ok:
        print("FAIL: chaos recovery diverged from control",
              file=sys.stderr)
        return 1
    print(f"OK: SIGKILL at round {killed_at} + corrupted "
          f"{os.path.basename(corrupted)}, recovered bit-identically")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--role", default="parent",
                    choices=("parent", "control", "child", "restart"),
                    help="parent (default) runs the other three roles "
                         "as children, one at a time")
    ap.add_argument("--root", required=True,
                    help="service checkpoint root")
    ap.add_argument("--result", default="",
                    help="JSON result path (control/restart roles)")
    ap.add_argument("--out", default="",
                    help="optional JSON report path (parent mode)")
    args = ap.parse_args()
    if args.role == "parent":
        return parent(args.root, args.out)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.role == "child":
        child(args.root)
    elif args.role == "control":
        control(args.root, args.result)
    else:
        restart(args.root, args.result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
