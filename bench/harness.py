"""One run of one cell: set-up, the timed window, the comparison, the
metrics.  Everything a cell needs is found by name from
``BENCHMARK.json``: its configuration file, ``bench/traffic/<traffic>.json``,
``bench/limits/<cell>.json`` and one reader per metric,
``bench/metrics/<metric>.py``.

Run order:

1. set-up (``bench/setup``): inputs from the seed, the deployment built
   through the program's entry point, then the first ``steps_compared``
   run_block calls (``bench/warmup``: they compile, and their iterates
   are what the reference follows);
2. the window: back-to-back run_block calls (``bench/run_block``), each
   ending in host values, until ``--seconds`` have passed; the call that
   straddles the end counts, with its time;
3. the device's memory peak is read; the program plays
   ``steps_compared`` calls more, untimed, from the window's end (the
   tail); its answers are taken and its state is freed;
4. the reference replays set-up and the compared calls, draws the
   window's calls without playing them (the rounds' draws give the work
   the window needed, ``bench/workcount.py``), then plays the tail from
   the program's iterate at the window's end; the numbers are held
   against their limits (``bench/compare.py``).

With ``trace`` the window runs under the profiler and the per-layer
metrics are reported; otherwise the end-to-end ones.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import tempfile
import time

import numpy as np

import compare
import generate
import trace_reduce
import workcount
from reference import Arith, Data, Reference
from system import System

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


#: reference variants that can be put in the program's place, compared
#: exactly as the program is (``bench/calibrate.py``, tests)
VARIANTS = {"reference": {}, "control": {"control": True},
            "bf16": {"operands": "bf16"},
            "half_batch": {"fault": "half_batch"},
            "cursor_drift": {"fault": "cursor_drift"}}


class NoAccelerator(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def metric_reader(name: str):
    """The `read(ctx)` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything cell `name` needs, resolved by name only."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    (wl,) = [w for w in bench["workloads"] if w["name"] == name] or [None]
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    (centry,) = [c for c in bench["configs"] if c["name"] == wl["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"]
                                  in reported else [])]
    return {
        "name": name,
        "chips": wl["chips"],
        "config": _read_json(os.path.join(root, centry["file"])),
        "traffic": _read_json(os.path.join(BENCH, "traffic",
                                           f"{wl['traffic']}.json")),
        "limits": _read_json(os.path.join(BENCH, "limits", f"{name}.json")),
        "end_to_end": [(m["name"], m["unit"], metric_reader(m["name"]))
                       for m in e2e],
        "per_layer": [(m["name"], m["unit"], metric_reader(m["name"]))
                      for m in per_layer],
    }


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) else v
    return out


def run(name: str, seed: int, seconds: float, trace: bool, *,
        start: float | None = None, root: str = ROOT,
        require_accelerator: bool = True, overrides: dict | None = None,
        variants: tuple = (), log=print) -> dict:
    """One run of cell `name`; returns the result object (the contract's
    last stdout line).  `overrides` patches the configuration (tests run
    cells at a size the CPU holds); each of `variants` (names in
    `VARIANTS`) is also compared with the reference in the program's
    place, under the result's ``variants`` key."""
    t0 = time.perf_counter() if start is None else start
    cell = load_cell(name, root)
    cfg = _merge(cell["config"], overrides or {})
    traffic = cell["traffic"]

    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"jax_init_s={time.perf_counter() - t0:.3f}")
    if require_accelerator and (dev.platform == "cpu"
                                or len(devs) < cell["chips"]):
        raise NoAccelerator(
            f"cell {name!r} needs {cell['chips']} accelerator chip(s); JAX "
            f"found {len(devs)} {dev.platform} device(s)")
    peak = None if dev.platform == "cpu" else workcount.peaks(dev.device_kind)

    from repro.obs import spans as obs_spans

    fl_seed, data_seed = generate.seeds(seed)
    hier = generate.hierarchical(cfg)
    steps = int(traffic["steps_compared"])
    with jax.profiler.TraceAnnotation("bench/setup"):
        x, y = generate.make_data(cfg, data_seed, fl_seed, host=hier)
        jax.block_until_ready((x, y))
        data_s = time.perf_counter() - t0
        span_totals = {}
        if trace:
            with obs_spans.collecting() as sp:
                system = System(cfg, traffic, fl_seed, x, y)
                span_totals = sp.totals()
        else:
            system = System(cfg, traffic, fl_seed, x, y)
        tw = time.perf_counter()
        thetas_p = []
        with jax.profiler.TraceAnnotation("bench/warmup"):
            for _ in range(steps):
                system.step()
                thetas_p.append(system.theta())
        warmup_s = time.perf_counter() - tw
    setup_s = time.perf_counter() - t0
    log(f"setup_s={setup_s:.3f} (data {data_s:.3f}, warm-up {warmup_s:.3f})")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    skipped0 = system.skipped()
    blocks, rounds = [], 0
    w0 = time.perf_counter()
    while True:
        b0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/run_block"):
            rounds += system.step()
        b1 = time.perf_counter()
        blocks.append(b1 - b0)
        if b1 - w0 >= seconds:
            break
    window_s = b1 - w0
    if trace:
        jax.profiler.stop_trace()
    ms = np.asarray(blocks) * 1e3
    med = float(np.median(ms))
    log(f"window_s={window_s:.3f} rounds={rounds} blocks={len(blocks)} "
        f"block_ms min={ms.min():.3f} median={med:.3f} max={ms.max():.3f} "
        f"over_2x_median={int(np.sum(ms > 2 * med))}")

    stats = [d.memory_stats() for d in devs[:cell["chips"]]]
    mem = max((s or {}).get("peak_bytes_in_use", 0) for s in stats) \
        if any(stats) else None
    failed = system.skipped() - skipped0
    theta_w = system.theta()
    for _ in range(steps):
        system.step()
    ar = Arith()
    probe = compare.probe(cfg["q"])
    sp = compare.side(compare.probed(system.answers(), ar.mm, probe),
                      thetas_p, system.theta(), system.returned())
    del system
    gc.collect()

    tr0 = time.perf_counter()
    net = generate.network(cfg, fl_seed)
    data = Data(x, y)

    def replay(**variant):
        r = Reference(cfg, traffic, fl_seed, net, data, **variant)
        setup = compare.probed(r.shards, ar.mm, probe)
        thetas = r.run(steps)
        back = r.skip(len(blocks))
        tail = r.run(steps, theta_w)[-1]
        return r, compare.side(setup, thetas, tail,
                               np.concatenate(r.returned)), back

    ref, sr, back = replay()
    correct, checks = compare.judge(
        compare.numbers(sp, sr, theta_w, ref.losses), cell["limits"])
    rows = workcount.needed_rows(back, ref.loads,
                                 sum(sh["u"] for sh in ref.shards))
    others = {kind: compare.numbers(replay(**VARIANTS[kind])[1], sr,
                                    theta_w, ref.losses)
              for kind in variants}
    log(f"reference_s={time.perf_counter() - tr0:.3f}")

    ctx = {"setup_s": setup_s, "warmup_s": warmup_s, "window_s": window_s,
           "rounds": rounds, "blocks_s": blocks, "spans": span_totals,
           "rows": rows, "q": cfg["q"], "c": cfg["classes"], "peak": peak,
           "trace": None}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": rounds, "failed": failed}
    if trace:
        red = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = red
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    metrics = {}
    for mname, unit, read in (cell["per_layer"] if trace
                              else cell["end_to_end"]):
        value = read(ctx)
        if value is not None:
            metrics[mname] = {"value": float(value), "unit": unit}
    result.update(metrics=metrics, device=device)
    if trace:
        result["breakdown"] = ctx["trace"]["breakdown"]
    if variants:
        result["variants"] = others
    result["checks"] = checks
    return result


def check_lines(result: dict) -> list[str]:
    """Each compared number beside its limit, one per line."""
    lines = []
    for k, c in result["checks"].items():
        ok = math.isfinite(c["value"]) and c["value"] <= c["limit"]
        lines.append(f"check {k} value={c['value']!r} limit={c['limit']!r} "
                     f"{'ok' if ok else 'FAIL'}")
    return lines
