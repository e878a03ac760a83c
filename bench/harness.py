"""One run of one cell: set-up, the timed window, the comparison, the
metrics.  Everything a cell needs is found by name from
``BENCHMARK.json``, under the checkout's root: its configuration file,
``bench/traffic/<traffic>.json``, ``bench/limits/<cell>.json``, its kind
``bench/kinds/<kind>.py`` and one reader per metric,
``bench/metrics/<metric>.py`` (kinds and readers not under the root are
taken from this benchmark's own).

A configuration names its kind (``"kind"``, default ``rff_coded``).  The
kind is what differs between configurations; a module defines:

``make_inputs(cfg, traffic, seed) -> inputs``
    the run's inputs made from the benchmark's seed (any whole number),
    in bulk or on the device; the harness waits for them and counts them
    in set-up.
``System(cfg, traffic, inputs)``
    the deployment built through the program's entry point, with
    ``step()`` (one call, ending in host values; returns the rounds or
    steps it played), ``skipped()`` (rounds the program skipped so far),
    ``snapshot()`` (host values of the iterate that the comparison
    needs: never the whole iterate where that is large, but such as
    norms per leaf and a loss) and ``handoff()`` (host values that the
    tail hands the reference).  The harness drops the system, and
    collects garbage, before `check` runs, so a reference can take the
    device's memory and run in blocks.
``check(cfg, traffic, inputs, ran, limits, variants)``
    ``(correct, checks, {variant: numbers}, ctx)``: ``ran`` holds the
    snapshots after the first calls (``warm``) and at the window's close
    (``window_end``), the window's call count (``calls``) and the
    handoff (``tail``); ``checks`` is ``{name: {"value", "limit"}}``;
    each of `variants` is compared with the reference in the program's
    place; ``ctx`` holds the kind's own entries for the metric readers,
    such as the work the window needed.
``VARIANTS``
    the names `check` takes in `variants` (``bench/calibrate.py``).

Run order:

1. set-up (``bench/setup``): the inputs, the system, then the first
   ``steps_compared`` calls (``bench/warmup``: they compile, and their
   snapshots are what the reference follows);
2. the window: back-to-back calls (``bench/run_block``) until
   ``--seconds`` have passed; the call that straddles the end counts,
   with its time;
3. the device's memory peak is read; the program plays
   ``steps_compared`` calls more, untimed, from the window's end (the
   tail); its handoff is taken and the system is freed;
4. the kind's `check`.

With ``trace`` the window runs under the profiler, with the program's
spans and counters on (``ctx["window_spans"]``,
``ctx["window_counters"]``), and the per-layer metrics are reported;
otherwise the spans stay off and the end-to-end metrics are reported.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import tempfile
import time

import numpy as np

import trace_reduce
from workcount import peaks

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
DEFAULT_KIND = "rff_coded"


class NoAccelerator(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _module(sub: str, name: str, root: str):
    """``bench/<sub>/<name>.py`` under `root`, else this benchmark's."""
    path = os.path.join(root, "bench", sub, f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(BENCH, sub, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{sub}_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: str = ROOT):
    """The `read(ctx)` of ``bench/metrics/<name>.py``."""
    return _module("metrics", name, root).read


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
    config = _read_json(os.path.join(root, centry["file"]))
    here = os.path.join(root, "bench")
    return {
        "name": name,
        "chips": wl["chips"],
        "config": config,
        "traffic": _read_json(os.path.join(here, "traffic",
                                           f"{wl['traffic']}.json")),
        "limits": _read_json(os.path.join(here, "limits", f"{name}.json")),
        "kind": _module("kinds", config.get("kind", DEFAULT_KIND), root),
        "end_to_end": [(m["name"], m["unit"], metric_reader(m["name"], root))
                       for m in e2e],
        "per_layer": [(m["name"], m["unit"], metric_reader(m["name"], root))
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
    cells at a size the CPU holds); each of `variants` (names in the
    kind's ``VARIANTS``) is also compared with the reference in the
    program's place, under the result's ``variants`` key."""
    t0 = time.perf_counter() if start is None else start
    cell = load_cell(name, root)
    cfg = _merge(cell["config"], overrides or {})
    traffic, kind = cell["traffic"], cell["kind"]

    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"jax_init_s={time.perf_counter() - t0:.3f}")
    if require_accelerator and (dev.platform == "cpu"
                                or len(devs) < cell["chips"]):
        raise NoAccelerator(
            f"cell {name!r} needs {cell['chips']} accelerator chip(s); JAX "
            f"found {len(devs)} {dev.platform} device(s)")
    peak = None if dev.platform == "cpu" else peaks(dev.device_kind)

    from repro.obs import spans as obs_spans

    steps = int(traffic["steps_compared"])
    with jax.profiler.TraceAnnotation("bench/setup"):
        inputs = kind.make_inputs(cfg, traffic, seed)
        jax.block_until_ready(inputs)
        data_s = time.perf_counter() - t0
        span_totals = {}
        if trace:
            with obs_spans.collecting() as sp:
                system = kind.System(cfg, traffic, inputs)
                span_totals = sp.totals()
        else:
            system = kind.System(cfg, traffic, inputs)
        tw = time.perf_counter()
        warm = []
        with jax.profiler.TraceAnnotation("bench/warmup"):
            for _ in range(steps):
                system.step()
                warm.append(system.snapshot())
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
    window_spans, window_counters = {}, {}
    with obs_spans.collecting() if trace else contextlib.nullcontext():
        w0 = time.perf_counter()
        while True:
            b0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/run_block"):
                rounds += system.step()
            b1 = time.perf_counter()
            blocks.append(b1 - b0)
            if b1 - w0 >= seconds:
                break
        if trace:
            window_spans = obs_spans.totals()
            window_counters = obs_spans.counters()
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
    window_end = system.snapshot()
    for _ in range(steps):
        system.step()
    ran = {"warm": warm, "window_end": window_end, "calls": len(blocks),
           "tail": system.handoff()}
    del system
    gc.collect()

    tr0 = time.perf_counter()
    correct, checks, others, kind_ctx = kind.check(
        cfg, traffic, inputs, ran, cell["limits"], variants)
    log(f"reference_s={time.perf_counter() - tr0:.3f}")

    ctx = {"setup_s": setup_s, "warmup_s": warmup_s, "window_s": window_s,
           "rounds": rounds, "blocks_s": blocks, "spans": span_totals,
           "window_spans": window_spans, "window_counters": window_counters,
           "peak": peak, "trace": None, **kind_ctx}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": rounds, "failed": failed}
    if trace:
        red = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir),
                                  window_spans)
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
