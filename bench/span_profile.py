#!/usr/bin/env python3
"""Where a cell's idle device time goes, by the program's own spans.

    python3 bench/span_profile.py --workload <cell> --seed <n>
        --seconds <s> [--spans 0|1]

Builds the cell and plays its warm-up calls as ``bench/harness.py``
does, then runs a profiled window of run_block calls with the program's
spans and counters on (``--spans 1``, the default) or off (``--spans
0``, as the harness's traced window runs: the pair gives the cost of
tracing).  The last line of stdout is one JSON object: the window
(calls, rounds, seconds), the trace reduction of ``bench/trace_reduce.py``
with ``idle_by_span`` added, the window's span totals and counters, and
the metrics they give (`METRICS`).

``idle_by_span`` splits device 0's idle time in the window by overlap,
not by midpoint, across the innermost program span open on the host
thread that made the calls; idle time under no program span goes to
``outside program spans``, so the entries sum to the window's idle time.
Nothing is compared with the reference: it only draws the window's
rounds, for the rows they needed (``bench/workcount.py``).
"""
from __future__ import annotations

import bisect
import contextlib
import gc
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

OUTSIDE = "outside program spans"


def innermost(spans) -> list[tuple[float, float, str]]:
    """The time `spans` cover, as sorted disjoint (start, end, name)
    pieces, each labelled with the innermost span open over it; `spans`
    are (start, end, name) events of one thread, so they nest."""
    out, stack, cur = [], [], -np.inf

    def upto(t):
        nonlocal cur
        if stack and t > cur:
            out.append((cur, t, stack[-1][2]))
        cur = max(cur, t)

    for ev in sorted(spans, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][1] <= ev[0]:
            upto(stack[-1][1])
            stack.pop()
        upto(ev[0])
        stack.append(ev)
    while stack:
        upto(stack[-1][1])
        stack.pop()
    return out


def split_idle(gaps, spans) -> dict[str, float]:
    """Seconds of `gaps` ((k, 2) disjoint nanosecond intervals) under the
    innermost of `spans` ((start, end, name) nanoseconds, one thread),
    the rest under `OUTSIDE`; the parts of each gap sum to the gap."""
    pieces = innermost(spans)
    ends = [p[1] for p in pieces]
    ns = {OUTSIDE: 0.0}
    for g0, g1 in np.asarray(gaps, float).reshape(-1, 2):
        inside = 0.0
        i = bisect.bisect_right(ends, g0)
        while i < len(pieces) and pieces[i][0] < g1:
            s, e, name = pieces[i]
            ov = min(g1, e) - max(g0, s)
            if ov > 0:
                ns[name] = ns.get(name, 0.0) + ov
                inside += ov
            i += 1
        ns[OUTSIDE] += (g1 - g0) - inside
    return {k: v * 1e-9 for k, v in ns.items()}


def idle_by_span(path: str, span_names) -> dict[str, float]:
    """`split_idle` of device 0's idle gaps in the window of trace file
    `path` (the window as `trace_reduce.reduce` takes it) across the
    program spans named in `span_names`, each name present (0 if it had
    no idle time)."""
    import trace_reduce as tr
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host_lines = [], []
    for plane in pd.planes:
        if tr._DEVICE.match(plane.name):
            ops = [tr._events(ln) for ln in plane.lines if ln.name == "XLA Ops"]
            devices.append((plane.name, ops[0] if ops else []))
        elif plane.name.startswith("/host:"):
            host_lines.extend(tr._events(ln) for ln in plane.lines)
    if not devices:
        raise ValueError(f"{path}: no device plane")
    _, ops0 = min(devices, key=lambda d: d[0])
    spans_line = next((ln for ln in host_lines
                       if any(e[2].startswith(tr.SPAN_PREFIX) for e in ln)), [])
    win = [e for e in spans_line if e[2] == tr.WINDOW_SPAN]
    if not win:
        raise ValueError(f"{path}: no {tr.WINDOW_SPAN} annotation")
    lo, hi = min(e[0] for e in win), max(e[1] for e in win)
    merged = tr._union(tr._clip(np.asarray([(s, e) for s, e, _ in ops0])
                                .reshape(-1, 2), lo, hi))
    edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    names = set(span_names)
    split = split_idle(gaps, [e for e in spans_line if e[2] in names])
    return {name: split.get(name, 0.0) for name in sorted(names)} | \
        {OUTSIDE: split[OUTSIDE]}


# ------------------------------------------------------------ metrics
def _idle_per_call_ms(span: str):
    def read(ctx):
        split = (ctx["trace"] or {}).get("idle_by_span") or {}
        if span not in split or not ctx["blocks_s"]:
            return None
        return 1e3 * split[span] / len(ctx["blocks_s"])
    return read


def _rows_per_needed(ctx):
    rec = (ctx.get("window_counters") or {}).get("round/rows")
    return None if rec is None or not ctx["rows"] else rec["total"] / ctx["rows"]


def _h2d_gb_per_round(ctx):
    rec = (ctx.get("window_counters") or {}).get("hier/h2d_bytes")
    if rec is None or not ctx["rounds"]:
        return None
    return rec["total"] / 1e9 / ctx["rounds"]


#: metrics of the window's spans and counters, read from a harness-shaped
#: ``ctx`` (``trace`` with ``idle_by_span``, ``window_counters``,
#: ``blocks_s``, ``rounds``, ``rows``); None where the input is missing
METRICS = {
    # device idle time per call while the block driver prepares the next
    # call's inputs (host draws and uploads, before dispatch), ms
    "prepare_idle_ms": _idle_per_call_ms("block/prepare"),
    # device idle time per call while the block driver fetches the
    # per-round outputs (the wait for the scan, then the copies), ms
    "fetch_idle_ms": _idle_per_call_ms("block/fetch"),
    # rows the round program read over the rows the rounds needed
    "rows_per_needed": _rows_per_needed,
    # bytes a hierarchical round hands to the device, GB per round
    "h2d_gb_per_round": _h2d_gb_per_round,
}


# ------------------------------------------------------------ the run
def profile(name: str, seed: int, seconds: float, spans_on: bool) -> dict:
    """One profiled window of cell `name` (module docstring)."""
    import jax

    import generate
    import harness
    import trace_reduce
    import workcount
    from reference import Data, Reference
    from repro.obs import spans as obs_spans
    from system import System

    cell = harness.load_cell(name)
    cfg, traffic = cell["config"], cell["traffic"]
    fl_seed, data_seed = generate.seeds(seed)
    x, y = generate.make_data(cfg, data_seed, fl_seed,
                              host=generate.hierarchical(cfg))
    with obs_spans.collecting() as sp:
        system = System(cfg, traffic, fl_seed, x, y)
        build_spans = sp.totals()
    steps = int(traffic["steps_compared"])
    for _ in range(steps):
        system.step()

    trace_dir = tempfile.mkdtemp(prefix="span_profile_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    blocks, rounds = [], 0
    window_spans, window_counters = {}, {}
    with obs_spans.collecting() if spans_on else contextlib.nullcontext():
        w0 = time.perf_counter()
        while True:
            b0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                rounds += system.step()
            b1 = time.perf_counter()
            blocks.append(b1 - b0)
            if b1 - w0 >= seconds:
                break
        if spans_on:
            window_spans = obs_spans.totals()
            window_counters = obs_spans.counters()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(trace_dir)
    red = trace_reduce.reduce(path)
    red["idle_by_span"] = idle_by_span(path, window_spans)
    shutil.rmtree(trace_dir, ignore_errors=True)
    del system
    gc.collect()

    ref = Reference(cfg, traffic, fl_seed, generate.network(cfg, fl_seed),
                    Data(x, y))
    ref.skip(steps)
    rows = workcount.needed_rows(ref.skip(len(blocks)), ref.loads,
                                 sum(sh["u"] for sh in ref.shards))
    ctx = {"window_s": b1 - w0, "rounds": rounds, "blocks_s": blocks,
           "rows": rows, "trace": red, "spans": build_spans,
           "window_spans": window_spans, "window_counters": window_counters}
    metrics = {k: read(ctx) for k, read in METRICS.items()}
    metrics["encode_s"] = harness.metric_reader("encode_s")(ctx)
    idle = red["window_s"] - red["busy_s"]
    return {"workload": name, "seed": seed, "spans": spans_on,
            "calls": len(blocks), "rounds": rounds,
            "window_s": ctx["window_s"], "rows_needed": rows,
            # the split's parts against the trace's idle time (one chip)
            "split_rel_error": abs(sum(red["idle_by_span"].values()) - idle)
            / idle,
            "metrics": {k: v for k, v in metrics.items() if v is not None},
            "trace": red, "window_spans": window_spans,
            "window_counters": window_counters}


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = profile(args.workload, args.seed, args.seconds, bool(args.spans))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
