"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The window is the span of the bench's own ``bench/run_block``
annotations (`jax.profiler.TraceAnnotation` in the harness), or of the
device ops where there are none.  For each device plane
(``/device:TPU:<i>``) the busy time is the union of its ``XLA Ops``
intervals inside the window; ``busy_s`` averages it over the devices.

Device 0's ops and idle gaps inside the window are read three ways:

* ``op_s``: the seconds of every op, keyed ``<module>/<op>``, for a
  reader of one kernel's time (ops nest: a ``while`` and the fusions in
  its body are each listed, so the values can sum past the busy time);
* ``breakdown``: the ten ops with the most time, and the idle gaps
  summed by what the host was doing over them: the innermost ``bench/``
  annotation and the deepest event under it on the same host thread (a
  runtime call such as ``PJRT_LoadedExecutable_Execute``, or a program
  span), or ``python`` where no event was open.  A gap is split by
  overlap across what the host did during it;
* ``idle_by_span``: the idle time split by overlap across the innermost
  of the program spans named in ``span_names`` (`split_idle`), the rest
  under ``outside program spans``, so the parts sum to the window's
  idle time.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

import numpy as np

WINDOW_SPAN = "bench/run_block"
SPAN_PREFIX = "bench/"
OUTSIDE = "outside program spans"
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
TOP = 10


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {directory}, found {paths}")
    return paths[0]


def _events(line) -> list[tuple[float, float, str]]:
    return [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def _union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted (k, 2) intervals."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _short_op(name: str) -> str:
    return name.split(" = ", 1)[0].strip()


def _short_module(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def innermost(spans, label=None) -> list[tuple[float, float, str]]:
    """The time `spans` cover, as sorted disjoint (start, end, name)
    pieces, each labelled with the innermost span open over it, or with
    ``label(open)`` of the (start, end, name) spans open over it from the
    outermost in; `spans` are events of one thread, so they nest."""
    out, stack, cur = [], [], -np.inf

    def upto(t):
        nonlocal cur
        if stack and t > cur:
            out.append((cur, t, stack[-1][2] if label is None
                        else label(stack)))
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


def split_idle(gaps, spans, label=None,
               outside: str = OUTSIDE) -> dict[str, float]:
    """Seconds of `gaps` ((k, 2) disjoint nanosecond intervals) under the
    pieces of `innermost(spans, label)`, the rest under `outside`; the
    parts of each gap sum to the gap."""
    pieces = innermost(spans, label)
    ends = [p[1] for p in pieces]
    ns = {outside: 0.0}
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
        ns[outside] += (g1 - g0) - inside
    return {k: v * 1e-9 for k, v in ns.items()}


def _host_label(stack) -> str:
    """What the host was doing: the innermost bench annotation, and the
    deepest event under it."""
    bench = [n for _, _, n in stack if n.startswith(SPAN_PREFIX)]
    rest = [n for _, _, n in stack if not n.startswith(SPAN_PREFIX)]
    return (bench[-1] if bench else "outside bench spans") + \
        " > " + (rest[-1] if rest else "python")


def _top(times: dict[str, float]) -> list[list]:
    ranked = sorted(times.items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in ranked[:TOP]]


def reduce(path: str, span_names=()) -> dict:
    """Busy and window seconds, idle share, every op's time, the
    breakdown and the idle split across the program spans `span_names`
    of one trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host_lines = [], []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            devices.append((plane.name, lines.get("XLA Ops", []),
                            lines.get("XLA Modules", [])))
        elif plane.name.startswith("/host:"):
            host_lines.extend(_events(ln) for ln in plane.lines)
    if not devices:
        raise ValueError(f"{path}: no device plane")
    devices.sort()
    spans_line = next((ln for ln in host_lines
                       if any(e[2].startswith(SPAN_PREFIX) for e in ln)), [])
    win = [e for e in spans_line if e[2] == WINDOW_SPAN]
    all_ops = [e for _, ops, _ in devices for e in ops]
    if win:
        lo, hi = min(e[0] for e in win), max(e[1] for e in win)
    elif all_ops:
        lo, hi = min(e[0] for e in all_ops), max(e[1] for e in all_ops)
    else:
        raise ValueError(f"{path}: neither bench spans nor device ops")
    busy = []
    for _, ops, _ in devices:
        iv = _clip(np.asarray([(s, e) for s, e, _ in ops]).reshape(-1, 2),
                   lo, hi)
        busy.append(float(np.sum(np.diff(_union(iv), axis=1))) * 1e-9)
    window_s = (hi - lo) * 1e-9
    busy_s = float(np.mean(busy))

    _, ops0, mods0 = devices[0]
    op_s: dict[str, float] = {}
    mods = sorted(mods0)
    starts = np.asarray([m[0] for m in mods])
    for s, e, name in ops0:
        if e <= lo or s >= hi:
            continue
        k = int(np.searchsorted(starts, s, side="right")) - 1
        mod = (_short_module(mods[k][2])
               if k >= 0 and mods[k][1] >= s else "?")
        key = f"{mod}/{_short_op(name)}"
        op_s[key] = op_s.get(key, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9

    merged = _union(_clip(np.asarray([(s, e) for s, e, _ in ops0])
                          .reshape(-1, 2), lo, hi))
    edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    host = split_idle(gaps, spans_line, _host_label,
                      "outside bench spans > python")
    names = set(span_names)
    split = split_idle(gaps, [e for e in spans_line if e[2] in names])
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 100.0 * (1.0 - busy_s / window_s),
        "n_devices": len(devices),
        "op_s": op_s,
        "idle_by_span": {n: split.get(n, 0.0) for n in sorted(names)}
        | {OUTSIDE: split[OUTSIDE]},
        "breakdown": {"device_ops": _top(op_s),
                      "idle_gaps": _top({k: v for k, v in host.items()
                                         if v > 0})},
    }
