"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

The window is the span of the bench's own ``bench/run_block``
annotations (`jax.profiler.TraceAnnotation` in the harness), or of the
device ops where there are none.  For each device plane
(``/device:TPU:<i>``) the busy time is the union of its ``XLA Ops``
intervals inside the window; ``busy_s`` averages it over the devices.

The ``breakdown`` holds the ten device ops with the most time (named
``<module>/<op>``) and the idle gaps of device 0, summed by what the
host was doing at each gap's midpoint: the innermost ``bench/``
annotation and the deepest event under it on the same host thread
(a runtime call such as ``PJRT_LoadedExecutable_Execute``), or
``python`` where no runtime call was open.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

WINDOW_SPAN = "bench/run_block"
SPAN_PREFIX = "bench/"
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


def _stab(events: list, points: np.ndarray) -> list[list[str]]:
    """For each sorted point, the names of the events covering it, from
    the outermost in; `events` are (start, end, name) on one thread."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    out, stack, i = [], [], 0
    for p in points:
        while i < len(events) and events[i][0] <= p:
            stack.append(events[i])
            i += 1
        out.append([e[2] for e in stack if e[0] <= p <= e[1]])
        stack = [e for e in stack if e[1] >= p]
    return out


def reduce(path: str) -> dict:
    """Busy and window seconds, idle share and the breakdown of one
    trace file."""
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
    op_time: dict[str, float] = {}
    mods = sorted(mods0)
    starts = np.asarray([m[0] for m in mods])
    for s, e, name in ops0:
        if e <= lo or s >= hi:
            continue
        k = int(np.searchsorted(starts, s, side="right")) - 1
        mod = (_short_module(mods[k][2])
               if k >= 0 and mods[k][1] >= s else "?")
        key = f"{mod}/{_short_op(name)}"
        op_time[key] = op_time.get(key, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
    device_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]

    merged = _union(_clip(np.asarray([(s, e) for s, e, _ in ops0])
                          .reshape(-1, 2), lo, hi))
    edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    gap_time: dict[str, float] = {}
    if len(gaps):
        mids = 0.5 * (gaps[:, 0] + gaps[:, 1])
        for (g0, g1), names in zip(gaps, _stab(spans_line, mids)):
            bench = [n for n in names if n.startswith(SPAN_PREFIX)]
            rest = [n for n in names if not n.startswith(SPAN_PREFIX)]
            label = (bench[-1] if bench else "outside bench spans") + \
                " > " + (rest[-1] if rest else "python")
            gap_time[label] = gap_time.get(label, 0.0) + (g1 - g0) * 1e-9
    idle_gaps = sorted(gap_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 100.0 * (1.0 - busy_s / window_s),
        "n_devices": len(devices),
        "breakdown": {"device_ops": [[k, v] for k, v in device_ops],
                      "idle_gaps": [[k, v] for k, v in idle_gaps]},
    }
