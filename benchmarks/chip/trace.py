"""Reduce a profiler trace (``.xplane.pb``) to the device numbers.

* busy: the union of the intervals in which an operation ran on a device,
  inside the window (the host span ``bench.window``), averaged over the
  devices used;
* per program: device seconds and calls of each jitted program, from the
  device's ``XLA Modules`` line, the trailing ``(id)`` of a name dropped;
* gaps: the idle stretches of the first device inside the window, longest
  first, each labelled with the innermost host span open at its middle,
  the benchmark's or the program's (what the host was doing while the
  device waited).

Host and device events share the profiler's clock.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def _program(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def load(path: str | Path) -> dict:
    """Events as (name, start_ns, end_ns): device ops and modules per
    device plane, and host spans of the benchmark and of the program
    (``program_spans.PREFIXES``)."""
    from jax.profiler import ProfileData

    from .program_spans import PREFIXES

    keep = (SPAN_PREFIX,) + PREFIXES
    pd = ProfileData.from_file(str(path))
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if m and line.name == OPS_LINE:
                ops[int(m.group(1))] = evs
            elif m and line.name == MODULES_LINE:
                modules[int(m.group(1))] = evs
            elif not m:
                host += [e for e in evs if e[0].startswith(keep)]
    return {"ops": ops, "modules": modules, "host": host}


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def window(events: dict) -> tuple[int, int]:
    """``bench.window``'s bounds, or the device operations' where the
    trace has no such span."""
    win = [e for e in events["host"] if e[0] == WINDOW_SPAN]
    if win:
        return win[0][1], win[0][2]
    allev = [e for evs in events["ops"].values() for e in evs]
    return min(e[1] for e in allev), max(e[2] for e in allev)


def idle(events: dict, lo: float, hi: float) -> list[tuple[float, float]]:
    """The first device's idle stretches between ``lo`` and ``hi``, in
    order."""
    first = sorted(events["ops"])[0]
    out, cur = [], lo
    for s, e in union(clip([(s, e) for _, s, e in events["ops"][first]], lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def reduce(events: dict, top: int = 10) -> dict:
    """The trace's numbers: ``busy_s``, ``window_s``, ``programs`` {name:
    (seconds, calls)} of the first device, ``gaps`` [(span, seconds)]."""
    if not events["ops"]:
        raise ValueError("the trace holds no device operations")
    lo, hi = window(events)
    window_ns = hi - lo
    busy = []
    for dev in sorted(events["ops"]):
        iv = union(clip([(s, e) for _, s, e in events["ops"][dev]], lo, hi))
        busy.append(sum(e - s for s, e in iv))
    first = sorted(events["ops"])[0]
    programs: dict[str, list] = {}
    for name, s, e in events["modules"].get(first, []):
        if e > lo and s < hi:
            p = programs.setdefault(_program(name), [0.0, 0])
            p[0] += (min(e, hi) - max(s, lo)) * 1e-9
            p[1] += 1
    spans = [e for e in events["host"] if e[0] != WINDOW_SPAN]
    gaps = []
    for s, e in sorted(idle(events, lo, hi), key=lambda x: x[0] - x[1])[:top]:
        mid = 0.5 * (s + e)
        open_ = [h for h in spans if h[1] <= mid < h[2]]
        label = min(open_, key=lambda h: h[2] - h[1])[0] if open_ else "no span"
        gaps.append((label, (e - s) * 1e-9))
    return {"busy_s": sum(busy) / len(busy) * 1e-9, "window_s": window_ns * 1e-9,
            "programs": {k: (v[0], v[1]) for k, v in programs.items()},
            "gaps": gaps}


def breakdown(red: dict, top: int = 10) -> dict:
    progs = sorted(red["programs"].items(), key=lambda kv: -kv[1][0])[:top]
    return {"device_ops": [[k, v[0]] for k, v in progs],
            "idle_gaps": [[k, v] for k, v in red["gaps"][:top]]}
