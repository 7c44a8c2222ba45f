"""The program's own host spans, read from a traced run's profile.

The program opens a ``jax.profiler.TraceAnnotation`` for each phase of its
training path, named with one of ``PREFIXES`` (the namespace is held here,
not imported from the program).  From the ``.xplane.pb`` of a traced run:

* ``host_spans(path)``: those spans and ``bench.window`` as (name,
  start_ns, end_ns), on every host thread;
* ``totals(host)``: seconds and calls of each program span inside the
  window, the numbers the per-layer readers divide;
* ``idle_by_span(events)``: the first device's idle time in the window
  summed under the innermost host span open at each instant (``events``
  as ``trace.load`` gives them);
* ``window_totals(ctx)``: ``totals`` of the run's own profile, read once
  per file; ``None`` for an untraced run.

A program that opens no such span gives empty totals, and a reader that
needs one reports nothing.
"""

from __future__ import annotations

from pathlib import Path

from . import harness, trace

PREFIXES = ("train.", "rollout.", "chem.", "predict.", "learner.")

_read: dict = {}     # (path, mtime_ns, size) -> totals


def host_spans(path: str | Path) -> list[tuple[str, int, int]]:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith(PREFIXES) or e.name == trace.WINDOW_SPAN]
    return out


def totals(host: list) -> dict[str, dict[str, float]]:
    """``{name: {"s": seconds, "n": calls}}`` of the program spans that
    lie inside ``bench.window`` (every span, if there is no window)."""
    win = [h for h in host if h[0] == trace.WINDOW_SPAN]
    lo, hi = (win[0][1], win[0][2]) if win else (float("-inf"), float("inf"))
    out: dict[str, dict[str, float]] = {}
    for name, s, e in host:
        if name != trace.WINDOW_SPAN and lo <= s and e <= hi:
            t = out.setdefault(name, {"s": 0.0, "n": 0})
            t["s"] += (e - s) * 1e-9
            t["n"] += 1
    return out


def window_totals(ctx: dict) -> dict | None:
    if ctx["trace"] is None:
        return None
    path = harness.trace_file(harness.TRACE_DIR)
    st = path.stat()
    key = (str(path), st.st_mtime_ns, st.st_size)
    if key not in _read:
        _read[key] = totals(host_spans(path))
    return _read[key]


def idle_by_span(events: dict) -> dict[str, float]:
    """Seconds of the first device's idle time in the window under each
    innermost open host span (the shortest of those open; ``"no span"``
    where none is); the values sum to the window less that device's busy
    time."""
    lo, hi = trace.window(events)
    idle = trace.idle(events, lo, hi)
    spans = [h for h in events["host"]
             if h[0] != trace.WINDOW_SPAN and h[2] > lo and h[1] < hi]
    cuts = sorted({lo, hi} | {max(h[1], lo) for h in spans}
                  | {min(h[2], hi) for h in spans})
    starts: dict[float, list] = {}
    for h in spans:
        starts.setdefault(max(h[1], lo), []).append(h)
    out: dict[str, float] = {}
    open_: list = []
    k = 0
    for a, b in zip(cuts, cuts[1:]):
        open_ = [h for h in open_ if h[2] > a] + starts.get(a, [])
        label = min(open_, key=lambda h: h[2] - h[1])[0] if open_ else "no span"
        while k < len(idle) and idle[k][1] <= a:
            k += 1
        ns, j = 0.0, k
        while j < len(idle) and idle[j][0] < b:
            ns += min(idle[j][1], b) - max(idle[j][0], a)
            j += 1
        if ns > 0:
            out[label] = out.get(label, 0.0) + ns * 1e-9
    return out
