"""Named host spans of the program, on the profiler's clock.

``span(name)`` marks one phase of the training path (an env step's Q
dispatch, host chemistry, the property tier, replay sampling, ...).  It
opens a ``jax.profiler.TraceAnnotation``, so a traced run shows the span
on the host plane beside the device's operations, and it adds the span's
wall seconds to a process-wide total with a call count and a self time:
the seconds less those of the spans opened directly inside it on the same
thread.  ``snapshot()`` returns the totals as plain numbers, so two
snapshots subtract.

The profiler's own start and stop is the switch for the timeline; the
totals are always kept (one ``perf_counter`` pair and a locked dict update
per span).  Open spans per phase (per env step, per predict call, per
update), never per slot, molecule or worker.

Span names start with the layer: ``train.``, ``rollout.``, ``chem.``,
``predict.`` or ``learner.``.  This module sits at the package root, like
``repro.faults``, because ``repro.predictors`` may not import
``repro.core``.
"""

from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

_lock = threading.Lock()
_totals: dict[str, list] = {}      # name -> [seconds, self seconds, calls]
_local = threading.local()         # .stack: children's seconds per open span


class span:
    """Context manager for one phase; ``with span(name) as t`` leaves the
    span's wall seconds in ``t.s`` once the block has closed, the same
    reading the totals received."""

    __slots__ = ("name", "s", "_t0", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.s = 0.0

    def __enter__(self) -> "span":
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(0.0)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        s = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        stack = _local.stack
        children = stack.pop()
        if stack:
            stack[-1] += s
        self.s = s
        with _lock:
            tot = _totals.get(self.name)
            if tot is None:
                tot = _totals[self.name] = [0.0, 0.0, 0]
            tot[0] += s
            tot[1] += s - children
            tot[2] += 1
        return False


def snapshot() -> dict[str, dict[str, float]]:
    """``{name: {"s": seconds, "self_s": self seconds, "n": calls}}`` of
    every span closed so far in this process."""
    with _lock:
        return {k: {"s": v[0], "self_s": v[1], "n": v[2]}
                for k, v in _totals.items()}
