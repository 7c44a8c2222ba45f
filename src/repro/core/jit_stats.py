"""Recompile accounting for the acting hot path.

The fleet rollout's perf claims rest on *shape discipline*: after warmup,
no environment step may trigger an XLA compile.  Two observers:

``RecompileCounter``  process-global compile counter built on
                      ``jax.monitoring``.  JAX emits
                      '/jax/compilation_cache/compile_requests_use_cache'
                      once per backend compile request (including nested
                      sub-jits) and nothing on tracing-cache hits, so a
                      window with delta == 0 provably ran entirely on
                      already-compiled shapes.  The count is monotone and
                      includes every jit in the process (predictors too),
                      which is exactly what the CI smoke gate wants.

``jit_cache_size``    per-function tracing-cache size (``fn._cache_size()``)
                      for pinpointing WHICH function grew when the global
                      counter fires.
"""

from __future__ import annotations

import jax.monitoring

_COMPILE_EVENT_PREFIXES = (
    "/jax/compilation_cache/compile_requests",
)


class RecompileCounter:
    """Singleton listener over jax.monitoring compile events.

    Usage::

        counter = RecompileCounter.install()
        ...warmup...
        mark = counter.count
        ...measured work...
        recompiles = counter.count - mark   # 0 == no new XLA compiles
    """

    _instance: "RecompileCounter | None" = None

    def __init__(self) -> None:
        self.count = 0

    @classmethod
    def install(cls) -> "RecompileCounter":
        if cls._instance is None:
            inst = cls()
            # one process-wide listener, registered once: a singleton
            jax.monitoring.register_event_listener(inst._on_event)
            cls._instance = inst
        return cls._instance

    def _on_event(self, event: str, **kwargs) -> None:
        if event.startswith(_COMPILE_EVENT_PREFIXES):
            self.count += 1

    def delta_since(self, mark: int) -> int:
        return self.count - mark

    def window(self) -> "CompileWindow":
        """Context manager over a measured region::

            with counter.window() as w:
                ...measured work...
            assert w.count == 0      # no XLA compiles inside the block

        ``w.count`` is live inside the block and frozen at exit — the idiom
        the multi-device verification runner and the benchmark smoke gates
        share for their recompiles-after-warmup gates.
        """
        return CompileWindow(self)


class CompileWindow:
    """Compile count within a ``with`` region (see ``RecompileCounter.window``)."""

    def __init__(self, counter: RecompileCounter) -> None:
        self._counter = counter
        self._mark = counter.count
        self._final: int | None = None

    @property
    def count(self) -> int:
        if self._final is not None:
            return self._final
        return self._counter.count - self._mark

    def __enter__(self) -> "CompileWindow":
        self._mark = self._counter.count
        self._final = None
        return self

    def __exit__(self, *exc) -> bool:
        self._final = self._counter.count - self._mark
        return False


def jit_cache_size(fn) -> int:
    """Tracing-cache entry count of a ``jax.jit``-wrapped function."""
    return fn._cache_size()
