"""The benchmark's shared machinery: where things are, the chip check,
the compile cache and compile counter, host spans, the trace window, and
the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by its name:

    configs/<config>.json      sizes, precision, limits of the comparison
    workloads/<cell>.json      the traffic mix: which driver, its parameters
    drivers/<driver>.py        its ``Run`` class: builds, warms, measures and
                               checks one cell (``drivers/train.py`` says what
                               a driver provides)
    metrics/<metric>.py        one reader: ``read(ctx) -> float | None``, for
                               every metric but ``setup_s``

``BENCHMARK.json`` at the checkout's root says which metrics a cell
reports.  Nothing here names a cell, a configuration, a driver or a metric.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from contextlib import contextmanager
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]                 # the checkout: BENCHMARK.json, src/
OUT = ROOT / ".bench"                  # run-time files (gitignored)
CACHE_DIR = OUT / "jax_cache"          # fixed path: it is part of the key


class BenchError(RuntimeError):
    """A run that cannot be measured: no chip, missing files, bad data."""


# ------------------------------------------------------------------ #
# files found by name
# ------------------------------------------------------------------ #
def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def benchmark_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    wl = load_json(HERE / "workloads" / f"{name}.json")
    wl["name"] = name
    return wl


def config(name: str) -> dict:
    cfg = load_json(HERE / "configs" / f"{name}.json")
    cfg["name"] = name
    return cfg


def per_layer_metrics(spec: dict, cell: str) -> list[dict]:
    """The per-layer metrics whose ``workloads`` list ``cell``."""
    return [m for m in spec["per_layer"] if cell in m["workloads"]]


def end_to_end_metrics(spec: dict, cell: str) -> list[dict]:
    return [m for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])]


def _load(kind: str, name: str, module: str):
    """Execute ``<kind>/<name>.py`` as the module ``module``."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind[:-1]} {name!r}: looked for "
                         f"{path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read`` function."""
    return _load("metrics", name, f"bench_metric_{name}").read


def driver_class(name: str):
    """``drivers/<name>.py``'s ``Run`` class.  The file is a module of
    this package's ``drivers``, so its relative imports resolve here."""
    pkg = f"{__package__}.drivers"
    importlib.import_module(pkg)
    return _load("drivers", name, f"{pkg}.{name}").Run


def add_program_to_path() -> None:
    """The system under test is the checkout's ``src/repro``."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"no repro package under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def program_seed(seed: int) -> int:
    """A 31-bit seed for the program, derived from ``--seed`` (which may
    exceed 32 bits); the same ``--seed`` always gives the same one."""
    import numpy as np

    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)


# ------------------------------------------------------------------ #
# device, compile cache, compile counter
# ------------------------------------------------------------------ #
def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping every
    program however short its compile, so every later run hits it."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    Path(path).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chip(count: int):
    """The devices, or ``BenchError`` when JAX finds no TPU or fewer
    chips than the cell asks for.  Never falls back to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < count:
        raise BenchError(f"the cell needs {count} chips, JAX sees {len(devices)}")
    return devices[:count]


def device_info(devices) -> dict:
    d = devices[0]
    peaks = [(x.memory_stats() or {}).get("peak_bytes_in_use") for x in devices]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
            "memory_peak_bytes": max((p for p in peaks if p is not None),
                                     default=None)}


class CompileCounter:
    """Compile requests seen through ``jax.monitoring`` (a copy of the
    program's ``RecompileCounter`` idea, kept with the yardstick): with
    the persistent cache on, every compile or cache load of a program
    fires ``compile_requests_use_cache``; a backend compile also records
    its duration."""

    def __init__(self):
        import jax.monitoring

        self.requests = 0
        self.backend = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend += 1

    @property
    def count(self) -> int:
        return max(self.requests, self.backend)


# ------------------------------------------------------------------ #
# host spans: a profiler annotation and a perf_counter total each
# ------------------------------------------------------------------ #
class Spans:
    """Named host spans.  Each is a ``jax.profiler.TraceAnnotation`` (so a
    traced run sees it on the host plane, on the device trace's clock) and
    a running ``perf_counter`` total with a call count."""

    def __init__(self):
        self.total: dict[str, float] = {}
        self.calls: dict[str, int] = {}

    @contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.total[name] = self.total.get(name, 0.0) \
                    + time.perf_counter() - t0
                self.calls[name] = self.calls.get(name, 0) + 1

    def snapshot(self) -> dict:
        return {"total": dict(self.total), "calls": dict(self.calls)}


def delta(after: dict, before: dict) -> dict:
    """Per-key difference of two flat or span snapshots."""
    out = {}
    for k, v in after.items():
        if isinstance(v, dict):
            out[k] = delta(v, before.get(k, {}))
        elif isinstance(v, (int, float)):
            out[k] = v - before.get(k, 0)
    return out


# ------------------------------------------------------------------ #
# the traced window
# ------------------------------------------------------------------ #
TRACE_DIR = OUT / "trace"


@contextmanager
def maybe_trace(on: bool):
    """Profile the block when ``on``; yields the directory it writes."""
    if not on:
        yield None
        return
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    TRACE_DIR.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # Python frames would swamp the host
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        yield TRACE_DIR
    finally:
        jax.profiler.stop_trace()


def trace_file(directory: Path) -> Path:
    found = sorted(directory.rglob("*.xplane.pb"))
    if not found:
        raise BenchError(f"the profiler wrote no .xplane.pb under {directory}")
    return found[-1]


# ------------------------------------------------------------------ #
# the result
# ------------------------------------------------------------------ #
def metric(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(result: dict, checks: dict) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard output,
    with the checks under their own key, last."""
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAILED'}")
    out = dict(result)
    out["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                     for k, v in checks.items()}
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
