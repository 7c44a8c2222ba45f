#!/usr/bin/env python3
"""Record the small device trace that ``tests/test_trace.py`` reduces.

    python3 benchmarks/chip/record_trace.py <out_dir>

Runs on the chip only.  Inside a ``bench.window`` host span,
three calls of one jitted matmul program (``bench_probe``) separated by
host spans (``bench.probe_gap``) that sleep, so the trace holds known
busy intervals, known idle gaps and the host span open in each gap.  Writes the profile under ``<out_dir>`` and prints the
planes, lines and a few events of each, which is how the reducer's plane
and line names were found.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path


def main(argv: list[str]) -> int:
    out = Path(argv[0] if argv else "chiprun_out/trace_probe").resolve()
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: this script records a device trace on the chip")

    @jax.jit
    def bench_probe(a, b):
        return jnp.tanh(a @ b) @ b

    a = jnp.ones((2048, 2048), jnp.float32)
    b = jnp.full((2048, 2048), 1e-3, jnp.float32)
    bench_probe(a, b).block_until_ready()      # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.probe_gap"):
                time.sleep(0.02)
            bench_probe(a, b).block_until_ready()
    jax.profiler.stop_trace()

    from jax.profiler import ProfileData
    path = next(out.rglob("*.xplane.pb"))
    print(f"trace {path} {path.stat().st_size} bytes")
    for plane in ProfileData.from_file(str(path)).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r} {len(evs)} events")
            for ev in evs[:6]:
                print(f"    {ev.name!r} start {ev.start_ns} dur {ev.duration_ns}")
    d = jax.devices()[0]
    print("device", d.platform, d.device_kind, len(jax.devices()))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
