"""Shared driver for the multi-device equivalence suite.

Each scenario runs as a ``repro.launch.verify`` SUBPROCESS because
``--xla_force_host_platform_device_count`` must be set in ``XLA_FLAGS``
before jax initialises — the parent pytest process keeps its own device
count (whatever CI forced), the children always force the verifier's fixed
device pool and size their mesh with ``--nd``.  Children of one scenario
are launched concurrently: each is single-scenario and mostly compile-bound,
so overlapping them roughly halves suite wall time on a 2-core host.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import numpy as np

from repro.launch.verify import assert_nd_equivalent

REPO = Path(__file__).resolve().parents[2]
SRC = REPO / "src"
CHILD_TIMEOUT_S = 600


def run_cells(tmp_path, nds, **kw) -> dict[int, dict[str, np.ndarray]]:
    """Run one scenario at every requested mesh size concurrently; return
    ``{nd: report arrays}`` (see repro.launch.verify for the report keys)."""
    procs = {}
    for nd in nds:
        out = Path(tmp_path) / f"nd{nd}.npz"
        cmd = [sys.executable, "-m", "repro.launch.verify",
               "--nd", str(nd), "--out", str(out)]
        for k, v in kw.items():
            cmd += ["--" + k.replace("_", "-"), str(v)]
        env = dict(os.environ)
        env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
        procs[nd] = (subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT, text=True), out)
    results = {}
    try:
        for nd, (p, out) in procs.items():
            stdout, _ = p.communicate(timeout=CHILD_TIMEOUT_S)
            assert p.returncode == 0, \
                f"verify child nd={nd} exited {p.returncode}:\n{stdout}"
            with np.load(out) as z:
                results[nd] = {k: z[k] for k in z.files}
    finally:
        # a hung child (and its unreaped siblings) must not outlive the
        # test and starve every later scenario of the host's cores
        for p, _ in procs.values():
            if p.poll() is None:
                p.kill()
                p.communicate()
    return results


def assert_equivalent(ref: dict, other: dict, ctx: str) -> None:
    """The run must reproduce the reference: transitions and rewards bit
    for bit, losses and parameters bit for bit at equal nd and within the
    stated cross-nd tolerance otherwise (``repro.launch.verify.
    assert_nd_equivalent``, the one contract ``chip_smoke.py --chips 4``
    also holds)."""
    assert_nd_equivalent(ref, other, ctx)
