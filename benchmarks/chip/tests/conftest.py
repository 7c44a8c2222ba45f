import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2]          # benchmarks/
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src"), str(Path(__file__).resolve().parent)):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """The benchmark tree copied under ``tmp_path`` with tiny sizes, the
    harness pointed at it; yields the copy's benchmark directory."""
    import tiny as t

    here = t.make_tree(tmp_path)
    t.point_harness_at(monkeypatch, here)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    yield here


def pytest_configure(config):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
