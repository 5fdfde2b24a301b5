import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import bench_tiny  # noqa: E402


@pytest.fixture
def tiny_bench(monkeypatch):
    """Point the harness at the tiny cells (``tiny.plan-1chip``,
    ``tiny.plan-4chip``, ``tiny.search-pod``) and leave the compile
    cache setting alone."""
    import harness
    bench_tiny.install(harness, monkeypatch.setattr)
    return harness.load_manifest()
