"""The benchmark's own tests: CPU rehearsals at tiny sizes (Pallas in
interpret mode), never a device number. ``pytest benchmark/tests`` from the
root of the repository; tier-1 collects ``tests/`` only."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("DS_TPU_PALLAS_INTERPRET", "1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import pytest  # noqa: E402

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny")


@pytest.fixture
def tiny_bench():
    return os.path.join(TINY, "BENCHMARK.json")


@pytest.fixture
def cpu_device():
    import jax
    return jax.devices()[:1], {"platform": "cpu", "kind": "cpu", "count": 1}
