"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the TPU analog of the reference's
in-process multi-rank harness, ``tests/unit/common.py:373`` DistributedTest with
world_size 1/2/4): ``xla_force_host_platform_device_count=8`` gives eight XLA
CPU devices so every sharding/collective path executes real multi-device code.
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

_SLOW_LIST = os.path.join(os.path.dirname(__file__), "slow_tests.txt")


def pytest_collection_modifyitems(config, items):
    """Apply the ``slow`` marker from tests/slow_tests.txt (measured nodeids,
    regenerated from ``--durations`` output). The fast lane
    ``pytest -m "not slow"`` is what CI and hosts with the TPU attached run;
    see README "Test lanes"."""
    try:
        with open(_SLOW_LIST) as f:
            slow = {ln.strip() for ln in f if ln.strip() and not ln.startswith("#")}
    except FileNotFoundError:
        return
    # one slow parametrization marks every sibling (same underlying cost)
    slow_prefixes = {s.split("[")[0] for s in slow}
    for item in items:
        if item.nodeid in slow or item.nodeid.split("[")[0] in slow_prefixes:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _reset_groups():
    """Each test gets a fresh global topology registry."""
    from deepspeed_tpu.parallel import groups
    groups.reset()
    yield
    groups.reset()


@pytest.fixture
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Pallas kernels in interpret mode — the only way they run on this CPU
    backend. The program takes the mode from ``DS_TPU_PALLAS_INTERPRET`` and
    from nothing else (never from the platform), so tests that drive a kernel
    path which has no XLA twin ask for it here."""
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
