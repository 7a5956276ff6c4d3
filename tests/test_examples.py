"""The shipped example scripts run end to end on the CPU mesh (reference
DeepSpeedExamples smoke coverage)."""

import os
import subprocess
import sys

import numpy as np
import pytest


def _run_example(script, argv, timeout=420):
    """Run an example in a child on the virtual 8-device CPU mesh
    (``JAX_PLATFORMS=cpu`` in the child's environment)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "") +
               " --xla_force_host_platform_device_count=8")
    path = os.path.join(repo, "examples", script)
    code = (
        f"import runpy, sys; sys.argv = {argv!r};"
        f"runpy.run_path({path!r}, run_name='__main__')")
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_train_gpt2_example(tmp_path):
    r = _run_example("train_gpt2.py", ["train_gpt2.py", "--steps", "6"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "saved checkpoint" in r.stdout
    losses = [float(l.rsplit(" ", 1)[1]) for l in r.stdout.splitlines()
              if l.startswith("step ")]
    assert losses and losses[-1] < losses[0]


def test_migrate_from_deepspeed_example():
    pytest.importorskip("torch")  # checkpoint synthesis writes .pt shards
    r = _run_example("migrate_from_deepspeed.py",
                     ["migrate_from_deepspeed.py", "--steps", "3"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "loaded 4 parameters (+ moments) at step 100" in r.stdout
    assert "resumed 3 steps" in r.stdout


@pytest.mark.slow
def test_train_infinity_example():
    r = _run_example("train_infinity.py",
                     ["train_infinity.py", "--steps", "6", "--layers", "2"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "streamed blocks: 2" in r.stdout
    losses = [float(l.rsplit(" ", 1)[1]) for l in r.stdout.splitlines()
              if l.startswith("step ")]
    assert losses and losses[-1] < losses[0]
