"""No involuntary full rematerialization in the sp x tp ZeRO-3 step.

Regression for the GSPMD storage-sharding leak: stage-3 params are stored
sharded over the zero axes (dp, sp); without the use-sharding constraint in
the jitted step (engine.py _build_micro_step), XLA propagated the hidden-dim
storage split into activation shardings and fell back to full replication at
every layer boundary ("Involuntary full rematerialization",
spmd_partitioner.cc:652). The reference's Ulysses path is all-to-all, never
replication (deepspeed/sequence/layer.py:44-109) — so must ours be.

Runs the compile in a subprocess to capture XLA's C++ stderr.
"""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
import jax
import numpy as np
import deepspeed_tpu
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.parallel.topology import MeshTopology

topo = MeshTopology(dp=-1, tp=2, sp=2)
cfg = LlamaConfig.tiny()
model = LlamaForCausalLM(cfg)
rng = np.random.default_rng(0)
ids = rng.integers(0, cfg.vocab_size, size=(4, 64)).astype(np.int32)
batch = {"input_ids": ids, "labels": ids}
engine, _, _, _ = deepspeed_tpu.initialize(
    model=model, mesh=topo,
    config={"train_batch_size": 4,
            "bf16": {"enabled": True},
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 3,
                                  "stage3_param_persistence_threshold": 0}})
loss = engine(batch)
engine.backward(loss)
engine.step()
print("STEP_OK", float(jax.device_get(loss)))
"""


@pytest.mark.slow
def test_sp_tp_zero3_step_has_no_involuntary_remat():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    assert "STEP_OK" in proc.stdout, out[-4000:]
    assert "Involuntary full rematerialization" not in out, (
        "GSPMD fell back to full replication at a sharding transition:\n"
        + "\n".join(l for l in out.splitlines()
                    if "Involuntary" in l)[:2000])
