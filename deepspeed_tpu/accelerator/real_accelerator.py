"""Accelerator selection (mirrors reference ``accelerator/real_accelerator.py:51-140``).

The reference probes imports and honors a ``DS_ACCELERATOR`` env override; here
the probe is over JAX platforms. TPU selects ``TPU_Accelerator``; anything else (cpu, gpu) still routes through the same
class since all device access is via JAX regardless of platform — only the
name/capabilities differ.
"""

import os

_accelerator = None


def get_accelerator():
    global _accelerator
    if _accelerator is not None:
        return _accelerator
    override = os.environ.get("DST_ACCELERATOR")
    from deepspeed_tpu.accelerator.tpu_accelerator import TPU_Accelerator
    _accelerator = TPU_Accelerator()
    if override:
        _accelerator._name = override
    return _accelerator


def set_accelerator(accel):
    """Injection hook (reference ``real_accelerator.py`` set_accelerator)."""
    global _accelerator
    _accelerator = accel
