"""Activation checkpointing — rematerialization on TPU.

Reference: ``runtime/activation_checkpointing/checkpointing.py`` — Megatron-
compatible ``checkpoint()`` (:990) / ``CheckpointFunction`` (:485) with
partitioned activations across MP ranks (:374), CPU checkpointing,
contiguous buffers and a CUDA RNG tracker (:123).

TPU mapping: the capability is ``jax.checkpoint`` (remat) — XLA recomputes
the forward inside backward instead of saving activations, trading FLOPs for
HBM exactly as the reference does, but scheduled by the compiler:

- ``partition_activations``: unnecessary as a mechanism — under GSPMD a saved
  residual inherits the sharding of the computation that produced it, so
  activations are already partitioned over the sp/tp axes. The flag is
  accepted and recorded.
- ``cpu_checkpointing``: maps to XLA host offload — the ``offload-dots``
  policy stores matmul results on ``pinned_host`` memory instead of HBM.
- ``contiguous_memory_optimization`` / ``synchronize`` / ``profile``: CUDA
  allocator/stream concerns; accepted for config parity, owned by XLA.
- RNG: JAX PRNG keys are functional, so the reference's
  ``CudaRNGStatesTracker`` (stash/restore CUDA RNG state so dropout matches
  between the two forwards) is automatic — ``jax.checkpoint`` replays the
  same key. A tracker shim keeps Megatron-style call sites working.

``checkpoint(fn, *args)`` is the drop-in functional API; ``checkpoint_wrapper``
wraps a flax module (``nn.remat``); scanned-block models apply the policy via
``policy_by_name`` (models/llama.py, models/gpt2.py).
"""

import contextlib
import functools

import jax

from deepspeed_tpu.utils.logging import logger

_CONFIG = {
    "partition_activations": False,
    "contiguous_checkpointing": False,
    "num_checkpoints": None,
    "checkpoint_in_cpu": False,
    "synchronize": False,
    "profile": False,
    "policy": "everything",
}


def policy_by_name(name, checkpoint_in_cpu=False):
    """Named remat policies (config key ``activation_checkpointing.policy``):

    - "everything": recompute everything (max memory saving; the reference's
      full activation checkpointing) — ``nothing_saveable``
    - "dots": save matmul outputs, recompute elementwise —
      ``dots_with_no_batch_dims_saveable``, usually the best TPU trade
    - "nothing": no remat (save all activations)

    ``checkpoint_in_cpu`` lifts saved dots to pinned host memory (the
    reference's CPU checkpointing). ``policy="nothing"`` (no remat) takes
    precedence — there is nothing to offload if everything is saved."""
    cp = jax.checkpoint_policies
    # the Pallas flash kernel is not a dot_general: what its backward reads
    # of it (output and log-sum-exp, named inside its custom_vjp) is saved
    # by name, on the device, beside the attention output that callers see
    # — otherwise backward re-runs the whole attention kernel
    from deepspeed_tpu.ops.pallas.flash_attention import RESIDUAL_NAMES
    flash = cp.save_only_these_names("flash_attn_out", *RESIDUAL_NAMES)
    if checkpoint_in_cpu and name != "nothing":
        # dots offload to pinned host. Written out: save_from_both_policies
        # takes booleans only and raises on the offload policy's answers
        offload = cp.offload_dot_with_no_batch_dims("device", "pinned_host")
        return lambda prim, *args, **params: (
            flash(prim, *args, **params) or offload(prim, *args, **params))
    return {
        "everything": cp.nothing_saveable,
        "dots": cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable, flash),
        "nothing": cp.everything_saveable,
    }[name]


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, num_checkpoints=None,
              checkpoint_in_cpu=None, synchronize=None, profile=None):
    """reference ``checkpointing.configure`` (:899) — record the global
    activation-checkpointing options."""
    if deepspeed_config is not None:
        ac = getattr(deepspeed_config, "activation_checkpointing", None)
        if ac is not None:
            _CONFIG.update(partition_activations=ac.partition_activations,
                           contiguous_checkpointing=ac.contiguous_memory_optimization,
                           num_checkpoints=ac.number_checkpoints,
                           checkpoint_in_cpu=ac.cpu_checkpointing,
                           synchronize=ac.synchronize_checkpoint_boundary,
                           profile=ac.profile, policy=ac.policy)
    for k, v in dict(partition_activations=partition_activations,
                     contiguous_checkpointing=contiguous_checkpointing,
                     num_checkpoints=num_checkpoints,
                     checkpoint_in_cpu=checkpoint_in_cpu,
                     synchronize=synchronize, profile=profile).items():
        if v is not None:
            _CONFIG[k] = v


def is_configured():
    return True


def current_policy():
    return policy_by_name(_CONFIG["policy"], _CONFIG["checkpoint_in_cpu"])


def checkpoint(function, *args):
    """Drop-in for reference ``checkpoint(function, *args)`` (:990): runs
    ``function`` now and rematerializes it during backward."""
    return jax.checkpoint(function, policy=current_policy(),
                          prevent_cse=False)(*args)


def checkpoint_wrapper(target, **remat_kwargs):
    """Wrap a flax ``nn.Module`` class or a plain function for remat."""
    import flax.linen as nn
    if isinstance(target, type) and issubclass(target, nn.Module):
        return nn.remat(target, policy=current_policy(), prevent_cse=False,
                        **remat_kwargs)
    return jax.checkpoint(target, policy=current_policy(), prevent_cse=False)


def non_reentrant_checkpoint(function, *args):
    """reference :725 — identical under XLA (there is no reentrant autograd)."""
    return checkpoint(function, *args)


def partition_activations_in_checkpoint(partition_activation):
    """reference :1038 — recorded only; GSPMD already shards residuals."""
    _CONFIG["partition_activations"] = partition_activation
    logger.info(f"partition_activations={partition_activation} (GSPMD shards "
                "saved residuals along the mesh automatically)")


# --------------------------------------------------------------------------
# RNG tracker shim (reference CudaRNGStatesTracker :123). JAX PRNG is
# functional — remat replays the same key, so dropout is consistent between
# the two forwards without stashing device RNG state. The shim preserves the
# Megatron call-site API for ported model code.
# --------------------------------------------------------------------------
class RNGStatesTracker:

    def __init__(self):
        self.states_ = {}

    def reset(self):
        self.states_.clear()

    def get_states(self):
        return dict(self.states_)

    def set_states(self, states):
        self.states_ = dict(states)

    def add(self, name, seed):
        if name in self.states_:
            raise Exception(f"seed {name} already exists")
        self.states_[name] = jax.random.PRNGKey(seed)

    @contextlib.contextmanager
    def fork(self, name="model-parallel-rng"):
        if name not in self.states_:
            raise Exception(f"rng state {name} is not added")
        key, sub = jax.random.split(self.states_[name])
        self.states_[name] = key
        yield sub


_RNG_TRACKER = RNGStatesTracker()


def get_cuda_rng_tracker():
    return _RNG_TRACKER


def model_parallel_cuda_manual_seed(seed):
    """reference :182 — seed the tracker (data-parallel + model-parallel
    streams)."""
    _RNG_TRACKER.reset()
    _RNG_TRACKER.add("model-parallel-rng", seed + 2718)
    _RNG_TRACKER.add("data-parallel-rng", seed)


def reset():
    _RNG_TRACKER.reset()
