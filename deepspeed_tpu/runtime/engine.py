"""DeepSpeedEngine — the TPU-native training engine.

Mirrors the capability surface of the reference ``DeepSpeedEngine``
(``deepspeed/runtime/engine.py:180``): ``forward`` (:1794) / ``backward``
(:1933) / ``step`` (:2132), gradient accumulation with boundary semantics,
mixed precision (fp16 dynamic loss scaling / bf16), ZeRO 0-3, gradient
clipping, LR scheduling, checkpoint save/load (:3056/:2712), monitoring and
wall-clock timers.

Architecture (deliberately NOT a transliteration): the reference drives eager
PyTorch with backward hooks, bucketed NCCL reduce-scatter and stream juggling.
Here the whole micro-step (forward+backward+grad-accumulate) and the whole
apply-step (unscale, clip, optimizer, loss-scale update, recast) are each ONE
jitted XLA program over a sharded state pytree; ZeRO partitioning is a set of
GSPMD sharding constraints (see ``runtime/zero/partition.py``) and XLA emits
the reduce-scatters/all-gathers the reference issues by hand. The
forward/backward/step imperative API is preserved on top: ``forward`` runs the
fused micro-step and stages the result, ``backward`` commits it, ``step``
applies the optimizer at the gradient-accumulation boundary.
"""

import json
import os
import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deepspeed_tpu.ops.adam import build_optimizer, set_lr
from deepspeed_tpu.resilience import CorruptCheckpointError, faults as _faults
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.parallel.topology import MeshTopology
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
from deepspeed_tpu.runtime.fp16.loss_scaler import (LossScaleState, init_loss_scale_state,
                                                    update_loss_scale)
from deepspeed_tpu.runtime.lr_schedules import LRSchedulerShim, get_lr_schedule
from deepspeed_tpu.runtime.utils import (clip_grads_by_global_norm, constrain_tree,
                                         count_parameters, global_norm, has_overflow,
                                         tree_cast, tree_where, tree_zeros_like)
from deepspeed_tpu.runtime.zero.partition import ZeroPartitioner
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER,
                                       STEP_GLOBAL_TIMER, SynchronizedWallClockTimer,
                                       ThroughputTimer)


class TrainState(NamedTuple):
    """The engine's entire training state as one sharded pytree."""
    params: Any            # working precision (bf16/fp16/fp32)
    master: Any            # fp32 master copy (None in pure-fp32 training)
    opt_state: Any
    grad_acc: Any          # gradient accumulation buffer (grad_accum_dtype)
    scale: LossScaleState
    global_step: jnp.ndarray
    skipped: jnp.ndarray   # overflow-skipped step count (device-side: no per-step host sync)
    rng: jnp.ndarray
    qgz_residual: Any = None  # qgZ error-feedback carry (stacked grad layout)


class StepStats(NamedTuple):
    grad_norm: jnp.ndarray
    overflow: jnp.ndarray
    lr: jnp.ndarray
    loss_scale: jnp.ndarray


class OptimizerShim:
    """Minimal object with the torch-optimizer surface the reference returns
    from initialize() — param_groups for LR introspection/HF compat.

    ``state_dict``/``load_state_dict`` round-trip the real optimizer state so
    HF-Trainer-side checkpointing does not silently drop it."""

    def __init__(self, engine, base_lr):
        self._engine = engine
        self.param_groups = [{"lr": base_lr}]

    @staticmethod
    def _fetch(leaf):
        # multi-host safe: leaves spanning non-addressable devices need the
        # cross-process gather; device_get alone raises there
        if getattr(leaf, "is_fully_addressable", True):
            return jax.device_get(leaf)
        from jax.experimental import multihost_utils
        return multihost_utils.process_allgather(leaf, tiled=True)

    def state_dict(self):
        st = self._engine.state
        if st is None:
            logger.warning("OptimizerShim.state_dict(): engine state not yet "
                           "initialized; returning empty dict")
            return {}
        sd = {"opt_state": jax.tree.map(self._fetch, st.opt_state),
              "global_step": int(self._fetch(st.global_step)),
              "scale": jax.tree.map(self._fetch, st.scale),
              "skipped": int(self._fetch(st.skipped))}
        if self._engine._offload is not None:
            # ZeRO-Offload: most (ratio=1.0: all) moments live in the host tier
            sd["offload"] = self._engine._offload.state_dict()
        if self._engine._param_store is not None:
            # ZeRO-Infinity param tier: streamed masters + moments are host-side
            sd["param_offload"] = self._engine._param_store.state_dict()
        return sd

    def load_state_dict(self, sd):
        if not sd:
            return
        st = self._engine.state
        if st is None:
            # lazy init (no model_parameters yet): defer and apply at init
            self._engine._pending_opt_state = sd
            return
        opt = jax.tree.map(
            lambda cur, new: jax.device_put(jnp.asarray(new, cur.dtype), cur.sharding),
            st.opt_state, sd["opt_state"])
        gs = jax.device_put(jnp.int32(sd.get("global_step", 0)),
                            st.global_step.sharding)
        repl = {"opt_state": opt, "global_step": gs}
        if "scale" in sd:
            repl["scale"] = jax.tree.map(
                lambda cur, new: jax.device_put(jnp.asarray(new, cur.dtype),
                                                cur.sharding),
                st.scale, LossScaleState(*sd["scale"]))
            repl["skipped"] = jax.device_put(jnp.int32(sd.get("skipped", 0)),
                                             st.skipped.sharding)
        self._engine.state = st._replace(**repl)
        if "offload" in sd and self._engine._offload is not None:
            self._engine._offload.load_state_dict(sd["offload"])
            self._engine._refresh_working_from_master()
        if "param_offload" in sd and self._engine._param_store is not None:
            self._engine._param_store.load_state_dict(sd["param_offload"])

    def zero_grad(self, set_to_none=True):
        pass  # grads live in the engine's accumulation buffer

    def step(self):
        raise RuntimeError("Call engine.step() — the engine owns the optimizer step")


# optimizer-name constants (reference runtime/engine.py:84)
ADAM_OPTIMIZER = "adam"
LAMB_OPTIMIZER = "lamb"


class DeepSpeedEngine:

    def __init__(self,
                 config=None,
                 model=None,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mesh=None,
                 collate_fn=None,
                 rng=None,
                 param_specs=None,
                 dont_change_device=False):
        self.config = config if isinstance(config, DeepSpeedConfig) else DeepSpeedConfig(config)
        self.module = model
        self._user_param_specs = param_specs

        # --- topology (reference engine.py:1094 _configure_distributed_model) ---
        if mesh is not None:
            if isinstance(mesh, MeshTopology):
                self.topology = mesh
                # the explicit mesh IS the process topology: install it so
                # model-level groups.get_topology() consumers (ring attention,
                # MoE group getters) see the same axes as the engine
                groups.initialize(mesh_topology=mesh)
            else:
                raise ValueError("pass a deepspeed_tpu.parallel.topology.MeshTopology")
        else:
            self.topology = groups.initialize(ep_size=self.config.expert_parallel_size,
                                              config=self.config)
        self.mesh = self.topology.mesh

        # --- elasticity enforcement (reference engine.py:243, elasticity.py:233) ---
        if self.config.elasticity_config.enabled:
            from deepspeed_tpu.elasticity import compute_elastic_config
            from deepspeed_tpu.elasticity.elasticity import ElasticityError
            ec = self.config.elasticity_config
            has_batch_info = (self.config.train_batch_size is not None
                              or self.config.train_micro_batch_size_per_gpu is not None
                              or self.config.gradient_accumulation_steps is not None)
            if has_batch_info and not ec.ignore_non_elastic_batch_info:
                raise ElasticityError(
                    "elasticity is enabled but the config also fixes batch sizes; "
                    "set ignore_non_elastic_batch_info to override (reference "
                    "elasticity/config.py semantics)")
            world = self.topology.data_parallel_size
            fb, _, mbs = compute_elastic_config(self.config._param_dict,
                                                world_size=world,
                                                return_microbatch=True)
            self.config.train_batch_size = fb
            self.config.train_micro_batch_size_per_gpu = mbs
            self.config.gradient_accumulation_steps = fb // (mbs * world)

        # --- batch arithmetic (reference config.py:789) ---
        tb, mb, gas = self.config.resolve_batch_params(self.topology.data_parallel_size)
        self.train_batch_size_value = tb
        self.micro_batch_size = mb
        self.gradient_accumulation_steps_value = gas

        # --- precision ---
        self.fp16_enabled = self.config.fp16.enabled
        self.bf16_enabled = self.config.bf16.enabled
        if self.fp16_enabled:
            self.working_dtype = jnp.float16
        elif self.bf16_enabled:
            self.working_dtype = jnp.bfloat16
        else:
            self.working_dtype = jnp.float32
        self.mixed_precision = self.working_dtype != jnp.float32
        self.dynamic_loss_scale = self.fp16_enabled and not (self.config.fp16.loss_scale > 0)
        gad = self.config.data_types.grad_accum_dtype
        self.grad_accum_dtype = {None: jnp.float32, "fp32": jnp.float32,
                                 "fp16": jnp.float16, "bf16": jnp.bfloat16}[gad]

        # --- model fn normalization ---
        self._model_fn = self._normalize_model_fn(model)

        # --- optimizer (reference engine.py:1228 _configure_optimizer) ---
        # Accepts: a name string, an optax.GradientTransformation (the functional
        # analog of the reference's client torch optimizer), a zero-arg/params
        # factory returning one, or None (use the config section).
        opt_cfg = self.config.optimizer
        self._tx = None
        if optimizer is not None and not isinstance(optimizer, str):
            tx = optimizer
            if callable(tx) and not isinstance(tx, optax.GradientTransformation):
                try:
                    tx = tx(model_parameters)
                except TypeError:
                    tx = tx()
            if not isinstance(tx, optax.GradientTransformation):
                raise ValueError(
                    "client optimizer must be an optax.GradientTransformation or a "
                    f"factory returning one, got {type(optimizer)}")
            self._tx, self._base_lr = tx, opt_cfg.params.get("lr", 1e-3)
        else:
            opt_name = optimizer if isinstance(optimizer, str) else opt_cfg.type
            self._tx, self._base_lr = build_optimizer(opt_name, opt_cfg.params)
        self.optimizer = OptimizerShim(self, self._base_lr)

        # --- LR schedule (reference engine.py:914) ---
        # Accepts: a name string, a callable step->lr (client schedule), or None.
        if lr_scheduler is not None and not isinstance(lr_scheduler, str):
            if not callable(lr_scheduler):
                raise ValueError("client lr_scheduler must be callable: step -> lr")
            self._schedule_fn = lr_scheduler
        else:
            sched_name = lr_scheduler if isinstance(lr_scheduler, str) else self.config.scheduler.type
            self._schedule_fn = get_lr_schedule(sched_name, self.config.scheduler.params,
                                                base_lr=opt_cfg.params.get("lr", self._base_lr))
        self.lr_scheduler = LRSchedulerShim(self._schedule_fn, engine=self)

        # --- dataloader (reference engine.py:1699 deepspeed_io) ---
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = DeepSpeedDataLoader(
                training_data, batch_size=self.micro_batch_size * self.topology.data_parallel_size,
                collate_fn=collate_fn, topology=self.topology)
            if self.config.prefetch_batches:
                # background assembly + ahead-of-time sharded device_put:
                # the host input pipeline overlaps the device step
                from deepspeed_tpu.runtime.dataloader import PrefetchLoader
                self.training_dataloader = PrefetchLoader(
                    self.training_dataloader,
                    sharding=self.topology.batch_sharding(),
                    depth=self.config.prefetch_batches)

        # --- monitoring / timers (reference engine.py:252, 2238) ---
        from deepspeed_tpu.monitor.monitor import MonitorMaster
        self.monitor = MonitorMaster(self.config)
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=tb, steps_per_output=self.config.steps_per_print,
            logging_fn=lambda m: log_dist(m, ranks=[0]))
        self.wall_clock_breakdown = self.config.wall_clock_breakdown

        # comms logging
        import deepspeed_tpu.comm as dist
        dist.configure(comms_config=self.config.comms_config)

        # unified telemetry (docs/OBSERVABILITY.md): configure the
        # process-global pipeline ONLY when this config enables it — a
        # disabled section must not clobber a pipeline another caller
        # (tests, benches) already switched on
        from deepspeed_tpu import telemetry
        if self.config.telemetry_config.enabled:
            telemetry.configure(config=self.config.telemetry_config)
        self._telemetry_monitor = bool(self.config.telemetry_config.monitor)

        # resilience (docs/RESILIENCE.md): fault injection, preemption-aware
        # save, step watchdog. Fault arming is config-driven here; the
        # DS_TPU_FAULTS env arms lazily even without a config section.
        rcfg = self.config.resilience_config
        if rcfg.faults:
            _faults.configure(rcfg.faults, seed=rcfg.fault_seed)
        # flight recorder (telemetry/flightrec.py): point bundles at the
        # configured destination and snapshot a config digest into every
        # bundle this process flushes
        from deepspeed_tpu.telemetry import flightrec as _flightrec
        if rcfg.postmortem_dir:
            _flightrec.configure(dir=rcfg.postmortem_dir)
        _flightrec.register_collector("engine/config", self._config_digest)
        self._last_save_dir = None
        self._preemption = None
        if rcfg.preemption.enabled:
            from deepspeed_tpu.resilience import PreemptionHandler
            self._preemption = PreemptionHandler().install()
        self._watchdog = None
        if rcfg.watchdog.enabled:
            from deepspeed_tpu.resilience import StepWatchdog
            wd = rcfg.watchdog
            self._watchdog = StepWatchdog(
                hang_factor=wd.hang_factor, min_interval_s=wd.min_interval_s,
                poll_interval_s=wd.poll_interval_s, window=wd.window,
                abort=wd.abort, exit_code=wd.exit_code,
                dump_file=wd.dump_file or None).start()

        # remat policy for model blocks (models read it at trace time)
        from deepspeed_tpu.runtime.activation_checkpointing import checkpointing
        checkpointing.configure(deepspeed_config=self.config)

        # --- counters (reference engine bookkeeping) ---
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self._step_applied = False
        self._last_stats: Optional[StepStats] = None
        self._staged_loss = None
        self._data_iterator = None  # persistent iterator for train_batch()
        self._host_sync_count = 0   # blocking device->host fetches (see _host_fetch)

        # --- state init ---
        self._rng_seed = rng if rng is not None else self.config.seed
        self.partitioner = None
        self.state: Optional[TrainState] = None
        self._micro_step_fn = None
        self._apply_step_fn = None
        self._fused_step_fn = None
        self._fused_gas_step_fn = None
        self._pending_fused_stats = None
        self._eval_step_fn = None
        self._offload = None  # ZeRO-Offload host tier (zero/offload.py)
        self._param_store = None  # ZeRO-Infinity param tier (zero/param_offload.py)
        self.quantized_weights = False  # ZeRO++ qwZ (set in _init_state)
        self._qgz_plan = None  # ZeRO++ qgZ (set in _init_state, zero/qgz.py)
        self._pending_opt_state = None  # OptimizerShim.load_state_dict pre-init
        self._async_ckpt_engine = None  # lazy (save_checkpoint(async_save=True))
        self.flops_profiler = None  # lazy (profiling/flops_profiler)
        self._param_transform = None  # compression hook (compression/compress.py)
        # trace-level correctness guards (runtime/guards.py)
        self._guards = None
        self._last_guard_batch = None
        if self.config.correctness_guards["enabled"]:
            from deepspeed_tpu.runtime.guards import TraceStabilityGuard
            self._guards = dict(self.config.correctness_guards,
                                snapshot=None, trace=TraceStabilityGuard())
        # legacy seqlen curriculum (reference engine.py:1826 curriculum hook)
        self.curriculum_scheduler = None
        if self.config.curriculum_enabled_legacy:
            from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler import (
                CurriculumScheduler)
            self.curriculum_scheduler = CurriculumScheduler(
                self.config.curriculum_learning)
        # data_efficiency umbrella (reference data_pipeline/config.py):
        # random-LTD scheduler exposed for model code to query kept tokens
        self.random_ltd_scheduler = None
        de = self.config.data_efficiency
        routing = de.get("data_routing", {})
        if routing.get("enabled") and routing.get("random_ltd", {}).get("enabled"):
            from deepspeed_tpu.runtime.data_pipeline.random_ltd import (
                RandomLTDScheduler)
            self.random_ltd_scheduler = RandomLTDScheduler(
                routing["random_ltd"])
        if model_parameters is not None:
            self._init_state(model_parameters)

        log_dist(
            f"DeepSpeedEngine: zero_stage={self.zero_optimization_stage()} "
            f"dtype={self.working_dtype.__name__} batch=({tb},{mb},{gas}) "
            f"topology={self.topology}", ranks=[0])

    # ------------------------------------------------------------------
    # setup
    # ------------------------------------------------------------------
    def _normalize_model_fn(self, model):
        if model is None:
            raise ValueError("deepspeed_tpu.initialize requires a model")
        if hasattr(model, "apply") and hasattr(model, "init"):  # flax module
            def model_fn(params, batch, rng, training=True):
                rngs = {"dropout": rng} if (rng is not None and training) else None
                kwargs = {}
                try:
                    out = model.apply({"params": params}, batch, rngs=rngs,
                                      deterministic=not training, **kwargs)
                except TypeError:
                    out = model.apply({"params": params}, batch, rngs=rngs, **kwargs)
                return out
            return model_fn
        if callable(model):
            def model_fn(params, batch, rng, training=True):
                try:
                    return model(params, batch, rng)
                except TypeError:
                    return model(params, batch)
            return model_fn
        raise ValueError(f"unsupported model type {type(model)}")

    def _resolve_param_specs(self, params):
        if self._user_param_specs is not None:
            return self._user_param_specs
        if self.module is not None and hasattr(self.module, "param_specs"):
            try:
                return self.module.param_specs(params)
            except Exception:
                return None
        return None

    def _offload_device(self):
        zc = self.config.zero_config
        if zc.cpu_offload:  # deprecated alias (reference zero/config.py)
            return "cpu"
        return zc.offload_optimizer_device

    def _init_state(self, model_parameters):
        # Force a copy: the engine's state buffers are donated to compiled steps,
        # so they must never alias the caller's arrays (astype/device_put return
        # the input unchanged when dtype+sharding already match).
        model_parameters = jax.tree.map(lambda x: jnp.array(x, copy=True), model_parameters)
        params_f32 = tree_cast(model_parameters, jnp.float32)
        self.partitioner = ZeroPartitioner(self.topology, self.config.zero_config,
                                           param_specs=self._resolve_param_specs(params_f32))
        self.partitioner.describe(params_f32)
        if self.config.zero_config.offload_param_device in ("cpu", "nvme"):
            # ZeRO-Infinity parameter tier: working params stream from
            # host/NVMe per scan block (zero/param_offload.py); subsumes the
            # optimizer-offload path for the streamed leaves
            return self._init_state_param_offload(params_f32)
        if self._offload_device() in ("cpu", "nvme"):
            if self.config.zero_config.zero_quantized_weights:
                raise ValueError("zero_quantized_weights cannot be combined with "
                                 "offload_optimizer")
            if self.config.zero_config.zero_quantized_gradients:
                raise ValueError("zero_quantized_gradients cannot be combined "
                                 "with offload_optimizer")
            return self._init_state_offload(params_f32)

        # ZeRO++ qwZ (reference zero_quantized_weights, zero/config.py:40):
        # the stage-3 working copy is stored as int8 + per-group scales, so
        # XLA's per-use all-gathers move int8 over the wire and HBM holds
        # half the bytes. Dequantization happens in-trace at use sites.
        qwz = bool(self.config.zero_config.zero_quantized_weights
                   and self.zero_optimization_stage() >= 3)
        # ZeRO++ hpZ composition: with a secondary partition the working copy
        # stays FULL precision sharded only over the ICI-local param axes
        # (per-use all-gathers ride ICI in bf16); only the primary
        # master->working exchange — the leg that crosses DCN — is quantized,
        # in _apply_core_builder. Without hpZ, qwZ keeps the int8 working
        # copy so XLA's per-use gathers move int8.
        self._qwz_hpz = bool(qwz and self.topology.zero_hierarchy == "hpz")
        self.quantized_weights = qwz and not self._qwz_hpz
        if qwz and not self.mixed_precision:
            raise ValueError("zero_quantized_weights requires fp16/bf16 training "
                             "(the fp32 master holds full precision)")

        working = tree_cast(params_f32, self.working_dtype)
        param_sh = self.partitioner.param_sharding(working)
        master_sh = self.partitioner.master_sharding(params_f32)
        grad_sh = self.partitioner.grad_sharding(params_f32)

        working = jax.tree.map(jax.device_put, working, param_sh)
        if self.quantized_weights:
            param_sh = self._qweight_sharding(param_sh, working)
            working = jax.jit(self._quantize_working)(working)
            working = jax.tree.map(jax.device_put, working, param_sh,
                                   is_leaf=self._is_qleaf)
        if self.mixed_precision:
            master = jax.tree.map(jax.device_put, params_f32, master_sh)
        else:
            master = None
            working = jax.tree.map(jax.device_put, params_f32, master_sh) \
                if self.zero_optimization_stage() >= 3 else working

        opt_target = master if master is not None else working
        opt_state = self._tx.init(opt_target)
        opt_sh = self.partitioner.opt_state_sharding(opt_state, params_f32)
        opt_state = jax.tree.map(jax.device_put, opt_state, opt_sh)

        # qgZ (ZeRO++ zero_quantized_gradients, reference stage3.py:1249):
        # gradients accumulate locally per device in a stacked buffer and are
        # quantize-reduced at the GAS boundary (zero/qgz.py)
        self._qgz_plan = None
        self._qgz_feedback = False
        qgz_residual = None
        if self.config.zero_config.zero_quantized_gradients:
            if self.zero_optimization_stage() < 2:
                raise ValueError("zero_quantized_gradients requires ZeRO stage >= 2 "
                                 "(gradients must be partitioned)")
            if self.quantized_weights and not self._qwz_hpz:
                # qwZ+qgZ would quantize BOTH legs of every exchange across
                # every axis; the composed ZeRO++ path keeps the secondary
                # (ICI) parameter traffic full-precision via hpZ
                raise ValueError(
                    "zero_quantized_gradients + zero_quantized_weights "
                    "requires a secondary parameter partition: set "
                    "zero_hpz_partition_size > 1 (ZeRO++ hpZ)")
            from deepspeed_tpu.runtime.zero.qgz import QgzPlan
            self._qgz_plan = QgzPlan(self.topology, self.partitioner, params_f32)
            grad_acc = self._qgz_plan.stacked_zeros(params_f32, self.grad_accum_dtype)
            grad_sh = self._qgz_plan.stacked_shardings(params_f32)
            self._qgz_feedback = bool(
                self.config.zero_config.zero_quantized_gradients_error_feedback)
            if self._qgz_feedback:
                # fp32 regardless of grad_accum_dtype: the carry is the small
                # difference the wire format dropped
                qgz_residual = self._qgz_plan.stacked_zeros(params_f32,
                                                            jnp.float32)
        else:
            grad_acc = tree_zeros_like(params_f32, self.grad_accum_dtype)
            grad_acc = jax.tree.map(jax.device_put, grad_acc, grad_sh)

        self._shardings = dict(params=param_sh, master=master_sh, grad=grad_sh,
                               opt=opt_sh,
                               use=self.partitioner.use_sharding(params_f32))
        rep = self.topology.replicated()
        scale = init_loss_scale_state(self.config.fp16) if self.fp16_enabled \
            else LossScaleState(jnp.float32(1.0), jnp.int32(0), jnp.int32(0))
        rng_key = jax.random.PRNGKey(self._rng_seed) if isinstance(self._rng_seed, int) \
            else self._rng_seed
        self.state = TrainState(
            params=working,
            master=master,
            opt_state=opt_state,
            grad_acc=grad_acc,
            scale=jax.tree.map(lambda x: jax.device_put(x, rep), scale),
            global_step=jax.device_put(jnp.int32(0), rep),
            skipped=jax.device_put(jnp.int32(0), rep),
            rng=jax.device_put(rng_key, rep),
            qgz_residual=qgz_residual,
        )
        n = count_parameters(params_f32)
        log_dist(f"model parameters: {n/1e6:.2f}M", ranks=[0])
        if self._pending_opt_state is not None:
            sd, self._pending_opt_state = self._pending_opt_state, None
            self.optimizer.load_state_dict(sd)

    def _init_state_offload(self, params_f32):
        """ZeRO-Offload/Infinity state layout (zero/offload.py): the offloaded
        leaves' fp32 master + Adam moments live on the host (DRAM or NVMe);
        only the non-offloaded remainder keeps a device-resident master/optax
        state. Mirrors reference ``offload_optimizer`` cpu/nvme paths."""
        from deepspeed_tpu.runtime.zero.offload import (HostOffloadOptimizer,
                                                        select_offload_leaves)
        zc = self.config.zero_config
        off_cfg = zc.offload_optimizer
        opt_cfg = self.config.optimizer
        opt_name = (opt_cfg.type or "adamw").lower()
        if opt_name not in ("adam", "adamw", "adagrad", "lion"):
            raise ValueError(
                f"offload_optimizer supports adam/adamw/adagrad/lion host steps "
                f"(csrc/adam/cpu_adam.cpp kernels); got {opt_name!r}")
        ratio = off_cfg.ratio if off_cfg.device != "none" else 1.0
        host_keys, _, _ = select_offload_leaves(params_f32, ratio)

        flat_items = jax.tree_util.tree_flatten_with_path(params_f32)[0]
        self._flat_keys = [jax.tree_util.keystr(p) for p, _ in flat_items]
        self._offload_host_indices = [i for i, k in enumerate(self._flat_keys)
                                      if k in host_keys]
        self._offload_device_indices = [i for i, k in enumerate(self._flat_keys)
                                        if k not in host_keys]

        working = tree_cast(params_f32, self.working_dtype)
        param_sh = self.partitioner.param_sharding(working)
        master_sh_full = self.partitioner.master_sharding(params_f32)
        grad_sh = self.partitioner.grad_sharding(params_f32)
        self._flat_param_sh = [s for s in jax.tree_util.tree_leaves(param_sh)]

        working = jax.tree.map(jax.device_put, working, param_sh)

        flat_f32 = [l for _, l in flat_items]
        flat_master_sh = jax.tree_util.tree_leaves(master_sh_full)
        master_d = {self._flat_keys[i]: jax.device_put(flat_f32[i], flat_master_sh[i])
                    for i in self._offload_device_indices}
        self._master_sh_d = {self._flat_keys[i]: flat_master_sh[i]
                             for i in self._offload_device_indices}
        host_leaves = {self._flat_keys[i]: np.asarray(jax.device_get(flat_f32[i]))
                       for i in self._offload_host_indices}
        opt_params = dict(opt_cfg.params or {})
        self._offload = HostOffloadOptimizer(host_leaves, off_cfg, opt_params,
                                             self.working_dtype,
                                             opt_name=opt_name)

        opt_state = self._tx.init(master_d)
        rep = self.topology.replicated()
        # sharding via the same partitioner logic as the non-offload path,
        # scoped to the device-resident subset
        if self.partitioner.param_specs is None:
            specs_d = None
        else:
            from jax.sharding import PartitionSpec as _P
            flat_specs = jax.tree_util.tree_flatten(
                self.partitioner.param_specs,
                is_leaf=lambda x: x is None or isinstance(x, _P))[0]
            specs_d = {self._flat_keys[i]: flat_specs[i]
                       for i in self._offload_device_indices}
        sub_partitioner = ZeroPartitioner(self.topology, zc, param_specs=specs_d)
        master_d_f32 = {self._flat_keys[i]: flat_f32[i]
                        for i in self._offload_device_indices}
        opt_sh = sub_partitioner.opt_state_sharding(opt_state, master_d_f32)
        opt_state = jax.tree.map(jax.device_put, opt_state, opt_sh)

        grad_acc = tree_zeros_like(params_f32, self.grad_accum_dtype)
        grad_acc = jax.tree.map(jax.device_put, grad_acc, grad_sh)
        self._shardings = dict(params=param_sh, master=self._master_sh_d,
                               grad=grad_sh, opt=opt_sh,
                               use=self.partitioner.use_sharding(params_f32))

        scale = init_loss_scale_state(self.config.fp16) if self.fp16_enabled \
            else LossScaleState(jnp.float32(1.0), jnp.int32(0), jnp.int32(0))
        rng_key = jax.random.PRNGKey(self._rng_seed) if isinstance(self._rng_seed, int) \
            else self._rng_seed
        self.state = TrainState(
            params=working, master=master_d, opt_state=opt_state, grad_acc=grad_acc,
            scale=jax.tree.map(lambda x: jax.device_put(x, rep), scale),
            global_step=jax.device_put(jnp.int32(0), rep),
            skipped=jax.device_put(jnp.int32(0), rep),
            rng=jax.device_put(rng_key, rep))
        n = count_parameters(params_f32)
        log_dist(f"model parameters: {n/1e6:.2f}M (offload={off_cfg.device}, "
                 f"ratio={ratio})", ranks=[0])
        if self._pending_opt_state is not None:
            sd, self._pending_opt_state = self._pending_opt_state, None
            self.optimizer.load_state_dict(sd)

    def _init_state_param_offload(self, params_f32):
        """ZeRO-Infinity parameter tier (zero/param_offload.py): the scan-
        stacked block parameters live on host DRAM or NVMe and stream through
        the compiled step per block; their fp32 masters + moments are host-side
        (CPU Adam). Small non-stacked leaves (embeddings, head, final norm)
        stay device-resident with the normal optax path — the
        ``stage3_param_persistence_threshold`` analog. Mirrors the reference's
        ``AsyncPartitionedParameterSwapper``/``DeepSpeedZeRoOffload`` stack
        (``swap_tensor/partitioned_param_swapper.py:36``,
        ``zero/parameter_offload.py:83``)."""
        from deepspeed_tpu.runtime.zero.param_offload import (BlockParamStore,
                                                              make_streaming_fetch)
        zc = self.config.zero_config
        if self.zero_optimization_stage() < 3:
            raise ValueError("offload_param requires ZeRO stage 3 (reference "
                             "zero/config.py: param offload is a stage-3 feature)")
        if zc.zero_quantized_weights or zc.zero_quantized_gradients:
            # neither tier exists in this mode: working params live host-side
            # (not as int8 device shards) and grads leave via host callbacks
            raise ValueError("zero_quantized_weights/zero_quantized_gradients "
                             "cannot be combined with offload_param")
        mod = self.module
        if not (hasattr(mod, "streaming_plan") and mod.streaming_plan()):
            raise ValueError(
                "offload_param needs a model exposing the streaming protocol "
                "(streaming_plan/streaming_split/streaming_apply, with "
                f"scan_layers=True); {type(mod).__name__} does not")
        opt_cfg = self.config.optimizer
        opt_name = (opt_cfg.type or "adamw").lower()
        if opt_name not in ("adam", "adamw", "adagrad", "lion"):
            raise ValueError(f"offload_param supports adam/adamw/adagrad/lion "
                             f"host steps, got {opt_name!r}")

        resident_f32, stacked_f32 = mod.streaming_split(params_f32)
        stacked_np = jax.tree.map(
            lambda x: np.asarray(jax.device_get(x), np.float32), stacked_f32)
        self._param_store = BlockParamStore(
            stacked_np, zc.offload_param, zc.offload_optimizer,
            dict(opt_cfg.params or {}), self.working_dtype, opt_name=opt_name)
        self._streaming_fetch = make_streaming_fetch(self._param_store)

        # resident leaves: the standard device path, partitioned over the same
        # topology (a dedicated partitioner — specs pattern-match names, so
        # they apply unchanged to the resident subset)
        res_specs = None
        if hasattr(mod, "param_specs"):
            try:
                res_specs = mod.param_specs(resident_f32)
            except Exception:
                res_specs = None
        self._res_partitioner = ZeroPartitioner(self.topology, zc,
                                                param_specs=res_specs)
        working = tree_cast(resident_f32, self.working_dtype)
        param_sh = self._res_partitioner.param_sharding(working)
        master_sh = self._res_partitioner.master_sharding(resident_f32)
        grad_sh = self._res_partitioner.grad_sharding(resident_f32)
        working = jax.tree.map(jax.device_put, working, param_sh)
        if self.mixed_precision:
            master = jax.tree.map(jax.device_put, resident_f32, master_sh)
        else:
            master = None
            working = jax.tree.map(jax.device_put, resident_f32, master_sh)
        opt_target = master if master is not None else working
        opt_state = self._tx.init(opt_target)
        opt_sh = self._res_partitioner.opt_state_sharding(opt_state, resident_f32)
        opt_state = jax.tree.map(jax.device_put, opt_state, opt_sh)
        grad_acc = tree_zeros_like(resident_f32, self.grad_accum_dtype)
        grad_acc = jax.tree.map(jax.device_put, grad_acc, grad_sh)
        self._shardings = dict(params=param_sh, master=master_sh, grad=grad_sh,
                               opt=opt_sh,
                               use=self._res_partitioner.use_sharding(resident_f32))
        rep = self.topology.replicated()
        scale = init_loss_scale_state(self.config.fp16) if self.fp16_enabled \
            else LossScaleState(jnp.float32(1.0), jnp.int32(0), jnp.int32(0))
        rng_key = jax.random.PRNGKey(self._rng_seed) if isinstance(self._rng_seed, int) \
            else self._rng_seed
        self.state = TrainState(
            params=working, master=master, opt_state=opt_state, grad_acc=grad_acc,
            scale=jax.tree.map(lambda x: jax.device_put(x, rep), scale),
            global_step=jax.device_put(jnp.int32(0), rep),
            skipped=jax.device_put(jnp.int32(0), rep),
            rng=jax.device_put(rng_key, rep))
        n = count_parameters(params_f32)
        n_res = count_parameters(resident_f32)
        log_dist(f"model parameters: {n/1e6:.2f}M ({(n-n_res)/1e6:.2f}M streamed "
                 f"from {zc.offload_param_device}, {n_res/1e6:.2f}M resident)",
                 ranks=[0])
        if self._pending_opt_state is not None:
            sd, self._pending_opt_state = self._pending_opt_state, None
            self.optimizer.load_state_dict(sd)

    def _ensure_initialized(self, batch):
        if self.state is not None:
            return
        self.init_params(batch)

    def init_params(self, sample_batch, rng=None):
        """Sharded (partition-at-construction) initialization — the ``zero.Init``
        analog (reference ``zero/partition_parameters.py:783``). The model's
        init is shape-evaluated abstractly, shardings are derived from the
        partitioner, and the real init runs under jit with those out_shardings
        so parameters are born sharded: no device ever holds the full tree."""
        if not (hasattr(self.module, "init")):
            raise ValueError("model_parameters required for non-flax models")
        from deepspeed_tpu.runtime.zero.partition import ZeroPartitioner
        from deepspeed_tpu.runtime.zero.sharded_init import (abstract_params,
                                                             materialize_sharded)
        if rng is None:
            rng = jax.random.PRNGKey(
                self._rng_seed if isinstance(self._rng_seed, int) else 0)
        abstract = abstract_params(self.module, sample_batch, rng)
        partitioner = ZeroPartitioner(self.topology, self.config.zero_config,
                                      param_specs=self._resolve_param_specs(abstract))
        params = materialize_sharded(self.module, sample_batch, partitioner, rng,
                                     abstract=abstract)
        self._init_state(params)

    # ------------------------------------------------------------------
    # qwZ working-weight quantization (ZeRO++; ops/quantizer.py)
    # ------------------------------------------------------------------
    @staticmethod
    def _is_qleaf(x):
        return isinstance(x, dict) and "q" in x and "scale" in x

    def _should_quantize(self, leaf):
        return (hasattr(leaf, "ndim") and leaf.ndim >= 2
                and jnp.issubdtype(leaf.dtype, jnp.floating)
                and leaf.size >= self.config.zero_config.stage3_param_persistence_threshold)

    def _quantize_working(self, working):
        from deepspeed_tpu.ops.quantizer import quantize_lastdim

        def q(leaf):
            if self._should_quantize(leaf):
                qv, s = quantize_lastdim(leaf)
                return {"q": qv, "scale": s}
            return leaf

        return jax.tree.map(q, working)

    def _dequantize_working(self, params):
        from deepspeed_tpu.ops.quantizer import dequantize_lastdim
        wd = self.working_dtype

        def dq(leaf):
            if self._is_qleaf(leaf):
                return dequantize_lastdim(leaf["q"], leaf["scale"], dtype=wd)
            return leaf

        return jax.tree.map(dq, params, is_leaf=self._is_qleaf)

    def _qweight_sharding(self, param_sh, working):
        """Sharding tree matching the quantized structure: q inherits the
        leaf's sharding (same shape/layout), scales are replicated (tiny)."""
        rep = self.topology.replicated()

        def sh(leaf, s):
            if self._should_quantize(leaf):
                return {"q": s, "scale": rep}
            return s

        return jax.tree.map(sh, working, param_sh)

    # ------------------------------------------------------------------
    # compiled step functions
    # ------------------------------------------------------------------
    def _loss_closures(self):
        """Shared captures for every grad-computing step (micro and fused)."""
        prescale = self.config.prescale_gradients
        predivide = self.config.gradient_predivide_factor
        fp16 = self.fp16_enabled
        model_fn = self._model_fn
        # PipelineEngine pre-multiplies: its one fused call already averages over
        # the GAS microbatches, so the apply-step's /gas must cancel
        mult = float(getattr(self, "_grad_scale_multiplier", 1.0))

        dq = self._dequantize_working if getattr(self, "quantized_weights", False) \
            else (lambda p: p)
        ptx = self._param_transform
        # ZeRO: params are STORED sharded over the zero axes but USED gathered
        # (model-parallel specs only) — the constraint makes GSPMD emit the
        # per-use all-gather and keeps the storage sharding out of the
        # activation sharding inference (partition.py use_sharding). The same
        # applies to raw gradients at stage >= 2: they are COMPUTED in use
        # sharding and resharded (reduce-scattered) only at the accumulator
        # write, or the grad storage sharding back-propagates through the
        # weight-grad matmuls into activations.
        grad_use_sh = self._shardings.get("use")
        use_sh = grad_use_sh if self.zero_optimization_stage() >= 3 else None

        def make_loss_fn(batch, sub, loss_scale, global_step):
            def loss_fn(p):
                if use_sh is not None:
                    p = constrain_tree(p, use_sh)
                if ptx is not None:
                    # compression transform inside the grad: QAT quant uses
                    # STE, pruning masks the gradient (compression/compress.py)
                    p = ptx(p, global_step)
                loss = model_fn(p, batch, sub, True)
                if isinstance(loss, tuple):
                    loss = loss[0]
                scaled = loss.astype(jnp.float32)
                if mult != 1.0:
                    scaled = scaled * mult
                if fp16:
                    scaled = scaled * loss_scale
                if prescale and predivide != 1.0:
                    scaled = scaled / predivide
                return scaled, loss
            return loss_fn

        return make_loss_fn, dq, grad_use_sh

    def _overlap_streaming_ready(self, plan):
        """Can the overlap schedule's prefetch leg run? Needs the qgZ manual
        path, a model speaking the streaming protocol, and no compression
        transform (ptx operates on the whole param tree, which a block-streamed
        forward never materializes). Bucketized grad reduce works regardless."""
        ov = self.config.overlap_config
        if not (ov.schedule and plan is not None):
            return False
        mod = self.module
        ok = (mod is not None and hasattr(mod, "streaming_plan")
              and hasattr(mod, "streaming_apply") and mod.streaming_plan()
              and self._param_transform is None)
        if not ok:
            logger.warning(
                "overlap.schedule: param prefetch disabled — model lacks the "
                "streaming protocol (streaming_plan/streaming_split/"
                "streaming_apply) or a compression transform is active; the "
                "bucketized grad exchange still applies")
        return bool(ok)

    def _build_micro_step(self):
        grad_sh = self._shardings["grad"]
        accum_dtype = self.grad_accum_dtype
        make_loss_fn, dq, grad_use_sh = self._loss_closures()

        plan = self._qgz_plan
        if plan is not None and self._overlap_streaming_ready(plan):
            return self._build_scheduled_micro_step(plan)
        if plan is not None:
            # qgZ: manual over the ZeRO data axes — per-device local grads
            # accumulated unreduced in the stacked buffer (zero/qgz.py)
            def micro_step(state: TrainState, batch):
                rng, sub = jax.random.split(state.rng)

                def body(params_local, acc_local, batch_local, loss_scale,
                         key, gstep):
                    # distinct dropout/noise per data-parallel replica (the
                    # auto path draws bits over the global batch shape)
                    idx = jnp.int32(0)
                    for a in plan.axes:
                        idx = idx * plan.sizes[a] + jax.lax.axis_index(a)
                    key = jax.random.fold_in(key, idx)
                    p = plan.gather_params(params_local)
                    loss_fn = make_loss_fn(batch_local, key, loss_scale, gstep)
                    (_, loss), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(p)
                    new_acc = jax.tree.map(
                        lambda a, g: a + g.astype(accum_dtype)[None],
                        acc_local, grads)
                    return new_acc, loss.astype(jnp.float32).reshape(1)

                from jax.sharding import PartitionSpec as P
                fn = jax.shard_map(
                    body, mesh=plan.mesh,
                    in_specs=(plan.param_in_specs(state.params),
                              plan.stacked_specs(state.grad_acc, project=True),
                              P(plan.axes), P(), P(), P()),
                    out_specs=(plan.stacked_specs(state.grad_acc, project=True),
                               P(plan.axes)),
                    axis_names=plan.manual, check_vma=False)
                new_acc, losses = fn(state.params, state.grad_acc, batch,
                                     state.scale.loss_scale, sub,
                                     state.global_step)
                # equal per-device micro-batch slices -> global mean
                return state._replace(grad_acc=new_acc, rng=rng), losses.mean()

            return jax.jit(micro_step, donate_argnums=(0,))

        def micro_step(state: TrainState, batch):
            rng, sub = jax.random.split(state.rng)
            loss_fn = make_loss_fn(batch, sub, state.scale.loss_scale,
                                   state.global_step)
            # qwZ: grads are taken w.r.t. the dequantized working weights
            # (XLA gathers the int8 shards, dequantizes at the use site)
            (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                dq(state.params))
            if grad_use_sh is not None:
                grads = constrain_tree(grads, grad_use_sh)
            grads = tree_cast(grads, accum_dtype)
            acc = jax.tree.map(lambda a, g: a + g, state.grad_acc, grads)
            acc = constrain_tree(acc, grad_sh)
            return state._replace(grad_acc=acc, rng=rng), loss

        return jax.jit(micro_step, donate_argnums=(0,))

    def _build_scheduled_micro_step(self, plan):
        """qgZ micro-step under ``overlap.schedule`` (zero/overlap_schedule.py).

        Differences from the unscheduled qgZ body, same math:

        - **Double-buffered prefetch.** Only the resident (non-block) leaves
          are gathered at step entry; each scan block's params are gathered
          per layer via ``plan.gather_block`` inside
          ``streaming_apply(prefetch_depth=D)`` — the scan carry holds the
          next D gathered blocks and each iteration issues block ``i+D``'s
          all-gather before block ``i``'s compute, so XLA's async-collective
          scheduling can hide the exchange under the previous layer's math.
        - **Shadow-input trick.** The stacked accumulator needs FULL-shape
          unreduced local grads, but differentiating through the per-block
          all-gather would make AD transpose it into a full-precision
          psum_scatter during backward — bypassing the quantized boundary
          exchange. So the gathers run on stop-gradient values and each
          fetched block adds a zeros "shadow" slice differentiated instead:
          ``fetch(i) = gather_block(stop_grad(stacked), i) + shadow[i]``.
          d(loss)/d(shadow) is exactly the stacked full-shape local grads.
        """
        accum_dtype = self.grad_accum_dtype
        prescale = self.config.prescale_gradients
        predivide = self.config.gradient_predivide_factor
        fp16 = self.fp16_enabled
        mult = float(getattr(self, "_grad_scale_multiplier", 1.0))
        mod = self.module
        ov = self.config.overlap_config
        depth = max(int(ov.prefetch_depth), 0)
        n_blocks = int(mod.streaming_plan()["num_blocks"])
        use_sh = (self._shardings.get("use")
                  if self.zero_optimization_stage() >= 3 else None)
        use_res = mod.streaming_split(use_sh)[0] if use_sh is not None else None
        resident_specs, stacked_specs = mod.streaming_split(plan.param_specs)
        log_dist(f"overlap.schedule on: prefetch_depth={depth} "
                 f"grad_buckets={int(ov.grad_buckets)} over {n_blocks} blocks",
                 ranks=[0])
        from jax.sharding import PartitionSpec as P

        def micro_step(state: TrainState, batch):
            rng, sub = jax.random.split(state.rng)

            def body(params_local, acc_local, batch_local, loss_scale,
                     key, gstep):
                idx = jnp.int32(0)
                for a in plan.axes:
                    idx = idx * plan.sizes[a] + jax.lax.axis_index(a)
                key = jax.random.fold_in(key, idx)
                resident_local, stacked_local = mod.streaming_split(
                    params_local)
                p_res = plan.gather_params(resident_local,
                                           specs=resident_specs)
                stacked_sg = jax.tree.map(jax.lax.stop_gradient,
                                          stacked_local)

                def full_zeros(x, spec):
                    shape = list(x.shape)
                    if spec is not None:
                        for d, e in enumerate(spec):
                            if e is None or d >= len(shape):
                                continue
                            for a in (e if isinstance(e, tuple) else (e,)):
                                if a in plan.manual:
                                    shape[d] *= plan.sizes[a]
                    return jnp.zeros(shape, x.dtype)

                shadow0 = jax.tree.map(full_zeros, stacked_local,
                                       stacked_specs)

                def loss_fn(args):
                    p_r, shadow = args
                    if use_res is not None:
                        p_r = constrain_tree(p_r, use_res)

                    def fetch(i):
                        blk = plan.gather_block(stacked_sg, stacked_specs, i)
                        return jax.tree.map(
                            lambda b, s: b + jax.lax.dynamic_index_in_dim(
                                s, i, axis=0, keepdims=False), blk, shadow)

                    loss = mod.streaming_apply(p_r, fetch, batch_local,
                                               deterministic=False, rng=key,
                                               prefetch_depth=depth)
                    if isinstance(loss, tuple):
                        loss = loss[0]
                    scaled = loss.astype(jnp.float32)
                    if mult != 1.0:
                        scaled = scaled * mult
                    if fp16:
                        scaled = scaled * loss_scale
                    if prescale and predivide != 1.0:
                        scaled = scaled / predivide
                    return scaled, loss

                (_, loss), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)((p_res, shadow0))
                g_full = mod.streaming_merge(*grads)
                new_acc = jax.tree.map(
                    lambda a, g: a + g.astype(accum_dtype)[None],
                    acc_local, g_full)
                return new_acc, loss.astype(jnp.float32).reshape(1)

            fn = jax.shard_map(
                body, mesh=plan.mesh,
                in_specs=(plan.param_in_specs(state.params),
                          plan.stacked_specs(state.grad_acc, project=True),
                          P(plan.axes), P(), P(), P()),
                out_specs=(plan.stacked_specs(state.grad_acc, project=True),
                           P(plan.axes)),
                axis_names=plan.manual, check_vma=False)
            new_acc, losses = fn(state.params, state.grad_acc, batch,
                                 state.scale.loss_scale, sub,
                                 state.global_step)
            return state._replace(grad_acc=new_acc, rng=rng), losses.mean()

        return jax.jit(micro_step, donate_argnums=(0,))

    def _apply_core_builder(self):
        """Shared optimizer-apply body: mean f32 grads -> new state + stats.
        Used by the standalone apply-step (grads from the accumulator) and
        the fused step (grads straight from backward, never materialized to
        the HBM accumulator)."""
        fp16 = self.fp16_enabled
        clip = self.config.gradient_clipping
        tx = self._tx
        param_sh = self._shardings["params"]
        master_sh = self._shardings["master"]
        working_dtype = self.working_dtype
        mixed = self.mixed_precision
        fp16_cfg = self.config.fp16
        dynamic = self.dynamic_loss_scale
        quantized = getattr(self, "quantized_weights", False)
        quantize_fn = self._quantize_working
        hpz_quant = getattr(self, "_qwz_hpz", False)
        should_q = self._should_quantize

        def hpz_exchange(working):
            """qwZ under hpZ: the primary master->working reshard (the one
            leg that crosses DCN — master is dp x dpr sharded, working only
            dp) moves int8 + scales; the working copy lands full precision so
            every later ICI gather is full precision."""
            from deepspeed_tpu import telemetry
            from deepspeed_tpu.ops.quantizer import (dequantize_lastdim,
                                                     quantize_lastdim)
            logical = wire = 0

            def ex(leaf, s):
                nonlocal logical, wire
                if not should_q(leaf):
                    return jax.lax.with_sharding_constraint(leaf, s)
                q, sc = quantize_lastdim(leaf)
                q = jax.lax.with_sharding_constraint(q, s)  # int8 over DCN
                logical += leaf.size * jnp.dtype(leaf.dtype).itemsize
                wire += q.size + sc.size * 4
                out = dequantize_lastdim(q, sc, dtype=working_dtype)
                return jax.lax.with_sharding_constraint(out, s)

            out = jax.tree.map(ex, working, param_sh)
            if telemetry.enabled():
                telemetry.record_comm("hpz_primary_exchange", int(logical),
                                      0.0, axis="dpr", traced=True,
                                      wire_bytes=int(wire))
            return out

        def core(state: TrainState, grads, lr):
            overflow = has_overflow(grads) if fp16 else jnp.asarray(False)
            safe_grads = jax.tree.map(lambda g: jnp.where(overflow, jnp.zeros_like(g), g), grads)
            norm = global_norm(safe_grads)
            if clip and clip > 0:
                safe_grads, norm = clip_grads_by_global_norm(safe_grads, clip, norm=norm)

            target = state.master if mixed else state.params
            opt_state = set_lr(state.opt_state, lr)
            updates, new_opt = tx.update(safe_grads, opt_state, target)
            new_target = optax.apply_updates(target, updates)
            # fp16 overflow => skip (keep old state) without host sync
            new_target = tree_where(overflow, target, new_target)
            new_opt = tree_where(overflow, opt_state, new_opt)
            new_target = constrain_tree(new_target, master_sh)

            if mixed:
                new_working = tree_cast(new_target, working_dtype)
                if quantized:
                    new_working = quantize_fn(new_working)
                    new_params = jax.tree.map(
                        lambda l, s: jax.lax.with_sharding_constraint(l, s),
                        new_working, param_sh, is_leaf=DeepSpeedEngine._is_qleaf)
                elif hpz_quant:
                    new_params = hpz_exchange(new_working)
                else:
                    new_params = constrain_tree(new_working, param_sh)
                new_master = new_target
            else:
                new_params = new_target
                new_master = None

            new_scale = update_loss_scale(state.scale, overflow, fp16_cfg, dynamic)
            new_acc = jax.tree.map(jnp.zeros_like, state.grad_acc)
            new_state = TrainState(params=new_params, master=new_master, opt_state=new_opt,
                                   grad_acc=new_acc, scale=new_scale,
                                   global_step=state.global_step + 1,
                                   skipped=state.skipped + overflow.astype(jnp.int32),
                                   rng=state.rng)
            stats = StepStats(grad_norm=norm, overflow=overflow, lr=jnp.asarray(lr, jnp.float32),
                              loss_scale=state.scale.loss_scale)
            return new_state, stats

        return core

    def _grad_denom(self, state, gas):
        denom = jnp.float32(gas)
        if self.fp16_enabled:
            denom = denom * state.scale.loss_scale
        predivide = self.config.gradient_predivide_factor
        if self.config.prescale_gradients and predivide != 1.0:
            denom = denom / jnp.float32(predivide)
        return denom

    def _build_apply_step(self):
        gas = self.gradient_accumulation_steps_value
        plan = self._qgz_plan
        feedback = getattr(self, "_qgz_feedback", False)
        core = self._apply_core_builder()
        # overlap.schedule: split the boundary exchange into byte-balanced
        # bucket chains XLA can pipeline against each other and the backward
        # epilogue (zero/overlap_schedule.py; bit-identical per leaf)
        ov = self.config.overlap_config
        buckets = max(int(ov.grad_buckets), 1) if ov.schedule else 1

        def apply_step(state: TrainState, lr):
            denom = self._grad_denom(state, gas)
            new_res = None
            if plan is not None:
                # qgZ boundary: quantized hierarchical reduction of the stacked
                # local grads (zero/qgz.py). The sum over the world of local
                # batch-means is world x the global mean — fold into the denom.
                if feedback:
                    summed, new_res = plan.reduce(
                        state.grad_acc, residual=state.qgz_residual,
                        return_residual=True, buckets=buckets)
                else:
                    summed = plan.reduce(state.grad_acc, buckets=buckets)
                qdenom = denom * jnp.float32(plan.world)
                grads = jax.tree.map(lambda g: g / qdenom, summed)
            else:
                grads = jax.tree.map(lambda g: g.astype(jnp.float32) / denom,
                                     state.grad_acc)
            new_state, stats = core(state, grads, lr)
            if new_res is not None:
                # overflow-skipped steps discarded the gradients the fresh
                # residual belongs to — keep the previous carry
                new_res = tree_where(stats.overflow, state.qgz_residual,
                                     new_res)
                new_state = new_state._replace(qgz_residual=new_res)
            return new_state, stats

        return jax.jit(apply_step, donate_argnums=(0,))

    def _build_fused_step(self):
        """One jit for grad computation + optimizer apply (``fused_step``
        config, GAS=1 only): gradients flow from backward straight into the
        update without the accumulator's HBM round-trip, and XLA schedules
        the update against the backward epilogue. forward() applies the
        optimizer at the boundary; step() consumes the staged stats."""
        make_loss_fn, dq, grad_use_sh = self._loss_closures()
        core = self._apply_core_builder()

        def fused_step(state: TrainState, batch, lr):
            rng, sub = jax.random.split(state.rng)
            loss_fn = make_loss_fn(batch, sub, state.scale.loss_scale,
                                   state.global_step)
            (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                dq(state.params))
            if grad_use_sh is not None:
                grads = constrain_tree(grads, grad_use_sh)
            denom = self._grad_denom(state, 1)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) / denom, grads)
            new_state, stats = core(state._replace(rng=rng), grads, lr)
            return new_state, loss, stats

        return jax.jit(fused_step, donate_argnums=(0,))

    def _fused_enabled(self):
        return (self.config.fused_step
                and self.gradient_accumulation_steps_value == 1
                and self._qgz_plan is None and self._offload is None
                and self._param_store is None)

    def _fused_gas_enabled(self):
        """Fused whole-window step: available through ``train_batch`` only —
        the imperative forward/backward/step API hands over one micro-batch at
        a time, but ``train_batch`` owns the window and can run it as a single
        compiled scan. The seqlen curriculum reshapes batches per step inside
        ``forward`` — that path must keep per-micro-step dispatch."""
        return (self.config.fused_step
                and self.gradient_accumulation_steps_value > 1
                and self._qgz_plan is None and self._offload is None
                and self._param_store is None
                and self.curriculum_scheduler is None)

    def _build_fused_gas_step(self):
        """One jit for the WHOLE gradient-accumulation window (``fused_step``
        at GAS>1): ``lax.scan`` over the stacked micro-batches accumulates
        grads in the scan carry — XLA aliases the carry buffers in place, so
        accumulation stops round-tripping a separate accumulator through HBM
        between dispatches, and the optimizer apply fuses with the last
        backward. The reference's analog is bucketed comm/compute overlap
        during backward (``zero/stage_1_and_2.py:922``); under XLA the
        scheduler owns overlap once everything is one program."""
        make_loss_fn, dq, grad_use_sh = self._loss_closures()
        core = self._apply_core_builder()
        gas = self.gradient_accumulation_steps_value
        accum_dtype = self.grad_accum_dtype

        def fused_gas_step(state: TrainState, batches, lr):
            rng, sub = jax.random.split(state.rng)

            # STATIC unroll over the window, not lax.scan: gas is small and
            # known at trace time, and an XLA while-loop would carry the
            # params-sized accumulator tree as loop state (copied at every
            # iteration boundary when aliasing fails — measured 1.7x SLOWER
            # than per-micro dispatch on the CPU mesh). Straight-line code
            # lets XLA alias the accumulate in place and fuse freely.
            acc, key, losses = state.grad_acc, sub, []
            for i in range(gas):
                mb = jax.tree.map(lambda x: x[i], batches)
                key, k = jax.random.split(key)
                loss_fn = make_loss_fn(mb, k, state.scale.loss_scale,
                                       state.global_step)
                (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    dq(state.params))
                if grad_use_sh is not None:
                    grads = constrain_tree(grads, grad_use_sh)
                acc = jax.tree.map(lambda a, g: a + g.astype(accum_dtype),
                                   acc, grads)
                losses.append(loss.astype(jnp.float32))

            denom = self._grad_denom(state, gas)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) / denom, acc)
            new_state, stats = core(state._replace(rng=rng), grads, lr)
            return new_state, jnp.stack(losses), stats

        return jax.jit(fused_gas_step, donate_argnums=(0,))

    def _shard_stacked_batches(self, batches):
        """Stack ``gas`` micro-batches along a new leading axis and shard:
        axis 0 (the window) replicated, axis 1 (the batch) over dp."""
        stacked = jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                               *batches)
        sharding = self.topology.stacked_batch_sharding()

        def put(x):
            x = jnp.asarray(x)
            try:
                return jax.device_put(x, sharding)
            except Exception:
                return jax.device_put(x, self.topology.replicated())

        return jax.tree.map(put, stacked)

    def _build_eval_step(self):
        model_fn = self._model_fn
        dq = self._dequantize_working if getattr(self, "quantized_weights", False) \
            else (lambda p: p)
        ptx = self._param_transform

        use_sh = self._shardings.get("use") \
            if self.zero_optimization_stage() >= 3 else None

        def eval_step(state: TrainState, batch):
            p = dq(state.params)
            if use_sh is not None:
                p = constrain_tree(p, use_sh)
            if ptx is not None:
                p = ptx(p, state.global_step)
            out = model_fn(p, batch, None, False)
            return out

        return jax.jit(eval_step)

    def set_param_transform(self, fn):
        """Install a pure (params, step) -> params transform applied inside
        the jitted steps (compression QAT/pruning hook). Forces recompile."""
        self._param_transform = fn
        self._micro_step_fn = None
        self._apply_step_fn = None
        self._fused_step_fn = None
        self._fused_gas_step_fn = None
        self._pending_fused_stats = None
        self._eval_step_fn = None

    def _build_offload_fns(self):
        """Compiled pieces of the offloaded apply-step: a grad-stats reduction
        (overflow + global norm, one tiny host sync) and the device-side
        update of the non-offloaded remainder (which also zeroes the grad
        buffer and advances counters/loss scale)."""
        fp16 = self.fp16_enabled
        tx = self._tx
        keys = self._flat_keys
        d_idx = self._offload_device_indices
        master_sh_d = self._master_sh_d
        param_sh = self._shardings["params"]
        working_dtype = self.working_dtype
        fp16_cfg = self.config.fp16
        dynamic = self.dynamic_loss_scale

        def grad_stats(grad_acc):
            g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grad_acc)
            overflow = has_overflow(g32) if fp16 else jnp.asarray(False)
            return overflow, global_norm(g32)

        def device_apply(state: TrainState, lr, inv_scale, overflow):
            flat_g = jax.tree_util.tree_leaves(state.grad_acc)
            grads_d = {keys[i]: flat_g[i].astype(jnp.float32) * inv_scale
                       for i in d_idx}
            opt_state = set_lr(state.opt_state, lr)
            updates, new_opt = tx.update(grads_d, opt_state, state.master)
            new_master = optax.apply_updates(state.master, updates)
            new_master = tree_where(overflow, state.master, new_master)
            new_opt = tree_where(overflow, opt_state, new_opt)
            new_master = constrain_tree(new_master, master_sh_d)

            flat_p, pdef = jax.tree_util.tree_flatten(state.params)
            new_flat_p = list(flat_p)
            for i in d_idx:
                new_flat_p[i] = new_master[keys[i]].astype(working_dtype)
            new_params = constrain_tree(
                jax.tree_util.tree_unflatten(pdef, new_flat_p), param_sh)
            new_acc = jax.tree.map(jnp.zeros_like, state.grad_acc)
            new_scale = update_loss_scale(state.scale, overflow, fp16_cfg, dynamic)
            return TrainState(params=new_params, master=new_master, opt_state=new_opt,
                              grad_acc=new_acc, scale=new_scale,
                              global_step=state.global_step + 1,
                              skipped=state.skipped + overflow.astype(jnp.int32),
                              rng=state.rng)

        self._offload_stats_fn = jax.jit(grad_stats)
        self._offload_apply_fn = jax.jit(device_apply, donate_argnums=(0,))

    def _offload_step(self, lr):
        """Apply-step under ZeRO-Offload: device handles the retained leaves
        and bookkeeping; the host tier (zero/offload.py) runs CPU Adam over
        the offloaded leaves and streams back the working copy. The device
        program is dispatched *before* the host update so XLA execution and
        host compute/PCIe overlap (the reference's stream overlap analog)."""
        gas = self.gradient_accumulation_steps_value
        overflow_a, raw_norm_a = self._offload_stats_fn(self.state.grad_acc)
        overflow = bool(jax.device_get(overflow_a))
        raw_norm = float(jax.device_get(raw_norm_a))
        scale_before = self.cur_scale  # the scale this step actually ran at
        denom = float(gas)
        if self.fp16_enabled:
            denom *= scale_before
        if self.config.prescale_gradients and self.config.gradient_predivide_factor != 1.0:
            denom /= float(self.config.gradient_predivide_factor)
        norm = raw_norm / denom
        clip = self.config.gradient_clipping
        clip_coef = 1.0
        if clip and clip > 0 and norm > clip:
            clip_coef = clip / (norm + 1e-6)
        inv_scale = clip_coef / denom

        host_grads = None
        if not overflow and self._offload_host_indices:
            flat_g = jax.tree_util.tree_leaves(self.state.grad_acc)
            host_grads = jax.device_get(
                {self._flat_keys[i]: flat_g[i] for i in self._offload_host_indices})
        # dispatch the device-side update first (async), then run host Adam
        new_state = self._offload_apply_fn(self.state, jnp.float32(lr),
                                           jnp.float32(inv_scale),
                                           jnp.asarray(overflow))
        if host_grads is not None:
            new_working = self._offload.step(
                {k: np.asarray(v, dtype=np.float32) for k, v in host_grads.items()},
                lr, inv_scale)
            flat_p, pdef = jax.tree_util.tree_flatten(new_state.params)
            for i in self._offload_host_indices:
                # copy: the host optimizer reuses its output buffers in place
                # next step, and device_put on CPU backends can be zero-copy —
                # params must never alias host memory (see _init_state note)
                leaf = np.array(new_working[self._flat_keys[i]], copy=True)
                flat_p[i] = jax.device_put(leaf, self._flat_param_sh[i])
            new_state = new_state._replace(
                params=jax.tree_util.tree_unflatten(pdef, flat_p))
        self.state = new_state
        return StepStats(grad_norm=jnp.float32(norm), overflow=jnp.asarray(overflow),
                         lr=jnp.float32(lr), loss_scale=jnp.float32(scale_before))

    def _build_param_offload_fns(self):
        """Compiled pieces of the ZeRO-Infinity param-tier step: the streaming
        micro-step (block fetches + host grad writes ride the compiled scan),
        device-side stats over the resident accumulator, the resident apply,
        and a streaming eval step."""
        fp16 = self.fp16_enabled
        mult = float(getattr(self, "_grad_scale_multiplier", 1.0))
        model = self.module
        fetch = self._streaming_fetch
        accum_dtype = self.grad_accum_dtype
        grad_sh = self._shardings["grad"]
        param_sh = self._shardings["params"]
        master_sh = self._shardings["master"]
        use_sh = self._shardings.get("use")
        tx = self._tx
        mixed = self.mixed_precision
        working_dtype = self.working_dtype
        fp16_cfg = self.config.fp16
        dynamic = self.dynamic_loss_scale
        ptx = self._param_transform

        def micro_step(state: TrainState, batch):
            rng, sub = jax.random.split(state.rng)

            def loss_fn(args):
                p, tok = args
                if use_sh is not None:
                    p = constrain_tree(p, use_sh)
                if ptx is not None:
                    p = ptx(p, state.global_step)
                loss = model.streaming_apply(p, lambda i: fetch(i, tok), batch,
                                             deterministic=False, rng=sub)
                if isinstance(loss, tuple):
                    loss = loss[0]
                scaled = loss.astype(jnp.float32)
                if mult != 1.0:
                    scaled = scaled * mult
                if fp16:
                    scaled = scaled * state.scale.loss_scale
                return scaled, loss

            (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                (state.params, jnp.zeros((), jnp.float32)))
            gp, _ = grads  # the token cotangent is a dummy
            acc = jax.tree.map(lambda a, g: a + g.astype(accum_dtype),
                               state.grad_acc, gp)
            acc = constrain_tree(acc, grad_sh)
            return state._replace(grad_acc=acc, rng=rng), loss

        def grad_stats(grad_acc):
            g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grad_acc)
            overflow = has_overflow(g32) if fp16 else jnp.asarray(False)
            return overflow, global_norm(g32) ** 2

        def device_apply(state: TrainState, lr, inv_scale, overflow):
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv_scale,
                                 state.grad_acc)
            target = state.master if mixed else state.params
            opt_state = set_lr(state.opt_state, lr)
            updates, new_opt = tx.update(grads, opt_state, target)
            new_target = optax.apply_updates(target, updates)
            new_target = tree_where(overflow, target, new_target)
            new_opt = tree_where(overflow, opt_state, new_opt)
            new_target = constrain_tree(new_target, master_sh)
            if mixed:
                new_params = constrain_tree(tree_cast(new_target, working_dtype),
                                            param_sh)
                new_master = new_target
            else:
                new_params, new_master = new_target, None
            new_acc = jax.tree.map(jnp.zeros_like, state.grad_acc)
            new_scale = update_loss_scale(state.scale, overflow, fp16_cfg, dynamic)
            return TrainState(params=new_params, master=new_master,
                              opt_state=new_opt, grad_acc=new_acc,
                              scale=new_scale,
                              global_step=state.global_step + 1,
                              skipped=state.skipped + overflow.astype(jnp.int32),
                              rng=state.rng)

        def eval_step(state: TrainState, batch):
            p = state.params
            if use_sh is not None:
                p = constrain_tree(p, use_sh)
            if ptx is not None:
                p = ptx(p, state.global_step)
            return model.streaming_apply(
                p, lambda i: fetch(i, jnp.zeros((), jnp.float32)), batch)

        self._micro_step_fn = jax.jit(micro_step, donate_argnums=(0,))
        self._po_stats_fn = jax.jit(grad_stats)
        self._po_apply_fn = jax.jit(device_apply, donate_argnums=(0,))
        self._eval_step_fn = jax.jit(eval_step)

    def _param_offload_step(self, lr):
        """Apply-step with the ZeRO-Infinity param tier: device applies the
        resident leaves; the host tier (CPU Adam over fp32 masters) consumes
        the grad accumulators the backward callbacks filled, then publishes
        the new working bytes for the next step's fetches. Global grad norm
        and fp16 overflow merge both tiers."""
        gas = self.gradient_accumulation_steps_value
        # join every micro-step's backward grad-write callbacks before
        # reading the host accumulators
        jax.effects_barrier()
        overflow_a, sq_a = self._po_stats_fn(self.state.grad_acc)
        overflow = bool(jax.device_get(overflow_a))
        dev_sq = float(jax.device_get(sq_a))
        host_sq, host_finite = self._param_store.grad_sq_and_finite()
        if self.fp16_enabled and not host_finite:
            overflow = True
        scale_before = self.cur_scale
        denom = float(gas)
        if self.fp16_enabled:
            denom *= scale_before
        if self.config.prescale_gradients and self.config.gradient_predivide_factor != 1.0:
            denom /= float(self.config.gradient_predivide_factor)
        norm = (dev_sq + host_sq) ** 0.5 / denom
        clip = self.config.gradient_clipping
        clip_coef = 1.0
        if clip and clip > 0 and norm > clip:
            clip_coef = clip / (norm + 1e-6)
        inv_scale = clip_coef / denom
        # dispatch the resident device update first (async), then run the
        # host-tier optimizer while the device works
        new_state = self._po_apply_fn(self.state, jnp.float32(lr),
                                      jnp.float32(inv_scale),
                                      jnp.asarray(overflow))
        if overflow:
            self._param_store.zero_grads()
        else:
            self._param_store.step(lr, inv_scale)
        self.state = new_state
        return StepStats(grad_norm=jnp.float32(norm), overflow=jnp.asarray(overflow),
                         lr=jnp.float32(lr), loss_scale=jnp.float32(scale_before))

    def _compiled(self):
        if self._micro_step_fn is None:
            if self._param_store is not None:
                self._build_param_offload_fns()
                self._fused_step_fn = None
                self._apply_step_fn = None
                return
            if self._fused_enabled():
                self._fused_step_fn = self._build_fused_step()
                self._micro_step_fn = self._build_micro_step()  # eval/GAS path
                self._apply_step_fn = self._build_apply_step()
            else:
                self._fused_step_fn = None
                self._micro_step_fn = self._build_micro_step()
                if self._offload is not None:
                    self._build_offload_fns()
                    self._apply_step_fn = None
                else:
                    self._apply_step_fn = self._build_apply_step()
            if self._fused_gas_enabled():
                self._fused_gas_step_fn = self._build_fused_gas_step()
            self._eval_step_fn = self._build_eval_step()
        elif self._apply_step_fn is None and self._offload is None:
            # invalidated (e.g. set_train_batch_size changed the baked-in
            # GAS denominator) — rebuild just the apply step
            self._apply_step_fn = self._build_apply_step()
            if self._fused_gas_enabled():
                self._fused_gas_step_fn = self._build_fused_gas_step()
            if self._fused_enabled():
                self._fused_step_fn = self._build_fused_step()
            else:
                self._fused_step_fn = None

    # ------------------------------------------------------------------
    # public API (reference engine.py:1794/1933/2132)
    # ------------------------------------------------------------------
    def _shard_batch(self, batch):
        sharding = self.topology.batch_sharding()

        def put(x):
            x = jnp.asarray(x)
            try:
                return jax.device_put(x, sharding)
            except Exception:
                return jax.device_put(x, self.topology.replicated())

        return jax.tree.map(put, batch)

    def forward(self, batch):
        """Run the fused forward+backward+accumulate micro-step and commit it.
        Returns the (unscaled) loss.

        Note on semantics vs the reference: eager PyTorch separates forward
        (activations) from backward (grads); one fused XLA program is both
        faster and simpler, so grads are accumulated here and ``backward`` is
        bookkeeping. The state is committed immediately — the old state buffers
        are donated to the compiled step, so holding the previous ``state``
        reference is invalid either way."""
        if self.curriculum_scheduler is not None and \
                self.curriculum_scheduler.curriculum_type == "seqlen":
            # curriculum BEFORE init/compile/profiling so every consumer sees
            # the real step shape. Difficulties are bucketed to powers of two
            # by default: a jitted step recompiles per distinct shape, so raw
            # per-step lengths would mean O(curriculum_steps) XLA compiles —
            # bucketing bounds it at log2(max/min) (set
            # curriculum_learning.tpu_shape_buckets=false for exact lengths).
            from deepspeed_tpu.runtime.data_pipeline.data_sampler import (
                apply_seqlen_curriculum)
            seqlen = self.curriculum_scheduler.update_difficulty(self.global_steps)
            if self.config.curriculum_learning.get("tpu_shape_buckets", True):
                bucket = 1 << max(0, (int(seqlen) - 1).bit_length())
                seqlen = min(bucket, self.curriculum_scheduler.max_difficulty)
            batch = apply_seqlen_curriculum(batch, seqlen)
        self._ensure_initialized(batch)
        self._compiled()
        # flops profiler (reference engine.py:1823 profile-step hook)
        if self.config.flops_profiler_config.enabled:
            if self.flops_profiler is None:
                from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler
                self.flops_profiler = FlopsProfiler(self)
            if self.flops_profiler.should_profile(self.global_steps):
                self.flops_profiler.profile_engine_step(batch)
        if self.wall_clock_breakdown:
            self.timers(FORWARD_GLOBAL_TIMER).start()
        self.tput_timer.start()
        from deepspeed_tpu import telemetry
        fused = getattr(self, "_fused_step_fn", None) is not None
        step = self.global_steps
        _span = telemetry.span_begin(FORWARD_GLOBAL_TIMER, step=step,
                                     fused=int(fused))
        built = telemetry.build_count()
        with telemetry.span("fwd/shard_batch", step=step):
            batch = self._shard_batch(batch)
        if self._guards is not None and self._guards["checkify_on_overflow"]:
            self._last_guard_batch = batch  # for overflow localization
        try:
            with telemetry.span("fwd/dispatch", step=step):
                if fused:
                    # fused_step config: grads + optimizer apply in ONE jit
                    # (GAS=1). The update is applied HERE; step() consumes
                    # the staged stats.
                    lr = self._schedule_fn(step)
                    self.state, loss, stats = self._fused_step_fn(
                        self.state, batch, lr)
                    self._pending_fused_stats = stats
                else:
                    self.state, loss = self._micro_step_fn(self.state, batch)
        except Exception as e:
            telemetry.maybe_oom_postmortem(e)
            raise
        self._staged_loss = loss
        # device-side running mean across the GAS window (reference averages
        # micro-step losses before the train_loss event; no host sync here)
        if self.monitor.enabled:
            if getattr(self, "_loss_accum", None) is None:
                self._loss_accum, self._loss_accum_n = loss, 1
            else:
                self._loss_accum = self._loss_accum + loss
                self._loss_accum_n += 1
        self._end_built(_span, built)
        if self.wall_clock_breakdown:
            self.timers(FORWARD_GLOBAL_TIMER).stop(token=loss)
        return loss

    __call__ = forward

    def backward(self, loss=None, retain_graph=False):
        """API-parity shim: gradient computation/reduction already ran fused
        inside ``forward`` (see note there). The ``bwd`` telemetry span
        therefore marks this bookkeeping on the host, not a grad pass."""
        assert self._staged_loss is not None, "backward() called before forward()"
        from deepspeed_tpu import telemetry
        _span = telemetry.span_begin(BACKWARD_GLOBAL_TIMER, step=self.global_steps)
        built = telemetry.build_count()
        staged_loss = self._staged_loss
        self._staged_loss = None
        self._end_built(_span, built)
        return staged_loss

    @staticmethod
    def _end_built(span, before):
        """End ``fwd``, ``bwd`` or ``step`` with what jax built under it:
        ``built`` programs (the build ledger's count since ``before``,
        ``telemetry/buildlog.py``) and their ``build_ms``: 0 and 0.0 on
        every step that found its executables."""
        from deepspeed_tpu import telemetry
        built = telemetry.build_count() - before
        span.set(built=built, build_ms=telemetry.build_ms(built))
        span.end()

    def is_gradient_accumulation_boundary(self):
        """reference engine.py:2153 semantics. ``_gas_offset`` rebases the
        window after an elastic ``set_train_batch_size`` resize."""
        rel = self.micro_steps - getattr(self, "_gas_offset", 0)
        return (rel + 1) % self.gradient_accumulation_steps_value == 0

    # --- sparse (embedding) gradient reduction -------------------------
    # reference engine.py:2470-2539: embedding grads travel as (indices,
    # values) pairs. On TPU the in-step reduction is GSPMD-emitted, so the
    # factored exchange is exposed two ways: host-side over SparseTensors
    # (this API, the reference's surface) and in-jit for shard_map grad paths
    # (runtime/comm/sparse_collectives.py).
    def sparse_allreduce_bucket(self, sparse_tensors):
        """Reduce a bucket of per-rank SparseTensors to their summed, deduped
        form (reference ``sparse_allreduce_bucket``)."""
        from deepspeed_tpu.runtime.sparse_tensor import sparse_all_reduce
        return sparse_all_reduce(sparse_tensors)

    def sparse_allreduce(self, sparse_tensor, ids=None, axis_name="dp"):
        """Factored allreduce of one embedding gradient.

        Host path (``SparseTensor``): dedupe via the rendezvous math.
        Device path: ``sparse_tensor`` = stacked per-device local grads
        [world, V, D] (sharded over ``axis_name``), ``ids`` = their token ids
        [world, N]; runs the static-shape factored exchange over the engine
        mesh — ``N x (D+1)`` traffic instead of ``V x D``
        (comm/sparse_collectives). Returns the dense [V, D] sum.
        """
        from deepspeed_tpu.runtime.sparse_tensor import SparseTensor
        if isinstance(sparse_tensor, SparseTensor):
            return sparse_tensor.deduplicate()
        assert ids is not None, "device-path sparse_allreduce needs token ids"
        cache = getattr(self, "_sparse_ar_fns", None)
        if cache is None:
            cache = self._sparse_ar_fns = {}
        fn = cache.get(axis_name)
        if fn is None:
            # built once per axis: jit caches by function identity
            from jax.sharding import PartitionSpec as P
            from deepspeed_tpu.runtime.comm.sparse_collectives import (
                sparse_all_reduce)
            fn = cache[axis_name] = jax.jit(jax.shard_map(
                lambda g, i: sparse_all_reduce(g[0], i[0], axis_name),
                mesh=self.topology.mesh, in_specs=(P(axis_name), P(axis_name)),
                out_specs=P(), check_vma=False))
        return fn(sparse_tensor, ids)

    def _host_fetch(self, value, what):
        """THE accounted device->host fetch. Every blocking d2h transfer the
        engine issues on its own behalf goes through here so the steady-state
        no-sync contract is auditable: ``host_sync_count`` must stay flat
        between ``steps_per_print``/monitor boundaries (enforced by the
        transfer-guard regression test). Do not call jax.device_get / float()
        on device values elsewhere in the train loop."""
        self._host_sync_count += 1
        from deepspeed_tpu import telemetry
        if telemetry.enabled():
            telemetry.count("host_sync", what=what)
        with telemetry.span("host_fetch", what=what):
            return jax.device_get(value)

    @property
    def host_sync_count(self):
        """Cumulative engine-issued blocking device->host fetches (bench's
        ``extra.host_sync_count``). Steady-state steps contribute zero."""
        return self._host_sync_count

    def step(self):
        """Optimizer step at the gradient-accumulation boundary (engine.py:2132)."""
        self._step_applied = False
        _faults.set_step(self.global_steps)
        _faults.maybe_fail("step.hang")
        try:
            # a whole slice dying mid-step: BEFORE the apply, so the fault
            # can never leave a half-applied optimizer step behind
            _faults.maybe_fail("slice.lost")
        except _faults.InjectedFault as e:
            self._handle_slice_loss(e)
        from deepspeed_tpu import telemetry
        _span = telemetry.span_begin(STEP_GLOBAL_TIMER, step=self.global_steps)
        built = telemetry.build_count()
        if self.wall_clock_breakdown:
            self.timers(STEP_GLOBAL_TIMER).start()
        if self.is_gradient_accumulation_boundary():
            old_state = self.state if self._guards is not None else None
            staged = getattr(self, "_pending_fused_stats", None)
            if staged is not None:
                stats = staged  # fused step already applied in forward()
                self._pending_fused_stats = None
                old_state = None  # forward() already replaced the state
            elif self._param_store is not None:
                stats = self._param_offload_step(self._schedule_fn(self.global_steps))
            elif self._offload is not None:
                stats = self._offload_step(self._schedule_fn(self.global_steps))
            else:
                lr = self._schedule_fn(self.global_steps)
                try:
                    self.state, stats = self._apply_step_fn(self.state, lr)
                except Exception as e:
                    telemetry.maybe_oom_postmortem(e)
                    raise
            if self._guards is not None:
                self._run_guards(old_state, stats)
            self._last_stats = stats
            self._step_applied = True
            self.global_steps += 1
            # NOTE: no per-step host sync on overflow — the skipped counter
            # lives in device state and is read lazily (skipped_steps property)
            self.lr_scheduler.step()
            if self.monitor.enabled and self.global_steps % self.config.steps_per_print == 0:
                events = [
                    ("Train/Samples/lr",
                     float(self._host_fetch(stats.lr, "monitor/lr")),
                     self.global_samples),
                    ("Train/Samples/loss_scale",
                     float(self._host_fetch(stats.loss_scale, "monitor/loss_scale")),
                     self.global_samples),
                ]
                if getattr(self, "_loss_accum", None) is not None:
                    # reference engine.py:1961 Train/Samples/train_loss —
                    # the GAS-window mean; fetch only at monitor cadence
                    mean = float(self._host_fetch(self._loss_accum,
                                                  "monitor/train_loss")) / \
                        self._loss_accum_n
                    events.insert(0, ("Train/Samples/train_loss", mean,
                                      self.global_samples))
                if self._telemetry_monitor and telemetry.enabled():
                    events.extend(telemetry.monitor_events(self.global_samples))
                self.monitor.write_events(events)
            self._loss_accum, self._loss_accum_n = None, 0
        self.micro_steps += 1
        self.global_samples += self.micro_batch_size * self.topology.data_parallel_size
        if self.wall_clock_breakdown:
            self.timers(STEP_GLOBAL_TIMER).stop()
        self._end_built(_span, built)
        if self._step_applied and telemetry.enabled():
            # goodput/MFU ledger mark + HBM sample, once per optimizer step
            telemetry.ledger_step(step=self.global_steps)
            telemetry.record_memory("step", step=self.global_steps)
        self.tput_timer.stop(global_step=self._step_applied)
        if self._step_applied and self.global_steps % self.config.steps_per_print == 0:
            log_dist(f"step={self.global_steps}, skipped={self.skipped_steps}, "
                     f"lr={self.get_lr()}, loss_scale={self.cur_scale}", ranks=[0])
        self._resilience_step_boundary()

    def _config_digest(self):
        """Postmortem-bundle collector: a stable digest + key shape facts
        of the user config, enough to tell WHICH config crashed without
        shipping the whole (possibly large) dict."""
        import hashlib
        raw = json.dumps(self.config._param_dict, sort_keys=True,
                         default=str)
        return {"sha256": hashlib.sha256(raw.encode()).hexdigest(),
                "keys": sorted(self.config._param_dict),
                "global_steps": self.global_steps,
                "train_batch_size": getattr(
                    self.config, "train_batch_size", None)}

    def _resilience_step_boundary(self):
        """Post-step resilience hooks (docs/RESILIENCE.md): feed the
        watchdog heartbeat, and honor a pending preemption request — save
        an emergency checkpoint, then exit with the clean-preemption code
        the elastic agent does not count against its restart budget."""
        if self._watchdog is not None:
            self._watchdog.beat()
        pre = self._preemption
        if pre is None or not pre.requested():
            return
        from deepspeed_tpu import telemetry
        cfg = self.config.resilience_config.preemption
        telemetry.record("Fault/preemption", 1, kind="counter",
                         signum=pre.signal_received, step=self.global_steps)
        save_dir = cfg.save_dir or self._last_save_dir
        if save_dir:
            with telemetry.span("recovery/emergency_save",
                                step=self.global_steps):
                path = self.save_checkpoint(save_dir, tag=cfg.tag)
            logger.warning(f"preemption (signal {pre.signal_received}): "
                           f"emergency checkpoint {path}; exiting "
                           f"{cfg.exit_code} (clean preemption)")
        else:
            logger.warning(f"preemption (signal {pre.signal_received}): no "
                           f"save_dir configured or used yet — exiting "
                           f"{cfg.exit_code} WITHOUT an emergency checkpoint")
        telemetry.flush_postmortem(
            "preemption",
            detail=f"signal {pre.signal_received} at step {self.global_steps}",
            exit_code=int(cfg.exit_code))
        raise SystemExit(int(cfg.exit_code))

    def _handle_slice_loss(self, fault):
        """A slice-loss fault (``slice.lost`` / ``comm.partition``) reached
        the step boundary. With ``resilience.elastic.enabled`` the engine
        performs the process-level hand-off: emergency *universal*
        checkpoint (topology-independent, so the relaunched gang can
        reshard it onto the survivors) then ``SystemExit(84)`` — the
        elastic agent's "reshardable slice loss" exit code
        (docs/RESILIENCE.md). Disabled, the fault propagates so an
        in-process ElasticReshardController can catch it and reshard
        without a relaunch."""
        ecfg = self.config.resilience_config.elastic
        if not ecfg.enabled:
            raise fault
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.checkpoint.universal import save_universal_checkpoint
        telemetry.record("Fault/slice_lost", 1, kind="counter",
                         point=fault.point, step=self.global_steps)
        save_dir = ecfg.save_dir or self._last_save_dir
        if save_dir:
            with telemetry.span("recovery/emergency_save",
                                step=self.global_steps):
                path = save_universal_checkpoint(
                    self, save_dir, tag=f"ustep{self.global_steps}")
            logger.warning(
                f"slice loss ({fault.point}): emergency universal "
                f"checkpoint {path}; exiting {ecfg.exit_code} "
                f"(reshardable slice loss)")
        else:
            logger.warning(
                f"slice loss ({fault.point}): no save_dir configured or "
                f"used yet — exiting {ecfg.exit_code} WITHOUT an "
                f"emergency checkpoint")
        telemetry.flush_postmortem(
            "slice_loss",
            detail=f"{fault.point} at step {self.global_steps}",
            exit_code=int(ecfg.exit_code))
        raise SystemExit(int(ecfg.exit_code))

    def _run_guards(self, old_state, stats):
        """Boundary-time correctness guards (runtime/guards.py): donation
        audit, sharding-drift check, retrace detection, and — on overflow —
        checkify-based NaN source localization (the reference's safe-mode
        re-verification, ``stage3.py:1249``)."""
        from deepspeed_tpu.runtime import guards as G
        g = self._guards
        # donation audit: only where XLA actually supports buffer aliasing
        # (CPU backends never donate — every leaf would "fail" the audit)
        if old_state is not None and jax.default_backend() != "cpu":
            G.check_donation(old_state, self.state)
        fns = dict(micro=self._micro_step_fn, apply=self._apply_step_fn,
                   fused=self._fused_step_fn, fused_gas=self._fused_gas_step_fn)
        g["boundaries"] = g.get("boundaries", 0) + 1
        if g["snapshot"] is None:
            g["snapshot"] = G.ShardingSnapshot(self.state)
        elif g["boundaries"] == 2:
            # trace baseline at the SECOND boundary: the first step's outputs
            # feed the second step with settled (non-weak) types, so the one
            # benign warmup retrace never counts as a storm
            g["trace"].record(**fns)
        elif self.global_steps % max(1, g["check_every"]) == 0:
            g["snapshot"].verify(self.state)
            g["trace"].verify(**fns)
        if (g["checkify_on_overflow"]
                and bool(self._host_fetch(stats.overflow, "guards/overflow"))
                and self._last_guard_batch is not None
                and self._param_store is None
                and not getattr(self, "quantized_weights", False)):
            report = G.locate_nonfinite(self._model_fn, self.state.params,
                                        self._last_guard_batch,
                                        rng=self.state.rng)
            if report:
                logger.warning(f"overflow localized (checkify float_checks): "
                               f"{report[:800]}")
            self._last_overflow_report = report

    def train_batch(self, data_iter=None):
        """Full GAS cycle — PipelineEngine-parity API (pipe/engine.py:327).

        With ``fused_step`` at GAS>1 the whole window runs as ONE compiled
        scan over the stacked micro-batches (``_build_fused_gas_step``)."""
        if data_iter is None:
            assert self.training_dataloader is not None
            if self._data_iterator is None:
                from deepspeed_tpu.runtime.dataloader import RepeatingLoader
                self._data_iterator = iter(RepeatingLoader(self.training_dataloader))
            data_iter = self._data_iterator
        gas = self.gradient_accumulation_steps_value
        if self._fused_gas_enabled():
            rel = self.micro_steps - getattr(self, "_gas_offset", 0)
            if rel % gas != 0:
                raise RuntimeError(
                    "fused train_batch mid-accumulation-window: finish the "
                    "window with forward/backward/step first")
            from deepspeed_tpu import telemetry
            with telemetry.span("dataloader", gas=gas):
                batches = [next(data_iter) for _ in range(gas)]
            self._ensure_initialized(batches[0])
            self._compiled()
            self.tput_timer.start()
            stacked = self._shard_stacked_batches(batches)
            lr = self._schedule_fn(self.global_steps)
            old_state = self.state if self._guards is not None else None
            self.state, losses, stats = self._fused_gas_step_fn(
                self.state, stacked, lr)
            self._last_stats = stats
            self._step_applied = True
            if self._guards is not None:
                self._run_guards(old_state, stats)
            self.micro_steps += gas
            self.global_steps += 1
            self.global_samples += self.micro_batch_size * \
                self.topology.data_parallel_size * gas
            self.lr_scheduler.step()
            mean = losses.mean()
            if self.monitor.enabled and \
                    self.global_steps % self.config.steps_per_print == 0:
                events = [
                    ("Train/Samples/train_loss",
                     float(self._host_fetch(mean, "monitor/train_loss")),
                     self.global_samples),
                    ("Train/Samples/lr",
                     float(self._host_fetch(stats.lr, "monitor/lr")),
                     self.global_samples),
                    ("Train/Samples/loss_scale",
                     float(self._host_fetch(stats.loss_scale,
                                            "monitor/loss_scale")),
                     self.global_samples)]
                if self._telemetry_monitor and telemetry.enabled():
                    events.extend(telemetry.monitor_events(self.global_samples))
                self.monitor.write_events(events)
            self.tput_timer.stop(global_step=True)
            self._resilience_step_boundary()
            # device-resident window mean: train_batch itself never blocks on
            # the result (reference returns the loss tensor, not a float) —
            # the caller decides when/whether to pay the d2h sync
            return mean
        from deepspeed_tpu import telemetry
        losses = []
        for _ in range(gas):
            with telemetry.span("dataloader"):
                batch = next(data_iter)
            loss = self.forward(batch)
            self.backward(loss)
            self.step()
            losses.append(loss)
        # device-side mean: one fused add chain, no per-micro-step d2h sync
        return sum(losses[1:], losses[0]) / len(losses)

    def eval_batch(self, batch):
        self._ensure_initialized(batch)
        self._compiled()
        from deepspeed_tpu import telemetry
        with telemetry.span("eval"):
            return self._eval_step_fn(self.state, self._shard_batch(batch))

    def write_events(self, event_list):
        """Forward (name, value, step) event tuples to the monitor fan-out
        (reference ``engine.py:2273``) — the hook telemetry exporters and
        user code share."""
        self.monitor.write_events(event_list)

    # ------------------------------------------------------------------
    # introspection (reference engine getter surface)
    # ------------------------------------------------------------------
    def zero_optimization_stage(self):
        return self.config.zero_config.stage

    def zero_optimization(self):
        return self.zero_optimization_stage() > 0

    def get_lr(self):
        return [float(self._host_fetch(self._last_stats.lr, "get_lr"))] \
            if self._last_stats is not None \
            else [float(self._schedule_fn(self.global_steps))]

    def get_global_grad_norm(self):
        return float(self._host_fetch(self._last_stats.grad_norm,
                                      "grad_norm")) \
            if self._last_stats is not None else 0.0

    def set_lr(self, lr):
        """Override the learning rate from here on (reference engine
        ``set_lr``): pins the schedule to a constant until changed again."""
        value = float(lr[0] if isinstance(lr, (list, tuple)) else lr)
        self._schedule_fn = lambda step: value
        # keep the scheduler shim's surface consistent with what is applied
        if hasattr(self.lr_scheduler, "schedule_fn"):
            self.lr_scheduler.schedule_fn = self._schedule_fn

    def get_mom(self):
        """reference ``get_mom``: first momentum coefficient (Adam beta1 /
        SGD momentum) from the optimizer config."""
        params = dict(getattr(self.config.optimizer, "params", {}) or {})
        opt_type = str(getattr(self.config.optimizer, "type", "")).lower()
        if "sgd" in opt_type:
            # matches the builder default (ops/adam.py): sgd momentum 0.0
            return [params.get("momentum", 0.0)]
        betas = params.get("betas", (0.9, 0.999))  # adam-family default
        return [list(betas)]

    def set_train_batch_size(self, train_batch_size):
        """Adjust the global batch size by changing gradient-accumulation
        steps; the micro-batch size is untouched (reference engine.py:411 —
        the elasticity resize hook). Only legal at an accumulation boundary
        (a mid-window resize would mis-scale the partial window)."""
        if getattr(self, "_grad_scale_multiplier", 1.0) != 1.0:
            raise NotImplementedError(
                "set_train_batch_size on PipelineEngine: the pipeline "
                "micro-batch count is baked into the compiled schedule")
        rel = self.micro_steps - getattr(self, "_gas_offset", 0)
        if rel % self.gradient_accumulation_steps_value != 0:
            raise RuntimeError(
                "set_train_batch_size mid-accumulation-window: call it only "
                "right after step() completed a window")
        mbs = self.train_micro_batch_size_per_gpu()
        dp = self.topology.data_parallel_size
        if train_batch_size % (mbs * dp) != 0:
            raise ValueError(
                f"train_batch_size {train_batch_size} not divisible by "
                f"micro_batch ({mbs}) x dp ({dp})")
        self.gradient_accumulation_steps_value = train_batch_size // (mbs * dp)
        self.train_batch_size_value = train_batch_size
        self.config.train_batch_size = train_batch_size
        self.config.gradient_accumulation_steps = \
            self.gradient_accumulation_steps_value
        self._gas_offset = self.micro_steps  # rebase the window
        # the fused apply-step bakes the GAS denominator in: invalidate and
        # let _compiled() rebuild lazily (offload keeps its own path; an
        # uninitialized engine has no shardings to build against yet). A
        # staged fused result from a pre-resize forward() is stale — dropping
        # it means that window's step is skipped, never double-applied.
        self._apply_step_fn = None
        self._fused_step_fn = None
        self._fused_gas_step_fn = None  # bakes gas as denominator AND scan length
        self._pending_fused_stats = None

    @property
    def skipped_steps(self):
        """Overflow-skipped optimizer steps (device counter, synced on read)."""
        return int(self._host_fetch(self.state.skipped, "skipped_steps")) \
            if self.state is not None else 0

    @property
    def cur_scale(self):
        return float(self._host_fetch(self.state.scale.loss_scale,
                                      "loss_scale")) \
            if self.state is not None else 1.0

    def loss_scale(self):
        return self.cur_scale

    def was_step_applied(self):
        return self._step_applied

    def train_micro_batch_size_per_gpu(self):
        return self.micro_batch_size

    def train_batch_size(self):
        return self.train_batch_size_value

    def gradient_accumulation_steps(self):
        return self.gradient_accumulation_steps_value

    def get_model_parameters(self, dtype=jnp.float32):
        """Gathered full-precision parameters (analog of
        ``zero_gather_16bit_weights_on_model_save`` / zero_to_fp32)."""
        rep = self.topology.replicated()
        if self._param_store is not None:
            # ZeRO-Infinity param tier: streamed blocks from host masters,
            # resident leaves from device
            src = self.state.master if self.state.master is not None \
                else self.state.params
            resident = jax.tree.map(
                lambda x: np.asarray(jax.device_get(jax.device_put(x, rep)),
                                     dtype=dtype), src)
            stacked = self._param_store.stacked_params(dtype=dtype)
            return self.module.streaming_merge(resident, stacked)
        if self._offload is not None:
            # merge device-resident masters with the host tier
            pdef = jax.tree_util.tree_structure(self.state.params)
            out = []
            for i, k in enumerate(self._flat_keys):
                if k in self.state.master:
                    out.append(np.asarray(jax.device_get(
                        jax.device_put(self.state.master[k], rep)), dtype=dtype))
                else:
                    out.append(self._offload.masters[k].reshape(
                        self._offload.shapes[k]).astype(dtype))
            return jax.tree_util.tree_unflatten(pdef, out)
        src = self.state.master if self.state.master is not None else self.state.params
        return jax.tree.map(lambda x: np.asarray(jax.device_put(x, rep), dtype=dtype), src)

    def _refresh_working_from_master(self):
        """Recompute the working-precision params from the fp32 masters (all
        tiers) — used after external master edits (tensor-fragment sets,
        universal checkpoint load)."""
        if self._param_store is not None:
            if self.state.master is not None:
                working = tree_cast(self.state.master, self.working_dtype)
                working = jax.tree.map(jax.device_put, working,
                                       self._shardings["params"])
                self.state = self.state._replace(params=working)
            self._param_store._publish_from_masters()
        elif self._offload is not None:
            flat_p, pdef = jax.tree_util.tree_flatten(self.state.params)
            for i, k in enumerate(self._flat_keys):
                if k in self.state.master:
                    leaf = self.state.master[k].astype(self.working_dtype)
                else:
                    leaf = jnp.asarray(
                        self._offload.masters[k].reshape(self._offload.shapes[k]),
                        dtype=self.working_dtype)
                flat_p[i] = jax.device_put(leaf, self._flat_param_sh[i])
            self.state = self.state._replace(
                params=jax.tree_util.tree_unflatten(pdef, flat_p))
        elif self.state.master is not None:
            working = tree_cast(self.state.master, self.working_dtype)
            if self.quantized_weights:
                working = jax.jit(self._quantize_working)(working)
            working = jax.tree.map(jax.device_put, working,
                                   self._shardings["params"],
                                   is_leaf=self._is_qleaf)
            self.state = self.state._replace(params=working)
        # pure-fp32: params ARE the masters; nothing to refresh

    # ------------------------------------------------------------------
    # checkpointing (reference engine.py:3056 save / :2712 load)
    # ------------------------------------------------------------------
    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True,
                        async_save=False):
        """``async_save=True`` uses the background-writer engine (the Nebula
        analog): training resumes after the device->host fetch; call
        ``commit_checkpoints()`` (or the next save/load) to join writes."""
        from deepspeed_tpu import telemetry
        with telemetry.span("ckpt/save", tag=str(tag) if tag else None,
                            async_save=async_save):
            path = self._save_checkpoint(save_dir, tag=tag,
                                         client_state=client_state,
                                         save_latest=save_latest,
                                         async_save=async_save)
        telemetry.record_memory("ckpt/save", step=self.global_steps)
        return path

    def _save_checkpoint(self, save_dir, tag=None, client_state=None,
                         save_latest=True, async_save=False):
        from deepspeed_tpu.runtime.checkpoint_engine.native_engine import (
            AsyncCheckpointEngine, NativeCheckpointEngine, atomic_write_text)
        tag = tag or f"global_step{self.global_steps}"
        self._last_save_dir = save_dir  # emergency-save target on preemption
        if async_save:
            if self._async_ckpt_engine is None:
                self._async_ckpt_engine = AsyncCheckpointEngine()
            engine = self._async_ckpt_engine
        else:
            # a sync save must order after any in-flight async publishes, or a
            # late async worker could move 'latest' back to an older tag
            self.commit_checkpoints()
            engine = NativeCheckpointEngine()
        path = os.path.join(save_dir, str(tag))
        meta = {
            "counters": {
                "global_steps": self.global_steps,
                "global_samples": self.global_samples,
                "micro_steps": self.micro_steps,
                "skipped_steps": self.skipped_steps,
                # accumulation-window rebase after set_train_batch_size —
                # without it a resumed resized engine misaligns boundaries
                "gas_offset": getattr(self, "_gas_offset", 0),
            },
            "lr_scheduler": self.lr_scheduler.state_dict(),
            "client_state": client_state or {},
            "ds_config": self.config._param_dict,
        }
        if async_save:
            # host-tier snapshot and the in-dir/post-publish writes run in the
            # worker: the tag dir only exists after the atomic publish, and
            # 'latest' must not point at an unpublished checkpoint. Deep-copy
            # the blobs — the host tier updates masters/moments in place while
            # the write is in flight.
            offload_blobs = None
            if self._offload is not None:
                offload_blobs = {k: np.array(v, copy=True)
                                 for k, v in self._offload.state_dict().items()}
            param_tier_blobs = None
            if self._param_store is not None:
                param_tier_blobs = {k: np.array(v, copy=True)
                                    for k, v in self._param_store.state_dict().items()}

            def in_dir(p):
                if offload_blobs is not None:
                    np.savez(os.path.join(p, "host_optimizer_states.npz"),
                             **offload_blobs)
                if param_tier_blobs is not None:
                    np.savez(os.path.join(p, "host_param_tier.npz"),
                             **param_tier_blobs)

            def after_publish():
                if save_latest:
                    atomic_write_text(os.path.join(save_dir, "latest"),
                                      str(tag))

            engine.save(self.state, path, meta=meta, extra_writer=in_dir,
                        on_published=after_publish)
            log_dist(f"async checkpoint {path} scheduled", ranks=[0])
            return path

        def in_dir_sync(p):
            # host-tier blobs land inside the tmp dir so the checksum
            # manifest covers them and the publish stays all-or-nothing
            if self._offload is not None:
                self._offload.save(os.path.join(p, "host_optimizer_states.npz"))
            if self._param_store is not None:
                np.savez(os.path.join(p, "host_param_tier.npz"),
                         **self._param_store.state_dict())

        engine.save(self.state, path, meta=meta, extra_writer=in_dir_sync)
        if save_latest:
            atomic_write_text(os.path.join(save_dir, "latest"), str(tag))
        log_dist(f"saved checkpoint {path}", ranks=[0])
        return path

    def commit_checkpoints(self):
        """Join outstanding async checkpoint writes (reference Nebula commit);
        raises if any background write failed."""
        if self._async_ckpt_engine is not None:
            return self._async_ckpt_engine.commit(None)
        return True

    @staticmethod
    def _checkpoint_tags(load_dir):
        """Candidate checkpoint tags in ``load_dir``, newest first.
        Numbered tags (trailing integer, e.g. ``global_step12``) order by
        step and rank above unnumbered ones, which order by mtime.
        Quarantined (``.corrupt``) and in-flight (``.tmp.``/``.old.``)
        directories are never candidates."""
        import re
        out = []
        for name in os.listdir(load_dir):
            p = os.path.join(load_dir, name)
            if not os.path.isdir(p) or ".corrupt" in name \
                    or ".tmp." in name or ".old." in name:
                continue
            if not os.path.exists(os.path.join(p, "meta.json")):
                continue
            m = re.search(r"(\d+)$", name)
            key = (1, int(m.group(1))) if m else (0, os.path.getmtime(p))
            out.append((key, name))
        return [n for _, n in sorted(out, reverse=True)]

    @staticmethod
    def _quarantine(path):
        """Move a corrupt tag aside to ``<tag>.corrupt`` (never deleted —
        it is forensic evidence) so tag listings skip it."""
        dst = f"{path}.corrupt"
        n = 0
        while os.path.exists(dst):
            n += 1
            dst = f"{path}.corrupt.{n}"
        try:
            os.replace(path, dst)
        except OSError:
            return None
        return dst

    def load_checkpoint(self, load_dir, tag=None, load_optimizer_states=True,
                        load_lr_scheduler_states=True, load_module_only=False):
        """Load a checkpoint; on :class:`CorruptCheckpointError` the corrupt
        tag is quarantined (renamed ``<tag>.corrupt``) and the load falls
        back to the newest prior valid tag automatically
        (docs/RESILIENCE.md recovery matrix)."""
        from deepspeed_tpu import telemetry
        with telemetry.span("ckpt/load", tag=str(tag) if tag else None):
            out = self._load_checkpoint(
                load_dir, tag=tag,
                load_optimizer_states=load_optimizer_states,
                load_lr_scheduler_states=load_lr_scheduler_states,
                load_module_only=load_module_only)
        telemetry.record_memory("ckpt/load", step=self.global_steps)
        return out

    def _load_checkpoint(self, load_dir, tag=None, load_optimizer_states=True,
                         load_lr_scheduler_states=True,
                         load_module_only=False):
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.runtime.checkpoint_engine.native_engine import (
            NativeCheckpointEngine, atomic_write_text)
        self.commit_checkpoints()  # never read a tag with writes in flight
        if tag is None:
            latest = os.path.join(load_dir, "latest")
            if not os.path.exists(latest):
                logger.warning(f"no 'latest' file in {load_dir}; nothing loaded")
                return None, {}
            with open(latest) as f:
                tag = f.read().strip()
        engine = NativeCheckpointEngine()
        assert self.state is not None, "engine state must be initialized before load"
        attempted, _rec_span = [], None
        while True:
            path = os.path.join(load_dir, str(tag))
            try:
                new_state = engine.load(path, template=self.state)
                meta = engine.load_meta(path)
                break
            except CorruptCheckpointError as e:
                if _rec_span is None:  # fault→recovery interval in the trace
                    _rec_span = telemetry.span_begin("recovery/ckpt_fallback")
                attempted.append(str(tag))
                telemetry.record("Fault/ckpt_corrupt", 1, kind="counter",
                                 tag=str(tag), file=e.file or "")
                q = self._quarantine(path) if os.path.isdir(path) else None
                logger.error(f"checkpoint {path} corrupt: {e}"
                             + (f" — quarantined to {q}" if q else ""))
                telemetry.flush_postmortem(
                    "corrupt_ckpt", detail=f"{path}: {e}"[:300],
                    extra={"quarantined": q, "tag": str(tag)})
                candidates = [t for t in self._checkpoint_tags(load_dir)
                              if t not in attempted]
                if not candidates:
                    logger.error(f"no prior valid checkpoint tag left in "
                                 f"{load_dir} (tried {attempted})")
                    raise
                tag = candidates[0]
                logger.warning(f"falling back to checkpoint tag {tag!r}")
        if attempted:
            # repair 'latest' so the NEXT restart goes straight to the tag
            # that actually loads
            atomic_write_text(os.path.join(load_dir, "latest"), str(tag))
            telemetry.record("Recovery/ckpt_fallback", 1, kind="counter",
                             tag=str(tag), skipped=len(attempted))
            _rec_span.end()
        if load_module_only or not load_optimizer_states:
            new_state = self.state._replace(params=new_state.params, master=new_state.master)
        # restore device placement/shardings
        shard_template = self.state
        new_state = jax.tree.map(
            lambda new, old: jax.device_put(jnp.asarray(new), old.sharding)
            if hasattr(old, "sharding") else new,
            new_state, shard_template)
        self.state = new_state
        host_states = os.path.join(path, "host_optimizer_states.npz")
        if self._offload is not None and load_optimizer_states and \
                os.path.exists(host_states):
            self._offload.load(host_states)
        host_params = os.path.join(path, "host_param_tier.npz")
        if self._param_store is not None and os.path.exists(host_params):
            data = np.load(host_params)
            self._param_store.load_state_dict(
                {name: data[name] for name in data.files})
        c = meta.get("counters", {"global_steps": 0, "global_samples": 0,
                                  "micro_steps": 0, "skipped_steps": 0})
        self.global_steps = int(c["global_steps"])
        self.global_samples = int(c["global_samples"])
        self.micro_steps = int(c["micro_steps"])
        self._gas_offset = int(c.get("gas_offset", 0))
        # skipped count travels inside the device state (TrainState.skipped)
        if load_lr_scheduler_states and "lr_scheduler" in meta:
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        log_dist(f"loaded checkpoint {path} (step {self.global_steps})", ranks=[0])
        return path, meta.get("client_state", {})

    def save_universal_checkpoint(self, out_dir, tag=None):
        """Universal (topology-independent) checkpoint (checkpoint/universal.py)."""
        from deepspeed_tpu.checkpoint import save_universal_checkpoint
        return save_universal_checkpoint(self, out_dir, tag=tag)

    def load_universal_checkpoint(self, universal_dir, load_optimizer_states=True):
        from deepspeed_tpu.checkpoint import load_universal_checkpoint
        return load_universal_checkpoint(self, universal_dir,
                                         load_optimizer_states=load_optimizer_states)

    def save_16bit_model(self, save_dir, save_filename=None):
        """reference engine ``save_16bit_model`` — gathered half-precision dump.

        For the in-tree model families (llama/mistral/qwen2/gpt2/opt/mixtral)
        this writes a real HF checkpoint (``model.safetensors`` +
        ``config.json``) that ``transformers.from_pretrained`` loads
        (checkpoint/hf.py export). Other models get an honest flax npz
        (``model_weights.npz`` — NOT named like a torch file)."""
        os.makedirs(save_dir, exist_ok=True)
        # fp16 stays 16-bit end to end; bf16 exports fp32 (numpy/safetensors
        # have no native bfloat16 — documented widening, not a silent one)
        dtype = np.float16 if self.fp16_enabled else np.float32
        params = self.get_model_parameters(dtype=dtype)
        cfg = getattr(self.module, "config", None)
        if save_filename is None and cfg is not None:
            from deepspeed_tpu.checkpoint import hf as hf_interop
            try:
                return hf_interop.export_pretrained(params, cfg, save_dir,
                                                    dtype=dtype)
            except hf_interop.UnsupportedModelError:
                pass  # unknown family -> npz fallback (real errors propagate)
        save_filename = save_filename or "model_weights.npz"
        flat = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            flat[jax.tree_util.keystr(path)] = leaf
        np.savez(os.path.join(save_dir, save_filename), **flat)
        return os.path.join(save_dir, save_filename)

    def load_hf_weights(self, model_dir):
        """Load a HuggingFace checkpoint directory into the live engine (the
        ``load_checkpoint(load_module_only=True)`` analog for HF checkpoints;
        reference ``module_inject/replace_module.py:182`` checkpoint path).
        The converted tree replaces params/master in place (shapes must match
        the engine's model)."""
        from deepspeed_tpu.checkpoint import hf as hf_interop
        _, params = hf_interop.load_pretrained(model_dir)
        if self.state is None:
            self._init_state(params)
            return params
        if self._offload is not None:
            raise NotImplementedError("load_hf_weights with offload_optimizer: "
                                      "load before the first step instead")
        if self.state.master is not None:
            master = jax.tree.map(
                lambda cur, new: jax.device_put(
                    jnp.asarray(new, cur.dtype), cur.sharding),
                self.state.master, params)
            self.state = self.state._replace(master=master)
            self._refresh_working_from_master()
        else:
            working = jax.tree.map(
                lambda cur, new: jax.device_put(
                    jnp.asarray(new, cur.dtype), cur.sharding),
                self.state.params, params)
            self.state = self.state._replace(params=working)
        return params
