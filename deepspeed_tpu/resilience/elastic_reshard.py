"""Elastic multi-slice training — survive slice loss mid-step, reshard to
the survivors, and re-expand, without losing the loss trajectory.

A multi-slice TPU job loses whole slices, not single chips: a DCN partition
or a preempted slice takes out a contiguous block of devices while the rest
of the gang is healthy. The reference DeepSpeed answer is elasticity
(``deepspeed/elasticity``): tear the job down, relaunch at the surviving
world size, resume from the last checkpoint. This module is the jax-native
version, and because sharding here is data (a ``jax.sharding.Mesh``) rather
than process groups, *resharding is a rebuild, not a renegotiation*:

1. a slice-loss fault surfaces (``slice.lost`` / ``comm.partition`` from
   :mod:`~deepspeed_tpu.resilience.faults`, or exit code
   :data:`EXIT_RESHARD_SLICE_LOSS` at the elastic-agent level),
2. :func:`build_topology_for` derives a :class:`MeshTopology` over the
   survivors — the ZeRO partition, QgzPlan and hpZ primary-exchange layout
   all re-derive from it at engine construction,
3. the newest durable universal-checkpoint tag (name-keyed fp32 fragments,
   crash-consistently published) is loaded under the new mesh —
   ``device_put`` against the survivor sharding IS the reshard,
4. the step loop resumes at exactly ``engine.global_steps`` — no step lost,
   none double-applied — and the loss trajectory continues bitwise (the
   fp32 master update is reduction-order independent across dp worlds for
   the fragment layout we save),
5. when capacity returns, the same path runs in reverse (expand).

Two consumers:

- **in-process** (:class:`ElasticReshardController` + :func:`run_elastic`):
  the CPU drill — 8 forced host devices, kill 4-of-8 mid-step, continue on
  4, re-expand to 8. Used by ``tests/test_elastic_reshard.py`` and
  ``scripts/fault_drill.py --drill slice-loss``.
- **cross-process** (:data:`EXIT_RESHARD_SLICE_LOSS`): the engine's
  ``_handle_slice_loss`` saves an emergency universal checkpoint and exits
  84; ``elasticity/elastic_agent.py`` classifies that exit, drops the dead
  hosts, and relaunches the survivors budget-free.

Module scope imports only the standard library (the resilience package
contract) — jax and the runtime are imported lazily inside functions.
"""

import math
import os
import time

from deepspeed_tpu.resilience import faults

#: Exit code a worker uses to report "my gang lost a slice but MY state is
#: durable — relaunch me on the survivors". Sibling of
#: ``EXIT_CLEAN_PREEMPTION`` (83) / ``EXIT_WATCHDOG_ABORT`` (85); like 83 it
#: does not burn elastic restart budget (the fault is the platform's, not
#: the job's).
EXIT_RESHARD_SLICE_LOSS = 84


class SliceLostError(RuntimeError):
    """A slice-loss condition detected outside the fault registry (e.g. a
    collective timeout the caller maps to a lost slice). Carries the set of
    lost slice indices when known."""

    def __init__(self, msg="slice lost", lost_slices=()):
        super().__init__(msg)
        self.lost_slices = tuple(lost_slices)


def is_slice_loss(exc):
    """Is this exception a reshardable slice loss (vs a real crash)?"""
    if isinstance(exc, SliceLostError):
        return True
    return (isinstance(exc, faults.InjectedFault)
            and exc.point in faults.SLICE_LOSS_POINTS)


# --------------------------------------------------------------- topology

def slice_devices(devices, n_slices):
    """Partition a flat device list into ``n_slices`` contiguous slices —
    the multi-slice model where devices [0..n/k) share slice 0's ICI."""
    n = len(devices)
    if n_slices < 1 or n % n_slices:
        raise ValueError(
            f"{n} devices do not split into {n_slices} equal slices")
    per = n // n_slices
    return [list(devices[i * per:(i + 1) * per]) for i in range(n_slices)]


def surviving_devices(devices, lost_slices, n_slices):
    """The devices left after the given slice indices die."""
    lost = set(lost_slices)
    keep = [s for i, s in enumerate(slice_devices(devices, n_slices))
            if i not in lost]
    if not keep:
        raise SliceLostError("all slices lost — nothing to reshard onto",
                             lost_slices=lost_slices)
    return [d for s in keep for d in s]


def build_topology_for(devices, like=None):
    """Derive the survivor/expanded :class:`MeshTopology` for ``devices``.

    ``like`` is the previous topology: model-parallel axes (pp/ep/sp/tp)
    are preserved — a slice loss shrinks the *data-parallel* world — and
    the hpZ/MiCS shard-group size is clamped to the largest divisor of the
    new dp world (collapsing the hierarchy entirely when the survivors fit
    a single shard group)."""
    from deepspeed_tpu.parallel.topology import MeshTopology
    if like is None:
        return MeshTopology(devices=devices)
    fixed = like.pp_size * like.ep_size * like.sp_size * like.tp_size
    n = len(devices)
    if n % fixed:
        raise SliceLostError(
            f"{n} surviving devices cannot carry the model-parallel layout "
            f"pp{like.pp_size} x ep{like.ep_size} x sp{like.sp_size} x "
            f"tp{like.tp_size} (= {fixed}); shrink is dp-only")
    new_dp = n // fixed
    shard, hierarchy = None, None
    if like.zero_hierarchy is not None:
        want = like.dp_size  # old shard-group size
        shard = math.gcd(want, new_dp)
        while new_dp % shard:  # pragma: no cover - gcd already divides
            shard -= 1
        if shard >= new_dp or shard <= 1:
            shard = None  # hierarchy collapses to plain ZeRO
        else:
            hierarchy = like.zero_hierarchy
    return MeshTopology(pp=like.pp_size, ep=like.ep_size, sp=like.sp_size,
                        tp=like.tp_size, devices=devices,
                        zero_shard_size=shard, zero_hierarchy=hierarchy)


# ----------------------------------------------------------------- replan

def replan_for_world(model, model_parameters, base_config, batch_fn, world,
                     compile_fn=None, **tune_kwargs):
    """Chip-free re-plan for a resharded world size: rank the config grid
    for an ``elastic:<world>x1`` topology (the autotuner parses the dp
    world straight out of the name) and return ``(config, ranking)``.
    ``compile_fn`` is injectable exactly as in ``tune_chip_free`` so the
    CPU drill re-plans without AOT compiles."""
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    tuner = Autotuner(model, model_parameters, base_config, batch_fn)
    return tuner.tune_chip_free(topology_name=f"elastic:{world}x1",
                                compile_fn=compile_fn, **tune_kwargs)


# ------------------------------------------------------------- controller

class ElasticReshardController:
    """Drives one training gang through shrink/expand reshard cycles.

    ``build_engine(mesh_topology)`` is the caller's closure that constructs
    a fresh engine (model init + ``deepspeed_tpu.initialize(mesh=...)``) —
    the controller owns *when* to rebuild, the closure owns *how*. Every
    rebuild re-derives the ZeRO partition, the quantized-gradient plan and
    the hpZ primary-exchange layout for the new mesh; state then arrives
    via the universal checkpoint, which is topology-free by construction.

    The step loop contract (:meth:`train_step`): a return of ``None`` means
    "a slice died and I resharded — replay this batch"; the caller indexes
    batches by ``engine.global_steps``, which the restore path rewinds to
    the last durable step, so no step is ever lost or double-applied.
    """

    def __init__(self, build_engine, ckpt_dir, n_slices=2, checkpoint_every=1,
                 replan_fn=None, restore_retries=2, restore_delay=0.05,
                 sleep=None, devices=None):
        self.build_engine = build_engine
        self.ckpt_dir = str(ckpt_dir)
        self.n_slices = n_slices
        self.checkpoint_every = max(1, int(checkpoint_every))
        self.replan_fn = replan_fn          # world -> plan (or None)
        self.restore_retries = restore_retries
        self.restore_delay = restore_delay
        self._sleep = sleep                 # injectable for tests
        self._all_devices = list(devices) if devices is not None else None
        self.engine = None
        self.last_plan = None
        self.world_history = []             # world size after every (re)build
        self.reshard_events = []            # dicts: kind/world/seconds/step/...

    # -- lifecycle -------------------------------------------------------
    def start(self):
        """Build the full-world engine and write the step-0 tag so even a
        fault on the very first step has a durable restore point."""
        import jax
        if self._all_devices is None:
            self._all_devices = list(jax.devices())
        self._build(self._all_devices, kind="start")
        self.checkpoint(force=True)
        return self.engine

    def _build(self, devices, kind, like=None):
        from deepspeed_tpu.parallel import groups
        groups.reset()
        topo = build_topology_for(devices, like=like)
        self.engine = self.build_engine(topo)
        world = topo.world_size()
        self.world_history.append(world)
        self._record("elastic/world_size", world, kind_tag=kind)
        return topo

    # -- step loop -------------------------------------------------------
    def train_step(self, batch):
        """One fwd/bwd/step. Returns the step's loss as a float, or ``None``
        if a slice was lost mid-step (state resharded to the survivors; the
        caller must replay the batch at the — rewound — current step)."""
        engine = self.engine
        try:
            loss = engine(batch)
            engine.backward(loss)
            engine.step()
        except BaseException as e:  # InjectedFault / SliceLostError
            if not is_slice_loss(e):
                raise
            self.shrink(lost_slices=getattr(e, "lost_slices", None) or (
                tuple(range(self.n_slices // 2, self.n_slices))))
            return None
        if engine.global_steps % self.checkpoint_every == 0:
            self.checkpoint()
        import numpy as np
        # the recorded loss is the trajectory evidence — the host read is
        # the point
        return float(
            np.asarray(loss))  # graftlint: allow[GL004] loss record is host

    def checkpoint(self, force=False):
        from deepspeed_tpu.checkpoint.universal import save_universal_checkpoint
        step = self.engine.global_steps
        tag = f"ustep{step}"
        if not force and os.path.isdir(os.path.join(self.ckpt_dir, tag)):
            return tag
        save_universal_checkpoint(self.engine, self.ckpt_dir, tag=tag)
        return tag

    # -- reshard ---------------------------------------------------------
    def shrink(self, lost_slices=None):
        """Reshard onto the survivors of ``lost_slices`` (default: the
        upper half of the slice set — the injected-drill convention)."""
        if lost_slices is None:
            lost_slices = tuple(range(self.n_slices // 2, self.n_slices))
        survivors = surviving_devices(self._all_devices, lost_slices,
                                      self.n_slices)
        return self._reshard(survivors, kind="shrink",
                             lost_slices=tuple(lost_slices))

    def expand(self, devices=None):
        """Re-expand onto the full (or given) device set — the reverse path
        of :meth:`shrink`, restoring the original partition layout."""
        return self._reshard(list(devices) if devices is not None
                             else list(self._all_devices), kind="expand")

    def _reshard(self, devices, kind, lost_slices=()):
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.checkpoint.universal import (
            latest_universal_tag, load_universal_checkpoint,
            read_universal_meta, topology_remap)
        from deepspeed_tpu.utils.logging import logger
        from deepspeed_tpu.utils.retry import retry_call
        t0 = time.perf_counter()
        old = self.engine.topology if self.engine is not None else None
        span = telemetry.span_begin("Recovery/reshard", event=kind,
                                    world=len(devices))
        try:
            topo = self._build(devices, kind=kind, like=old)
            tag = latest_universal_tag(self.ckpt_dir)
            if tag is None:
                raise SliceLostError(
                    f"no durable universal tag under {self.ckpt_dir!r} to "
                    f"reshard from", lost_slices=lost_slices)
            tag_dir = os.path.join(self.ckpt_dir, tag)
            remap = topology_remap(read_universal_meta(tag_dir), topo)
            retry_call(lambda: load_universal_checkpoint(self.engine, tag_dir),
                       retries=self.restore_retries,
                       base_delay=self.restore_delay,
                       retry_on=(OSError, ValueError), sleep=self._sleep)
            if self.replan_fn is not None:
                self.last_plan = self.replan_fn(topo.world_size())
        finally:
            span.end()
        seconds = time.perf_counter() - t0
        event = {"kind": kind, "world": topo.world_size(),
                 "from_world": remap["from_world"], "tag": tag,
                 "step": self.engine.global_steps, "seconds": seconds,
                 "lost_slices": tuple(lost_slices),
                 "axis_deltas": remap["axis_deltas"]}
        self.reshard_events.append(event)
        self._record("elastic/reshard_s", seconds, kind_tag=kind)
        telemetry.count("Recovery/reshard", event=kind,
                        world=topo.world_size())
        logger.warning(
            f"elastic reshard ({kind}): world {remap['from_world']} -> "
            f"{topo.world_size()}, resumed at step {self.engine.global_steps} "
            f"from tag {tag!r} in {seconds:.3f}s")
        return event

    def _record(self, name, value, kind_tag=""):
        from deepspeed_tpu import telemetry
        telemetry.record(name, value, kind="gauge", event=kind_tag)


def run_elastic(controller, batches, expand_at=None):
    """Drive ``controller`` over ``batches``, replaying on reshard.

    Batches are indexed by ``engine.global_steps`` — after a shrink the
    restore path rewinds that counter to the last durable step, so the
    replay picks up the exact batch whose optimizer step never applied.
    ``expand_at``: step number before which to re-expand to the full world
    (checked when the loop reaches it, i.e. after step ``expand_at - 1``
    committed). Returns ``{"losses": {step: loss}, "opt_steps": [...]}``
    plus the controller's world/reshard history."""
    if controller.engine is None:
        controller.start()
    losses = {}
    opt_steps = []
    n = len(batches)
    while controller.engine.global_steps < n:
        step = controller.engine.global_steps
        if expand_at is not None and step >= expand_at and \
                controller.world_history[-1] < controller.world_history[0]:
            controller.expand()
            continue  # re-read global_steps under the restored engine
        loss = controller.train_step(batches[step])
        if loss is None:
            continue  # slice lost — replay at the rewound step
        losses[step] = loss
        opt_steps.append(controller.engine.global_steps)
    return {"losses": losses, "opt_steps": opt_steps,
            "world_history": list(controller.world_history),
            "reshard_events": list(controller.reshard_events)}


# ------------------------------------------------------------------ drill

def run_elastic_drill(ckpt_dir, steps=6, fail_at_step=2, expand_at=4,
                      n_slices=2, hidden_dim=32, replan=False):
    """The in-process 8→4→8 drill (CPU, 8 forced host devices): train with
    a ``slice.lost`` fault armed mid-run, shrink to the surviving half,
    re-expand, and compare the loss trajectory against a fault-free
    full-world reference run: bitwise at a restore step that runs on the
    reference's world, within ``RESTORE_LOSS_MAX_ULPS`` at one that runs on
    the survivors'. Returns the baseline payload consumed by
    ``perf_gate.py check_elastic_baseline`` and asserted by the e2e test.
    """
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.checkpoint.universal import _opt_step_count
    from deepspeed_tpu.parallel import groups
    from tests.simple_model import SimpleModel, random_batches

    config = {
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3,
                              "stage3_param_persistence_threshold": 0},
    }
    batches = random_batches(steps, batch_size=8, seed=1)
    model = SimpleModel(hidden_dim=hidden_dim)
    init_params = model.init(jax.random.PRNGKey(0), batches[0])["params"]

    def build_engine(topo):
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, model_parameters=init_params, config=dict(config),
            mesh=topo)
        return engine

    # fault-free full-world reference trajectory
    faults.reset()
    groups.reset()
    ref_engine = build_engine(build_topology_for(list(jax.devices())))
    ref_losses = {}
    for i, b in enumerate(batches):
        loss = ref_engine(b)
        ref_engine.backward(loss)
        ref_engine.step()
        ref_losses[i] = float(
            np.asarray(loss))  # graftlint: allow[GL004] bitwise reference

    replan_calls = []

    def replan_fn(world):
        plan, _ = replan_for_world(
            model, init_params, dict(config),
            lambda mbs: batches[0], world,
            compile_fn=_drill_compile_fn)
        replan_calls.append(world)
        return plan

    groups.reset()
    controller = ElasticReshardController(
        build_engine, ckpt_dir, n_slices=n_slices,
        replan_fn=replan_fn if replan else None)
    controller.start()
    # arm AFTER start: the whole upper half of the slice set dies exactly
    # once, mid-step (before the optimizer apply)
    faults.configure(f"slice.lost:once@step{fail_at_step}", seed=0)
    try:
        result = run_elastic(controller, batches, expand_at=expand_at)
    finally:
        faults.reset()

    worlds = result["world_history"]
    # the replayed forward AT each restore step against the full-world
    # reference, in float32 ulps: bitwise on the reference's own world (the
    # re-expansion: the state came back exact); on the survivors' the same
    # loss is summed over half the devices in another order, 0 or 1 ulp
    events = {e["step"]: e for e in result["reshard_events"]}
    restore_steps = list(events)
    ulps = {s: _ulps32(result["losses"][s], ref_losses[s])
            for s in restore_steps if s in ref_losses}
    bitwise = all(n == 0 for s, n in ulps.items()
                  if events[s]["world"] == worlds[0])
    traj_rel_err = max(
        abs(result["losses"][i] - ref_losses[i]) / max(abs(ref_losses[i]),
                                                       1e-12)
        for i in ref_losses)
    payload = {
        "drill": "elastic-reshard-8-4-8",
        "steps": steps,
        "fail_at_step": fail_at_step,
        "expand_at": expand_at,
        "world_sequence": worlds,
        "reshard_count": len(result["reshard_events"]),
        "reshard_s": {e["kind"]: round(e["seconds"], 4)
                      for e in result["reshard_events"]},
        "steps_lost": steps - len(result["losses"]),
        "steps_double_applied": sum(
            1 for a, b in zip(result["opt_steps"], result["opt_steps"][1:])
            if b <= a),
        "final_optimizer_step": _opt_step_count(
            controller.engine.state.opt_state),
        "restore_steps": restore_steps,
        "restore_loss_bitwise_equal": bool(bitwise),
        "restore_loss_ulps": {str(s): n for s, n in sorted(ulps.items())},
        "trajectory_max_rel_err": traj_rel_err,
        "losses": {str(k): v for k, v in sorted(result["losses"].items())},
        "ref_losses": {str(k): v for k, v in sorted(ref_losses.items())},
        "replan_worlds": replan_calls,
    }
    return payload


#: float32 ulps a restore step's loss may lie from the full-world reference's
#: on another world (``scripts/perf_gate.py`` holds the record to the same)
RESTORE_LOSS_MAX_ULPS = 4


def _ulps32(a, b):
    """Float32 values between ``a`` and ``b`` (both of one sign)."""
    import struct
    bits = struct.unpack("<2i", struct.pack("<2f", a, b))
    return abs(bits[0] - bits[1])


def _drill_compile_fn(fn, abstract):
    """Synthetic compile for chip-free re-planning inside the CPU drill."""
    class _Mem:
        temp_size_in_bytes = 1 << 20
        output_size_in_bytes = 1 << 20
    return {"flops": 1e9, "bytes accessed": 1e8}, _Mem()
