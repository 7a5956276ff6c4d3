"""Op registry — the analog of ``op_builder/`` (reference ``op_builder/builder.py:108``).

The reference JIT-compiles CUDA extensions per accelerator with compatibility
probing (``is_compatible``, ``builder.py:250``) and a ``load()`` entry point.
Here every op has a pure-jnp reference implementation and optionally a Pallas
TPU kernel; ``load()`` returns the best available implementation, and
``is_compatible`` reports whether the fast path can run on the current backend.
"""

from deepspeed_tpu.utils.logging import logger

_REGISTRY = {}
_POPULATED = False


class OpBuilder:
    """Base op builder: name + jnp fallback + optional pallas impl."""

    NAME = None

    def __init__(self):
        self._loaded = None

    def is_compatible(self, verbose=False):
        """Whether the Pallas slot runs here: the shared gate
        (``pallas_enabled``: TPU, interpret or assume-TPU) is open and this
        builder declares a kernel. A kernel module that fails to import
        raises — there is no quiet reference takeover."""
        ok = pallas_enabled() and self.pallas_available()
        if verbose and not ok:
            logger.info(f"op {self.NAME}: pure-XLA implementation")
        return ok

    def pallas_available(self):
        return self.pallas_impl() is not None

    def pallas_impl(self):
        return None

    def reference_impl(self):
        raise NotImplementedError

    def load(self, verbose=False):
        """Return the best implementation (reference ``builder.py:463`` load)."""
        if self._loaded is None:
            if self.is_compatible(verbose=verbose):
                self._loaded = self.pallas_impl()
            else:
                self._loaded = self.reference_impl()
        return self._loaded


def pallas_interpret():
    """True when Pallas kernels should run in interpret mode (CPU emulation
    of the grid program). Slow; exists so multi-chip dryruns on a virtual
    CPU mesh can exercise the REAL kernel code path — padding, custom vjp,
    GSPMD composition — instead of silently taking the XLA fallback."""
    import os
    return bool(os.environ.get("DS_TPU_PALLAS_INTERPRET"))


def pallas_enabled():
    """True when Pallas fast paths may be used: a TPU backend is live and the
    DS_TPU_DISABLE_PALLAS kill-switch is off. THE shared gate — heuristics
    and op wrappers must not re-implement platform probing.
    DS_TPU_PALLAS_INTERPRET forces True on any platform (interpret mode).
    DS_TPU_ASSUME_TPU forces True WITHOUT interpret: for AOT topology
    compiles (scripts/aot_tpu_check.py) where the host platform is CPU but
    the compile target is a real TPU — traced programs must be byte-for-byte
    the on-chip programs, flash kernels included."""
    import os
    if os.environ.get("DS_TPU_DISABLE_PALLAS"):
        return False
    if pallas_interpret() or os.environ.get("DS_TPU_ASSUME_TPU"):
        return True
    import jax
    return jax.devices()[0].platform == "tpu"


def takes_kernel(kernel, supported, detail):
    """Whether a call site with a pure-XLA twin takes its Pallas kernel:
    Pallas is on and the kernel tiles the call's shapes (``supported``, the
    kernel module's ``is_supported``). A no is a dispatch record (``kernel``,
    "fallback", "no_tpu" | "unsupported_shape") and, with Pallas on, one
    warning naming ``detail``: a backend that is off is expected, shapes that
    lose the fast path on the chip are not."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.utils.logging import warning_once
    if not pallas_enabled():
        telemetry.record_dispatch(kernel, "fallback", "no_tpu")
        return False
    if not supported:
        telemetry.record_dispatch(kernel, "fallback", "unsupported_shape")
        warning_once(f"{kernel}: {detail}; XLA fallback")
        return False
    return True


# ---------------------------------------------------------------------------
# Block-config resolution — the tuning-table half of kernel dispatch.
#
# Every Pallas kernel resolves its block sizes here before invoking
# ``sharded_kernel_call``: persistent per-device tuning table first
# (autotuning/kernel_table.py, regenerated by scripts/tune_kernels.py),
# hardcoded ladder as the fallback. The decision is a telemetry dispatch
# record (outcome "tuning", reason tuned|ladder_fallback|env_override) and
# the last-resolved config per kernel is exposed to bench/debug via
# ``active_kernel_configs()``.
# ---------------------------------------------------------------------------

_ACTIVE_BLOCK_CONFIGS = {}  # kernel name -> last-resolved BlockConfig
_ACTIVE_KERNEL_FACTS = {}  # kernel name -> what else its last dispatch noted


def resolve_block_config(kernel, dims, dtype, validate=None, ladder=None):
    """Table-first block-size resolution for one kernel dispatch.

    ``dims`` is the kernel's canonical shape signature
    (``kernel_table.KERNEL_DIMS``), ``validate(blocks, dims)`` rejects tuned
    blocks that don't fit the *exact* shape, and ``ladder()`` returns the
    hardcoded fallback knobs. Runs at trace time (shapes are static);
    always returns a ``BlockConfig``.
    """
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.autotuning import kernel_table

    cfg, reason = kernel_table.resolve(kernel, dims, str(dtype),
                                       validate=validate)
    if cfg is None:
        knobs = ladder() if ladder is not None else {}
        cfg = kernel_table.BlockConfig.make(kernel, source="ladder", **knobs)
    telemetry.record_dispatch(kernel, "tuning", reason)
    _ACTIVE_BLOCK_CONFIGS[kernel] = cfg
    return cfg


def note_block_config(kernel, cfg, reason="env_override"):
    """Record an externally-chosen config (env override, sweep candidate) so
    telemetry and ``active_kernel_configs`` stay truthful."""
    from deepspeed_tpu import telemetry
    telemetry.record_dispatch(kernel, "tuning", reason)
    _ACTIVE_BLOCK_CONFIGS[kernel] = cfg
    return cfg


def note_kernel_facts(kernel, **facts):
    """What a kernel's dispatch knows from its shapes beside its blocks
    (``flash_mha``: ``visible_share``, the share of the [tq, tk] square whose
    logits its blocks and tiles compute), for ``active_kernel_configs``."""
    _ACTIVE_KERNEL_FACTS[kernel] = facts


def active_kernel_configs():
    """kernel -> {knob: value..., "source": ..., noted facts} for the last
    block config each kernel dispatched with in this process (bench ``extra``
    reporting)."""
    out = {}
    for k, cfg in sorted(_ACTIVE_BLOCK_CONFIGS.items()):
        d = cfg.as_dict()
        d["source"] = cfg.source
        d.update(_ACTIVE_KERNEL_FACTS.get(k, {}))
        out[k] = d
    return out


# ---------------------------------------------------------------------------
# SPMD kernel dispatch — the op-layer half of the Pallas shard_map bridge
# (topology half: ``parallel/topology.py:use_kernel_mesh`` and friends).
#
# GSPMD auto-partitioning stops at Mosaic custom calls: a Pallas kernel traced
# under a multi-device jit fails to compile with "Mosaic kernels cannot be
# automatically partitioned. Please wrap the call in a shard_map." Every
# Pallas kernel wrapper therefore routes its invocation through
# ``sharded_kernel_call``, which wraps the call in a ``shard_map`` over the
# active mesh's data (batch/token/expert) and head (TP) axes — and degrades
# to a plain call whenever sharding is impossible or pointless, so
# single-device behavior and the pure-XLA twins are untouched.
# ---------------------------------------------------------------------------


def sharded_kernel_call(fn, args, in_roles, out_roles, accept=None, name=None,
                        block_config=None):
    """Invoke kernel ``fn(*args)``, shard_map-wrapped over the active mesh.

    ``block_config`` is the ``BlockConfig`` this dispatch runs with (resolved
    by ``resolve_block_config``); it is attached to the per-kernel active
    config registry so bench/telemetry can attribute throughput to the blocks
    actually in flight.

    ``in_roles``/``out_roles``: per-dimension role tags, one tuple per
    argument / output — each entry ``"data"`` (shard over the mesh's
    batch-like axes), ``"head"`` (shard over the TP axis) or ``None``
    (replicate). ``out_roles`` may be a single tuple (one output) or a list
    of tuples (tuple output).

    A role is only honored when every dimension tagged with it divides
    evenly by the corresponding axis product; otherwise that role is dropped
    (those dims stay replicated). ``accept(shard_shapes)`` — per-shard shapes
    after the division — lets kernels veto sharding that violates their
    block/tile constraints. Falls back to a direct ``fn(*args)`` when no mesh
    is active, the mesh is trivial, or no role survives the checks.

    ``name`` labels the telemetry dispatch counter (default: ``fn.__name__``).
    Every decision — sharded, fallback, veto — is recorded with a reason code
    (docs/OBSERVABILITY.md) when telemetry is enabled, so a silent XLA
    fallback becomes a visible metric instead of a perf mystery.

    The mesh binds at TRACE time: jax trace caches (including inner ``jit``
    wrappers around callers of this, keyed on shapes only) will replay a
    previously captured shard_map even after the active mesh changed.
    Processes that flip meshes between traces of the same shapes (AOT
    sweeps, tests) must ``jax.clear_caches()`` in between.
    """
    import jax
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.parallel import topology

    kname = name or getattr(fn, "__name__", "kernel")
    if block_config is not None:
        _ACTIVE_BLOCK_CONFIGS[kname] = block_config
    mesh = topology.active_kernel_mesh()
    if mesh is None:
        telemetry.record_dispatch(kname, "fallback", "no_mesh")
        return fn(*args)
    if mesh.size == 1:
        telemetry.record_dispatch(kname, "fallback", "trivial_mesh",
                                  mesh_size=1)
        return fn(*args)
    roles = topology.kernel_partition_axes(mesh)
    shape = dict(mesh.shape)
    factor = {"data": 1, "head": 1}
    if roles["data"]:
        f = 1
        for a in roles["data"]:
            f *= shape[a]
        factor["data"] = f
    if roles["head"]:
        factor["head"] = shape[roles["head"]]

    # a role survives only if every dim tagged with it divides evenly
    tagged = {"data": [], "head": []}
    for arg, r in zip(args, in_roles):
        for d, role in enumerate(r):
            if role is not None:
                tagged[role].append(arg.shape[d])
    live = {}
    for role in ("data", "head"):
        if tagged[role] and factor[role] > 1 and \
                all(s % factor[role] == 0 for s in tagged[role]):
            live[role] = roles["data"] if role == "data" else roles["head"]
    if not live:
        telemetry.record_dispatch(kname, "fallback", "no_live_role",
                                  mesh_size=mesh.size)
        return fn(*args)
    if accept is not None:
        shard_shapes = [
            tuple(s // factor[role] if (role := r[d]) in live else s
                  for d, s in enumerate(arg.shape))
            for arg, r in zip(args, in_roles)]
        if not accept(shard_shapes):
            telemetry.record_dispatch(kname, "veto", "accept_veto",
                                      mesh_size=mesh.size)
            return fn(*args)

    def spec(r):
        return P(*[live.get(role) for role in r])

    in_specs = tuple(spec(r) for r in in_roles)
    if isinstance(out_roles, list):
        out_specs = tuple(spec(r) for r in out_roles)
    else:
        out_specs = spec(out_roles)
    telemetry.record_dispatch(kname, "sharded",
                              "+".join(sorted(live)) or "ok",
                              mesh_size=mesh.size)
    wrapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    return wrapped(*args)


def register_op_builder(cls):
    assert cls.NAME is not None
    _REGISTRY[cls.NAME] = cls
    return cls


def get_op_builder(name):
    _populate()
    return _REGISTRY.get(name)


def available_ops():
    _populate()
    return sorted(_REGISTRY)


def _populate():
    # import modules for registration side effects. Guarded by a flag, not
    # registry emptiness: a direct `import deepspeed_tpu.ops.X` elsewhere
    # partially fills the registry and must not suppress the full population.
    global _POPULATED
    if _POPULATED:
        return
    _POPULATED = True
    for mod in ("adam", "aio", "cpu_adam", "flash_attention", "quantizer",
                "cpu_adagrad", "cpu_lion", "evoformer_attn",
                "sparse_attention.sparse_self_attention", "spatial",
                "inference_builders"):
        __import__(f"deepspeed_tpu.ops.{mod}")
