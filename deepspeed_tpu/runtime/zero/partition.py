"""ZeRO partitioning as GSPMD sharding specs.

The heart of the reference is partitioning params/grads/optimizer state across
the DP world (``zero/stage_1_and_2.py:96``, ``zero/stage3.py:75``,
``zero/partition_parameters.py:783``). On TPU the same capability is a *sharding
rule*: for each parameter leaf, pick an axis to shard over the ZeRO mesh axes
(dp, ep, sp), composed with any model-parallel (tp/ep) spec the model already
declares. XLA's GSPMD partitioner then emits the reduce-scatter (grads) and
all-gather (params) collectives that the reference implements by hand with
bucketed NCCL calls.

Stage semantics (reference ``zero/config.py``):
  0: master/opt replicated, grads replicated        (plain DP)
  1: master/opt sharded                             (optimizer-state partitioning)
  2: + gradient accumulation buffer sharded         (gradient partitioning)
  3: + working (bf16) params sharded                (parameter partitioning)

``stage3_param_persistence_threshold`` (reference ``zero/config.py:194``): leaves
smaller than the threshold stay replicated — identical capability (small params
are "persisted" rather than gathered per-use).
"""

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.utils.logging import logger


def _leaf_spec_with_zero(leaf, base_spec, zero_axes, mesh_sizes, threshold):
    """Compose ``base_spec`` (model-parallel) with a ZeRO shard axis choice.

    Mesh axes already consumed by the model spec (e.g. 'ep' on a stacked expert
    axis) are excluded — a NamedSharding may use each axis once."""
    shape = np.asarray(leaf.shape, dtype=np.int64) if hasattr(leaf, "shape") else None
    if shape is None or leaf.size < max(threshold, 1) or leaf.ndim == 0:
        return base_spec
    base = tuple(base_spec) if base_spec is not None else ()
    base = base + (None,) * (leaf.ndim - len(base))
    used = set()
    for entry in base:
        if entry is None:
            continue
        for a in (entry if isinstance(entry, tuple) else (entry,)):
            used.add(a)
    axes = tuple(a for a in zero_axes if a not in used)
    if not axes:
        return base_spec
    world = int(np.prod([mesh_sizes[a] for a in axes]))
    if world <= 1:
        return base_spec
    # choose the largest dimension not already sharded that divides the world
    best_dim, best_size = None, 0
    for d in range(leaf.ndim):
        if base[d] is not None:
            continue
        if shape[d] % world == 0 and shape[d] > best_size:
            best_dim, best_size = d, shape[d]
    if best_dim is None:
        return base_spec
    new = list(base)
    new[best_dim] = axes if len(axes) > 1 else axes[0]
    return P(*new)


class ZeroPartitioner:
    """Computes per-leaf shardings for every engine-state component."""

    def __init__(self, topology, zero_config, param_specs=None):
        self.topology = topology
        self.config = zero_config
        self.stage = zero_config.stage
        self.mesh = topology.mesh
        # only keep zero axes that actually have extent > 1. Master/opt/grads
        # shard over the full ZeRO world; working params may use the smaller
        # hierarchical group (hpZ secondary partition / MiCS shard group).
        self.zero_axes = tuple(a for a in topology.zero_axes if topology.get_dim(a) > 1)
        self.param_axes = tuple(a for a in topology.param_zero_axes
                                if topology.get_dim(a) > 1)
        self.zero_world = int(np.prod([topology.get_dim(a) for a in self.zero_axes])) if self.zero_axes else 1
        self.param_specs = param_specs  # pytree of P or None (model/tp specs)
        self._unfit_logged = set()
        self.threshold = zero_config.stage3_param_persistence_threshold

    def _base_specs(self, params):
        if self.param_specs is None:
            return jax.tree.map(lambda _: None, params)
        return jax.tree.map(self._fit_spec, params, self.param_specs,
                            is_leaf=lambda x: x is None)

    def _fit_spec(self, leaf, spec):
        """A model's spec names mesh axes without knowing their extent: keep
        an entry only where the dimension divides evenly (GPT-2's published
        vocabulary, 50257, is odd — its vocab-split table stays replicated
        over tp instead of failing the whole placement)."""
        if spec is None or not hasattr(leaf, "shape"):
            return spec
        fitted = []
        for d, entry in enumerate(tuple(spec)):
            axes = entry if isinstance(entry, tuple) else (entry,)
            n = int(np.prod([self.topology.get_dim(a)
                             for a in axes if a is not None]))
            if entry is not None and leaf.shape[d] % n != 0:
                key = (tuple(leaf.shape), d, axes)
                if key not in self._unfit_logged:
                    self._unfit_logged.add(key)
                    logger.warning(
                        f"param spec {spec} on shape {tuple(leaf.shape)}: "
                        f"dim {d} does not divide by {axes} (x{n}); "
                        f"replicated")
                entry = None
            fitted.append(entry)
        return P(*fitted)

    def _zero_tree(self, params, threshold, axes=None):
        base = self._base_specs(params)
        axes = self.zero_axes if axes is None else axes
        if not axes:
            return base
        sizes = {a: self.topology.get_dim(a) for a in axes}
        return jax.tree.map(
            lambda leaf, spec: _leaf_spec_with_zero(leaf, spec, axes,
                                                    sizes, threshold),
            params, base, is_leaf=lambda x: x is None)

    def _to_sharding(self, spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s if s is not None else P()),
            spec_tree, is_leaf=lambda x: x is None or isinstance(x, P))

    # --- public per-component sharding trees ---
    def param_sharding(self, params):
        """Working-precision params: sharded only at stage 3 (plus model specs).
        Under hpZ/MiCS hierarchy the shard axes are the ICI-local group only
        (reference secondary tensors, ``partition_parameters.py`` hpZ)."""
        if self.stage >= 3:
            spec = self._zero_tree(params, self.threshold, axes=self.param_axes)
        else:
            spec = self._base_specs(params)
        return self._to_sharding(spec)

    def use_sharding(self, params):
        """Sharding at *use* sites inside the jitted step: model-parallel specs
        only, ZeRO axes gathered. Constraining params to this tree before
        ``model.apply`` is the GSPMD form of stage 3's per-use parameter
        all-gather (reference ``zero/partitioned_param_coordinator.py`` fetch):
        XLA inserts the all-gather at the use and — crucially — stops the
        *storage* sharding (hidden dim split over dp/sp) from propagating into
        activation shardings, which otherwise forces involuntary full
        rematerialization at sharding transitions."""
        return self._to_sharding(self._base_specs(params))

    def master_sharding(self, params):
        """fp32 master + optimizer moments: sharded from stage 1 up. Persistence
        threshold does NOT apply (the reference shards all optimizer state)."""
        if self.stage >= 1:
            spec = self._zero_tree(params, threshold=0)
        else:
            spec = self._base_specs(params)
        return self._to_sharding(spec)

    def grad_sharding(self, params):
        """Gradient accumulation buffer: sharded from stage 2 up."""
        if self.stage >= 2:
            spec = self._zero_tree(params, threshold=0)
        else:
            spec = self._base_specs(params)
        return self._to_sharding(spec)

    def opt_state_sharding(self, opt_state, params):
        """Optimizer state leaves that mirror a param shape get the master
        sharding; scalars/counters are replicated."""
        master = self.master_sharding(params)
        flat_master, _ = jax.tree.flatten(master)
        by_shape = {}
        for leaf, sh in zip(jax.tree.leaves(params), flat_master):
            by_shape.setdefault(tuple(leaf.shape), sh)
        rep = NamedSharding(self.mesh, P())

        def pick(leaf):
            if hasattr(leaf, "shape") and tuple(leaf.shape) in by_shape and leaf.ndim > 0:
                return by_shape[tuple(leaf.shape)]
            return rep

        return jax.tree.map(pick, opt_state)

    def describe(self, params):
        """Human-readable partition report (analog of the reference's partition
        logging in stage_1_and_2.py)."""
        shardings = self.master_sharding(params)
        n_sharded = sum(1 for s in jax.tree.leaves(shardings) if s.spec != P())
        total = len(jax.tree.leaves(params))
        logger.info(f"ZeRO stage {self.stage}: sharding {n_sharded}/{total} leaves "
                    f"over axes {self.zero_axes} (world {self.zero_world})")
