"""qgZ — ZeRO++ quantized gradient reduction, wired into the engine grad path.

Reference: ``zero_quantized_gradients`` routes the stage-3 gradient reduction
through ``all_to_all_quant_reduce`` (``runtime/zero/stage3.py:1249`` →
``runtime/comm/coalesced_collectives.py:81``): int4 all-to-all + reduce within
the node, int8 across nodes — ~4x less cross-node gradient traffic.

TPU design: under GSPMD the gradient all-reduce is emitted by XLA and cannot be
intercepted, so the qgZ engine path flips the ZeRO data axes to *manual*
(``jax.shard_map(axis_names={dp, dpr}, check_vma=False)``) while every other
axis (tp/sp/ep) stays compiler-managed:

- the micro-step computes **local** (unreduced) per-device gradients and
  accumulates them in a stacked ``[zero_world, ...]`` buffer sharded over the
  manual axes — exactly the reference's unreduced per-rank grad buffers;
- at the GAS boundary :func:`QgzPlan.reduce` performs the hierarchical
  quantized exchange per leaf along its ZeRO shard dimension: int4 blocks
  all-to-all'd over ``dp`` (ICI) and locally reduced, then int8 over ``dpr``
  (DCN), landing each device exactly its GSPMD gradient shard (axes-major
  chunk order). Leaves with no ZeRO-shardable dimension fall back to a plain
  ``psum``.

Trade-off vs the auto path (documented, inherent to manual-mode): stage-3
params are all-gathered at micro-step entry instead of per-use inside the
layer scan.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.runtime.comm.coalesced_collectives import exchange_reduce


class QgzPlan:
    """Everything the engine needs to run qgZ: manual axes, spec trees for the
    stacked local-grad buffer, and the boundary reduction."""

    def __init__(self, topology, partitioner, params_abstract, group_size=2048,
                 intra_bits=4, inter_bits=8):
        self.topology = topology
        self.mesh = topology.mesh
        self.group_size = group_size
        self.intra_bits = intra_bits
        self.inter_bits = inter_bits
        # hierarchy: dp rides ICI (intra), dpr rides DCN (inter)
        axes = tuple(a for a in ("dpr", "dp") if topology.get_dim(a) > 1)
        for a in ("ep", "sp"):
            if topology.get_dim(a) > 1:
                raise ValueError(
                    f"zero_quantized_gradients currently supports dp/dpr ZeRO "
                    f"axes only (got {a} size {topology.get_dim(a)} in the "
                    f"ZeRO world)")
        if not axes:
            raise ValueError("zero_quantized_gradients requires a data-parallel "
                             "world > 1")
        self.axes = axes                      # GSPMD chunk-major order
        self.sizes = {a: topology.get_dim(a) for a in axes}
        self.world = int(np.prod(list(self.sizes.values())))
        self.manual = set(axes)

        # per-leaf target gradient spec (the partitioner's stage>=2 layout)
        self.grad_specs = partitioner._zero_tree(params_abstract, threshold=0)
        self.base_specs = partitioner._base_specs(params_abstract)
        self.param_specs = (partitioner._zero_tree(params_abstract,
                                                   partitioner.threshold,
                                                   axes=partitioner.param_axes)
                            if partitioner.stage >= 3 else self.base_specs)

    # --- spec plumbing -------------------------------------------------
    def _project(self, spec):
        """Spec projected onto the manual axes (auto-axis entries dropped) —
        what shard_map in_specs must describe."""
        if spec is None:
            return P()
        out = []
        for e in spec:
            if e is None:
                out.append(None)
                continue
            axes = tuple(a for a in (e if isinstance(e, tuple) else (e,))
                         if a in self.manual)
            out.append(axes if len(axes) > 1 else (axes[0] if axes else None))
        return P(*out)

    def param_in_specs(self, params):
        return jax.tree.map(lambda _, s: self._project(s), params,
                            self.param_specs)

    def batch_in_spec(self):
        return P(self.axes)

    def stacked_spec(self, base_spec, project=False):
        base = tuple(base_spec) if base_spec is not None else ()
        stacked = P(self.axes, *base)
        return self._project(stacked) if project else stacked

    def stacked_specs(self, params, project=False):
        """Full specs (for buffer shardings) or manual-axis-projected specs
        (for shard_map in/out_specs — those may only mention manual axes)."""
        return jax.tree.map(
            lambda _, s: self.stacked_spec(s, project=project), params,
            self.base_specs)

    def stacked_shardings(self, params):
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            self.stacked_specs(params),
            is_leaf=lambda x: isinstance(x, P))

    def stacked_zeros(self, params, dtype):
        # allocate directly sharded (jit with out_shardings): device_put of a
        # host/default-device zeros would transiently stage world x leaf bytes
        # on one device — the OOM ZeRO exists to avoid
        shardings = self.stacked_shardings(params)
        shapes = jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct((self.world,) + tuple(leaf.shape),
                                              dtype), params)
        make = jax.jit(
            lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes),
            out_shardings=shardings)
        return make()

    def _gather_leaf(self, x, spec, skip_dims=0):
        """All-gather one leaf's manual-axis shards; ``skip_dims`` drops
        leading spec entries (a sliced-out scan dim shifts the rest left)."""
        if spec is None:
            return x
        for d, e in enumerate(spec[skip_dims:] if skip_dims else spec):
            if e is None:
                continue
            man = tuple(a for a in (e if isinstance(e, tuple) else (e,))
                        if a in self.manual)
            if man:
                x = lax.all_gather(x, man, axis=d, tiled=True)
        return x

    def gather_params(self, params_local, specs=None):
        """Inside the shard_map body: all-gather stage-3 param shards over the
        manual axes (the reference's param all-gather, done at step entry).
        ``specs`` restricts to a subtree (the overlap pass gathers only the
        resident leaves here; stacked blocks stream via gather_block)."""
        specs = self.param_specs if specs is None else specs
        return jax.tree.map(self._gather_leaf, params_local, specs)

    def gather_block(self, stacked_local, specs, i):
        """One scan block's params, gathered: slice block ``i`` off each
        stacked leaf's leading scan dim, then all-gather its ZeRO shards.
        This is the per-layer shard exchange the overlap schedule issues on
        the previous layer's boundary (overlap_schedule.scheduled_scan) —
        same math as slicing the monolithic gather, HBM holds O(depth)
        blocks instead of the stack."""
        def one(x, spec):
            # the partitioner may have put the ZeRO shard on the scan dim
            # itself — gather it first so index ``i`` addresses global blocks
            if spec is not None and len(spec) and spec[0] is not None:
                e = spec[0]
                man = tuple(a for a in (e if isinstance(e, tuple) else (e,))
                            if a in self.manual)
                if man:
                    x = lax.all_gather(x, man, axis=0, tiled=True)
            x = lax.dynamic_index_in_dim(x, i, axis=0, keepdims=False)
            return self._gather_leaf(x, spec, skip_dims=1)
        return jax.tree.map(one, stacked_local, specs)

    # --- leaf-wise zero-dim discovery ---------------------------------
    def _zero_dim(self, grad_spec, base_spec):
        """(dim, axes) the partitioner chose for this leaf's ZeRO shard, or
        (None, None) when the leaf stays replicated over the manual axes."""
        if grad_spec is None:
            return None, None
        base = tuple(base_spec) if base_spec is not None else ()
        for d, e in enumerate(grad_spec):
            if e is None:
                continue
            be = base[d] if d < len(base) else None
            if e == be:
                continue  # model-parallel entry, unchanged by the partitioner
            axes = tuple(a for a in (e if isinstance(e, tuple) else (e,))
                         if a in self.manual)
            if axes:
                return d, axes
        return None, None

    # --- boundary reduction --------------------------------------------
    def _reduce_leaf(self, local, d, axes, want_error=False):
        """Hierarchical quantized exchange of one leaf's chunks along dim d.

        ``local``: this device's full-shape accumulated gradient. Returns this
        device's chunk (the GSPMD shard for spec entry ``axes`` on dim d, in
        axes-major order). ``want_error=True`` additionally returns this
        device's quantization residual mapped back into ``local``'s
        coordinates (the error-feedback carry: stage-1 errors at their source
        chunks, the stage-2 error at this device's own dp chunk column)."""
        moved = jnp.moveaxis(local, d, 0)
        rest = moved.shape[1:]
        err = None
        if axes == ("dpr", "dp"):
            R, D = self.sizes["dpr"], self.sizes["dp"]
            chunks = moved.reshape(R, D, -1)                  # [R, D, m]
            m = chunks.shape[2]
            # stage 1 (ICI): dp-peer i receives slab chunks[:, i]
            slabs = chunks.transpose(1, 0, 2).reshape(D, -1)  # [D, R*m]
            s1 = exchange_reduce(slabs, "dp", self.intra_bits,
                                 self.group_size,
                                 return_error=want_error)     # [R*m]
            partial = s1[0] if want_error else s1
            # stage 2 (DCN): dpr-peer r receives row r of the partial
            s2 = exchange_reduce(partial.reshape(R, m), "dpr",
                                 self.inter_bits, self.group_size,
                                 return_error=want_error)     # [m]
            out = s2[0] if want_error else s2
            if want_error:
                # e1 [D, R*m] back to chunk coords; e2 [R, m] lands at this
                # device's own dp column (it is an error on the partial sum
                # only this device held — re-fed here, the next step's stage-1
                # sum carries it forward)
                e1 = s1[1].reshape(D, R, m).transpose(1, 0, 2)   # [R, D, m]
                my_dp = lax.axis_index("dp")
                hot = (jax.nn.one_hot(my_dp, D, dtype=e1.dtype)
                       [None, :, None])                          # [1, D, 1]
                err = (e1 + s2[1][:, None, :] * hot).reshape(moved.shape)
        else:
            (axis,) = axes
            n = self.sizes[axis]
            bits = self.intra_bits if axis == "dp" else self.inter_bits
            s1 = exchange_reduce(moved.reshape(n, -1), axis, bits,
                                 self.group_size, return_error=want_error)
            out = s1[0] if want_error else s1
            if want_error:
                err = s1[1].reshape(moved.shape)
        chunk_shape = (moved.shape[0] // self.world
                       if axes == ("dpr", "dp") else
                       moved.shape[0] // self.sizes[axes[0]],) + rest
        out = jnp.moveaxis(out.reshape(chunk_shape), 0, d)
        if want_error:
            return out, jnp.moveaxis(err, 0, d)
        return out

    @staticmethod
    def _bucketize(sizes, buckets):
        """Contiguous leaf-index groups with roughly equal byte load — the
        grad-bucket split the overlap schedule issues as independent
        exchanges. Deterministic (leaf order), never empty, always exactly
        ``min(buckets, len(sizes))`` groups."""
        k = max(1, min(int(buckets), len(sizes)))
        total = float(sum(sizes)) or 1.0
        groups, cur, acc = [], [], 0.0
        for j, s in enumerate(sizes):
            cur.append(j)
            acc += s
            remaining_leaves = len(sizes) - j - 1
            remaining_groups = k - len(groups) - 1
            if (len(groups) < k - 1
                    and (acc >= total * (len(groups) + 1) / k
                         or remaining_leaves == remaining_groups)
                    and remaining_leaves >= remaining_groups):
                groups.append(cur)
                cur = []
        if cur:
            groups.append(cur)
        return groups

    def reduce(self, acc_stacked, residual=None, return_residual=False,
               buckets=1):
        """Stacked local-grad buffer -> GSPMD-sharded summed gradients.

        Inside shard_map over the manual axes, each leaf either does the
        quantized hierarchical exchange along its ZeRO dim or (no shardable
        dim) a plain fp psum.

        ``buckets`` > 1 (the overlap schedule's async grad reduce): the leaf
        list splits into that many contiguous byte-balanced groups, each
        exchanged in its OWN shard_map region — the resulting program is
        ``buckets`` independent collective chains instead of one monolithic
        chain, so XLA's latency-hiding scheduler can pipeline one bucket's
        quantize/dequantize math under another bucket's wire time and start
        exchanging as soon as a bucket's grads exist. Leaf-wise math is
        untouched — bucketization is bit-identical to the monolithic reduce.

        Error feedback (``zero_quantized_gradients_error_feedback``):
        ``residual`` is the previous step's quantization error in the same
        stacked layout as ``acc_stacked``; it is folded into each leaf before
        quantization. ``return_residual=True`` returns ``(grads, residual')``
        where ``residual'`` is this step's fresh error carry (zeros for psum
        leaves — they are never quantized)."""
        if return_residual and residual is None:
            raise ValueError("return_residual=True needs the previous "
                             "residual (pass stacked zeros on the first step)")
        leaves, treedef = jax.tree.flatten(acc_stacked)
        gspecs = treedef.flatten_up_to(self.grad_specs)
        bspecs = treedef.flatten_up_to(self.base_specs)
        res_leaves = (treedef.flatten_up_to(residual)
                      if residual is not None else [None] * len(leaves))
        out_projs = [self._project(s) for s in gspecs]
        in_projs = [self.stacked_spec(s, project=True) for s in bspecs]

        def one(leaf, res, gspec, bspec):
            local = leaf[0].astype(jnp.float32)            # [*shape]
            if res is not None:
                local = local + res[0].astype(jnp.float32)
            d, axes = self._zero_dim(gspec, bspec)
            if d is None:
                out = lax.psum(local, tuple(self.axes))
                # psum leaves are never quantized: zero error carry
                return out, (jnp.zeros_like(local)[None]
                             if return_residual else None)
            if return_residual:
                out, err = self._reduce_leaf(local, d, axes, want_error=True)
                return out, err[None]
            return self._reduce_leaf(local, d, axes), None

        sizes = [l.size * jnp.dtype(l.dtype).itemsize for l in leaves]
        groups = self._bucketize(sizes, buckets)

        out_leaves = [None] * len(leaves)
        err_leaves = [None] * len(leaves)
        for idxs in groups:
            g_in = [in_projs[j] for j in idxs]
            g_out = [out_projs[j] for j in idxs]

            def body(acc_list, res_list, _idxs=idxs):
                pairs = [one(leaf, res, gspecs[j], bspecs[j])
                         for leaf, res, j in zip(acc_list, res_list, _idxs)]
                if not return_residual:
                    return [p[0] for p in pairs]
                return [p[0] for p in pairs], [p[1] for p in pairs]

            if residual is None:
                fn = jax.shard_map(lambda a, _i=idxs, _b=body: _b(a, [None] * len(_i)),
                                   mesh=self.mesh, in_specs=(g_in,),
                                   out_specs=g_out,
                                   axis_names=self.manual, check_vma=False)
                got = fn([leaves[j] for j in idxs])
                errs = [None] * len(idxs)
            else:
                out_specs = ((g_out, g_in) if return_residual else g_out)
                fn = jax.shard_map(body, mesh=self.mesh,
                                   in_specs=(g_in, g_in),
                                   out_specs=out_specs,
                                   axis_names=self.manual, check_vma=False)
                got = fn([leaves[j] for j in idxs],
                         [res_leaves[j] for j in idxs])
                got, errs = got if return_residual else (got, [None] * len(idxs))
            for j, g, e in zip(idxs, got, errs):
                out_leaves[j] = g
                err_leaves[j] = e

        grads = jax.tree.unflatten(treedef, out_leaves)
        if not return_residual:
            return grads
        return grads, jax.tree.unflatten(treedef, err_leaves)
