"""Sharded MoE core: gating + expert-parallel dispatch.

Mirrors reference ``deepspeed/moe/sharded_moe.py``: ``TopKGate`` (:372) with
top-1/top-2/top-k gating, capacity factor, minimum capacity, optional noisy
gating and the GShard load-balancing auxiliary loss (:181,:288); ``MOELayer``
(:455) dispatch → expert FFN → combine.

TPU-native design: dispatch/combine are the GShard einsum formulation over a
token-capacity layout. The expert dimension E is sharded over the ``ep`` mesh
axis and tokens are sharded over the data axes, so the two dispatch einsums
*are* the all-to-alls — XLA GSPMD materializes them as such on ICI (the
explicit ``lax.all_to_all`` path in comm.py exists for shard_map callers).
Everything is branch-free and statically shaped (capacity fixed at trace time),
as TPU requires — the reference's dynamic drop-token paths become masked
writes into the fixed-capacity buffer.
"""

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn


def _one_hot(idx, num):
    return jax.nn.one_hot(idx, num, dtype=jnp.float32)


def _token_sharding():
    """NamedSharding for a [tokens, features] matrix over the flattened data
    axes, or None outside an initialized process-group topology."""
    from deepspeed_tpu.parallel import groups
    topo = groups._TOPOLOGY
    if topo is None:
        return None
    return topo.sharding(("dpr", "dp", "ep", "sp"), None)


@dataclasses.dataclass
class RoutingPlan:
    """Index-form routing decision — the single source of gating truth.

    The dense [S, E, C] combine/dispatch tensors of the GShard formulation and
    the routed gather/scatter dispatch (reference CUTLASS
    ``moe_scatter``/``moe_gather`` + grouped GEMM,
    ``inference/v2/kernels/ragged_ops/moe_scatter``) are both derived from
    this, so the two MOELayer dispatch modes can never diverge numerically.

    experts/pos/gates: [S, k] — choice j of token s goes to slot
    ``(experts[s,j], pos[s,j])`` weighted ``gates[s,j]`` (0 when dropped).
    """
    l_aux: Any
    experts: Any      # [S, k] int32
    pos: Any          # [S, k] int32 (position in the expert's capacity queue)
    gates: Any        # [S, k] float32, 0 for dropped choices
    exp_counts: Any   # [E] pre-drop routing counts
    capacity: int
    num_experts: int


def top1_routing(logits, capacity_factor=1.0, min_capacity=4,
                 noisy_gate_policy=None, rng=None, used_token_mask=None,
                 drop_tokens=True):
    """Top-1 routing (reference ``sharded_moe.py:181``) in index form."""
    S, E = logits.shape
    capacity = _capacity(S, E, 1, capacity_factor, min_capacity, drop_tokens)

    if noisy_gate_policy == "RSample" and rng is not None:
        logits_w_noise = logits + jax.random.gumbel(rng, logits.shape)
    else:
        logits_w_noise = logits
    gates = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(logits_w_noise, axis=-1)  # [S]
    mask1 = _one_hot(idx, E)  # [S, E]
    if used_token_mask is not None:
        mask1 = mask1 * used_token_mask[:, None]

    # position of each token within its expert's queue
    pos_in_expert = jnp.cumsum(mask1, axis=0) * mask1  # 1-based
    keep = (pos_in_expert <= capacity) & (mask1 > 0)
    mask1_kept = mask1 * keep.astype(mask1.dtype)

    # load-balancing loss (GShard): E * sum_e mean_s(gates) * mean_s(mask)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    gate_val = jnp.sum(gates * mask1_kept, axis=-1)  # [S], 0 when dropped
    pos = jnp.sum((pos_in_expert - 1) * mask1_kept, axis=-1).astype(jnp.int32)
    # reference returns PRE-drop routing counts (sharded_moe.py:209) so router
    # imbalance/overflow stays observable
    exp_counts = jnp.sum(mask1, axis=0)
    return RoutingPlan(l_aux, idx[:, None], pos[:, None], gate_val[:, None],
                       exp_counts, capacity, E)


def topk_routing(logits, k=2, capacity_factor=1.0, min_capacity=4,
                 drop_tokens=True, normalize_gates=True):
    """Top-k routing (reference top2gating ``sharded_moe.py:288`` generalized
    to k) in index form."""
    S, E = logits.shape
    capacity = _capacity(S, E, k, capacity_factor, min_capacity, drop_tokens)
    gates = jax.nn.softmax(logits, axis=-1)

    # iterative top-k with masking (static k)
    masks, idxs = [], []
    g = gates
    for _ in range(k):
        idx = jnp.argmax(g, axis=-1)
        m = _one_hot(idx, E)
        masks.append(m)
        idxs.append(idx)
        g = g * (1 - m)
    # aux loss on first choice (reference top2gating)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(masks[0], axis=0)
    l_aux = jnp.sum(me * ce) * E

    # queue positions: ranks within each expert across all k choices, first
    # choices first (matches reference ordering: locations2 += sum(mask1))
    offset = jnp.zeros((E,), jnp.float32)
    pos_cols, gate_cols = [], []
    for m in masks:
        pos = (jnp.cumsum(m, axis=0) - 1) * m + offset[None, :] * m  # 0-based
        keep = (pos < capacity) & (m > 0)
        mk = m * keep.astype(m.dtype)
        gate_cols.append(jnp.sum(gates * mk, axis=-1))           # [S]
        pos_cols.append(jnp.sum(pos * mk, axis=-1).astype(jnp.int32))
        offset = offset + jnp.sum(m, axis=0)
    gates_sk = jnp.stack(gate_cols, axis=1)                      # [S, k]
    if normalize_gates:
        # reference normalizes by the sum of the SELECTED (kept) gate mass
        denom = jnp.sum(gates_sk, axis=1, keepdims=True)
        gates_sk = gates_sk / jnp.maximum(denom, 1e-9)
    exp_counts = jnp.sum(sum(masks), axis=0)  # pre-drop (see top1 note)
    return RoutingPlan(l_aux, jnp.stack(idxs, axis=1),
                       jnp.stack(pos_cols, axis=1), gates_sk,
                       exp_counts, capacity, E)


def _densify(plan: RoutingPlan, S):
    """[S,E,C] combine/dispatch from a RoutingPlan (GShard einsum form)."""
    C, E = plan.capacity, plan.num_experts
    s_idx = jnp.broadcast_to(jnp.arange(S)[:, None], plan.experts.shape)
    combine = jnp.zeros((S, E, C), jnp.float32).at[
        s_idx, plan.experts, jnp.minimum(plan.pos, C - 1)].add(plan.gates)
    return combine, combine > 0


def top1gating(logits, capacity_factor=1.0, min_capacity=4, noisy_gate_policy=None,
               rng=None, used_token_mask=None, drop_tokens=True):
    """Top-1 gating (reference ``sharded_moe.py:181``).

    logits: [S, E]. Returns (l_aux, combine [S,E,C], dispatch [S,E,C], exp_counts [E]).
    """
    plan = top1_routing(logits, capacity_factor, min_capacity, noisy_gate_policy,
                        rng, used_token_mask, drop_tokens)
    combine, dispatch = _densify(plan, logits.shape[0])
    return plan.l_aux, combine, dispatch, plan.exp_counts


def topkgating(logits, k=2, capacity_factor=1.0, min_capacity=4, drop_tokens=True,
               normalize_gates=True):
    """Top-k gating (reference top2gating ``sharded_moe.py:288`` generalized to k).

    logits: [S, E]. Returns (l_aux, combine [S,E,C], dispatch [S,E,C], exp_counts).
    """
    plan = topk_routing(logits, k, capacity_factor, min_capacity, drop_tokens,
                        normalize_gates)
    combine, dispatch = _densify(plan, logits.shape[0])
    return plan.l_aux, combine, dispatch, plan.exp_counts


def _capacity(S, E, k, capacity_factor, min_capacity, drop_tokens):
    """reference ``sharded_moe.py`` _capacity: tokens-per-expert budget (ceil,
    matching the reference's math.ceil)."""
    import math
    if not drop_tokens:
        return S  # full capacity: nothing can drop
    cap = max(math.ceil((S * k / E) * capacity_factor), min_capacity)
    return min(cap, S)


class TopKGate(nn.Module):
    """reference ``sharded_moe.py:372`` TopKGate — linear router + gating."""
    num_experts: int
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True

    @nn.compact
    def __call__(self, x, train=True, as_plan=False):
        # router in fp32 (reference casts gate input to fp32)
        wg = self.param("wg", nn.initializers.normal(0.02),
                        (x.shape[-1], self.num_experts), jnp.float32)
        logits = x.astype(jnp.float32) @ wg
        # pin logits to the token layout: without it, ZeRO's wg-grad sharding
        # back-propagates through d(wg) = x^T @ d(logits) into the token
        # matrix and GSPMD full-replicates it (spmd_partitioner b/433785288)
        token_sh = _token_sharding()
        if token_sh is not None:
            logits = jax.lax.with_sharding_constraint(logits, token_sh)
        cf = self.capacity_factor if train else self.eval_capacity_factor
        rng = self.make_rng("gating") if (train and self.noisy_gate_policy == "RSample"
                                          and self.has_rng("gating")) else None
        if self.k == 1:
            plan = top1_routing(logits, cf, self.min_capacity,
                                self.noisy_gate_policy, rng=rng,
                                drop_tokens=self.drop_tokens)
        else:
            plan = topk_routing(logits, self.k, cf, self.min_capacity,
                                drop_tokens=self.drop_tokens)
        if as_plan:
            return plan
        combine, dispatch = _densify(plan, logits.shape[0])
        return plan.l_aux, combine, dispatch, plan.exp_counts


class _GmmParam(nn.Module):
    """One stacked [E, in, out] expert kernel under the SAME flax path the
    vmapped Experts module would create (experts/<Cls>_0/<name>/kernel), so
    the gmm backend is checkpoint/HF-interop compatible with the vmap one."""
    shape: tuple

    @nn.compact
    def __call__(self):
        # lecun_normal with in_axis=-2 == per-expert Dense default variance
        return self.param("kernel", nn.initializers.lecun_normal(
            in_axis=-2, out_axis=-1, batch_axis=(0,)), self.shape, jnp.float32)


class _GmmInner(nn.Module):
    shapes: dict

    @nn.compact
    def __call__(self):
        return {nm: _GmmParam(tuple(shp), name=nm)()
                for nm, shp in self.shapes.items()}


class _GmmExpertBox(nn.Module):
    """Creates the stacked expert kernels at vmap-identical paths."""
    inner_name: str
    shapes: dict

    @nn.compact
    def __call__(self):
        return _GmmInner(self.shapes, name=self.inner_name)()


class Experts(nn.Module):
    """E experts applied to [E, C, D] inputs; parameters stacked on the expert
    axis and sharded over 'ep' (reference ``moe/experts.py`` DistributedExperts)."""
    expert_factory: Callable[[], nn.Module]
    num_experts: int

    @nn.compact
    def __call__(self, x):
        VmappedExpert = nn.vmap(
            lambda mdl, xs: mdl(xs),
            variable_axes={"params": 0},
            split_rngs={"params": True},
            in_axes=0, out_axes=0,
            axis_size=self.num_experts,
            metadata_params={nn.meta.PARTITION_NAME: "expert"},
        )
        return VmappedExpert(self.expert_factory(), x)


class MOELayer(nn.Module):
    """reference ``sharded_moe.py:455`` MOELayer: gate → dispatch(all-to-all) →
    experts → combine(all-to-all). Returns (output, l_aux, exp_counts).

    ``dispatch_mode``:
      "indices" (default) — routed dispatch: tokens are scattered into each
        expert's [C, D] bin by routing indices and gathered back weighted by
        their gates (the reference's moe_scatter / grouped GEMM / moe_gather
        pipeline, ``inference/v2/kernels/ragged_ops``, as a *training* path).
        O(E·C·D + S·k·D) memory traffic.
      "einsum" — the GShard [S,E,C] one-hot einsum formulation; O(S·E·C·D)
        MXU/HBM work. Kept as the numerics oracle; both modes consume the same
        RoutingPlan so they agree to float tolerance.
      "gmm" — megablox grouped GEMM over ragged expert row-groups
        (``ops/pallas/grouped_gemm.py``) as the TRAINING path: no capacity
        dimension at all, O(S·k) MXU rows regardless of skew. Requires a
        gated-MLP expert that declares GMM_COMPAT/gmm_shapes (e.g.
        MixtralExpertMLP); the expert params are created at vmap-identical
        flax paths so checkpoints/HF interop are unchanged. Same RoutingPlan,
        same numerics (dropped choices contribute zero-weighted rows).
        Composes with an ep mesh: the expert stacks shard over 'ep' and each
        shard exchanges routed rows with its peers through the explicit
        dispatch/combine all-to-all (``_gmm_ep_forward``), optionally with a
        quantized wire (``a2a_wire_bits``). With ``drop_tokens=False`` this
        is the DROPLESS path: capacity is never consulted, no token is
        dropped, no padding beyond the m-tile (docs/MOE.md).
    """
    expert_factory: Callable[[], nn.Module]
    num_experts: int
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    dispatch_mode: str = "indices"
    # wire precision of the expert-parallel dispatch/combine all-to-all
    # (gmm mode under an ep mesh): None = full precision (the ICI default),
    # 8/4 = quantized wire via the qwZ/qgZ kernel pair. Forward-only —
    # see runtime/comm/coalesced_collectives.expert_all_to_all.
    a2a_wire_bits: Optional[int] = None

    @nn.compact
    def __call__(self, x, train=True):
        if self.dispatch_mode not in ("indices", "einsum", "gmm"):
            raise ValueError(f"MOELayer dispatch_mode must be 'indices', "
                             f"'einsum' or 'gmm', got {self.dispatch_mode!r}")
        orig_shape = x.shape
        D = x.shape[-1]
        xf = x.reshape(-1, D)  # [S, D] tokens sharded over data axes
        S = xf.shape[0]
        plan = TopKGate(
            self.num_experts, self.k, self.capacity_factor, self.eval_capacity_factor,
            self.min_capacity, self.noisy_gate_policy, self.drop_tokens,
            name="gate")(xf, train, as_plan=True)
        E, C = plan.num_experts, plan.capacity

        if self.dispatch_mode == "gmm":
            return self._gmm_forward(x, xf, plan)

        if self.dispatch_mode == "einsum":
            combine, dispatch = _densify(plan, S)
            # dispatch einsum == all-to-all when E is ep-sharded, S dp-sharded
            expert_in = jnp.einsum("sec,sd->ecd", dispatch.astype(xf.dtype), xf)
            expert_out = Experts(self.expert_factory, self.num_experts,
                                 name="experts")(expert_in)
            out = jnp.einsum("sec,ecd->sd", combine.astype(expert_out.dtype),
                             expert_out)
            return out.reshape(orig_shape), plan.l_aux, plan.exp_counts

        # routed dispatch (moe_scatter): slot (e, c) <- token index, built by
        # scatter over the kept choices; empty slots read token 0 and are
        # zeroed by the validity mask (the einsum path's implicit zeros)
        kept = plan.gates > 0                                    # [S, k]
        pos_c = jnp.minimum(plan.pos, C - 1)
        flat_slot = plan.experts * C + pos_c                     # [S, k]
        token_of = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[:, None],
                                    flat_slot.shape)
        slot_token = jnp.zeros((E * C,), jnp.int32).at[
            jnp.where(kept, flat_slot, E * C)].set(token_of, mode="drop")
        slot_valid = jnp.zeros((E * C,), jnp.bool_).at[
            jnp.where(kept, flat_slot, E * C)].set(True, mode="drop")
        expert_in = jnp.take(xf, slot_token, axis=0).reshape(E, C, D)
        expert_in = expert_in * slot_valid.reshape(E, C, 1).astype(xf.dtype)
        # Pin the dispatch boundary: the gather output lives on the expert
        # (ep) layout, tokens on the data layout. Without the pin, the expert
        # weights' tp spec back-propagates THROUGH the gather into the token
        # matrix and GSPMD falls back to full replication (the same
        # involuntary-rematerialization failure as the ZeRO-3 use-sharding
        # case, engine.py _build_micro_step). The token->expert transition
        # then lowers to the dispatch all-to-all, as in the reference
        # (deepspeed/moe/sharded_moe.py _AllToAll).
        token_sh, expert_sh = self._dispatch_shardings()
        if expert_sh is not None:
            expert_in = jax.lax.with_sharding_constraint(expert_in, expert_sh)

        expert_out = Experts(self.expert_factory, self.num_experts,
                             name="experts")(expert_in)
        if expert_sh is not None:
            expert_out = jax.lax.with_sharding_constraint(expert_out, expert_sh)

        # combine (moe_gather): each token reads its k slots, gate-weighted.
        # One [S, D] gather per choice (k is tiny and static) — keeping every
        # intermediate in the token layout lets GSPMD propagate the batch
        # sharding cleanly (a fused [S*k, D] gather+reshape made the partitioner
        # fall back to full replication at the reshape).
        flat_out = expert_out.reshape(E * C, -1)
        out = None
        for j in range(self.k):
            yj = jnp.take(flat_out, flat_slot[:, j], axis=0)  # [S, Dout]
            if token_sh is not None:
                yj = jax.lax.with_sharding_constraint(yj, token_sh)
            term = yj.astype(jnp.float32) * plan.gates[:, j, None]
            out = term if out is None else out + term
        return (out.astype(x.dtype).reshape(orig_shape), plan.l_aux,
                plan.exp_counts)

    def _gmm_forward(self, x, xf, plan):
        """Ragged grouped-GEMM expert FFN (megablox) routed by the plan."""
        expert = self.expert_factory()
        names = getattr(expert, "GMM_COMPAT", None)
        if names is None or not hasattr(expert, "gmm_shapes"):
            raise ValueError(
                "dispatch_mode='gmm' needs a gated-MLP expert declaring "
                "GMM_COMPAT + gmm_shapes (e.g. MixtralExpertMLP); "
                f"{type(expert).__name__} does not")
        D = xf.shape[-1]
        shapes = {nm: (self.num_experts, *shp)
                  for nm, shp in expert.gmm_shapes(D).items()}
        kernels = _GmmExpertBox(f"{type(expert).__name__}_0", shapes,
                                name="experts")()
        from deepspeed_tpu.parallel import groups
        topo = groups._TOPOLOGY
        ep = topo.ep_size if topo is not None else 1
        if topo is not None and topo.tp_size > 1:
            # the ragged kernel has no tp decomposition; a tp-sharded mesh
            # would make GSPMD all-gather the expert stacks every step
            raise ValueError(
                "dispatch_mode='gmm' does not compose with tp meshes "
                f"(mesh has tp={topo.tp_size}); use dispatch_mode='indices'")
        if ep > 1 and self.num_experts % ep != 0:
            raise ValueError(
                f"dispatch_mode='gmm' under expert parallelism needs "
                f"num_experts ({self.num_experts}) divisible by the mesh's "
                f"ep axis ({ep})")
        from deepspeed_tpu.ops.pallas import grouped_gemm as gg
        if not gg.is_supported(D, shapes[names[0]][-1]):
            raise ValueError(
                f"dispatch_mode='gmm': d_model={D} / d_ff="
                f"{shapes[names[0]][-1]} must be multiples of "
                f"{gg.ROW_ALIGN} for the megablox kernel")
        from deepspeed_tpu.ops.registry import pallas_interpret
        interpret = pallas_interpret()
        w1 = kernels[names[0]].astype(x.dtype)
        w3 = kernels[names[1]].astype(x.dtype)
        w2 = kernels[names[2]].astype(x.dtype)
        if ep > 1:
            out = _gmm_ep_forward(xf, plan, w1, w2, w3, topo,
                                  n_experts=self.num_experts,
                                  a2a_wire_bits=self.a2a_wire_bits,
                                  dtype=x.dtype, interpret=interpret)
        else:
            out = gg.moe_ffn_gmm(xf, plan.gates, plan.experts, w1, w2, w3,
                                 n_experts=self.num_experts, dtype=x.dtype,
                                 interpret=interpret)
        return out.reshape(x.shape), plan.l_aux, plan.exp_counts

    def _dispatch_shardings(self):
        """(token [S,D], expert [E,C,D]) NamedShardings from the process-group
        topology, or (None, None) outside an initialized mesh. Tokens ride the
        flattened data axes; expert bins ride 'ep' (reference expert-parallel
        group, ``deepspeed/utils/groups.py _get_expert_parallel_group``)."""
        from deepspeed_tpu.parallel import groups
        topo = groups._TOPOLOGY
        token = _token_sharding()
        if topo is None:
            return None, None
        if topo.ep_size <= 1 or self.num_experts % topo.ep_size != 0:
            # no usable ep axis: leave the expert batch unconstrained so GSPMD
            # remains free to shard the E/C dims over the data axes
            return token, None
        return token, topo.sharding("ep", None, None)


def _gmm_ep_forward(xf, plan, w1, w2, w3, topo, *, n_experts, a2a_wire_bits,
                    dtype, interpret):
    """Expert-parallel grouped-GEMM forward: tokens stay sharded over the
    flattened data axes, the stacked expert kernels shard over 'ep', and each
    shard exchanges its routed rows with its ep peers through the explicit
    dispatch/combine all-to-all (reference ``_AllToAll``,
    ``sharded_moe.py:455``) around the local ragged FFN.

    An explicit shard_map rather than ``sharded_kernel_call``: the tokens and
    the weights need DIFFERENT specs (data axes vs 'ep'), and the a2a must be
    a real in-body collective — GSPMD cannot be trusted to place it."""
    from jax.sharding import PartitionSpec as P


    tok2 = P(("dpr", "dp", "ep", "sp"), None)
    wspec = P("ep", None, None)

    def body(xl, gl, el, w1l, w2l, w3l):
        return _moe_gmm_ep_shard(xl, gl, el, w1l, w2l, w3l,
                                 n_experts=n_experts, ep_axis="ep",
                                 bits=a2a_wire_bits, dtype=dtype,
                                 interpret=interpret)

    fn = jax.shard_map(
        body, mesh=topo.mesh,
        in_specs=(tok2, tok2, tok2, wspec, wspec, wspec),
        out_specs=tok2, check_vma=False)
    return fn(xf, plan.gates, plan.experts, w1, w2, w3)


def _moe_gmm_ep_shard(xl, gl, el, w1, w2, w3, *, n_experts, ep_axis, bits,
                      dtype, interpret):
    """One ep shard's dropless dispatch → local grouped FFN → combine.

    xl [Sl, D] local tokens; gl/el [Sl, k] local gates/expert ids (GLOBAL
    expert numbering); w1/w3 [E/ep, D, F], w2 [E/ep, F, D] — this shard's
    contiguous slice of the expert stack (expert e lives on peer e // E_local
    — ``moe/utils.moe_param_specs`` layout).

    Statically shaped: the per-peer send buffer holds the worst case (every
    local row routed to one peer). Empty slots carry zero rows tagged with
    the sentinel local id ``E_local``; they ride the last local expert's
    group as padding (zero FFN input → zero output) and their results are
    never gathered back. Differentiable end to end when ``bits`` is None —
    scatter/gather/all_to_all all transpose cleanly."""
    from jax import lax

    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    from deepspeed_tpu.runtime.comm.coalesced_collectives import (
        expert_all_to_all,
    )

    ep = lax.axis_size(ep_axis)
    E_local = n_experts // ep
    Sl, D = xl.shape
    k = el.shape[-1]
    R = Sl * k

    # moe_scatter by destination PEER (not expert): stable-sort the local
    # (token, choice) rows by their expert's owning shard
    flat_e = el.reshape(-1).astype(jnp.int32)            # [R] global ids
    dest = flat_e // E_local                             # [R] owning peer
    token_of = jnp.arange(R, dtype=jnp.int32) // k
    order = jnp.argsort(dest, stable=True)
    xs = jnp.take(xl, jnp.take(token_of, order), axis=0)  # [R, D]
    es = jnp.take(flat_e % E_local, order)               # [R] local ids
    ds = jnp.take(dest, order)                           # [R]
    counts = jnp.zeros((ep,), jnp.int32).at[dest].add(1)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(R, dtype=jnp.int32) - jnp.take(starts, ds)

    send_x = jnp.zeros((ep, R, D), xl.dtype).at[ds, pos].set(xs)
    send_e = jnp.full((ep, R), E_local, jnp.int32).at[ds, pos].set(es)

    recv_x = expert_all_to_all(send_x, ep_axis, bits=bits, op="a2a_dispatch")
    recv_e = lax.all_to_all(send_e, ep_axis, split_axis=0, concat_axis=0,
                            tiled=False)

    # sentinel padding rows fold into the last local expert's group; their
    # zero inputs produce zero outputs and nobody reads them back
    rows_e = jnp.minimum(recv_e.reshape(ep * R), E_local - 1)
    y_rows = gg.moe_ffn_gmm_rows(recv_x.reshape(ep * R, D), rows_e,
                                 w1, w2, w3, n_experts=E_local, dtype=dtype,
                                 interpret=interpret)

    back = expert_all_to_all(y_rows.reshape(ep, R, D), ep_axis, bits=bits,
                             op="a2a_combine")

    # moe_gather: read each routed row back from the slot it was sent from,
    # unsort, and gate-combine the k choices in fp32
    ys = back[ds, pos]                                   # [R, D]
    inv = jnp.argsort(order, stable=True)
    y = jnp.take(ys, inv, axis=0).reshape(Sl, k, D)
    return jnp.sum(y.astype(jnp.float32) * gl[..., None],
                   axis=1).astype(dtype)
