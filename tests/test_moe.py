"""MoE tests (mirrors reference ``tests/unit/moe/test_moe.py``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.moe.sharded_moe import top1gating, topkgating, MOELayer, TopKGate
from deepspeed_tpu.moe.layer import MoE


class ExpertMLP(nn.Module):
    hidden: int = 32
    d_model: int = 16

    @nn.compact
    def __call__(self, x):
        h = nn.relu(nn.Dense(self.hidden)(x))
        return nn.Dense(self.d_model)(h)


def test_top1gating_shapes_and_capacity():
    logits = jax.random.normal(jax.random.PRNGKey(0), (32, 4))
    l_aux, combine, dispatch, counts = top1gating(logits, capacity_factor=1.0, min_capacity=4)
    S, E, C = combine.shape
    assert (S, E) == (32, 4)
    assert C == max(int(32 / 4 * 1.0), 4)
    # every dispatched token has exactly one (expert, slot)
    assert dispatch.sum(axis=(1, 2)).max() <= 1
    # no slot is double-booked
    assert dispatch.astype(np.int32).sum(axis=0).max() <= 1
    assert float(l_aux) > 0
    assert counts.sum() <= 32


def test_top1gating_capacity_drops():
    # all tokens to expert 0 -> only `capacity` survive
    logits = jnp.zeros((16, 4)).at[:, 0].set(10.0)
    l_aux, combine, dispatch, counts = top1gating(logits, capacity_factor=1.0, min_capacity=4)
    # exp_counts is PRE-drop routing (reference semantics): overflow observable
    assert int(counts[0]) == 16
    # but only `capacity` = max(16/4, 4) = 4 slots are actually dispatched
    assert int(dispatch.sum()) == 4


def test_topk_gating_two_choices():
    logits = jax.random.normal(jax.random.PRNGKey(1), (64, 8))
    l_aux, combine, dispatch, counts = topkgating(logits, k=2, capacity_factor=2.0)
    # each token dispatched to at most 2 slots
    per_token = dispatch.sum(axis=(1, 2))
    assert per_token.max() <= 2
    # combine weights per token sum to ~1 (normalized) for fully-kept tokens
    w = combine.sum(axis=(1, 2))
    kept = per_token == 2
    np.testing.assert_allclose(np.asarray(w)[np.asarray(kept)], 1.0, rtol=1e-4)


def test_moe_layer_forward_and_grads():
    model = MOELayer(lambda: ExpertMLP(), num_experts=4, k=1, capacity_factor=2.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    params = model.init(jax.random.PRNGKey(1), x)["params"]
    (out, l_aux, counts) = model.apply({"params": params}, x)
    assert out.shape == x.shape
    assert np.isfinite(float(l_aux))

    def loss_fn(p):
        o, la, _ = model.apply({"params": p}, x)
        return (o ** 2).mean() + 0.01 * la

    grads = jax.grad(loss_fn)(params)
    gate_g = jax.tree.leaves(grads["gate"])
    assert all(np.isfinite(np.asarray(g)).all() for g in gate_g)
    # expert params stacked on leading expert axis
    expert_kernel = jax.tree.leaves(params["experts"])[0]
    assert expert_kernel.shape[0] == 4


def test_moe_module_residual():
    model = MoE(hidden_size=16, expert_factory=lambda: ExpertMLP(), num_experts=4,
                use_residual=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    params = model.init(jax.random.PRNGKey(1), x)["params"]
    out, l_aux, counts = model.apply({"params": params}, x)
    assert out.shape == x.shape
    assert "coefficient" in params


def test_moe_ep_sharded_training(eight_devices):
    """MoE model trains under the engine with experts sharded over ep axis."""
    import deepspeed_tpu
    from deepspeed_tpu.moe.utils import moe_param_specs

    class MoEModel(nn.Module):
        @nn.compact
        def __call__(self, batch, deterministic=True):
            x = batch["x"]
            h = nn.Dense(16)(x)
            out, l_aux, _ = MoE(hidden_size=16,
                                expert_factory=lambda: ExpertMLP(d_model=16),
                                num_experts=4, k=1, capacity_factor=2.0,
                                name="moe")(h, train=not deterministic)
            pred = nn.Dense(4)(out)
            return jnp.mean((pred - batch["y"]) ** 2) + 0.01 * l_aux

    rng = np.random.default_rng(0)
    def batch(i):
        r = np.random.default_rng(i)
        x = r.normal(size=(16, 16)).astype(np.float32)
        return {"x": x, "y": (x[:, :4] * 2).astype(np.float32)}

    model = MoEModel()
    params = model.init(jax.random.PRNGKey(0), batch(0))["params"]
    specs = moe_param_specs(params)
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    engine = DeepSpeedEngine(
        model=model, model_parameters=params, param_specs=specs,
        config={"train_batch_size": 16,
                "expert_parallel_size": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": 1}},
    )
    # expert leaves must actually be ep-sharded via the MoE specs
    from jax.sharding import PartitionSpec as P
    ek = engine.state.params["moe"]["deepspeed_moe"]["experts"]["ExpertMLP_0"]["Dense_0"]["kernel"]
    assert "ep" in jax.tree_util.tree_leaves(
        [ek.sharding.spec], is_leaf=lambda x: isinstance(x, P))[0][0], ek.sharding.spec
    losses = []
    for i in range(15):
        loss = engine(batch(i))
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_moe_ep_parity_with_dense_dispatch(eight_devices):
    """Expert-parallel einsum dispatch must equal a per-token dense compute."""
    model = MOELayer(lambda: ExpertMLP(), num_experts=4, k=1, capacity_factor=100.0,
                     min_capacity=64)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 16))
    params = model.init(jax.random.PRNGKey(1), x)["params"]
    out, _, counts = model.apply({"params": params}, x)
    # with huge capacity nothing drops: every token routed
    assert int(np.asarray(counts).sum()) == 16

    # manual reference: per-token argmax expert, apply that expert's MLP, scale by gate
    xf = x.reshape(-1, 16)
    wg = np.asarray(params["gate"]["wg"])
    logits = xf @ wg
    gates = jax.nn.softmax(logits, axis=-1)
    choice = np.argmax(np.asarray(logits), axis=-1)
    ek = params["experts"]["ExpertMLP_0"]
    ref = []
    for s in range(16):
        e = int(choice[s])
        h = np.maximum(np.asarray(xf[s]) @ np.asarray(ek["Dense_0"]["kernel"][e]) +
                       np.asarray(ek["Dense_0"]["bias"][e]), 0)
        o = h @ np.asarray(ek["Dense_1"]["kernel"][e]) + np.asarray(ek["Dense_1"]["bias"][e])
        ref.append(o * float(gates[s, e]))
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 16), np.stack(ref),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# routed (indices) dispatch vs GShard einsum oracle (VERDICT r2 #4)
# ---------------------------------------------------------------------------

def _moe_pair(k, num_experts=4, capacity_factor=2.0, drop_tokens=True):
    mk = lambda mode: MOELayer(lambda: ExpertMLP(), num_experts=num_experts,
                               k=k, capacity_factor=capacity_factor,
                               drop_tokens=drop_tokens, dispatch_mode=mode)
    return mk("indices"), mk("einsum")


@pytest.mark.parametrize("k", [1, 2])
def test_indices_dispatch_matches_einsum(k):
    import numpy as np
    routed, dense = _moe_pair(k)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16))
    params = routed.init(jax.random.PRNGKey(1), x)["params"]
    out_r, laux_r, cnt_r = routed.apply({"params": params}, x)
    out_d, laux_d, cnt_d = dense.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_d),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(laux_r), float(laux_d), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(cnt_r), np.asarray(cnt_d))


def test_indices_dispatch_matches_einsum_with_drops():
    import numpy as np
    routed, dense = _moe_pair(k=2, capacity_factor=0.5)  # force drops
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 16))
    params = routed.init(jax.random.PRNGKey(4), x)["params"]
    out_r, *_ = routed.apply({"params": params}, x)
    out_d, *_ = dense.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_d),
                               atol=1e-5, rtol=1e-5)


def test_indices_dispatch_gradients_match_einsum():
    import numpy as np
    routed, dense = _moe_pair(k=2)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 16, 16))
    params = routed.init(jax.random.PRNGKey(6), x)["params"]

    def loss(mdl):
        def f(p, xx):
            out, laux, _ = mdl.apply({"params": p}, xx)
            return jnp.sum(out ** 2) + 0.01 * laux
        return f

    gr = jax.grad(loss(routed))(params, x)
    gd = jax.grad(loss(dense))(params, x)
    flat_r = jax.tree_util.tree_leaves(gr)
    flat_d = jax.tree_util.tree_leaves(gd)
    for a, b in zip(flat_r, flat_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_indices_dispatch_no_dense_sec_tensor_ep2():
    """The ep>1 sharded lowering must not contain the dense [S, E, C]
    dispatch tensor (VERDICT r2 #4 done-criterion): trace through the real
    process-group topology (ep=2) so expert params carry their ep sharding."""
    import numpy as np
    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.parallel.topology import MeshTopology

    E, k = 4, 2
    S_tokens = 2 * 16
    routed, dense = _moe_pair(k, num_experts=E)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16))
    params = routed.init(jax.random.PRNGKey(1), x)["params"]
    topo = MeshTopology(dp=-1, ep=2)
    groups.initialize(mesh_topology=topo)
    try:
        def run(mdl):
            def f(p, xx):
                out, laux, _ = mdl.apply({"params": p}, xx)
                return jnp.sum(out) + laux
            # lower with sharded operands: x over the data axes, expert
            # params over ep (stacked axis 0), everything else replicated
            x_sh = jax.device_put(x, topo.sharding("ep", None, None))
            p_sh = jax.tree_util.tree_map_with_path(
                lambda path, l: jax.device_put(
                    l, topo.sharding("ep", *([None] * (l.ndim - 1)))
                    if "experts" in jax.tree_util.keystr(path)
                    and l.shape[0] == E else topo.replicated()),
                params)
            return jax.jit(f).lower(p_sh, x_sh).as_text()

        cap = int(np.ceil(S_tokens * k / E) * 2.0)  # capacity_factor=2.0
        dense_shape = f"tensor<{S_tokens}x{E}x{cap}xf32>"
        assert dense_shape in run(dense), "oracle lowering should carry [S,E,C]"
        assert dense_shape not in run(routed), \
            f"routed lowering still materializes the dense {dense_shape} dispatch"
    finally:
        groups.reset()


# ---------------------------------------------------------------------------
# megablox grouped-GEMM training backend (VERDICT r2 #4 "call grouped_gemm")
# ---------------------------------------------------------------------------

class GmmExpertMLP(nn.Module):
    """Gated MLP matching the gmm contract (128-aligned dims)."""
    hidden: int = 128
    d_model: int = 128
    GMM_COMPAT = ("w1", "w3", "w2")

    def gmm_shapes(self, d_model):
        return {"w1": (d_model, self.hidden), "w3": (d_model, self.hidden),
                "w2": (self.hidden, d_model)}

    @nn.compact
    def __call__(self, x):
        dense = lambda f, nm: nn.Dense(f, use_bias=False, name=nm)
        return dense(self.d_model, "w2")(
            nn.silu(dense(self.hidden, "w1")(x)) * dense(self.hidden, "w3")(x))


@pytest.mark.parametrize("k", [1, 2])
def test_gmm_backend_matches_indices(pallas_interpret, k):
    mk = lambda mode: MOELayer(lambda: GmmExpertMLP(), num_experts=4, k=k,
                               capacity_factor=100.0, dispatch_mode=mode)
    gmm, routed = mk("gmm"), mk("indices")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 128))
    params = gmm.init(jax.random.PRNGKey(1), x)["params"]
    # identical param structure -> the vmap/indices path runs the SAME params
    out_g, laux_g, cnt_g = gmm.apply({"params": params}, x)
    out_r, laux_r, cnt_r = routed.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(float(laux_g), float(laux_r), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(cnt_g), np.asarray(cnt_r))


def test_gmm_backend_gradients_match(pallas_interpret):
    mk = lambda mode: MOELayer(lambda: GmmExpertMLP(), num_experts=4, k=2,
                               capacity_factor=100.0, dispatch_mode=mode)
    gmm, routed = mk("gmm"), mk("indices")
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 128))
    params = gmm.init(jax.random.PRNGKey(3), x)["params"]

    def loss(mdl):
        def f(p, xx):
            out, laux, _ = mdl.apply({"params": p}, xx)
            return jnp.sum(out ** 2) + 0.01 * laux
        return f

    gg = jax.grad(loss(gmm))(params, x)
    gr = jax.grad(loss(routed))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(gg),
                    jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_gmm_backend_param_tree_matches_vmap(pallas_interpret):
    """gmm creates kernels at vmap-identical paths (checkpoint/HF compat)."""
    mk = lambda mode: MOELayer(lambda: GmmExpertMLP(), num_experts=4, k=1,
                               dispatch_mode=mode)
    x = jnp.zeros((1, 8, 128))
    pg = mk("gmm").init(jax.random.PRNGKey(0), x)["params"]
    pv = mk("indices").init(jax.random.PRNGKey(0), x)["params"]
    sg = jax.tree_util.tree_structure(pg)
    sv = jax.tree_util.tree_structure(pv)
    assert sg == sv, f"{sg} != {sv}"
    for a, b in zip(jax.tree_util.tree_leaves(pg),
                    jax.tree_util.tree_leaves(pv)):
        assert a.shape == b.shape


def test_gmm_backend_rejects_incompatible_expert():
    layer = MOELayer(lambda: ExpertMLP(), num_experts=4, dispatch_mode="gmm")
    x = jnp.zeros((1, 8, 16))
    with pytest.raises(ValueError, match="gated-MLP"):
        layer.init(jax.random.PRNGKey(0), x)


def test_mixtral_gmm_backend_forward_parity(pallas_interpret):
    """Mixtral with moe_backend='gmm' matches the default backend on the
    same params (128-aligned tiny config)."""
    from deepspeed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
    base = dict(vocab_size=256, hidden_size=128, intermediate_size=128,
                num_hidden_layers=1, num_attention_heads=4,
                num_key_value_heads=2, num_local_experts=4,
                max_position_embeddings=64, dtype=jnp.float32)
    m_v = MixtralForCausalLM(MixtralConfig(**base))
    m_g = MixtralForCausalLM(MixtralConfig(**base, moe_backend="gmm"))
    ids = np.arange(32, dtype=np.int32).reshape(2, 16) % 256
    params = m_v.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    out_v = m_v.apply({"params": params}, {"input_ids": ids})
    out_g = m_g.apply({"params": params}, {"input_ids": ids})
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_v),
                               atol=3e-4, rtol=3e-4)


def test_gmm_backend_rejects_tp_mesh():
    """gmm must refuse tp meshes instead of silently all-gathering the
    expert stacks (review r3 finding). ep meshes now COMPOSE through the
    explicit dispatch/combine all-to-all (ISSUE 15 dropless path) — only
    tp remains incompatible."""
    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.parallel.topology import MeshTopology
    groups.initialize(mesh_topology=MeshTopology(dp=-1, tp=2))
    try:
        layer = MOELayer(lambda: GmmExpertMLP(), num_experts=4,
                         dispatch_mode="gmm")
        x = jnp.zeros((1, 8, 128))
        with pytest.raises(ValueError, match="does not compose"):
            layer.init(jax.random.PRNGKey(0), x)
    finally:
        groups.reset()


# ---------------------------------------------------------------------------
# dropless routing + expert-parallel a2a (ISSUE 15)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2])
def test_dropless_gmm_matches_dense_all_experts(pallas_interpret, k):
    """drop_tokens=False consults no capacity at all (capacity_factor=inf
    semantics): the grouped-GEMM path must match the dense all-experts
    einsum formulation on the same params, with every routed choice kept."""
    mk = lambda mode: MOELayer(lambda: GmmExpertMLP(), num_experts=4, k=k,
                               drop_tokens=False, dispatch_mode=mode)
    gmm, dense = mk("gmm"), mk("einsum")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 128))
    params = gmm.init(jax.random.PRNGKey(1), x)["params"]
    out_g, laux_g, cnt_g = gmm.apply({"params": params}, x)
    out_d, laux_d, cnt_d = dense.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_d),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(float(laux_g), float(laux_d), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(cnt_g), np.asarray(cnt_d))
    # dropless: every (token, choice) pair survives
    assert int(np.asarray(cnt_g).sum()) == 2 * 16 * k


def test_dropless_skewed_batch_drops_nothing(pallas_interpret):
    """Adversarial skew (every token's top choice is expert 0): the drop
    path sheds to capacity, the dropless path keeps all — and still matches
    the dense reference."""
    mk = lambda mode, drop: MOELayer(lambda: GmmExpertMLP(), num_experts=4,
                                     k=1, drop_tokens=drop,
                                     dispatch_mode=mode)
    # strictly positive tokens + a gate that weights only expert 0's
    # column: every token's logits are (positive, 0, 0, 0) -> expert 0
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (1, 32, 128))) + 0.1
    params = mk("gmm", False).init(jax.random.PRNGKey(3), x)["params"]
    params["gate"]["wg"] = jnp.zeros_like(
        params["gate"]["wg"]).at[:, 0].set(10.0)
    out_g, _, cnt = mk("gmm", False).apply({"params": params}, x)
    out_d, _, _ = mk("einsum", False).apply({"params": params}, x)
    assert int(np.asarray(cnt)[0]) == 32  # all 32 routed to expert 0, kept
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_d),
                               atol=2e-5, rtol=2e-5)
    # the drop path on the same batch sheds to capacity — the contrast
    # dropless removes
    _, _, cnt_drop = mk("einsum", True).apply({"params": params}, x)
    assert int(np.asarray(cnt_drop)[0]) == 32  # exp_counts stays PRE-drop


@pytest.mark.parametrize("k", [1, 2])
def test_dropless_aux_loss_matches_drop_path_under_capacity(k):
    """topk_routing's aux loss uses PRE-drop counts by design, so on an
    under-capacity batch (nothing would drop) the drop and dropless paths
    must produce IDENTICAL aux loss, router counts, and outputs."""
    mk = lambda drop: MOELayer(lambda: ExpertMLP(), num_experts=4, k=k,
                               capacity_factor=100.0, min_capacity=64,
                               drop_tokens=drop)
    drop, dropless = mk(True), mk(False)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 16))
    params = drop.init(jax.random.PRNGKey(5), x)["params"]
    out_a, laux_a, cnt_a = drop.apply({"params": params}, x)
    out_b, laux_b, cnt_b = dropless.apply({"params": params}, x)
    assert float(laux_a) == float(laux_b)  # bit-identical by construction
    np.testing.assert_array_equal(np.asarray(cnt_a), np.asarray(cnt_b))
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               atol=1e-6, rtol=1e-6)


def test_dropless_training_trajectory_matches_drop_path():
    """10 SGD steps on an under-capacity batch: the dropless loss
    trajectory tracks the drop path within 1e-5 (ISSUE 15 acceptance)."""
    def run(drop_tokens):
        model = MOELayer(lambda: ExpertMLP(), num_experts=4, k=2,
                         capacity_factor=100.0, min_capacity=64,
                         drop_tokens=drop_tokens)
        x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 16))
        y = jax.random.normal(jax.random.PRNGKey(7), (2, 16, 16))
        params = model.init(jax.random.PRNGKey(8), x)["params"]

        def loss_fn(p):
            out, laux, _ = model.apply({"params": p}, x)
            return jnp.mean((out - y) ** 2) + 0.01 * laux

        losses = []
        for _ in range(10):
            loss, grads = jax.value_and_grad(loss_fn)(params)
            params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
            losses.append(float(loss))
        return losses

    np.testing.assert_allclose(run(True), run(False), atol=1e-5, rtol=0)


def test_gmm_ep_dropless_matches_single_host(pallas_interpret, eight_devices):
    """The expert-parallel dispatch/combine a2a round-trip (ep=2) must
    reproduce the single-host grouped-GEMM result on the same params."""
    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.parallel.topology import MeshTopology

    layer = MOELayer(lambda: GmmExpertMLP(), num_experts=4, k=2,
                     drop_tokens=False, dispatch_mode="gmm")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 128))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    out_ref, laux_ref, cnt_ref = layer.apply({"params": params}, x)
    groups.initialize(mesh_topology=MeshTopology(dp=-1, ep=2))
    try:
        out_ep, laux_ep, cnt_ep = layer.apply({"params": params}, x)
    finally:
        groups.reset()
    np.testing.assert_allclose(np.asarray(out_ep), np.asarray(out_ref),
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(float(laux_ep), float(laux_ref), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(cnt_ep), np.asarray(cnt_ref))


def test_gmm_ep_gradients_flow(pallas_interpret, eight_devices):
    """bits=None keeps the ep round-trip differentiable end to end: grads
    under the ep mesh match the single-host grads."""
    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.parallel.topology import MeshTopology

    layer = MOELayer(lambda: GmmExpertMLP(), num_experts=4, k=2,
                     drop_tokens=False, dispatch_mode="gmm")
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 128))
    params = layer.init(jax.random.PRNGKey(3), x)["params"]

    def loss_fn(p):
        out, laux, _ = layer.apply({"params": p}, x)
        return jnp.sum(out ** 2) + 0.01 * laux

    g_ref = jax.grad(loss_fn)(params)
    groups.initialize(mesh_topology=MeshTopology(dp=-1, ep=2))
    try:
        g_ep = jax.grad(loss_fn)(params)
    finally:
        groups.reset()
    for a, b in zip(jax.tree_util.tree_leaves(g_ep),
                    jax.tree_util.tree_leaves(g_ref)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_gmm_ep_quantized_wire_records_telemetry(pallas_interpret, eight_devices):
    """a2a_wire_bits=8 ships the int8+scales wire: output stays close to
    the fp result and the dispatch/combine wire bytes land in telemetry at
    ~0.25x the logical payload."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.parallel.topology import MeshTopology

    mk = lambda bits: MOELayer(lambda: GmmExpertMLP(), num_experts=4, k=2,
                               drop_tokens=False, dispatch_mode="gmm",
                               a2a_wire_bits=bits)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 128))
    params = mk(None).init(jax.random.PRNGKey(5), x)["params"]
    groups.initialize(mesh_topology=MeshTopology(dp=-1, ep=2))
    telemetry.configure(enabled=True)
    telemetry.reset()
    try:
        out_fp, _, _ = mk(None).apply({"params": params}, x)
        out_q, _, _ = mk(8).apply({"params": params}, x)
        summ = telemetry.summary()
    finally:
        telemetry.configure(enabled=False)
        telemetry.reset()
        groups.reset()
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_fp),
                               atol=0.05, rtol=0.05)
    ops = summ["comm"]["ops"]
    for op in ("a2a_dispatch", "a2a_combine"):
        st = ops[op]["ep"]
        assert st["bytes"] > 0
        # fp pass records wire==bytes; the int8 pass adds ~0.25x — combined
        # ratio over both passes lands well under 1
        assert st["wire_bytes"] < st["bytes"]


def test_moe_utils_reference_surface():
    """has_moe_layers / split / group helpers (reference moe/utils.py)."""
    from deepspeed_tpu.moe.utils import (configure_moe_param_groups,
                                         has_moe_layers, is_moe_param,
                                         is_moe_param_group,
                                         split_params_into_shared_and_expert_params)
    model = MOELayer(lambda: ExpertMLP(), num_experts=4, k=1)
    x = jnp.zeros((1, 8, 16))
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    found, n = has_moe_layers(params)
    assert found and n > 0
    shared, expert = split_params_into_shared_and_expert_params(params)
    assert expert and shared  # gate wg is shared; expert kernels are expert
    assert all(is_moe_param(k) for k in expert)
    groups = configure_moe_param_groups(params)
    assert len(groups) == 2
    assert not is_moe_param_group(groups[0]) and is_moe_param_group(groups[1])
    dense_only = {"dense": {"kernel": jnp.zeros((4, 4))}}
    assert has_moe_layers(dense_only) == (False, 0)
    assert len(configure_moe_param_groups(dense_only)) == 1
