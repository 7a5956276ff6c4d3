"""Steady-state host-sync contract (docs/AUTOTUNING.md, "host-sync-free
stepping"): between ``steps_per_print``/monitor boundaries the engine must
issue ZERO blocking device->host transfers — the loss, overflow flag,
grad norm and skipped counter all stay device-resident, and every fetch the
engine does issue goes through ``_host_fetch`` so ``host_sync_count`` audits
it.

Enforcement is layered because the CPU backend's arrays are host-visible
(zero-copy, so jax's transfer guard never fires there):

1. ``jax.transfer_guard_device_to_host("disallow_explicit")`` wraps the
   steady-state region — on a real TPU any d2h transfer (including an
   explicit ``jax.device_get``) raises;
2. ``jax.device_get`` is monkeypatched to count calls — effective on CPU CI;
3. ``engine.host_sync_count`` must stay flat across steady-state steps and
   tick exactly once per accounted boundary fetch.
"""

import jax
import numpy as np
import pytest

import deepspeed_tpu
from tests.simple_model import SimpleModel, random_batches

NEVER = 10 ** 9  # steps_per_print cadence that a short test never reaches


def _make_engine(extra=None, seed=0):
    cfg = {
        "train_batch_size": 8,
        "steps_per_print": NEVER,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    }
    cfg.update(extra or {})
    model = SimpleModel()
    batch = random_batches(1, 8)[0]
    params = model.init(jax.random.PRNGKey(seed), batch)["params"]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=cfg)
    return engine


class _GetCounter:
    """Counting wrapper around jax.device_get (calls through)."""

    def __init__(self):
        self.calls = 0
        self._orig = jax.device_get

    def __call__(self, x):
        self.calls += 1
        return self._orig(x)


@pytest.fixture
def counted_device_get(monkeypatch):
    counter = _GetCounter()
    monkeypatch.setattr(jax, "device_get", counter)
    return counter


def test_steady_state_step_has_no_host_sync(counted_device_get):
    engine = _make_engine()
    batches = random_batches(8, 8)
    # warmup: compile + let output weak-types settle OUTSIDE the guard
    for b in batches[:2]:
        loss = engine(b)
        engine.backward(loss)
        engine.step()
    jax.block_until_ready(engine.state.params)

    base_syncs = engine.host_sync_count
    base_gets = counted_device_get.calls
    with jax.transfer_guard_device_to_host("disallow_explicit"):
        for b in batches[2:]:
            loss = engine(b)
            engine.backward(loss)
            engine.step()
    assert engine.host_sync_count == base_syncs, \
        "steady-state step() issued an accounted host sync"
    assert counted_device_get.calls == base_gets, \
        "steady-state step() called jax.device_get"
    assert engine.global_steps == len(batches)
    # the result is still correct once the caller pays the sync
    assert np.isfinite(float(jax.device_get(loss)))


def test_boundary_fetches_are_counted(counted_device_get):
    engine = _make_engine()
    b = random_batches(1, 8)[0]
    loss = engine(b)
    engine.backward(loss)
    engine.step()

    base = engine.host_sync_count
    engine.get_lr()
    assert engine.host_sync_count == base + 1
    _ = engine.cur_scale
    assert engine.host_sync_count == base + 2
    _ = engine.skipped_steps
    assert engine.host_sync_count == base + 3
    engine.get_global_grad_norm()
    assert engine.host_sync_count == base + 4
    # every accounted fetch went through exactly one device_get
    assert counted_device_get.calls >= 4


def test_steps_per_print_boundary_syncs():
    """The log_dist boundary (steps_per_print=1 -> every step) fetches
    skipped/lr/scale through the accounted path."""
    engine = _make_engine({"steps_per_print": 1})
    b = random_batches(1, 8)[0]
    loss = engine(b)
    engine.backward(loss)
    base = engine.host_sync_count
    engine.step()
    assert engine.host_sync_count > base


def test_train_batch_returns_device_resident_loss(counted_device_get):
    engine = _make_engine({"train_batch_size": 16,
                           "gradient_accumulation_steps": 2})
    batches = random_batches(8, 8)
    it = iter(batches)
    engine.train_batch(it)  # warmup window (compile)

    base_gets = counted_device_get.calls
    base_syncs = engine.host_sync_count
    with jax.transfer_guard_device_to_host("disallow_explicit"):
        mean = engine.train_batch(it)
    assert isinstance(mean, jax.Array), \
        "train_batch must return the device-resident window mean"
    assert counted_device_get.calls == base_gets
    assert engine.host_sync_count == base_syncs
    assert np.isfinite(float(jax.device_get(mean)))


def test_fused_gas_train_batch_no_steady_state_sync(counted_device_get):
    engine = _make_engine({"train_batch_size": 16,
                           "gradient_accumulation_steps": 2,
                           "fused_step": True})
    batches = random_batches(8, 8)
    it = iter(batches)
    engine.train_batch(it)  # warmup: compiles the fused GAS scan

    base_gets = counted_device_get.calls
    with jax.transfer_guard_device_to_host("disallow_explicit"):
        mean = engine.train_batch(it)
    assert isinstance(mean, jax.Array)
    assert counted_device_get.calls == base_gets
    assert engine._fused_gas_step_fn is not None
    assert np.isfinite(float(jax.device_get(mean)))


def test_host_sync_counter_in_telemetry(tmp_path):
    """When telemetry is on, accounted fetches land in the host_sync
    counter (bench surfaces the same number via extra.host_sync_count)."""
    from deepspeed_tpu import telemetry
    telemetry.configure(enabled=True)
    try:
        engine = _make_engine()
        b = random_batches(1, 8)[0]
        loss = engine(b)
        engine.backward(loss)
        engine.step()
        engine.get_lr()
        counters = telemetry.summary()["counters"]
        assert "host_sync" in counters
        assert any("get_lr" in tag for tag in counters["host_sync"])
    finally:
        telemetry.configure(enabled=False)


@pytest.mark.parametrize("device_sampling", [True, False])
def test_serving_round_is_one_fetch_whatever_its_dispatches(
        counted_device_get, device_sampling):
    """A serving round costs ONE accounted fetch (one ``device_get`` of its
    dispatches' results as a list), whether it took one dispatch (decode
    rows only) or several (the rows of one token together, every other row
    alone: uid 4's prompt of 3 tokens too)."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(scan_layers=True, remat=False)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    engine = InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 8,
                          "max_ragged_batch_size": 64,
                          "max_context": 64, "num_kv_blocks": 48},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})
    sched = SplitFuseScheduler(engine, device_sampling=device_sampling)
    rng = np.random.default_rng(7)
    submit = lambda uid, n: sched.submit(
        uid, rng.integers(0, cfg.vocab_size, n).astype(np.int32),
        max_new_tokens=6)
    submit(0, 5)
    seen = set()
    for arrivals in ([], [(1, 20)], [(2, 12), (3, 30)], [(4, 3)], [], []):
        for uid, n in arrivals:
            submit(uid, n)
        syncs, gets = engine.host_sync_count, counted_device_get.calls
        sched.step()
        assert engine.host_sync_count == syncs + 1
        assert counted_device_get.calls == gets + 1
        seen.add(len(engine.last_batch_shapes))
    assert seen == {1, 2, 3}, "rounds of one, two and three dispatches"
    assert (sched.rounds, sched.dispatches) == (6, 1 + 2 + 3 + 2 + 1 + 1)
