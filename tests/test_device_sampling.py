"""On-device serving sampler (`inference/v2/sampling.py`): the jitted
temperature/top-k/top-p + categorical draw must honor the same contract as
the host sampler it replaces (greedy at temp 0, support restricted to the
top-k/top-p set, deterministic per (seed, position)), and the scheduler's
device path must agree with the host path on greedy decodes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.sampling import (
    _row_sample, sample_rows, sample_rows_packed, verify_rows_packed)


def _rows(v=97, s=4, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(s, v)).astype(np.float32))


def _params(temps, top_ks, top_ps, seeds, positions):
    """The five per-row vectors as ``sample_rows`` takes them."""
    return (jnp.asarray(temps, jnp.float32), jnp.asarray(top_ks, jnp.int32),
            jnp.asarray(top_ps, jnp.float32), jnp.asarray(seeds, jnp.int32),
            jnp.asarray(positions, jnp.int32))


def _call(logits, *params):
    return np.asarray(sample_rows(logits, *_params(*params)))


def test_greedy_rows_are_argmax():
    logits = _rows()
    ids = _call(logits, [0.0] * 4, [0] * 4, [1.0] * 4, [1, 2, 3, 4],
                [0, 1, 2, 3])
    np.testing.assert_array_equal(ids, np.argmax(np.asarray(logits), -1))


def test_top_k_one_is_argmax_at_any_temperature():
    logits = _rows(seed=1)
    ids = _call(logits, [5.0] * 4, [1] * 4, [1.0] * 4, [7] * 4, [0] * 4)
    np.testing.assert_array_equal(ids, np.argmax(np.asarray(logits), -1))


def test_top_k_restricts_support():
    logits = _rows(s=1, seed=2)
    top5 = set(np.argsort(np.asarray(logits)[0])[::-1][:5].tolist())
    for seed in range(40):
        ids = _call(logits, [2.0], [5], [1.0], [seed], [0])
        assert int(ids[0]) in top5, f"seed {seed} escaped the top-5 set"


def test_tiny_top_p_is_argmax():
    logits = _rows(seed=3)
    ids = _call(logits, [3.0] * 4, [0] * 4, [1e-6] * 4, [9, 8, 7, 6],
                [0] * 4)
    np.testing.assert_array_equal(ids, np.argmax(np.asarray(logits), -1))


def test_top_p_restricts_support():
    """Sampled ids must come from the smallest prefix reaching top_p mass."""
    logits = _rows(s=1, seed=4)
    temp = 1.5
    scaled = np.asarray(logits)[0] / temp
    order = np.argsort(scaled)[::-1]
    probs = np.exp(scaled[order] - scaled[order][0])
    probs /= probs.sum()
    cutoff_idx = int(np.sum(np.cumsum(probs) < 0.5))
    allowed = set(order[:cutoff_idx + 1].tolist())
    for seed in range(40):
        ids = _call(logits, [temp], [0], [0.5], [seed], [0])
        assert int(ids[0]) in allowed, f"seed {seed} escaped the top-p set"


def test_deterministic_per_seed_and_position():
    logits = _rows(seed=5)
    a = _call(logits, [1.0] * 4, [0] * 4, [1.0] * 4, [11, 12, 13, 14],
              [0, 1, 2, 3])
    b = _call(logits, [1.0] * 4, [0] * 4, [1.0] * 4, [11, 12, 13, 14],
              [0, 1, 2, 3])
    np.testing.assert_array_equal(a, b)
    # position changes the draw stream: across 16 positions x 4 rows at
    # temperature 1 over 97 logits, at least one draw must differ
    draws = [_call(logits, [1.0] * 4, [0] * 4, [1.0] * 4, [11, 12, 13, 14],
                   [p] * 4) for p in range(16)]
    assert any(not np.array_equal(draws[0], d) for d in draws[1:]), \
        "position did not perturb the sampling stream"


def test_rows_independent_of_batch_composition():
    """Row i's draw depends only on (its logits, its params) — the contract
    that lets the scheduler fuse arbitrary request mixes into one batch."""
    logits = _rows(s=4, seed=6)
    batch = _call(logits, [0.9, 0.0, 1.7, 1.0], [5, 0, 0, 3],
                  [1.0, 1.0, 0.7, 1.0], [21, 22, 23, 24], [0, 4, 9, 2])
    for i in range(4):
        solo = _call(logits[i:i + 1], [[0.9, 0.0, 1.7, 1.0][i]],
                     [[5, 0, 0, 3][i]], [[1.0, 1.0, 0.7, 1.0][i]],
                     [[21, 22, 23, 24][i]], [[0, 4, 9, 2][i]])
        assert int(solo[0]) == int(batch[i])


# -- one branch a dispatch: all greedy takes the argmax, any sampled row sorts ---

VERIFY_K = 3


def _entry_logits(entry, s, seed, v=97):
    shape = (s, VERIFY_K, v) if entry == "verify_rows_packed" else (s, v)
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape).astype(np.float32))


def _entry_args(entry, logits, temps, top_ks, top_ps, seeds, positions):
    """(jitted entry, its arguments) for rows of these parameters; a verify
    row's ``positions`` is the stream position of its LAST column."""
    if entry == "sample_rows":
        return sample_rows, (logits,) + _params(temps, top_ks, top_ps, seeds,
                                                positions)
    fparams = jnp.asarray([temps, top_ps], jnp.float32)
    if entry == "verify_rows_packed":
        return verify_rows_packed, (
            logits, fparams, jnp.asarray([top_ks, seeds, positions], jnp.int32))
    # the ids for the host; what it keeps on the device has a test of its own
    rows = list(range(len(temps)))
    return (lambda *args: sample_rows_packed(*args)[0]), (
        logits, fparams, jnp.asarray([top_ks, seeds, positions, rows], jnp.int32),
        jnp.zeros(len(rows), jnp.int32))


def _unbranched(entry, logits, temps, top_ks, top_ps, seeds, positions):
    """``vmap(_row_sample)`` with no branch above it: what every entry
    returned before it had two arms."""
    *args, pos = _params(temps, top_ks, top_ps, seeds, positions)
    if entry != "verify_rows_packed":
        return np.asarray(jax.vmap(_row_sample)(logits, *args, pos))
    cols = [jax.vmap(_row_sample)(logits[:, c], *args,
                                  pos - (VERIFY_K - 1) + c)
            for c in range(VERIFY_K)]
    return np.stack([np.asarray(c) for c in cols], axis=1)


ENTRIES = ("sample_rows", "sample_rows_packed", "verify_rows_packed")


@pytest.mark.parametrize("entry", ENTRIES)
def test_all_greedy_dispatch_with_padded_rows_is_the_argmax(entry):
    """Three greedy requests (whatever their top-k and top-p) and the zero
    rows ``_packed_sampler`` pads with: the argmax of every row, and of
    every column of a verify row."""
    logits = _entry_logits(entry, s=8, seed=20)
    n = 3
    pad = lambda xs: list(xs) + [0] * (8 - n)
    fn, args = _entry_args(entry, logits, pad([0.0] * n), pad([5, 0, 1]),
                           pad([0.3, 1.0, 0.9]), pad([3, 4, 5]),
                           pad([7, 0, 2]))
    np.testing.assert_array_equal(np.asarray(fn(*args)),
                                  np.argmax(np.asarray(logits), -1))


def _primitives(jaxpr):
    """Names of every equation of ``jaxpr`` and of the jaxprs inside it."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _primitives(sub)
    return names


@pytest.mark.parametrize("entry", ENTRIES)
def test_entry_branches_once_and_its_argmax_arm_neither_sorts_nor_draws(entry):
    """The CPU cannot time the arms; it can read the program: ONE ``cond``
    at the top level (under a ``vmap`` it would be a select, and both arms
    would run), none below it, and an argmax arm with no sort, no scan and
    no random bits, beside a sampling arm that has all three."""
    logits = _entry_logits(entry, s=4, seed=21)
    fn, args = _entry_args(entry, logits, [0.0] * 4, [0] * 4, [1.0] * 4,
                           [0] * 4, [0] * 4)
    top = jax.make_jaxpr(fn)(*args).jaxpr
    jitted, = top.eqns
    body = jitted.params["jaxpr"].jaxpr
    conds = [e for e in body.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    assert _primitives(body).count("cond") == 1, "a cond below the top level"
    # index 0 is the arm of a false predicate: no row samples
    argmax_arm, sort_arm = (_primitives(b.jaxpr)
                            for b in conds[0].params["branches"])
    costly = {"sort", "cumsum", "random_bits"}
    assert "argmax" in argmax_arm and not costly & set(argmax_arm), argmax_arm
    assert costly <= set(sort_arm), sort_arm
    # nothing of the sampler's is left outside the arms
    assert not costly & {e.primitive.name for e in body.eqns}


@pytest.mark.parametrize("entry", ENTRIES)
def test_one_sampled_row_leaves_every_row_the_id_it_has_alone(entry):
    """A dispatch of greedy rows with ONE sampled row takes the sorting arm:
    every row gets the id ``vmap(_row_sample)`` gives it (what the entry
    returned before it branched) and the id it gets in a dispatch of its
    own: the greedy rows the argmax arm's, the sampled row the other's."""
    logits = _entry_logits(entry, s=4, seed=22)
    params = ([0.0, 0.0, 1.3, 0.0], [0, 4, 6, 0], [1.0, 0.5, 0.8, 1.0],
              [31, 32, 33, 34], [5, 9, 3, 0])
    fn, args = _entry_args(entry, logits, *params)
    batch = np.asarray(fn(*args))
    np.testing.assert_array_equal(batch, _unbranched(entry, logits, *params))
    for i in range(4):
        fn, args = _entry_args(entry, logits[i:i + 1],
                               *([p[i]] for p in params))
        np.testing.assert_array_equal(np.asarray(fn(*args))[0], batch[i])
    greedy = [0, 1, 3]
    np.testing.assert_array_equal(
        batch[greedy], np.argmax(np.asarray(logits), -1)[greedy])


def test_packed_sampler_keeps_each_rows_id_at_its_place_on_the_device():
    """``sample_rows_packed`` returns the ids twice: ``[S]`` for the host's
    fetch, and written into the buffer the next round's forward reads, each
    at the place ``iparams[3]`` gives; a padded row's place is past the end
    and writes nothing, and a place no row names keeps what it held. The
    buffer's length is the engine's, whatever the dispatch's ``S``."""
    logits = _entry_logits("sample_rows_packed", s=4, seed=23)
    kept = jnp.arange(100, 106, dtype=jnp.int32)
    iparams = jnp.asarray([[0] * 4, [0] * 4, [0] * 4, [5, 2, 6, 6]], jnp.int32)
    ids, after = sample_rows_packed(logits, jnp.zeros((2, 4), jnp.float32),
                                    iparams, kept)
    ids, after = np.asarray(ids), np.asarray(after)
    np.testing.assert_array_equal(ids, np.argmax(np.asarray(logits), -1))
    assert after.shape == (6,) and after.dtype == np.int32
    assert after[5] == ids[0] and after[2] == ids[1]
    np.testing.assert_array_equal(after[[0, 1, 3, 4]], [100, 101, 103, 104])


@pytest.fixture(scope="module")
def served():
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig.tiny(scan_layers=True, remat=False)
    model = LlamaForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 8)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    return cfg, model, params


def _make_sched(served, device_sampling):
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
    cfg, model, params = served
    engine = InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": 16,
                          "max_context": 128,
                          "num_kv_blocks": 64},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})
    return SplitFuseScheduler(engine, token_budget=16,
                              device_sampling=device_sampling)


def test_scheduler_greedy_device_matches_host(served):
    cfg, _, _ = served
    prompt = np.random.default_rng(10).integers(
        0, cfg.vocab_size, 23).astype(np.int32)
    outs = []
    for dev in (True, False):
        sched = _make_sched(served, device_sampling=dev)
        sched.submit(0, prompt, max_new_tokens=6)
        outs.append(sched.run_to_completion()[0].tolist())
    assert outs[0] == outs[1], (
        f"device greedy {outs[0]} != host greedy {outs[1]}")


def test_scheduler_sampled_device_reproducible(served):
    cfg, _, _ = served
    prompt = np.random.default_rng(11).integers(
        0, cfg.vocab_size, 9).astype(np.int32)

    def run(seed):
        sched = _make_sched(served, device_sampling=True)
        sched.submit(0, prompt, max_new_tokens=5, temperature=0.8,
                     top_k=20, seed=seed)
        return sched.run_to_completion()[0].tolist()

    assert run(123) == run(123), "same seed must reproduce on device"
