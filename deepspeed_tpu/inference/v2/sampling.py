"""On-device per-sequence sampling for the ragged serving path.

The reference's FastGen loop keeps sampling host-side in DeepSpeed-MII (the
v2 engine returns logits — ``deepspeed/inference/v2/engine_v2.py:107`` — and
MII's postprocessing samples them); on TPU that design transfers a full
``[S, vocab]`` float tensor device->host every decode step, which caps
tokens/s well below kernel capability. Here the temperature/top-k/top-p
transform AND the categorical draw run inside one jitted program on the
device; the host receives only ``[S]`` int32 token ids.

Per-row (per-request) parameters are traced values, so one compiled program
serves every mix of greedy/sampled requests — no retrace when a new request
arrives with a different temperature. One program, two arms: each entry
branches ONCE for the whole dispatch (``_dispatch_sample``), on whether any
of its rows has a temperature above 0. Where none has (padded rows carry
0.0), the ids are the rows' argmax and the device sorts, scans and draws
nothing; where one has, every row goes through ``_row_sample``, whose
select gives a greedy row that same argmax. The branch sits above the
``vmap`` because a ``cond`` under ``vmap`` lowers to a select, which runs
both arms: sorting a ``[64, 200064]`` dispatch whose result no row read was
the largest device operation of a decode round (PERF.md, PR 38).
Determinism: each row draws from ``fold_in(PRNGKey(seed), position)``, so a
(seed, position) pair always yields the same token, independent of batch
composition — the same contract the host sampler in ``scheduler.py``
provides.

Semantics mirror ``SplitFuseScheduler._sample`` (greedy at temperature 0;
top-k keeps values >= the kth largest; top-p keeps the smallest set with
cumulative probability >= top_p, always including the top token; top-p is
computed over the already-top-k-masked distribution).
"""

import functools

import jax
import jax.numpy as jnp

_NEG = -1e9


def _row_sample(logits, temp, top_k, top_p, seed, position):
    """Sample one token from one row of logits. All params traced scalars."""
    greedy = jnp.argmax(logits).astype(jnp.int32)
    v = logits.shape[-1]
    scaled = logits.astype(jnp.float32) / jnp.maximum(temp, 1e-6)
    # top-k: keep values >= the kth largest (top_k <= 0 disables)
    sorted_desc = jnp.sort(scaled)[::-1]
    kth = sorted_desc[jnp.clip(top_k - 1, 0, v - 1)]
    masked = jnp.where((top_k > 0) & (scaled < kth), _NEG, scaled)
    # top-p over the post-top-k distribution (matches the host sampler's
    # sequential masking); cutoff_idx always keeps the top token. Masking
    # below-kth values to _NEG preserves descending order, so the sorted
    # masked array falls out of the first sort — no second O(V log V) sort.
    sorted_m = jnp.where((top_k > 0) & (sorted_desc < kth), _NEG, sorted_desc)
    probs = jax.nn.softmax(sorted_m)
    cutoff_idx = jnp.clip(jnp.sum(jnp.cumsum(probs) < top_p), 0, v - 1)
    cutoff = sorted_m[cutoff_idx]
    masked = jnp.where((top_p < 1.0) & (masked < cutoff), _NEG, masked)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), position)
    sampled = jax.random.categorical(key, masked).astype(jnp.int32)
    return jnp.where(temp <= 0.0, greedy, sampled)


def _dispatch_sample(sample_all, logits, temps):
    """The ids of one dispatch: ``sample_all()`` (every row through
    ``_row_sample``) where any row of ``temps`` samples, else the argmax
    over the vocabulary and nothing more. The ids are the same either way:
    a row of temperature 0 gets its argmax from ``_row_sample`` too."""
    return jax.lax.cond(
        jnp.any(temps > 0.0), sample_all,
        lambda: jnp.argmax(logits, axis=-1).astype(jnp.int32))


@jax.jit
def sample_rows(logits, temps, top_ks, top_ps, seeds, positions):
    """Vectorized per-row sampling.

    Args:
        logits: ``[S, V]`` float — device array straight from the ragged
            forward (never materialized on the host).
        temps/top_ps: ``[S]`` float32; top_ks/seeds/positions: ``[S]`` int32.

    Returns ``[S]`` int32 token ids (still on device; the caller transfers
    4*S bytes instead of 4*S*V).
    """
    return _dispatch_sample(
        lambda: jax.vmap(_row_sample)(logits, temps, top_ks, top_ps, seeds,
                                      positions), logits, temps)


@jax.jit
def verify_rows_packed(logits, fparams, iparams):
    """Per-row, per-column sampling for a draft-then-verify round.

    ``logits`` is ``[S, K, V]`` — the LAST-aligned ``K`` chunk positions of
    each row, straight from ``ragged_forward_verify``. ``iparams[2]`` holds
    each row's stream position for the FINAL column; column ``c`` is then
    sampled at stream position ``iparams[2][s] - (K-1) + c`` with the row's
    own ``(temp, top_k, top_p, seed)`` — i.e. exactly the draw plain decode
    would make once the stream reaches that position. The host compares
    these target tokens against the drafts to find the accepted prefix;
    every emitted token therefore IS the plain-decode stream. Columns
    before a row's chunk (or before stream position 0) are padding the
    caller never reads.

    ``fparams`` ``[2, S]`` float32 (temps, top_ps); ``iparams`` ``[3, S]``
    int32 (top_ks, seeds, last-column stream positions).
    Returns ``[S, K]`` int32.
    """
    k = logits.shape[1]
    cols = jnp.arange(k, dtype=jnp.int32)

    def row(lg, temp, top_k, top_p, seed, last_pos):
        return jax.vmap(
            lambda l, c: _row_sample(l, temp, top_k, top_p, seed,
                                     last_pos - (k - 1) + c)
        )(lg, cols)

    return _dispatch_sample(
        lambda: jax.vmap(row)(logits, fparams[0], iparams[0], fparams[1],
                              iparams[1], iparams[2]), logits, fparams[0])


@functools.partial(jax.jit, donate_argnums=(3,))
def sample_rows_packed(logits, fparams, iparams, kept):
    """``sample_rows`` with the five per-row parameter vectors packed into
    two host arrays — ``fparams`` ``[2, S]`` float32 (temps, top_ps) and
    ``iparams`` ``[4, S]`` int32 (top_ks, seeds, positions, and where each
    row's id goes in ``kept``) — unpacked inside the trace. Two
    host->device transfers per decode dispatch instead of five; on CPU
    fleets stepping several schedulers per round the per-dispatch host time
    is the serving bottleneck, not the math.

    Returns ``(ids, kept)``: the ``[S]`` ids for the host's one fetch a
    round, and ``kept`` (donated; its length fixed by the engine's limits,
    whatever ``S``) with each row's id written at ``iparams[3]``, a place
    past its end for a padded row, which writes nothing: the next round's
    forward reads a decode row's token there (``engine_v2.packed_forward``)
    before the host has it.
    """
    ids = _dispatch_sample(
        lambda: jax.vmap(_row_sample)(logits, fparams[0], iparams[0],
                                      fparams[1], iparams[1], iparams[2]),
        logits, fparams[0])
    return ids, kept.at[iparams[3]].set(ids, mode="drop")
