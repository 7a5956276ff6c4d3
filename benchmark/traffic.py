"""The one general traffic generator. A traffic mix is a data file of
parameters (``benchmark/traffic/<name>.json``); this module turns it and a
seed into requests. New mixes are new files, never new code.

Every seed gets the SAME multiset of lengths and arrival gaps (drawn once from
the mix's ``shape_seed``) with other token ids: runs with different seeds then
do the same work. ``"order": "by_seed"`` (the default) gives each seed another
order of them; ``"order": "fixed"`` keeps one order for all seeds, for mixes
whose tail is read from so few requests that their order decides it.
"""

import numpy as np


def _lengths(rng, spec, n):
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def _arrival_gaps(rng, mix, n):
    """n gaps whose sum is exactly n / rate, so that every window is offered
    the same load."""
    if mix["arrival"] == "poisson":
        gaps = rng.exponential(1.0, n)
    elif mix["arrival"] == "burst":
        gaps = np.zeros(n)
        gaps[::mix["burst_size"]] = 1.0
    else:
        raise ValueError(f"unknown arrival schedule {mix['arrival']!r}")
    return gaps * (n / mix["rate"]) / gaps.sum()


def requests(mix, seed, seconds, vocab):
    """Open loop -> {"loop": "open", "requests": [(due_s, prompt ids,
    max_new_tokens)]} with every due time inside [0, seconds).
    Closed loop -> {"loop": "closed", "clients": [[(prompt ids,
    max_new_tokens), ...] per client], "phase": [fraction of its first answer
    each client has still to get when the window opens]}."""
    shape = np.random.default_rng(mix.get("shape_seed", 0))
    order = np.random.default_rng(
        [int(seed) if mix.get("order", "by_seed") == "by_seed" else 0, 0x6F726472])
    toks = np.random.default_rng([int(seed), 0x746F6B73])
    prompt = lambda n: toks.integers(0, vocab, int(n), dtype=np.int32)
    if mix["loop"] == "open":
        n = max(1, int(mix["rate"] * seconds))
        p_len, o_len = _lengths(shape, mix["prompt"], n), _lengths(shape, mix["output"], n)
        gaps = _arrival_gaps(shape, mix, n)
        perm, gperm = order.permutation(n), order.permutation(n)
        # every gap of the multiset is used: the first request waits its own
        # gap less the smallest one, the last is due that much before the end
        due = np.cumsum(gaps[gperm]) - gaps.min()
        return {"loop": "open", "requests": [
            (float(due[i]), prompt(p_len[perm[i]]), int(o_len[perm[i]])) for i in range(n)]}
    if mix["loop"] == "closed":
        c, per = mix["clients"], mix["requests_per_client"]
        p_len = _lengths(shape, mix["prompt"], c * per)
        o_len = _lengths(shape, mix["output"], c * per)
        perm = order.permutation(c * per).reshape(c, per)
        clients = [[(prompt(p_len[j]), int(o_len[j])) for j in perm[i]] for i in range(c)]
        phase = (order.permutation(c) + 1) / c
        return {"loop": "closed", "clients": clients, "phase": phase.tolist()}
    raise ValueError(f"unknown loop {mix['loop']!r}")
