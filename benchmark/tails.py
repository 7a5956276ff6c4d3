"""What a serving window says about its own tail, computed from its gaps and
rounds once it has closed: the rank ``token_gap_p99_ms`` is read at, whether
that rank lies on a plateau of equal gaps or beside an edge, and how many of
the rounds were stalls. Nothing here changes the statistic: p99 stays the
nearest-rank percentile of every gap (``harness.percentile``)."""

import math
import statistics

PLATEAU_TOLERANCE = 0.01      # the neighbours' gaps lie within this share of p99's
STALL_MS = 50.0               # a round this much longer than its class's median


def p99_rank(n):
    """Rank of the nearest-rank p99 of ``n`` values, counted from the longest
    (1 = the longest)."""
    return n - math.ceil(0.99 * n) + 1


def plateau(gaps_ms):
    """{"rank": k, "p99_ms", "plateau_ms": [gap at rank floor(0.6 k), gap at
    rank ceil(1.5 k)], "holds"}: the window stands on a plateau when both
    neighbours are within 1 % of the gap at rank k, so that some 0.4 k more
    long gaps, or 0.5 k fewer, leave p99 where it is. None without gaps."""
    if not gaps_ms:
        return None
    xs = sorted(gaps_ms, reverse=True)
    k = p99_rank(len(xs))
    at = lambda rank: xs[min(max(rank, 1), len(xs)) - 1]
    p99, above, below = at(k), at(math.floor(0.6 * k)), at(math.ceil(1.5 * k))
    holds = above - p99 <= PLATEAU_TOLERANCE * p99 and p99 - below <= PLATEAU_TOLERANCE * p99
    return {"rank": k, "p99_ms": p99, "plateau_ms": [above, below], "holds": bool(holds)}


def chunk_bucket(prefill_tokens):
    """The power-of-two bucket (16..) a round's prompt tokens fall in; 0 for none."""
    if not prefill_tokens:
        return 0
    b = 16
    while b < prefill_tokens:
        b *= 2
    return b


def stall_rounds(rounds):
    """Indices of the rounds longer by ``STALL_MS`` or more than the median
    round of their class (same chunk bucket; decode rows or none). ``rounds``:
    [(end_s, ms, prefill_tokens, decode_rows)]."""
    classes = {}
    for i, (_, ms, prefill, rows) in enumerate(rounds):
        classes.setdefault((chunk_bucket(prefill), bool(rows)), []).append((i, ms))
    out = []
    for members in classes.values():
        median = statistics.median(ms for _, ms in members)
        out += [i for i, ms in members if ms - median >= STALL_MS]
    return sorted(out)


def p99_with_stalls(gaps_ms, gap_round, rounds, n=6, extra_ms=115.0):
    """p99 of the window's gaps had its ``n`` rounds with the most decode rows
    each taken ``extra_ms`` longer: every gap that ended in such a round grows
    by that much. ``gap_round[i]`` is the index in ``rounds`` of gap i's round."""
    if not gaps_ms:
        return None
    hit = set(sorted(set(gap_round), key=lambda r: (-rounds[r][3], r))[:n])
    return plateau([g + extra_ms if r in hit else g for g, r in zip(gaps_ms, gap_round)])["p99_ms"]


def describe(gaps_ms, gap_round, rounds):
    """The fields a serving run prints beside ``token_gap_p99_ms``."""
    found = plateau(gaps_ms) or {"rank": None, "plateau_ms": None, "holds": None}
    return {"token_gap_p99_rank": found["rank"], "token_gap_plateau_ms": found["plateau_ms"],
            "token_gap_on_plateau": found["holds"], "stall_rounds": len(stall_rounds(rounds)),
            "token_gap_p99_with_6_stalls_ms": p99_with_stalls(gaps_ms, gap_round, rounds)}
