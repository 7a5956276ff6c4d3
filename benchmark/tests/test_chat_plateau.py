"""Where ``chat-poisson``'s p99 gap stands, met on a CPU before a chip: a
discrete-event model of the cell's window (the committed traffic file through
the one generator; the scheduler's composition rule; round lengths by class
from ``data/mistral_round_ms.json``, medians of runs on the chip) and the
plateau rule of ``benchmark/tails.py`` on its gaps. No number here is a device
number: the lengths are a table, and the test is about ranks."""

import json
import os

import numpy as np
import pytest

from benchmark import harness, tails, traffic

CELL = "mistral-7b-l16.chat-poisson"
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "mistral_round_ms.json")) as f:
    TABLE = json.load(f)


def model_window(mix, table, seconds=45.0, seed=1, stalls=0, jitter_seed=0):
    """One window of the open loop: -> (gaps_ms, gap_round, rounds as
    ``serve.Driver.window`` writes them, ttft_s). Decode rows first, the rest
    of the token budget to the waiting prompts in the order they came, each
    chunk a dispatch of its own in its power-of-two bucket; a request's first
    token comes with the round that ends its prompt. ``stalls`` rounds, the
    ones with the most decode rows, take ``stall_ms`` longer."""
    load = traffic.requests(mix, seed, seconds, 32000)["requests"]

    def play(stalled):
        rng = np.random.default_rng(jitter_seed)      # the same jitter up to the first stall
        pending = [(due, len(prompt), max_new) for due, prompt, max_new in load]
        active, now, rounds, gaps, gap_round, ttft = [], 0.0, [], [], [], []
        while pending or active:
            while pending and pending[0][0] <= now:
                due, n_prompt, max_new = pending.pop(0)
                active.append({"due": due, "left": n_prompt, "out": 0, "max_new": max_new, "last": None})
            if not active:
                now = pending[0][0]
                continue
            if now >= seconds and all(r["out"] for r in active):
                break
            decoding = [r for r in active if not r["left"]]
            budget, chunks, first = table["token_budget"] - len(decoding), [], []
            for r in active:
                if r["left"] and budget >= 1:
                    take = min(budget, r["left"])
                    r["left"] -= take
                    budget -= take
                    chunks.append(take)
                    if not r["left"]:
                        first.append(r)
            ms = table["host_ms"] + sum(table["chunk_ms"][str(tails.chunk_bucket(c))] for c in chunks)
            if decoding:
                first_row, base, per_row = [c for c in table["decode_ms_from_rows"] if c[0] <= len(decoding)][-1]
                ms += base + per_row * (len(decoding) - first_row)
            ms *= 1.0 + table["jitter"] * rng.standard_normal()
            if len(rounds) in stalled:
                ms += table["stall_ms"]
            start, now = now, now + ms / 1e3
            for r in decoding + first:
                if r["last"] is None:
                    ttft.append(now - r["due"])
                elif start < seconds:
                    gaps.append(1e3 * (now - r["last"]))
                    gap_round.append(len(rounds))
                r["last"] = now
                r["out"] += 1
            if start < seconds:
                rounds.append((now, ms, sum(chunks), len(decoding)))
            active = [r for r in active if r["out"] < r["max_new"]]
        return gaps, gap_round, rounds, ttft

    plain = play(set())
    if not stalls:
        return plain
    busiest = sorted(range(len(plain[2])), key=lambda i: (-plain[2][i][3], i))[:stalls]
    return play(set(busiest))


@pytest.fixture(scope="module")
def mix():
    return harness.Cell(CELL).traffic


def _bound():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["end_to_end"]}["token_gap_p99_ms"]["bound"]


@pytest.mark.parametrize("stalls", [0, 3, 6])
def test_the_committed_rate_keeps_p99_on_the_long_rounds_plateau(mix, stalls):
    """With no stall the rule holds; 3 or 6 stall rounds of +115 ms at the
    busiest rows move p99 along the plateau by less than the metric's bound
    (on the chip the plateau slopes by the decode rows' bucket, and runs with
    stalls fail the 1 % rule by their upper neighbour: PERF.md section 6)."""
    quiet = tails.plateau(model_window(mix, TABLE)[0])
    assert quiet["holds"], (mix["rate"], quiet)
    gaps, gap_round, rounds, ttft = model_window(mix, TABLE, stalls=stalls)
    found = tails.plateau(gaps)
    assert len(tails.stall_rounds(rounds)) == stalls
    assert abs(found["p99_ms"] - quiet["p99_ms"]) <= _bound() * quiet["p99_ms"], (stalls, found, quiet)
    long_gaps = sum(g >= 45 for g in gaps)
    assert found["rank"] * 2 < long_gaps                 # the cliff is far below the rank
    assert len(ttft) == int(mix["rate"] * 45)            # every request got its first token


def test_one_request_a_second_sits_on_the_cliff(mix):
    """At 1.0 request/s (PRs 26-46) p99 is the 64th longest of ~6,370 gaps
    and 60-62 gaps are long: the rule fails, and a few stall rounds move p99
    from the short round's plateau to the long one's."""
    slow = dict(mix, rate=1.0)
    gaps, gap_round, rounds, _ = model_window(slow, TABLE)
    found = tails.plateau(gaps)
    assert found["rank"] == 64 and 6300 < len(gaps) < 6450
    long_gaps = sum(g >= 45 for g in gaps)
    assert 55 <= long_gaps <= 63
    assert not found["holds"]
    moved = tails.p99_with_stalls(gaps, gap_round, rounds)
    assert moved > 1.3 * found["p99_ms"]


def test_rank_plateau_and_stall_rounds_of_a_hand_made_window():
    # 1,000 gaps: p99 is the 11th longest; ranks 6 and 17 are its neighbours
    gaps = [50.0] * 5 + [49.9] * 20 + [13.0] * 975
    assert tails.p99_rank(1000) == 11 and tails.p99_rank(6373) == 64
    found = tails.plateau(gaps)
    assert found == {"rank": 11, "p99_ms": 49.9, "plateau_ms": [49.9, 49.9], "holds": True}
    # an edge three gaps below the rank: the neighbour at rank 17 is a short round's
    edge = [50.0] * 13 + [33.0] * 987
    found = tails.plateau(edge)
    assert found["p99_ms"] == 50.0 and found["plateau_ms"] == [50.0, 33.0] and not found["holds"]
    assert tails.plateau([]) is None
    assert harness.percentile(gaps, 99) == 49.9       # the same rank as the harness's p99
    # rounds: (end_s, ms, prefill_tokens, decode_rows); classes by bucket and decode rows
    rounds = [(0.0, 13.0, 0, 2)] * 9 + [(0.0, 130.0, 0, 3)] + [(0.0, 49.0, 500, 2)] * 3 \
        + [(0.0, 98.0, 300, 1), (0.0, 99.5, 512, 2), (0.0, 37.0, 512, 0)]
    assert tails.stall_rounds(rounds) == [9, 14]      # 98 ms is alone in its class: its own median
    assert tails.chunk_bucket(0) == 0 and tails.chunk_bucket(7) == 16 and tails.chunk_bucket(257) == 512
    # six stalls at the busiest rounds: every gap of those rounds grows
    gaps = [13.0] * 990 + [49.0] * 10
    gap_round = [i // 10 for i in range(1000)]
    rounds = [(0.0, 13.0, 0, 10)] * 100
    assert tails.p99_with_stalls(gaps, gap_round, rounds, n=1) == 49.0      # ten longer gaps: rank 11 is the 49 ms
    assert tails.p99_with_stalls(gaps, gap_round, rounds, n=2) == pytest.approx(13.0 + 115.0)
    fields = tails.describe(gaps, gap_round, rounds)
    assert fields["token_gap_p99_rank"] == 11 and fields["stall_rounds"] == 0
    assert fields["token_gap_on_plateau"] is False and fields["token_gap_plateau_ms"] == [49.0, 13.0]
