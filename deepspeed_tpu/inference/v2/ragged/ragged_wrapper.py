"""Ragged batch assembly (mirrors reference
``deepspeed/inference/v2/ragged/ragged_wrapper.py:31``).

The reference packs tokens into pinned host buffers consumed by ragged CUDA
kernels. The XLA-native layout is a *padded dense* batch with static shapes:
``[S, Q]`` token ids (S = sequence slots, Q = per-seq new-token budget) plus
per-sequence metadata (true new-token counts, tokens already in cache, block
tables). Padding rows/cols are masked inside the model and their KV writes go
to the trash block, so one compiled program serves any mix of prefill and
decode — the property the reference gets from ragged kernels.

One rectangle per round would give every row the longest row's width: 64
decode rows beside one 400-token prompt chunk are 32768 slots. A round is
therefore dispatched by chunk-length class (``dispatch_rows``): its short
rows together, each other row alone as ``[1, C]``. In a plain round the
short rows are the rows of exactly ONE new token, the decode rows, and go as
``[D, 1]``: the dense layers, the KV write and the scan cost what a
dispatch's token slots are, padding included, and a decode row has one. A
verify round's rows carry ``[last] + drafts`` and go as ``[D, max(8, k)]``.
"""

import math

import numpy as np
from jax import lax

#: floor of the width of a verify round's short class (``short_row_tokens``).
#: A plain round's short class is one token wide and does not read this.
SHORT_ROW_TOKENS = 8

#: least width of a row dispatched alone: a prompt's last 2-8 tokens take the
#: ``[1, 16]`` program that chunks of 9-16 tokens compile anyway, so no
#: twelfth program is warmed for them
LONE_ROW_TOKENS = 16


def short_row_tokens(verify_k=None):
    """Most new tokens of a row of a round's short class, which is also that
    class's width: 1 in a plain round (``verify_k`` None or 0), in a round
    that verifies ``verify_k`` positions a row the verify width and at least
    ``SHORT_ROW_TOKENS`` (a verify row stays short whatever the width)."""
    return max(SHORT_ROW_TOKENS, verify_k) if verify_k else 1


def dispatch_rows(lengths, short):
    """The dispatches of a round whose rows carry ``lengths`` new tokens, as
    [(rows, min_seqs, min_tokens)] for ``RaggedBatchWrapper.build``: the rows
    of at most ``short`` tokens together (if any), padded to at least 4
    sequences and exactly ``short`` tokens, then each longer row alone, its
    width at least ``LONE_ROW_TOKENS``. Only ``[D, short]`` and ``[1, C]``
    batches follow from it, D and C powers of two, whatever the round
    mixes."""
    together = [i for i, n in enumerate(lengths) if n <= short]
    alone = [([i], 1, LONE_ROW_TOKENS) for i, n in enumerate(lengths)
             if n > short]
    return ([(together, 4, short)] if together else []) + alone


def pack(fields):
    """A dispatch's host arrays as ONE flat int32 buffer, so that they cross
    to the device in one transfer: ``fields`` is ``{name: array}`` in the
    order the program takes them (tokens, lengths, positions, the tokens'
    sources, then every cache group's tables as the state manager gives
    them). Returns
    ``(layout, packed)``: ``layout`` the ``(name, shape)`` of each field in
    order, which ``unpack`` slices by (every width is fixed for an engine, so
    it is a function of the dispatch's two buckets), ``packed`` a fresh
    buffer (a transfer may read it after the call returns). An array of
    another dtype is refused by name, not cast."""
    for name, a in fields.items():
        if a.dtype != np.int32:
            raise TypeError(f"dispatch array {name!r} is {a.dtype}, not int32: "
                            "it cannot join the packed buffer")
    layout = tuple((name, a.shape) for name, a in fields.items())
    return layout, np.concatenate([a.ravel() for a in fields.values()])


def unpack(layout, packed):
    """``{name: array}`` of ``pack``'s fields back out of the flat buffer,
    by static slices: inside a program (``packed`` traced) they fuse into
    their consumers."""
    fields, at = {}, 0
    for name, shape in layout:
        n = math.prod(shape)
        fields[name] = lax.slice(packed, (at,), (at + n,)).reshape(shape)
        at += n
    if at != packed.shape[0]:
        raise ValueError(f"layout holds {at} values, the buffer {packed.shape[0]}")
    return fields


class RaggedBatchWrapper:

    def __init__(self, max_seqs, max_new_tokens_per_seq, max_blocks_per_seq,
                 trash_block):
        self.max_seqs = max_seqs
        self.max_q = max_new_tokens_per_seq
        self.max_blocks = max_blocks_per_seq
        self.trash_block = trash_block
        self.clear()

    def clear(self):
        self._rows = []  # (uid, tokens, seen, blocks, src)

    def insert_sequence(self, uid, tokens, seen_tokens, kv_blocks, src=-1):
        """``src``: where the row's one new token lies among the ids the
        round before left on the device (``engine_v2.packed_forward`` reads
        it there), -1 where ``tokens`` holds it."""
        if len(self._rows) >= self.max_seqs:
            raise ValueError(f"batch already holds {self.max_seqs} sequences")
        if len(tokens) > self.max_q:
            raise ValueError(f"{len(tokens)} new tokens > per-seq budget {self.max_q}")
        if len(kv_blocks) > self.max_blocks:
            raise ValueError(f"sequence needs {len(kv_blocks)} blocks > table width "
                             f"{self.max_blocks}")
        self._rows.append((uid, list(tokens), seen_tokens, list(kv_blocks), src))

    @property
    def current_sequences(self):
        return len(self._rows)

    @property
    def current_tokens(self):
        return sum(len(row[1]) for row in self._rows)

    @property
    def uids(self):
        return [row[0] for row in self._rows]

    def build(self, min_seqs=4, min_tokens=1):
        """Pad to the static [S, Q] / [S, MB] device layout.

        S and Q are bucketed to the smallest power-of-two multiple of
        ``min_seqs`` / ``min_tokens`` covering the batch, to bound recompiles
        while keeping decode batches cheap; ``dispatch_rows`` says which
        floors a dispatch takes.
        """
        S = min_seqs
        while S < len(self._rows):
            S *= 2
        S = min(S, self.max_seqs)
        longest = max((len(row[1]) for row in self._rows), default=1)
        Q = min_tokens
        while Q < longest:
            Q *= 2
        Q = min(Q, self.max_q)

        tokens = np.zeros((S, Q), np.int32)
        q_len = np.zeros((S,), np.int32)
        seen = np.zeros((S,), np.int32)
        src = np.full((S,), -1, np.int32)
        block_tables = np.full((S, self.max_blocks), self.trash_block, np.int32)
        for i, (_, toks, sn, blocks, at) in enumerate(self._rows):
            tokens[i, :len(toks)] = toks
            q_len[i] = len(toks)
            seen[i] = sn
            src[i] = at
            block_tables[i, :len(blocks)] = blocks
        return {"tokens": tokens, "q_len": q_len, "seen": seen, "src": src,
                "block_tables": block_tables}
