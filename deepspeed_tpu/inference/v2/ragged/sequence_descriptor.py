"""Sequence bookkeeping (mirrors reference
``deepspeed/inference/v2/ragged/sequence_descriptor.py``)."""

import dataclasses
from typing import Dict, List, Optional


@dataclasses.dataclass
class DSSequenceDescriptor:
    uid: int
    seen_tokens: int = 0          # tokens already resident in the KV cache
    in_flight_tokens: int = 0     # tokens scheduled in the current forward
    kv_blocks: List[int] = dataclasses.field(default_factory=list)
    # host handle while the sequence's KV lives in the swap tier
    # (ragged/kv_cache.py swap_out) — kv_blocks is empty meanwhile
    swap_handle: object = None
    # prefix-cache bookkeeping, populated only when prefix_caching is on:
    # every token routed through the sequence (prompt + generated), and the
    # chain digest of each committed full block (digests[i] commits to
    # tokens[:(i+1)*block_size] and labels kv_blocks[i] in the cache)
    tokens: List[int] = dataclasses.field(default_factory=list)
    digests: List[bytes] = dataclasses.field(default_factory=list)
    # models with more than the one paged group (ragged/cache_groups.py):
    # per further paged group the pages held and the index (in blocks from
    # the sequence's start) of the first of them, pages before it having been
    # freed behind the window; the slot of recurrent state; and while swapped
    # out, those groups' host copies
    group_blocks: Dict[str, List[int]] = dataclasses.field(default_factory=dict)
    group_base: Dict[str, int] = dataclasses.field(default_factory=dict)
    slot: Optional[int] = None
    group_swap: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def is_swapped(self) -> bool:
        return self.swap_handle is not None

    @property
    def cur_allocated_blocks(self) -> int:
        return len(self.kv_blocks)

    def extend_blocks(self, blocks):
        self.kv_blocks.extend(blocks)

    def post_forward(self):
        """Commit in-flight tokens after a forward (reference
        ``sequence_descriptor.py`` seen_tokens update)."""
        self.seen_tokens += self.in_flight_tokens
        self.in_flight_tokens = 0
