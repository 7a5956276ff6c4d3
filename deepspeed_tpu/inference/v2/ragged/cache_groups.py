"""What a model keeps per sequence between dispatches, declared by the model
and owned by the one ``DSStateManager``.

A model's serving description is a tuple of groups. The first is always a
paged group named ``"kv"``: the one whose tables ``RaggedBatchWrapper`` builds
and which the prefix cache, the page wire and the host tiers work on. A
homogeneous stack (the llama family, mixtral, opt, the parallel block) is that
one group. Further groups are either more paged groups (layers whose pages are
allocated apart, with a ``window`` that lets pages be freed once every later
query has left them behind) or one slot group (leaves of fixed size a
sequence, addressed by a slot id: recurrent state).
"""

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class PagedGroup:
    name: str
    layers: int
    kv_heads: int
    head_dim: int
    # None: pages live as long as the sequence (a window, if the model has
    # one, is then only a mask). An int: pages wholly before
    # ``seen - window`` are freed after each round.
    window: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class SlotGroup:
    name: str
    # ((leaf name, shape of one sequence's slot with the layer axis first,
    # dtype name), ...); the pool is [layers, slots + 1, *shape[1:]]
    leaves: Tuple[Tuple[str, Tuple[int, ...], str], ...]


def homogeneous(cfg):
    """The one paged group of a stack whose every layer caches K and V."""
    head_dim = getattr(cfg, "head_dim", None) or \
        cfg.hidden_size // cfg.num_attention_heads
    kv_heads = getattr(cfg, "num_key_value_heads",
                       cfg.num_attention_heads)  # OPT has no GQA field
    return (PagedGroup("kv", cfg.num_hidden_layers, kv_heads, head_dim),)
