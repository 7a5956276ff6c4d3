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

A paged group's page is two leaves, a K pool and a V pool, unless the group
says ``leaves=1``: then it is ONE pool whose row a token (``head_dim`` wide,
``kv_heads`` of them, 1 for a latent row) is read for the scores AND, its
first ``value_dim`` columns, for the values. Latent (MLA) attention keeps such
a row: the normalised latent beside the rotated position part, nothing a
head. What works on a K and V pair and was not extended (the prefix cache,
speculation's rollback, int8 pages, the page wire, the host tiers) refuses a
one-leaf group by this field.

A K and V pair may keep a THIRD leaf beside it, ``index_dim`` columns a token
and layer in one head of its own: the key of a learned indexer, which scores
every cached token of a sequence for each query and so picks the tokens whose
K and V are read (learned sparse attention). It lives in the same pages, under
the same allocator and the same table as its token's K and V, and is
allocated, freed, preempted and resumed with them; what was not extended to
it is refused by this field as for a group of one leaf (``kv_pair``).

A third kind beside the paged and the slot group is NOT a sequence's: a
``CounterGroup``, a small int32 accumulator of named fields that the family's
forward adds to, a dispatch (what only the device knows of a dispatch: which
experts its rows landed on). The state manager holds the one array in
``cache_view`` / ``cache_update``, donated and returned like a pool, and hands
it out on demand (``device_counters``: one fetch outside any round, never a
round's own). No sequence owns a part of it, so nothing that carries a
sequence's state carries it: allocation, free, preemption and resume leave it
as it is, and the prefix cache, the page wire and the host tiers (swap) have
nothing of it to key, ship or spill. A family that declares none gets no leaf.
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
    # 2: a K pool and a V pool. 1: one pool whose rows are keys and, their
    # first ``value_dim`` columns, values
    leaves: int = 2
    value_dim: Optional[int] = None
    # columns of a further leaf of one head beside K and V (an indexer's key
    # a token and layer); None: no such leaf. With it, the tokens a query
    # reads at most (what the indexer picks): the state manager counts a
    # dispatch's sparse rows and selected tokens by it
    index_dim: Optional[int] = None
    index_topk: Optional[int] = None

    def __post_init__(self):
        if self.leaves not in (1, 2):
            raise ValueError("a paged group's page is 1 leaf or 2")
        if (self.leaves == 1) != (self.value_dim is not None):
            raise ValueError("value_dim belongs to a group of one leaf, "
                             "and such a group states it")
        if self.index_dim is not None and (self.leaves != 2 or self.window):
            raise ValueError("an index leaf stands beside a K and V pair "
                             "whose pages live as long as the sequence")
        if (self.index_dim is None) != (self.index_topk is None):
            raise ValueError("index_topk belongs to a group with an index "
                             "leaf, and such a group states it")

    @property
    def kv_pair(self):
        """Whether a page is a K and a V leaf and nothing else: what the
        prefix cache, speculation's rollback, int8 pages, the page wire and
        the host tiers work on."""
        return self.leaves == 2 and self.index_dim is None


@dataclasses.dataclass(frozen=True)
class SlotGroup:
    name: str
    # ((leaf name, shape of one sequence's slot with the layer axis first,
    # dtype name), ...); the pool is [layers, slots + 1, *shape[1:]]
    leaves: Tuple[Tuple[str, Tuple[int, ...], str], ...]


@dataclasses.dataclass(frozen=True)
class CounterGroup:
    name: str
    # the accumulator is int32 [len(fields)], a field a place
    fields: Tuple[str, ...]

    def __post_init__(self):
        if not self.fields or len(set(self.fields)) != len(self.fields):
            raise ValueError("a counter group names its fields, each once")


def homogeneous(cfg):
    """The one paged group of a stack whose every layer caches K and V."""
    head_dim = getattr(cfg, "head_dim", None) or \
        cfg.hidden_size // cfg.num_attention_heads
    kv_heads = getattr(cfg, "num_key_value_heads",
                       cfg.num_attention_heads)  # OPT has no GQA field
    return (PagedGroup("kv", cfg.num_hidden_layers, kv_heads, head_dim),)
