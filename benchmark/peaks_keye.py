"""The work Keye-VL-2.0's learned sparse attention needs, computed from
shapes (``benchmark/peaks.py`` has the peaks and ``roofline_seconds``),
whatever implements it. Sizes come from the configuration file's published
keys: an indexer of 16 heads of 64 over ONE key head of 64 (128 B a token and
layer in bfloat16), 32 query heads over 4 KV heads of 128 (K and V of a token
and layer: 2,048 B), 2,048 tokens read a query.
"""


def pairs(new, end):
    """(query, context token) pairs a row of ``new`` queries ending at
    position ``end`` has in one layer: query ``j`` sits at ``end - new + j``
    and sees the tokens up to itself."""
    return new * end - new * (new - 1) // 2


def selected(cfg, new, end):
    """(query, selected token) pairs of the same row in one layer: a query
    at position ``p`` reads ``min(p + 1, topk)`` tokens."""
    topk = cfg["sa_config"]["topk"]
    return sum(min(end - new + j + 1, topk) for j in range(new))


def index_key_bytes(cfg, itemsize=2):
    return cfg["sa_config"]["indexer_head_dim"] * itemsize


def kv_token_bytes(cfg, itemsize=2):
    """K and V of one token in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def dsa_index_flops(cfg, new, end):
    """The index scores of a row over every layer: a (query, context token)
    pair costs ``heads x head_dim`` multiply-adds (the ReLU, the weighted sum
    over heads and the selection are not counted: no peak bounds them)."""
    sa = cfg["sa_config"]
    return 2.0 * cfg["num_hidden_layers"] * sa["indexer_num_heads"] \
        * sa["indexer_head_dim"] * pairs(new, end)


def dsa_index_bytes(cfg, new, end, itemsize=2):
    """The index keys of the row's ``end`` tokens, once a layer."""
    return float(cfg["num_hidden_layers"] * end * index_key_bytes(cfg, itemsize))


def dsa_read_flops(cfg, new, end):
    """The read over every layer: a (query, selected token) pair and head
    costs ``head_dim`` multiply-adds for the score and as many for the value
    sum."""
    return 4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * cfg["head_dim"] * selected(cfg, new, end)


def dsa_read_bytes(cfg, new, end, itemsize=2):
    """K and V of the tokens READ, once a layer: a decode row reads
    ``min(context, topk)`` tokens, a chunk the lesser of its context and the
    sum of its queries' set sizes (its queries may share tokens, or not)."""
    tokens = min(end, selected(cfg, new, end))
    return float(cfg["num_hidden_layers"] * tokens * kv_token_bytes(cfg, itemsize))
