"""The work Mellum2's expert GEMMs and its attention over two kinds of pages
need, computed from shapes (``benchmark/peaks.py`` has the peaks and
``roofline_seconds``). Sizes come from the configuration file's published
keys: hidden 2304, experts of width 896, 64 of them, 8 a token; K and V of 4
heads x 128 in bfloat16, 2 KiB a token and layer.
"""


def expert_layers(cfg):
    return sum(t == "sparse" for t in cfg["mlp_layer_types"][:cfg["num_hidden_layers"]])


def attention_layers(cfg):
    """(sliding layers, full layers) of the layers that are run."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return kinds.count("sliding_attention"), kinds.count("full_attention")


def experts_hit(cfg, tokens):
    """Experts a dispatch of ``tokens`` real tokens reads in a layer, under
    uniform routing: ``E (1 - (1 - k/E)^tokens)``; all 64 from 64 tokens on
    (63.99)."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return E * (1.0 - (1.0 - k / E) ** tokens)


def moe_gmm_flops(cfg, tokens):
    """The three GEMMs of a dispatch of ``tokens`` real tokens over every
    expert layer: ``k`` rows a token through ``hidden x width`` three times,
    2 operations a multiply-add: ``6 x hidden x width x k x tokens`` a layer.
    The same count whatever implements the layer."""
    return 6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * cfg["num_experts_per_tok"] * tokens * expert_layers(cfg)


def moe_gmm_bytes(cfg, tokens, itemsize=2):
    """HBM bytes the same dispatch must move: the three matrices of the
    experts it hits, and its ``k x tokens`` rows of ``hidden`` in and out."""
    one_expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize
    rows = 2 * cfg["num_experts_per_tok"] * tokens * cfg["hidden_size"] * itemsize
    return float(expert_layers(cfg) * (experts_hit(cfg, tokens) * one_expert + rows))


def kv_token_bytes(cfg, itemsize=2):
    """K and V of one token in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def _visible(new, end, window=None):
    """(keys a row of ``new`` queries ending at position ``end`` must read,
    query-key pairs it must score) in one layer: query ``j`` sits at position
    ``end - new + j`` and sees the keys up to itself, the last ``window``."""
    if window is None:
        return end, new * end - new * (new - 1) // 2
    first = end - new + 1                    # keys the first query could see
    pairs = sum(min(first + j, window) for j in range(new))
    return min(end, window + new - 1), pairs


def mixed_attn_bytes(cfg, new, end, itemsize=2):
    """HBM bytes the attention of one row (``new`` queries ending at
    ``end``) must move over every layer: K and V of ``end`` tokens in each
    full layer and of ``min(end, window + new - 1)`` in each sliding layer,
    and the row's q in and o out."""
    sliding, full = attention_layers(cfg)
    keys_w, _ = _visible(new, end, cfg["sliding_window"])
    qo = 2 * new * cfg["num_attention_heads"] * cfg["head_dim"] * itemsize
    return float(kv_token_bytes(cfg, itemsize) * (full * end + sliding * keys_w)
                 + (sliding + full) * qo)


def mixed_attn_flops(cfg, new, end):
    """QK^T and PV of the same row: 4 x heads x head_dim a query-key pair."""
    sliding, full = attention_layers(cfg)
    per_pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return per_pair * (full * _visible(new, end)[1]
                       + sliding * _visible(new, end, cfg["sliding_window"])[1])
