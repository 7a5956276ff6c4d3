"""The work Phi-4-mini-flash-reasoning's two kinds of kernel need, computed
from shapes (``benchmark/peaks.py`` has the peaks and ``roofline_seconds``).

Sizes come from the configuration file: ``d_inner = expand x hidden_size``,
``d_state``, and per attention layer K and V of ``num_key_value_heads x
head_dim`` each in bfloat16 (5,120 B a token a layer as published).
"""


def _sizes(cfg):
    a = cfg["assumed"]["sizes"]
    L = cfg["num_hidden_layers"]
    return {"d_inner": a["mamba_expand"] * cfg["hidden_size"], "d_state": a["mamba_d_state"],
            "mamba_layers": L // 4 + 1,
            # layers that read the full layer's pages: itself and the cross layers
            "global_readers": L // 4, "window_layers": L // 4,
            "kv_token_bytes": 2 * cfg["num_key_value_heads"]
            * (cfg["hidden_size"] // cfg["num_attention_heads"]) * 2,
            "heads": cfg["num_attention_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"]}


def selective_scan_bytes(cfg, rows, real_tokens):
    """HBM bytes the scans of dispatches with ``rows`` sequences and
    ``real_tokens`` real tokens in all must move, over every Mamba layer: each
    row's float32 state read and written, and per real token c (bf16), Delta
    (float32), B and C (float32) read and y (bf16) written. The gate z is
    applied outside the kernel and not counted."""
    s = _sizes(cfg)
    state = rows * 2 * s["d_state"] * s["d_inner"] * 4
    token = real_tokens * (s["d_inner"] * (2 + 4 + 2) + 2 * s["d_state"] * 4)
    return float(s["mamba_layers"] * (state + token))


def selective_scan_flops(cfg, real_tokens):
    """Per real token, layer and (channel, state) element: exp's argument,
    the decay, the input's two products and its add, the output's product and
    add: ~9 (the exp itself counted as one)."""
    s = _sizes(cfg)
    return 9.0 * real_tokens * s["mamba_layers"] * s["d_inner"] * s["d_state"]


def hybrid_decode_bytes(cfg, context_tokens, window_context_tokens):
    """K and V bytes a decode round's attention must read: the whole context
    once for each layer that reads the full layer's pages, and the part of it
    inside the window for each window layer."""
    s = _sizes(cfg)
    return float(s["kv_token_bytes"] * (context_tokens * s["global_readers"]
                                        + window_context_tokens * s["window_layers"]))


def hybrid_decode_flops(cfg, context_tokens, window_context_tokens):
    """One new token a sequence: per key, head and layer QK^T over head_dim
    and PV over the pair's 2 x head_dim."""
    s = _sizes(cfg)
    per_key = s["heads"] * (2.0 * s["head_dim"] + 2.0 * 2 * s["head_dim"])
    return per_key * (context_tokens * s["global_readers"]
                      + window_context_tokens * s["window_layers"])
