"""The work LongCat-Flash-Chat's double layer needs, computed from shapes
(``benchmark/peaks.py`` has the peaks and ``roofline_seconds``). Sizes come
from the configuration file's published keys: hidden 6144, two dense SwiGLUs
of 12288 a layer, experts of 2048, 64 heads of 128 | 64 on a latent of 512; a
token keeps 512 + 64 values a SUB-BLOCK, two a layer.
"""

from benchmark import peaks_kanana2


def mla_config(cfg):
    """The keys ``peaks_kanana2``'s counts read, with the planes a token's
    latent rows lie in (two a layer) in the layers' place: the same lesser-form
    count at 64 heads and 8 planes."""
    return dict(cfg, num_hidden_layers=2 * cfg["num_layers"])


def mla_attn_flops(cfg, new, end):
    return peaks_kanana2.mla_attn_flops(mla_config(cfg), new, end)


def mla_attn_bytes(cfg, new, end, itemsize=2):
    return peaks_kanana2.mla_attn_bytes(mla_config(cfg), new, end, itemsize)


def expert_bytes(cfg, itemsize=2):
    """The three matrices of one routed expert."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"] * itemsize


def moe_gmm_flops(cfg, held_rows):
    """The three grouped GEMMs over the rows that LANDED on an expert held
    (the device counters' ``held_rows``, every layer and dispatch summed): a
    row through ``hidden x width`` three times, 2 operations a multiply-add.
    A row that took a zero expert or another share's expert costs none."""
    return 6.0 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"] * held_rows


def moe_gmm_bytes(cfg, experts_hit, held_rows, itemsize=2):
    """HBM bytes the same GEMMs must move: the matrices of the experts that
    were HIT (the counters' ``experts_hit``: held experts with at least one
    row, a layer and dispatch, summed), and each landed row of ``hidden`` in
    and out."""
    return float(experts_hit * expert_bytes(cfg, itemsize)
                 + 2 * held_rows * cfg["hidden_size"] * itemsize)


def dense_ffn_flops(cfg, tokens):
    """The two dense SwiGLUs of every layer on ``tokens`` real tokens of a
    dispatch: three products of ``hidden x ffn`` each."""
    return 2.0 * cfg["num_layers"] * 6.0 * cfg["hidden_size"] \
        * cfg["ffn_hidden_size"] * tokens


def dense_ffn_bytes(cfg, tokens, itemsize=2):
    """HBM bytes of the same: each FFN's three matrices once a dispatch, its
    tokens of ``hidden`` in and out."""
    d, f = cfg["hidden_size"], cfg["ffn_hidden_size"]
    return float(2 * cfg["num_layers"] * (3 * d * f + 2 * tokens * d) * itemsize)
