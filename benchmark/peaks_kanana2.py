"""The work Kanana-2's latent attention needs, computed from shapes
(``benchmark/peaks.py`` has the peaks and ``roofline_seconds``). Sizes come
from the configuration file's published keys: 32 heads of 128 | 64 on a latent
of 512, values of 128; a token keeps 512 + 64 values a layer, 1,152 B in
bfloat16, whatever width the program pads its row to.
"""


def latent_token_bytes(cfg, itemsize=2):
    """What one token keeps in one layer: the latent and the shared rotated
    position part."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def pairs(new, end):
    """Query-key pairs a row of ``new`` queries ending at position ``end``
    must score in one layer: query ``j`` sits at ``end - new + j`` and sees
    the keys up to itself."""
    return new * end - new * (new - 1) // 2


def mla_attn_bytes(cfg, new, end, itemsize=2):
    """HBM bytes the attention of one row must move over every layer: the
    latent rows of its ``end`` tokens, once; its q in (heads x 192) and its o
    out (heads x 128)."""
    H = cfg["num_attention_heads"]
    qo = new * H * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                    + cfg["v_head_dim"]) * itemsize
    return float(cfg["num_hidden_layers"] * (end * latent_token_bytes(cfg, itemsize) + qo))


def mla_attn_flops_absorbed(cfg, new, end):
    """The query taken into the latent's columns: a pair and head costs the
    score over 512 + 64 columns and the value sum over 512, 2 operations a
    multiply-add. (Absorbing q and taking o back through ``W_UV`` belong to
    the projections: ``new`` rows, no keys.)"""
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return 2.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * pairs(new, end) * (2 * r + dr)


def mla_attn_flops_materialised(cfg, new, end):
    """Every head's keys and values up-projected from the ``end`` latents
    first (``end x 512 x heads x (128 + 128)``), then a pair and head costs
    192 for the score and 128 for the value sum."""
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return 2.0 * cfg["num_hidden_layers"] * H \
        * (end * r * (dn + dv) + pairs(new, end) * (dn + dr + dv))


def mla_attn_flops(cfg, new, end):
    """The LESSER of the two forms, whatever the program runs: the count does
    not change with the implementation."""
    return min(mla_attn_flops_absorbed(cfg, new, end),
               mla_attn_flops_materialised(cfg, new, end))
