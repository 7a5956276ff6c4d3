"""The work Kimi-Linear's two mixers need, computed from shapes
(``benchmark/peaks.py`` has the peaks and ``roofline_seconds``). Sizes come
from the configuration file's published keys: 32 KDA heads of 128 x 128
(key x value) in float32, a state of 65,536 B a head, 2,097,152 B a sequence
and layer; what a token brings to a head (q, k, the decay's log and v, a row
of 128 each, and beta) and takes from it (o) is float32 as the program hands
it to the kernels. The latent attention is Kanana-2's count
(``peaks_kanana2``) over the MLA layers alone (``mla_config``).
"""

CHUNK = 64            # tokens a chunk of the chunk form the counts are of
F32 = 4


def _kda(cfg):
    lin = cfg["linear_attn_config"]
    return len(lin["kda_layers"]), lin["num_heads"], lin["head_dim"]


def state_bytes(cfg):
    """One sequence's KDA state in one layer."""
    _, H, d = _kda(cfg)
    return H * d * d * F32


def token_bytes(cfg):
    """What one token moves through one KDA layer's state kernel: q, k, g and
    v read (a row of ``head_dim`` a head each), beta read, o written."""
    _, H, d = _kda(cfg)
    return H * (5 * d + 1) * F32


def kda_step_bytes(cfg, rows):
    """HBM bytes the one-step update of ``rows`` decode rows must move over
    every KDA layer: a row's state read and written once, its token's vectors
    in and its o out."""
    layers, _, _ = _kda(cfg)
    return float(layers * rows * (2 * state_bytes(cfg) + token_bytes(cfg)))


def kda_step_flops(cfg, rows):
    """A head's step: the decay (dk dv multiplies), ``S'^T k`` and ``S^T q``
    (2 dk dv each), the correction's outer product and its sum (2 dk dv)."""
    layers, H, d = _kda(cfg)
    return float(layers * rows * H * 7 * d * d)


def kda_chunk_bytes(cfg, rows, tokens):
    """The chunk form over ``tokens`` tokens of ``rows`` rows: every token's
    vectors in and its o out, a row's state read and written once."""
    layers, _, _ = _kda(cfg)
    return float(layers * (rows * 2 * state_bytes(cfg) + tokens * token_bytes(cfg)))


def kda_chunk_flops(cfg, tokens):
    """The chunk form's operations at C = 64 a head (2 a multiply-add),
    whatever the kernel does inside: the strictly lower ``A`` (C (C - 1) / 2
    pairs of dk), the lower q-k scores (C (C + 1) / 2 of dk), ``(K * Gam) S_0``
    and ``(q * Gam) S_0`` (C dk dv each), the forward substitution (C (C - 1) /
    2 rows of dv), the scores times U (C (C + 1) / 2 of dv), the new state (C
    dk dv + dk dv): a chunk's count over its 64 tokens, times the tokens."""
    layers, H, d = _kda(cfg)
    C = CHUNK
    a_chunk = (C * (C - 1) * d + C * (C + 1) * d          # A, the q-k scores
               + 2 * 2 * C * d * d                        # both products with S_0
               + C * (C - 1) * d + C * (C + 1) * d        # the substitution, scores x U
               + 2 * C * d * d + d * d)                   # the new state
    return float(layers * H * tokens * a_chunk / C)


def mla_config(cfg):
    """The configuration as ``peaks_kanana2`` reads it: its
    ``num_hidden_layers`` counts the layers that keep latent pages."""
    return dict(cfg, num_hidden_layers=len(cfg["linear_attn_config"]["full_attn_layers"]))
