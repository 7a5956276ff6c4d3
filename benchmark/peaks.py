"""The yardstick's constants and arithmetic: peak rates per chip, and the
operations and bytes an algorithm needs, computed from shapes.

Peaks are the published ones (Google Cloud documentation, "TPU v5e": 197
TFLOP/s bf16, 819 GB/s HBM, 16 GB). A ``device_kind`` that is not in the table
is an error, never a default: a share of an unknown peak means nothing.
"""

_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e system architecture)"}
#: keyed by device_kind as jax reports it
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise SystemExit(f"benchmark: no peaks for device_kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def roofline_seconds(flops, nbytes, peaks):
    """(least seconds the chip could take, which bound it is)."""
    t_f = flops / peaks["bf16_flops"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")


# -- model FLOPs (training): what forward and backward REQUIRE; recomputation
# under remat is not counted --------------------------------------------------

def gpt2_train_flops_per_token(cfg, seq):
    """6 x (matmul parameters) + causal attention, per trained token.

    Matmul parameters: 12 d^2 per layer (qkv 3d^2, proj d^2, mlp 8d^2) and
    the tied LM head V x d (the embedding lookup is no matmul; wpe adds
    none). Attention: QK^T and PV are 2 x 2 x seq x d FLOPs per token
    forward over the full square; causal masking needs half; backward is
    twice forward -> 3 x (4 seq d) / 2 = 6 seq d per layer."""
    d, n_layer, vocab = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    matmul_params = n_layer * 12 * d * d + vocab * d
    return 6.0 * matmul_params + n_layer * 6.0 * seq * d


def flash_train_flops(batch, heads, seq, head_dim):
    """FLOPs one causal flash forward + backward needs over [batch, heads,
    seq, head_dim]: forward 2 matmuls (QK^T, PV), backward 5 (recompute QK^T,
    dV, dP, dQ, dK), each 2 x seq x seq x head_dim, halved by causality."""
    return 7 * 2.0 * batch * heads * seq * seq * head_dim / 2


def flash_train_bytes(batch, heads, seq, head_dim, itemsize=2):
    """Least HBM bytes of the same: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv (the [seq] row
    statistics are left out: under 1 %)."""
    return (4 + 8) * batch * heads * seq * head_dim * itemsize


def paged_decode_bytes(context_tokens, layers, kv_heads, head_dim, itemsize=2):
    """HBM bytes a decode round's paged attention must read: K and V of every
    context token of every sequence, in every layer."""
    return 2.0 * context_tokens * layers * kv_heads * head_dim * itemsize


def paged_decode_flops(context_tokens, layers, heads, head_dim):
    """QK^T and PV for one new token per sequence against its context."""
    return 2 * 2.0 * context_tokens * layers * heads * head_dim
