"""FastGen v2 engine config (mirrors reference
``deepspeed/inference/v2/config_v2.py`` + ``ragged/manager_configs.py``)."""

from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel


class DSStateManagerConfig(DeepSpeedConfigModel):
    """Ragged state-manager knobs (reference ``ragged/manager_configs.py``)."""
    max_tracked_sequences = 2048
    max_ragged_batch_size = 768          # max total new tokens per put()
    max_ragged_sequence_count = 512      # max sequences per put()
    max_context = 8192                   # max tokens a single sequence may hold
    memory_config = "reserve"            # accepted for parity
    num_kv_blocks = None                 # explicit block count; None = derive
    # KV storage dtype: "fp" keeps pages in kv_cache.cache_dtype; "int8"
    # stores pages int8 with per-token fp32 scales (quantize-on-write in the
    # forward, fused dequant-on-read in the paged kernel) — ~4x page capacity
    # vs fp32 at generation-parity quality (test-pinned).
    kv_dtype = "fp"
    # host-DRAM KV spill tier capacity, in blocks. 0 disables the tier.
    # When > 0, parked prefix-cache blocks under pool pressure SPILL to host
    # (contents preserved, device id freed) instead of being evicted; the
    # pressure order becomes spill-to-host -> evict-to-free -> preempt-live.
    host_kv_blocks = 0
    # NVMe tier under the host tier (ZeRO-Infinity's disk rung, the 1M-token
    # regime): when the host tier fills, its oldest payload demotes to the
    # in-tree swap_tensor aio path instead of forcing an eviction — pressure
    # order spill -> NVMe -> evict -> preempt. Requires host_kv_blocks > 0.
    nvme_kv_blocks = 0
    nvme_kv_dir = ""                     # "" = fresh tempdir per manager


class KVCacheConfig(DeepSpeedConfigModel):
    block_size = 64
    cache_dtype = "bf16"


class SpeculativeConfig(DeepSpeedConfigModel):
    """Draft-then-verify decode knobs.

    Self-speculation by default: an n-gram prompt-lookup drafter (zero extra
    weights) proposes up to ``max_draft_tokens`` per decode row; the verify
    round batches ``[last_token] + drafts`` through the same ragged prefill
    kernel as a SplitFuse chunk and rolls the paged cursor back over any
    rejected tail. Generation is bit-exact with plain decode either way
    (test-pinned): accepted tokens are by construction exactly the tokens
    plain decode would have emitted at those ``(seed, position)`` stream
    points, so the knob only changes how many forwards the stream costs.
    """
    enabled = False
    # max drafted tokens per sequence per round (verify chunk is this + 1)
    max_draft_tokens = 4
    # longest suffix n-gram the drafter matches against prompt+generated
    ngram_max = 3
    # second, smaller page-size class for draft-model KV: draft pages are
    # parent blocks carved into ``draft_page_divisor`` sub-pages riding the
    # same refcounted pool. 0 disables the class (self-speculation drafts
    # no KV).
    draft_page_divisor = 0


class RaggedInferenceEngineConfig(DeepSpeedConfigModel):
    """Top-level v2 config (reference ``config_v2.py:29``)."""
    tensor_parallel = {"tp_size": 1}
    state_manager = DSStateManagerConfig()
    kv_cache = KVCacheConfig()
    # block-granular prefix caching with copy-on-write sharing
    # (ragged/prefix_cache.py). Default off: generation is bit-exact either
    # way (test-pinned) but the knob gates all hashing/refcount bookkeeping
    # so the disabled path does zero extra work per step.
    prefix_caching = False
    # draft-then-verify decode (see SpeculativeConfig). Default off: the
    # disabled path does zero extra work per step (test-pinned).
    speculative = SpeculativeConfig()
    # per-class serving SLO latency targets, keyed by class name::
    #
    #     {"interactive": {"ttft_target_s": 0.5, "tpot_target_s": 0.05},
    #      "batch": {"ttft_target_s": 5.0, "tpot_target_s": 0.5}}
    #
    # The scheduler installs these into telemetry (set_slo_classes) at
    # construction; requests tagged ``submit(..., slo_class=...)`` then feed
    # per-class attainment counters and burn-rate gauges
    # (docs/SERVING.md "SLO classes"). Empty = no per-class tracking.
    slo_classes = {}
