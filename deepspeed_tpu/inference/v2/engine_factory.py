"""v2 engine factory (mirrors reference ``inference/v2/engine_factory.py:68``
``build_hf_engine``): HF checkpoint directory in, ragged serving engine out.

Families (reference maps eight policies, :68-129): llama / llama2 / mistral /
qwen2 / qwen / internlm route to the scanned llama ragged implementation
(qkv-bias and sliding-window handled per config), mixtral to the MoE ragged
implementation, falcon / phi (phi-1/2) to the parallel block, opt to its own.
Weights come through the HF converter (``checkpoint/hf.py``) directly in the
serving dtype. phi4flash (Phi-4-mini-flash-reasoning: Mamba, window and full
differential attention, gated memory units) is served from an in-tree model
through ``build_engine``; it has no HF converter, and no prefix cache,
speculation or page export yet. mellum2 (Mellum2: window and full attention
layers over two paged groups, sparse experts in every layer) likewise, and
kanana2 (Kanana-2, a DeepSeek-V3 tree: latent attention over ONE paged group
of one leaf, sigmoid-routed experts beside a shared one, of which the tree may
hold a share), and keye_vl2 (Keye-VL-2.0's language model: learned sparse
attention, every query reading the ``topk`` cached tokens its indexer picks,
over one paged group whose page keeps the indexer's key beside K and V;
softmax-routed experts of which the tree may hold a share), and longcat_flash
(LongCat-Flash-Chat: shortcut-connected double layers, two latent attentions
with a low-rank query and two dense FFNs each, beside one expert layer whose
router's last columns are identity experts that compute nothing; one paged
group of one leaf with two planes a layer, and a counter group the expert
layers add to on the device), and kimi_linear (Kimi-Linear: three layers of
gated delta-rule linear attention, a matrix state a head in a slot group, to
one of latent attention without positions, whose pages are the one paged
group's planes; Kanana-2's expert rule; a counter group).

A family is three things, resolved here: its ragged forward, its verify
forward (or None) and its cache groups (``ragged/cache_groups.py``); two more
where its module has them: ``prepare_params(cfg, params)``, the tree as its
forward reads it, made once by ``InferenceEngineV2`` when it is built, and
``dispatch_report(cfg, real_tokens, chunk)``, what a dispatch reports of the
family beside the engine's and the cache groups' own counts.
"""

import importlib

import numpy as np

from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.utils.logging import logger

#: a family is one row: its module under ``model_implementations``, which
#: exports ``ragged_forward`` and, where the family has one,
#: ``ragged_forward_verify``, ``prepare_params`` and ``dispatch_report``
_IMPLEMENTATION = {"llama": "llama", "mistral": "llama", "qwen2": "llama",
                   "qwen": "llama", "internlm": "llama",  # llama trees (hf.py)
                   "mixtral": "mixtral", "falcon": "parallel_block",
                   "phi": "parallel_block", "opt": "opt",
                   "phi4flash": "phi4flash", "mellum2": "mellum2",
                   "kanana2": "kanana2", "keye_vl2": "keye_vl2",
                   "longcat_flash": "longcat_flash",
                   "kimi_linear": "kimi_linear"}

#: families ``build_engine`` serves from an in-tree model and tree
SERVED_FAMILIES = tuple(_IMPLEMENTATION)
#: families ``build_hf_engine`` loads from a checkpoint directory
SUPPORTED_FAMILIES = tuple(
    f for f in SERVED_FAMILIES
    if f not in ("phi4flash", "mellum2", "kanana2", "keye_vl2",
                 "longcat_flash", "kimi_linear"))  # no HF converter

#: the one place a config class names its family; any other is a llama tree
_FAMILY_OF_CONFIG = {"MixtralConfig": "mixtral",
                     "ParallelBlockConfig": "falcon",
                     "OPTConfig": "opt",
                     "Phi4FlashConfig": "phi4flash",
                     "Mellum2Config": "mellum2",
                     "Kanana2Config": "kanana2",
                     "KeyeVL2Config": "keye_vl2",
                     "LongcatFlashConfig": "longcat_flash",
                     "KimiLinearConfig": "kimi_linear"}


def _implementation(model, family):
    family = family or _FAMILY_OF_CONFIG.get(type(model.config).__name__,
                                             "llama")
    name = _IMPLEMENTATION[family]
    if name == "llama" and not getattr(model.config, "scan_layers", True):
        raise ValueError("ragged llama engine requires scan_layers=True params")
    return importlib.import_module(
        f"deepspeed_tpu.inference.v2.model_implementations.{name}")


def build_hf_engine(path, engine_config=None, dtype=None):
    """Build a ragged engine from a HuggingFace checkpoint dir.

    Args:
        path: directory with config.json + safetensors/bin weights.
        engine_config: ``RaggedInferenceEngineConfig`` or dict.
        dtype: serving dtype (default bfloat16).
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    import ml_dtypes

    from deepspeed_tpu.checkpoint import hf as hf_interop

    mt = hf_interop.detect_model_type(path)
    if mt not in SUPPORTED_FAMILIES:
        raise ValueError(f"ragged engine supports {SUPPORTED_FAMILIES}, "
                         f"got model_type {mt!r}")
    dtype = np.dtype(dtype) if dtype is not None else np.dtype(ml_dtypes.bfloat16)
    model, params = hf_interop.load_pretrained(path, dtype=dtype)
    # thread the serving dtype through to COMPUTE, not just storage: the
    # ragged forwards cast with cfg.dtype at every use site
    jdt = {np.dtype(np.float32): jnp.float32,
           np.dtype(np.float16): jnp.float16}.get(dtype, jnp.bfloat16)
    model = type(model)(dataclasses.replace(model.config, dtype=jdt))
    logger.info(f"build_hf_engine: {mt} from {path} "
                f"({sum(x.size for x in jax.tree.leaves(params))/1e6:.1f}M params, "
                f"dtype {jdt.__name__})")
    return build_engine(model, params, engine_config, family=mt)


def resolve_forward_fn(model, family=None):
    """The ragged implementation for a model family (the reference's policy
    map, ``engine_factory.py:68-129``)."""
    return _implementation(model, family).ragged_forward


def resolve_verify_fn(model, family=None):
    """The k-token verify forward for a model family, or ``None`` when the
    family has no speculative-verify implementation yet (the engine refuses
    speculation rather than silently falling back to a different program)."""
    return getattr(_implementation(model, family), "ragged_forward_verify",
                   None)


def resolve_report_fn(model, family=None):
    """The family's ``dispatch_report(cfg, real_tokens, chunk)`` (the
    dispatch's real tokens, and the token slots a row of it): what a dispatch
    reports beyond what the engine and the cache groups say of it, as the two
    mappings ``moe_layer.dispatch_report`` describes; ``None`` for a family
    that exports none (no reporter, not a reporter of zeros)."""
    return getattr(_implementation(model, family), "dispatch_report", None)


def resolve_prepare_fn(model, family=None):
    """The family's ``prepare_params(cfg, params)``: the tree as its forward
    reads it, or ``None`` for a family that serves the tree as trained.
    ``InferenceEngineV2`` applies it, once, however it was built; nothing
    else does."""
    return getattr(_implementation(model, family), "prepare_params", None)


def resolve_cache_groups(model):
    """What the model keeps per sequence between dispatches: its own
    ``cache_groups(config)`` where the stack is not homogeneous, else the one
    paged group of K and V in every layer."""
    from deepspeed_tpu.inference.v2.ragged.cache_groups import homogeneous
    declared = getattr(model, "cache_groups", None)
    return declared(model.config) if declared else homogeneous(model.config)


def build_engine(model, params, engine_config=None, family=None):
    """Build a ragged engine from an in-tree model + param tree (the tree
    as the model trains it: the engine makes it ready for the family)."""
    return InferenceEngineV2(model, params, engine_config,
                             prepare_fn=resolve_prepare_fn(model, family),
                             forward_fn=resolve_forward_fn(model, family),
                             verify_fn=resolve_verify_fn(model, family),
                             cache_groups=resolve_cache_groups(model),
                             report_fn=resolve_report_fn(model, family))
