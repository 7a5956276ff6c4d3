"""Op-builder slots for the inference kernel sets (reference
``op_builder/{transformer_inference,inference_core_ops,
inference_cutlass_builder,ragged_ops,ragged_utils,random_ltd}.py``):
one registry row per reference builder so ``ds_tpu_report`` shows the same
compatibility matrix surface. Each maps to the TPU implementation that
fills the reference kernels' role."""

from deepspeed_tpu.ops.registry import OpBuilder, register_op_builder


@register_op_builder
class RaggedOpsBuilder(OpBuilder):
    """Paged blocked-flash decode + ragged batch machinery
    (reference ragged_ops: blocked_flash, kv rotary copy, logits_gather)."""
    NAME = "ragged_ops"

    def reference_impl(self):
        from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
            _paged_attention_dense)
        return _paged_attention_dense

    def pallas_impl(self):
        from deepspeed_tpu.ops.pallas.paged_attention import paged_mha
        return paged_mha


@register_op_builder
class RaggedUtilsBuilder(OpBuilder):
    """Ragged batch host buffers (reference ragged_utils fast_host_buffer):
    numpy-padded static layouts in RaggedBatchWrapper."""
    NAME = "ragged_utils"

    def reference_impl(self):
        from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (
            RaggedBatchWrapper)
        return RaggedBatchWrapper


@register_op_builder
class InferenceCoreOpsBuilder(OpBuilder):
    """Core inference kernels (reference inference_core_ops: layer/rms norm,
    gated activations, cuda_linear FP6/int8 GEMM). The fused dequant-GEMM is
    the Pallas member; norms/activations are XLA-fused."""
    NAME = "inference_core_ops"

    def reference_impl(self):
        from deepspeed_tpu.inference.quantization.quantization import (
            QuantizedParameter)
        return QuantizedParameter.dequantized

    def pallas_impl(self):
        from deepspeed_tpu.ops.pallas.quantized_matmul import (
            quantized_matmul)
        return quantized_matmul


@register_op_builder
class InferenceCutlassBuilder(OpBuilder):
    """Grouped expert GEMMs (reference inference_cutlass_builder moe_gemm /
    mixed_gemm): the megablox ragged grouped GEMM."""
    NAME = "inference_cutlass_builder"

    def reference_impl(self):
        from deepspeed_tpu.inference.v2.model_implementations.moe_layer import (
            moe_ffn)
        return moe_ffn

    def pallas_impl(self):
        from deepspeed_tpu.ops.pallas.grouped_gemm import moe_ffn_gmm
        return moe_ffn_gmm


@register_op_builder
class TransformerInferenceBuilder(OpBuilder):
    """v1 fused transformer inference ops (reference transformer_inference):
    the KV-cached decode path of every model family + the flash kernel."""
    NAME = "transformer_inference"

    def reference_impl(self):
        from deepspeed_tpu.inference.generation import generate
        return generate

    def pallas_impl(self):
        from deepspeed_tpu.ops.pallas.flash_attention import flash_mha
        return flash_mha


@register_op_builder
class RandomLTDBuilder(OpBuilder):
    """Token sort/gather for random layerwise token dropping (reference
    random_ltd csrc): jnp argsort/take — trivial in XLA."""
    NAME = "random_ltd"

    def reference_impl(self):
        from deepspeed_tpu.runtime.data_pipeline import random_ltd
        return random_ltd
