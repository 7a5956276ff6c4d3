"""ZeRO-Inference weight-only quantization.

Reference ``deepspeed/inference/quantization/`` (``QuantizedParameter``,
``utils.py``): model weights are stored int8/int4 groupwise-quantized (plus
fp scales) and dequantized on the fly in forward, cutting weight memory 2-4x
so much larger models fit per device — the "20x cheaper inference" README
claim combines this with KV/weight offload.

TPU mapping: ``QuantizedParameter`` is a registered pytree whose children are
the int8/packed-int4 values + fp32 group scales and whose aux data (shape,
bits, group size) is static — so a quantized parameter tree flows through
``jit`` unchanged, weights stay int8 in HBM, and the in-trace dequant fuses
into the consuming matmul.
"""

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.quantizer import (dequantize, dequantize_lastdim,
                                         quantize, quantize_lastdim)


@jax.tree_util.register_pytree_node_class
class QuantizedParameter:
    """A single quantized weight (reference ``QuantizedParameter``)."""

    def __init__(self, q, scale, shape, num_bits, group_size):
        self.q = q
        self.scale = scale
        self.shape = tuple(int(s) for s in shape)
        self.num_bits = int(num_bits)
        self.group_size = int(group_size)

    def tree_flatten(self):
        return (self.q, self.scale), (self.shape, self.num_bits, self.group_size)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale = children
        shape, num_bits, group_size = aux
        return cls(q, scale, shape, num_bits, group_size)

    @classmethod
    def from_array(cls, w, num_bits=8, group_size=256):
        if num_bits in (6, 12):
            # FP6-LLM-style float quantization (ops/fp_quantizer.py)
            from deepspeed_tpu.ops.fp_quantizer import quantize_fp
            q, s = quantize_fp(w, bits=num_bits, group_size=group_size)
        elif num_bits == 8:
            q, s = quantize_lastdim(w, group_size=group_size)
        else:
            q, s = quantize(w, num_bits=num_bits, group_size=group_size)
        return cls(q, s, w.shape, num_bits, group_size)

    def dequantized(self, dtype=jnp.bfloat16):
        if self.num_bits in (6, 12):
            from deepspeed_tpu.ops.fp_quantizer import dequantize_fp
            return dequantize_fp(self.q, self.scale, self.shape,
                                 bits=self.num_bits,
                                 group_size=self.group_size, dtype=dtype)
        if self.num_bits == 8:
            return dequantize_lastdim(self.q, self.scale,
                                      group_size=self.group_size, dtype=dtype)
        return dequantize(self.q, self.scale, self.shape,
                          num_bits=self.num_bits, group_size=self.group_size,
                          dtype=dtype)

    def matmul(self, x, out_dtype=None):
        """``x @ dequant(self)`` (reference cuda_linear / mixed_gemm slot):
        the Pallas fused dequant-GEMM kernel (HBM reads stay int8-sized) for
        a 2-D weight when Pallas is on and the shapes tile, else XLA
        dequantize-then-matmul.

        Integration status: this is the serving-layer API for the fused
        path; the v1 engine's dense-dequant proxy remains the default until
        the kernel is validated on hardware (scripts/tpu_kernel_smoke.py)."""
        from deepspeed_tpu.ops.pallas import quantized_matmul as qm
        from deepspeed_tpu.ops.registry import pallas_interpret, takes_kernel
        M = int(np.prod(x.shape[:-1]))
        if takes_kernel(
                "quantized_matmul",
                len(self.shape) == 2 and qm.is_supported(
                    M, *self.shape, self.group_size, self.num_bits),
                f"[{M}, K] @ {tuple(self.shape)} (group {self.group_size}, "
                f"{self.num_bits} bits) not kernel-tileable"):
            K, N = self.shape
            out = qm.quantized_matmul(x.reshape(M, K), self.q, self.scale,
                                      self.group_size, out_dtype=out_dtype,
                                      interpret=pallas_interpret())
            return out.reshape(x.shape[:-1] + (N,))
        return x @ self.dequantized(out_dtype or x.dtype)

    @property
    def nbytes(self):
        return int(np.asarray(self.q).nbytes + np.asarray(self.scale).nbytes)


def _is_qleaf(x):
    return isinstance(x, QuantizedParameter)


def quantize_param_tree(params, num_bits=8, group_size=256, min_size=0,
                        exclude=("embed", "norm", "bias", "scale")):
    """Quantize every matrix leaf of a parameter tree (reference
    ``_init_group_wise_weight_quantization``). Leaves matching ``exclude``
    patterns (embeddings/norms stay fp by default), vectors, and leaves below
    ``min_size`` stay untouched."""
    def q(path, leaf):
        key = jax.tree_util.keystr(path).lower()
        if (not hasattr(leaf, "ndim")) or leaf.ndim < 2 or \
                leaf.size < min_size or any(e in key for e in exclude):
            return leaf
        return QuantizedParameter.from_array(jnp.asarray(leaf), num_bits,
                                             group_size)

    return jax.tree_util.tree_map_with_path(q, params)


def dequantize_param_tree(params, dtype=jnp.bfloat16):
    """In-trace inverse — jit-safe, fused into consumers by XLA."""
    return jax.tree.map(
        lambda l: l.dequantized(dtype) if _is_qleaf(l) else l,
        params, is_leaf=_is_qleaf)


def quantized_nbytes(params):
    """Total weight bytes of a (possibly quantized) tree — the memory win."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=_is_qleaf):
        if _is_qleaf(leaf):
            total += leaf.nbytes
        else:
            total += int(np.asarray(leaf).nbytes)
    return total
