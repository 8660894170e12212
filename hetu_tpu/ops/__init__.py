"""Functional ops.

The reference implements ~171 CUDA/CPU kernel files dispatched through a
per-op OpInterface (SURVEY.md §2.3).  On TPU ~90% of those lower to plain
jax.numpy/lax, which XLA fuses onto the MXU/VPU; this package holds the
functional forms plus the hand-written Pallas kernels for the hot ops
(flash attention, fused norms, rotary) and the collective-based ops
(ring attention, vocab-parallel CE).
"""
from hetu_tpu.ops.activations import gelu, silu, swiglu, relu, leaky_relu, mish, softplus, hardswish, sigmoid, dropout
from hetu_tpu.ops.norms import rms_norm, layer_norm, residual_rms_norm, residual_layer_norm
from hetu_tpu.ops.rotary import (build_rope_cache, apply_rotary, apply_rotary_qk,
                                 build_yarn_rope_cache, yarn_inv_freq,
                                 yarn_mscale)
from hetu_tpu.ops.losses import (
    softmax_cross_entropy,
    softmax_cross_entropy_sparse,
    vocab_parallel_cross_entropy,
    mse_loss,
    nll_loss,
    kl_div_loss,
    binary_cross_entropy,
)
from hetu_tpu.ops.attention import attention, flash_attention
from hetu_tpu.ops import tensor
from hetu_tpu.ops.quantization import (
    quantize_int8, dequantize_int8, quantize_int4, dequantize_int4,
    quantized_matmul_int8, pack_nibbles, unpack_nibbles,
)
