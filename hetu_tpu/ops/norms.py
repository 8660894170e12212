"""Normalization ops (reference: hetu/impl/kernel/{RMSNorm,FusedLayerNorm}.cu).

Computed in float32 regardless of input dtype (the reference's fused kernels
accumulate in fp32), cast back to the input dtype at the end; XLA fuses the
whole body into one VPU loop so a Pallas kernel is only warranted when fusing
across op boundaries — which is exactly what `residual_rms_norm` /
`residual_layer_norm` do: the residual-add + norm pair the transformer
blocks emit fuses into ONE pass (ops/pallas/fused_norm) behind the
HETU_TPU_PALLAS routing, with this module's composition as the fallback.
"""
from typing import Optional

import jax
import jax.numpy as jnp


def rms_norm(x, weight, eps: float = 1e-5):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jnp.reciprocal(jnp.sqrt(var + eps))
    return (y * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jnp.reciprocal(jnp.sqrt(var + eps))
    y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dtype)


def residual_rms_norm(x, h, weight, eps: float = 1e-5,
                      use_pallas: Optional[bool] = None, layout=None):
    """Fused residual-add + RMSNorm: returns (rms_norm(x + h) * weight,
    x + h) — the pre-norm block's pair, one Pallas pass when routed
    (HETU_TPU_PALLAS auto/1/0 + the `norm` kernel gate), the exact seed
    composition otherwise.  `layout` (a DistributedStates) declares how
    x/h and both results lie over the mesh (the gain is replicated):
    under a multi-device mesh the kernel runs once per shard of it."""
    from hetu_tpu.ops import pallas as _pl
    from hetu_tpu.ops.pallas import fused_norm as _fn
    layouts = None if layout is None else (layout, layout, None)
    if use_pallas is None:
        use_pallas = _pl.resolve_route(
            "norm", _fn.check_shapes, x.shape, h.shape, weight.shape,
            layouts=layouts)
    if use_pallas:
        with jax.named_scope("pallas_residual_rmsnorm"):
            return _pl.per_shard(
                lambda x, h, w: _fn.fused_residual_rmsnorm(x, h, w, eps),
                layouts, (layout, layout))(x, h, weight)
    s = x + h
    return rms_norm(s, weight, eps), s


def residual_layer_norm(x, h, weight, bias, eps: float = 1e-5,
                        use_pallas: Optional[bool] = None, layout=None):
    """Fused residual-add + LayerNorm: returns (layer_norm(x + h), x + h).
    Same routing contract as `residual_rms_norm`."""
    from hetu_tpu.ops import pallas as _pl
    from hetu_tpu.ops.pallas import fused_norm as _fn
    operands = (x, h, weight) + (() if bias is None else (bias,))
    layouts = (None if layout is None
               else (layout, layout) + (None,) * (len(operands) - 2))
    if use_pallas is None:
        use_pallas = _pl.resolve_route(
            "norm", _fn.check_shapes, x.shape, h.shape, weight.shape,
            layouts=None if layout is None else layouts[:3])
    if use_pallas:
        with jax.named_scope("pallas_residual_layernorm"):
            return _pl.per_shard(
                lambda x, h, w, *b: _fn.fused_residual_layernorm(
                    x, h, w, b[0] if b else None, eps),
                layouts, (layout, layout))(*operands)
    s = x + h
    return layer_norm(s, weight, bias, eps), s
