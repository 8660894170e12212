"""Activations (reference: hetu/graph/ops/{Gelu,Silu,SwiGLU,...}.cc).

Plain jax.numpy — XLA fuses these into adjacent matmuls on TPU, which is why
the reference's fused CUDA kernels (FusedUnary.cu, SwiGLU.cu) need no Pallas
counterpart for the epilogue case.
"""
import jax
import jax.numpy as jnp


def relu(x):
    return jnp.maximum(x, 0)


def leaky_relu(x, negative_slope=0.01):
    return jnp.where(x >= 0, x, negative_slope * x)


def gelu(x, approximate=True):
    return jax.nn.gelu(x, approximate=approximate)


def silu(x):
    return x * jax.nn.sigmoid(x)


def sigmoid(x):
    return jax.nn.sigmoid(x)


def swiglu(gate, up, use_pallas=None, layout=None):
    """SwiGLU combine (reference: ops/SwiGLU.cc): silu(gate) * up.

    Routes to the fused Pallas kernel (ops/pallas/swiglu — one pass,
    custom-vjp backward) under HETU_TPU_PALLAS; the jnp composition is
    the exact fallback.  `layout` (a DistributedStates) declares how
    gate/up/result lie over the mesh: under a multi-device mesh the
    kernel runs once per shard of it (ops/pallas.per_shard)."""
    from hetu_tpu.ops import pallas as _pl
    from hetu_tpu.ops.pallas import swiglu as _sw
    layouts = None if layout is None else (layout, layout)
    if use_pallas is None:
        use_pallas = _pl.resolve_route("swiglu", _sw.check_shapes,
                                       gate.shape, up.shape, layouts=layouts)
    if use_pallas:
        with jax.named_scope("pallas_swiglu"):
            return _pl.per_shard(_sw.fused_swiglu, layouts, layout)(gate, up)
    return silu(gate) * up


def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


def softplus(x):
    return jax.nn.softplus(x)


def hardswish(x):
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


def dropout(x, rate: float, rng=None, deterministic: bool = True):
    """Functional dropout (reference: hetu/graph/ops/Dropout.cc)."""
    if deterministic or rate == 0.0 or rng is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, 0).astype(x.dtype)
