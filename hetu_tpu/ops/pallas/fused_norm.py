"""Fused residual-add + RMSNorm / LayerNorm Pallas kernels.

Rebuild of the reference's fused norm kernels (reference:
hetu/impl/kernel/RMSNorm.cu, FusedLayerNorm.cu — residual-in, norm-out in
one pass with fp32 accumulators).  The XLA composition is a multi-pass
chain (add -> upcast -> square -> mean -> scale -> weight-mul -> downcast),
each pass a round trip of the [tokens, hidden] activation through HBM;
this kernel reads x and h once and writes the normed output AND the new
residual stream once (`ops/pallas/traffic.py` prices the two analytically
— the bench `detail.kernels` record).

Forward returns BOTH outputs because the pre-norm transformer needs both:

    s = x + h          # the residual stream the block returns
    y = norm(s) * w    # what feeds the next matmul

The backward is a custom_vjp running a second fused kernel: it receives
cotangents for y AND s (the residual stream is consumed downstream too),
recomputes the row statistics from the saved s (cheaper than saving
inv/mean: one fused read instead of extra HBM residents), and emits
dx (= dh) plus dw/db folded onto 8 sublane rows that are summed outside.

Shape contract (`compatible` mirrors the entry validation EXACTLY — the
drift test pins them): hidden (the normed axis) must be lane-aligned
(% 128) and the flattened token count must tile into sublanes (% 8).
Rows per grid step are sized to keep each VMEM resident near ~0.5 MB.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.pallas import _interpret, fit_sublane_block

#: per-buffer VMEM budget (bytes, f32) used to pick the row-block size
_VMEM_ROW_BUDGET = 512 * 1024


def check_shapes(x_shape, h_shape, w_shape) -> Tuple[int, int]:
    """Entry validation — raises ValueError exactly when `compatible`
    says False (the drift-test contract).  Returns (tokens, hidden)."""
    if tuple(x_shape) != tuple(h_shape):
        raise ValueError(f"residual/branch shapes differ: {x_shape} vs "
                         f"{h_shape}")
    if len(x_shape) < 2:
        raise ValueError(f"need at least [tokens, hidden], got {x_shape}")
    hidden = x_shape[-1]
    if tuple(w_shape) != (hidden,):
        raise ValueError(f"weight shape {w_shape} != ({hidden},)")
    tokens = 1
    for d in x_shape[:-1]:
        tokens *= d
    if hidden % 128:
        raise ValueError(f"hidden {hidden} is not lane-aligned (% 128); "
                         f"the XLA fallback handles this shape")
    if tokens % 8:
        raise ValueError(f"token count {tokens} does not tile into "
                         f"sublanes (% 8); the XLA fallback handles it")
    return tokens, hidden


def compatible(x_shape, h_shape=None, w_shape=None) -> bool:
    """The dispatcher's shape gate — implemented AS the entry validation
    so gate and kernel can never drift."""
    h_shape = x_shape if h_shape is None else h_shape
    w_shape = (x_shape[-1],) if w_shape is None else w_shape
    try:
        check_shapes(x_shape, h_shape, w_shape)
        return True
    except ValueError:
        return False


def _fit_rows(tokens: int, hidden: int) -> int:
    """Largest divisor of `tokens` that is a multiple of 8 and keeps one
    f32 [rows, hidden] buffer near the VMEM budget (never 0: the gate
    admits only token counts that divide by 8)."""
    return fit_sublane_block(
        tokens, max(8, _VMEM_ROW_BUDGET // max(hidden * 4, 1)))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, h_ref, w_ref, b_ref, y_ref, s_ref, *, eps, kind,
                has_bias):
    x = x_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    s = x + h
    if kind == "rms":
        var = jnp.mean(s * s, axis=-1, keepdims=True)
        y = s * jax.lax.rsqrt(var + eps)
    else:
        mu = jnp.mean(s, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(s - mu), axis=-1, keepdims=True)
        y = (s - mu) * jax.lax.rsqrt(var + eps)
    y = y * w_ref[...].astype(jnp.float32)
    if has_bias:
        y = y + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    s_ref[...] = s.astype(s_ref.dtype)


def _bwd_kernel(s_ref, w_ref, dy_ref, dr_ref, dx_ref, dw_ref, db_ref, *,
                eps, kind):
    s = s_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    if kind == "rms":
        inv = jax.lax.rsqrt(jnp.mean(s * s, axis=-1, keepdims=True) + eps)
        xhat = s * inv
        g = dy * w
        ds = inv * (g - xhat * jnp.mean(g * xhat, axis=-1, keepdims=True))
    else:
        mu = jnp.mean(s, axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(
            jnp.mean(jnp.square(s - mu), axis=-1, keepdims=True) + eps)
        xhat = (s - mu) * inv
        g = dy * w
        ds = inv * (g - jnp.mean(g, axis=-1, keepdims=True)
                    - xhat * jnp.mean(g * xhat, axis=-1, keepdims=True))
    ds = ds + dr_ref[...].astype(jnp.float32)
    dx_ref[...] = ds.astype(dx_ref.dtype)

    # dw/db accumulate across the (sequential) grid in ONE resident
    # (8, hidden) block: the TPU lowering refuses a (1, hidden) block on
    # an (n, hidden) array, and folding rows onto the 8 sublanes is pure
    # VPU adds — the 8 -> 1 reduce happens once, outside the kernel.
    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    rows, hidden = dy.shape
    dw_ref[...] += (dy * xhat).reshape(rows // 8, 8, hidden).sum(axis=0)
    # accumulated even for the bias-free RMS variant (discarded outside):
    # an output block a kernel MIGHT not write is undefined on TPU
    db_ref[...] += dy.reshape(rows // 8, 8, hidden).sum(axis=0)


def _call_fwd(x2, h2, w2, b2, *, eps, kind, has_bias, rows, hidden):
    n = x2.shape[0] // rows
    kern = functools.partial(_fwd_kernel, eps=eps, kind=kind,
                             has_bias=has_bias)
    row_spec = pl.BlockSpec((rows, hidden), lambda i: (i, 0))
    w_spec = pl.BlockSpec((1, hidden), lambda i: (0, 0))
    return pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[row_spec, row_spec, w_spec, w_spec],
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, x2.dtype),
                   jax.ShapeDtypeStruct(x2.shape, x2.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
    )(x2, h2, w2, b2)


def _call_bwd(s2, w2, dy2, dr2, *, eps, kind, rows, hidden):
    n = s2.shape[0] // rows
    kern = functools.partial(_bwd_kernel, eps=eps, kind=kind)
    row_spec = pl.BlockSpec((rows, hidden), lambda i: (i, 0))
    w_spec = pl.BlockSpec((1, hidden), lambda i: (0, 0))
    part_spec = pl.BlockSpec((8, hidden), lambda i: (0, 0))
    dx, dw_parts, db_parts = pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[row_spec, w_spec, row_spec, row_spec],
        out_specs=[row_spec, part_spec, part_spec],
        out_shape=[jax.ShapeDtypeStruct(s2.shape, s2.dtype),
                   jax.ShapeDtypeStruct((8, hidden), jnp.float32),
                   jax.ShapeDtypeStruct((8, hidden), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(s2, w2, dy2, dr2)
    return dx, dw_parts.sum(axis=0), db_parts.sum(axis=0)


# ---------------------------------------------------------------------------
# public API (custom VJP)
# ---------------------------------------------------------------------------

def _fused(x, h, weight, bias, *, eps, kind):
    shape = x.shape
    hidden = shape[-1]
    has_bias = bias is not None
    tokens, hidden = check_shapes(shape, h.shape, weight.shape)
    rows = _fit_rows(tokens, hidden)
    x2 = x.reshape(tokens, hidden)
    h2 = h.reshape(tokens, hidden)
    w2 = weight.reshape(1, hidden)
    b2 = (bias.reshape(1, hidden) if has_bias
          else jnp.zeros((1, hidden), weight.dtype))
    y2, s2 = _call_fwd(x2, h2, w2, b2, eps=eps, kind=kind,
                       has_bias=has_bias, rows=rows, hidden=hidden)
    return y2.reshape(shape), s2.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused_vjp(x, h, weight, bias, eps, kind, has_bias):
    return _fused(x, h, weight, bias, eps=eps, kind=kind)


def _fused_fwd(x, h, weight, bias, eps, kind, has_bias):
    y, s = _fused(x, h, weight, bias, eps=eps, kind=kind)
    return (y, s), (s, weight)


def _fused_bwd(eps, kind, has_bias, res, cts):
    s, weight = res
    dy, dr = cts
    shape = s.shape
    hidden = shape[-1]
    tokens = s.size // hidden
    rows = _fit_rows(tokens, hidden)
    dx2, dw, db = _call_bwd(
        s.reshape(tokens, hidden), weight.reshape(1, hidden),
        dy.reshape(tokens, hidden), dr.reshape(tokens, hidden),
        eps=eps, kind=kind, rows=rows, hidden=hidden)
    dx = dx2.reshape(shape)
    # dx and dh are the SAME cotangent: s = x + h
    return (dx, dx, dw.astype(weight.dtype),
            db.astype(weight.dtype) if has_bias else None)


_fused_vjp.defvjp(_fused_fwd, _fused_bwd)


def fused_residual_rmsnorm(x, h, weight, eps: float = 1e-5):
    """One fused pass: s = x + h; y = rms_norm(s) * weight.  Returns
    (y, s).  Raises ValueError on shapes outside the gate (`compatible`)
    — dispatchers fall back to the XLA composition instead."""
    return _fused_vjp(x, h, weight, None, eps, "rms", False)


def fused_residual_layernorm(x, h, weight, bias, eps: float = 1e-5):
    """One fused pass: s = x + h; y = layer_norm(s) * weight + bias.
    Returns (y, s).  `bias` may be None (scale-only LayerNorm)."""
    return _fused_vjp(x, h, weight, bias, eps, "ln", bias is not None)
