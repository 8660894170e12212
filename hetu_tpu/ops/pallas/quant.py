"""Fused blockwise quantize/dequantize Pallas kernels.

Rebuild of the reference's quantization kernels (reference:
hetu/graph/ops/Quantization.h backed by bitsandbytes CUDA kernels;
EQuARX, PAPERS.md, motivates fusing the quantize that feeds every
compressed collective).  `comm/compress.quantize_blockwise` is an XLA
chain (abs -> blockmax -> div -> round -> clip -> cast) that round-trips
the flat buffer through HBM per op; this kernel does one read of the
f32 buffer and one write of the int8 payload + per-block scales.  The
quantize-for-collectives step (DP grad sync, SP compress, ZeRO refresh,
KV pages) routes here via the dispatcher in `comm/compress`.

The int payload is BIT-IDENTICAL to the jnp path and the f32 scales
agree to 1 ulp (XLA may realize /qmax as multiply-by-reciprocal in one
of the two lowerings): same absmax/qmax scale,
same round-half-to-even, same 1e-12 scale floor, int4 values on the
same [-7, 7] grid (packing to nibbles stays in `comm/compress` —
byte-shuffling is free next to the collective itself).  Stochastic
rounding keeps the XLA path (it needs a threaded rng).

Shape contract (drift-tested against `compatible`): buffer length must
divide by block_size, and block_size must be lane-aligned (% 128)."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.pallas import _interpret, fit_sublane_block

#: quantize blocks (rows) handled per grid step
_ROWS = 256


def check_shapes(n: int, block_size: int, bits: int = 8) -> int:
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")
    if block_size % 128:
        raise ValueError(f"block_size {block_size} is not lane-aligned "
                         f"(% 128); the XLA fallback handles it")
    if n % block_size:
        raise ValueError(f"buffer of {n} elements is not a multiple of "
                         f"block_size={block_size}; pad first")
    if not fit_sublane_block(n // block_size, _ROWS):
        raise ValueError(f"{n // block_size} blocks have no "
                         f"sublane-aligned (% 8) row block; the XLA "
                         f"fallback handles it")
    return n // block_size


def compatible(n: int, block_size: int, bits: int = 8) -> bool:
    try:
        check_shapes(n, block_size, bits)
        return True
    except ValueError:
        return False


def _quant_kernel(x_ref, q_ref, s_ref, *, qmax):
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qmax
    scale = jnp.maximum(scale, 1e-12)
    q_ref[...] = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, y_ref):
    y_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def quantize_blockwise_pallas(x, block_size: int, *, bits: int = 8
                              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Flat buffer -> (q int8 [n//bs, bs], scales f32 [n//bs]) in one
    fused pass (deterministic rounding only).  Raises ValueError on
    shapes outside `compatible`."""
    flat = x.reshape(-1).astype(jnp.float32)
    nb = check_shapes(flat.shape[0], block_size, bits)
    qmax = 127.0 if bits == 8 else 7.0
    rows = fit_sublane_block(nb, _ROWS)
    blk = pl.BlockSpec((rows, block_size), lambda i: (i, 0))
    s_blk = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=(nb // rows,),
        in_specs=[blk],
        out_specs=[blk, s_blk],
        out_shape=[jax.ShapeDtypeStruct((nb, block_size), jnp.int8),
                   jax.ShapeDtypeStruct((nb, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
    )(flat.reshape(nb, block_size))
    return q, s[:, 0]


def dequantize_blockwise_pallas(q, scale) -> jnp.ndarray:
    """(q int8 [nb, bs], scales f32 [nb]) -> flat f32 [nb*bs] in one
    fused pass.  Raises ValueError on shapes outside `compatible`."""
    nb, bs = q.shape
    check_shapes(nb * bs, bs)
    rows = fit_sublane_block(nb, _ROWS)
    blk = pl.BlockSpec((rows, bs), lambda i: (i, 0))
    s_blk = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    y = pl.pallas_call(
        _dequant_kernel,
        grid=(nb // rows,),
        in_specs=[blk, s_blk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((nb, bs), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
    )(q, scale.reshape(nb, 1).astype(jnp.float32))
    return y.reshape(-1)
