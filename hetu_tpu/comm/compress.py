"""Blockwise int8/int4 quantize/dequantize primitives for collectives.

The payload format is the one `comm/wire.py` prices: flat f32 buffers cut
into blocks of `block_size`, each block carried as int8 values (or int4
values packed two per byte, `pack_int4`) plus one f32 absmax scale.
Unlike `ops/quantization.py` (weight-only storage quantization, arbitrary
nd-shapes), these primitives are collective-facing: they keep the block
axis outermost so chunks of whole blocks can ride all-to-all /
all-gather rows, and they offer

  * stochastic rounding — unbiased E[deq(q)] = x, the standard variance-
    for-bias trade for gradient compression (EQuARX, PAPERS.md),
  * error feedback — `ef_quantize` folds the previous round's
    quantization residual into the buffer before quantizing and returns
    the new residual, the SGD-with-memory correction that restores
    convergence when the same buffer is compressed every step, and
  * int4 (`bits=4`): symmetric [-7, 7] grid, absmax/7 scale, same block
    layout.  The wire carries two values per byte (`pack_int4` /
    `unpack_int4` — offset-binary nibbles, value+8 in [1, 15], high
    nibble = even index); block_size must be even.

All functions are jit-safe and shard_map-safe (elementwise + block
reductions only, no collectives here).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from hetu_tpu.comm.wire import DEFAULT_BLOCK


def _qmax(bits: int) -> float:
    if bits == 8:
        return 127.0
    if bits == 4:
        return 7.0
    raise ValueError(f"bits must be 8 or 4, got {bits}")


def quantize_blockwise(x, block_size: int = DEFAULT_BLOCK, *,
                       stochastic: bool = False,
                       rng: Optional[jax.Array] = None,
                       bits: int = 8
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Flat f32 [n] (n % block_size == 0) -> (q int8 [n//bs, bs],
    scales f32 [n//bs]).  Deterministic round-to-nearest by default;
    stochastic=True rounds up with probability equal to the fractional
    part (needs `rng`), making the dequantized value unbiased.
    bits=4 quantizes to the [-7, 7] grid (still carried as int8 here;
    `pack_int4` packs two values per byte for the wire)."""
    qmax = _qmax(bits)
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    if n % block_size:
        raise ValueError(f"buffer of {n} elements is not a multiple of "
                         f"block_size={block_size}; pad first "
                         f"(comm.bucketer does)")
    if not stochastic:
        # fused Pallas quantize (ops/pallas/quant — one pass instead of
        # the abs/max/div/round/clip/cast chain) when the HETU_TPU_PALLAS
        # routing and the kernel's shape gate allow; int payload
        # bit-identical to the jnp path below, scales to 1 ulp (tested),
        # so every consumer (grad sync, SP compress, ZeRO refresh, KV
        # pages) inherits it transparently
        from hetu_tpu.ops.pallas import resolve_route
        from hetu_tpu.ops.pallas import quant as _pq
        if resolve_route("quant", _pq.check_shapes, n, block_size, bits):
            with jax.named_scope("pallas_quantize"):
                return _pq.quantize_blockwise_pallas(flat, block_size,
                                                     bits=bits)
    blocks = flat.reshape(-1, block_size)
    scale = jnp.max(jnp.abs(blocks), axis=1) / qmax
    scale = jnp.maximum(scale, 1e-12)
    y = blocks / scale[:, None]
    if stochastic:
        if rng is None:
            raise ValueError("stochastic rounding needs an rng key")
        floor = jnp.floor(y)
        frac = y - floor
        up = jax.random.uniform(rng, y.shape) < frac
        y = floor + up.astype(jnp.float32)
    else:
        y = jnp.round(y)
    q = jnp.clip(y, -qmax, qmax).astype(jnp.int8)
    return q, scale


def dequantize_blockwise(q, scale) -> jnp.ndarray:
    """(q int8 [nb, bs], scales f32 [nb]) -> flat f32 [nb*bs]."""
    from hetu_tpu.ops.pallas import resolve_route
    from hetu_tpu.ops.pallas import quant as _pq
    if resolve_route("quant", _pq.check_shapes, q.shape[0] * q.shape[1],
                     q.shape[1]):
        with jax.named_scope("pallas_dequantize"):
            return _pq.dequantize_blockwise_pallas(q, scale)
    return (q.astype(jnp.float32) * scale[:, None]).reshape(-1)


def pack_int4(q) -> jnp.ndarray:
    """int8 [nb, bs] with values in [-8, 7] -> uint8 [nb, bs//2]: two
    offset-binary nibbles per byte (value+8; even index rides the high
    nibble).  The wire format of the int4 modes.  Byte-shuffling is
    delegated to `ops.quantization.pack_nibbles` — ONE packer shared
    with the weight-storage format, so the two layouts are transposes
    of a single implementation instead of cousins that can drift."""
    from hetu_tpu.ops.quantization import pack_nibbles
    u = (q.astype(jnp.int32) + 8).astype(jnp.uint8)
    return pack_nibbles(u, even_high=True)


def unpack_int4(p) -> jnp.ndarray:
    """uint8 [nb, bs//2] -> int8 [nb, bs] (inverse of `pack_int4`)."""
    from hetu_tpu.ops.quantization import unpack_nibbles
    return unpack_nibbles(p, even_high=True).astype(jnp.int8) - 8


def ef_quantize(x, residual, block_size: int = DEFAULT_BLOCK, *,
                stochastic: bool = False,
                rng: Optional[jax.Array] = None,
                bits: int = 8
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Error-feedback quantize: compress c = x + residual and return
    (q, scales, new_residual = c - dequantize(q)).  With residual=None
    behaves like plain quantize (new_residual still returned, for a
    uniform calling convention)."""
    flat = x.reshape(-1).astype(jnp.float32)
    c = flat if residual is None else flat + residual.reshape(-1)
    q, scale = quantize_blockwise(c, block_size, stochastic=stochastic,
                                  rng=rng, bits=bits)
    new_residual = c - dequantize_blockwise(q, scale)
    return q, scale, new_residual
