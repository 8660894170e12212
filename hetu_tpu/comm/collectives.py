"""Quantized collectives for shard_map manual regions (EQuARX-style).

`comm/grad_sync.py` compresses ONE hand-built path (the DP grad sync).
This module makes quantization a property of the COLLECTIVE instead:
drop-in `all_gather_q` / `reduce_scatter_q` / `all_to_all_q` /
`all_reduce_q` that move blockwise-int8 (or packed-int4) payloads plus
f32 block scales over the wire and dequantize on arrival, usable
anywhere a `lax` collective runs inside a `shard_map` manual region —
the SP activation gathers/scatters in `dstates.convert`, the hetero-TP
pipeline's sequence-parallel edges (`parallel/hetero_pp.py`), and any
future explicit path.

Differentiability: each collective is a `jax.custom_vjp` whose backward
is the TRANSPOSE collective, also quantized — an all-gather's cotangent
rides a quantized reduce-scatter and vice versa (straight-through
through the quantizer, the standard treatment: round() has zero gradient
almost everywhere, so differentiating through the quantize would kill
training).  Forward and backward therefore both get the byte reduction.

Fallbacks keep semantics exact where quantization is wrong or not worth
it: mode "none", non-float dtypes (token ids, segment ids, MoE indices)
and buffers smaller than one quantization block take the plain `lax`
path — bit-identical to not using this module at all.

Flag: `HETU_TPU_SP_COMPRESS = none | int8 | int4` routes the
`dstates.convert` + hetero-PP SP call sites; "none" (default) is
HLO-byte-identical to an unset environment.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from hetu_tpu.comm.compress import (dequantize_blockwise, pack_int4,
                                    quantize_blockwise, unpack_int4)
from hetu_tpu.comm.wire import DEFAULT_BLOCK, mode_bits

#: HETU_TPU_SP_COMPRESS values — activation compression is stateless, so
#: there are no "-ef" variants here (EF memory belongs to per-step
#: gradient state, not to per-call activation transport)
ACT_MODES = ("none", "int8", "int4")


def sp_mode() -> str:
    """The HETU_TPU_SP_COMPRESS flag value."""
    from hetu_tpu.utils import flags
    return flags.str_flag("HETU_TPU_SP_COMPRESS")


def eligible(x, mode: str, block_size: int = DEFAULT_BLOCK) -> bool:
    """Quantize only when it helps: compressing mode, a float payload,
    and at least one quantization block of elements (smaller buffers
    would PAY bytes: the padded block + scale exceeds the raw payload)."""
    return (mode not in (None, "none")
            and jnp.issubdtype(x.dtype, jnp.floating)
            and x.size >= block_size)


# ---------------------------------------------------------------------------
# flat quantize/dequantize helpers (padding + int4 packing)
# ---------------------------------------------------------------------------

def axis_size(axis_name: str) -> int:
    """Static size of a bound mesh axis."""
    return int(lax.axis_size(axis_name))


def _group_size(axis_name: str, groups) -> int:
    if groups:
        return len(groups[0])
    return axis_size(axis_name)


def _q_flat(flat, block: int, bits: int):
    """f32 [n] -> (wire payload [nb, bs or bs//2], scales [nb]); pads to
    a block multiple (the pad quantizes to zero and is sliced off on
    arrival)."""
    n = flat.shape[0]
    pad = (-n) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    q, s = quantize_blockwise(flat, block, bits=bits)
    if bits == 4:
        q = pack_int4(q)
    return q, s


def _dq_flat(q, s, n: int, bits: int):
    if bits == 4:
        q = unpack_int4(q)
    flat = dequantize_blockwise(q, s)
    if flat.shape[0] != n:
        flat = lax.slice(flat, (0,), (n,))
    return flat


def _q_rows(rows, block: int, bits: int):
    """[r, m] f32 rows -> ([r, nb, bs or bs//2], [r, nb]) with column
    padding to a block multiple."""
    m = rows.shape[1]
    pad = (-m) % block
    if pad:
        rows = jnp.concatenate(
            [rows, jnp.zeros((rows.shape[0], pad), jnp.float32)], axis=1)
    q, s = jax.vmap(lambda r: quantize_blockwise(r, block, bits=bits))(rows)
    if bits == 4:
        q = pack_int4(q)
    return q, s


def _dq_rows(q, s, m: int, bits: int):
    """Inverse of `_q_rows`: -> [r, m] f32."""
    return jax.vmap(lambda qq, ss: _dq_flat(qq, ss, m, bits))(q, s)


# ---------------------------------------------------------------------------
# all-gather
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _all_gather_q(x, axis_name, axis, tiled, mode, block, groups):
    bits = mode_bits(mode)
    npart = _group_size(axis_name, groups)
    q, s = _q_flat(x.reshape(-1).astype(jnp.float32), block, bits)
    qg = lax.all_gather(q, axis_name, axis=0, axis_index_groups=groups)
    sg = lax.all_gather(s, axis_name, axis=0, axis_index_groups=groups)
    parts = jax.vmap(lambda qq, ss: _dq_flat(qq, ss, x.size, bits))(qg, sg)
    out = jnp.moveaxis(parts.reshape((npart,) + x.shape), 0, axis)
    if tiled:
        shape = list(x.shape)
        shape[axis] *= npart
        out = out.reshape(shape)
    return out.astype(x.dtype)


def _all_gather_q_fwd(x, axis_name, axis, tiled, mode, block, groups):
    return _all_gather_q(x, axis_name, axis, tiled, mode, block, groups), None


def _all_gather_q_bwd(axis_name, axis, tiled, mode, block, groups, _, ct):
    # transpose of a (tiled) all-gather: reduce-scatter of the cotangent
    dx = _reduce_scatter_q(ct, axis_name, axis, True, mode, block, groups)
    if not tiled:
        dx = jnp.squeeze(dx, axis)
    return (dx,)


_all_gather_q.defvjp(_all_gather_q_fwd, _all_gather_q_bwd)


def all_gather_q(x, axis_name: str, *, axis: int = 0, tiled: bool = False,
                 mode: str = "int8", block_size: int = DEFAULT_BLOCK,
                 axis_index_groups=None):
    """Quantized `lax.all_gather` (same axis/tiled semantics).  Exact
    fallback when `eligible` says quantizing would not pay."""
    groups = _norm_groups(axis_index_groups)
    if not eligible(x, mode, block_size):
        return lax.all_gather(x, axis_name, axis=axis, tiled=tiled,
                              axis_index_groups=axis_index_groups)
    return _all_gather_q(x, axis_name, axis, tiled, mode, block_size, groups)


# ---------------------------------------------------------------------------
# reduce-scatter (psum_scatter)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _reduce_scatter_q(x, axis_name, dim, tiled, mode, block, groups):
    if not tiled:
        raise NotImplementedError(
            "reduce_scatter_q supports tiled=True only (the form every "
            "call site in this repo uses)")
    bits = mode_bits(mode)
    npart = _group_size(axis_name, groups)
    if x.shape[dim] % npart:
        raise ValueError(
            f"cannot scatter dim {dim} of size {x.shape[dim]} over "
            f"{npart} participants (not divisible)")
    chunk = x.shape[dim] // npart
    xm = jnp.moveaxis(x, dim, 0).astype(jnp.float32)
    rest = xm.shape[1:]
    rows = xm.reshape(npart, -1)
    row_elems = rows.shape[1]
    q, s = _q_rows(rows, block, bits)
    q2 = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                        axis_index_groups=groups)
    s2 = lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0,
                        axis_index_groups=groups)
    shard = jnp.sum(_dq_rows(q2, s2, row_elems, bits), axis=0)
    out = shard.reshape((chunk,) + rest)
    return jnp.moveaxis(out, 0, dim).astype(x.dtype)


def _reduce_scatter_q_fwd(x, axis_name, dim, tiled, mode, block, groups):
    return (_reduce_scatter_q(x, axis_name, dim, tiled, mode, block, groups),
            None)


def _reduce_scatter_q_bwd(axis_name, dim, tiled, mode, block, groups, _, ct):
    # transpose of a tiled reduce-scatter: all-gather of the cotangent
    return (_all_gather_q(ct, axis_name, dim, True, mode, block, groups),)


_reduce_scatter_q.defvjp(_reduce_scatter_q_fwd, _reduce_scatter_q_bwd)


def reduce_scatter_q(x, axis_name: str, *, scatter_dimension: int = 0,
                     tiled: bool = True, mode: str = "int8",
                     block_size: int = DEFAULT_BLOCK,
                     axis_index_groups=None):
    """Quantized `lax.psum_scatter` (tiled): quantize my buffer, ride the
    chunks on an int all-to-all, dequantize + sum the received chunks."""
    groups = _norm_groups(axis_index_groups)
    if not eligible(x, mode, block_size):
        return lax.psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=tiled,
                                axis_index_groups=axis_index_groups)
    return _reduce_scatter_q(x, axis_name, scatter_dimension, tiled, mode,
                             block_size, groups)


# ---------------------------------------------------------------------------
# all-to-all
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _all_to_all_q(x, axis_name, split_axis, concat_axis, mode, block, groups):
    bits = mode_bits(mode)
    npart = _group_size(axis_name, groups)
    if x.shape[split_axis] % npart:
        raise ValueError(
            f"cannot split dim {split_axis} of size {x.shape[split_axis]} "
            f"over {npart} participants (not divisible)")
    xm = jnp.moveaxis(x, split_axis, 0).astype(jnp.float32)
    chunk = xm.shape[0] // npart
    rest = xm.shape[1:]
    rows = xm.reshape(npart, -1)
    row_elems = rows.shape[1]
    q, s = _q_rows(rows, block, bits)
    q2 = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                        axis_index_groups=groups)
    s2 = lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0,
                        axis_index_groups=groups)
    parts = _dq_rows(q2, s2, row_elems, bits).reshape(
        (npart, chunk) + rest)
    pieces = [jnp.moveaxis(parts[i], 0, split_axis) for i in range(npart)]
    return jnp.concatenate(pieces, axis=concat_axis).astype(x.dtype)


def _all_to_all_q_fwd(x, axis_name, split_axis, concat_axis, mode, block,
                      groups):
    return (_all_to_all_q(x, axis_name, split_axis, concat_axis, mode,
                          block, groups), None)


def _all_to_all_q_bwd(axis_name, split_axis, concat_axis, mode, block,
                      groups, _, ct):
    # transpose of a tiled all-to-all: the reverse all-to-all
    return (_all_to_all_q(ct, axis_name, concat_axis, split_axis, mode,
                          block, groups),)


_all_to_all_q.defvjp(_all_to_all_q_fwd, _all_to_all_q_bwd)


def all_to_all_q(x, axis_name: str, *, split_axis: int, concat_axis: int,
                 mode: str = "int8", block_size: int = DEFAULT_BLOCK,
                 axis_index_groups=None):
    """Quantized tiled `lax.all_to_all` (same split/concat semantics)."""
    groups = _norm_groups(axis_index_groups)
    if not eligible(x, mode, block_size):
        return lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True,
                              axis_index_groups=axis_index_groups)
    return _all_to_all_q(x, axis_name, split_axis, concat_axis, mode,
                         block_size, groups)


# ---------------------------------------------------------------------------
# all-reduce (psum) = quantized reduce-scatter + quantized all-gather
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _all_reduce_q(x, axis_name, mode, block, groups):
    bits = mode_bits(mode)
    npart = _group_size(axis_name, groups)
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    pad = (-n) % (npart * block)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    shard = _reduce_scatter_q(flat, axis_name, 0, True, mode, block, groups)
    full = _all_gather_q(shard, axis_name, 0, True, mode, block, groups)
    if pad:
        full = lax.slice(full, (0,), (n,))
    return full.reshape(x.shape).astype(x.dtype)


def _all_reduce_q_fwd(x, axis_name, mode, block, groups):
    return _all_reduce_q(x, axis_name, mode, block, groups), None


def _all_reduce_q_bwd(axis_name, mode, block, groups, _, ct):
    # psum is self-adjoint
    return (_all_reduce_q(ct, axis_name, mode, block, groups),)


_all_reduce_q.defvjp(_all_reduce_q_fwd, _all_reduce_q_bwd)


def all_reduce_q(x, axis_name: str, *, mode: str = "int8",
                 block_size: int = DEFAULT_BLOCK, axis_index_groups=None):
    """Quantized `lax.psum`: the EQuARX decomposition (quantized
    reduce-scatter, then quantized all-gather of the reduced shard)."""
    groups = _norm_groups(axis_index_groups)
    if not eligible(x, mode, block_size):
        return lax.psum(x, axis_name, axis_index_groups=axis_index_groups)
    return _all_reduce_q(x, axis_name, mode, block_size, groups)


def _norm_groups(groups) -> Optional[Tuple[Tuple[int, ...], ...]]:
    """axis_index_groups as a hashable tuple-of-tuples (custom_vjp
    nondiff args must hash)."""
    if groups is None:
        return None
    return tuple(tuple(int(i) for i in g) for g in groups)
