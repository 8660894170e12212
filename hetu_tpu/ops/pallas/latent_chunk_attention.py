"""Latent chunk attention: the attention of the chunk program (chunked
prefill) for a LATENT cache (multi-head latent attention: one vector
`[RMSNorm(c_kv) | RoPE(k_rope) | 0 ...]` a token, `models/kimi_k2`),
blockwise, with its scores on the chip.

One row's chunk of C queries at positions start .. start + C - 1 attends
the scratch's latents it has just been written into (`models/generation.
extend_cache`, through `kimi_k2.MLAttention.attend_dense`).  The XLA
composition (`MLAttention._attend_composed`) walks the latents in blocks
of 512 positions with a `fori_loop` and, every trip, writes the float32
scores of all heads to HBM and crosses them for the mask, the maximum,
`exp`, the sum and the cast, and reads and writes the float32
accumulator: ~0.45 GB a trip at Ling's shape, 16% of that cell's device
time for ONE layer (PERF.md s6, PR 44).  Here, `chunk_attention.py`'s
rule over a latent operand:

* **the grid walks (head, row tile, key block)**, the key blocks
  innermost; the float32 scores, the probabilities, the running maximum
  and sum and the accumulator live a tile (VMEM).  The online softmax is
  `chunk_attention`'s own three functions (`softmax_init`,
  `softmax_step`, `softmax_finish`): one copy;
* **only the key blocks a tile can see are fetched and multiplied**: the
  number of live blocks is scalar-prefetched from the traced `start`
  (`start + C` positions, no more; a tile of part of a chunk stops at
  its own last row), the latent block's index map CLAMPS to the last
  live block and `pl.when` skips the dead steps, so a chunk over a 32k
  scratch pays for its prefix;
* **the mask is by global position**; a block wholly in the cached
  prefix takes none.  A layer that SELECTS what each query attends
  (models/deepseek_v32) hands in its own mask instead, `keep` [C, M]
  int8, nonzero where the query attends the position (a subset of what it
  may see; every query keeps one position or more): a block of it rides
  beside the latent block and every live block is masked by it.  Dense
  work under a sparse mask: the right mathematics, not yet the right
  cost (PERF.md s7);
* **a head's `k_nope | v` of a key block are made HERE, from the block's
  latents by the head's `[r, dn + dv]` columns of `W_kvb`**, in the
  cache's dtype as the composition makes them: nothing expanded ever
  lies in HBM, and with a row tile of the whole chunk (C <= `_ROW_TILE`)
  a (head, block) is expanded exactly once, the FLOPs the composition
  spends on it.  Nothing is ABSORBED: q.(W c) as (q W).c costs
  2 x (576 + 512) FLOP a pair a head against 2 x (192 + 128), 3.4 times
  the work of a layer that is compute-bound once its scores stay here;
* **the same arithmetic as the composition, in the same precisions**:
  the scores are the nope part + the rope part, each a product in the
  cache's dtype with float32 accumulation, `* softmax_scale`; float32
  statistics; the probabilities cast to the cache's dtype for p.v;
  float32 accumulator, one division at the end.

Every operand is read where it lies, by lane-dense column blocks: q
`[C, nh * (dn + stored - r)]` (a head's `[q_nope | q_rope | 0 ...]`, the
rope part widened with zeros to the lanes the latent STORES behind its
rank, so both products are lane-aligned), `W_kvb` `[r, nh * (dn + dv)]`,
the latents `[M, stored]`; the output is written `[C, nh * dv]`, what
`MLAttention.output` takes.  No relayout before or after.

Shape contract (`check_shapes`, drift-tested against `compatible`): ONE
row (b = 1) at ONE start, C > 1 queries, C a multiple of the sublane
tile of the dtype, the rank r, the nope and the value head dims and the
stored lanes behind the rank (>= the rope dim) multiples of 128, a cache
length M that `fit_block` divides into key blocks of a multiple of 128
(a cache shorter than that is one block, if a multiple of the sublane
tile).  Rows at depths of their own (the verify step, the gather decode
route) and single queries are refused: they keep the composition.  The
ROUTE's gate (`check_route`) also refuses what the kernel takes but does
not pay for.  Forward only (serving); no vjp.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.pallas import _interpret
from hetu_tpu.ops.pallas.chunk_attention import (NEG_INF, live_blocks,
                                                 require_score_bytes,
                                                 softmax_finish,
                                                 softmax_init, softmax_step)
from hetu_tpu.ops.pallas.flash_attention import fit_block

# Block sizes, from the shapes alone: a row tile of the whole chunk where
# it has up to `_ROW_TILE` rows (a head's block is then expanded once)
# against key blocks of up to `_KEY_BLOCK` positions.  From the sweep on
# a v5e at the two cells' shapes (PERF.md s6, PR 44).
_KEY_BLOCK = 1024
_ROW_TILE = 2048
_VMEM_LIMIT = 64 << 20


def _row_tile(C: int) -> int:
    """Rows of a head's chunk a grid step takes: the whole chunk, or its
    largest divisor within `_ROW_TILE` that keeps the sublane tiling (a
    multiple of 16); 0 where there is none."""
    if C <= _ROW_TILE:
        return C
    t = _ROW_TILE - _ROW_TILE % 16
    while t and C % t:
        t -= 16
    return t


def check_shapes(q_nope_shape, q_rope_shape, lat_shape, w_shape,
                 start_shape=(), *, dtype=None
                 ) -> Tuple[int, int, int, int, int]:
    """-> (C, nh, M, row tile, key block), or ValueError with the reason
    the composition takes the shape instead.  q_nope [b, C, nh, dn],
    q_rope [b, C, nh, dr], the latents [b, M, stored], W_kvb [r, nh,
    dn + dv]."""
    if len(q_nope_shape) != 4 or len(q_rope_shape) != 4 \
            or len(lat_shape) != 3 or len(w_shape) != 3:
        raise ValueError(
            f"expected q_nope [b, C, nh, dn], q_rope [b, C, nh, dr], "
            f"latents [b, M, stored] and W_kvb [r, nh, dn + dv], got "
            f"{q_nope_shape} / {q_rope_shape} / {lat_shape} / {w_shape}")
    b, C, nh, dn = q_nope_shape
    dr = q_rope_shape[-1]
    _, M, stored = lat_shape
    r, dv = w_shape[0], w_shape[2] - dn
    if tuple(q_rope_shape[:3]) != (b, C, nh) or w_shape[1] != nh:
        raise ValueError(f"q_rope {q_rope_shape} / W_kvb {w_shape} do not "
                         f"match q_nope {q_nope_shape}")
    if b != 1 or lat_shape[0] != 1 or math.prod(start_shape) != 1:
        raise ValueError(f"{b} rows at starts {tuple(start_shape)}: the "
                         f"kernel takes ONE row's chunk at one start; rows "
                         f"at depths of their own keep the composition")
    if C == 1:
        raise ValueError("C = 1: a single query has no chunk to block; "
                         "the composition (or the paged kernel) takes it")
    if r % 128 or dn % 128 or dv <= 0 or dv % 128:
        raise ValueError(f"rank {r}, nope dim {dn} and value dim {dv} are "
                         f"not lane-aligned (% 128)")
    if stored - r < dr or (stored - r) % 128:
        raise ValueError(f"a latent stored in {stored} lanes holds "
                         f"{stored - r} behind its rank of {r}: not the "
                         f"rope part's {dr} in lane-aligned (% 128) rows")
    sub = 32 // (jnp.dtype(dtype).itemsize if dtype is not None else 2)
    tr = _row_tile(C)
    if C % sub or not tr:
        raise ValueError(f"a chunk of C = {C} rows does not tile by the "
                         f"{sub} sublanes of the dtype")
    kb = fit_block(_KEY_BLOCK, M)
    if kb % 128 and (M > 128 or M % sub):
        raise ValueError(f"cache length {M} has no key block that is a "
                         f"multiple of 128 (best: {kb})")
    return C, nh, M, tr, kb


def check_route(q_nope_shape, q_rope_shape, lat_shape, w_shape,
                start_shape=(), *, dtype=None):
    """The gate `MLAttention.attend_dense` hands to `resolve_route`: the
    shapes the kernel takes (`check_shapes`) AND for which it pays, by
    `chunk_attention.check_route`'s measure and threshold (the K/V
    kernel's measured tie: here nothing under Kimi's 512 MB was timed):
    the float32 scores the composition would form over the cache it is
    handed (heads x C x positions).  Forced flags ask neither."""
    out = check_shapes(q_nope_shape, q_rope_shape, lat_shape, w_shape,
                       start_shape, dtype=dtype)
    C, nh, M = out[:3]
    require_score_bytes(nh, C, M)
    return out


def compatible(q_nope_shape, q_rope_shape, lat_shape, w_shape,
               start_shape=(), *, dtype=None) -> bool:
    try:
        check_shapes(q_nope_shape, q_rope_shape, lat_shape, w_shape,
                     start_shape, dtype=dtype)
        return True
    except ValueError:
        return False


def _tile_blocks(s, t, tr: int, kb: int):
    """Key blocks row tile `t` can see: up to the block of its last row's
    position, and never more than the chunk's (`s` = [live blocks of the
    chunk, start])."""
    return jnp.minimum(s[0], (s[1] + (t + 1) * tr - 1) // kb + 1)


def _kernel(s_ref, q_ref, w_ref, lat_ref, *refs, scale, tr, kb, r, dn,
            keep=False):
    # `keep`: one more operand [tr, kb] after the latents, the layer's mask
    keep_ref, refs = (refs[0], refs[1:]) if keep else (None, refs)
    o_ref, m_scr, l_scr, acc_scr = refs
    t, j = pl.program_id(1), pl.program_id(2)
    q0 = s_ref[1]
    live = _tile_blocks(s_ref, t, tr, kb)
    stats = (m_scr, l_scr, acc_scr)
    pl.when(j == 0)(lambda: softmax_init(*stats))

    def update(masked: bool):
        q, lat = q_ref[...], lat_ref[...]
        # this head's k_nope | v of the block, in the cache's dtype
        kv = jax.lax.dot_general(
            lat[:, :r], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(lat.dtype)
        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(q[:, :dn], kv[:, :dn], dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(q[:, dn:], lat[:, r:], dims,
                                   preferred_element_type=jnp.float32)
             ) * scale
        if keep_ref is not None:
            s = jnp.where(keep_ref[...] != 0, s, NEG_INF)
        elif masked:
            qpos = q0 + t * tr + jax.lax.broadcasted_iota(
                jnp.int32, (tr, 1), 0)
            kpos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (1, kb), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        softmax_step(s, kv[:, dn:], *stats)

    if keep_ref is not None:
        pl.when(j < live)(lambda: update(True))
    else:
        clear = j * kb + kb - 1 <= q0 + t * tr
        pl.when((j < live) & clear)(lambda: update(False))
        pl.when((j < live) & jnp.logical_not(clear))(lambda: update(True))
    pl.when(j == pl.num_programs(2) - 1)(
        lambda: softmax_finish(o_ref, *stats))


def latent_chunk_attention(q_nope, q_rope, lat, wkv_b, start, *,
                           softmax_scale: float, keep=None):
    """q_nope [1, C, nh, dn] and q_rope [1, C, nh, dr] (rotated) at
    positions start .. start + C - 1 (start a traced scalar, or [1]);
    lat [1, M, stored] the cached latents `[c_kv r | k_rope dr | 0 ...]`
    of positions 0 .. M - 1, every one of them up to start + C - 1
    written; wkv_b [r, nh, dn + dv].  Query i sees key position j iff
    j <= start + i; given `keep` [C, M] (int8, or bool), iff keep[i, j] is
    nonzero (the caller's mask lets nothing through that lies past
    start + i, and something for every i).  Returns [1, C, nh * dv].
    Raises ValueError on
    shapes outside `compatible` (`MLAttention._attend_composed` takes
    those)."""
    C, nh, M, tr, kb = check_shapes(q_nope.shape, q_rope.shape, lat.shape,
                                    wkv_b.shape, jnp.shape(start),
                                    dtype=lat.dtype)
    dn, dr = q_nope.shape[-1], q_rope.shape[-1]
    r, stored = wkv_b.shape[0], lat.shape[-1]
    dv, dq = wkv_b.shape[-1] - dn, dn + stored - r
    start = jnp.reshape(jnp.asarray(start, jnp.int32), ())
    scalars = jnp.stack([live_blocks(start, C, M, kb)[1], start])
    # a head's [q_nope | q_rope | 0 ...], the rope part as wide as the
    # lanes the latent stores behind its rank
    q = jnp.concatenate(
        [q_nope[0], q_rope[0]] + ([jnp.zeros(
            (C, nh, stored - r - dr), q_nope.dtype)] if stored - r > dr
            else []), axis=-1).astype(lat.dtype).reshape(C, nh * dq)

    def key_block(h, t, j, s):
        return jnp.minimum(j, _tile_blocks(s, t, tr, kb) - 1), 0

    masks = () if keep is None else (keep.astype(jnp.int8),)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=softmax_scale, tr=tr, kb=kb, r=r,
                          dn=dn, keep=bool(masks)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nh, C // tr, M // kb),
            in_specs=[pl.BlockSpec((tr, dq), lambda h, t, j, s: (t, h)),
                      pl.BlockSpec((r, dn + dv), lambda h, t, j, s: (0, h)),
                      pl.BlockSpec((kb, stored), key_block)] + [
                pl.BlockSpec((tr, kb), lambda h, t, j, s: (
                    t, key_block(h, t, j, s)[0])) for _ in masks],
            out_specs=pl.BlockSpec((tr, dv), lambda h, t, j, s: (t, h)),
            scratch_shapes=[pltpu.VMEM((tr, 1), jnp.float32),
                            pltpu.VMEM((tr, 1), jnp.float32),
                            pltpu.VMEM((tr, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((C, nh * dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
    )(scalars, q, wkv_b.astype(lat.dtype).reshape(r, nh * (dn + dv)),
      lat[0], *masks)
    return out[None]
