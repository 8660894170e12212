"""Paged LATENT attention, the decode kernel of multi-head latent
attention (MLA: DeepSeek-V2/V3, Kimi-K2) in its absorbed form.

A token's cache entry is ONE vector of `dk` values, `[c_kv | k_rope]`
(512 | 64 for Kimi-K2): every query head attends the same cached vector
(multi-query attention), its key is the whole vector and its value the
first `dv` values of it.  The pool is one array `[P, page_size, dk]`
(page 0 = the null page).

**The walk** is `paged_attention._walk_live_blocks`, the one page walk
of `ops/pallas`, over ONE stream.  The grid is the slots; the pool stays
whole in HBM (`memory_space=pl.ANY`), the table and the positions are
scalar-prefetched; inside a grid step a loop of the slot's OWN trip
count, ``ceil((positions[s] // page_size + 1) / pages_per_block)``,
runs over blocks of `pages_per_block` pages, each page fetched by a
copy of the kernel's own into double buffers `[2, pages_per_block,
page_size, dk]`, the next block — in a slot's last block the NEXT
slot's first — in flight while a block is computed (so the slot axis is
`"arbitrary"`).  A page slot past a slot's length costs nothing: no
grid step, no copy, no table entry read; an idle slot (position 0, a
null table row) costs one grid step and one page.

**A block's arithmetic** is one online-softmax update (float32 running
max, sum and accumulator).  A fetched block is used twice where it
lies: whole, as the key operand `[T, dk]` of q.k, and, its first `dv`
lanes, as the value operand of p.v.  Both products take the pool's
dtype (bfloat16 in serving) with float32 accumulation; `p` is cast to
the pool's dtype before p.v.  In a slot's last block the scores past
its position are masked and the value rows past it zeroed, so that what
a buffer holds there (the page's own tail, an earlier block, nothing
yet) cannot reach the result through ``0 * x``; that block is computed
WHOLE, dead pages and all (cutting it to its live pages, page by page
or in runs of 4 / 2 / 1, was level at 4 pages a block on the chip:
PERF.md s6, PR 54).

`pages_per_block` is derived here from what the wrapper sees (query
rows, page size, latent width, item size, table width): as many pages
as hold `_BLOCK_TOKENS` tokens within `paged_attention._VMEM_BUDGET` of
buffers and float32 scores, never more than the table is wide, at least
one.  No caller chooses it; `kernel_routes["paged_latent"]` says it
beside the dispatcher's reason.

Shape contract (`check_shapes`, drift-tested against `compatible`):
q [S, nq, dk], pool [P, ps, dk], table [S, max_pages], positions [S];
dk and `dv` <= dk multiples of 128, ps a multiple of 8.  Mosaic takes a
page of width 576 (it spans the whole last dim), but XLA then lays a
`[P, ps, 576]` pool out page-size-minor to avoid padding 576 to 640
lanes, and copies the whole pool into the kernel's row-major layout at
every call (compiled for a described v5e, PR 27: 2.0 GB of temporaries).
So the CALLER pads a token's vector to a multiple of 128 lanes (576 ->
640, zeros that add nothing to q.k) and the pool's HBM bytes are what
the tiled layout would have held anyway.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.pallas import _interpret, _note_engagement
from hetu_tpu.ops.pallas.paged_attention import (_VMEM_BUDGET,
                                                 _walk_live_blocks)

NEG_INF = -1e30


def check_shapes(q_shape, pool_shape, table_shape, pos_shape, *,
                 value_dim: int) -> Tuple[int, int, int, int, int]:
    if len(q_shape) != 3 or len(pool_shape) != 3:
        raise ValueError(f"expected q [S, nq, dk] and pool [P, ps, dk], "
                         f"got {q_shape} / {pool_shape}")
    S, nq, dk = q_shape
    P, ps, dk_p = pool_shape
    if dk_p != dk:
        raise ValueError(f"latent width mismatch: q {dk}, pool {dk_p}")
    if dk % 128 or not 0 < value_dim <= dk or value_dim % 128:
        raise ValueError(f"latent width {dk} and value width {value_dim} "
                         f"must be multiples of 128 lanes, value <= latent")
    if ps % 8:
        raise ValueError(f"page size {ps} is not a multiple of 8 (the "
                         f"sublane tile)")
    if len(table_shape) != 2 or table_shape[0] != S:
        raise ValueError(f"table {table_shape} must be [S={S}, max_pages]")
    if tuple(pos_shape) != (S,):
        raise ValueError(f"positions {pos_shape} must be [S={S}]")
    return S, nq, dk, P, ps


def compatible(q_shape, pool_shape, table_shape, pos_shape, *,
               value_dim: int) -> bool:
    try:
        check_shapes(q_shape, pool_shape, table_shape, pos_shape,
                     value_dim=value_dim)
        return True
    except ValueError:
        return False


# A block's size.  On a v5e, the kernel alone at the three cells' shapes
# (96 slots x 64 heads x a table of 10; 64 x 64 x 16; 32 x 32 x 128; pages
# of 256, contexts as the cells draw them: my chip runs, PR 54), blocks of
# 256 / 512 / 1,024 / 2,048 tokens take 0.35 / 0.28 / 0.27 / 0.29, 0.44 /
# 0.34 / 0.32 / 0.33 and 0.87 / 0.64 / 0.58 / 0.58 ms where the page-slot
# grid took 0.52, 0.59 and 1.34: 1,024 at 64 rows and at 32 (PERF.md s6).
_BLOCK_TOKENS = 1024


def _token_vmem_bytes(nq: int, dk: int, itemsize: int) -> int:
    """VMEM bytes one cached token of a block takes: its vector in the
    two buffers, and the float32 scores and probabilities (and their
    cast) of `nq` query rows against it."""
    return 2 * dk * itemsize + 3 * nq * 4


def pages_per_block(nq: int, ps: int, dk: int, itemsize: int,
                    max_pages: int) -> int:
    """Pages the walk fetches and attends at once, from the shapes alone:
    `_BLOCK_TOKENS` tokens where `paged_attention._VMEM_BUDGET` holds
    them (more query rows or wider elements: fewer), never more than the
    table is wide, at least one."""
    tokens = min(_BLOCK_TOKENS,
                 _VMEM_BUDGET // _token_vmem_bytes(nq, dk, itemsize))
    return max(1, min(tokens // ps, max_pages))


def _kernel(table_ref, pos_ref, q_ref, c_hbm, o_ref, c_buf, sem, parity,
            *, scale, ps, ppb, dv, mp):
    """One grid step = one slot: its `nq` query rows against its live
    blocks of the latent pool."""
    T = ppb * ps
    nq, dk = q_ref.shape[1:]
    pos = pos_ref[pl.program_id(0)]
    q = q_ref[0].astype(c_buf.dtype)

    def pages_of(s):
        return jnp.minimum(pos_ref[s] // ps + 1, mp)

    def block(b, buffer, carry, last):
        m_prev, l_prev, acc = carry
        c = c_buf[buffer].reshape(T, dk)        # fetched once, used twice
        s = jax.lax.dot_general(
            q, c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [nq, T]
        v = c[:, :dv]
        if last:                    # a block before the last is all live
            col = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
            s = jnp.where(col <= pos - b * T, s, NEG_INF)
            row = jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
            v = jnp.where(row <= pos - b * T, v, jnp.zeros_like(v))
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p_ = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p_, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p_.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [nq, dv]
        return m_new, l_new, acc * corr + pv

    carry = (jnp.full((nq, 1), NEG_INF, jnp.float32),
             jnp.zeros((nq, 1), jnp.float32),
             jnp.zeros((nq, dv), jnp.float32))
    _, l, acc = _walk_live_blocks(
        pages_of, ((table_ref, c_hbm, c_buf),), sem, parity, carry, block,
        ppb=ppb)
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_latent_attention(q, pool, table, positions, *, value_dim: int,
                           softmax_scale: Optional[float] = None):
    """q: [S, nq, dk] (one token per slot, the absorbed query
    `[q_nope W_kb | q_rope]`); pool: [P, page_size, dk]; table:
    [S, max_pages] int32 page ids; positions: [S] int32 — slot s attends
    the cached vectors at global positions <= positions[s].  Returns
    the latent output [S, nq, value_dim] = softmax(q.c * scale) c[:, :dv],
    which the caller takes through W_vb.  Raises ValueError on shapes
    outside `compatible`."""
    S, nq, dk, P, ps = check_shapes(q.shape, pool.shape, table.shape,
                                    positions.shape, value_dim=value_dim)
    scale = softmax_scale if softmax_scale is not None else dk ** -0.5
    ppb = pages_per_block(nq, ps, dk, pool.dtype.itemsize, table.shape[1])
    _note_engagement("paged_latent", f"pages_per_block={ppb}")
    return _launch(q, pool, table.astype(jnp.int32),
                   positions.astype(jnp.int32), value_dim=value_dim,
                   scale=scale, ppb=ppb, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("value_dim", "scale", "ppb",
                                             "interpret"))
def _launch(q, pool, table, positions, *, value_dim, scale, ppb, interpret):
    """Jitted, so that a program whose layer bodies call the kernel at one
    shape (eight in LongCat's decode program) traces and lowers the walk
    ONCE: `setup_s` is judged, and an equation costs ~0.5 ms each time."""
    S, nq, dk = q.shape
    ps, mp = pool.shape[1], table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, nq, dk), lambda s, *_: (s, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, nq, value_dim), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, ppb, ps, dk), pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 1)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, ps=ps, ppb=ppb,
                          dv=value_dim, mp=mp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nq, value_dim), q.dtype),
        # in order: a slot's last block fetches the next slot's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="pallas_paged_latent_attention",
    )(table, positions, q, pool)


def paged_latent_attention_xla(q, pool, table, positions, *, value_dim: int,
                               softmax_scale: Optional[float] = None):
    """The same result by XLA: the slots' pages gathered through the
    table into a dense [S, max_pages * ps, dk] view and a masked softmax
    over it, float32.  The route `HETU_TPU_PALLAS=0` and a shape the
    kernel's gate refuses take it; it reads every page slot of the
    table, live or not."""
    S, nq, dk = q.shape
    ps = pool.shape[1]
    scale = softmax_scale if softmax_scale is not None else dk ** -0.5
    c = pool[table].reshape(S, table.shape[1] * ps, dk).astype(jnp.float32)
    s = jnp.einsum("snd,skd->snk", q.astype(jnp.float32), c) * scale
    live = jnp.arange(c.shape[1])[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None, :], s, NEG_INF), axis=-1)
    return jnp.einsum("snk,skd->snd", p, c[..., :value_dim]).astype(q.dtype)
