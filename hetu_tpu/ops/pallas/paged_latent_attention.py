"""Paged LATENT attention, the decode kernel of multi-head latent
attention (MLA: DeepSeek-V2/V3, Kimi-K2) in its absorbed form.

A token's cache entry is ONE vector of `dk` values, `[c_kv | k_rope]`
(512 | 64 for Kimi-K2): every query head attends the same cached vector
(multi-query attention), its key is the whole vector and its value the
first `dv` values of it.  The pool is one array `[P, page_size, dk]`
(page 0 = the null page) and the kernel walks each slot's page list as
`paged_attention.py` does, by scalar-prefetched block index maps, with
one difference in the grid's cost: the index map CLAMPS the page slot to
the slot's last live page, so a page slot past the slot's length names
the block the previous step already holds and moves no bytes; what such
a step costs is the grid step itself (~0.35 us on a v5e), its compute
skipped by `pl.when`.

Each page is DMA'd ONCE per step and used twice in VMEM: as the key
operand `[ps, dk]` of q.k and, its first `dv` lanes, as the value
operand of p.v.  Both products take the pool's dtype (bfloat16 in
serving) with float32 accumulation; the online softmax is float32.

Shape contract (`check_shapes`, drift-tested against `compatible`):
q [S, nq, dk], pool [P, ps, dk], table [S, max_pages], positions [S];
dk and `dv` <= dk multiples of 128, ps a multiple of 8.  Mosaic takes a
block of width 576 (it spans the whole last dim), but XLA then lays a
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

from hetu_tpu.ops.pallas import _interpret

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


def _kernel(table_ref, pos_ref, q_ref, c_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale, ps, dv, mp):
    s_idx = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    pos = pos_ref[s_idx]

    @pl.when(p * ps <= pos)
    def _compute():
        q = q_ref[0]                                    # [nq, dk]
        c = c_ref[0]                                    # [ps, dk]: read once
        s = jax.lax.dot_general(
            q, c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [nq, ps]
        kpos = p * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        m_prev = m_scr[:]                               # [nq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p_ = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * corr + jnp.sum(p_, axis=1, keepdims=True)
        pv = jax.lax.dot_general(                       # value = c[:, :dv]
            p_.astype(c.dtype), c[:, :dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [nq, dv]
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = m_new

    @pl.when(p == mp - 1)
    def _fin():
        l = l_scr[:]
        o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


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
    mp = table.shape[1]
    scale = softmax_scale if softmax_scale is not None else dk ** -0.5

    def page(s, p, tab, pos):
        # past the slot's last live page: name that page again (no DMA)
        return tab[s, jnp.minimum(p, pos[s] // ps)], 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, mp),
        in_specs=[pl.BlockSpec((1, nq, dk), lambda s, p, *_: (s, 0, 0)),
                  pl.BlockSpec((1, ps, dk), page)],
        out_specs=pl.BlockSpec((1, nq, value_dim),
                               lambda s, p, *_: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((nq, 1), jnp.float32),
                        pltpu.VMEM((nq, 1), jnp.float32),
                        pltpu.VMEM((nq, value_dim), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, ps=ps, dv=value_dim, mp=mp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nq, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="pallas_paged_latent_attention",
    )(table.astype(jnp.int32), positions.astype(jnp.int32), q, pool)


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
