"""Paged-attention decode Pallas kernel: attend directly over the serving
KV pool's page tables (gather-free decode).

The serving engine's decode step (PR 7 follow-up, closed here) used to
GATHER every slot's pages into a dense [S, max_len, n_kv, hd] view per
layer before attending — three passes over the cache bytes (gather read,
dense write, attention read), most of them over DEAD tail positions.
This kernel walks each slot's page list via scalar-prefetched block
index maps (the splash-attention technique the flash kernel already
uses for its live-pair tables): grid (slot, page_slot), with the K/V
BlockSpec index maps reading `table[s, p]` so each grid step DMAs ONE
page straight from the pool.  Pages past the slot's live length are
scheduled but compute-skipped (`pl.when`); the null page (id 0) that
inactive slots point at is masked the same way the dense path masks it
(position mask over the global key index).

Online-softmax accumulation across a slot's pages mirrors the flash
forward; GQA folds grouped q heads against the pool's kv heads via an
in-VMEM reshape (no materialized repeat).  Decode is forward-only — no
vjp (the training path keeps flash attention).

int8 pages (``HETU_TPU_KV_QUANT=int8``, the PR 9 "exact-fp pages only"
gap closed): the kernel takes the pool's per-head-vector f32 absmax
scales as two extra page-indexed operands and dequantizes each page
IN-VMEM (``k * scale``) right after the DMA — the HBM read is the int8
payload (+ the small scale plane), ~3.9x fewer cache bytes per decode
step than fp32 pages (ops/pallas/traffic.paged_attn_traffic prices it;
`detail.kernels` records the row).  The token K/V scattered pre-kernel
quantize through the SAME blockwise primitives the gather path uses
(comm/compress -> ops/pallas/quant when routed), so pool contents are
bit-identical across the two decode programs.

int4 pages (``HETU_TPU_KV_QUANT=int4``) push the same trick to nibble
storage: the pool holds uint8 payloads of HALF the head dim packed via
`ops/quantization.pack_nibbles` (even index = LOW nibble, values offset
by +8) plus the same per-head-vector f32 scale plane; the kernel unpacks
and dequantizes in-VMEM (``(nibble - 8) * scale``), ~7.5x fewer cache
bytes than fp32 pages at hd=128.

`paged_verify` is the multi-query sibling (spec-decode verification):
q carries C = k+1 query positions per slot, all attending the slot's
pages in ONE launch with per-position causal masks (query i sees keys
at global positions <= positions[s] + i).  Same page walk, same online
softmax with C*nq accumulator rows, same none/int8/int4 page modes —
it replaces the gather program `verify_step_slots` used to dispatch
(three passes over the cache bytes) with one pass over the quantized
pool.

Shape contract (drift-tested against `compatible`/`verify_compatible`):
hd % 128, q heads divide by kv heads, table/positions/q agree on the
slot count, scales present iff quant, pool head dim halved for int4."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.pallas import _interpret

NEG_INF = -1e30


def _check_pool(q_heads_hd, pool_shape, table_shape, pos_shape, S, *,
                quant: str) -> Tuple[int, int, int]:
    nq, hd = q_heads_hd
    if quant not in ("none", "int8", "int4"):
        raise ValueError(f"paged-attention page mode {quant!r} "
                         "unsupported; known: ('none', 'int8', 'int4')")
    P, ps, n_kv, hd_p = pool_shape
    hd_stored = hd // 2 if quant == "int4" else hd
    if hd_p != hd_stored:
        raise ValueError(f"head dim mismatch: q {hd} expects pool "
                         f"{hd_stored} ({quant} pages), got {hd_p}")
    if nq % n_kv:
        raise ValueError(f"q heads {nq} must divide by kv heads {n_kv}")
    if len(table_shape) != 2 or table_shape[0] != S:
        raise ValueError(f"table {table_shape} must be [S={S}, max_pages]")
    if tuple(pos_shape) != (S,):
        raise ValueError(f"positions {pos_shape} must be [S={S}]")
    if hd % 128:
        raise ValueError(f"head dim {hd} is not lane-aligned (% 128); "
                         f"the gather fallback handles it")
    return P, ps, n_kv


def check_shapes(q_shape, pool_shape, table_shape, pos_shape, *,
                  quant: str = "none"
                  ) -> Tuple[int, int, int, int, int, int]:
    if len(q_shape) != 3 or len(pool_shape) != 4:
        raise ValueError(f"expected q [S, nq, hd] and pool [P, ps, n_kv, "
                         f"hd], got {q_shape} / {pool_shape}")
    S, nq, hd = q_shape
    P, ps, n_kv = _check_pool((nq, hd), pool_shape, table_shape,
                              pos_shape, S, quant=quant)
    return S, nq, hd, P, ps, n_kv


def check_shapes_verify(q_shape, pool_shape, table_shape, pos_shape, *,
                         quant: str = "none"
                         ) -> Tuple[int, int, int, int, int, int, int]:
    if len(q_shape) != 4 or len(pool_shape) != 4:
        raise ValueError(f"expected q [S, C, nq, hd] and pool [P, ps, "
                         f"n_kv, hd], got {q_shape} / {pool_shape}")
    S, C, nq, hd = q_shape
    if C < 1:
        raise ValueError(f"verify needs at least one query position, "
                         f"got C={C}")
    P, ps, n_kv = _check_pool((nq, hd), pool_shape, table_shape,
                              pos_shape, S, quant=quant)
    return S, C, nq, hd, P, ps, n_kv


def compatible(q_shape, pool_shape, table_shape, pos_shape, *,
               quant: str = "none") -> bool:
    try:
        check_shapes(q_shape, pool_shape, table_shape, pos_shape,
                      quant=quant)
        return True
    except ValueError:
        return False


def verify_compatible(q_shape, pool_shape, table_shape, pos_shape, *,
                      quant: str = "none") -> bool:
    try:
        check_shapes_verify(q_shape, pool_shape, table_shape, pos_shape,
                             quant=quant)
        return True
    except ValueError:
        return False


def _load_page(page_ref, scale_ref, *, quant, ps, n_kv, hd):
    """DMA'd page block -> dequantized f32 [ps, n_kv, hd] in VMEM."""
    x = page_ref[0]
    if quant == "none":
        return x.astype(jnp.float32)
    if quant == "int4":
        # unpack the nibble payload [ps, n_kv, hd//2] (even index = LOW
        # nibble, ops/quantization.pack_nibbles layout, +8 offset)
        p8 = x.astype(jnp.uint8)
        lo = (p8 & 0xF).astype(jnp.int32) - 8
        hi = (p8 >> 4).astype(jnp.int32) - 8
        x = jnp.stack((lo, hi), axis=-1).reshape(ps, n_kv, hd)
    x = x.astype(jnp.float32)
    # one f32 absmax scale per head-vector (the kv_pool blockwise layout)
    return x * scale_ref[0].astype(jnp.float32)[..., None]


def _kernel(*refs, scale, ps, n_kv, group, mp, quant):
    if quant != "none":
        (table_ref, pos_ref, stable_ref, q_ref, k_ref, v_ref, ks_ref,
         vs_ref, o_ref, m_scr, l_scr, acc_scr) = refs
    else:
        (table_ref, pos_ref, q_ref, k_ref, v_ref,
         o_ref, m_scr, l_scr, acc_scr) = refs
        ks_ref = vs_ref = None
    s_idx = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    pos = pos_ref[s_idx]

    # page p holds global positions [p*ps, (p+1)*ps); skip the compute
    # body for wholly-future pages (they are scheduled — the grid is
    # static — but move no math; their DMA reads the null page)
    @pl.when(p * ps <= pos)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # [nq, hd]
        nq, hd = q.shape
        k = _load_page(k_ref, ks_ref, quant=quant, ps=ps, n_kv=n_kv, hd=hd)
        v = _load_page(v_ref, vs_ref, quant=quant, ps=ps, n_kv=n_kv, hd=hd)
        qg = q.reshape(n_kv, group, hd)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32) * scale  # [n_kv, g, ps]
        kpos = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ps), 2)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        sf = s.reshape(nq, ps)

        m_prev = m_scr[:]                               # [nq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(sf, axis=1, keepdims=True))
        p_ = jnp.exp(sf - m_new)                        # [nq, ps]
        corr = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * corr + jnp.sum(p_, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p_.reshape(n_kv, group, ps), v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)          # [n_kv, g, hd]
        acc_scr[:] = acc_scr[:] * corr + pv.reshape(nq, hd)
        m_scr[:] = m_new

    @pl.when(p == mp - 1)
    def _fin():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _verify_kernel(*refs, scale, C, ps, n_kv, group, mp, quant):
    """Multi-query form: the slot's q block carries C = k+1 positions;
    accumulator rows are laid out (n_kv, C, group) so the grouped-GQA
    contraction stays a single batched dot per page."""
    if quant != "none":
        (table_ref, pos_ref, stable_ref, q_ref, k_ref, v_ref, ks_ref,
         vs_ref, o_ref, m_scr, l_scr, acc_scr) = refs
    else:
        (table_ref, pos_ref, q_ref, k_ref, v_ref,
         o_ref, m_scr, l_scr, acc_scr) = refs
        ks_ref = vs_ref = None
    s_idx = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    pos = pos_ref[s_idx]

    # the LAST query position (pos + C - 1) decides which pages hold any
    # visible keys; wholly-future pages move no math
    @pl.when(p * ps <= pos + (C - 1))
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # [C, nq, hd]
        nq, hd = q.shape[1], q.shape[2]
        k = _load_page(k_ref, ks_ref, quant=quant, ps=ps, n_kv=n_kv, hd=hd)
        v = _load_page(v_ref, vs_ref, quant=quant, ps=ps, n_kv=n_kv, hd=hd)
        rows = n_kv * C * group
        qg = q.reshape(C, n_kv, group, hd).transpose(1, 0, 2, 3) \
              .reshape(n_kv, C * group, hd)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32) * scale  # [n_kv, C*g, ps]
        # per-position causal mask: query i sees keys at global
        # positions <= pos + i
        ci = jax.lax.broadcasted_iota(jnp.int32, (1, C, 1, ps), 1)
        kp = p * ps + jax.lax.broadcasted_iota(jnp.int32, (1, C, 1, ps), 3)
        s = jnp.where(kp <= pos + ci, s.reshape(n_kv, C, group, ps),
                      NEG_INF)
        sf = s.reshape(rows, ps)

        m_prev = m_scr[:]                               # [rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(sf, axis=1, keepdims=True))
        p_ = jnp.exp(sf - m_new)                        # [rows, ps]
        corr = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * corr + jnp.sum(p_, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p_.reshape(n_kv, C * group, ps), v,
            (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)          # [n_kv, C*g, hd]
        acc_scr[:] = acc_scr[:] * corr + pv.reshape(rows, hd)
        m_scr[:] = m_new

    @pl.when(p == mp - 1)
    def _fin():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        hd = o_ref.shape[3]
        o = (acc_scr[:] / l_safe).reshape(n_kv, C, group, hd) \
            .transpose(1, 0, 2, 3).reshape(C, n_kv * group, hd)
        o_ref[0] = o.astype(o_ref.dtype)


def _resolve_quant(quant, k_scale, v_scale):
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    if quant is None:
        quant = "int8" if k_scale is not None else "none"
    if (quant != "none") != (k_scale is not None):
        raise ValueError(f"page mode {quant!r} needs scales iff "
                         "quantized (int8/int4)")
    return quant


def _scalar_prefetch(table, positions, scale_table, k_scale, P, ps, n_kv,
                     quant):
    """The kernels' scalar-prefetch operands: (table, positions) and,
    for quantized pages, the page ids the SCALE planes are read by —
    `scale_table` into planes of their own page count, or `table`
    itself into planes of the payload's P pages."""
    scalars = [table.astype(jnp.int32), positions.astype(jnp.int32)]
    if quant == "none":
        return scalars
    if scale_table is None:
        scale_table = table
    else:
        P = k_scale.shape[0]
    if tuple(k_scale.shape) != (P, ps, n_kv):
        raise ValueError(f"scales {k_scale.shape} must be "
                         f"[P={P}, ps={ps}, n_kv={n_kv}]")
    if scale_table.shape != table.shape:
        raise ValueError(f"scale_table {scale_table.shape} must match "
                         f"table {table.shape}")
    return scalars + [scale_table.astype(jnp.int32)]


def paged_attention(q, k_pool, v_pool, table, positions, *,
                    softmax_scale: Optional[float] = None,
                    k_scale=None, v_scale=None, quant=None,
                    scale_table=None):
    """Decode attention over paged KV.  q: [S, nq, hd] (one token per
    slot); k_pool/v_pool: [P, page_size, n_kv, hd] (page 0 = the null
    page); table: [S, max_pages] int32 page ids; positions: [S] int32 —
    slot s attends over global positions <= positions[s].  int8 pools
    pass their per-head-vector f32 scales [P, page_size, n_kv] as
    k_scale/v_scale and dequantize in-kernel; int4 pools additionally
    pass ``quant="int4"`` (uint8 nibble payloads, pool head dim hd//2).
    ``scale_table`` [S, max_pages] gives the scales page ids of their
    own, for a caller whose scale planes are not laid out like the
    payload (models/generation hands the kernel every layer's payload
    pages in one array and one layer's scales; ignored for exact pages).
    Returns [S, nq, hd].  Raises ValueError on shapes outside
    `compatible` (the dense-gather fallback in models/generation
    handles those)."""
    quant = _resolve_quant(quant, k_scale, v_scale)
    S, nq, hd, P, ps, n_kv = check_shapes(
        q.shape, k_pool.shape, table.shape, positions.shape, quant=quant)
    scalars = _scalar_prefetch(table, positions, scale_table, k_scale,
                               P, ps, n_kv, quant)
    mp = table.shape[1]
    group = nq // n_kv
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    hd_p = k_pool.shape[-1]

    page_spec = pl.BlockSpec((1, ps, n_kv, hd_p),
                             lambda s, p, tab, *_: (tab[s, p], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, nq, hd), lambda s, p, *_: (s, 0, 0)),
        page_spec, page_spec,
    ]
    operands = [q, k_pool, v_pool]
    if quant != "none":
        scale_spec = pl.BlockSpec(
            (1, ps, n_kv), lambda s, p, tab, pos, stab: (stab[s, p], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(S, mp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nq, hd),
                               lambda s, p, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nq, 1), jnp.float32),
            pltpu.VMEM((nq, 1), jnp.float32),
            pltpu.VMEM((nq, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, ps=ps, n_kv=n_kv,
                          group=group, mp=mp, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(*scalars, *operands)


def paged_verify(q, k_pool, v_pool, table, positions, *,
                 softmax_scale: Optional[float] = None,
                 k_scale=None, v_scale=None, quant=None,
                 scale_table=None):
    """Multi-query verify attention over paged KV (spec decoding).
    q: [S, C, nq, hd] — slot s's C = k+1 query positions sit at global
    positions positions[s]..positions[s]+C-1, each attending causally
    over the slot's pages; pools/table/scales/scale_table exactly as
    `paged_attention`.  Returns [S, C, nq, hd].  Raises ValueError on
    shapes outside `verify_compatible` (the gather verify program in
    models/generation handles those)."""
    quant = _resolve_quant(quant, k_scale, v_scale)
    S, C, nq, hd, P, ps, n_kv = check_shapes_verify(
        q.shape, k_pool.shape, table.shape, positions.shape, quant=quant)
    scalars = _scalar_prefetch(table, positions, scale_table, k_scale,
                               P, ps, n_kv, quant)
    mp = table.shape[1]
    group = nq // n_kv
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    hd_p = k_pool.shape[-1]
    rows = n_kv * C * group

    page_spec = pl.BlockSpec((1, ps, n_kv, hd_p),
                             lambda s, p, tab, *_: (tab[s, p], 0, 0, 0))
    in_specs = [
        pl.BlockSpec((1, C, nq, hd), lambda s, p, *_: (s, 0, 0, 0)),
        page_spec, page_spec,
    ]
    operands = [q, k_pool, v_pool]
    if quant != "none":
        scale_spec = pl.BlockSpec(
            (1, ps, n_kv), lambda s, p, tab, pos, stab: (stab[s, p], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(S, mp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, C, nq, hd),
                               lambda s, p, *_: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_verify_kernel, scale=scale, C=C, ps=ps,
                          n_kv=n_kv, group=group, mp=mp, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, C, nq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(*scalars, *operands)
