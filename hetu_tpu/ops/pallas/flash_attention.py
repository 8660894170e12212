"""Flash attention for TPU in Pallas.

Rebuild of the reference's fused attention
(reference: hetu/impl/kernel/FlashAttention.cu:150 run_mha_fwd wrapping the
vendored flash-attn 2; varlen/cu_seqlens handled by the kernel there).
TPU-first design decisions:

- online-softmax forward with float32 accumulators in VMEM scratch; the grid
  is (batch, q_heads, pair) where `pair` walks a **compressed list of live
  (q-block, k-block) tiles** — causally-dead tiles are never scheduled, the
  TPU analog of flash-attn 2's causal-skip launch geometry (reference:
  hetu/impl/kernel/FlashAttention.cu:150 + third_party/flash_attn). The
  live-pair tables ride in as scalar-prefetch operands (the splash-attention
  technique), so ANY static block mask — contiguous causal, ring-step
  offsets, SYM split quadrants (ParallelAttention.cc:212 GenerateAttnInfo) —
  compresses the same way, forward and backward alike.
- packed varlen batches are masked by **segment ids**, the static-shape
  equivalent of cu_seqlens; causality is masked by **global positions**, which
  are explicit inputs so ring-attention context parallelism (chunks owned by
  other cp ranks, head+tail symmetric split) reuses this same kernel for every
  ring step (reference: ParallelAttention.cc ExecFlashAttn :660).
- GQA folds the kv-head broadcast into the k/v BlockSpec index maps (no
  materialized repeat); dk/dv come back per q-head and are group-summed
  outside the kernel.
- forward also emits LSE so the ring's online-softmax merge
  (reference ExecCorr :606) can combine partial attentions.
- backward = two Pallas kernels (dq over k-blocks; dkv over q-blocks) using
  the saved LSE + delta trick from flash-attn 2; both run on compressed
  triangular grids.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.pallas import _interpret

NEG_INF = -1e30


# swept on v5e at b8/s2048/h12/d128 (tools_bench_attn.py, 2026-07): f+b
# 1024/1024 7.05ms < 1024/512 7.50 < 512/512 7.92 — bigger tiles amortize
# per-tile VPU/DMA overhead; causal skip granularity loss is smaller than
# the win. VMEM: the fp32 score tile is 4MB, well within budget.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024


# ---------------------------------------------------------------------------
# static block masks + compressed pair tables
# ---------------------------------------------------------------------------

BlockMask = Tuple[Tuple[bool, ...], ...]  # hashable [nq][nk] live-tile grid


def causal_block_mask(sq: int, sk: int, block_q: int, block_k: int,
                      q_offset: Optional[int] = None,
                      k_offset: int = 0) -> BlockMask:
    """Live-tile grid for contiguous causal attention: tile (qi, ki) is live
    iff its best-case query position can see its earliest key position.
    `q_offset`/`k_offset` are the global positions of element 0 on each side
    (default: bottom-right alignment, q_offset = sk - sq + k_offset) — this is
    how ring steps express "my queries vs. a rotated KV chunk"
    (reference: ParallelAttention.cc:212 GenerateAttnInfo mask kinds)."""
    nq, nk = sq // block_q, sk // block_k
    if q_offset is None:
        q_offset = sk - sq + k_offset
    rows = []
    for qi in range(nq):
        q_max = q_offset + qi * block_q + block_q - 1
        rows.append(tuple(k_offset + ki * block_k <= q_max
                          for ki in range(nk)))
    return tuple(rows)


def full_block_mask(sq: int, sk: int, block_q: int, block_k: int) -> BlockMask:
    return tuple((True,) * (sk // block_k) for _ in range(sq // block_q))


def block_mask_live_frac(mask: BlockMask) -> float:
    """Fraction of tiles scheduled (diagnostics / cost models)."""
    flat = [x for row in mask for x in row]
    return sum(flat) / max(1, len(flat))


def _pair_tables(mask: BlockMask):
    """Row-major compressed enumeration of live tiles.

    Returns int32 arrays (row, col, first, last, valid) of length T. Rows
    with zero live tiles get one dummy (row, 0) pair with valid=0 so their
    output block is still initialized (to the "attends to nothing" value)
    and written; the kernels skip the compute body for valid=0."""
    rows, cols, first, last, valid = [], [], [], [], []
    for r, row in enumerate(mask):
        live = [c for c, ok in enumerate(row) if ok]
        ok = 1 if live else 0
        live = live or [0]
        for j, c in enumerate(live):
            rows.append(r)
            cols.append(c)
            first.append(1 if j == 0 else 0)
            last.append(1 if j == len(live) - 1 else 0)
            valid.append(ok)
    return (np.asarray(rows, np.int32), np.asarray(cols, np.int32),
            np.asarray(first, np.int32), np.asarray(last, np.int32),
            np.asarray(valid, np.int32))


def _check_mask(mask: BlockMask, nq: int, nk: int):
    if len(mask) != nq or any(len(row) != nk for row in mask):
        raise ValueError(
            f"block_mask shape ({len(mask)},{len(mask[0]) if mask else 0}) "
            f"does not match the ({nq},{nk}) block grid — rebuild it with "
            f"the actual (possibly clamped) block sizes")


def fit_block(requested: int, s: int) -> int:
    """Largest block <= requested that divides s: steps down the
    128-aligned ladder first, then any divisor — the ONE block-picking rule
    for the single-device kernel and the ring (hetu_tpu.parallel.
    ring_attention uses this as _pick_block), so both entry points get the
    same tile geometry."""
    b = min(requested, s)
    while s % b:
        b = b - 128 if b > 128 else b - 1
        if b <= 0:
            raise ValueError(f"cannot block seq len {s}")
    return b


def _transpose_mask(mask: BlockMask) -> BlockMask:
    return tuple(zip(*mask))


def check_default_shapes(sq: int, sk: int, d: int,
                         block_q: int = DEFAULT_BLOCK_Q,
                         block_k: int = DEFAULT_BLOCK_K):
    """The public entry's shape validation under the DEFAULT block
    geometry — raises ValueError exactly when `compatible` says False
    (the drift-test contract; tests/test_pallas_kernels.py).  Returns
    the fitted (block_q, block_k)."""
    bq0, bk0 = min(block_q, sq), min(block_k, sk)
    bq = fit_block(block_q, sq)
    bk = fit_block(block_k, sk)
    if (bq != bq0 and bq < 128) or (bk != bk0 and bk < 128):
        raise ValueError(f"seq lens ({sq},{sk}) fit no lane-aligned block "
                         f"ladder (best: q={bq}, k={bk}); pad via "
                         f"the bucket ladder or pass block_q/block_k "
                         f"explicitly")
    if d % 128:
        raise ValueError(f"head dim {d} is not lane-aligned (% 128); "
                         f"pass block_q/block_k explicitly to opt out of "
                         f"the default geometry")
    return bq, bk


def check_shapes(q_shape, k_shape):
    """`check_default_shapes` of [b, s, h, d] operand shapes — the gate
    `ops.attention.flash_attention` hands to `resolve_route`."""
    return check_default_shapes(q_shape[1], k_shape[1], q_shape[-1])


def compatible(q_shape, k_shape) -> bool:
    """Will the public entry accept these [b, s, h, d] shapes under the
    DEFAULT block geometry?  Implemented AS the entry validation so the
    auto-route gate (`ops.attention.flash_attention`) can never drift
    from what the kernel accepts."""
    try:
        check_shapes(q_shape, k_shape)
        return True
    except ValueError:
        return False


def _mask(s, q_pos, k_pos, q_seg, k_seg, causal):
    """Combined causal+segment mask for one (Bq, Bk) score tile."""
    m = None
    if causal:
        m = q_pos[:, None] >= k_pos[None, :]
    if q_seg is not None:
        seg = q_seg[:, None] == k_seg[None, :]
        m = seg if m is None else (m & seg)
    if m is not None:
        s = jnp.where(m, s, NEG_INF)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(qi_ref, ki_ref, first_ref, last_ref, valid_ref,
                qpos_ref, kpos_ref, qseg_ref, kseg_ref, q_ref, k_ref, v_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale, causal,
                use_seg):
    t = pl.program_id(2)

    @pl.when(first_ref[t] == 1)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(valid_ref[t] == 1)  # dummy tiles of all-dead rows: init+fin only
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [Bq, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [Bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = qpos_ref[0, 0]
        k_pos = kpos_ref[0, 0]
        q_seg = qseg_ref[0, 0] if use_seg else None
        k_seg = kseg_ref[0, 0] if use_seg else None
        s = _mask(s, q_pos, k_pos, q_seg, k_seg, causal)

        m_prev = m_scr[:]                               # [Bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # fully-masked row: m_new == NEG_INF and exp(s - m_new) would be 1;
        # shift the reference point so p underflows to 0 instead
        m_exp = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - m_exp)                          # [Bq, Bk]
        corr = jnp.exp(m_prev - m_new)                  # [Bq, 1]
        l_new = l_scr[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)             # [Bk, d]
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(last_ref[t] == 1)
    def _fin():
        l = l_scr[:]
        # rows with no visible key (l==0) output 0, lse = -inf-ish
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = (m_scr[:] + jnp.log(l_safe))[:, 0]
        lse_ref[0, 0, 0] = jnp.where(l[:, 0] == 0.0, NEG_INF, lse)


def _fwd(q, k, v, q_pos, k_pos, q_seg, k_seg, *, scale, causal,
         block_q, block_k, block_mask: Optional[BlockMask] = None,
         debug=False):
    """q: [b, hq, sq, d]; k/v: [b, hkv, sk, d]; positions/segments: [b, s].
    `block_mask` is a static live-tile grid; dead tiles are never scheduled.
    Returns (o [b,hq,sq,d], lse [b,hq,sq])."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq lens ({sq},{sk}) must divide by blocks "
                         f"({block_q},{block_k})")
    nq, nk = sq // block_q, sk // block_k
    use_seg = q_seg is not None
    if not use_seg:
        q_seg = jnp.zeros((b, sq), jnp.int32)
        k_seg = jnp.zeros((b, sk), jnp.int32)
    if block_mask is None:
        block_mask = full_block_mask(sq, sk, block_q, block_k)
    _check_mask(block_mask, nq, nk)
    qi_m, ki_m, first, last, valid = _pair_tables(block_mask)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, use_seg=use_seg)

    q_pos = q_pos.reshape(b, 1, sq)
    k_pos = k_pos.reshape(b, 1, sk)
    q_seg = q_seg.reshape(b, 1, sq)
    k_seg = k_seg.reshape(b, 1, sk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, hq, len(qi_m)),
        in_specs=[
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, t, qm, km, *_: (bi, 0, qm[t])),
            pl.BlockSpec((1, 1, block_k),
                         lambda bi, hi, t, qm, km, *_: (bi, 0, km[t])),
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, t, qm, km, *_: (bi, 0, qm[t])),
            pl.BlockSpec((1, 1, block_k),
                         lambda bi, hi, t, qm, km, *_: (bi, 0, km[t])),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, t, qm, km, *_: (bi, hi, qm[t], 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, t, qm, km, *_:
                         (bi, hi // group, km[t], 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, t, qm, km, *_:
                         (bi, hi // group, km[t], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, t, qm, km, *_: (bi, hi, qm[t], 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda bi, hi, t, qm, km, *_: (bi, hi, 0, qm[t])),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, 1, sq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        debug=debug,
        interpret=_interpret(),
    )(qi_m, ki_m, first, last, valid, q_pos, k_pos, q_seg, k_seg, q, k, v)
    return o, lse.reshape(b, hq, sq)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(qi_ref, ki_ref, first_ref, last_ref, valid_ref,
                   qpos_ref, kpos_ref, qseg_ref, kseg_ref, q_ref, k_ref,
                   v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *,
                   scale, causal, use_seg):
    t = pl.program_id(2)

    @pl.when(first_ref[t] == 1)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(valid_ref[t] == 1)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0][:, None]                 # [Bq,1]
        lse = jnp.where(lse <= NEG_INF / 2, 0.0, lse)   # masked-row guard
        delta = delta_ref[0, 0, 0][:, None]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = qpos_ref[0, 0]
        k_pos = kpos_ref[0, 0]
        q_seg = qseg_ref[0, 0] if use_seg else None
        k_seg = kseg_ref[0, 0] if use_seg else None
        s = _mask(s, q_pos, k_pos, q_seg, k_seg, causal)
        p = jnp.exp(s - lse)                            # [Bq, Bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                           # [Bq, Bk]
        dq_scr[:] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(last_ref[t] == 1)
    def _fin():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(ki_ref, qi_ref, first_ref, last_ref, valid_ref,
                    qpos_ref, kpos_ref, qseg_ref, kseg_ref, q_ref, k_ref,
                    v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    dk_scr, dv_scr, *, scale, causal, use_seg):
    t = pl.program_id(2)

    @pl.when(first_ref[t] == 1)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(valid_ref[t] == 1)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0][:, None]
        lse = jnp.where(lse <= NEG_INF / 2, 0.0, lse)   # masked-row guard
        delta = delta_ref[0, 0, 0][:, None]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = qpos_ref[0, 0]
        k_pos = kpos_ref[0, 0]
        q_seg = qseg_ref[0, 0] if use_seg else None
        k_seg = kseg_ref[0, 0] if use_seg else None
        s = _mask(s, q_pos, k_pos, q_seg, k_seg, causal)
        p = jnp.exp(s - lse)                            # [Bq, Bk]
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[:] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(last_ref[t] == 1)
    def _fin():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, q_pos, k_pos, q_seg, k_seg, *, scale, causal,
         block_q, block_k, block_mask: Optional[BlockMask] = None,
         delta=None):
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq lens ({sq},{sk}) must divide by blocks "
                         f"({block_q},{block_k})")
    use_seg = q_seg is not None
    if not use_seg:
        q_seg = jnp.zeros((b, sq), jnp.int32)
        k_seg = jnp.zeros((b, sk), jnp.int32)
    if block_mask is None:
        block_mask = full_block_mask(sq, sk, block_q, block_k)
    _check_mask(block_mask, sq // block_q, sk // block_k)

    if delta is None:  # loop-invariant for ring callers — pass it in
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    q_pos = q_pos.reshape(b, 1, sq)
    k_pos = k_pos.reshape(b, 1, sk)
    q_seg = q_seg.reshape(b, 1, sq)
    k_seg = k_seg.reshape(b, 1, sk)
    lse4 = lse.reshape(b, hq, 1, sq)
    delta4 = delta.reshape(b, hq, 1, sq)

    # dq: rows = q blocks, inner walk over that row's live k blocks
    qi_m, ki_m, first, last, valid = _pair_tables(block_mask)
    dq_grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, hq, len(qi_m)),
        in_specs=[
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, t, qm, km, *_: (bi, 0, qm[t])),
            pl.BlockSpec((1, 1, block_k),
                         lambda bi, hi, t, qm, km, *_: (bi, 0, km[t])),
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, t, qm, km, *_: (bi, 0, qm[t])),
            pl.BlockSpec((1, 1, block_k),
                         lambda bi, hi, t, qm, km, *_: (bi, 0, km[t])),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, t, qm, km, *_: (bi, hi, qm[t], 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, t, qm, km, *_:
                         (bi, hi // group, km[t], 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, t, qm, km, *_:
                         (bi, hi // group, km[t], 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, t, qm, km, *_: (bi, hi, qm[t], 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda bi, hi, t, qm, km, *_: (bi, hi, 0, qm[t])),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda bi, hi, t, qm, km, *_: (bi, hi, 0, qm[t])),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, t, qm, km, *_:
                               (bi, hi, qm[t], 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          use_seg=use_seg),
        grid_spec=dq_grid,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(qi_m, ki_m, first, last, valid,
      q_pos, k_pos, q_seg, k_seg, q, k, v, do, lse4, delta4)

    # dk/dv per Q HEAD (rows = k blocks, inner walk over live q blocks), then
    # group-summed to kv heads outside.
    ki_t, qi_t, first_t, last_t, valid_t = _pair_tables(
        _transpose_mask(block_mask))
    dkv_grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, hq, len(ki_t)),
        in_specs=[
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, t, km, qm, *_: (bi, 0, qm[t])),
            pl.BlockSpec((1, 1, block_k),
                         lambda bi, hi, t, km, qm, *_: (bi, 0, km[t])),
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, t, km, qm, *_: (bi, 0, qm[t])),
            pl.BlockSpec((1, 1, block_k),
                         lambda bi, hi, t, km, qm, *_: (bi, 0, km[t])),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, t, km, qm, *_: (bi, hi, qm[t], 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, t, km, qm, *_:
                         (bi, hi // group, km[t], 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, t, km, qm, *_:
                         (bi, hi // group, km[t], 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, t, km, qm, *_: (bi, hi, qm[t], 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda bi, hi, t, km, qm, *_: (bi, hi, 0, qm[t])),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda bi, hi, t, km, qm, *_: (bi, hi, 0, qm[t])),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, t, km, qm, *_: (bi, hi, km[t], 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, t, km, qm, *_: (bi, hi, km[t], 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          use_seg=use_seg),
        grid_spec=dkv_grid,
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, sk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(ki_t, qi_t, first_t, last_t, valid_t,
      q_pos, k_pos, q_seg, k_seg, q, k, v, do, lse4, delta4)

    if group > 1:
        dk = dk.reshape(b, hkv, group, sk, d).sum(axis=2)
        dv = dv.reshape(b, hkv, group, sk, d).sum(axis=2)
    # fp32 out — single-device callers cast once; the ring accumulates fp32
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API (custom VJP)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _flash(q, k, v, q_pos, k_pos, q_seg, k_seg, scale, causal, block_q,
           block_k, block_mask):
    o, _ = _fwd(q, k, v, q_pos, k_pos, q_seg, k_seg, scale=scale,
                causal=causal, block_q=block_q, block_k=block_k,
                block_mask=block_mask)
    return o


def _flash_fwd(q, k, v, q_pos, k_pos, q_seg, k_seg, scale, causal, block_q,
               block_k, block_mask):
    o, lse = _fwd(q, k, v, q_pos, k_pos, q_seg, k_seg, scale=scale,
                  causal=causal, block_q=block_q, block_k=block_k,
                  block_mask=block_mask)
    # the two residuals only this launch can make, named HERE for the
    # "dots_attn" remat policy (nn/remat.py): a name on `_flash`'s result
    # outside is another variable and saves neither, and a checkpointed
    # block's backward would launch `_fwd` again to make them.  `o` goes
    # out as the primal result under the same name: one variable
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, o, lse, q_pos, k_pos, q_seg, k_seg)


def _flash_bwd(scale, causal, block_q, block_k, block_mask, res, do):
    q, k, v, o, lse, q_pos, k_pos, q_seg, k_seg = res
    dq, dk, dv = _bwd(q, k, v, o, lse, do, q_pos, k_pos, q_seg, k_seg,
                      scale=scale, causal=causal, block_q=block_q,
                      block_k=block_k, block_mask=block_mask)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None, None, None)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    segment_ids: Optional[jnp.ndarray] = None,
                    kv_segment_ids: Optional[jnp.ndarray] = None,
                    q_positions: Optional[jnp.ndarray] = None,
                    kv_positions: Optional[jnp.ndarray] = None,
                    softmax_scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    block_mask: Optional[BlockMask] = None):
    """Flash attention. q/k/v: [batch, seq, heads, head_dim] (kv heads may
    divide q heads — GQA). segment_ids: [batch, seq] packed-batch ids
    (0 = pad); positions: [batch, seq] global positions for causal masking.
    Defaults: kv = arange(sk); q = arange(sq) + (sk - sq), i.e. BOTTOM-RIGHT
    causal alignment for sq != sk (the HF convention) — pass explicit
    positions under CP or for other alignments. `block_mask` (static
    [nq][nk] bool grid) overrides the scheduled-tile set; by default causal
    attention with contiguous positions schedules only at-or-below-diagonal
    tiles. Returns [b, s, hq, d]."""
    b, sq, hq, d = q.shape
    sk = k.shape[1]
    default_blocks = block_q == DEFAULT_BLOCK_Q and block_k == DEFAULT_BLOCK_K
    if default_blocks:
        # under the DEFAULT ladder, a shrink below lane alignment (or an
        # unaligned head dim) means the shape fits no reasonable tile —
        # reject via the shared validation (`check_default_shapes`, the
        # same predicate the auto-route gate evaluates).  An EXPLICIT
        # caller block choice is honored at whatever divisor fit_block
        # lands on (the caller opted out of the default geometry).
        block_q, block_k = check_default_shapes(sq, sk, d)
    else:
        block_q = fit_block(block_q, sq)
        block_k = fit_block(block_k, sk)
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    # contiguous positions on both sides -> tiles above the diagonal are
    # never scheduled (the causal 2x), fwd AND bwd
    if block_mask is None and causal and q_positions is None \
            and kv_positions is None:
        block_mask = causal_block_mask(sq, sk, block_q, block_k)
    # [b, s, h, d] -> [b, h, s, d]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if q_positions is None:
        # bottom-right causal alignment for sq != sk (queries are the LAST
        # sq positions — the HF / reference-attention convention)
        q_positions = jnp.broadcast_to(
            jnp.arange(sq, dtype=jnp.int32) + (sk - sq), (b, sq))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32), (b, sk))
    if segment_ids is not None and kv_segment_ids is None:
        kv_segment_ids = segment_ids
    o = _flash(qt, kt, vt, q_positions.astype(jnp.int32),
               kv_positions.astype(jnp.int32),
               segment_ids.astype(jnp.int32) if segment_ids is not None else None,
               kv_segment_ids.astype(jnp.int32) if kv_segment_ids is not None else None,
               scale, causal, block_q, block_k, block_mask)
    return o.transpose(0, 2, 1, 3)


def flash_attention_with_lse(q, k, v, *, causal: bool = True,
                             segment_ids=None, kv_segment_ids=None,
                             q_positions=None, kv_positions=None,
                             softmax_scale: Optional[float] = None,
                             block_q: int = DEFAULT_BLOCK_Q,
                             block_k: int = DEFAULT_BLOCK_K,
                             block_mask: Optional[BlockMask] = None) -> Tuple:
    """Forward-only variant returning (out [b,s,h,d], lse [b,h,s]) for the
    ring-attention merge. Differentiation is handled by the ring layer."""
    b, sq, hq, d = q.shape
    sk = k.shape[1]
    bq0, bk0 = min(block_q, sq), min(block_k, sk)
    block_q = fit_block(block_q, sq)
    block_k = fit_block(block_k, sk)
    if (block_q != bq0 and block_q < 128) or (block_k != bk0 and block_k < 128):
        raise ValueError(f"seq lens ({sq},{sk}) fit no lane-aligned block "
                         f"ladder; pad via the bucket ladder")
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    if block_mask is None and causal and q_positions is None \
            and kv_positions is None:
        block_mask = causal_block_mask(sq, sk, block_q, block_k)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if q_positions is None:
        q_positions = jnp.broadcast_to(
            jnp.arange(sq, dtype=jnp.int32) + (sk - sq), (b, sq))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32), (b, sk))
    if segment_ids is not None and kv_segment_ids is None:
        kv_segment_ids = segment_ids
    o, lse = _fwd(qt, kt, vt, q_positions.astype(jnp.int32),
                  kv_positions.astype(jnp.int32),
                  segment_ids.astype(jnp.int32) if segment_ids is not None else None,
                  kv_segment_ids.astype(jnp.int32) if kv_segment_ids is not None else None,
                  scale=scale, causal=causal, block_q=block_q, block_k=block_k,
                  block_mask=block_mask)
    return o.transpose(0, 2, 1, 3), lse
