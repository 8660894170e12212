"""Fused rotary-embedding Pallas kernel: RoPE applied to q AND k in one
pass (reference: hetu/impl/kernel/rotary.cu — the fused varlen rotary).

The XLA composition (`ops.rotary.apply_rotary` called once for q, once
for k) gathers the cos/sin tables twice and round-trips each half-split
product through HBM; this kernel reads the per-position cos/sin rows
ONCE and rotates both tensors in VMEM.  The rotation is linear, so the
custom-vjp backward is the SAME kernel with the sin table negated
(rotation by -theta) — no residuals beyond the tables.

Layout: q [b, s, nq, hd], k [b, s, nk, hd]; cos/sin arrive PRE-GATHERED
per (batch, position) as [b, s, hd//2] (the dispatcher in `ops.rotary`
does the position_ids lookup — one tiny gather feeding one fused pass).

Shape contract (drift-tested against `compatible`): hd must be even and
lane-aligned (% 128); b/s/heads are free.  s is row-blocked to a VMEM
budget in multiples of 8 (the sublane tile the TPU lowering wants of a
second-minor block dim); where no such block divides s the last one
overhangs, which is safe here because every row is rotated on its own:
the overhang's reads are never used and its writes are dropped."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.pallas import _interpret, fit_sublane_block

_VMEM_SEQ_BUDGET = 512 * 1024


def check_shapes(q_shape, k_shape) -> Tuple[int, int, int, int, int]:
    if len(q_shape) != 4 or len(k_shape) != 4:
        raise ValueError(f"expected [b, s, heads, hd], got {q_shape} / "
                         f"{k_shape}")
    b, s, nq, hd = q_shape
    if k_shape[0] != b or k_shape[1] != s or k_shape[3] != hd:
        raise ValueError(f"q/k disagree outside the head dim: {q_shape} "
                         f"vs {k_shape}")
    if hd % 2:
        raise ValueError(f"head dim {hd} must be even for the half-split "
                         f"rotation")
    if hd % 128:
        raise ValueError(f"head dim {hd} is not lane-aligned (% 128); "
                         f"the XLA fallback handles it")
    return b, s, nq, k_shape[2], hd


def compatible(q_shape, k_shape) -> bool:
    try:
        check_shapes(q_shape, k_shape)
        return True
    except ValueError:
        return False


def _fit_seq(s: int, width: int) -> int:
    """Row block of s keeping one f32 [S, width] buffer in budget.  The
    [b, s, hd/2] tables block on s as their second-minor axis, so S is s
    itself or a multiple of 8: one that divides s where there is one,
    else the largest in budget (the grid then overhangs s)."""
    cap = max(8, _VMEM_SEQ_BUDGET // max(width * 4, 1))
    return fit_sublane_block(s, cap) or cap - cap % 8


def _kernel(cos_ref, sin_ref, q_ref, k_ref, qo_ref, ko_ref, *, d2):
    cos = cos_ref[0][:, None, :]                       # [S, 1, hd/2]
    sin = sin_ref[0][:, None, :]

    def rot(x):
        xf = x.astype(jnp.float32)
        x1 = xf[..., :d2]
        x2 = xf[..., d2:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    qo_ref[...] = rot(q_ref[0])[None].astype(qo_ref.dtype)
    ko_ref[...] = rot(k_ref[0])[None].astype(ko_ref.dtype)


def _apply(q, k, cos_t, sin_t):
    b, s, nq, nk, hd = check_shapes(q.shape, k.shape)
    d2 = hd // 2
    S = _fit_seq(s, max(nq, nk) * hd)
    kern = functools.partial(_kernel, d2=d2)
    cs_spec = pl.BlockSpec((1, S, d2), lambda bi, si: (bi, si, 0))
    q_spec = pl.BlockSpec((1, S, nq, hd), lambda bi, si: (bi, si, 0, 0))
    k_spec = pl.BlockSpec((1, S, nk, hd), lambda bi, si: (bi, si, 0, 0))
    return pl.pallas_call(
        kern,
        grid=(b, pl.cdiv(s, S)),
        in_specs=[cs_spec, cs_spec, q_spec, k_spec],
        out_specs=[q_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret(),
    )(cos_t, sin_t, q, k)


@jax.custom_vjp
def _rotary_qk(q, k, cos_t, sin_t):
    return _apply(q, k, cos_t, sin_t)


def _rotary_fwd(q, k, cos_t, sin_t):
    return _apply(q, k, cos_t, sin_t), (cos_t, sin_t)


def _rotary_bwd(res, cts):
    cos_t, sin_t = res
    dqo, dko = cts
    # rotation is orthogonal: the vjp rotates the cotangents by -theta
    dq, dk = _apply(dqo, dko, cos_t, -sin_t)
    return dq, dk, None, None


_rotary_qk.defvjp(_rotary_fwd, _rotary_bwd)


def fused_rotary_qk(q, k, cos_t, sin_t):
    """Rotate q [b,s,nq,hd] and k [b,s,nk,hd] by the pre-gathered
    per-position tables cos_t/sin_t [b,s,hd//2] in one fused pass.
    Raises ValueError on shapes outside `compatible`."""
    return _rotary_qk(q, k, cos_t, sin_t)
