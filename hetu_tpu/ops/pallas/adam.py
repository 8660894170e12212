"""Fused AdamW update Pallas kernel (the optimizer's HBM diet.

Rebuild of the reference's fused Adam (reference:
hetu/impl/kernel/Optimizers.cu — one kernel reads p/g/m/v and writes
p'/m'/v').  The XLA lowering of `optim/optimizer.AdamW.update` is a
per-leaf chain of elementwise ops; XLA fuses most of it, but the
observatory's traffic model (ops/pallas/traffic.py) still charges the
chain its materialized intermediates (mhat, vhat, the decay product),
and the fused kernel pins the floor: read p+g+m+v once, write
p'+m'+v' once, nothing else.

The math is EXACTLY the optimizer's (f32 master arithmetic, bias
corrections c1/c2 computed OUTSIDE and passed as traced scalars with
the lr, so schedules stay host-side closures): m' = b1*m + (1-b1)*g;
v' = b2*v + (1-b2)*g^2; p' = p - lr*((m'/c1)/(sqrt(v'/c2)+eps) +
wd*p).  b1/b2/eps/wd are static (they pick the compiled kernel, like
every other hyperparameter-shaped knob).

Shape contract (drift-tested against `compatible`): the four leaf
buffers share one shape whose element count is lane-aligned (% 128) and
whose lane rows block legally (<= 256 rows, or a multiple-of-8 divisor);
ragged leaves (biases, norm gains) keep the XLA path.

**`auto` does not route this kernel** (`ops/pallas.AUTO_KEEPS_XLA`,
PR 39); it runs where `HETU_TPU_PALLAS=1` forces it.  The kernel sees a
leaf as `[n/128, 128]`, and on a TPU that reshape is no view: an array
is tiled `T(8,128)` over its LAST TWO dimensions, so
`[2, 4096, 28672] -> [1835008, 128]` is a COPY of the leaf, for p, g, m
and v on the way in and p', m', v' on the way out (seven `reshape`
instructions a leaf in the optimized HLO).  The kernel proper ran at
~90% of its bytes' floor, the copies took three quarters of the
`pallas_adam` scope, and XLA's chain, which reads a leaf where it lies
and is ONE fusion with the rescale that makes the gradient, was the
faster in both train cells (PERF.md s6, PR 39).  What would let a
kernel win: the leaf taken in its own layout AND the rescale's factor
as a fourth scalar (ROADMAP queue 1 item 3)."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.pallas import _interpret, fit_sublane_block

#: leaf rows (of 128 lanes) handled per grid step
_ROWS = 256


def check_shapes(p_shape, g_shape, m_shape, v_shape) -> int:
    shapes = (tuple(p_shape), tuple(g_shape), tuple(m_shape),
              tuple(v_shape))
    if len(set(shapes)) != 1:
        raise ValueError(f"p/g/m/v shapes must match, got {shapes}")
    n = 1
    for d in p_shape:
        n *= int(d)
    if n == 0 or n % 128:
        raise ValueError(f"leaf of {n} elements is not lane-aligned "
                         f"(% 128); the XLA update handles it")
    if not fit_sublane_block(n // 128, _ROWS):
        raise ValueError(f"leaf of {n // 128} lane rows has no "
                         f"sublane-aligned (% 8) row block; the XLA update "
                         f"handles it")
    return n


def compatible(p_shape, g_shape=None, m_shape=None, v_shape=None) -> bool:
    g_shape = p_shape if g_shape is None else g_shape
    m_shape = p_shape if m_shape is None else m_shape
    v_shape = p_shape if v_shape is None else v_shape
    try:
        check_shapes(p_shape, g_shape, m_shape, v_shape)
        return True
    except ValueError:
        return False


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                 np_ref, nm_ref, nv_ref, *, b1, b2, eps, wd):
    lr = sc_ref[0, 0]
    c1 = sc_ref[0, 1]
    c2 = sc_ref[0, 2]
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * jnp.square(g)
    mhat = m / c1
    vhat = v / c2
    pf = p_ref[...].astype(jnp.float32)
    newp = pf - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * pf)
    np_ref[...] = newp.astype(np_ref.dtype)
    nm_ref[...] = m
    nv_ref[...] = v


def adam_update(p, g, m, v, lr, c1, c2, *, b1: float, b2: float,
                eps: float, weight_decay: float
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One leaf's fused AdamW step -> (new_p, new_m, new_v).  lr/c1/c2
    are traced f32 scalars (step-dependent); b1/b2/eps/weight_decay are
    static.  Raises ValueError on shapes outside `compatible`."""
    n = check_shapes(p.shape, g.shape, m.shape, v.shape)
    nb = n // 128
    rows = fit_sublane_block(nb, _ROWS)
    sc = jnp.stack([jnp.asarray(lr, jnp.float32),
                    jnp.asarray(c1, jnp.float32),
                    jnp.asarray(c2, jnp.float32)]).reshape(1, 3)
    blk = pl.BlockSpec((rows, 128), lambda i: (i, 0))
    sc_blk = pl.BlockSpec((1, 3), lambda i: (0, 0))
    newp, newm, newv = pl.pallas_call(
        functools.partial(_adam_kernel, b1=float(b1), b2=float(b2),
                          eps=float(eps), wd=float(weight_decay)),
        grid=(nb // rows,),
        in_specs=[blk, blk, blk, blk, sc_blk],
        out_specs=[blk, blk, blk],
        out_shape=[jax.ShapeDtypeStruct((nb, 128), p.dtype),
                   jax.ShapeDtypeStruct((nb, 128), jnp.float32),
                   jax.ShapeDtypeStruct((nb, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
    )(p.reshape(nb, 128), g.reshape(nb, 128),
      m.astype(jnp.float32).reshape(nb, 128),
      v.astype(jnp.float32).reshape(nb, 128), sc)
    return (newp.reshape(p.shape), newm.reshape(p.shape),
            newv.reshape(p.shape))
