"""Fused SwiGLU combine Pallas kernel.

Rebuild of the reference's fused SwiGLU (reference:
hetu/impl/kernel/SwiGLU.cu): y = silu(gate) * up in ONE pass over the
[tokens, intermediate] pair, instead of the XLA chain (sigmoid ->
gate*sig -> *up) that round-trips the activation through HBM per op.
The backward is the fused derivative kernel:

    dgate = dy * up * sig * (1 + gate * (1 - sig))
    dup   = dy * gate * sig

computed from the SAVED (gate, up) pair — silu(gate) is recomputed in
VMEM rather than kept resident in HBM.

Shape contract (drift-tested against `compatible`): the last dim must be
lane-aligned (% 128) and the flattened leading dims must tile into
sublanes (% 8).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.pallas import _interpret
from hetu_tpu.ops.pallas.fused_norm import _fit_rows


def check_shapes(g_shape, u_shape) -> Tuple[int, int]:
    if tuple(g_shape) != tuple(u_shape):
        raise ValueError(f"gate/up shapes differ: {g_shape} vs {u_shape}")
    if len(g_shape) < 2:
        raise ValueError(f"need at least [tokens, inner], got {g_shape}")
    inner = g_shape[-1]
    tokens = 1
    for d in g_shape[:-1]:
        tokens *= d
    if inner % 128:
        raise ValueError(f"inner dim {inner} is not lane-aligned (% 128)")
    if tokens % 8:
        raise ValueError(f"token count {tokens} does not tile into "
                         f"sublanes (% 8)")
    return tokens, inner


def compatible(g_shape, u_shape=None) -> bool:
    try:
        check_shapes(g_shape, g_shape if u_shape is None else u_shape)
        return True
    except ValueError:
        return False


def _fwd_kernel(g_ref, u_ref, y_ref):
    g = g_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    y_ref[...] = (g * jax.nn.sigmoid(g) * u).astype(y_ref.dtype)


def _bwd_kernel(g_ref, u_ref, dy_ref, dg_ref, du_ref):
    g = g_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    sig = jax.nn.sigmoid(g)
    dg_ref[...] = (dy * u * sig * (1.0 + g * (1.0 - sig))).astype(
        dg_ref.dtype)
    du_ref[...] = (dy * g * sig).astype(du_ref.dtype)


def _run(kern, inputs, out_shapes, rows, inner, n):
    spec = pl.BlockSpec((rows, inner), lambda i: (i, 0))
    return pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[spec] * len(inputs),
        out_specs=[spec] * len(out_shapes) if len(out_shapes) > 1 else spec,
        out_shape=(out_shapes if len(out_shapes) > 1 else out_shapes[0]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
    )(*inputs)


@jax.custom_vjp
def _swiglu(gate, up):
    tokens, inner = check_shapes(gate.shape, up.shape)
    rows = _fit_rows(tokens, inner)
    y = _run(_fwd_kernel,
             (gate.reshape(tokens, inner), up.reshape(tokens, inner)),
             [jax.ShapeDtypeStruct((tokens, inner), gate.dtype)],
             rows, inner, tokens // rows)
    return y.reshape(gate.shape)


def _swiglu_fwd(gate, up):
    return _swiglu(gate, up), (gate, up)


def _swiglu_bwd(res, dy):
    gate, up = res
    shape = gate.shape
    inner = shape[-1]
    tokens = gate.size // inner
    rows = _fit_rows(tokens, inner)
    dg, du = _run(_bwd_kernel,
                  (gate.reshape(tokens, inner), up.reshape(tokens, inner),
                   dy.reshape(tokens, inner)),
                  [jax.ShapeDtypeStruct((tokens, inner), gate.dtype),
                   jax.ShapeDtypeStruct((tokens, inner), up.dtype)],
                  rows, inner, tokens // rows)
    return dg.reshape(shape), du.reshape(shape)


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def fused_swiglu(gate, up):
    """silu(gate) * up in one fused pass (custom-vjp backward included).
    Raises ValueError on shapes outside `compatible` — dispatchers fall
    back to the XLA composition."""
    return _swiglu(gate, up)
