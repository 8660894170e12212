"""The selective scan of a Mamba-1 layer (`nn/mamba.MambaMixer`: the
Mamba layers of models/phi4_flash and of models/jamba): the walk over a
block of positions (prefill) and the single-position update (decode).

Which entry runs what: `recurrence` and `step` are XLA compositions on
every backend (`step` is the decode program's; it stands at 59-87% of
its state's bytes and has no kernel).  `chunk_scan` is the ONE entry of
the walk and asks `ops.pallas.resolve_route("selective_scan")`: on a TPU,
for the shapes its gate takes, `ops/pallas/selective_scan.py` walks the
chunk inside one launch with the state in VMEM (PR 52); every other
backend and shape runs the XLA composition `_chunk_scan_xla` below, the
form the kernel is tested against and its backward pass.

Per channel d of `d_inner` and state lane n of `d_state`, state h in
R^{N x D} (float32, the channels in the LANES: [16, 5120] tiles whole,
[5120, 16] would be padded eightfold), per position t an input u_t and a
step Delta_t > 0 a channel, B_t and C_t a state lane, A < 0 a channel and
lane:

    h_t = exp(Delta_t A) * h_{t-1} + (Delta_t u_t) B_t^T
    y_t = C_t^T h_t + D * u_t

The recurrence is DIAGONAL: a decay a channel and lane, no matrix a head,
so nothing of it is a matrix product.  `chunk_scan` walks the positions
in order (the composition `BLOCK` of them a loop iteration: the body is
that many steps written out, so that the state stays on the chip between
them and the loop's own cost is paid once a block); it computes exactly
what `recurrence`, position by position, defines.

A position whose Delta is 0 leaves the state as it found it (exp(0) = 1,
nothing added): that is how a chunk's padding rows (`valid`) and a decode
pass's idle slots (`live`) are masked.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
#: positions a loop iteration of `chunk_scan` walks
BLOCK = 16


def _advance(h, u, delta, A, B, C):
    """One position: h [b, N, D]; u, delta [b, D]; B, C [b, N]; A [N, D].
    -> (y [b, D] without the skip term, h')."""
    h = jnp.exp(delta[:, None, :] * A) * h \
        + (delta * u)[:, None, :] * B[:, :, None]
    return jnp.sum(h * C[:, :, None], axis=1), h


def recurrence(h, u, delta, A, B, C, D):
    """The definition, position by position: h [b, N, D] float32; u,
    delta [b, s, D]; B, C [b, s, N]; A [N, D]; D [D].
    -> (y [b, s, D] float32, h')."""
    u, delta, B, C = (x.astype(F32) for x in (u, delta, B, C))

    def one(h, x):
        y, h = _advance(h, *x[:2], A, *x[2:])
        return h, y
    h, y = lax.scan(one, h.astype(F32), tuple(
        jnp.moveaxis(x, 1, 0) for x in (u, delta, B, C)))
    return jnp.moveaxis(y, 0, 1) + D.astype(F32) * u, h


def chunk_scan(h, u, delta, A, B, C, D, valid=None):
    """`recurrence` over a chunk of s positions a row, of which the
    first valid[b] (default: all) are the sequence's: the rest leave the
    state alone (their y is not the sequence's and is finite).
    -> (y [b, s, D] float32, h').

    **The one entry, two routes** (`ops.pallas.resolve_route(
    "selective_scan")`, recorded in `kernel_routes`): on a TPU, for the
    shapes the kernel's gate takes (float32 state, d_state a multiple of
    8, d_inner of the kernel's lanes, s of its positions),
    `ops/pallas/selective_scan.py` under the scope
    `pallas_selective_scan`; everywhere else the XLA composition below,
    which is also what the kernel is tested against and what its
    backward pass is."""
    from hetu_tpu.ops import pallas as _pl
    from hetu_tpu.ops.pallas import selective_scan as _ss
    if _pl.resolve_route("selective_scan", _ss.check_shapes, h.shape,
                         u.shape, B.shape, state_dtype=h.dtype):
        if valid is None:
            valid = jnp.full((u.shape[0],), u.shape[1], jnp.int32)
        with jax.named_scope("pallas_selective_scan"):
            return _chunk_scan_kernel(h, u, delta, A, B, C, D, valid)
    return _chunk_scan_xla(h, u, delta, A, B, C, D, valid)


@jax.custom_vjp
def _chunk_scan_kernel(h, u, delta, A, B, C, D, valid):
    """The kernel forward; backward, the composition's (the serving
    programs never differentiate; `MambaMixer.forward` may)."""
    from hetu_tpu.ops.pallas import selective_scan as _ss
    return _ss.selective_scan(h, u, delta, A, B, C, D, valid)


def _kernel_fwd(*args):
    return _chunk_scan_kernel(*args), args


def _kernel_bwd(args, ct):
    *x, valid = args
    _, pull = jax.vjp(lambda *x: _chunk_scan_xla(*x, valid), *x)
    return (*pull(ct), None)


_chunk_scan_kernel.defvjp(_kernel_fwd, _kernel_bwd)


def _chunk_scan_xla(h, u, delta, A, B, C, D, valid):
    """`chunk_scan` as an XLA composition: a loop of `BLOCK` positions
    written out a trip."""
    b, s = u.shape[:2]
    u, delta, B, C = (x.astype(F32) for x in (u, delta, B, C))
    if valid is not None:
        delta = jnp.where(jnp.arange(s)[None, :, None]
                          < valid[:, None, None], delta, 0.0)
    T = math.gcd(s, BLOCK)

    def block(h, x):
        # x: each [T, b, ...]
        ys = []
        for t in range(T):
            y, h = _advance(h, x[0][t], x[1][t], A, x[2][t], x[3][t])
            ys.append(y)
        return h, jnp.stack(ys)
    h, y = lax.scan(block, h.astype(F32), tuple(
        jnp.moveaxis(x, 1, 0).reshape((s // T, T, b) + x.shape[2:])
        for x in (u, delta, B, C)))
    y = jnp.moveaxis(y.reshape((s, b) + y.shape[3:]), 0, 1)
    return y + D.astype(F32) * u, h


def step(h, u, delta, A, B, C, D, live=None):
    """One position a row: u, delta [b, D]; B, C [b, N]; rows where
    `live` [b] is False leave their state as it is.
    -> (y [b, D] float32, h')."""
    u, delta, B, C = (x.astype(F32) for x in (u, delta, B, C))
    if live is not None:
        delta = jnp.where(live[:, None], delta, 0.0)
    y, h = _advance(h.astype(F32), u, delta, A, B, C)
    return y + D.astype(F32) * u, h
