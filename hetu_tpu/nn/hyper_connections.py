"""Manifold-constrained hyper-connections (mHC): the residual path of a
block whose carry is a STREAM of `n` hidden vectors a token, not one.

Hyper-Connections (arXiv:2409.19606) widen the residual to n vectors and
let every sublayer read its input as a learned, input-dependent mix of
them and write its output back into each; mHC (arXiv:2512.24880) holds
the stream-to-stream matrix on the manifold of doubly stochastic matrices
by Sinkhorn-Knopp iterations, so that the stream's mean is carried
through any depth.  One sublayer F (attention or MLP, with its norm)
around the carry X [n, C] of a token, with the sublayer's own `phi`
[n C, n^2 + 2 n], `b` [n^2 + 2 n], `alpha` [3]:

    x^ = vec(X) / sqrt(mean(vec(X)^2) + rms_eps)            (over n C)
    [u_pre | u_post | u_res] = x^ phi                        (n, n, n^2)
    H_pre  = sigmoid(alpha_pre u_pre + b_pre)                [n]
    H_post = 2 sigmoid(alpha_post u_post + b_post)           [n]
    M      = exp(clamp(alpha_res mat(u_res) + b_res, lo, hi))  [n, n]
    `iters` times:  M <- M / (rowsum(M) + eps);  M <- M / (colsum(M) + eps)
    H_res  = M
    h  = sum_i H_pre[i] X[i]                                 (the PRE-mix)
    y  = F(h)
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y             (the POST-mix)

The coefficients (everything down to H_res) are float32, whatever the
stream's dtype; the stream is read 4-wide for them and for h, and read and
written 4-wide once for X'.  With n = 1 and H_pre = H_post = H_res = 1
this is `X + F(X)`.

A plain XLA composition (a fused kernel for the two mixes is ROADMAP's),
under three scopes a device trace is summed by (obs.scope_map):
`mhc_pre` (the norm, the product with phi, H_pre, H_post and the pre-mix),
`mhc_sinkhorn` (the exponential and the iterations) and `mhc_post`.  The
coefficients are computed with the ROWS on the minor dimension ([n, n,
rows]), so that the forty normalisations of a sublayer run over whole
lanes; the compiler makes ~83 small operations of them, whichever way
the sums over an axis of n are written (reductions, adds of slabs, entry
by entry: compiler, PR 55), 0.14 ms of a chunk program's 38.8 at 1,024
rows and 0.04 of a decode pass's 9.5 (my chip run, PR 55: PERF.md s5).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.module import Module, ParamSpec

F32 = jnp.float32


def sinkhorn(logits, iters: int, eps: float, clamp=(-30.0, 30.0)):
    """logits [n, n, ...] -> exp(clamp(logits)) after `iters` rounds of
    rows (axis 1 summed) then columns (axis 0 summed) normalised, `eps`
    in each denominator: doubly stochastic to the iterations' reach."""
    m = jnp.exp(jnp.clip(logits, *clamp))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def pre_mix(X, h_pre):
    """X [rows, n, C], h_pre [n, rows] -> h [rows, C] = sum_i h_pre[i]
    X[i], accumulated in float32, in the stream's dtype."""
    h = sum(h_pre[i][:, None] * X[:, i].astype(F32)
            for i in range(X.shape[1]))
    return h.astype(X.dtype)


def post_mix(X, y, h_post, h_res):
    """X [rows, n, C], y [rows, C], h_post [n, rows], h_res [n, n, rows]
    -> X' [rows, n, C], X'[i] = sum_j h_res[i, j] X[j] + h_post[i] y,
    accumulated in float32, in the stream's dtype."""
    n = X.shape[1]
    xs = [X[:, j].astype(F32) for j in range(n)]
    yf = y.astype(F32)
    return jnp.stack(
        [sum(h_res[i, j][:, None] * xs[j] for j in range(n))
         + h_post[i][:, None] * yf for i in range(n)],
        axis=1).astype(X.dtype)


#: how far H_pre's logits lean to the ONE stream a sublayer reads as a
#: model is initialised (`reading`; the configuration's
#: `assumed.mhc_init` says on what readings)
PRE_LEAN = 2.0


def reading(spec: ParamSpec, stream: int, n: int) -> ParamSpec:
    """The `b` of a connection whose pre-mix reads mostly `stream`:
    H_pre's logits + `PRE_LEAN` there and - `PRE_LEAN` on the others
    (Hyper-Connections initialises layer k to read stream k mod n; a
    sublayer whose input were the streams' mean would hardly see how
    H_res spreads the history over them, their sum being kept by any
    column-stochastic H_res)."""
    def leaning(key, shape, dtype):
        toward = 2.0 * (jnp.arange(n) == stream) - 1.0
        return spec.init(key, shape, dtype).at[:n].add(
            (PRE_LEAN * toward).astype(dtype))
    return dataclasses.replace(spec, init=leaning)


class HyperConnection(Module):
    """ONE sublayer's connection to a stream of `n` vectors of `hidden`."""

    def __init__(self, hidden: int, n: int, *, sinkhorn_iters: int,
                 eps: float, rms_eps: float, clamp=(-30.0, 30.0),
                 initializer_range: float = 0.02, logit_std: float = 0.5,
                 diagonal: float = 2.0, bias_range: float = 0.1):
        super().__init__()
        self.n = n
        self.iters, self.eps, self.rms_eps = sinkhorn_iters, eps, rms_eps
        self.clamp = tuple(float(c) for c in clamp)
        k = n * n + 2 * n
        self.param("phi", (n * hidden, k), init.normal(initializer_range),
                   dtype=F32)

        def bias(key, shape, dtype):
            # H_res leans to the identity: each stream mostly carried on
            lean = jnp.concatenate([jnp.zeros((2 * n,), dtype),
                                    diagonal * jnp.eye(n, dtype=dtype)
                                    .reshape(-1)])
            return lean + bias_range * jax.random.normal(key, shape, dtype)
        self.param("b", (k,), bias, dtype=F32)
        # x^ has unit RMS over n C values, so x^ phi has the standard
        # deviation initializer_range sqrt(n C): `alpha` brings the
        # input-dependent part of every logit to `logit_std`
        self.param("alpha", (3,), init.constant(
            logit_std / (initializer_range * math.sqrt(n * hidden))),
            dtype=F32)

    def coefficients(self, params, X, dtype=F32):
        """X [rows, n, C] -> (H_pre [n, rows], H_post [n, rows], H_res
        [n, n, rows]), in `dtype` (float32; a test asks for bfloat16 to
        show what that costs)."""
        n = self.n
        hi = lax.Precision.HIGHEST if dtype == F32 else None
        with jax.named_scope("mhc_pre"):
            x = X.reshape(X.shape[0], -1).astype(dtype)
            x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                              + jnp.asarray(self.rms_eps, dtype))
            # rows on the minor dimension from here on
            u = jnp.einsum("rk,kj->jr", x, params["phi"].astype(dtype),
                           precision=hi)
            alpha = params["alpha"].astype(dtype)
            b = params["b"].astype(dtype)[:, None]
            h_pre = jax.nn.sigmoid(alpha[0] * u[:n] + b[:n])
            h_post = 2.0 * jax.nn.sigmoid(alpha[1] * u[n:2 * n] + b[n:2 * n])
        with jax.named_scope("mhc_sinkhorn"):
            h_res = sinkhorn(
                (alpha[2] * u[2 * n:] + b[2 * n:]).reshape(n, n, -1),
                self.iters, self.eps, self.clamp)
        return h_pre, h_post, h_res

    def pre(self, params, X):
        """The carry X [..., n, C] -> (the sublayer's input h [..., C],
        what `post` takes)."""
        rows = X.reshape((-1,) + X.shape[-2:])
        h_pre, h_post, h_res = self.coefficients(params, rows)
        with jax.named_scope("mhc_pre"):
            h = pre_mix(rows, h_pre).reshape(X.shape[:-2] + X.shape[-1:])
        return h, (h_post, h_res)

    def post(self, mix, X, y):
        """The carry X [..., n, C] and the sublayer's output y [..., C]
        -> the carry behind the sublayer."""
        h_post, h_res = mix
        with jax.named_scope("mhc_post"):
            return post_mix(X.reshape((-1,) + X.shape[-2:]),
                            y.reshape(-1, y.shape[-1]), h_post,
                            h_res).reshape(X.shape)
