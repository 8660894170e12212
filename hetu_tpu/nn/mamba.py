"""The Mamba-1 mixer, once, for every family that has one
(models/phi4_flash, models/jamba).  Imported by those models only:
`hetu_tpu.nn` does not re-export it, so no other family's engine pays
for the import.

x one token's normed hidden state:

    [u, z] = x W_in;  u' = silu(conv_K(u) + b_c), causal and depthwise;
    [dt_r, B_t, C_t] = u' W_x;
    (`inner_norms`, Jamba's: dt_r, B_t and C_t each through an RMSNorm
     with a learned gain over its own width, eps `norm_eps`)
    Delta_t = softplus(dt_r W_dt + b_dt);  A = -exp(A_log);
    h_t = exp(Delta_t A) h_{t-1} + (Delta_t u'_t) B_t^T;
    y_t = h_t C_t + D u'_t;  out = W_out [y_t * silu(z_t)].

**A SEQUENCE's cache is h (float32, [d_state, d_inner]: the channels in
the lanes) and the convolution's last K - 1 inputs; a token stores
nothing** (`state_shapes`, which the model's cache contract repeats):
`state_chunk` / `state_step`, the hooks of models/generation.py, over
`ops/selective_scan`.  The hooks return y_t third, for a model whose
later layers read it (Phi-4-flash's gated memory units).

Scopes, inside the layer's own (`attn` > `ssm`): `ssm_proj` (W_in, W_x,
W_dt), `ssm_conv`, `ssm_norm` (the three inner norms, where the family
has them), `ssm_scan` (a chunk; on a TPU `ssm_scan/pallas_selective_scan`,
the kernel of `ops/pallas/selective_scan.py`) / `ssm_step` (one position),
`ssm_out`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.module import Module
from hetu_tpu.ops import selective_scan

F32 = jnp.float32


def _dt_bias(key, shape, dtype=F32):
    """b_dt with softplus(b_dt) log-uniform in [0.001, 0.1], as Mamba
    initialises it."""
    dt = jnp.exp(jax.random.uniform(key, shape, F32)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log(key, shape, dtype=F32):
    """A_log [d_state, d_inner] = log(1 .. d_state) a channel."""
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[0] + 1, dtype=F32))[:, None],
        shape).astype(dtype)


def state_shapes(d_inner: int, d_state: int, d_conv: int, compute_dtype):
    """What a sequence stores in a Mamba layer, as a cache contract's
    `state_shapes` takes it: the float32 state, the channels in the
    lanes, and the convolution's last `d_conv` - 1 inputs."""
    return (((d_state, d_inner), "float32"),
            ((d_conv - 1, d_inner), jnp.dtype(compute_dtype).name))


class MambaMixer(Module):
    """The Mamba-1 mixer (module docstring).  Its hooks take the layer's
    whole attention: normed hidden states in, the residual's addend out,
    the sequence's state in and out, and the scan's output y third."""

    def __init__(self, hidden_size: int, d_inner: int, d_state: int,
                 d_conv: int, dt_rank: int, *, param_dtype, compute_dtype,
                 initializer_range: float = 0.02, inner_norms: bool = False,
                 norm_eps: float = 1e-6):
        super().__init__()
        self.d_inner, self.d_state, self.d_conv, self.dt_rank = (
            d_inner, d_state, d_conv, dt_rank)
        self.inner_norms, self.norm_eps = inner_norms, norm_eps
        self.state_shapes = state_shapes(d_inner, d_state, d_conv,
                                         compute_dtype)
        w = init.normal(initializer_range)
        h, di, N, K, R, dt = (hidden_size, d_inner, d_state, d_conv, dt_rank,
                              param_dtype)
        self.param("w_in", (h, 2 * di), w, dtype=dt)          # u | z
        # tap i multiplies the input i - (K - 1) positions back
        self.param("conv_w", (K, di), init.uniform(K ** -0.5), dtype=dt)
        self.param("conv_b", (di,), init.normal(0.1), dtype=dt)
        self.param("w_x", (di, R + 2 * N), w, dtype=dt)       # dt_r | B | C
        self.param("w_dt", (R, di), init.uniform(R ** -0.5), dtype=dt)
        # float32 whatever the model's dtype: the step is exponentiated
        # over thousands of positions
        self.param("dt_bias", (di,), _dt_bias, dtype=F32)
        self.param("A_log", (N, di), _a_log, dtype=F32)
        self.param("D", (di,), init.ones, dtype=F32)
        self.param("w_out", (di, h), w, dtype=dt)
        if inner_norms:
            # one gain vector: dt_r's R, then B's N, then C's N
            self.param("inner_norm", (R + 2 * N,), init.ones, dtype=dt)

    def _normed(self, params, x):
        """[dt_r | B | C] [.., R + 2 N], each part through its RMSNorm
        (float32 statistics, a learned gain), in x's dtype."""
        R, N = self.dt_rank, self.d_state
        with jax.named_scope("ssm_norm"):
            xf = x.astype(F32)
            parts = [xf[..., :R], xf[..., R: R + N], xf[..., R + N:]]
            parts = [p * lax.rsqrt(jnp.mean(jnp.square(p), -1, keepdims=True)
                                   + self.norm_eps) for p in parts]
            return (jnp.concatenate(parts, -1)
                    * params["inner_norm"].astype(F32)).astype(x.dtype)

    def _inputs(self, params, hn, conv):
        """hn [b, s, hidden], conv [b, K - 1, d_inner] (the inputs before
        the first position) -> (u' [b, s, di], z, Delta float32, B, C
        [b, s, N], the inputs [b, K - 1 + s, di])."""
        di, N, R, K = self.d_inner, self.d_state, self.dt_rank, self.d_conv
        s = hn.shape[1]
        with jax.named_scope("ssm_proj"):
            uz = hn @ params["w_in"].astype(hn.dtype)
            u, z = uz[..., :di], uz[..., di:]
        with jax.named_scope("ssm_conv"):
            xx = jnp.concatenate([conv.astype(u.dtype), u], axis=1)
            w = params["conv_w"].astype(F32)
            y = sum(w[i] * xx[:, i: i + s].astype(F32) for i in range(K))
            u1 = jax.nn.silu(y + params["conv_b"].astype(F32)).astype(
                hn.dtype)
        with jax.named_scope("ssm_proj"):
            x = u1 @ params["w_x"].astype(hn.dtype)
        if self.inner_norms:
            x = self._normed(params, x)
        with jax.named_scope("ssm_proj"):
            delta = jax.nn.softplus(
                (x[..., :R] @ params["w_dt"].astype(hn.dtype)).astype(F32)
                + params["dt_bias"])
        return u1, z, delta, x[..., R: R + N], x[..., R + N:], xx

    def _out(self, params, y, z):
        with jax.named_scope("ssm_out"):
            g = y * jax.nn.silu(z.astype(F32))
            return g.astype(z.dtype) @ params["w_out"].astype(z.dtype)

    # -- the hooks (models/generation.py) ---------------------------------
    def state_chunk(self, params, hn, state, start, valid):
        """hn [b, C, hidden] (normed); state = (h [b, N, di] float32,
        conv [b, K - 1, di]): the rows' own, as the last chunk left them
        (zeros where this is the first).  The first valid[b] positions
        are the sequence's; the rest are padding, which the scan leaves
        out of the state (`chunk_scan`'s `valid`), and the convolution's
        tail is taken where the valid rows end.
        -> (out [b, C, hidden], state', y [b, C, di] in hn's dtype)."""
        h, conv = state
        K = self.d_conv
        u1, z, delta, B, C, xx = self._inputs(params, hn, conv)
        with jax.named_scope("ssm_conv"):
            conv = jax.vmap(lambda a, n: lax.dynamic_slice_in_dim(
                a, n, K - 1, axis=0))(xx, valid).astype(conv.dtype)
        with jax.named_scope("ssm_scan"):
            y, h = selective_scan.chunk_scan(
                h, u1, delta, -jnp.exp(params["A_log"]), B, C, params["D"],
                valid=valid)
        return self._out(params, y, z), (h, conv), y.astype(hn.dtype)

    def state_step(self, params, hn, state, live):
        """One position a row: hn [b, 1, hidden]; rows where `live` [b]
        is False (idle slots) leave their state as it is.
        -> (out [b, 1, hidden], state', y [b, 1, di])."""
        h, conv = state
        u1, z, delta, B, C, xx = self._inputs(params, hn, conv)
        with jax.named_scope("ssm_conv"):
            conv = jnp.where(live[:, None, None], xx[:, 1:],
                             conv.astype(xx.dtype)).astype(conv.dtype)
        with jax.named_scope("ssm_step"):
            y, h = selective_scan.step(
                h, u1[:, 0], delta[:, 0], -jnp.exp(params["A_log"]),
                B[:, 0], C[:, 0], params["D"], live=live)
        y = y[:, None]
        return self._out(params, y, z), (h, conv), y.astype(hn.dtype)

    def zero_state(self, b: int, dtype):
        return tuple(jnp.zeros((b,) + shape, dt if dt == "float32" else dtype)
                     for shape, dt in self.state_shapes)

    def forward(self, params, hn):
        """Whole sequences hn [b, s, h] from zero state -> (out, y)."""
        b, s = hn.shape[:2]
        out, _, y = self.state_chunk(
            params, hn, self.zero_state(b, hn.dtype),
            jnp.zeros((b,), jnp.int32), jnp.full((b,), s, jnp.int32))
        return out, y
