"""Phi-4-mini-flash-reasoning (`phi4flash`, the SambaY decoder-hybrid-
decoder, arXiv:2507.06607): a SELF-decoder of (Mamba-1, window attention)
pairs that ends in one more Mamba layer and ONE full-attention layer, and
a CROSS-decoder of (gated memory unit, cross attention) pairs that keep no
cache of their own: the cross layers attend the full layer's keys and
values, the memory units gate the last Mamba layer's scan output.
Serving only: `ServingEngine` takes the model through the programs of
`models/generation.py`, by the hooks below; `Trainer` does not know it
(ROADMAP).

One layer, pre-norm (LayerNorm with bias), x one token's normed hidden
state; every layer ends in the same bias-free SwiGLU MLP; no positional
encoding of any kind; embedding and head tied.

* Mamba-1 (`MambaMixer`; layers 0, 2, .., L/2): [u, z] = x W_in; u' =
  silu(conv_K(u) + b_c), causal and depthwise; [dt_r, B_t, C_t] = u' W_x;
  Delta_t = softplus(dt_r W_dt + b_dt); A = -exp(A_log);
  h_t = exp(Delta_t A) h_{t-1} + (Delta_t u'_t) B_t^T; y_t = h_t C_t +
  D u'_t; out = W_out [y_t * silu(z_t)].  **A SEQUENCE's cache is h
  (float32, [d_state, d_inner]: the channels in the lanes) and the
  convolution's last K - 1 inputs; a token stores nothing** (the
  contract's `state_shapes`): `state_chunk` / `state_step`, over
  `ops/selective_scan`.  The hooks return y_t third: layer L/2, the
  block that says `hands_on`, hands it to the later layers as the MEMORY
  (before the gate).
* Gated memory unit (`GatedMemoryUnit`; layers L/2 + 2, + 4, ..):
  out = W_2 [m_t * silu(x W_1)], m_t the memory AT THE SAME TOKEN.  No
  recurrence, no cache (the contract's `NO_CACHE`): the `mix` hook.
* Differential attention (`DiffAttention`; arXiv:2410.05258): the query
  heads are PAIRS (q_1, q_2), the K/V heads pairs (k_1, k_2), (v_1, v_2),
  query pair i reading K/V pair i // group.  a_j = softmax(q_j k_j^T /
  sqrt(head_dim) + mask) [v_1 | v_2]; o = (1 - lambda_init) * RMSNorm(a_1
  - lambda a_2), lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
  lambda_init = 0.8 - 0.6 exp(-0.3 l) by the layer's index l; then W_o.
  Window layers see key j iff t - window < j <= t.  The cross layers have
  W_q and W_o only and attend the full layer's entries, causally.

  **To the kernels this is plain GQA.**  A K/V pair is one lane row
  [k_1 | k_2] (and [v_1 | v_2]) of 2 x head_dim = 128, and a pair's
  queries go out as two heads [q_1, 0] and [0, q_2] of the row's width:
  their scores against [k_1 | k_2] are q_1 . k_1 and q_2 . k_2, their
  values the row [v_1 | v_2], so `cache_contract.KVAttention` takes 2 x
  pairs query heads over the K/V pairs as it takes any grouped-query
  layer, at the scale head_dim^-1/2 (`softmax_scale`); the subtraction,
  the norm and the scale follow in `output` (scope `diff_out`).  Half of
  each product's multiplications are by zeros: the price of lane rows
  the kernels take.

  **What a token STORES** is those rows `kv_fold` to a stored row
  (`config.kv_row`: 2 rows of 640 lanes at the published sizes, 5 pairs
  each), because the device holds an array's second-minor dim in tiles
  of 1, 2, 4 or 8 rows: 10 rows of 128 would be held as 16, 60% more
  pool, and the paged kernel's page copies are refused at 10 (Mosaic:
  "slice shape must be aligned to tiling (8)"; the compile for the
  described chip, PR 43).  A dense cache (the chunk program, whole
  prompts) is read back as its pairs' rows, a reshape, and attended in
  the 128-wide form.  The PAGED pool is read where it lies: the decode
  step's queries are laid at their pair's place in a stored row's width
  ([0, .., q_1, 0, .., 0] of 640) and the pair's 128 values cut out of
  the 640 the kernel returns: the kernel's products are `kv_fold` times
  the 128-wide form's, its bytes the model's own.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from hetu_tpu.models.cache_contract import (NO_CACHE, CacheContract,
                                            KVAttention)
from hetu_tpu.models.phi4_flash.config import (CROSS, FULL, GMU, SSM, WINDOW,
                                               Phi4FlashConfig)
from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.module import Module, stack_param_specs
from hetu_tpu.nn.parallel import ParallelLayerNorm, VocabParallelEmbedding
from hetu_tpu.ops import selective_scan
from hetu_tpu.parallel.strategy import ParallelStrategy

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


class MambaMixer(Module):
    """The Mamba-1 mixer (module docstring).  Its hooks take the layer's
    whole attention: normed hidden states in, the residual's addend out,
    the sequence's state in and out, and the scan's output y third."""

    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.config = c = config
        w = init.normal(c.initializer_range)
        h, di, N, K, R, dt = (c.hidden_size, c.d_inner, c.mamba_d_state,
                              c.mamba_d_conv, c.mamba_dt_rank, c.param_dtype)
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

    def _inputs(self, params, hn, conv):
        """hn [b, s, hidden], conv [b, K - 1, d_inner] (the inputs before
        the first position) -> (u' [b, s, di], z, Delta float32, B, C
        [b, s, N], the inputs [b, K - 1 + s, di])."""
        c = self.config
        di, N, R, K = c.d_inner, c.mamba_d_state, c.mamba_dt_rank, \
            c.mamba_d_conv
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
        K = self.config.mamba_d_conv
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
                     for shape, dt in self.config.state_shapes)

    def forward(self, params, hn):
        """Whole sequences hn [b, s, h] from zero state -> (out, y)."""
        b, s = hn.shape[:2]
        out, _, y = self.state_chunk(
            params, hn, self.zero_state(b, hn.dtype),
            jnp.zeros((b,), jnp.int32), jnp.full((b,), s, jnp.int32))
        return out, y


class GatedMemoryUnit(Module):
    """out = W_2 [m * silu(x W_1)], m what the memory layer handed on
    (module docstring): the `mix` hook of a layer that keeps no cache."""

    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        c = config
        w = init.normal(c.initializer_range)
        self.param("w_1", (c.hidden_size, c.d_inner), w, dtype=c.param_dtype)
        self.param("w_2", (c.d_inner, c.hidden_size), w, dtype=c.param_dtype)

    def mix(self, params, hn, handed):
        g = jax.nn.silu((hn @ params["w_1"].astype(hn.dtype)).astype(F32))
        return (handed.astype(F32) * g).astype(hn.dtype) \
            @ params["w_2"].astype(hn.dtype)


class DiffAttention(KVAttention, Module):
    """Differential attention over the K/V pairs' rows (module
    docstring); `window` is how far back the layer reads (None:
    everything), `cross` a layer with W_q and W_o only, whose `project`
    makes no entries: it attends another layer's."""

    def __init__(self, config: Phi4FlashConfig, window: Optional[int],
                 cross: bool = False):
        Module.__init__(self)
        self.config = c = config
        self.window, self.cross = window, cross
        w = init.normal(c.initializer_range)
        nq, nkv, hd, dt = (c.num_attention_heads, c.num_key_value_heads,
                           c.head_dim, c.param_dtype)
        cols = nq * hd if cross else (nq + 2 * nkv) * hd
        self.param("w_qkv", (c.hidden_size, cols), w, dtype=dt)
        self.param("b_qkv", (cols,), w, dtype=dt)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            self.param(name, (hd,), init.normal(c.lambda_std), dtype=F32)
        # 0.8 - 0.6 exp(-0.3 l), by the layer's index: a buffer, set by
        # the model that knows the layer (`_with_lambda_init`)
        self.param("lambda_init", (), init.zeros, dtype=F32)
        self.param("subln", (2 * hd,), init.ones, dtype=dt)
        self.param("w_o", (nq * hd, c.hidden_size), w, dtype=dt)
        self.param("b_o", (c.hidden_size,), w, dtype=dt)

    def softmax_scale(self, width: int) -> float:
        # the queries are head_dim wide, laid in rows of 2 head_dim
        return self.config.head_dim ** -0.5

    def project(self, params, hn, rope, pos_ids):
        """hn [b, s, h] (normed) -> (q [b, s, nq, 2 hd]: a pair's two
        heads as [q_1, 0] and [0, q_2]; entries (k, v) [b, s, nkv / 2,
        2 hd], or () for a cross layer)."""
        c = self.config
        nq, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        x = hn @ params["w_qkv"].astype(hn.dtype) \
            + params["b_qkv"].astype(hn.dtype)
        lead = x.shape[:-1]
        q = x[..., :nq * hd].reshape(lead + (nq // 2, 2, hd))
        zero = jnp.zeros_like(q[..., 0, :])
        q = jnp.stack([jnp.concatenate([q[..., 0, :], zero], -1),
                       jnp.concatenate([zero, q[..., 1, :]], -1)],
                      axis=-2).reshape(lead + (nq, 2 * hd))
        if self.cross:
            return q, ()
        k = x[..., nq * hd: (nq + nkv) * hd].reshape(lead + c.kv_row)
        v = x[..., (nq + nkv) * hd:].reshape(lead + c.kv_row)
        return q, (k, v)

    def _pair_rows(self, arrays):
        """Stored rows [.., rows, fold * 2 hd] as the pairs' own
        [.., pairs, 2 hd]."""
        c = self.config
        return tuple(a.reshape(a.shape[:-2] + (c.kv_pairs, 2 * c.head_dim))
                     for a in arrays)

    def _place(self):
        """[nq, fold] one-hot: where in a stored row query head j's K/V
        pair lies (pair (j // 2) // group, fold to a row)."""
        c = self.config
        pair = (jnp.arange(c.num_attention_heads) // 2) // (
            c.num_attention_heads // c.num_key_value_heads)
        return jax.nn.one_hot(pair % c.kv_fold, c.kv_fold)

    def attend_prompt(self, params, q, entries, window=None):
        """Whole prompts over their own (or the full layer's) entries:
        the XLA composition (ops/pallas/flash_attention has no scale of
        the caller's)."""
        from hetu_tpu.models.generation import _attend_cached_chunk
        b, s, nq, hd = q.shape
        return _attend_cached_chunk(
            q, *self._pair_rows(entries), 0, self.softmax_scale(hd),
            window=window).reshape(b, s, -1)

    def attend_dense(self, params, q, caches, start, window=None, first=0):
        """A dense cache is read as its pairs' rows (module docstring)."""
        return super().attend_dense(params, q, self._pair_rows(caches),
                                    start, window=window, first=first)

    def attend_paged(self, params, q, pools, table, positions, base, *,
                     window=None):
        """The paged pool is read where it lies, in stored rows: q
        [S, 1, nq, 2 hd] laid at its pair's place in a stored row's
        width, the pair's 2 hd values cut out of what comes back.  Where
        the kernel is refused (`KVAttention.attend_paged`): the slot's
        pages gathered and read as the pairs' rows."""
        c = self.config
        S, C, nq, hd = q.shape
        if c.kv_fold == 1:
            return super().attend_paged(params, q, pools, table, positions,
                                        base, window=window)
        place = self._place().astype(q.dtype)                 # [nq, fold]
        wide = (q[..., None, :] * place[:, :, None]).reshape(
            S, C, nq, c.kv_fold * hd)
        if not self._paged_kernel_takes(params, wide, pools, table, window):
            return self._attend_gathered(params, q, pools, table, positions,
                                         base, window, rows=self._pair_rows)
        out = self._attend_paged_kernel(params, wide, pools, table,
                                        positions, base, window=window)
        out = out.reshape(S, C, nq, c.kv_fold, hd)
        return jnp.einsum("scnfd,nf->scnd", out, place.astype(out.dtype)
                          ).reshape(S, C, nq * hd)

    def lambda_of(self, params):
        return (jnp.exp(jnp.sum(params["lambda_q1"] * params["lambda_k1"]))
                - jnp.exp(jnp.sum(params["lambda_q2"] * params["lambda_k2"]))
                + params["lambda_init"])

    def output(self, params, attn):
        """attn [b, s, nq * 2 hd], a pair's a_1 then a_2 ->
        W_o [(1 - lambda_init) RMSNorm(a_1 - lambda a_2)] + b_o."""
        c = self.config
        with jax.named_scope("diff_out"):
            a = attn.reshape(attn.shape[:-1] + (c.num_attention_heads // 2,
                                                2, 2 * c.head_dim)
                             ).astype(F32)
            d = a[..., 0, :] - self.lambda_of(params) * a[..., 1, :]
            d = d * lax.rsqrt(jnp.mean(jnp.square(d), -1, keepdims=True)
                              + c.layer_norm_eps)
            d = d * params["subln"].astype(F32) \
                * (1.0 - params["lambda_init"])
            return d.reshape(attn.shape[:-1] + (-1,)).astype(attn.dtype) \
                @ params["w_o"].astype(attn.dtype) \
                + params["b_o"].astype(attn.dtype)


class SwiGLU(Module):
    """W_down [silu(x W_gate) * (x W_up)], gate | up one matrix, no
    bias."""

    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        c = config
        w = init.normal(c.initializer_range)
        self.param("w_gate_up", (c.hidden_size, 2 * c.intermediate_size), w,
                   dtype=c.param_dtype)
        self.param("w_down", (c.intermediate_size, c.hidden_size), w,
                   dtype=c.param_dtype)

    def forward(self, params, x):
        gu = x @ params["w_gate_up"].astype(x.dtype)
        i = gu.shape[-1] // 2
        return (jax.nn.silu(gu[..., :i]) * gu[..., i:]) \
            @ params["w_down"].astype(x.dtype)


class Phi4Block(Module):
    """One decoder layer; `mixer` is one of config.SSM .. CROSS."""

    SCOPES = {SSM: "ssm", WINDOW: "attn_window", FULL: "attn_full",
              GMU: "gmu", CROSS: "attn_cross"}

    def __init__(self, config: Phi4FlashConfig, strategy: ParallelStrategy,
                 mixer: str, hands_on: bool = False):
        super().__init__()
        c = config
        self.mixer = mixer
        #: how far back the layer reads (models/generation.py `_layer`)
        self.window = c.sliding_window if mixer == WINDOW else None
        #: the trace scope of the layer's mixer, inside `attn`
        self.attn_scope = self.SCOPES[mixer]
        #: the state layer whose scan output the later layers are handed
        self.hands_on = hands_on
        #: the layer's `mix` is given what was handed on
        self.takes_handed = mixer == GMU
        norm = dict(eps=c.layer_norm_eps, param_dtype=c.param_dtype)
        self.input_norm = ParallelLayerNorm(c.hidden_size, strategy, **norm)
        self.attn = (MambaMixer(c) if mixer == SSM else
                     GatedMemoryUnit(c) if mixer == GMU else
                     DiffAttention(c, self.window, cross=mixer == CROSS))
        self.post_norm = ParallelLayerNorm(c.hidden_size, strategy, **norm)
        self.mlp = SwiGLU(c)

    def mlp_stats(self, params, x):
        return self.mlp(params, x), None


def _with_lambda_init(specs, values):
    """A block's parameter specs with its attention's `lambda_init`
    buffer set: `values` a float (one layer) or a list (a stack's)."""
    import dataclasses as dc
    spec = specs["attn"]["lambda_init"]
    const = jnp.asarray(values, F32)
    specs["attn"]["lambda_init"] = dc.replace(
        spec, init=lambda key, shape, dtype: const.astype(dtype))
    return specs


class _Period(Module):
    """`count` periods of layers: one block a layer of the period, each
    block's parameters STACKED [count, ...] under the block's name, for
    `_walk_layers` to scan (a run whose block and parameters are tuples).
    `first` is the model's index of the first layer."""

    def __init__(self, config: Phi4FlashConfig, strategy: ParallelStrategy,
                 first: int, count: int):
        super().__init__()
        self.config, self.first, self.count = config, first, count
        self.names = [config.mixer_of(first + p)
                      for p in range(config.mb_per_layer)]
        self.blocks = tuple(Phi4Block(config, strategy, m)
                            for m in self.names)

    def param_specs(self):
        c, P = self.config, len(self.blocks)
        out = {}
        for p, (name, block) in enumerate(zip(self.names, self.blocks)):
            specs = stack_param_specs(block.param_specs(), self.count)
            if name in (WINDOW, CROSS):
                _with_lambda_init(specs, [
                    c.lambda_init(self.first + i * P + p)
                    for i in range(self.count)])
            out[name] = specs
        return out

    def run(self, params):
        return (self.blocks, tuple(params[n] for n in self.names),
                self.count)

    def layers(self, params):
        """(block, one layer's parameters) in layer order."""
        for i in range(self.count):
            for name, block in zip(self.names, self.blocks):
                yield block, jax.tree.map(lambda a: a[i], params[name])


class _Single(Module):
    """One layer with arrays of its own."""

    def __init__(self, config, strategy, layer: int, **kw):
        super().__init__()
        self.config, self.layer = config, layer
        self.block = Phi4Block(config, strategy, config.mixer_of(layer), **kw)

    def param_specs(self):
        specs = self.block.param_specs()
        if self.block.mixer == FULL:
            _with_lambda_init(specs, self.config.lambda_init(self.layer))
        return specs

    def run(self, params):
        return (self.block, params, None)

    def layers(self, params):
        yield self.block, params


class Phi4FlashModel(Module):
    def __init__(self, config: Phi4FlashConfig, strategy: ParallelStrategy):
        super().__init__()
        c = config
        half = c.memory_layer
        self.embed = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size, strategy, param_dtype=c.param_dtype,
            weight_init=init.normal(c.initializer_range))
        P = c.mb_per_layer
        self.self_decoder = _Period(c, strategy, 0, half // P)
        self.memory = _Single(c, strategy, half, hands_on=True)
        self.shared_kv = _Single(c, strategy, half + 1)
        self.cross_decoder = _Period(c, strategy, half + 2,
                                     (c.num_hidden_layers - half - 2) // P)
        self.final_norm = ParallelLayerNorm(c.hidden_size, strategy,
                                            eps=c.layer_norm_eps,
                                            param_dtype=c.param_dtype)

    PARTS = ("self_decoder", "memory", "shared_kv", "cross_decoder")


class Phi4FlashLMHeadModel(Module):
    #: the layer from whose attention on only the rows whose logits are
    #: read need computing: it holds the pages the later layers read, and
    #: they keep none (models/generation.extend_cache `read_row`)
    read_rows_from: int
    #: the engine's counter of the state bytes a decode pass reads and
    #: writes (serving/engine.py)
    state_counter = "serve.ssm_state_bytes"
    STATS = ()

    def __init__(self, config: Phi4FlashConfig,
                 strategy: Optional[ParallelStrategy] = None):
        super().__init__()
        strategy = strategy or ParallelStrategy()
        if strategy.mesh.num_devices > 1:
            raise NotImplementedError(
                "models/phi4_flash runs on one device: sharded mixers are "
                "not built (ROADMAP)")
        self.config, self.strategy = config, strategy
        self.model = Phi4FlashModel(config, strategy)
        self.read_rows_from = config.shared_kv_layer

    # -- what the serving programs of models/generation.py take -----------
    def cache_contract(self) -> CacheContract:
        """K and V rows a token in the window layers and the full layer
        (pages); a state a sequence in the Mamba layers (by slot);
        NOTHING in the cross-decoder: a cross layer reads the full
        layer's entries, a gated memory unit none."""
        c = self.config
        L = c.num_hidden_layers
        kinds = [c.mixer_of(l) for l in range(L)]
        return CacheContract(
            L, (c.kv_row, c.kv_row), dtype=c.compute_dtype,
            windows=tuple(c.sliding_window if k == WINDOW else None
                          for k in kinds),
            state_shapes=tuple(c.state_shapes if k == SSM else None
                               for k in kinds),
            reads=tuple(c.shared_kv_layer if k == CROSS else
                        NO_CACHE if k == GMU else None for k in kinds))

    def rope_tables(self, max_len: int):
        return None                 # no positional encoding of any kind

    def embed_tokens(self, params, ids, pos_ids):
        return self.model.embed(params["model"]["embed"], ids).astype(
            self.config.compute_dtype)

    def serving_layers(self, params):
        """Four runs: the (Mamba, window) pairs scanned, the memory layer
        and the full layer called, the (GMU, cross) pairs scanned."""
        return [getattr(self.model, part).run(params["model"][part])
                for part in self.model.PARTS]

    def final_hidden(self, params, x):
        return self.model.final_norm(params["model"]["final_norm"], x)

    def lm_head_weight(self, params):
        return params["model"]["embed"]["weight"].T

    def logits(self, params, hidden):
        with jax.named_scope("lm_head"):
            return hidden @ self.lm_head_weight(params).astype(hidden.dtype)

    def forward(self, params, input_ids):
        """Logits [b, s, vocab] of whole sequences at positions 0..s-1,
        every Mamba layer from zero state: the layers one after the
        other through the same hooks (`attend_prompt`, `state_chunk`)."""
        b, s = input_ids.shape
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        with jax.named_scope("embed"):
            x = self.embed_tokens(params, input_ids, pos)
        memory = shared = None
        with jax.named_scope("layer"):
            for part in self.model.PARTS:
                for block, lp in getattr(self.model, part).layers(
                        params["model"][part]):
                    hn = block.input_norm(lp["input_norm"], x)
                    if block.mixer == SSM:
                        out, y = block.attn(lp["attn"], hn)
                        memory = y if block.hands_on else memory
                    elif block.mixer == GMU:
                        out = block.attn.mix(lp["attn"], hn, memory)
                    else:
                        q, entries = block.attn.project(lp["attn"], hn,
                                                        None, pos)
                        if block.mixer == FULL:
                            shared = entries
                        out = block.attn.output(
                            lp["attn"], block.attn.attend_prompt(
                                lp["attn"], q, entries or shared,
                                window=block.window))
                    x = x + out
                    x = x + block.mlp(lp["mlp"], block.post_norm(
                        lp["post_norm"], x))
        return self.logits(params, self.final_hidden(params, x))
