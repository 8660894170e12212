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

* Mamba-1 (layers 0, 2, .., L/2): `nn/mamba.MambaMixer`, the mixer
  this family shares with models/jamba, without inner norms; its
  equations, its state a SEQUENCE (h float32 [d_state, d_inner] and the
  convolution's last K - 1 inputs; a token stores nothing: the
  contract's `state_shapes`) and its scopes are written there.  Its
  hooks return the scan's output y_t third: layer L/2, the block that
  says `hands_on`, hands it to the later layers as the MEMORY (before
  the gate).
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

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from hetu_tpu.models.cache_contract import (NO_CACHE, CacheContract,
                                            KVAttention)
from hetu_tpu.models.phi4_flash.config import (CROSS, FULL, GMU, SSM, WINDOW,
                                               Phi4FlashConfig)
from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.mamba import MambaMixer
from hetu_tpu.nn.module import Module, stack_param_specs
from hetu_tpu.nn.parallel import ParallelLayerNorm, VocabParallelEmbedding
from hetu_tpu.parallel.strategy import ParallelStrategy

F32 = jnp.float32


def _mamba(c: Phi4FlashConfig) -> MambaMixer:
    """The shared Mamba-1 mixer at this configuration's sizes, without
    inner norms."""
    return MambaMixer(c.hidden_size, c.d_inner, c.mamba_d_state,
                      c.mamba_d_conv, c.mamba_dt_rank,
                      param_dtype=c.param_dtype,
                      compute_dtype=c.compute_dtype,
                      initializer_range=c.initializer_range)


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
        self.attn = (_mamba(c) if mixer == SSM else
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
