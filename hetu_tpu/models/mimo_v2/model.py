"""MiMo-V2 (`model_type: mimo_v2_flash`): decoder layers whose attention
reads either the last `sliding_window` positions or everything, by
`hybrid_layer_pattern`, and the two kinds of layer differ in more than
their reach: in how many KV heads a token stores (`num_key_value_heads`
where a layer reads everything, `swa_num_key_value_heads` under the
window), in the base of the rotation, and in a learned SINK in the
window layers' softmax.  Keys are `head_dim` wide and values
`v_head_dim`.  A dense SwiGLU where `moe_layer_freq` is 0, sigmoid-routed
experts with NO shared expert after.  Serving only: `ServingEngine` takes
the model through the programs of `models/generation.py`, by the hooks
below; `Trainer` does not know it (ROADMAP).

One layer, x one token's hidden state, two pre-norms:
`h = x + Attn(N1(x))`; `y = h + FFN(N2(h))`.

* Attention (`MiMoAttention`; how a query attends is
  `cache_contract.KVAttention`'s, with the layer's window and sink): q, k
  and v from ONE matrix, no bias.  The first `int(head_dim *
  partial_rotary_factor)` values of every q and k head are rotated
  (half-split over those values; base `rope_theta` where the layer reads
  everything, `swa_rope_theta` under the window), the others carry no
  position; v is multiplied by `attention_value_scale`; softmax at
  head_dim^-0.5 over j <= t or t - window < j <= t, q head n reading kv
  head n // group, with exp(sink_n) in a window layer's denominator;
  y = o W_o, o of `v_head_dim` a head.  The matrix's columns are laid
  out so that no product is sliced off a lane boundary: every q head's
  rotated values, every q head's plain values, k's rotated, k's plain,
  v.  A key is STORED in `stored_key_dim` lanes, zeros beyond its 192
  (the cache contract's `stored_shapes`).
* The expert layer is `nn.moe.SharedRoutedExperts(n_shared_experts=0)`
  (sigmoid scores, a bias that only chooses, the chosen scores over their
  sum) over the experts this chip holds: a token none of whose experts
  is held gets 0 from the layer.
* The head is untied.  The published model's multi-token-prediction
  layers are in no key of its config and are not built: one token a
  sequence a step.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu import ops
from hetu_tpu.models.cache_contract import CacheContract, KVAttention
from hetu_tpu.models.mimo_v2.config import MiMoV2Config
from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.module import Module
from hetu_tpu.nn.moe import (MOE_STATS, SharedRoutedExperts, add_moe_stats,
                             moe_layer_stats, zero_moe_stats)
from hetu_tpu.nn.parallel import ParallelRMSNorm, VocabParallelEmbedding
from hetu_tpu.parallel.strategy import ParallelStrategy


class MiMoAttention(KVAttention, Module):
    """Grouped-query attention with keys wider than values, a partial
    rotation, a value scale and, where `sink`, a learned scalar a query
    head in the softmax's denominator; `window` is how far back the layer
    reads (None: everything), `n_kv` its KV heads."""

    def __init__(self, config: MiMoV2Config, *, window: Optional[int],
                 n_kv: int, sink: bool):
        Module.__init__(self)
        self.config = c = config
        self.window, self.n_kv = window, n_kv
        w = init.normal(c.initializer_range)
        nq, hd, hv = c.num_attention_heads, c.head_dim, c.v_head_dim
        self.param("wqkv", (c.hidden_size, (nq + n_kv) * hd + n_kv * hv), w,
                   dtype=c.param_dtype)
        self.param("wo", (nq * hv, c.hidden_size), w, dtype=c.param_dtype)
        if sink:
            # float32 whatever the model's dtype: it stands beside
            # float32 scores
            self.param("sink", (nq,), init.normal(c.sink_range),
                       dtype=jnp.float32)

    def sink(self, params, window):
        return params.get("sink")

    def project(self, params, hn, rope, pos_ids):
        """hn [b, s, h] (normed) at positions pos_ids [b, s] -> (q
        [b, s, nq, head_dim], entries (k [b, s, n_kv, stored_key_dim],
        v [b, s, n_kv, v_head_dim]))."""
        c = self.config
        nq, nkv, hd, hv = (c.num_attention_heads, self.n_kv, c.head_dim,
                           c.v_head_dim)
        r = c.rotary_dim
        x = hn @ params["wqkv"].astype(hn.dtype)
        lead, at, parts = x.shape[:-1], 0, []
        for heads, width in ((nq, r), (nq, hd - r), (nkv, r), (nkv, hd - r),
                             (nkv, hv)):
            parts.append(x[..., at: at + heads * width]
                         .reshape(lead + (heads, width)))
            at += heads * width
        q_r, q_p, k_r, k_p, v = parts
        cos, sin = rope[0 if self.window is None else 1]
        q_r = ops.apply_rotary(q_r, cos, sin, pos_ids)
        k_r = ops.apply_rotary(k_r, cos, sin, pos_ids)
        pad = jnp.zeros(lead + (nkv, c.stored_key_dim - hd), x.dtype)
        v = v * jnp.asarray(c.attention_value_scale, v.dtype)
        return (jnp.concatenate([q_r, q_p], -1),
                (jnp.concatenate([k_r, k_p, pad], -1), v))

    def output(self, params, attn):
        return attn @ params["wo"].astype(attn.dtype)


class DenseMLP(Module):
    """SwiGLU of a dense layer (fused gate|up [h, 2 I])."""

    def __init__(self, config: MiMoV2Config):
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


class MiMoBlock(Module):
    """One decoder layer: window or full attention by `window` (and the
    KV heads and the sink that go with the kind), a dense FFN or the
    expert layer by `moe`."""

    def __init__(self, config: MiMoV2Config, strategy: ParallelStrategy, *,
                 layer: int):
        super().__init__()
        c = config
        #: how far back the layer reads (models/generation.py `_layer`)
        self.window = c.window_of(layer)
        #: the trace scope of the layer's attention, inside `attn`
        self.attn_scope = ("attn_full" if self.window is None
                           else "attn_window")
        self.moe = bool(c.moe_layer_freq[layer])
        norm = dict(eps=c.layernorm_epsilon, param_dtype=c.param_dtype)
        self.input_norm = ParallelRMSNorm(c.hidden_size, strategy, **norm)
        self.attn = MiMoAttention(c, window=self.window,
                                  n_kv=c.kv_heads_of(layer),
                                  sink=c.sink_of(layer))
        self.post_norm = ParallelRMSNorm(c.hidden_size, strategy, **norm)
        if self.moe:
            self.mlp = SharedRoutedExperts(
                c.hidden_size, c.moe_intermediate_size,
                n_routed_experts=c.router_experts,
                experts_held=c.experts_held, first_expert=c.first_expert,
                top_k=c.num_experts_per_tok, n_shared_experts=0,
                norm_topk_prob=c.norm_topk_prob,
                routed_scaling_factor=c.routed_scaling_factor or 1.0,
                param_dtype=c.param_dtype,
                initializer_range=c.initializer_range,
                bias_range=c.router_bias_range)
        else:
            self.mlp = DenseMLP(c)

    def mlp_stats(self, params, x):
        """-> (y, MOE_STATS of this execution; zeros for a dense FFN)."""
        if self.moe:
            y, st = self.mlp(params, x)
            return y, moe_layer_stats(st)
        return self.mlp(params, x), zero_moe_stats()

    def forward(self, params, x, rope, pos_ids):
        """Whole sequences x [b, s, h] at positions 0..s-1."""
        with jax.named_scope("attn"), jax.named_scope(self.attn_scope):
            hn = self.input_norm(params["input_norm"], x)
            q, entries = self.attn.project(params["attn"], hn, rope, pos_ids)
            x = x + self.attn.output(
                params["attn"], self.attn.attend_prompt(
                    params["attn"], q, entries, window=self.window))
        with jax.named_scope("mlp"):
            y, _ = self.mlp_stats(params["mlp"],
                                  self.post_norm(params["post_norm"], x))
        return x + y


class MiMoV2Model(Module):
    """Every layer has arrays of its own (`layer_<i>`): the layers
    differ, and a scan over stacked expert weights would slice each
    layer's out of the stack at every execution (models/kimi_k2)."""

    def __init__(self, config: MiMoV2Config, strategy: ParallelStrategy):
        super().__init__()
        c = config
        self.embed = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size, strategy, param_dtype=c.param_dtype,
            weight_init=init.normal(c.initializer_range))
        self.blocks = [
            self.add_module(f"layer_{i}", MiMoBlock(c, strategy, layer=i))
            for i in range(c.num_hidden_layers)]
        self.final_norm = ParallelRMSNorm(c.hidden_size, strategy,
                                          eps=c.layernorm_epsilon,
                                          param_dtype=c.param_dtype)


class MiMoV2LMHeadModel(Module):
    def __init__(self, config: MiMoV2Config,
                 strategy: Optional[ParallelStrategy] = None):
        super().__init__()
        strategy = strategy or ParallelStrategy()
        if strategy.mesh.num_devices > 1:
            raise NotImplementedError(
                "models/mimo_v2 runs on one device: experts across chips "
                "(ep > 1) and sharded layers are not built (ROADMAP)")
        self.config, self.strategy = config, strategy
        self.model = MiMoV2Model(config, strategy)
        self.param("lm_head", (config.hidden_size, config.vocab_size),
                   init.normal(config.initializer_range),
                   dtype=config.param_dtype)

    # -- what the serving programs of models/generation.py take -----------
    def cache_contract(self) -> CacheContract:
        """A kind of layer is a window AND what a token stores there: K
        of `head_dim` (held in `stored_key_dim` lanes) and V of
        `v_head_dim`, over the kind's own KV heads."""
        c = self.config
        layers = range(c.num_hidden_layers)

        def shapes(key_dim):
            return tuple(((c.kv_heads_of(i), key_dim),
                          (c.kv_heads_of(i), c.v_head_dim)) for i in layers)
        token, stored = shapes(c.head_dim), shapes(c.stored_key_dim)
        return CacheContract(
            c.num_hidden_layers, token[0], stored[0], dtype=c.compute_dtype,
            windows=tuple(c.window_of(i) for i in layers),
            layer_token_shapes=token, layer_stored_shapes=stored)

    def rope_tables(self, max_len: int):
        """(the table of the layers that read everything, the window
        layers'), each over the rotated values alone."""
        c = self.config
        return tuple(ops.build_rope_cache(max_len, c.rotary_dim, theta)
                     for theta in (c.rope_theta, c.swa_rope_theta))

    zero_stats = staticmethod(zero_moe_stats)
    add_stats = staticmethod(add_moe_stats)
    STATS = MOE_STATS

    def embed_tokens(self, params, ids, pos_ids):
        return self.model.embed(params["model"]["embed"], ids).astype(
            self.config.compute_dtype)

    def serving_layers(self, params):
        """Runs (block, parameters, None) in the model's layer order:
        every layer its own arrays, called and never scanned."""
        return [(block, params["model"][f"layer_{i}"], None)
                for i, block in enumerate(self.model.blocks)]

    def final_hidden(self, params, x):
        return self.model.final_norm(params["model"]["final_norm"], x)

    def lm_head_weight(self, params):
        return params["lm_head"]

    def logits(self, params, hidden):
        with jax.named_scope("lm_head"):
            return hidden @ params["lm_head"].astype(hidden.dtype)

    def forward(self, params, input_ids):
        """Logits [b, s, vocab] of whole sequences at positions 0..s-1."""
        b, s = input_ids.shape
        rope = self.rope_tables(s)
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        with jax.named_scope("embed"):
            x = self.embed_tokens(params, input_ids, pos)
        with jax.named_scope("layer"):
            for block, lp, _ in self.serving_layers(params):
                x = block(lp, x, rope, pos)
        return self.logits(params, self.final_hidden(params, x))
