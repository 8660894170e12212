"""Trinity (`model_type: afmoe`): decoder layers whose attention reads
either the last `sliding_window` positions or everything, by
`layer_types`, a dense SwiGLU in the leading layers and sigmoid-routed
experts with a shared expert after.  Serving only: `ServingEngine` takes
the model through the programs of `models/generation.py`, by the hooks
below; `Trainer` does not know it (ROADMAP).

One layer, x one token's hidden state, four norms:
`x = x + N2(Attn(N1(x)))`; `x = x + N4(FFN(N3(x)))`.

* Attention (`GatedAttention`; how a query attends is
  `cache_contract.KVAttention`'s, with the layer's window): q, k, v and
  a gate g = x W_g, from ONE matrix (the q columns, then k's, v's, g's);
  q and k RMS-normalised over each head with a learned gain; on a WINDOW
  layer q and k are rotated (half-split, whole head) and key j is seen
  by query t iff t - window < j <= t; a FULL layer carries no position
  at all and sees j <= t; softmax at head_dim^-0.5, q head n reading kv
  head n // group; y = (o * sigmoid(g)) W_o.
* The expert layer is `nn.moe.SharedRoutedExperts` (sigmoid scores, a
  bias that only chooses, the chosen scores over their sum times
  `route_scale`, one shared expert) over the experts this chip holds.
* The embedding is multiplied by sqrt(hidden) (`mup_enabled`); the head
  is untied.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu import ops
from hetu_tpu.models.cache_contract import (CacheContract, KVAttention,
                                            head_rms_norm, kv_contract)
from hetu_tpu.models.trinity.config import TrinityConfig
from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.module import Module
from hetu_tpu.nn.moe import (MOE_STATS, SharedRoutedExperts, add_moe_stats,
                             moe_layer_stats, zero_moe_stats)
from hetu_tpu.nn.parallel import ParallelRMSNorm, VocabParallelEmbedding
from hetu_tpu.parallel.strategy import ParallelStrategy


class GatedAttention(KVAttention, Module):
    """Grouped-query attention with RMSNorm over each head of q and k, a
    rotation on window layers only, and a sigmoid gate on what attention
    returns; `window` is how far back the layer reads (None:
    everything)."""

    def __init__(self, config: TrinityConfig, strategy: ParallelStrategy,
                 window: Optional[int]):
        Module.__init__(self)
        self.config = c = config
        self.window = window
        w = init.normal(c.initializer_range)
        nq, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        self.param("wqkvg", (c.hidden_size, (2 * nq + 2 * nkv) * hd), w,
                   dtype=c.param_dtype)
        self.param("q_norm", (hd,), init.ones, dtype=c.param_dtype)
        self.param("k_norm", (hd,), init.ones, dtype=c.param_dtype)
        self.param("wo", (nq * hd, c.hidden_size), w, dtype=c.param_dtype)
        self.out_norm = ParallelRMSNorm(c.hidden_size, strategy,
                                        eps=c.rms_norm_eps,
                                        param_dtype=c.param_dtype)

    def project(self, params, hn, rope, pos_ids):
        """hn [b, s, h] (normed) at positions pos_ids [b, s] -> (q
        [b, s, nq, hd], entries (k, v) [b, s, n_kv, hd], the gate
        [b, s, nq * hd] that `output` takes)."""
        c = self.config
        nq, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        x = hn @ params["wqkvg"].astype(hn.dtype)
        lead = x.shape[:-1]
        q = x[..., :nq * hd].reshape(lead + (nq, hd))
        k = x[..., nq * hd: (nq + nkv) * hd].reshape(lead + (nkv, hd))
        v = x[..., (nq + nkv) * hd: (nq + 2 * nkv) * hd] \
            .reshape(lead + (nkv, hd))
        gate = x[..., (nq + 2 * nkv) * hd:]
        q = head_rms_norm(q, params["q_norm"], c.rms_norm_eps)
        k = head_rms_norm(k, params["k_norm"], c.rms_norm_eps)
        if self.window is not None:
            cos, sin = rope
            q = ops.apply_rotary(q, cos, sin, pos_ids)
            k = ops.apply_rotary(k, cos, sin, pos_ids)
        return q, (k, v), gate

    def output(self, params, attn, gate):
        y = (attn * jax.nn.sigmoid(gate.astype(jnp.float32))
             .astype(attn.dtype)) @ params["wo"].astype(attn.dtype)
        return self.out_norm(params["out_norm"], y)


class DenseMLP(Module):
    """SwiGLU of the leading dense layers (fused gate|up [h, 2 I])."""

    def __init__(self, config: TrinityConfig):
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


class NormedFFN(Module):
    """A layer's FFN (dense, or the expert layer) and the norm on what it
    returns (the fourth of a layer's norms)."""

    def __init__(self, config: TrinityConfig, strategy: ParallelStrategy,
                 moe: bool):
        super().__init__()
        c = config
        self.moe = moe
        if moe:
            self.ffn = SharedRoutedExperts(
                c.hidden_size, c.moe_intermediate_size,
                n_routed_experts=c.router_experts,
                experts_held=c.experts_held, first_expert=c.first_expert,
                top_k=c.num_experts_per_tok,
                n_shared_experts=c.num_shared_experts,
                norm_topk_prob=c.route_norm,
                routed_scaling_factor=c.route_scale,
                param_dtype=c.param_dtype,
                initializer_range=c.initializer_range,
                bias_range=c.expert_bias_range)
        else:
            self.ffn = DenseMLP(c)
        self.out_norm = ParallelRMSNorm(c.hidden_size, strategy,
                                        eps=c.rms_norm_eps,
                                        param_dtype=c.param_dtype)

    def forward(self, params, x):
        """-> (y, MOE_STATS of this execution; zeros for a dense FFN)."""
        if self.moe:
            y, st = self.ffn(params["ffn"], x)
            st = moe_layer_stats(st)
        else:
            y, st = self.ffn(params["ffn"], x), zero_moe_stats()
        return self.out_norm(params["out_norm"], y), st


class TrinityBlock(Module):
    """One decoder layer: window or full attention by `window`, a dense
    FFN or the expert layer by `moe`."""

    def __init__(self, config: TrinityConfig, strategy: ParallelStrategy, *,
                 window: Optional[int], moe: bool):
        super().__init__()
        c = config
        #: how far back the layer reads (models/generation.py `_layer`)
        self.window = window
        #: the trace scope of the layer's attention, inside `attn`
        self.attn_scope = "attn_full" if window is None else "attn_window"
        norm = dict(eps=c.rms_norm_eps, param_dtype=c.param_dtype)
        self.input_norm = ParallelRMSNorm(c.hidden_size, strategy, **norm)
        self.attn = GatedAttention(c, strategy, window)
        self.post_norm = ParallelRMSNorm(c.hidden_size, strategy, **norm)
        self.mlp = NormedFFN(c, strategy, moe)

    def mlp_stats(self, params, x):
        return self.mlp(params, x)

    def forward(self, params, x, rope, pos_ids):
        """Whole sequences x [b, s, h] at positions 0..s-1."""
        with jax.named_scope("attn"), jax.named_scope(self.attn_scope):
            hn = self.input_norm(params["input_norm"], x)
            q, entries, gate = self.attn.project(params["attn"], hn, rope,
                                                 pos_ids)
            x = x + self.attn.output(
                params["attn"], self.attn.attend_prompt(
                    params["attn"], q, entries, window=self.window), gate)
        with jax.named_scope("mlp"):
            y, _ = self.mlp_stats(params["mlp"],
                                  self.post_norm(params["post_norm"], x))
        return x + y


class TrinityModel(Module):
    """Every layer has arrays of its own (`layer_<i>`): the layers
    differ, and a scan over stacked expert weights would slice each
    layer's out of the stack at every execution (models/kimi_k2)."""

    def __init__(self, config: TrinityConfig, strategy: ParallelStrategy):
        super().__init__()
        c = config
        self.embed = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size, strategy, param_dtype=c.param_dtype,
            weight_init=init.normal(c.initializer_range))
        self.blocks = []
        for i in range(c.num_hidden_layers):
            block = TrinityBlock(c, strategy, window=c.window_of(i),
                                 moe=i >= c.num_dense_layers)
            self.blocks.append(self.add_module(f"layer_{i}", block))
        self.final_norm = ParallelRMSNorm(c.hidden_size, strategy,
                                          eps=c.rms_norm_eps,
                                          param_dtype=c.param_dtype)


class TrinityLMHeadModel(Module):
    def __init__(self, config: TrinityConfig,
                 strategy: Optional[ParallelStrategy] = None):
        super().__init__()
        strategy = strategy or ParallelStrategy()
        if strategy.mesh.num_devices > 1:
            raise NotImplementedError(
                "models/trinity runs on one device: experts across chips "
                "(ep > 1) and sharded layers are not built (ROADMAP)")
        self.config, self.strategy = config, strategy
        self.model = TrinityModel(config, strategy)
        self.param("lm_head", (config.hidden_size, config.vocab_size),
                   init.normal(config.initializer_range),
                   dtype=config.param_dtype)

    # -- what the serving programs of models/generation.py take -----------
    def cache_contract(self) -> CacheContract:
        c = self.config
        return kv_contract(
            c.num_hidden_layers, c.num_key_value_heads, c.head_dim,
            dtype=c.compute_dtype,
            windows=tuple(c.window_of(i)
                          for i in range(c.num_hidden_layers)))

    def rope_tables(self, max_len: int):
        c = self.config
        return ops.build_rope_cache(max_len, c.head_dim, c.rope_theta)

    zero_stats = staticmethod(zero_moe_stats)
    add_stats = staticmethod(add_moe_stats)
    STATS = MOE_STATS

    def embed_tokens(self, params, ids, pos_ids):
        c = self.config
        x = self.model.embed(params["model"]["embed"], ids).astype(
            c.compute_dtype)
        if c.mup_enabled:
            x = x * jnp.asarray(math.sqrt(c.hidden_size), x.dtype)
        return x

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
