"""LongCat-Flash: a published layer is TWO latent-attention sublayers,
each followed by a dense SwiGLU, and ONE shortcut-connected expert layer
(ScMoE) that reads the first sublayer's post-attention norm and is added
after the second sublayer's FFN.  Serving only: `ServingEngine` takes the
model through the programs of `models/generation.py`, by the hooks below.

One published layer, h a token's hidden state (all norms RMSNorm):

    for i in (0, 1):
        h = h + MLA_i(norm_in_i(h))
        u = norm_post_i(h)
        if i == 0:  s = MoE(u)              # the shortcut branch
        h = h + SwiGLU_i(u)
    h = h + s

* **The walk sees 2 x `num_layers` layers** (the cache contract's count):
  each sublayer is an attention + MLP layer as `generation._layer` knows
  it, with a latent cache of its own.  The first sublayer's block says
  `mlp_hands_on`: its `mlp_stats` returns the dense FFN's output for the
  residual and hands the expert branch on; the second's says
  `mlp_takes_handed` and adds it behind its own FFN.
* MLA is `kimi_k2.MLAttention` with the two factors the configuration
  states (`mla_scale_q_lora`, `mla_scale_kv_lora`): q after W_qb times
  sqrt(hidden / q_lora_rank), the normed latent times sqrt(hidden /
  kv_lora_rank) before W_kvb.  A token's cache entry is the SCALED latent
  and the rotated k_rope, so both latent kernels and the absorbed decode
  form take it as they take Kimi's.  No YaRN: plain rotation, theta 1e7.
* The expert layer is `nn.moe.SharedRoutedExperts` scored by softmax over
  `n_routed_experts` + `zero_expert_num` outputs, the latter identity
  experts (a chosen one returns its input), no shared expert, the top
  `moe_topk` weighted by their scores times `routed_scaling_factor`, not
  renormalised.
"""
from __future__ import annotations

import copy
from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu import ops
from hetu_tpu.models.cache_contract import CacheContract
from hetu_tpu.models.kimi_k2.model import DenseMLP, MLAttention
from hetu_tpu.models.longcat_flash.config import LongCatFlashConfig
from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.module import Module
from hetu_tpu.nn.moe import (ZERO_MOE_STATS, SharedRoutedExperts,
                             moe_layer_stats, stats_ops)
from hetu_tpu.nn.parallel import ParallelRMSNorm, VocabParallelEmbedding
from hetu_tpu.parallel.strategy import ParallelStrategy

zero_stats, add_stats = stats_ops(ZERO_MOE_STATS)


class ShortcutExpertsMLP(Module):
    """The first sublayer's MLP side: its dense FFN, and the expert layer
    on the same input, whose output does NOT join the residual here."""

    def __init__(self, config: LongCatFlashConfig):
        super().__init__()
        c = config
        self.dense = DenseMLP(c)
        self.experts = SharedRoutedExperts(
            c.hidden_size, c.expert_ffn_hidden_size,
            n_routed_experts=c.n_routed_experts,
            experts_held=c.experts_held, first_expert=c.first_expert,
            top_k=c.moe_topk, n_shared_experts=0, norm_topk_prob=False,
            routed_scaling_factor=c.routed_scaling_factor,
            param_dtype=c.param_dtype,
            initializer_range=c.initializer_range,
            bias_range=c.correction_bias_range, scoring="softmax",
            n_zero_experts=c.zero_expert_num)

    def forward(self, params, x):
        """-> (SwiGLU(x), the expert layer's stats, MoE(x))."""
        branch, st = self.experts(params["experts"], x)
        return self.dense(params["dense"], x), st, branch


class LongCatSublayer(Module):
    """Sublayer `i` (0 or 1) of a published layer."""

    def __init__(self, config: LongCatFlashConfig,
                 strategy: ParallelStrategy, *, first: bool):
        super().__init__()
        c = config
        #: generation._layer: the MLP side hands the expert branch on
        #: (the first sublayer), or takes it (the second)
        self.mlp_hands_on, self.mlp_takes_handed = first, not first
        norm = dict(eps=c.rms_norm_eps, param_dtype=c.param_dtype)
        self.input_norm = ParallelRMSNorm(c.hidden_size, strategy, **norm)
        self.attn = MLAttention(c, strategy)
        self.post_norm = ParallelRMSNorm(c.hidden_size, strategy, **norm)
        self.mlp = ShortcutExpertsMLP(c) if first else DenseMLP(c)

    def mlp_stats(self, params, x, handed=None):
        if self.mlp_hands_on:
            y, st, branch = self.mlp(params, x)
            return y, moe_layer_stats(st), branch
        return self.mlp(params, x) + handed, zero_stats()

    def forward(self, params, x, rope, pos_ids, handed=None):
        """-> (x, what is handed on)."""
        with jax.named_scope("attn"):
            x = x + self.attn(params["attn"],
                              self.input_norm(params["input_norm"], x),
                              rope, pos_ids)
        with jax.named_scope("mlp"):
            y, _, *new = self.mlp_stats(
                params["mlp"], self.post_norm(params["post_norm"], x), handed)
        return x + y, (new[0] if new else handed)


class _Layers(Module):
    """The published layers, each two sublayers with parameter subtrees
    `layer_<l>/sub_<i>` of their own; never stacked to scan
    (models/kimi_k2 says why)."""

    def __init__(self, config: LongCatFlashConfig,
                 strategy: ParallelStrategy):
        super().__init__()
        self.first = LongCatSublayer(config, strategy, first=True)
        self.second = LongCatSublayer(config, strategy, first=False)
        self.num = config.num_layers

    def param_specs(self):
        one = {"sub_0": self.first.param_specs(),
               "sub_1": self.second.param_specs()}
        return {f"layer_{l}": copy.deepcopy(one) for l in range(self.num)}

    def runs(self, params):
        """(block, a sublayer's own parameters, None) per CACHE layer:
        layer 2l is published layer l's first sublayer, 2l + 1 its
        second."""
        return [(block, params[f"layer_{l}"][f"sub_{i}"], None)
                for l in range(self.num)
                for i, block in enumerate((self.first, self.second))]


class LongCatFlashModel(Module):
    def __init__(self, config: LongCatFlashConfig,
                 strategy: ParallelStrategy):
        super().__init__()
        c = config
        self.embed = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size, strategy, param_dtype=c.param_dtype,
            weight_init=init.normal(c.initializer_range))
        self.layers = _Layers(c, strategy)
        self.final_norm = ParallelRMSNorm(c.hidden_size, strategy,
                                          eps=c.rms_norm_eps,
                                          param_dtype=c.param_dtype)


class LongCatFlashLMHeadModel(Module):
    def __init__(self, config: LongCatFlashConfig,
                 strategy: Optional[ParallelStrategy] = None):
        super().__init__()
        strategy = strategy or ParallelStrategy()
        if strategy.mesh.num_devices > 1:
            raise NotImplementedError(
                "models/longcat_flash runs on one device: experts across "
                "chips (ep > 1), whose exchange the shortcut is there to "
                "hide, and a sharded MLA are not built (ROADMAP)")
        self.config, self.strategy = config, strategy
        self.model = LongCatFlashModel(config, strategy)
        self.param("lm_head", (config.hidden_size, config.vocab_size),
                   init.normal(config.initializer_range),
                   dtype=config.param_dtype)

    # -- what the serving programs of models/generation.py take -----------
    def cache_contract(self) -> CacheContract:
        """Two latent cache layers a published layer."""
        c = self.config
        return CacheContract(2 * c.num_layers, ((c.latent_dim,),),
                             ((c.latent_stored_dim,),), c.compute_dtype,
                             kind="latent")

    def rope_tables(self, max_len: int):
        c = self.config
        return ops.build_rope_cache(max_len, c.qk_rope_head_dim, c.rope_theta)

    zero_stats = staticmethod(zero_stats)
    add_stats = staticmethod(add_stats)
    #: an expert layer's counts as every expert family's, and the pairs
    #: on identity experts
    STATS = ZERO_MOE_STATS

    def embed_tokens(self, params, ids, pos_ids):
        return self.model.embed(params["model"]["embed"], ids).astype(
            self.config.compute_dtype)

    def serving_layers(self, params):
        return self.model.layers.runs(params["model"]["layers"])

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
        handed = None
        with jax.named_scope("layer"):
            for block, lp, _ in self.serving_layers(params):
                x, handed = block(lp, x, rope, pos, handed)
        return self.logits(params, self.final_hidden(params, x))
