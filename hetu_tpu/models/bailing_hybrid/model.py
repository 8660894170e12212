"""Ling-3.0 (`bailing_hybrid`): linear-attention layers (KDA, a gated
delta rule with a decay per key channel) five to every one latent-
attention layer (MLA), leading dense layers, then layers of sigmoid-routed
experts chosen inside a few GROUPS of experts, with a shared expert.
Serving only: `ServingEngine` takes the model through the programs of
`models/generation.py`, by the hooks below; `Trainer` does not know it
(ROADMAP).

One layer, pre-norm, x one token's hidden state (normed):

* KDA layer ((l + 1) % `layer_group_size` != 0).  [q' | k' | v'] = x
  W_qkv, 32 heads of 128 each; each channel through a causal depthwise
  convolution over the last `short_conv_kernel_size` positions, then SiLU;
  q and k L2-normalised a head, q times 128^-1/2.  Log-decay a head and
  key channel g = `kda_lower_bound` * sigmoid(exp(A_log_h) (x W_g)_h +
  dt_bias) in (-5, 0); beta = sigmoid(x W_beta) a head.  State S in
  R^{128 x 128} a head, float32:
      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t
  out = W_o [RMSNorm_head(o_t) * sigmoid(x W_z)].  **A SEQUENCE's cache
  is S and the convolution's last 3 inputs, whatever its length; a token
  stores nothing** (the contract's `state_shapes`): the `state_chunk` /
  `state_step` hooks, over `ops/delta_rule`.
* MLA layer: Kimi-K2's (models/kimi_k2.MLAttention: a token stores
  [RMSNorm(c_kv) | RoPE(k_rope)] in 640 lanes; expanded in prefill,
  absorbed in decode) with q = x W_q directly (`q_lora_rank` null), no
  rotary scaling, and a gate a head on the attention's output, o_h *
  sigmoid((x W_a)_h).
* The expert layer is `nn.moe.SharedRoutedExperts` with `n_group` /
  `topk_group`.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from hetu_tpu import ops
from hetu_tpu.models.bailing_hybrid.config import BailingHybridConfig
from hetu_tpu.models.cache_contract import CacheContract
from hetu_tpu.models.kimi_k2.model import DenseMLP, MLAttention
from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.module import Module
from hetu_tpu.nn.moe import (MOE_STATS, SharedRoutedExperts, add_moe_stats,
                             moe_layer_stats, zero_moe_stats)
from hetu_tpu.nn.parallel import ParallelRMSNorm, VocabParallelEmbedding
from hetu_tpu.ops import delta_rule
from hetu_tpu.parallel.strategy import ParallelStrategy

F32 = jnp.float32


def _uniform(lo: float, hi: float):
    def fn(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, F32, lo, hi).astype(dtype)
    return fn


class KDAttention(Module):
    """The linear-attention mixer (module docstring).  Its hooks take the
    layer's whole attention: normed hidden states in, the residual's
    addend out, the sequence's state in and out."""

    def __init__(self, config: BailingHybridConfig,
                 strategy: ParallelStrategy):
        super().__init__()
        self.config = c = config
        w = init.normal(c.initializer_range)
        nh, hd, h, dt = (c.num_attention_heads, c.head_dim, c.hidden_size,
                         c.param_dtype)
        # q' | k' | v' columns, a head's 128 together
        self.param("w_qkv", (h, c.kda_channels), w, dtype=dt)
        # tap i multiplies the input i - (K - 1) positions back
        self.param("conv_w", (c.short_conv_kernel_size, c.kda_channels),
                   init.normal(0.5), dtype=dt)
        self.param("w_g", (h, nh * hd), w, dtype=dt)
        self.param("w_beta", (h, nh), w, dtype=dt)
        self.param("w_z", (h, nh * hd), w, dtype=dt)
        # float32 whatever the model's dtype: the decay is exponentiated
        # over thousands of positions.  exp(A_log) in (0.25, 1), dt_bias
        # in (-8, -3): decays of 0.002 to 0.25 a position, a memory of
        # 4 to 600 positions by channel (the published values are
        # trained; `assumed` of the configuration file)
        self.param("A_log", (nh,), _uniform(-1.386, 0.0), dtype=F32)
        self.param("dt_bias", (nh * hd,), _uniform(-8.0, -3.0), dtype=F32)
        self.o_norm = ParallelRMSNorm(hd, strategy, eps=c.rms_norm_eps,
                                      param_dtype=dt)
        self.param("wo", (nh * hd, h), w, dtype=dt)

    # -- the parts -----------------------------------------------------------
    def _gates(self, params, hn):
        """(g [.., nh, hd] float32 in (lower bound, 0), beta [.., nh]
        float32, z [.., nh, hd])."""
        c = self.config
        nh, hd = c.num_attention_heads, c.head_dim
        raw = (hn @ params["w_g"].astype(hn.dtype)).astype(F32).reshape(
            hn.shape[:-1] + (nh, hd))
        g = c.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(params["A_log"])[:, None] * raw
            + params["dt_bias"].reshape(nh, hd))
        beta = jax.nn.sigmoid(
            (hn @ params["w_beta"].astype(hn.dtype)).astype(F32))
        z = (hn @ params["w_z"].astype(hn.dtype)).reshape(
            hn.shape[:-1] + (nh, hd))
        return g, beta, z

    def _qkv(self, y, unit=True):
        """Convolved channels y [.., 3 nh hd] -> q, k, v [.., nh, hd]
        float32: SiLU, then (`unit`) q and k of unit length a head, q
        scaled; the chunk program leaves that to the scan, which has a
        head's 128 lanes loaded (`delta_rule.chunk_scan`'s `qk_scale`)."""
        c = self.config
        nh, hd = c.num_attention_heads, c.head_dim
        q, k, v = (x.reshape(x.shape[:-1] + (nh, hd)) for x in jnp.split(
            jax.nn.silu(y.astype(F32)), 3, axis=-1))
        if unit:
            q = delta_rule.unit_length(q) * hd ** -0.5
            k = delta_rule.unit_length(k)
        return q, k, v

    def _out(self, params, o, z):
        """o [.., nh, hd] float32, z the gate's pre-activation ->
        W_o [RMSNorm_head(o) * sigmoid(z)]."""
        with jax.named_scope("kda_out"):
            o = self.o_norm(params["o_norm"], o.astype(z.dtype))
            o = o * jax.nn.sigmoid(z.astype(F32)).astype(z.dtype)
            return o.reshape(o.shape[:-2] + (-1,)) \
                @ params["wo"].astype(o.dtype)

    # -- the hooks (models/generation.py) ---------------------------------
    def state_chunk(self, params, hn, state, start, valid):
        """hn [b, C, hidden] (normed); state = (S [b, nh, hd, hd]
        float32, conv [b, K - 1, channels]): the rows' own, as the last
        chunk left them (zeros where this is the first).  The first
        valid[b] positions are the sequence's; the rest are padding: the
        scan leaves them out of the state (`chunk_scan`'s `valid`), and
        the convolution's tail is taken where the valid rows end.
        -> (out [b, C, hidden], state')."""
        c = self.config
        S, conv = state
        b, C = hn.shape[:2]
        K = c.short_conv_kernel_size
        with jax.named_scope("kda_proj"):
            x = hn @ params["w_qkv"].astype(hn.dtype)        # [b, C, ch]
            g, beta, z = self._gates(params, hn)
        with jax.named_scope("kda_conv"):
            xx = jnp.concatenate([conv.astype(x.dtype), x], axis=1)
            w = params["conv_w"].astype(F32)
            y = sum(w[i] * xx[:, i: i + C].astype(F32) for i in range(K))
            # the last K - 1 inputs up to the last VALID position
            conv = jax.vmap(lambda a, n: lax.dynamic_slice_in_dim(
                a, n, K - 1, axis=0))(xx, valid).astype(conv.dtype)
            q, k, v = self._qkv(y, unit=False)
        with jax.named_scope("kda_scan"):
            o, S = delta_rule.chunk_scan(S, q, k, v, g, beta, valid=valid,
                                         g_floor=c.kda_lower_bound,
                                         qk_scale=c.head_dim ** -0.5)
        return self._out(params, o, z), (S, conv)

    def state_step(self, params, hn, state, live):
        """One position a row: hn [b, 1, hidden]; rows where `live` [b]
        is False (idle slots) leave their state as it is.
        -> (out [b, 1, hidden], state')."""
        c = self.config
        S, conv = state
        K = c.short_conv_kernel_size
        hn1 = hn[:, 0]
        with jax.named_scope("kda_proj"):
            x = hn1 @ params["w_qkv"].astype(hn.dtype)       # [b, ch]
            g, beta, z = self._gates(params, hn1)
        with jax.named_scope("kda_conv"):
            xx = jnp.concatenate([conv.astype(x.dtype), x[:, None]], axis=1)
            w = params["conv_w"].astype(F32)
            y = sum(w[i] * xx[:, i].astype(F32) for i in range(K))
            conv = jnp.where(live[:, None, None], xx[:, 1:],
                             conv.astype(x.dtype)).astype(conv.dtype)
            q, k, v = self._qkv(y)
        with jax.named_scope("kda_step"):
            g = jnp.where(live[:, None, None], g, 0.0)
            beta = jnp.where(live[:, None], beta, 0.0)
            o, S = delta_rule.step(S, q, k, v, g, beta)
        return self._out(params, o, z)[:, None], (S, conv)

    def forward(self, params, hn):
        """Whole sequences hn [b, s, h] from zero state."""
        c = self.config
        b, s = hn.shape[:2]
        state = (jnp.zeros((b, c.num_attention_heads, c.head_dim,
                            c.head_dim), F32),
                 jnp.zeros((b, c.short_conv_kernel_size - 1,
                            c.kda_channels), hn.dtype))
        return self.state_chunk(params, hn, state, jnp.zeros((b,), jnp.int32),
                                jnp.full((b,), s, jnp.int32))[0]


class GatedMLAttention(MLAttention):
    """`kimi_k2.MLAttention` with q = x W_q directly (no low-rank q) and a
    gate a head on the attention's output; what a token stores and how a
    query attends it (`attend_dense`, `attend_paged`, `attend_prompt`)
    are Kimi's."""

    def __init__(self, config: BailingHybridConfig,
                 strategy: ParallelStrategy):
        Module.__init__(self)
        self.config = c = config
        w = init.normal(c.initializer_range)
        nh, dt = c.num_attention_heads, c.param_dtype
        self.param("wq", (c.hidden_size, nh * c.qk_head_dim), w, dtype=dt)
        self.param("wkv_a", (c.hidden_size, c.latent_dim), w, dtype=dt)
        self.kv_norm = ParallelRMSNorm(c.kv_lora_rank, strategy,
                                       eps=c.rms_norm_eps, param_dtype=dt)
        self.param("wkv_b", (c.kv_lora_rank, nh,
                             c.qk_nope_head_dim + c.v_head_dim), w, dtype=dt)
        self.param("w_gate", (c.hidden_size, nh), w, dtype=dt)
        self.param("wo", (nh * c.v_head_dim, c.hidden_size), w, dtype=dt)

    def project(self, params, hn, rope, pos_ids):
        """-> (q, entries, gate): as Kimi's, and the gate's
        pre-activation [b, s, nh] for `output`."""
        c = self.config
        cos, sin = rope
        r, dn = c.kv_lora_rank, c.qk_nope_head_dim
        with jax.named_scope("mla_q"):
            q = (hn @ params["wq"].astype(hn.dtype)).reshape(
                hn.shape[:-1] + (c.num_attention_heads, c.qk_head_dim))
            q_rope = ops.apply_rotary(q[..., dn:], cos, sin, pos_ids)
            gate = hn @ params["w_gate"].astype(hn.dtype)
        with jax.named_scope("mla_kv"):
            ckv = hn @ params["wkv_a"].astype(hn.dtype)
            k_rope = ops.apply_rotary(ckv[..., None, r:], cos, sin,
                                      pos_ids)[..., 0, :]
            latent = jnp.concatenate(
                [self.kv_norm(params["kv_norm"], ckv[..., :r]), k_rope]
                + ([jnp.zeros(ckv.shape[:-1] + (
                    c.latent_stored_dim - c.latent_dim,), ckv.dtype)]
                   if c.latent_stored_dim > c.latent_dim else []), axis=-1)
        return (q[..., :dn], q_rope), (latent,), gate

    def output(self, params, attn, gate):
        with jax.named_scope("mla_out"):
            c = self.config
            g = jax.nn.sigmoid(gate.astype(F32)).astype(attn.dtype)
            attn = attn.reshape(attn.shape[:-1] + (c.num_attention_heads,
                                                   c.v_head_dim))
            return (attn * g[..., None]).reshape(attn.shape[:-2] + (-1,)) \
                @ params["wo"].astype(attn.dtype)

    def forward(self, params, hn, rope, pos_ids):
        q, entries, gate = self.project(params, hn, rope, pos_ids)
        return self.output(params, self.attend_prompt(params, q, entries),
                           gate)


class BailingBlock(Module):
    def __init__(self, config: BailingHybridConfig,
                 strategy: ParallelStrategy, *, kda: bool, moe: bool):
        super().__init__()
        c = config
        self.kda, self.moe = kda, moe
        #: the scope the layer's attention runs under inside `attn`
        self.attn_scope = "kda" if kda else None
        norm = dict(eps=c.rms_norm_eps, param_dtype=c.param_dtype)
        self.input_norm = ParallelRMSNorm(c.hidden_size, strategy, **norm)
        self.attn = (KDAttention if kda else GatedMLAttention)(c, strategy)
        self.post_norm = ParallelRMSNorm(c.hidden_size, strategy, **norm)
        if moe:
            self.mlp = SharedRoutedExperts(
                c.hidden_size, c.moe_intermediate_size,
                n_routed_experts=c.num_experts,
                experts_held=c.experts_held, first_expert=c.first_expert,
                top_k=c.num_experts_per_tok,
                n_shared_experts=c.num_shared_experts,
                norm_topk_prob=c.norm_topk_prob,
                routed_scaling_factor=c.routed_scaling_factor,
                n_group=c.n_group, topk_group=c.topk_group,
                param_dtype=c.param_dtype,
                initializer_range=c.initializer_range,
                bias_range=c.correction_bias_range)
        else:
            self.mlp = DenseMLP(c)

    def mlp_stats(self, params, x):
        if not self.moe:
            return self.mlp(params, x), zero_moe_stats()
        y, st = self.mlp(params, x)
        return y, moe_layer_stats(st)

    def forward(self, params, x, rope, pos_ids):
        with jax.named_scope("attn"):
            hn = self.input_norm(params["input_norm"], x)
            x = x + (self.attn(params["attn"], hn) if self.kda else
                     self.attn(params["attn"], hn, rope, pos_ids))
        with jax.named_scope("mlp"):
            y, _ = self.mlp_stats(params["mlp"],
                                  self.post_norm(params["post_norm"], x))
        return x + y


class _Layers(Module):
    """The layers, each a block of its own kind with a parameter subtree
    `layer_<i>` of its own; never stacked to scan (models/kimi_k2 says
    why)."""

    def __init__(self, config: BailingHybridConfig,
                 strategy: ParallelStrategy):
        super().__init__()
        c = config
        for i in range(c.num_hidden_layers):
            self.add_module(f"layer_{i}", BailingBlock(
                c, strategy, kda=c.is_kda(i),
                moe=i >= c.first_k_dense_replace))
        self.num = c.num_hidden_layers

    def runs(self, params):
        return [(getattr(self, f"layer_{i}"), params[f"layer_{i}"], None)
                for i in range(self.num)]


class BailingHybridModel(Module):
    def __init__(self, config: BailingHybridConfig,
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


class BailingHybridLMHeadModel(Module):
    def __init__(self, config: BailingHybridConfig,
                 strategy: Optional[ParallelStrategy] = None):
        super().__init__()
        strategy = strategy or ParallelStrategy()
        if strategy.mesh.num_devices > 1:
            raise NotImplementedError(
                "models/bailing_hybrid runs on one device: experts across "
                "chips (ep > 1) and sharded mixers are not built (ROADMAP)")
        self.config, self.strategy = config, strategy
        self.model = BailingHybridModel(config, strategy)
        if config.tie_word_embeddings:
            raise NotImplementedError("Ling-3.0's head is untied")
        self.param("lm_head", (config.hidden_size, config.vocab_size),
                   init.normal(config.initializer_range),
                   dtype=config.param_dtype)

    # -- what the serving programs of models/generation.py take -----------
    def cache_contract(self) -> CacheContract:
        """A latent a token in the MLA layers (pages); a state a sequence
        in the KDA layers (by slot)."""
        c = self.config
        return CacheContract(
            c.num_hidden_layers, ((c.latent_dim,),),
            ((c.latent_stored_dim,),), c.compute_dtype, kind="latent",
            state_shapes=tuple(c.state_shapes if c.is_kda(l) else None
                               for l in range(c.num_hidden_layers)))

    def rope_tables(self, max_len: int):
        c = self.config
        return ops.build_rope_cache(max_len, c.qk_rope_head_dim, c.rope_theta)

    zero_stats = staticmethod(zero_moe_stats)
    add_stats = staticmethod(add_moe_stats)
    STATS = MOE_STATS

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
        """Logits [b, s, vocab] of whole sequences at positions 0..s-1,
        every KDA layer from zero state."""
        b, s = input_ids.shape
        rope = self.rope_tables(s)
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        with jax.named_scope("embed"):
            x = self.embed_tokens(params, input_ids, pos)
        with jax.named_scope("layer"):
            for block, lp, _ in self.serving_layers(params):
                x = block(lp, x, rope, pos)
        return self.logits(params, self.final_hidden(params, x))
