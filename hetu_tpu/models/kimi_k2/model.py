"""Kimi-K2 (the DeepSeek-V3 block): multi-head latent attention, a
leading dense layer, then layers of sigmoid-routed experts with a shared
expert, YaRN rotary scaling.  Serving only: `ServingEngine` takes the
model through the programs of `models/generation.py`, by the hooks below;
`Trainer` does not know it (ROADMAP).

One layer, pre-norm, x one token's hidden state:

* MLA.  c_q = RMSNorm(x W_qa); [q_nope | q_rope] = c_q W_qb per head;
  [c_kv | k_rope] = x W_kva, c_kv = RMSNorm(c_kv), k_rope = RoPE(k_rope),
  ONE k_rope for all heads; [k_nope | v] = c_kv W_kvb per head;
  k = [k_nope | k_rope], q = [q_nope | RoPE(q_rope)]; causal softmax of
  q.k * s, times v, through W_o.  **A token's cache entry is
  [c_kv | k_rope]**, `latent_dim` values, stored padded to whole 128-lane
  rows.  Chunked prefill and the whole-sequence forward use this
  EXPANDED form over the cached latents; the decode step uses the
  ABSORBED form, the same mathematics: q_lat = q_nope W_kvb[k]^T, scores
  q_lat.c_kv + q_rope.k_rope, o_lat = P c_kv, o = o_lat W_kvb[v]: 64
  heads against one cached vector (`ops/pallas/paged_latent_attention`).
* Rotation is written half-split (as `ops.apply_rotary`); the published
  code de-interleaves q_rope and k_rope first, a fixed permutation of
  weight columns that random weights do not see.
* The expert layer is `nn.moe.SharedRoutedExperts`.
"""
from __future__ import annotations

import copy
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from hetu_tpu import ops
from hetu_tpu.models.cache_contract import CacheContract
from hetu_tpu.models.kimi_k2.config import KimiK2Config
from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.module import Module
from hetu_tpu.nn.moe import (MOE_STATS, SharedRoutedExperts,
                             add_moe_stats, moe_layer_stats, zero_moe_stats)
from hetu_tpu.nn.parallel import ParallelRMSNorm, VocabParallelEmbedding
from hetu_tpu.parallel.strategy import ParallelStrategy

NEG_INF = -1e30


class MLAttention(Module):
    def __init__(self, config: KimiK2Config, strategy: ParallelStrategy):
        super().__init__()
        self.config = c = config
        w = init.normal(c.initializer_range)
        nh, dt = c.num_attention_heads, c.param_dtype
        self.param("wq_a", (c.hidden_size, c.q_lora_rank), w, dtype=dt)
        self.q_norm = ParallelRMSNorm(c.q_lora_rank, strategy,
                                      eps=c.rms_norm_eps, param_dtype=dt)
        self.param("wq_b", (c.q_lora_rank, nh * c.qk_head_dim), w, dtype=dt)
        self.param("wkv_a", (c.hidden_size, c.latent_dim), w, dtype=dt)
        self.kv_norm = ParallelRMSNorm(c.kv_lora_rank, strategy,
                                       eps=c.rms_norm_eps, param_dtype=dt)
        self.param("wkv_b", (c.kv_lora_rank, nh,
                             c.qk_nope_head_dim + c.v_head_dim), w, dtype=dt)
        self.param("wo", (nh * c.v_head_dim, c.hidden_size), w, dtype=dt)
        #: factors on the low-rank query after W_qb and on the normed
        #: latent before W_kvb and the cache (LongCat-Flash's
        #: `mla_scale_q_lora` / `mla_scale_kv_lora`); at 1.0, Kimi's, no
        #: product
        self.q_lora_scale = c.mla_q_lora_scale
        self.kv_lora_scale = c.mla_kv_lora_scale

    # -- how a token's cache entry is made ---------------------------------
    def project(self, params, hn, rope, pos_ids):
        """hn [b, s, h] (normed) at positions pos_ids [b, s] ->
        (q, entries): q = (q_nope [b, s, nh, dn], q_rope [b, s, nh, dr],
        rotated); entries = (latent [b, s, stored],), the token's cache
        entry [RMSNorm(c_kv) | RoPE(k_rope) | 0 ...] (the normed latent
        times `kv_lora_scale`: what is cached is what W_kvb takes)."""
        q, q_rope, _ = self.project_queries(params, hn, rope, pos_ids)
        entries = self.project_entries(params, hn, rope, pos_ids)
        return (q[..., :self.config.qk_nope_head_dim], q_rope), entries

    def project_queries(self, params, hn, rope, pos_ids):
        """(a head's [q_nope | q_rope] before the rotation [b, s, nh, dn +
        dr], q_rope rotated, the normed low-rank query c_q [b, s,
        q_lora_rank] both are made from: what a layer's indexer reads
        too, models/deepseek_v32)."""
        c = self.config
        cos, sin = rope
        dn = c.qk_nope_head_dim
        with jax.named_scope("mla_q"):
            cq = self.q_norm(params["q_norm"],
                             hn @ params["wq_a"].astype(hn.dtype))
            q = (cq @ params["wq_b"].astype(hn.dtype)).reshape(
                cq.shape[:-1] + (c.num_attention_heads, c.qk_head_dim))
            if self.q_lora_scale != 1.0:
                q = (q.astype(jnp.float32) * self.q_lora_scale).astype(q.dtype)
            q_rope = ops.apply_rotary(q[..., dn:], cos, sin, pos_ids)
        return q, q_rope, cq

    def project_entries(self, params, hn, rope, pos_ids):
        """`project`'s entries: (latent [b, s, stored],)."""
        c = self.config
        cos, sin = rope
        r = c.kv_lora_rank
        with jax.named_scope("mla_kv"):
            ckv = hn @ params["wkv_a"].astype(hn.dtype)
            k_rope = ops.apply_rotary(ckv[..., None, r:], cos, sin,
                                      pos_ids)[..., 0, :]
            c_kv = self.kv_norm(params["kv_norm"], ckv[..., :r])
            if self.kv_lora_scale != 1.0:
                # (in float32: sqrt(12) is no bfloat16 number)
                c_kv = (c_kv.astype(jnp.float32)
                        * self.kv_lora_scale).astype(c_kv.dtype)
            latent = jnp.concatenate(
                [c_kv, k_rope]
                + ([jnp.zeros(ckv.shape[:-1] + (
                    c.latent_stored_dim - c.latent_dim,), ckv.dtype)]
                   if c.latent_stored_dim > c.latent_dim else []), axis=-1)
        return (latent,)

    # -- how a query attends the cache --------------------------------------
    def attend_dense(self, params, q, caches, start, keep=None):
        """EXPANDED form.  q of a C-token block at positions
        start[b] + i; caches = (latents [b, M, stored],) holding every
        position <= start + C - 1.  Returns [b, C, nh * dv].  `keep`
        [b, C, M] bool (a layer that selects what it attends: models/
        deepseek_v32): of the positions a query sees, those it attends.

        Which shapes take which attention (the route record
        `kernel_routes["latent_chunk_attn"]` says it per traced layer):
        ONE row's chunk of C > 1 queries at one start, the chunk program
        of chunked prefill, takes the blockwise kernel
        (ops/pallas/latent_chunk_attention: a head's k_nope | v made
        from a key block's latents inside the kernel, the scores on the
        chip, only the key blocks the chunk can see read) where
        `ops.pallas.resolve_route` and the kernel's gate allow.
        Everything else keeps the XLA composition `_attend_composed`:
        any shape the gate refuses, every backend but a TPU, a single
        query (C = 1: the decode step over a dense cache) and rows at
        depths of their own (start [b > 1]: the verify step), which no
        flag forces.  Both are the same arithmetic in the same
        precisions; the online softmax of the kernel is
        `ops/pallas/chunk_attention`'s, of the composition its own loop."""
        from hetu_tpu.ops.pallas import latent_chunk_attention as _lca
        from hetu_tpu.ops.pallas import _note_route, resolve_route
        q_nope, q_rope = q
        (lat,) = caches
        if q_nope.shape[0] == 1 and q_nope.shape[1] > 1:
            kernel = resolve_route(
                "latent_chunk_attn", _lca.check_route, q_nope.shape,
                q_rope.shape, lat.shape, params["wkv_b"].shape,
                jnp.shape(start), dtype=lat.dtype)
        else:
            kernel = False
            _note_route("latent_chunk_attn", False,
                        "a single query, or rows at depths of their own: "
                        "the composition")
        if not kernel:
            return self._attend_composed(params, q, caches, start, keep=keep)
        with jax.named_scope("pallas_latent_chunk_attention"):
            return _lca.latent_chunk_attention(
                q_nope, q_rope, lat, params["wkv_b"], start,
                softmax_scale=self.config.softmax_scale,
                keep=None if keep is None else keep[0])

    def _attend_composed(self, params, q, caches, start, block: int = 512,
                         keep=None):
        """`attend_dense` as an XLA composition: keys are walked in
        blocks of `block` cached positions up to the last one any query
        sees (a loop with a data-dependent trip count, online softmax),
        each block's k_nope and v made from its latents by W_kvb.  `keep`
        [b, C, M] bool (a layer that selects what it attends): of the
        positions a query sees, those it attends."""
        c = self.config
        q_nope, q_rope = q
        (lat,) = caches
        b, C, nh, dn = q_nope.shape
        M, r, dr, dv = lat.shape[1], c.kv_lora_rank, c.qk_rope_head_dim, \
            c.v_head_dim
        kb = min(block, M)
        if M % kb:
            raise ValueError(f"cache length {M} is not a multiple of the "
                             f"key block {kb}")
        start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
        qpos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        wkv_b = params["wkv_b"].astype(lat.dtype)
        f32 = jnp.float32

        def body(i, carry):
            m, l, acc = carry
            blk = lax.dynamic_slice_in_dim(lat, i * kb, kb, axis=1)
            kv = jnp.einsum("bkr,rnd->bknd", blk[..., :r], wkv_b)
            s = (jnp.einsum("bqnd,bknd->bnqk", q_nope, kv[..., :dn],
                            preferred_element_type=f32)
                 + jnp.einsum("bqnd,bkd->bnqk", q_rope, blk[..., r:r + dr],
                              preferred_element_type=f32)) * c.softmax_scale
            kpos = i * kb + jnp.arange(kb, dtype=jnp.int32)
            seen = kpos[None, None, :] <= qpos[:, :, None]     # [b, C, kb]
            if keep is not None:
                seen = seen & lax.dynamic_slice_in_dim(keep, i * kb, kb,
                                                       axis=2)
            s = jnp.where(seen[:, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr + jnp.einsum(
                "bnqk,bknd->bnqd", p.astype(lat.dtype), kv[..., dn:],
                preferred_element_type=f32)
            return m_new, l, acc

        blocks = (jnp.max(start) + C + kb - 1) // kb
        m, l, acc = lax.fori_loop(
            0, jnp.minimum(blocks, M // kb), body,
            (jnp.full((b, nh, C, 1), NEG_INF, f32),
             jnp.zeros((b, nh, C, 1), f32),
             jnp.zeros((b, nh, C, dv), f32)))
        out = acc / jnp.where(l == 0.0, 1.0, l)
        return out.transpose(0, 2, 1, 3).reshape(b, C, nh * dv) \
            .astype(q_nope.dtype)

    def absorb_query(self, params, q):
        """The absorbed decode query [b, nh, stored]:
        [q_nope W_kvb[k]^T | q_rope | 0 ...] of single-token q."""
        c = self.config
        q_nope, q_rope = q
        w_k = params["wkv_b"][..., :c.qk_nope_head_dim].astype(q_nope.dtype)
        q_lat = jnp.einsum("bnd,rnd->bnr", q_nope[:, 0], w_k)
        pad = c.latent_stored_dim - c.latent_dim
        parts = [q_lat, q_rope[:, 0]]
        if pad:
            parts.append(jnp.zeros(q_lat.shape[:-1] + (pad,), q_lat.dtype))
        return jnp.concatenate(parts, axis=-1)

    def attend_paged(self, params, q, pools, table, positions, base):
        """ABSORBED form over the paged pool.  pools = (latent pages of
        all layers [L * P, ps, stored],), this layer's P pages from
        `base` on; table [S, max_pages] of page ids within a layer;
        slot s attends positions <= positions[s].  -> [S, 1, nh * dv]."""
        from hetu_tpu.ops.pallas import paged_latent_attention as _pla
        from hetu_tpu.ops.pallas import resolve_route
        c = self.config
        (pool,) = pools
        table = table + base
        qa = self.absorb_query(params, q)                # [S, nh, stored]
        kw = dict(value_dim=c.kv_lora_rank, softmax_scale=c.softmax_scale)
        if resolve_route("paged_latent", _pla.check_shapes, qa.shape,
                         pool.shape, table.shape, positions.shape,
                         value_dim=c.kv_lora_rank):
            with jax.named_scope("pallas_paged_latent_attention"):
                o_lat = _pla.paged_latent_attention(qa, pool, table,
                                                    positions, **kw)
        else:
            with jax.named_scope("paged_latent_attention_xla"):
                o_lat = _pla.paged_latent_attention_xla(qa, pool, table,
                                                        positions, **kw)
        return self.expand_output(params, o_lat)

    def expand_output(self, params, o_lat):
        """The absorbed form's latent output [S, nh, r] through W_kvb's
        value columns -> [S, 1, nh * dv]."""
        w_v = params["wkv_b"][..., self.config.qk_nope_head_dim:].astype(
            o_lat.dtype)
        o = jnp.einsum("bnr,rnd->bnd", o_lat, w_v)
        return o.reshape(o.shape[0], 1, -1)

    def output(self, params, attn):
        with jax.named_scope("mla_out"):
            return attn @ params["wo"].astype(attn.dtype)

    def attend_prompt(self, params, q, entries):
        """Whole prompts attending their own entries, causally: the
        composition (`prefill` and `forward`: on no cell's path, and what
        the tests compare the chunk program with)."""
        b, s = entries[0].shape[:2]
        return self._attend_composed(params, q, entries,
                                     jnp.zeros((b,), jnp.int32),
                                     block=math.gcd(s, 512))

    def forward(self, params, hn, rope, pos_ids):
        """Whole sequences hn [b, s, h] at positions 0..s-1."""
        q, entries = self.project(params, hn, rope, pos_ids)
        return self.output(params, self.attend_prompt(params, q, entries))


class DenseMLP(Module):
    """SwiGLU of the leading dense layers (fused gate|up [h, 2 I])."""

    def __init__(self, config: KimiK2Config):
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


class KimiBlock(Module):
    #: the attention of every layer (models/deepseek_v32 brings its own,
    #: which selects what it attends)
    ATTENTION = MLAttention

    def __init__(self, config: KimiK2Config, strategy: ParallelStrategy,
                 *, moe: bool):
        super().__init__()
        c = config
        self.moe = moe
        norm = dict(eps=c.rms_norm_eps, param_dtype=c.param_dtype)
        self.input_norm = ParallelRMSNorm(c.hidden_size, strategy, **norm)
        self.attn = self.ATTENTION(c, strategy)
        self.post_norm = ParallelRMSNorm(c.hidden_size, strategy, **norm)
        if moe:
            self.mlp = SharedRoutedExperts(
                c.hidden_size, c.moe_intermediate_size,
                n_routed_experts=c.n_routed_experts,
                experts_held=c.experts_held, first_expert=c.first_expert,
                top_k=c.num_experts_per_tok,
                n_shared_experts=c.n_shared_experts,
                norm_topk_prob=c.norm_topk_prob,
                routed_scaling_factor=c.routed_scaling_factor,
                param_dtype=c.param_dtype,
                initializer_range=c.initializer_range,
                bias_range=c.correction_bias_range,
                n_group=c.n_group, topk_group=c.topk_group)
        else:
            self.mlp = DenseMLP(c)

    def mlp_stats(self, params, x):
        """(mlp(x), MOE_STATS of this execution; zeros for a dense
        layer)."""
        if not self.moe:
            return self.mlp(params, x), zero_moe_stats()
        y, st = self.mlp(params, x)
        return y, moe_layer_stats(st)

    def forward(self, params, x, rope, pos_ids):
        with jax.named_scope("attn"):
            x = x + self.attn(params["attn"],
                              self.input_norm(params["input_norm"], x),
                              rope, pos_ids)
        with jax.named_scope("mlp"):
            y, _ = self.mlp_stats(params["mlp"],
                                  self.post_norm(params["post_norm"], x))
        return x + y


class _Layers(Module):
    """`num` blocks of one kind, one parameter subtree `layer_<i>` each,
    run one after the other.  Never stacked to scan: a scan over stacked
    weights slices each layer's out of the stack into a fresh buffer at
    every execution, 1.35 GB a layer a decode step at Kimi-K2's widths
    (35.2 against 13.3 ms a step: my chip run, PR 27)."""

    def __init__(self, block: KimiBlock, num: int):
        super().__init__()
        self.block, self.num = block, num

    def param_specs(self):
        specs = self.block.param_specs()
        return {f"layer_{i}": copy.deepcopy(specs) for i in range(self.num)}

    def runs(self, params):
        """(block, a layer's own parameters, None) per layer: runs of one,
        which the walk of models/generation.py calls and never scans."""
        return [(self.block, params[f"layer_{i}"], None)
                for i in range(self.num)]


class KimiK2Model(Module):
    def __init__(self, config: KimiK2Config, strategy: ParallelStrategy,
                 block=KimiBlock):
        super().__init__()
        c = config
        self.embed = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size, strategy, param_dtype=c.param_dtype,
            weight_init=init.normal(c.initializer_range))
        self.dense_layers = _Layers(block(c, strategy, moe=False),
                                    c.first_k_dense_replace)
        self.moe_layers = _Layers(block(c, strategy, moe=True),
                                  c.num_moe_layers)
        self.final_norm = ParallelRMSNorm(c.hidden_size, strategy,
                                          eps=c.rms_norm_eps,
                                          param_dtype=c.param_dtype)


class KimiK2LMHeadModel(Module):
    #: the block of every layer (models/xing4 brings its own around this
    #: one's sublayers)
    BLOCK = KimiBlock

    def __init__(self, config: KimiK2Config,
                 strategy: Optional[ParallelStrategy] = None):
        super().__init__()
        strategy = strategy or ParallelStrategy()
        if strategy.mesh.num_devices > 1:
            raise NotImplementedError(
                "models/kimi_k2 runs on one device: experts across chips "
                "(ep > 1) and a sharded MLA are not built (ROADMAP)")
        self.config, self.strategy = config, strategy
        self.model = KimiK2Model(config, strategy, self.BLOCK)
        if config.tie_word_embeddings:
            raise NotImplementedError("Kimi-K2's head is untied")
        self.param("lm_head", (config.hidden_size, config.vocab_size),
                   init.normal(config.initializer_range),
                   dtype=config.param_dtype)

    # -- what the serving programs of models/generation.py take -----------
    def cache_contract(self) -> CacheContract:
        c = self.config
        return CacheContract(c.num_hidden_layers, ((c.latent_dim,),),
                             ((c.latent_stored_dim,),), c.compute_dtype,
                             kind="latent")

    def rope_tables(self, max_len: int):
        c = self.config
        rs = c.rope_scaling
        if not rs:
            return ops.build_rope_cache(max_len, c.qk_rope_head_dim,
                                        c.rope_theta)
        return ops.build_yarn_rope_cache(
            max_len, c.qk_rope_head_dim, c.rope_theta,
            **{k: rs[k] for k in (
                "factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "mscale", "mscale_all_dim") if k in rs})

    zero_stats = staticmethod(zero_moe_stats)
    add_stats = staticmethod(add_moe_stats)
    STATS = MOE_STATS

    def embed_tokens(self, params, ids, pos_ids):
        return self.model.embed(params["model"]["embed"], ids).astype(
            self.config.compute_dtype)

    def serving_layers(self, params):
        """Runs (block, parameters, count) in the pool's layer order:
        the leading dense layers, then the expert layers."""
        m, mp = self.model, params["model"]
        return (m.dense_layers.runs(mp["dense_layers"])
                + m.moe_layers.runs(mp["moe_layers"]))

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
