"""DeepSeek-V3.2: Kimi-K2's block (the one DeepSeek-V3 published: latent
attention, leading dense layers, then sigmoid-routed experts in groups
beside a shared one, YaRN) whose attention SELECTS what it attends
(DeepSeek Sparse Attention).  Serving only: `ServingEngine` takes the
model through the programs of `models/generation.py`.

In every layer, for the token at position t with hn_t its normed hidden
state and c_q,t MLA's own normed low-rank query (`MLAttention.
project_queries`):

    q^I_{t,j} = (c_q,t W^I_qb)_j   in R^D, j = 1..H; first `qk_rope_head_dim`
                values rotated (RoPE at t, MLA's tables, half-split)
    k^I_s     = LayerNorm(hn_s W^I_k) in R^D; the same values rotated at s:
                ONE key a token for all heads, the token's SECOND cache entry
    w_{t,j}   = (hn_t W^I_w)_j * H^-1/2 * D^-1/2
    I_{t,s}   = sum_j w_{t,j} ReLU(q^I_{t,j} . k^I_s)         s <= t, float32
    S_t       = the min(index_topk, t + 1) positions of largest I_{t,s};
                equal scores: the lower position
    o_t       = MLA's attention of q_t over the positions of S_t alone

`ops/sparse_attention.py` has the scores, the exact selection and the
attention over a gathered selection.  The three programs order them the
same way, score -> select -> attend (scopes `dsa_score`, `dsa_select`,
`dsa_attend` inside `attn`, beside `dsa_index_q` and `dsa_index_k` where
the indexer's projections run):

* the chunk program and whole prompts (`attend_dense`, `attend_prompt`)
  score a chunk's rows against every cached index key, make the mask of
  the selection and attend UNDER IT in MLA's expanded form: the blockwise
  kernel `ops/pallas/latent_chunk_attention` given `keep`, or the
  composition.  Dense work, sparse mathematics (PERF.md s6 has the
  reading of the gathered form beside it);
* the decode step (`attend_paged`) reads the index keys of each slot's
  context through the page table, selects, GATHERS the selected latents
  (position -> page and offset -> the entry: `index_topk` a slot a layer
  and no more) and attends them in MLA's absorbed form.

A token's cache entries are two, under one page table: MLA's latent
`[c_kv | k_rope | 0 ...]` and the index key.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu import ops
from hetu_tpu.models.cache_contract import CacheContract
from hetu_tpu.models.deepseek_v32.config import DeepseekV32Config
from hetu_tpu.models.kimi_k2.model import (KimiBlock, KimiK2LMHeadModel,
                                           MLAttention)
from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.module import Module
from hetu_tpu.nn.parallel import ParallelLayerNorm
from hetu_tpu.ops import sparse_attention as dsa
from hetu_tpu.parallel.strategy import ParallelStrategy


#: the epsilon of the LayerNorm on the index key (no key of the published
#: config: the published code's value)
INDEX_NORM_EPS = 1e-6


class LightningIndexer(Module):
    """The indexer's projections: queries and head weights of a token,
    and the key it stores."""

    def __init__(self, config: DeepseekV32Config,
                 strategy: ParallelStrategy):
        super().__init__()
        c = config
        self.heads, self.dim = c.index_n_heads, c.index_head_dim
        self.rotated = c.qk_rope_head_dim
        w, dt = init.normal(c.initializer_range), c.param_dtype
        self.param("wq_b", (c.q_lora_rank, self.heads * self.dim), w,
                   dtype=dt)
        self.param("wk", (c.hidden_size, self.dim), w, dtype=dt)
        self.k_norm = ParallelLayerNorm(self.dim, strategy,
                                        eps=INDEX_NORM_EPS, param_dtype=dt)
        self.param("w_heads", (c.hidden_size, self.heads), w, dtype=dt)
        self.scale = self.heads ** -0.5 * self.dim ** -0.5

    def _rotate(self, x, rope, pos_ids):
        """x [b, s, heads, D]: its first `rotated` values rotated."""
        cos, sin = rope
        return jnp.concatenate(
            [ops.apply_rotary(x[..., :self.rotated], cos, sin, pos_ids),
             x[..., self.rotated:]], axis=-1)

    def queries(self, params, cq, hn, rope, pos_ids):
        """-> (q^I [b, s, H, D], w [b, s, H] float32)."""
        with jax.named_scope("dsa_index_q"):
            q = (cq @ params["wq_b"].astype(cq.dtype)).reshape(
                cq.shape[:-1] + (self.heads, self.dim))
            w = jnp.einsum("bsh,hj->bsj", hn,
                           params["w_heads"].astype(hn.dtype),
                           preferred_element_type=jnp.float32) * self.scale
            return self._rotate(q, rope, pos_ids), w

    def key(self, params, hn, rope, pos_ids):
        """-> k^I [b, s, D]."""
        with jax.named_scope("dsa_index_k"):
            k = self.k_norm(params["k_norm"],
                            hn @ params["wk"].astype(hn.dtype))
            return self._rotate(k[..., None, :], rope, pos_ids)[..., 0, :]


class DSAttention(MLAttention):
    """MLA whose queries attend the positions an indexer selects."""

    def __init__(self, config: DeepseekV32Config,
                 strategy: ParallelStrategy):
        super().__init__(config, strategy)
        self.indexer = LightningIndexer(config, strategy)
        self.topk = config.index_topk

    def project(self, params, hn, rope, pos_ids):
        """MLA's `project` with the indexer's part: q = (q_nope, q_rope,
        q^I, w); entries = (latent [b, s, stored], k^I [b, s, D])."""
        q, q_rope, cq = self.project_queries(params, hn, rope, pos_ids)
        qi, w = self.indexer.queries(params["indexer"], cq, hn, rope,
                                     pos_ids)
        return (q[..., :self.config.qk_nope_head_dim], q_rope, qi, w), (
            self.project_entries(params, hn, rope, pos_ids)
            + (self.indexer.key(params["indexer"], hn, rope, pos_ids),))

    def _keep(self, q, keys, qpos):
        """The mask of every query's selection, bool [b, C, M]."""
        with jax.named_scope("dsa_score"):
            scores = dsa.index_scores(q[2], q[3], keys, qpos)
        with jax.named_scope("dsa_select"):
            return dsa.select_mask(scores, self.topk)

    def attend_dense(self, params, q, caches, start):
        """MLA's `attend_dense` (the kernel or the composition, by the
        same route) under the mask of the selection.  caches = (latents
        [b, M, stored], index keys [b, M, D])."""
        lat, keys = caches
        b, C = q[0].shape[:2]
        M = keys.shape[1]
        start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
        qpos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        # (over the part of the scratch the chunk can see: by_width)
        keep = dsa.by_width(M, jnp.max(qpos) + 1, lambda W: jnp.pad(
            self._keep(q, keys[:, :W], qpos), ((0, 0), (0, 0), (0, M - W))))
        with jax.named_scope("dsa_attend"):
            return super().attend_dense(params, q[:2], (lat,), start,
                                        keep=keep)

    def attend_prompt(self, params, q, entries):
        b, s = entries[0].shape[:2]
        keep = self._keep(q, entries[1], jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32), (b, s)))
        with jax.named_scope("dsa_attend"):
            return self._attend_composed(
                params, q[:2], entries[:1], jnp.zeros((b,), jnp.int32),
                block=math.gcd(s, 512), keep=keep)

    def attend_paged(self, params, q, pools, table, positions, base):
        """Score the slot's context through the page table, select, and
        attend the GATHERED selection in the absorbed form.  pools =
        (latent pages [L * P, ps, stored], index-key pages [L * P, ps,
        D]).  -> [S, 1, nh * dv]."""
        c = self.config
        lat_pool, key_pool = pools
        S, ps = positions.shape[0], lat_pool.shape[1]
        table = table + base
        M = table.shape[1] * ps
        K = min(self.topk, M)
        with jax.named_scope("dsa_score"):
            keys = key_pool[table].reshape(S, M, key_pool.shape[-1])
            scores = dsa.index_scores(q[2], q[3], keys,
                                      positions[:, None])[:, 0]
        with jax.named_scope("dsa_select"):
            idx, valid = dsa.select_indices(scores, K)
        with jax.named_scope("dsa_attend"):
            page = jnp.take_along_axis(table, idx // ps, axis=1)
            o_lat = dsa.attend_selected(
                self.absorb_query(params, q[:2]), lat_pool[page, idx % ps],
                valid, value_dim=c.kv_lora_rank,
                softmax_scale=c.softmax_scale)
            return self.expand_output(params, o_lat)


class DeepseekV32Block(KimiBlock):
    ATTENTION = DSAttention


class DeepseekV32LMHeadModel(KimiK2LMHeadModel):
    BLOCK = DeepseekV32Block

    def __init__(self, config: DeepseekV32Config,
                 strategy: Optional[ParallelStrategy] = None):
        super().__init__(config, strategy)
        if config.embed_initializer_range is not None:
            specs = self.model.embed._params
            specs["weight"] = dataclasses.replace(
                specs["weight"],
                init=init.normal(config.embed_initializer_range))

    def cache_contract(self) -> CacheContract:
        c = self.config
        return CacheContract(
            c.num_hidden_layers, ((c.latent_dim,), (c.index_head_dim,)),
            ((c.latent_stored_dim,), (c.index_head_dim,)), c.compute_dtype,
            kind="latent", selects=(c.index_topk,) * c.num_hidden_layers)
