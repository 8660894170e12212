"""GPT-2/3 model family.

Rebuild of the reference GPT (reference: python/hetu/models/gpt/gpt_model.py +
tests/ci_test/hetu_gpt_ds_parallel.py — the CI workload model): learned
position embeddings, pre-LN blocks, GELU MLP, MHA with biases, tied LM head
by default.  Shares the TPU-first machinery of the LLaMA family (strategy-
driven layouts, scan-over-layers + remat, flash attention, pipeline, CP).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from hetu_tpu import ops
from hetu_tpu.dstates import DistributedStates as DS
from hetu_tpu.models.cache_contract import KVAttention
from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.module import Module, stack_param_specs
from hetu_tpu.nn.parallel import (ParallelLayerNorm, RowParallelLinear,
                                  VocabParallelEmbedding)
from hetu_tpu.parallel.strategy import ParallelStrategy


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 1024
    layer_norm_eps: float = 1e-5
    # the GPT family has a hetero-TP pipeline block maker too
    # (parallel/hetero_pp.py gpt_block_maker)
    supports_hetero_tp: bool = True
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    attention_dropout: float = 0.0
    hidden_dropout: float = 0.0

    # heterogeneous pipeline stage layer counts (see LlamaConfig)
    pipeline_stage_layers: object = None

    param_dtype: object = jnp.float32
    compute_dtype: object = jnp.bfloat16
    use_scan: bool = True
    remat: bool = True
    remat_policy: str = "nothing"
    use_flash_attention: bool = True

    def __post_init__(self):
        from hetu_tpu.nn.remat import validate_remat_policy
        validate_remat_policy(self.remat_policy)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def intermediate_size(self) -> int:
        return 4 * self.hidden_size

    @staticmethod
    def tiny(**kw) -> "GPTConfig":
        d = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                 num_attention_heads=4, max_position_embeddings=256)
        d.update(kw)
        return GPTConfig(**d)

    @staticmethod
    def gpt2_large(**kw) -> "GPTConfig":
        d = dict(hidden_size=1280, num_hidden_layers=36,
                 num_attention_heads=20)
        d.update(kw)
        return GPTConfig(**d)

    def num_params(self) -> int:
        h, L, v = self.hidden_size, self.num_hidden_layers, self.vocab_size
        per_layer = 4 * h * h + 2 * 4 * h * h + 9 * h + 4 * h  # qkv/o + mlp + biases/norms
        emb = v * h + self.max_position_embeddings * h
        return L * per_layer + emb + 2 * h

    def flops_per_token(self, seq_len: int) -> float:
        n = self.num_params()
        return 6.0 * n + 12 * self.num_hidden_layers * self.hidden_size * seq_len


class GPTAttention(Module, KVAttention):
    """MHA with biases (reference: gpt_model.py GPTAttention)."""

    def __init__(self, config: GPTConfig, strategy: ParallelStrategy):
        super().__init__()
        self.config, self.strategy = config, strategy
        c, hd = config, config.head_dim
        self.n_heads = c.num_attention_heads
        if self.n_heads % max(strategy.tp, 1):
            raise ValueError(f"heads={self.n_heads} vs tp={strategy.tp}")
        # [h, heads, 3, hd]: per head [q|k|v] — TP splits the heads dim
        qkv_ds = strategy.fsdp(
            DS.make(4, {1: "tp"}) if strategy.tp > 1 else None, 4, 0)
        self.param("wqkv", (c.hidden_size, self.n_heads, 3, hd),
                   init.normal(c.initializer_range), dtype=c.param_dtype,
                   ds=qkv_ds)
        self.param("bqkv", (self.n_heads, 3, hd), init.zeros,
                   dtype=c.param_dtype,
                   ds=DS.make(3, {0: "tp"}) if strategy.tp > 1 else None)
        self.o_proj = RowParallelLinear(
            c.hidden_size, c.hidden_size, strategy, bias=True,
            param_dtype=c.param_dtype,
            weight_init=init.normal(c.initializer_range))

    def forward(self, params, x, *, position_ids=None, segment_ids=None,
                rng=None, deterministic=True):
        c, st = self.config, self.strategy
        b, s, h = x.shape
        hd = c.head_dim
        qkv = jnp.einsum("bsh,hngd->bsngd", x, params["wqkv"].astype(x.dtype))
        qkv = qkv + params["bqkv"].astype(x.dtype)
        qkv = st.constrain(qkv, st.act_qkv())
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        use_attn_dropout = (c.attention_dropout > 0.0 and not deterministic
                            and rng is not None)
        if st.cp > 1:
            from hetu_tpu.parallel.ring_attention import ring_attention_gspmd
            attn = ring_attention_gspmd(q, k, v, strategy=st,
                                        segment_ids=segment_ids,
                                        position_ids=position_ids)
        elif use_attn_dropout:
            attn = ops.attention(q, k, v, causal=True, segment_ids=segment_ids,
                                 dropout_rate=c.attention_dropout,
                                 dropout_rng=jax.random.fold_in(rng, 1))
        else:
            attn = ops.flash_attention(
                q, k, v, causal=True, segment_ids=segment_ids,
                use_pallas=None if c.use_flash_attention else False,
                layout=st.act_attn())
        # each route names what the "dots_attn" remat policy keeps of it
        # where it makes it; none is put here (models/llama/model.py)
        attn = st.constrain(attn, st.act_attn())
        return self.o_proj(params["o_proj"], attn.reshape(b, s, h))

    # -- the serving programs' hooks (models/generation.py); how a query
    # attends the cached K/V is `KVAttention`'s ----------------------------
    def project(self, params, hn, rope, pos_ids):
        """hn [b, s, h] (normed) -> (q, entries (k, v)), each
        [b, s, heads, hd]: the biased fused projection of `forward`
        (positions are in the embedding: `rope` is None)."""
        qkv = jnp.einsum("bsh,hngd->bsngd", hn,
                         params["wqkv"].astype(hn.dtype)) \
            + params["bqkv"].astype(hn.dtype)
        return qkv[..., 0, :], (qkv[..., 1, :], qkv[..., 2, :])

    def output(self, params, attn):
        return self.o_proj(params["o_proj"], attn)


class GPTMLP(Module):
    def __init__(self, config: GPTConfig, strategy: ParallelStrategy):
        super().__init__()
        self.strategy = strategy
        c = config
        i = c.intermediate_size
        self.param("w_up", (c.hidden_size, i),
                   init.normal(c.initializer_range), dtype=c.param_dtype,
                   ds=strategy.col_weight())
        self.param("b_up", (i,), init.zeros, dtype=c.param_dtype,
                   ds=strategy.col_bias())
        self.down = RowParallelLinear(i, c.hidden_size, strategy, bias=True,
                                      param_dtype=c.param_dtype,
                                      weight_init=init.normal(c.initializer_range))

    def forward(self, params, x):
        st = self.strategy
        y = x @ params["w_up"].astype(x.dtype) + params["b_up"].astype(x.dtype)
        y = st.constrain(y, st.act_inner())
        return self.down(params["down"], ops.gelu(y))


class GPTBlock(Module):
    def __init__(self, config: GPTConfig, strategy: ParallelStrategy):
        super().__init__()
        self.config = config
        c = config
        self.ln1 = ParallelLayerNorm(c.hidden_size, strategy,
                                     eps=c.layer_norm_eps,
                                     param_dtype=c.param_dtype)
        self.attn = GPTAttention(c, strategy)
        self.ln2 = ParallelLayerNorm(c.hidden_size, strategy,
                                     eps=c.layer_norm_eps,
                                     param_dtype=c.param_dtype)
        self.mlp = GPTMLP(c, strategy)

    # the serving programs' names for the two norms and the MLP
    # (`serving_layers` hands the parameters out under the same names)
    input_norm = property(lambda self: self.ln1)
    post_norm = property(lambda self: self.ln2)

    def mlp_stats(self, params, x):
        return self.mlp(params, x), None

    def forward(self, params, x, *, position_ids=None, segment_ids=None,
                rng=None, deterministic=True):
        c = self.config
        # phase scopes for HLO/trace attribution (see LlamaBlock.forward)
        with jax.named_scope("attn"):
            h = self.attn(params["attn"], self.ln1(params["ln1"], x),
                          position_ids=position_ids, segment_ids=segment_ids,
                          rng=rng, deterministic=deterministic)
        if not deterministic and rng is not None:
            h = ops.dropout(h, c.hidden_dropout, jax.random.fold_in(rng, 2),
                            deterministic)
        with jax.named_scope("mlp"):
            # residual-add + ln2 as ONE fused Pallas pass when routed
            # (nn/parallel.ParallelLayerNorm.residual; fallback = the
            # seed composition `x = x + h; ln2(x)`)
            normed, x = self.ln2.residual(params["ln2"], x, h)
            h = self.mlp(params["mlp"], normed)
        if not deterministic and rng is not None:
            h = ops.dropout(h, c.hidden_dropout, jax.random.fold_in(rng, 3),
                            deterministic)
        return x + h


class GPTModel(Module):
    """Backbone (reference: gpt_model.py GPTModel)."""

    def __init__(self, config: GPTConfig,
                 strategy: Optional[ParallelStrategy] = None):
        super().__init__()
        strategy = strategy or ParallelStrategy()
        self.config, self.strategy = config, strategy
        c = config
        self.wte = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size, strategy, param_dtype=c.param_dtype,
            weight_init=init.normal(c.initializer_range))
        self.param("wpe", (c.max_position_embeddings, c.hidden_size),
                   init.normal(c.initializer_range), dtype=c.param_dtype)
        self.block = GPTBlock(c, strategy)
        self.final_ln = ParallelLayerNorm(c.hidden_size, strategy,
                                          eps=c.layer_norm_eps,
                                          param_dtype=c.param_dtype)

    def param_specs(self):
        out = dict(self._params)
        out["wte"] = self.wte.param_specs()
        out["final_ln"] = self.final_ln.param_specs()
        block_specs = self.block.param_specs()
        if self.config.use_scan:
            lead = "pp" if self.strategy.pp > 1 else None
            out["blocks"] = stack_param_specs(
                block_specs, self.config.num_hidden_layers, lead_axis=lead)
        else:
            import copy
            for i in range(self.config.num_hidden_layers):
                out[f"block_{i}"] = copy.deepcopy(block_specs)
        return out

    def forward(self, params, input_ids, *, position_ids=None,
                segment_ids=None, rng=None, deterministic=True,
                n_micro=None):
        c, st = self.config, self.strategy
        b, s = input_ids.shape
        pos = position_ids if position_ids is not None else \
            jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        with jax.named_scope("embed"):
            x = self.wte(params["wte"], input_ids)
            x = x + jnp.take(params["wpe"], pos, axis=0)
            x = x.astype(c.compute_dtype)
            x = st.constrain(x, st.act_hidden())

        use_drop = not deterministic and rng is not None
        if st.pp > 1:
            if not c.use_scan:
                raise ValueError("pipeline parallelism requires use_scan")
            from hetu_tpu.core.mesh import current_mesh
            from hetu_tpu.parallel.pipeline import staged_stack_forward
            mesh = current_mesh()
            if mesh is None:
                raise ValueError("pipeline needs a mesh (use hetu_tpu.use_mesh)")

            if st.pp_tp_eff is not None:
                # per-stage hetero TP (see LlamaModel counterpart)
                from hetu_tpu.parallel.hetero_pp import (
                    gpt_block_maker, staged_stack_forward_hetero_tp)
                if st.cp > 1 or (use_drop and c.attention_dropout > 0.0):
                    raise NotImplementedError(
                        "pp_tp_eff composes with cp=1, hidden dropout only")
                x, _aux = staged_stack_forward_hetero_tp(
                    gpt_block_maker(c, tp=st.tp,
                                    sequence_parallel=st.sequence_parallel),
                    self.block.param_specs(), params["blocks"], x,
                    num_layers=c.num_hidden_layers, pp=st.pp, tp=st.tp,
                    tp_eff=st.pp_tp_eff, mesh=mesh,
                    rng=rng if use_drop else None,
                    sequence_parallel=st.sequence_parallel,
                    position_ids=position_ids, segment_ids=segment_ids,
                    stage_layers=c.pipeline_stage_layers, n_micro=n_micro,
                    remat=c.remat, remat_policy=c.remat_policy,
                    state_spec=st.pipeline_state_spec())
                return self.final_ln(params["final_ln"], x)

            def block_fn(layer_params, x_mb, pos_mb, seg_mb, rng=None):
                with jax.named_scope("layer"):
                    out = self.block(layer_params, x_mb,
                                     position_ids=pos_mb,
                                     segment_ids=seg_mb, rng=rng,
                                     deterministic=rng is None)
                return out, jnp.zeros((), jnp.float32)

            x, _aux = staged_stack_forward(
                block_fn, params["blocks"], x,
                num_layers=c.num_hidden_layers, pp=st.pp, mesh=mesh,
                position_ids=position_ids, segment_ids=segment_ids,
                stage_layers=c.pipeline_stage_layers,
                n_micro=n_micro, remat=c.remat, remat_policy=c.remat_policy,
                state_spec=st.pipeline_state_spec(),
                rng=rng if use_drop else None,
                # see llama._pipeline_forward: cp ring ppermute is not
                # branch-safe, so hetero-exec stays off under cp>1
                hetero_exec="auto" if st.cp == 1 else False)
            return self.final_ln(params["final_ln"], x)
        layer_rngs = (jax.random.split(rng, c.num_hidden_layers)
                      if use_drop else None)
        if c.use_scan:
            def body(carry, xs):
                layer_params, layer_rng = xs
                # "layer" scope: per-layer HLO attribution of the
                # scanned stack (obs.hlo_profile; see llama counterpart)
                with jax.named_scope("layer"):
                    return self.block(layer_params, carry,
                                      position_ids=position_ids,
                                      segment_ids=segment_ids,
                                      rng=layer_rng if use_drop else None,
                                      deterministic=deterministic), None
            fn = body
            if c.remat:
                from hetu_tpu.nn.remat import remat_policy
                fn = jax.checkpoint(body, policy=remat_policy(c.remat_policy))
            xs = (params["blocks"],
                  layer_rngs if use_drop else
                  jnp.zeros((c.num_hidden_layers,), jnp.uint32))
            x, _ = lax.scan(fn, x, xs)
        else:
            from hetu_tpu.nn.remat import remat_policy
            for i in range(c.num_hidden_layers):
                def blk(p, y, i=i):
                    with jax.named_scope(f"layer_{i}"):
                        return self.block(
                            p, y, position_ids=position_ids,
                            segment_ids=segment_ids,
                            rng=layer_rngs[i] if use_drop else None,
                            deterministic=deterministic)
                if c.remat:
                    blk = jax.checkpoint(blk,
                                         policy=remat_policy(c.remat_policy))
                x = blk(params[f"block_{i}"], x)
        return self.final_ln(params["final_ln"], x)


class GPTLMHeadModel(Module):
    """LM head (tied by default — reference GPTLMHeadModel)."""

    def __init__(self, config: GPTConfig,
                 strategy: Optional[ParallelStrategy] = None):
        super().__init__()
        strategy = strategy or ParallelStrategy()
        self.config, self.strategy = config, strategy
        self.model = GPTModel(config, strategy)
        if not config.tie_word_embeddings:
            if strategy.tp > 1 and config.vocab_size % strategy.tp:
                raise ValueError(
                    f"vocab size {config.vocab_size} must divide by tp="
                    f"{strategy.tp}; pad the vocab (e.g. 50257 -> 50304)")
            lm_ds = strategy.fsdp(
                DS.make(2, {1: "tp"}) if strategy.tp > 1 else None, 2, 0)
            self.param("lm_head", (config.hidden_size, config.vocab_size),
                       init.normal(config.initializer_range),
                       dtype=config.param_dtype, ds=lm_ds)

    # -- what the serving programs of models/generation.py take -----------
    #: the programs carry no stats vector for this family
    STATS = ()

    def embed_tokens(self, params, ids, pos_ids):
        mp = params["model"]
        x = self.model.wte(mp["wte"], ids) \
            + jnp.take(mp["wpe"], pos_ids, axis=0)
        return x.astype(self.config.compute_dtype)

    def rope_tables(self, max_len: int):
        return None     # learned positions, added in `embed_tokens`

    def serving_layers(self, params):
        """Runs (block, parameters, count) in the cache's layer order
        (as `LlamaLMHeadModel.serving_layers`), a layer's parameters
        under the names the programs read: ln1 / ln2 as input_norm /
        post_norm."""
        mp, n = params["model"], self.config.num_hidden_layers

        def named(lp):
            return {"input_norm": lp["ln1"], "attn": lp["attn"],
                    "post_norm": lp["ln2"], "mlp": lp["mlp"]}
        if self.config.use_scan:
            return [(self.model.block, named(mp["blocks"]), n)]
        return [(self.model.block, named(mp[f"block_{i}"]), None)
                for i in range(n)]

    def final_hidden(self, params, x):
        return self.model.final_ln(params["model"]["final_ln"], x)

    def lm_head_weight(self, params):
        """The head as a [hidden, vocab] matrix, tied or not."""
        if self.config.tie_word_embeddings:
            return params["model"]["wte"]["weight"].T
        return params["lm_head"]

    def logits(self, params, hidden):
        """hidden -> logits via the tied/untied head (one implementation
        for the training forward AND the generation decode paths)."""
        with jax.named_scope("lm_head"):
            if self.config.tie_word_embeddings:
                w = params["model"]["wte"]["weight"].astype(hidden.dtype).T
            else:
                w = params["lm_head"].astype(hidden.dtype)
            return self.strategy.constrain(hidden @ w,
                                           self.strategy.act_logits())

    def forward(self, params, input_ids, labels=None, *, position_ids=None,
                segment_ids=None, loss_reduction: str = "mean", rng=None,
                deterministic=True, n_micro=None,
                include_aux_loss: bool = True, labels_shifted: bool = False):
        # include_aux_loss: accepted for API uniformity with the MoE-capable
        # LLaMA family; GPT has no router losses so it is a no-op
        hidden = self.model(params["model"], input_ids,
                            position_ids=position_ids,
                            segment_ids=segment_ids, rng=rng,
                            deterministic=deterministic, n_micro=n_micro)
        logits = self.logits(params, hidden)
        if labels is None:
            return logits
        # labels_shifted: host pre-shifted targets (CP seq reorder) — see
        # LlamaLMHeadModel.forward
        if labels_shifted:
            lg, tgt = logits, labels
        else:
            lg, tgt = logits[:, :-1, :], labels[:, 1:]
        if loss_reduction not in ("mean", "sum"):
            raise ValueError(f"loss_reduction must be 'mean' or 'sum', got "
                             f"{loss_reduction!r}")
        if loss_reduction == "sum":
            loss = ops.softmax_cross_entropy_sparse(
                lg, tgt, ignore_index=-100, reduction="sum")
            count = jnp.sum((tgt != -100).astype(jnp.float32))
            return loss, count
        return ops.softmax_cross_entropy_sparse(
            lg, tgt, ignore_index=-100)

    # ------------------------------------------------------------------
    def pipeline_train_grads(self, params, input_ids, labels, *,
                             position_ids=None, segment_ids=None,
                             n_micro: int, labels_shifted: bool = False,
                             loss_scale=1.0, skip_dead_halves="auto",
                             rng=None):
        """1F1B (PipeDream-flush) training pass for the GPT family —
        ((loss_sum, count), grads); mirrors LlamaLMHeadModel
        .pipeline_train_grads (reference: executable_graph.cc:836).
        wte+wpe run inside stage 0, final_ln + (tied) head + CE inside the
        last stage; O(pp) activation ring buffer."""
        from hetu_tpu.core.mesh import current_mesh
        from hetu_tpu.nn.remat import remat_policy
        from hetu_tpu.parallel.pipeline import (
            build_stage_stack, unstack_stage_grads)
        from hetu_tpu.parallel.pipeline_1f1b import pipeline_train_1f1b

        c, st = self.config, self.strategy
        if st.pp <= 1:
            raise ValueError("pipeline_train_grads requires pp > 1")
        if st.pp_tp_eff is not None and (
                st.cp > 1 or (rng is not None and c.attention_dropout > 0.0)):
            raise NotImplementedError(
                "pp_tp_eff under 1f1b composes with cp=1, hidden dropout "
                "only (same envelope as the GPipe hetero path)")
        if not c.use_scan:
            raise ValueError("1f1b requires use_scan")
        mesh = current_mesh()
        if mesh is None:
            raise ValueError("pipeline needs a mesh (use hetu_tpu.use_mesh)")

        stack = params["model"]["blocks"]
        sp, layer_mask, stage_layers = build_stage_stack(
            stack, c.num_hidden_layers, st.pp, c.pipeline_stage_layers)
        ep = {"wte": params["model"]["wte"],
              "wpe": params["model"]["wpe"],
              "final_ln": params["model"]["final_ln"]}
        if not c.tie_word_embeddings:
            ep["lm_head"] = params["lm_head"]
        count = jnp.sum(((labels if labels_shifted else labels[:, 1:])
                         != -100).astype(jnp.float32))

        use_drop = rng is not None and (c.hidden_dropout > 0.0
                                        or c.attention_dropout > 0.0)

        def stage_scan(sp_slice, x0, pos, seg, mask_row, drop_seed, offset):
            def body(carry, xs):
                lp, mj = xs if mask_row is not None else (xs, None)
                x_c, gid = carry
                layer_rng = None
                if use_drop:
                    # masks replay exactly in the backward visit: the seed
                    # rides the saved token stream, the id is the stage
                    # offset + local layer index (see llama counterpart)
                    layer_rng = jax.random.fold_in(
                        jax.random.key(drop_seed), gid)
                out = self.model.block(lp, x_c, position_ids=pos,
                                       segment_ids=seg, rng=layer_rng,
                                       deterministic=not use_drop)
                if mj is not None:
                    out = jnp.where(mj > 0, out, x_c)
                return (out, gid + 1), None

            fn = body
            if c.remat:
                fn = jax.checkpoint(body, policy=remat_policy(c.remat_policy))
            xs = sp_slice if mask_row is None else (sp_slice, mask_row)
            from hetu_tpu.core.vma import cast_varying, vma_of
            gid0 = (offset if offset is not None
                    else cast_varying(jnp.zeros((), jnp.uint32),
                                      tuple(vma_of(x0))))
            (y, _), _ = lax.scan(fn, (x0, gid0), xs)
            return y

        def head_loss(ep_, y, lab):
            hidden = self.model.final_ln(ep_["final_ln"], y)
            if c.tie_word_embeddings:
                w = ep_["wte"]["weight"].astype(hidden.dtype).T
            else:
                w = ep_["lm_head"].astype(hidden.dtype)
            logits = hidden @ w
            if labels_shifted:
                lg, tgt = logits, lab
            else:
                lg, tgt = logits[:, :-1, :], lab[:, 1:]
            return ops.softmax_cross_entropy_sparse(
                lg, tgt, ignore_index=-100, reduction="sum")

        def embed_micro(ep_, ids, pos_row):
            """wte + wpe + cast + constrain for one [mb, s] micro — ONE
            implementation for the homogeneous stage_fn AND the hetero-TP
            round bodies (which differ only in position-row indexing)."""
            pos_eff = pos_row if pos_row is not None else jnp.broadcast_to(
                jnp.arange(ids.shape[1], dtype=jnp.int32), ids.shape)
            emb = self.model.wte(ep_["wte"], ids) \
                + jnp.take(ep_["wpe"], pos_eff, axis=0)
            return st.constrain(emb.astype(c.compute_dtype),
                                st.act_hidden())

        def stage_fn(sp_slice, ep_, x_in, feed_b, feed_s, flg):
            ids = feed_b["ids"]
            pos = feed_s.get("position_ids")
            emb = embed_micro(ep_, ids, pos)
            x0 = jnp.where(flg["is_first"] > 0, emb, x_in)
            drop = feed_s.get("dropout_rng")
            y = stage_scan(sp_slice, x0, pos, feed_s.get("segment_ids"),
                           flg.get("layer_mask"),
                           drop[0, 0] if drop is not None else None,
                           flg.get("stage_offset"))
            ce = head_loss(ep_, y, feed_b["labels"]) * flg["is_last"]
            return y, ce, jnp.zeros((), jnp.float32)

        ride = {}
        if position_ids is not None:
            ride["position_ids"] = position_ids
        if segment_ids is not None:
            ride["segment_ids"] = segment_ids
        flags_extra = {}
        if layer_mask is not None:
            flags_extra["layer_mask"] = layer_mask
        if use_drop:
            from hetu_tpu.parallel.pipeline_1f1b import build_dropout_ride
            ride["dropout_rng"], flags_extra["stage_offset"] = \
                build_dropout_ride(rng, n_micro, input_ids.shape,
                                   stage_layers)

        custom = None
        if st.pp_tp_eff is not None:
            # per-stage hetero TP round bodies (see llama counterpart)
            from hetu_tpu.parallel.hetero_pp import (
                gpt_block_maker, hetero_tp_1f1b_rounds)

            def embed_fn(ep_, feed_b, feed_s):
                pos = feed_s.get("position_ids")
                # riders carry a leading pp dim here: stage 0's row
                return embed_micro(ep_, feed_b["ids"],
                                   pos[0] if pos is not None else None)

            custom = hetero_tp_1f1b_rounds(
                gpt_block_maker(c, tp=st.tp,
                                sequence_parallel=st.sequence_parallel),
                self.model.block.param_specs(), embed_fn, head_loss,
                mesh=mesh, pp=st.pp, tp=st.tp, tp_eff=st.pp_tp_eff,
                stage_layers=stage_layers, remat=c.remat,
                remat_policy=c.remat_policy, compute_dtype=c.compute_dtype,
                token_keys=tuple(ride.keys()),
                sequence_parallel=st.sequence_parallel)

        ce_sum, _aux, d_stage, d_edge = pipeline_train_1f1b(
            stage_fn, sp, ep, input_ids, labels, ride,
            n_micro=n_micro, mesh=mesh, hidden_size=c.hidden_size,
            compute_dtype=c.compute_dtype, aux_seed=0.0,
            state_spec=st.pipeline_state_spec(), loss_scale=loss_scale,
            skip_dead_halves=skip_dead_halves,
            flags_extra=flags_extra or None, custom_rounds=custom)

        d_blocks = unstack_stage_grads(
            d_stage, c.num_hidden_layers, st.pp, stage_layers)
        grads = {"model": {"wte": d_edge["wte"], "wpe": d_edge["wpe"],
                           "blocks": d_blocks,
                           "final_ln": d_edge["final_ln"]}}
        if not c.tie_word_embeddings:
            grads["lm_head"] = d_edge["lm_head"]
        return (ce_sum, count), grads
