"""LLaMA model family, TPU-first.

Functional rebuild of the reference LLaMA
(reference: python/hetu/models/llama/llama_model.py:88 LlamaAttention,
:292 LlamaMLP, :342 LlamaBlock, :385 LlamaModel, :446 LlamaLMHeadModel)
with TPU-native choices:

- fused, kv-group-aligned QKV projection (one MXU matmul; the TP split lands
  on kv-head-group boundaries so no resharding is needed after the reshape)
- fused gate+up projection stored [h, 2, I] (TP split on I)
- scan-over-layers (`lax.scan` over stacked per-layer params) — one compiled
  block body instead of L copies; remat (`jax.checkpoint`) per block is the
  reference's recompute pass (recompute/recompute.cc) for free
- layouts come from ParallelStrategy; the same model code runs single-chip,
  TP/SP, DP×TP, and (via the parallel engines) PP and ring-attention CP.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from hetu_tpu import ops
from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.module import Module, ParamSpec, stack_param_specs
from hetu_tpu.nn.remat import remat_policy as _remat_policy
from hetu_tpu.nn.parallel import (
    ColumnParallelLinear, ParallelRMSNorm, RowParallelLinear,
    VocabParallelEmbedding,
)
from hetu_tpu.parallel.strategy import ParallelStrategy
from hetu_tpu.models.cache_contract import KVAttention
from hetu_tpu.models.llama.config import LlamaConfig
from hetu_tpu.dstates import DistributedStates as DS


class LlamaAttention(Module, KVAttention):
    """GQA attention with RoPE (reference: llama_model.py:88)."""

    def __init__(self, config: LlamaConfig, strategy: ParallelStrategy):
        super().__init__()
        self.config, self.strategy = config, strategy
        c, hd = config, config.head_dim
        self.n_q, self.n_kv = c.num_attention_heads, c.num_key_value_heads
        self.group = self.n_q // self.n_kv  # q heads per kv head
        if self.n_kv % max(strategy.tp, 1) != 0:
            raise ValueError(
                f"num_key_value_heads={self.n_kv} must divide by tp={strategy.tp}")
        # qkv weight [h, n_kv, group+2, hd]: per kv group [q...q | k | v].
        # TP shards the n_kv dim -> the fused matmul splits cleanly.
        qkv_ds = DS.make(4, {1: "tp"}) if strategy.tp > 1 else None
        qkv_ds = strategy.fsdp(qkv_ds, 4, 0)
        self.param("wqkv", (c.hidden_size, self.n_kv, self.group + 2, hd),
                   init.normal(c.initializer_range), dtype=c.param_dtype,
                   ds=qkv_ds)
        self.o_proj = RowParallelLinear(
            c.hidden_size, c.hidden_size, strategy, bias=False,
            param_dtype=c.param_dtype,
            weight_init=init.normal(c.initializer_range))

    def forward(self, params, x, *, cos, sin,
                position_ids: Optional[jnp.ndarray] = None,
                segment_ids: Optional[jnp.ndarray] = None,
                rng: Optional[jnp.ndarray] = None,
                deterministic: bool = True):
        c, st = self.config, self.strategy
        b, s, h = x.shape
        hd = c.head_dim
        qkv = jnp.einsum("bsh,hkgd->bskgd", x, params["wqkv"].astype(x.dtype))
        qkv = st.constrain(qkv, st.act_qkv())
        q = qkv[..., : self.group, :].reshape(b, s, self.n_q, hd)
        k = qkv[..., self.group, :]
        v = qkv[..., self.group + 1, :]

        # one fused Pallas pass over q AND k when routed
        # (HETU_TPU_PALLAS; fallback = the seed's two apply_rotary calls)
        q, k = ops.apply_rotary_qk(q, k, cos, sin, position_ids,
                                   layout=st.act_attn())

        use_attn_dropout = (c.attention_dropout > 0.0 and not deterministic
                            and rng is not None)
        if st.cp > 1:
            if use_attn_dropout:
                # mirror the pipeline's explicit guard — silently dropping a
                # configured attention_dropout would be a training-semantics
                # surprise
                raise NotImplementedError(
                    "attention_dropout inside ring attention (cp > 1)")
            # the ring composes with the GSPMD pipeline too (a full
            # shard_map nests cleanly inside vmap(spmd_axis_name='pp');
            # only the PARTIAL-manual shard_map mode is partitioner-hostile)
            from hetu_tpu.parallel.ring_attention import ring_attention_gspmd
            attn = ring_attention_gspmd(q, k, v, strategy=st,
                                        segment_ids=segment_ids,
                                        position_ids=position_ids)
        elif use_attn_dropout:
            # dropout on attention probs only exists in the XLA composition
            attn = ops.attention(q, k, v, causal=True, segment_ids=segment_ids,
                                 dropout_rate=c.attention_dropout,
                                 dropout_rng=jax.random.fold_in(rng, 1))
        else:
            # use_pallas=None -> auto (Pallas kernel when built & on TPU)
            attn = ops.flash_attention(
                q, k, v, causal=True, segment_ids=segment_ids,
                use_pallas=None if c.use_flash_attention else False,
                layout=st.act_attn())
        # no name is put on `attn` for the "dots_attn" remat policy here:
        # each route names what it keeps where it makes it (nn/remat.py).
        # The flash kernel keeps its own [b, heads, s, hd] `o`, from which
        # o_proj's backward remakes its operand by a transpose; a name
        # here would keep this copy beside it, a second 67 MB a layer at
        # Mistral's widths
        attn = st.constrain(attn, st.act_attn())
        out = self.o_proj(params["o_proj"], attn.reshape(b, s, self.n_q * hd))
        return out

    # -- the serving programs' hooks (models/generation.py); how a query
    # attends the cached K/V is `KVAttention`'s ----------------------------
    def project(self, params, hn, rope, pos_ids):
        """hn [b, s, h] (normed) at positions pos_ids [b, s] ->
        (q [b, s, n_q, hd], entries (k, v) [b, s, n_kv, hd]), q and k
        rotated: the fused projection of `forward`.  `wqkv` as
        `serving_view` relaid it, a matrix [h, q heads | k heads | v
        heads], is multiplied where it lies in the layers' stack, and
        q, k, v are cut out of the product's OUTPUT."""
        b, s, _ = hn.shape
        hd, w = self.config.head_dim, params["wqkv"].astype(hn.dtype)
        if w.ndim == 2:
            qkv = hn @ w
            q, k, v = (a.reshape(b, s, -1, hd) for a in jnp.split(
                qkv, [self.n_q * hd, (self.n_q + self.n_kv) * hd], axis=-1))
        else:
            qkv = jnp.einsum("bsh,hkgd->bskgd", hn, w)
            q = qkv[..., : self.group, :].reshape(b, s, self.n_q, hd)
            k = qkv[..., self.group, :]
            v = qkv[..., self.group + 1, :]
        q, k = ops.apply_rotary_qk(q, k, *rope, pos_ids)
        return q, (k, v)

    def serving_view(self, wqkv):
        """`wqkv` [..., h, n_kv, g + 2, hd] as the matrix `project`
        multiplies in place, [..., h, (n_q + 2 n_kv) * hd]: the same
        numbers with the columns regrouped q heads | k heads | v heads,
        q head j staying with kv head j // g."""
        lead, g = wqkv.shape[:-3], self.group
        return jnp.concatenate(
            [wqkv[..., :g, :].reshape(*lead, -1),
             wqkv[..., g, :].reshape(*lead, -1),
             wqkv[..., g + 1, :].reshape(*lead, -1)], axis=-1)

    def output(self, params, attn):
        return self.o_proj(params["o_proj"], attn)


class LlamaMLP(Module):
    """SwiGLU MLP with fused gate+up (reference: llama_model.py:292)."""

    def __init__(self, config: LlamaConfig, strategy: ParallelStrategy):
        super().__init__()
        self.config, self.strategy = config, strategy
        c = config
        gu_ds = DS.make(3, {2: "tp"}) if strategy.tp > 1 else None
        gu_ds = strategy.fsdp(gu_ds, 3, 0)
        self.param("w_gate_up", (c.hidden_size, 2, c.intermediate_size),
                   init.normal(c.initializer_range), dtype=c.param_dtype,
                   ds=gu_ds)
        self.down_proj = RowParallelLinear(
            c.intermediate_size, c.hidden_size, strategy, bias=False,
            param_dtype=c.param_dtype,
            weight_init=init.normal(c.initializer_range))

    def forward(self, params, x):
        st = self.strategy
        gu = jnp.einsum("bsh,hci->bsci", x, params["w_gate_up"].astype(x.dtype))
        gu = st.constrain(gu, st.act_gate_up())
        hidden = ops.swiglu(gu[:, :, 0, :], gu[:, :, 1, :],
                            layout=st.act_inner())
        return self.down_proj(params["down_proj"], hidden)

    # -- the serving programs' form (models/generation.py) ----------------
    def serve(self, params, x):
        """`forward`, reading `w_gate_up` as `serving_view` relaid it, a
        matrix [h, gate columns | up columns] multiplied where it lies in
        the layers' stack, gate and up cut out of the product's OUTPUT;
        the training layout goes through `forward` itself."""
        w = params["w_gate_up"].astype(x.dtype)
        if w.ndim != 2:
            return self.forward(params, x)
        gate, up = jnp.split(x @ w, 2, axis=-1)
        hidden = ops.swiglu(gate, up, layout=self.strategy.act_inner())
        return self.down_proj(params["down_proj"], hidden)

    @staticmethod
    def serving_view(w_gate_up):
        """`w_gate_up` [..., h, 2, I] as [..., h, 2 * I]: the gate's
        columns, then the up projection's."""
        return w_gate_up.reshape(*w_gate_up.shape[:-2], -1)


class LlamaBlock(Module):
    """Pre-norm transformer block (reference: llama_model.py:342)."""

    def __init__(self, config: LlamaConfig, strategy: ParallelStrategy):
        super().__init__()
        self.config = config
        c = config
        self.input_norm = ParallelRMSNorm(c.hidden_size, strategy,
                                          eps=c.rms_norm_eps,
                                          param_dtype=c.param_dtype)
        self.attn = LlamaAttention(c, strategy)
        self.post_norm = ParallelRMSNorm(c.hidden_size, strategy,
                                         eps=c.rms_norm_eps,
                                         param_dtype=c.param_dtype)
        if c.num_experts > 0:
            from hetu_tpu.nn.moe import MoEConfig, MoELayer
            self.mlp = MoELayer(
                c.hidden_size, c.intermediate_size,
                MoEConfig(num_experts=c.num_experts, top_k=c.moe_top_k,
                          capacity_factor=c.moe_capacity_factor,
                          gate=c.moe_gate, dispatch=c.moe_dispatch,
                          sam_group_size=c.moe_sam_group_size),
                strategy, param_dtype=c.param_dtype,
                initializer_range=c.initializer_range)
        else:
            self.mlp = LlamaMLP(c, strategy)

    def mlp_stats(self, params, x):
        """(mlp(x), what the layer counts of itself: nothing) — the
        serving programs' form of the MLP, dense or routed."""
        if self.config.num_experts > 0:
            return self.mlp(params, x)[0], None
        return self.mlp.serve(params, x), None

    def forward(self, params, x, *, cos, sin, position_ids=None,
                segment_ids=None, rng=None, deterministic=True,
                token_ids=None):
        c = self.config
        # named phase scopes survive into the optimized HLO metadata and
        # profiler traces (utils/profiling.py phase_breakdown reads them;
        # reference: impl/profiler/profiler.h:25 per-op cost attribution)
        with jax.named_scope("attn"):
            h = self.attn(params["attn"],
                          self.input_norm(params["input_norm"], x),
                          cos=cos, sin=sin, position_ids=position_ids,
                          segment_ids=segment_ids, rng=rng,
                          deterministic=deterministic)
        if not deterministic and rng is not None:
            h = ops.dropout(h, c.hidden_dropout, jax.random.fold_in(rng, 2),
                            deterministic)
        # the residual-add + post-norm pair fuses into ONE Pallas pass
        # when routed (nn/parallel.ParallelRMSNorm.residual); the
        # fallback is exactly the seed composition `x = x + h; norm(x)`
        aux = jnp.zeros((), jnp.float32)
        if c.num_experts > 0:
            with jax.named_scope("moe"):
                normed, x = self.post_norm.residual(params["post_norm"],
                                                    x, h)
                h, aux = self.mlp(params["mlp"], normed,
                                  token_ids=token_ids)
        else:
            with jax.named_scope("mlp"):
                normed, x = self.post_norm.residual(params["post_norm"],
                                                    x, h)
                h = self.mlp(params["mlp"], normed)
        if not deterministic and rng is not None:
            h = ops.dropout(h, c.hidden_dropout, jax.random.fold_in(rng, 3),
                            deterministic)
        return x + h, aux


class LlamaDecoderStack(Module):
    """All decoder layers as ONE scanned block with stacked params
    (use_scan=True) or a python loop of per-layer subtrees (False)."""

    def __init__(self, config: LlamaConfig, strategy: ParallelStrategy):
        super().__init__()
        self.config, self.strategy = config, strategy
        self.block = LlamaBlock(config, strategy)
        self.num_layers = config.num_hidden_layers

    def param_specs(self):
        block_specs = self.block.param_specs()
        if self.config.use_scan:
            # pp shards the layer dim -> each stage holds its layer slice
            lead = "pp" if self.strategy.pp > 1 else None
            return {"layers": stack_param_specs(block_specs, self.num_layers,
                                                lead_axis=lead)}
        import copy
        return {f"layer_{i}": copy.deepcopy(block_specs)
                for i in range(self.num_layers)}

    def forward(self, params, x, *, cos, sin, position_ids=None,
                segment_ids=None, rng=None, deterministic=True,
                n_micro: Optional[int] = None, token_ids=None):
        c = self.config
        st = self.strategy
        use_drop = not deterministic and rng is not None
        if st.pp > 1:
            if not c.use_scan:
                raise ValueError("pipeline parallelism requires use_scan")
            return self._pipeline_forward(params, x, cos=cos, sin=sin,
                                          position_ids=position_ids,
                                          segment_ids=segment_ids,
                                          n_micro=n_micro,
                                          rng=rng if use_drop else None)
        layer_rngs = (jax.random.split(rng, self.num_layers)
                      if use_drop else None)

        def body(carry, xs):
            x_c, aux_c = carry
            layer_params, layer_rng = xs
            # the "layer" scope marks the scanned block body in HLO
            # metadata: per-layer attribution (obs.hlo_profile) groups
            # the whole stack under layer/... with the scan's trip
            # count multiplying through (unrolled stacks get layer_<i>)
            with jax.named_scope("layer"):
                out, aux = self.block(layer_params, x_c, cos=cos, sin=sin,
                                      position_ids=position_ids,
                                      segment_ids=segment_ids,
                                      rng=layer_rng if use_drop else None,
                                      deterministic=deterministic,
                                      token_ids=token_ids)
            return (out, aux_c + aux), None

        if c.use_scan:
            fn = body
            if c.remat:
                fn = jax.checkpoint(body, policy=_remat_policy(c.remat_policy))
            xs = (params["layers"],
                  layer_rngs if use_drop else
                  jnp.zeros((self.num_layers,), jnp.uint32))
            (x, aux), _ = lax.scan(fn, (x, jnp.zeros((), jnp.float32)), xs)
            return x, aux

        aux_total = jnp.zeros((), jnp.float32)
        for i in range(self.num_layers):
            def blk(p, y, i=i):
                # per-layer scope: decoder block i is individually
                # attributable in the optimized HLO (obs.hlo_profile
                # layer_table groups by layer_<i>/<phase>)
                with jax.named_scope(f"layer_{i}"):
                    return self.block(p, y, cos=cos, sin=sin,
                                      position_ids=position_ids,
                                      segment_ids=segment_ids,
                                      rng=layer_rngs[i] if use_drop else None,
                                      deterministic=deterministic,
                                      token_ids=token_ids)
            if c.remat:
                blk = jax.checkpoint(blk, policy=_remat_policy(c.remat_policy))
            x, aux = blk(params[f"layer_{i}"], x)
            aux_total = aux_total + aux
        return x, aux_total

    def _pipeline_forward(self, params, x, *, cos, sin, position_ids,
                          segment_ids, n_micro: Optional[int], rng=None):
        """pp > 1: run the decoder stack through the circular SPMD pipeline
        (hetu_tpu.parallel.pipeline; reference: executable_graph.cc:803/:836
        pipeline schedules).  Uneven stage_layers (the Malleus layout) run as
        padded + masked stage stacks."""
        from hetu_tpu.core.mesh import current_mesh
        from hetu_tpu.parallel.pipeline import staged_stack_forward

        st, c = self.strategy, self.config
        mesh = current_mesh()
        if mesh is None:
            raise ValueError("pipeline needs a mesh (use hetu_tpu.use_mesh)")

        if st.pp_tp_eff is not None:
            # unequal effective TP per stage in ONE program (reference:
            # distributed_states.h:158 unions over unequal stage groups)
            from hetu_tpu.parallel.hetero_pp import (
                llama_block_maker, staged_stack_forward_hetero_tp)
            if c.num_experts > 0 or st.cp > 1:
                raise NotImplementedError(
                    "pp_tp_eff composes with dense blocks, cp=1")
            if rng is not None and c.attention_dropout > 0.0:
                raise NotImplementedError(
                    "attention_dropout inside the hetero-TP pipeline "
                    "(hidden_dropout is supported)")
            return staged_stack_forward_hetero_tp(
                llama_block_maker(c, cos, sin, tp=st.tp,
                                  sequence_parallel=st.sequence_parallel),
                self.block.param_specs(), params["layers"], x,
                num_layers=self.num_layers, pp=st.pp, tp=st.tp,
                tp_eff=st.pp_tp_eff, mesh=mesh, rng=rng,
                sequence_parallel=st.sequence_parallel,
                position_ids=position_ids, segment_ids=segment_ids,
                stage_layers=c.pipeline_stage_layers, n_micro=n_micro,
                remat=c.remat, remat_policy=c.remat_policy,
                state_spec=st.pipeline_state_spec())

        def block_fn(layer_params, x_mb, pos_mb, seg_mb, rng=None):
            with jax.named_scope("layer"):
                return self.block(layer_params, x_mb, cos=cos, sin=sin,
                                  position_ids=pos_mb, segment_ids=seg_mb,
                                  rng=rng, deterministic=rng is None)

        return staged_stack_forward(
            block_fn, params["layers"], x,
            num_layers=self.num_layers, pp=st.pp, mesh=mesh,
            position_ids=position_ids, segment_ids=segment_ids,
            stage_layers=c.pipeline_stage_layers, n_micro=n_micro,
            remat=c.remat, remat_policy=c.remat_policy,
            state_spec=st.pipeline_state_spec(), rng=rng,
            # ragged (hetero-exec) stages skip untaken-branch collectives;
            # the cp ring's explicit ppermute spans all stages in one
            # instruction, and the MoE dispatch's grouped collectives
            # check-fail XLA's partitioner inside a non-uniform cond —
            # both layouts stay padded
            hetero_exec="auto" if (st.cp == 1 and c.num_experts == 0)
            else False)


class LlamaModel(Module):
    """Backbone: embed + decoder stack + final norm
    (reference: llama_model.py:385)."""

    def __init__(self, config: LlamaConfig,
                 strategy: Optional[ParallelStrategy] = None):
        super().__init__()
        strategy = strategy or ParallelStrategy()
        self.config, self.strategy = config, strategy
        c = config
        self.embed = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size, strategy, param_dtype=c.param_dtype,
            weight_init=init.normal(c.initializer_range))
        self.layers = LlamaDecoderStack(c, strategy)
        self.final_norm = ParallelRMSNorm(c.hidden_size, strategy,
                                          eps=c.rms_norm_eps,
                                          param_dtype=c.param_dtype)

    def forward(self, params, input_ids, *, position_ids=None,
                segment_ids=None, rng=None, deterministic=True,
                n_micro=None):
        c, st = self.config, self.strategy
        with jax.named_scope("embed"):
            x = self.embed(params["embed"], input_ids).astype(c.compute_dtype)
            x = st.constrain(x, st.act_hidden())
            # numerics tap (obs/numerics.py, HETU_TPU_NUMERICS): no-op —
            # and zero traced ops — unless a collector is active.  Taps
            # sit at model BOUNDARIES (embed/hidden/logits), not inside
            # the scanned layer stack, so their values can always escape
            # to the step's auxiliary stats pytree.
            from hetu_tpu.obs import numerics as _numerics
            _numerics.tap_tree("embed", x)
        cos, sin = ops.build_rope_cache(
            c.max_position_embeddings, c.head_dim, c.rope_theta,
            dtype=jnp.float32)
        x, aux = self.layers(params["layers"], x, cos=cos, sin=sin,
                             position_ids=position_ids,
                             segment_ids=segment_ids,
                             rng=rng, deterministic=deterministic,
                             n_micro=n_micro, token_ids=input_ids)
        hidden = self.final_norm(params["final_norm"], x)
        from hetu_tpu.obs import numerics as _numerics
        _numerics.tap_tree("hidden", hidden)
        return hidden, aux


class LlamaLMHeadModel(Module):
    """LM head + loss (reference: llama_model.py:446 LlamaLMHeadModel with
    VocabParallelCrossEntropy).  In GSPMD mode the CE over the tp-sharded
    vocab dim compiles to the same max/denominator collectives the reference
    implements by hand."""

    def __init__(self, config: LlamaConfig,
                 strategy: Optional[ParallelStrategy] = None):
        super().__init__()
        strategy = strategy or ParallelStrategy()
        self.config, self.strategy = config, strategy
        c = config
        self.model = LlamaModel(c, strategy)
        if not c.tie_word_embeddings:
            if strategy.tp > 1 and c.vocab_size % strategy.tp:
                raise ValueError(
                    f"vocab size {c.vocab_size} must divide by tp="
                    f"{strategy.tp}; pad the vocab (e.g. 50257 -> 50304)")
            lm_ds = strategy.fsdp(
                DS.make(2, {1: "tp"}) if strategy.tp > 1 else None, 2, 0)
            self.param("lm_head", (c.hidden_size, c.vocab_size),
                       init.normal(c.initializer_range), dtype=c.param_dtype,
                       ds=lm_ds)

    # -- what the serving programs of models/generation.py take -----------
    #: the programs carry no stats vector for this family
    STATS = ()

    def embed_tokens(self, params, ids, pos_ids):
        return self.model.embed(params["model"]["embed"], ids).astype(
            self.config.compute_dtype)

    def rope_tables(self, max_len: int):
        c = self.config
        return ops.build_rope_cache(c.max_position_embeddings, c.head_dim,
                                    c.rope_theta)

    def serving_params(self, params):
        """The parameters as the serving programs read them: the two
        fused weights of every layer as plain matrices
        (`LlamaAttention.serving_view`, `LlamaMLP.serving_view`), every
        other leaf the caller's own.  The training layouts ([.., n_kv,
        g + 2, hd] for TP over the kv heads, [.., 2, I] for TP over I)
        cannot be multiplied where they lie in a stack of layers: a
        layer's 80 MB would be copied out at every layer of every
        program (models/generation._walk_layers).  Relaid by ONE program
        on the device; only for parameters that are whole on one device
        (the view has no sharding specs: `ServingEngine` asks)."""
        block = self.model.layers.block
        views = {("attn", "wqkv"): block.attn.serving_view}
        if self.config.num_experts == 0:
            views[("mlp", "w_gate_up")] = block.mlp.serving_view
        # {"layers": the stack} or, built with use_scan=False, a layer each
        layers = params["model"]["layers"]
        relaid = jax.jit(lambda fused: {
            name: {at: views[at](w) for at, w in f.items()}
            for name, f in fused.items()})(
                {name: {at: lp[at[0]][at[1]] for at in views}
                 for name, lp in layers.items()})

        def with_views(lp, new):
            lp = dict(lp)
            for (part, leaf), w in new.items():
                lp[part] = {**lp[part], leaf: w}
            return lp
        return {**params, "model": {**params["model"], "layers": {
            name: with_views(lp, relaid[name])
            for name, lp in layers.items()}}}

    def serving_layers(self, params):
        """Runs (block, parameters, count) in the cache's layer order:
        the whole stack as one run of stacked parameters (scanned), or,
        built with use_scan=False, a run per layer (count None: the
        layer's own arrays, called)."""
        stack, lp = self.model.layers, params["model"]["layers"]
        if self.config.use_scan:
            return [(stack.block, lp["layers"], stack.num_layers)]
        return [(stack.block, lp[f"layer_{i}"], None)
                for i in range(stack.num_layers)]

    def final_hidden(self, params, x):
        return self.model.final_norm(params["model"]["final_norm"], x)

    def lm_head_weight(self, params):
        """The head as a [hidden, vocab] matrix, tied or not."""
        if self.config.tie_word_embeddings:
            return params["model"]["embed"]["weight"].T
        return params["lm_head"]

    def logits(self, params, hidden):
        c = self.config
        with jax.named_scope("lm_head"):
            if c.tie_word_embeddings:
                w = params["model"]["embed"]["weight"].astype(hidden.dtype).T
            else:
                w = params["lm_head"].astype(hidden.dtype)
            logits = hidden @ w
            logits = self.strategy.constrain(logits,
                                             self.strategy.act_logits())
            from hetu_tpu.obs import numerics as _numerics
            _numerics.tap_tree("logits", logits)
            return logits

    def forward(self, params, input_ids, labels=None, *, position_ids=None,
                segment_ids=None, rng=None, deterministic=True,
                loss_reduction: str = "mean", n_micro=None,
                include_aux_loss: bool = True, labels_shifted: bool = False):
        """include_aux_loss: fold MoE router losses into the returned loss
        (disable for evaluation so perplexity stays comparable to dense).

        labels_shifted: labels[t] is ALREADY the next-token target of
        input[t] (host-side pre-shift) — required when the seq axis was
        reordered (CP sym/stripe splits), where array adjacency no longer
        means token adjacency (reference: bucket.py:193
        generate_cp_pack_data pre-shifts before the CP split)."""
        hidden, aux = self.model(params["model"], input_ids,
                                 position_ids=position_ids,
                                 segment_ids=segment_ids,
                                 rng=rng, deterministic=deterministic,
                                 n_micro=n_micro)
        logits = self.logits(params, hidden)
        if labels is None:
            return logits
        # next-token objective: logits[t] predicts labels[t+1] (or labels[t]
        # when pre-shifted)
        if labels_shifted:
            lg, tgt = logits, labels
        else:
            lg, tgt = logits[:, :-1, :], labels[:, 1:]
        if loss_reduction not in ("mean", "sum"):
            raise ValueError(f"loss_reduction must be 'mean' or 'sum', got "
                             f"{loss_reduction!r}")
        if loss_reduction == "sum":
            # (sum, token_count) — lets grad accumulation / DP weight micro
            # batches by their true token counts instead of mean-of-means
            with jax.named_scope("loss"):
                loss = ops.softmax_cross_entropy_sparse(
                    lg, tgt, ignore_index=-100, reduction="sum")
                count = jnp.sum((tgt != -100).astype(jnp.float32))
            # aux (MoE router losses) scales with the token count so that
            # sum/count recovers mean-loss + aux
            if include_aux_loss:
                loss = loss + aux * count
            return loss, count
        with jax.named_scope("loss"):
            loss = ops.softmax_cross_entropy_sparse(
                lg, tgt, ignore_index=-100)
        return loss + aux if include_aux_loss else loss

    # ------------------------------------------------------------------
    def pipeline_train_grads(self, params, input_ids, labels, *,
                             position_ids=None, segment_ids=None,
                             n_micro: int, labels_shifted: bool = False,
                             loss_scale=1.0, skip_dead_halves="auto",
                             rng=None):
        """1F1B (PipeDream-flush) training pass: returns
        ((loss_sum, count), grads) with grads matching `params` exactly
        (reference: executable_graph.cc:836 GeneratePipedreamFlushSchedule).

        Bit-parity with the GPipe autodiff path is tested; memory holds
        O(pp) stage inputs instead of O(n_micro) — use for large n_micro.
        Embedding runs inside stage 0 and final_norm + LM head + CE inside
        the last stage (hetu_tpu.parallel.pipeline_1f1b module docs)."""
        from hetu_tpu.core.mesh import current_mesh
        from hetu_tpu.parallel.pipeline import (
            build_stage_stack, unstack_stage_grads)
        from hetu_tpu.parallel.pipeline_1f1b import pipeline_train_1f1b

        c, st = self.config, self.strategy
        if st.pp <= 1:
            raise ValueError("pipeline_train_grads requires pp > 1")
        if st.pp_tp_eff is not None and (
                c.num_experts > 0 or st.cp > 1
                or (rng is not None and c.attention_dropout > 0.0)):
            raise NotImplementedError(
                "pp_tp_eff under 1f1b composes with dense blocks, cp=1, "
                "hidden dropout only (same envelope as the GPipe hetero "
                "path)")
        if not c.use_scan:
            raise ValueError("1f1b requires use_scan")
        mesh = current_mesh()
        if mesh is None:
            raise ValueError("pipeline needs a mesh (use hetu_tpu.use_mesh)")

        stack = params["model"]["layers"]["layers"]
        sp, layer_mask, stage_layers = build_stage_stack(
            stack, c.num_hidden_layers, st.pp, c.pipeline_stage_layers)
        ep = {"embed": params["model"]["embed"],
              "final_norm": params["model"]["final_norm"]}
        if not c.tie_word_embeddings:
            ep["lm_head"] = params["lm_head"]
        count = jnp.sum(((labels if labels_shifted else labels[:, 1:])
                         != -100).astype(jnp.float32))

        cos, sin = ops.build_rope_cache(
            c.max_position_embeddings, c.head_dim, c.rope_theta,
            dtype=jnp.float32)
        block = self.model.layers.block

        use_drop = rng is not None and (c.hidden_dropout > 0.0
                                        or c.attention_dropout > 0.0)

        def stage_scan(sp_slice, x0, pos, seg, mask_row, drop_seed, offset):
            def body(carry, xs):
                lp, mj = xs if mask_row is not None else (xs, None)
                x_c, aux_c, gid = carry
                layer_rng = None
                if use_drop:
                    # (micro bits, global layer id) -> a mask the backward
                    # visit REPRODUCES exactly: the seed rides the saved
                    # token stream, the id comes from the stage offset
                    layer_rng = jax.random.fold_in(
                        jax.random.key(drop_seed), gid)
                with jax.named_scope("layer"):
                    out, aux = block(lp, x_c, cos=cos, sin=sin,
                                     position_ids=pos, segment_ids=seg,
                                     rng=layer_rng,
                                     deterministic=not use_drop)
                if mj is not None:
                    out = jnp.where(mj > 0, out, x_c)
                    aux = aux * mj
                return (out, aux_c + aux, gid + 1), None

            fn = body
            if c.remat:
                fn = jax.checkpoint(body, policy=_remat_policy(c.remat_policy))
            xs = sp_slice if mask_row is None else (sp_slice, mask_row)
            # under the shard_map 1f1b round bodies x0 (and hence any
            # data-derived aux — mask-multiplied OR MoE router losses) is
            # pp-varying, so the scan's aux carry must start varying too
            from hetu_tpu.core.vma import cast_varying, vma_of
            init_aux = cast_varying(jnp.zeros((), jnp.float32),
                                    tuple(vma_of(x0)))
            gid0 = (offset if offset is not None
                    else cast_varying(jnp.zeros((), jnp.uint32),
                                      tuple(vma_of(x0))))
            (y, aux, _), _ = lax.scan(fn, (x0, init_aux, gid0), xs)
            return y, aux

        def head_loss(ep_, y, lab):
            hidden = self.model.final_norm(ep_["final_norm"], y)
            shim = {"model": {"embed": ep_["embed"]}}
            if not c.tie_word_embeddings:
                shim["lm_head"] = ep_["lm_head"]
            logits = self.logits(shim, hidden)
            if labels_shifted:
                lg, tgt = logits, lab
            else:
                lg, tgt = logits[:, :-1, :], lab[:, 1:]
            return ops.softmax_cross_entropy_sparse(
                lg, tgt, ignore_index=-100, reduction="sum")

        def stage_fn(sp_slice, ep_, x_in, feed_b, feed_s, flg):
            emb = self.model.embed(ep_["embed"], feed_b["ids"])
            emb = st.constrain(emb.astype(c.compute_dtype), st.act_hidden())
            x0 = jnp.where(flg["is_first"] > 0, emb, x_in)
            drop = feed_s.get("dropout_rng")
            y, aux = stage_scan(sp_slice, x0,
                                feed_s.get("position_ids"),
                                feed_s.get("segment_ids"),
                                flg.get("layer_mask"),
                                drop[0, 0] if drop is not None else None,
                                flg.get("stage_offset"))
            ce = head_loss(ep_, y, feed_b["labels"]) * flg["is_last"]
            return y, ce, aux

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
        state_spec = st.pipeline_state_spec()

        custom = None
        if st.pp_tp_eff is not None:
            # per-stage hetero TP: manual-(pp, tp) switch round bodies with
            # the edges (vocab embedding, loss head) composed in auto mode
            # (parallel/hetero_pp.py hetero_tp_1f1b_rounds)
            from hetu_tpu.parallel.hetero_pp import (
                hetero_tp_1f1b_rounds, llama_block_maker)

            def embed_fn(ep_, feed_b, feed_s):
                emb = self.model.embed(ep_["embed"], feed_b["ids"])
                return st.constrain(emb.astype(c.compute_dtype),
                                    st.act_hidden())

            custom = hetero_tp_1f1b_rounds(
                llama_block_maker(c, cos, sin, tp=st.tp,
                                  sequence_parallel=st.sequence_parallel),
                block.param_specs(), embed_fn, head_loss,
                mesh=mesh, pp=st.pp, tp=st.tp, tp_eff=st.pp_tp_eff,
                stage_layers=stage_layers, remat=c.remat,
                remat_policy=c.remat_policy, compute_dtype=c.compute_dtype,
                token_keys=tuple(ride.keys()),
                sequence_parallel=st.sequence_parallel)

        ce_sum, aux_sum, d_stage, d_edge = pipeline_train_1f1b(
            stage_fn, sp, ep, input_ids, labels, ride,
            n_micro=n_micro, mesh=mesh, hidden_size=c.hidden_size,
            compute_dtype=c.compute_dtype, aux_seed=count,
            state_spec=state_spec, loss_scale=loss_scale,
            skip_dead_halves=skip_dead_halves,
            flags_extra=flags_extra or None, custom_rounds=custom)

        d_layers = unstack_stage_grads(
            d_stage, c.num_hidden_layers, st.pp, stage_layers)
        grads = {"model": {"embed": d_edge["embed"],
                           "layers": {"layers": d_layers},
                           "final_norm": d_edge["final_norm"]}}
        if not c.tie_word_embeddings:
            grads["lm_head"] = d_edge["lm_head"]
        return (ce_sum + aux_sum * count, count), grads
