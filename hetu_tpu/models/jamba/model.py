"""Jamba (`model_type: jamba`; AI21-Jamba2-3B): a decoder of Mamba-1
layers with an attention layer every `attn_layer_period` layers (layer i
is attention iff i % period == offset: layers 7 and 21 of 28), every
layer followed by the same bias-free SwiGLU.  Serving only:
`ServingEngine` takes the model through the programs of
`models/generation.py`, by the hooks below; `Trainer` does not know a
state layer (ROADMAP).

One layer, pre-norm (RMSNorm, a learned gain, `rms_norm_eps`), x one
token's normed hidden state; no positional encoding of any kind (the
Mamba layers carry the order); embedding and head tied.

* Mamba-1 (`nn/mamba.MambaMixer`, the mixer this family shares with
  models/phi4_flash, with `inner_norms`: dt_r, B_t and C_t each through
  an RMSNorm before they are used).  A SEQUENCE's cache is the float32
  state [d_state, d_inner] and the convolution's last K - 1 inputs; a
  token stores nothing (the contract's `state_shapes`).
* Attention (`JambaAttention`): q = x W_q (20 heads of 128), k = x W_k,
  v = x W_v (ONE head of 128), no bias, no rotation; causal
  softmax(q k^T / sqrt(128)) v; W_o.  To the kernels plain grouped-query
  attention over one K/V head (`cache_contract.KVAttention`): a token
  stores `[1, 128]` in K and in V a layer, which the pool holds at its
  own bytes and the paged kernel reads as pages of `[page_size, 128]`
  (ops/pallas/paged_attention: "One K/V head").

The layers are RUNS of like neighbours (`JambaConfig.runs`): a run of
Mamba layers is one block whose parameters are stacked [count, ...] and
scanned, an attention layer has arrays of its own and is called: five
layer bodies a program at the published depth (7, 1, 13, 1, 6), where
two scanned periods of 14 would hold 28.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu.models.cache_contract import CacheContract, KVAttention
from hetu_tpu.models.jamba.config import FULL, SSM, JambaConfig
from hetu_tpu.models.kimi_k2.model import DenseMLP
from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.mamba import MambaMixer, state_shapes
from hetu_tpu.nn.module import Module, stack_param_specs
from hetu_tpu.nn.parallel import ParallelRMSNorm, VocabParallelEmbedding
from hetu_tpu.parallel.strategy import ParallelStrategy


class JambaAttention(KVAttention, Module):
    """Grouped-query attention without bias or positional encoding
    (module docstring); what attends is `KVAttention`."""

    def __init__(self, config: JambaConfig):
        Module.__init__(self)
        self.config = c = config
        w = init.normal(c.initializer_range)
        nq, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        self.param("w_qkv", (c.hidden_size, (nq + 2 * nkv) * hd), w,
                   dtype=c.param_dtype)
        self.param("w_o", (nq * hd, c.hidden_size), w, dtype=c.param_dtype)

    def project(self, params, hn, rope, pos_ids):
        """hn [b, s, h] (normed) -> (q [b, s, nq, hd], entries (k, v)
        [b, s, n_kv, hd])."""
        c = self.config
        nq, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        x = hn @ params["w_qkv"].astype(hn.dtype)
        lead = x.shape[:-1]
        return (x[..., :nq * hd].reshape(lead + (nq, hd)),
                (x[..., nq * hd: (nq + nkv) * hd].reshape(lead + (nkv, hd)),
                 x[..., (nq + nkv) * hd:].reshape(lead + (nkv, hd))))

    def output(self, params, attn):
        return attn @ params["w_o"].astype(attn.dtype)


class JambaBlock(Module):
    """One decoder layer; `mixer` is config.SSM or config.FULL."""

    SCOPES = {SSM: "ssm", FULL: "attn_full"}

    def __init__(self, config: JambaConfig, strategy: ParallelStrategy,
                 mixer: str):
        super().__init__()
        c = config
        self.mixer = mixer
        #: every layer reads everything (models/generation.py `_layer`)
        self.window = None
        #: the trace scope of the layer's mixer, inside `attn`
        self.attn_scope = self.SCOPES[mixer]
        norm = dict(eps=c.rms_norm_eps, param_dtype=c.param_dtype)
        self.input_norm = ParallelRMSNorm(c.hidden_size, strategy, **norm)
        self.attn = (JambaAttention(c) if mixer == FULL else MambaMixer(
            c.hidden_size, c.d_inner, c.mamba_d_state, c.mamba_d_conv,
            c.mamba_dt_rank, param_dtype=c.param_dtype,
            compute_dtype=c.compute_dtype,
            initializer_range=c.initializer_range, inner_norms=True,
            norm_eps=c.rms_norm_eps))
        self.post_norm = ParallelRMSNorm(c.hidden_size, strategy, **norm)
        self.mlp = DenseMLP(c)

    def mlp_stats(self, params, x):
        return self.mlp(params, x), None


class _Run(Module):
    """`count` neighbouring layers of one mixer: one block; a Mamba
    run's parameters STACKED [count, ...] for `_walk_layers` to scan, an
    attention layer's its own."""

    def __init__(self, config: JambaConfig, strategy: ParallelStrategy,
                 mixer: str, count: int):
        super().__init__()
        self.block = JambaBlock(config, strategy, mixer)
        # (attention layers never neighbour: the config's period rule)
        self.count = count if mixer == SSM else None

    def param_specs(self):
        specs = self.block.param_specs()
        return specs if self.count is None else stack_param_specs(
            specs, self.count)

    def run(self, params):
        return (self.block, params, self.count)

    def layers(self, params):
        """(block, one layer's parameters) in layer order."""
        if self.count is None:
            yield self.block, params
        else:
            for i in range(self.count):
                yield self.block, jax.tree.map(lambda a: a[i], params)


class JambaModel(Module):
    def __init__(self, config: JambaConfig, strategy: ParallelStrategy):
        super().__init__()
        c = config
        self.embed = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size, strategy, param_dtype=c.param_dtype,
            weight_init=init.normal(c.initializer_range))
        #: the runs' names in the parameter tree, by their first layer
        self.parts = tuple(f"layers_{first}" for _, first, _ in c.runs)
        for name, (mixer, _, count) in zip(self.parts, c.runs):
            self.add_module(name, _Run(c, strategy, mixer, count))
        self.final_norm = ParallelRMSNorm(c.hidden_size, strategy,
                                          eps=c.rms_norm_eps,
                                          param_dtype=c.param_dtype)


class JambaLMHeadModel(Module):
    #: the engine's counter of the state bytes a decode pass reads and
    #: writes (serving/engine.py), under the name Phi-4-flash gives it
    state_counter = "serve.ssm_state_bytes"
    STATS = ()

    def __init__(self, config: JambaConfig,
                 strategy: Optional[ParallelStrategy] = None):
        super().__init__()
        strategy = strategy or ParallelStrategy()
        if strategy.mesh.num_devices > 1:
            raise NotImplementedError(
                "models/jamba runs on one device: sharded mixers are not "
                "built (ROADMAP)")
        self.config, self.strategy = config, strategy
        self.model = JambaModel(config, strategy)

    # -- what the serving programs of models/generation.py take -----------
    def cache_contract(self) -> CacheContract:
        """K and V of `num_key_value_heads` x `head_dim` a token in the
        attention layers (pages); a state a sequence in the Mamba layers
        (by slot)."""
        c = self.config
        kv = (c.num_key_value_heads, c.head_dim)
        state = state_shapes(c.d_inner, c.mamba_d_state, c.mamba_d_conv,
                             c.compute_dtype)
        return CacheContract(
            c.num_hidden_layers, (kv, kv), dtype=c.compute_dtype,
            state_shapes=tuple(state if m == SSM else None
                               for m in c.mixers))

    def rope_tables(self, max_len: int):
        return None                 # no positional encoding of any kind

    def embed_tokens(self, params, ids, pos_ids):
        return self.model.embed(params["model"]["embed"], ids).astype(
            self.config.compute_dtype)

    def serving_layers(self, params):
        """The runs of `JambaConfig.runs`: Mamba runs scanned, attention
        layers called."""
        return [getattr(self.model, part).run(params["model"][part])
                for part in self.model.parts]

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
        with jax.named_scope("layer"):
            for part in self.model.parts:
                for block, lp in getattr(self.model, part).layers(
                        params["model"][part]):
                    hn = block.input_norm(lp["input_norm"], x)
                    if block.mixer == SSM:
                        out, _ = block.attn(lp["attn"], hn)
                    else:
                        q, entries = block.attn.project(lp["attn"], hn,
                                                        None, pos)
                        out = block.attn.output(
                            lp["attn"], block.attn.attend_prompt(
                                lp["attn"], q, entries))
                    x = x + out
                    x = x + block.mlp(lp["mlp"], block.post_norm(
                        lp["post_norm"], x))
        return self.logits(params, self.final_hidden(params, x))
