"""LFM2-MoE (`model_type: lfm2_moe`; LFM2-8B-A1B): a decoder whose
operator is a gated SHORT CONVOLUTION in most layers and grouped-query
attention in the others (`layer_types`), a dense SwiGLU after the leading
`num_dense_layers` operators and sigmoid-routed experts with no shared
expert after the rest.  Serving only: `ServingEngine` takes the model
through the programs of `models/generation.py`, by the hooks below;
`Trainer` does not know a state layer, an expert layer or a router
bias's update rule (ROADMAP).

One layer, pre-norm (RMSNorm, a learned gain, `norm_eps`), no bias
anywhere: `h = x + Op(N1(x))`; `y = h + FFN(N2(h))`; the head is the
embedding, after one more norm.

* Gated short convolution (`ShortConv`), u one token's normed hidden
  state: `[B | C | z] = u W_in` (in that order); `a = B * z`; `c_t =
  sum_j w_j a_{t-(K-1)+j}`, depthwise and causal over K = `conv_L_cache`
  taps, the LAST tap on the current position, zeros before the sequence;
  `o = (C * c) W_out`.  No activation, no norm inside.  **A SEQUENCE's
  cache is a's last K - 1 positions and nothing else; a token stores
  nothing** (the contract's `state_shapes`): 2 x 2,048 values a layer at
  the published sizes, held as ONE row of 4,096 (the older position
  first), because a `[.., 2, 2048]` array is laid in tiles of 16 rows on
  the device and a slot's rows would lie in an eighth of what is moved.
  `state_chunk` starts from the slot's row and leaves the inputs of the
  chunk's last VALID rows; `state_step` shifts one position in.
* Attention (`Lfm2Attention`): q (32 heads), k, v (8 heads) of
  `head_dim` 64 from one matrix; q and k RMS-normalised over each head
  with a learned gain (`cache_contract.head_rms_norm`), then rotated
  (half-split over the whole head, `rope_theta`); causal softmax at
  64^-1/2, q head j reading K/V head j // 4; `W_o`.

  **To the kernels this is grouped-query attention over rows of 128.**
  The paged and the chunk kernel take heads of a multiple of 128 lanes;
  a token's K of a layer is 8 x 64 = 512 values = four whole lane rows,
  so what a token STORES is `[4, 128]`: K/V heads 2r and 2r + 1 side by
  side in row r (`config.kv_row`: the model's own 2,048 B a token a
  layer, no padded lane).  The queries of the heads that read K/V head
  2r go out as `[q, 0]` and those of 2r + 1 as `[0, q]`: their scores
  against the row are q . k of their own head, at the scale 64^-1/2
  (`softmax_scale`), and the values come back as the row `[p v_2r | p
  v_2r+1]`, of which `output` keeps the head's own half.  A query group
  is 8 heads over a row.  Half of each product's multiplications are by
  zeros: the price of lane rows the kernels take (models/phi4_flash
  serves its differential pairs so), which a decode step bound by the
  pages' bytes does not feel.
* Experts: `nn.moe.SharedRoutedExperts` with no shared expert and every
  expert held: sigmoid scores, `expert_bias` chooses and does not weigh,
  the chosen scores over (their sum + 1e-6) times
  `routed_scaling_factor`.

Scopes, inside `attn`: `short_conv` (the norm, the state's rows taken out
and written back) > `short_conv_proj` (W_in, W_out), `short_conv_mix`
(the two gates, the taps, the tail); `attn_full` for the attention
layers.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from hetu_tpu import ops
from hetu_tpu.models.cache_contract import (CacheContract, KVAttention,
                                            head_rms_norm)
from hetu_tpu.models.kimi_k2.model import DenseMLP
from hetu_tpu.models.lfm2_moe.config import CONV, FULL, Lfm2MoeConfig
from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.module import Module
from hetu_tpu.nn.moe import (MOE_STATS, SharedRoutedExperts, add_moe_stats,
                             moe_layer_stats, zero_moe_stats)
from hetu_tpu.nn.parallel import ParallelRMSNorm, VocabParallelEmbedding
from hetu_tpu.parallel.strategy import ParallelStrategy

F32 = jnp.float32


class ShortConv(Module):
    """The gated short convolution (module docstring).  Its hooks take
    the layer's whole operator: normed hidden states in, the residual's
    addend out, the sequence's state in and out."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        c = config
        self.hidden, self.taps = c.hidden_size, c.conv_L_cache
        self.state_shapes = c.conv_state
        w = init.normal(c.initializer_range)
        self.param("w_in", (c.hidden_size, 3 * c.hidden_size), w,
                   dtype=c.param_dtype)                      # B | C | z
        # tap j multiplies the input (K - 1) - j positions back
        self.param("conv_w", (self.taps, c.hidden_size),
                   init.normal(c.conv_tap_std), dtype=c.param_dtype)
        self.param("w_out", (c.hidden_size, c.hidden_size), w,
                   dtype=c.param_dtype)

    def _mix(self, params, hn, tail):
        """hn [b, s, hidden] (normed); tail [b, (K - 1) * hidden], the
        inputs before the first position -> (the gated convolution
        [b, s, hidden] before W_out, the inputs [b, K - 1 + s, hidden])."""
        h, K = self.hidden, self.taps
        b, s = hn.shape[:2]
        with jax.named_scope("short_conv_proj"):
            x = hn @ params["w_in"].astype(hn.dtype)
        with jax.named_scope("short_conv_mix"):
            a = (x[..., :h].astype(F32)
                 * x[..., 2 * h:].astype(F32)).astype(hn.dtype)
            xx = jnp.concatenate(
                [tail.reshape(b, K - 1, h).astype(hn.dtype), a], axis=1)
            w = params["conv_w"].astype(F32)
            c = sum(w[j] * xx[:, j: j + s].astype(F32) for j in range(K))
            y = (x[..., h: 2 * h].astype(F32) * c).astype(hn.dtype)
        return y, xx

    def _out(self, params, y):
        with jax.named_scope("short_conv_proj"):
            return y @ params["w_out"].astype(y.dtype)

    # -- the hooks (models/generation.py) ---------------------------------
    def state_chunk(self, params, hn, state, start, valid):
        """hn [b, C, hidden] (normed); state = (tail [b, (K - 1) *
        hidden],): the rows' own, as the last chunk left them (zeros
        where this is the first).  The first valid[b] positions are the
        sequence's; the tail is taken where they end, so that the
        chunk's padding rows move nothing.
        -> (out [b, C, hidden], state')."""
        (tail,) = state
        K = self.taps
        y, xx = self._mix(params, hn, tail)
        with jax.named_scope("short_conv_mix"):
            new = jax.vmap(lambda a, n: lax.dynamic_slice_in_dim(
                a, n, K - 1, axis=0))(xx, valid)
            new = new.reshape(tail.shape).astype(tail.dtype)
        return self._out(params, y), (new,)

    def state_step(self, params, hn, state, live):
        """One position a row: hn [b, 1, hidden]; rows where `live` [b]
        is False (idle slots) leave their state as it is.
        -> (out [b, 1, hidden], state')."""
        (tail,) = state
        y, xx = self._mix(params, hn, tail)
        with jax.named_scope("short_conv_mix"):
            new = jnp.where(live[:, None],
                            xx[:, 1:].reshape(tail.shape).astype(tail.dtype),
                            tail)
        return self._out(params, y), (new,)

    def forward(self, params, hn):
        """Whole sequences hn [b, s, hidden] from zero state -> out."""
        b, s = hn.shape[:2]
        (shape, _), = self.state_shapes
        out, _ = self.state_chunk(
            params, hn, (jnp.zeros((b,) + shape, hn.dtype),),
            jnp.zeros((b,), jnp.int32), jnp.full((b,), s, jnp.int32))
        return out


class Lfm2Attention(KVAttention, Module):
    """Grouped-query attention with RMSNorm over each head of q and k
    and a rotation, its K/V heads stored `kv_fold` a lane row (module
    docstring); what attends is `KVAttention`."""

    def __init__(self, config: Lfm2MoeConfig):
        Module.__init__(self)
        self.config = c = config
        w = init.normal(c.initializer_range)
        nq, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        self.param("w_qkv", (c.hidden_size, (nq + 2 * nkv) * hd), w,
                   dtype=c.param_dtype)
        self.param("q_norm", (hd,), init.ones, dtype=c.param_dtype)
        self.param("k_norm", (hd,), init.ones, dtype=c.param_dtype)
        self.param("w_o", (nq * hd, c.hidden_size), w, dtype=c.param_dtype)

    def softmax_scale(self, width: int) -> float:
        # the queries are head_dim wide, laid in rows of kv_fold head_dim
        return self.config.head_dim ** -0.5

    def _split(self, lead):
        """The query heads as [rows, fold, the heads of one K/V head]."""
        c = self.config
        return lead + (c.kv_row[0], c.kv_fold,
                       c.num_attention_heads // c.num_key_value_heads)

    def project(self, params, hn, rope, pos_ids):
        """hn [b, s, h] (normed) at positions pos_ids [b, s] -> (q
        [b, s, nq, fold * hd]: a head's values at its K/V head's place in
        the row, zeros beside; entries (k, v) [b, s, nkv / fold,
        fold * hd])."""
        c = self.config
        nq, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        x = hn @ params["w_qkv"].astype(hn.dtype)
        lead = x.shape[:-1]
        q = x[..., :nq * hd].reshape(lead + (nq, hd))
        k = x[..., nq * hd: (nq + nkv) * hd].reshape(lead + (nkv, hd))
        v = x[..., (nq + nkv) * hd:]
        cos, sin = rope
        q = ops.apply_rotary(head_rms_norm(q, params["q_norm"], c.norm_eps),
                             cos, sin, pos_ids)
        k = ops.apply_rotary(head_rms_norm(k, params["k_norm"], c.norm_eps),
                             cos, sin, pos_ids)
        if c.kv_fold > 1:
            q = q.reshape(self._split(lead) + (hd,))
            zero = jnp.zeros_like(q[..., 0, :, :])
            q = jnp.stack(
                [jnp.concatenate([zero] * f + [q[..., f, :, :]]
                                 + [zero] * (c.kv_fold - 1 - f), -1)
                 for f in range(c.kv_fold)], axis=-3)
        return (q.reshape(lead + (nq, c.kv_row[1])),
                (k.reshape(lead + c.kv_row), v.reshape(lead + c.kv_row)))

    def output(self, params, attn):
        """attn [b, s, nq * fold * hd], a head's row `[p v_2r | p
        v_2r+1]` -> W_o over each head's own part of it."""
        c = self.config
        hd, lead = c.head_dim, attn.shape[:-1]
        if c.kv_fold > 1:
            a = attn.reshape(self._split(lead) + (c.kv_fold, hd))
            attn = jnp.stack([a[..., f, :, f, :] for f in range(c.kv_fold)],
                             axis=-3).reshape(lead + (-1,))
        return attn @ params["w_o"].astype(attn.dtype)


class Lfm2Block(Module):
    """One decoder layer: its operator by `layer_types`, a dense FFN or
    the expert layer by `num_dense_layers`."""

    SCOPES = {CONV: "short_conv", FULL: "attn_full"}

    def __init__(self, config: Lfm2MoeConfig, strategy: ParallelStrategy, *,
                 layer: int):
        super().__init__()
        c = config
        self.mixer = c.layer_types[layer]
        #: every layer reads everything (models/generation.py `_layer`)
        self.window = None
        #: the trace scope of the layer's operator, inside `attn`
        self.attn_scope = self.SCOPES[self.mixer]
        self.moe = layer >= c.num_dense_layers
        norm = dict(eps=c.norm_eps, param_dtype=c.param_dtype)
        self.input_norm = ParallelRMSNorm(c.hidden_size, strategy, **norm)
        self.attn = ShortConv(c) if self.mixer == CONV else Lfm2Attention(c)
        self.post_norm = ParallelRMSNorm(c.hidden_size, strategy, **norm)
        if self.moe:
            self.mlp = SharedRoutedExperts(
                c.hidden_size, c.moe_intermediate_size,
                n_routed_experts=c.num_experts, experts_held=c.num_experts,
                first_expert=0, top_k=c.num_experts_per_tok,
                n_shared_experts=0, norm_topk_prob=c.norm_topk_prob,
                routed_scaling_factor=float(c.routed_scaling_factor),
                param_dtype=c.param_dtype,
                initializer_range=(c.expert_initializer_range
                                   or c.initializer_range),
                bias_range=c.expert_bias_std, bias_mean=c.expert_bias_mean,
                norm_eps=c.route_norm_eps)
        else:
            self.mlp = DenseMLP(c)

    def mlp_stats(self, params, x):
        """-> (y, MOE_STATS of this execution; zeros for a dense FFN)."""
        if self.moe:
            y, st = self.mlp(params, x)
            return y, moe_layer_stats(st)
        return self.mlp(params, x), zero_moe_stats()

    def forward(self, params, x, rope, pos_ids):
        """Whole sequences x [b, s, h] at positions 0..s-1, a
        convolution layer from zero state."""
        with jax.named_scope("attn"), jax.named_scope(self.attn_scope):
            hn = self.input_norm(params["input_norm"], x)
            if self.mixer == CONV:
                out = self.attn(params["attn"], hn)
            else:
                q, entries = self.attn.project(params["attn"], hn, rope,
                                               pos_ids)
                out = self.attn.output(
                    params["attn"], self.attn.attend_prompt(
                        params["attn"], q, entries))
            x = x + out
        with jax.named_scope("mlp"):
            y, _ = self.mlp_stats(params["mlp"],
                                  self.post_norm(params["post_norm"], x))
        return x + y


class Lfm2MoeModel(Module):
    """Every layer has arrays of its own (`layer_<i>`): a scan over
    stacked expert weights would slice each layer's out of the stack at
    every execution (models/kimi_k2)."""

    def __init__(self, config: Lfm2MoeConfig, strategy: ParallelStrategy):
        super().__init__()
        c = config
        self.embed = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size, strategy, param_dtype=c.param_dtype,
            weight_init=init.normal(c.initializer_range))
        self.blocks = [
            self.add_module(f"layer_{i}", Lfm2Block(c, strategy, layer=i))
            for i in range(c.num_hidden_layers)]
        self.final_norm = ParallelRMSNorm(c.hidden_size, strategy,
                                          eps=c.norm_eps,
                                          param_dtype=c.param_dtype)


class Lfm2MoeLMHeadModel(Module):
    #: the engine's counter of the state bytes a decode pass reads and
    #: writes (serving/engine.py)
    state_counter = "serve.conv_state_bytes"
    zero_stats = staticmethod(zero_moe_stats)
    add_stats = staticmethod(add_moe_stats)
    STATS = MOE_STATS

    def __init__(self, config: Lfm2MoeConfig,
                 strategy: Optional[ParallelStrategy] = None):
        super().__init__()
        strategy = strategy or ParallelStrategy()
        if strategy.mesh.num_devices > 1:
            raise NotImplementedError(
                "models/lfm2_moe runs on one device: experts across chips "
                "(ep > 1) and sharded layers are not built (ROADMAP)")
        self.config, self.strategy = config, strategy
        self.model = Lfm2MoeModel(config, strategy)

    # -- what the serving programs of models/generation.py take -----------
    def cache_contract(self) -> CacheContract:
        """K and V rows a token in the attention layers (pages); the
        convolution's tail a sequence in the others (by slot)."""
        c = self.config
        return CacheContract(
            c.num_hidden_layers, (c.kv_row, c.kv_row), dtype=c.compute_dtype,
            state_shapes=tuple(c.conv_state if t == CONV else None
                               for t in c.layer_types))

    def rope_tables(self, max_len: int):
        c = self.config
        return ops.build_rope_cache(max_len, c.head_dim, c.rope_theta)

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
        return params["model"]["embed"]["weight"].T

    def logits(self, params, hidden):
        with jax.named_scope("lm_head"):
            return hidden @ self.lm_head_weight(params).astype(hidden.dtype)

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
