"""Kimi-K2 configuration: the published keys of
https://huggingface.co/moonshotai/Kimi-K2.6/blob/main/config.json
(`model_type: kimi_k2`, the block DeepSeek-V3 published) under their
published names, plus which part of an expert-parallel deployment this
chip holds (`first_expert`, `experts_held`)."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass
class KimiK2Config:
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432          # the dense layers' SwiGLU width
    moe_intermediate_size: int = 2048       # one expert's width
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1          # leading dense layers
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 384             # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    #: the router's groups of neighbouring experts and how many of them a
    #: token's experts may lie in (`nn.moe.noaux_tc_gate`); 1 and 1,
    #: Kimi's: no groups
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.827
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    #: {"type": "yarn", "factor", "original_max_position_embeddings",
    #: "beta_fast", "beta_slow", "mscale", "mscale_all_dim"} or None
    rope_scaling: Optional[dict] = None
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    #: what `MLAttention.project` multiplies the low-rank query and the
    #: normed latent by (no key of the published config: the published
    #: model has neither; models/longcat_flash states both)
    mla_q_lora_scale: float = 1.0
    mla_kv_lora_scale: float = 1.0
    #: the experts this chip holds of each layer's `n_routed_experts`
    #: (None = all of them: the whole layer)
    first_expert: int = 0
    experts_held: Optional[int] = None
    #: std of the random `e_score_correction_bias` (a buffer of the
    #: published model whose values are not in `config`)
    correction_bias_range: float = 0.02

    param_dtype: object = jnp.float32
    compute_dtype: object = jnp.bfloat16

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        if not 0 < self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError("need at least one leading dense layer and "
                             "one expert layer")
        if self.rope_scaling and self.rope_scaling.get("type") != "yarn":
            raise ValueError(f"rope_scaling {self.rope_scaling!r}: only "
                             "'yarn' is implemented")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """What a token stores a layer: c_kv and the shared k_rope."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_stored_dim(self) -> int:
        """`latent_dim` padded to whole 128-lane rows
        (ops/pallas/paged_latent_attention says why)."""
        return -(-self.latent_dim // 128) * 128

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def softmax_scale(self) -> float:
        from hetu_tpu.ops.rotary import yarn_mscale
        s = self.qk_head_dim ** -0.5
        rs = self.rope_scaling
        if rs and rs.get("mscale_all_dim"):
            m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
            s = s * m * m
        return s
