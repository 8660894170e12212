"""LongCat-Flash configuration: the published keys of
https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/main/config.json
under their published names, plus which part of an expert-parallel
deployment this chip holds (`first_expert`, `experts_held`)."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass
class LongCatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288            # each of a layer's two dense FFNs
    expert_ffn_hidden_size: int = 2048      # one routed expert's width
    num_layers: int = 28                    # published layers: TWO sublayers each
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512             # the router's routed outputs
    zero_expert_num: int = 256              # identity outputs behind them
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    attention_bias: bool = False
    attention_method: str = "MLA"
    initializer_range: float = 0.02
    #: the routed experts this chip holds of each layer's
    #: `n_routed_experts` (None = all of them: the whole layer)
    first_expert: int = 0
    experts_held: Optional[int] = None
    #: std of the random `e_score_correction_bias` (a buffer of the
    #: published model whose values are not in `config`)
    correction_bias_range: float = 0.002

    param_dtype: object = jnp.float32
    compute_dtype: object = jnp.bfloat16

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        for key, want in (("zero_expert_type", "identity"),
                          ("attention_method", "MLA"),
                          ("attention_bias", False)):
            if getattr(self, key) != want:
                raise ValueError(f"models/longcat_flash implements "
                                 f"{key}={want!r}, not "
                                 f"{getattr(self, key)!r}")

    # -- what `kimi_k2.MLAttention` and `DenseMLP` read -------------------
    @property
    def intermediate_size(self) -> int:
        return self.ffn_hidden_size

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """What a token stores a CACHE layer (a sublayer): the scaled
        normed latent and the shared k_rope."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_stored_dim(self) -> int:
        """`latent_dim` padded to whole 128-lane rows."""
        return -(-self.latent_dim // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def mla_q_lora_scale(self) -> float:
        """On [q_nope | q_rope] after W_qb (`mla_scale_q_lora`)."""
        return ((self.hidden_size / self.q_lora_rank) ** 0.5
                if self.mla_scale_q_lora else 1.0)

    @property
    def mla_kv_lora_scale(self) -> float:
        """On the normed latent, before W_kvb and the cache
        (`mla_scale_kv_lora`)."""
        return ((self.hidden_size / self.kv_lora_rank) ** 0.5
                if self.mla_scale_kv_lora else 1.0)
