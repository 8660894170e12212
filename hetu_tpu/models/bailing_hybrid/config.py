"""Ling-3.0 (`model_type: bailing_hybrid`) configuration: the published
keys of
https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json
that give the language model its shape, under their published names, plus
which part of an expert-parallel deployment this chip holds
(`first_expert`, `experts_held`)."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass
class BailingHybridConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144           # the dense layers' SwiGLU width
    moe_intermediate_size: int = 768        # one expert's width
    moe_shared_expert_intermediate_size: int = 768
    num_hidden_layers: int = 42
    first_k_dense_replace: int = 2          # leading dense layers
    #: layer l is latent attention where (l + 1) % layer_group_size == 0,
    #: else linear attention (KDA): 5 to 1
    layer_group_size: int = 6
    num_attention_heads: int = 32
    head_dim: int = 128                     # KDA's key and value width
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    num_experts: int = 512                  # the router's width
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 6000000.0
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    #: the experts this chip holds of each layer's `num_experts`
    #: (None = all of them: the whole layer)
    first_expert: int = 0
    experts_held: Optional[int] = None
    #: std of the random expert bias (a buffer of the published model
    #: whose values are not in `config`)
    correction_bias_range: float = 0.02

    param_dtype: object = jnp.float32
    compute_dtype: object = jnp.bfloat16

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.num_experts
        if not 0 < self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError("need at least one leading dense layer and "
                             "one expert layer")
        if self.num_hidden_layers < self.layer_group_size:
            raise ValueError("need a whole period of the layer pattern: "
                             "some layer has to hold pages")
        if (self.moe_shared_expert_intermediate_size
                != self.moe_intermediate_size * self.num_shared_experts):
            raise ValueError("the shared expert is built as wide as "
                             "num_shared_experts routed experts")
        if 16 * abs(self.kda_lower_bound) > 87.0:
            raise ValueError("kda_lower_bound: blocks of 16 positions "
                             "(ops/delta_rule.BLOCK) must stay inside "
                             "float32's range")

    def is_kda(self, layer: int) -> bool:
        return (layer + 1) % self.layer_group_size != 0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """What a token stores in a latent layer: c_kv and the shared
        k_rope."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_stored_dim(self) -> int:
        """`latent_dim` padded to whole 128-lane rows."""
        return -(-self.latent_dim // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def kda_channels(self) -> int:
        """Channels of the short convolution: q', k' and v' of every
        head."""
        return 3 * self.num_attention_heads * self.head_dim

    @property
    def state_shapes(self):
        """What a sequence stores in a KDA layer: the float32 state a
        head, and the convolution's last `short_conv_kernel_size` - 1
        inputs."""
        return (((self.num_attention_heads, self.head_dim, self.head_dim),
                 "float32"),
                ((self.short_conv_kernel_size - 1, self.kda_channels),
                 jnp.dtype(self.compute_dtype).name))
