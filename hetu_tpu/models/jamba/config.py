"""Jamba (`model_type: jamba`) configuration: the published keys of
https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json
under their published names."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

#: what a layer's mixer is (`JambaConfig.mixer_of`)
SSM, FULL = "ssm", "full"


@dataclasses.dataclass
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    #: layer i is attention iff i % attn_layer_period == attn_layer_offset
    #: (the family's rule), else Mamba-1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    #: layer i's feed-forward part is `num_experts` experts iff i %
    #: expert_layer_period == expert_layer_offset; with `num_experts` 1
    #: (the published value) every layer's is the dense SwiGLU
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    sliding_window: Optional[int] = None
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    #: whole prompts attend by the XLA path (`KVAttention.attend_prompt`:
    #: the tests' full forward; serving never runs it)
    use_flash_attention: bool = False

    param_dtype: object = jnp.float32
    compute_dtype: object = jnp.bfloat16

    def __post_init__(self):
        if self.num_experts != 1 or self.num_experts_per_tok != 1:
            raise NotImplementedError(
                "models/jamba builds the dense feed-forward part of "
                "num_experts 1: experts beside state-space layers are not "
                "built (ROADMAP)")
        if (self.mamba_conv_bias is not True or self.mamba_proj_bias
                or self.hidden_act != "silu" or self.sliding_window
                or self.tie_word_embeddings is not True):
            raise NotImplementedError(
                "models/jamba builds the published model: a bias on the "
                "convolution and none on Mamba's projections, silu, no "
                "window, a tied head")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError("the query heads divide by the K/V heads and "
                             "the hidden size by the query heads")
        if SSM not in self.mixers or FULL not in self.mixers:
            raise ValueError(
                f"attn_layer_period {self.attn_layer_period} / offset "
                f"{self.attn_layer_offset} over {self.num_hidden_layers} "
                "layers leave no Mamba layer or no attention layer")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def mixer_of(self, layer: int) -> str:
        return FULL if layer % self.attn_layer_period \
            == self.attn_layer_offset else SSM

    @property
    def mixers(self):
        return tuple(self.mixer_of(l) for l in range(self.num_hidden_layers))

    @property
    def runs(self):
        """The layers as runs of like neighbours, (mixer, first layer,
        count): 7 Mamba, 1 attention, 13 Mamba, 1 attention, 6 Mamba at
        the published depth."""
        out = []
        for l, m in enumerate(self.mixers):
            if out and out[-1][0] == m:
                out[-1][2] += 1
            else:
                out.append([m, l, 1])
        return tuple(tuple(r) for r in out)
