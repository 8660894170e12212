"""Phi-4-mini-flash-reasoning (`model_type: phi4flash`) configuration: the
published keys of
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json
under their published names, plus the Mamba-1 sizes that config does not
give (the family's convention: `mamba_*`)."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

#: what a layer's mixer is (`Phi4FlashConfig.mixer_of`)
SSM, WINDOW, FULL, GMU, CROSS = "ssm", "window", "full", "gmu", "cross"


@dataclasses.dataclass
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    #: one layer in `mb_per_layer` is a Mamba layer (first half) or a
    #: gated memory unit (second half)
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    # not in the published config: Mamba-1's sizes (d_inner = expand x
    # hidden; dt_rank None = hidden / 16)
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None
    initializer_range: float = 0.02
    #: std of the four learned vectors lambda is made of
    lambda_std: float = 0.1

    param_dtype: object = jnp.float32
    compute_dtype: object = jnp.bfloat16

    def __post_init__(self):
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = -(-self.hidden_size // 16)
        L, mb = self.num_hidden_layers, self.mb_per_layer
        if mb != 2 or L % 4 or L < 8:
            raise ValueError("models/phi4_flash builds mb_per_layer 2 over "
                             "a depth that is a multiple of 4, at least 8: "
                             "(Mamba, window) pairs, a Mamba layer, a full "
                             "layer, (GMU, cross) pairs")
        if self.tie_word_embeddings is not True or self.mlp_bias \
                or self.lm_head_bias:
            raise NotImplementedError("the published model ties its head "
                                      "and has no MLP or head bias")
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2 \
                or (self.num_attention_heads // 2) % (
                    self.num_key_value_heads // 2):
            raise ValueError("differential attention pairs the query heads "
                             "and the K/V heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def memory_layer(self) -> int:
        """The Mamba layer whose output the gated memory units read."""
        return self.num_hidden_layers // 2

    @property
    def shared_kv_layer(self) -> int:
        """The one full-attention layer: the cross-decoder's K and V."""
        return self.num_hidden_layers // 2 + 1

    def mixer_of(self, layer: int) -> str:
        if layer <= self.memory_layer:
            return SSM if layer % self.mb_per_layer == 0 else WINDOW
        if layer == self.shared_kv_layer:
            return FULL
        return GMU if layer % self.mb_per_layer == 0 else CROSS

    def lambda_init(self, layer: int) -> float:
        import math
        return 0.8 - 0.6 * math.exp(-0.3 * layer)

    @property
    def kv_pairs(self) -> int:
        return self.num_key_value_heads // 2

    @property
    def kv_fold(self) -> int:
        """How many K/V pairs' rows [k_1 | k_2] lie side by side in ONE
        stored row: the fewest that leave a number of rows the device
        tiles without padding (1, 2, 4 or a multiple of 8 second-minor
        rows; 10 rows of 128 would be held, and refused by the paged
        kernel's page copies, as 16).  10 pairs: 5 a row, 2 rows of 640."""
        r = self.kv_pairs
        return next(f for f in range(1, r + 1) if r % f == 0
                    and (r // f in (1, 2, 4) or (r // f) % 8 == 0))

    @property
    def kv_row(self):
        """What a token stores in K and in V, an attention layer:
        `kv_fold` K/V pairs a row, a pair's two heads side by side."""
        return (self.kv_pairs // self.kv_fold,
                self.kv_fold * 2 * self.head_dim)

    @property
    def state_shapes(self):
        """What a sequence stores in a Mamba layer
        (`nn/mamba.state_shapes`)."""
        from hetu_tpu.nn.mamba import state_shapes
        return state_shapes(self.d_inner, self.mamba_d_state,
                            self.mamba_d_conv, self.compute_dtype)
