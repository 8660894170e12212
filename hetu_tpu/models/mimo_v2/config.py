"""MiMo-V2 configuration: the published keys of
https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json
(`model_type: mimo_v2_flash`) under their published names, plus which
part of an expert-parallel deployment this chip holds (`first_expert`,
`experts_held`; `router_experts` is the router's published width, which
the file of a cut deployment keeps beside the experts held)."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass
class MiMoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384          # the dense layers' SwiGLU width
    moe_intermediate_size: int = 2048       # one expert's width
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    #: KV heads of a layer that reads everything, and of a window layer
    num_key_value_heads: int = 4
    swa_num_key_value_heads: int = 8
    head_dim: int = 192                     # q and k, a head
    v_head_dim: int = 128                   # v, a head
    sliding_window: int = 128
    #: per layer, 0 = reads everything, 1 = reads `sliding_window`
    #: (None: layer 0 full, then periods of 5 window layers + 1 full
    #: with the first period one window layer short, as published)
    hybrid_layer_pattern: Optional[Tuple[int, ...]] = None
    #: per layer, 0 = dense SwiGLU, 1 = the expert layer (None: layer 0
    #: dense)
    moe_layer_freq: Optional[Tuple[int, ...]] = None
    partial_rotary_factor: float = 0.334    # of `head_dim`, rotated
    rope_theta: float = 5000000.0           # layers that read everything
    swa_rope_theta: float = 10000.0         # window layers
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    router_experts: int = 256               # the router's width
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: Optional[float] = None   # None = 1
    max_position_embeddings: int = 262144
    layernorm_epsilon: float = 1e-5
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    #: the experts this chip holds of each layer's `router_experts`
    #: (None = all of them: the whole layer)
    first_expert: int = 0
    experts_held: Optional[int] = None
    #: std of the random router bias and of the random sinks (buffers and
    #: parameters of the published model whose values are not in `config`)
    router_bias_range: float = 0.002
    sink_range: float = 8.0

    param_dtype: object = jnp.float32
    compute_dtype: object = jnp.bfloat16

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.experts_held is None:
            self.experts_held = self.router_experts
        if self.hybrid_layer_pattern is None:
            self.hybrid_layer_pattern = tuple(
                0 if i == 0 or (i >= 5 and (i - 5) % 6 == 0) else 1
                for i in range(n))
        if self.moe_layer_freq is None:
            self.moe_layer_freq = (0,) + (1,) * (n - 1)
        self.hybrid_layer_pattern = tuple(self.hybrid_layer_pattern)
        self.moe_layer_freq = tuple(self.moe_layer_freq)
        for name in ("hybrid_layer_pattern", "moe_layer_freq"):
            got = getattr(self, name)
            if len(got) != n or set(got) - {0, 1}:
                raise ValueError(f"{name} is one 0 or 1 a layer "
                                 f"({n} layers), got {got}")
        if self.tie_word_embeddings:
            raise ValueError("MiMo-V2's head is untied")

    @property
    def rotary_dim(self) -> int:
        """The leading values of every q and k head that are rotated."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def stored_key_dim(self) -> int:
        """Lanes a key head is STORED in: `head_dim` up to whole 128s
        (the device's layout pads the minor dim to that anyway)."""
        return -(-self.head_dim // 128) * 128

    def window_of(self, layer: int) -> Optional[int]:
        """How far back layer `layer` reads, its own position counted
        (None: everything)."""
        return self.sliding_window if self.hybrid_layer_pattern[layer] \
            else None

    def kv_heads_of(self, layer: int) -> int:
        return (self.swa_num_key_value_heads
                if self.hybrid_layer_pattern[layer]
                else self.num_key_value_heads)

    def sink_of(self, layer: int) -> bool:
        return (self.add_swa_attention_sink_bias
                if self.hybrid_layer_pattern[layer]
                else self.add_full_attention_sink_bias)
