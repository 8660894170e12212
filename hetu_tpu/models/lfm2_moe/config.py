"""LFM2-MoE (`model_type: lfm2_moe`) configuration: the published keys of
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json under
their published names."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

#: what a layer's operator is (`layer_types`)
CONV, FULL = "conv", "full_attention"
#: the published order: 18 gated short convolutions, 6 attention layers
_PUBLISHED = tuple(FULL if i in (2, 6, 10, 14, 18, 21) else CONV
                   for i in range(24))


@dataclasses.dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168           # the dense layers' SwiGLU width
    moe_intermediate_size: int = 1792       # one expert's width
    num_hidden_layers: int = 24
    num_dense_layers: int = 2               # leading dense layers
    layer_types: Optional[Tuple[str, ...]] = None
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    #: taps of the short convolution: a sequence keeps the last
    #: `conv_L_cache` - 1 inputs of it
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    #: not a key of the published file: only tied do the weights count to
    #: the published 8.3B (benchmarks/configs/lfm2-8b-a1b-depth12.json)
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    #: std of the random `expert_bias` (a trained buffer whose values are
    #: not in `config`), the offset common to its values (the choice of
    #: experts does not see one), and the std of the convolution's taps
    expert_bias_std: float = 0.02
    expert_bias_mean: float = 0.0
    conv_tap_std: float = 0.5
    #: std of the router's and the routed experts' matrices where it is
    #: not `initializer_range`
    expert_initializer_range: Optional[float] = None
    #: what stands beside the chosen scores' sum (`norm_topk_prob`)
    route_norm_eps: float = 1e-6

    param_dtype: object = jnp.float32
    compute_dtype: object = jnp.bfloat16

    def __post_init__(self):
        if self.layer_types is None:
            if self.num_hidden_layers != len(_PUBLISHED):
                raise ValueError("layer_types: the published order is of "
                                 f"{len(_PUBLISHED)} layers")
            self.layer_types = _PUBLISHED
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.num_hidden_layers} layers")
        bad = set(self.layer_types) - {CONV, FULL}
        if bad:
            raise ValueError(f"layer_types {sorted(bad)}: known are "
                             f"{CONV!r} and {FULL!r}")
        if FULL not in self.layer_types:
            raise ValueError("some layer has to hold pages: a slot is live "
                             "where it holds one (models/cache_contract.py)")
        if (self.conv_bias or not self.use_expert_bias
                or not self.tie_word_embeddings):
            raise NotImplementedError(
                "models/lfm2_moe builds the published model: no bias on the "
                "convolution, a bias that chooses the experts, a tied head")
        if self.conv_L_cache < 2:
            raise ValueError("a convolution of one tap keeps no state")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers beyond the layers")
        if (self.hidden_size % self.num_attention_heads
                or self.num_attention_heads % self.num_key_value_heads
                or self.num_key_value_heads % self.kv_fold):
            raise ValueError(
                "the hidden size divides by the query heads, those by the "
                f"K/V heads, and those by the {self.kv_fold} a stored row "
                "holds")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_fold(self) -> int:
        """K/V heads a STORED row holds: two where a head is narrower than
        the device's 128 lanes (models/lfm2_moe/model.py)."""
        return 2 if self.head_dim % 128 else 1

    @property
    def kv_row(self) -> Tuple[int, int]:
        """What a token stores in K (and in V) of an attention layer."""
        return (self.num_key_value_heads // self.kv_fold,
                self.kv_fold * self.head_dim)

    @property
    def conv_state(self):
        """What a sequence stores in a convolution layer, as a cache
        contract's `state_shapes` takes it: the last `conv_L_cache` - 1
        inputs of the taps, one after the other in ONE row (oldest
        first)."""
        return ((((self.conv_L_cache - 1) * self.hidden_size,),
                 jnp.dtype(self.compute_dtype).name),)
