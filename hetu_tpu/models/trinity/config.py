"""Trinity configuration: the published keys of
https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json
(`model_type: afmoe`) under their published names, plus which part of an
expert-parallel deployment this chip holds (`first_expert`,
`experts_held`; `router_experts` is the router's published width, which
the file of a cut deployment keeps beside the experts held)."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class TrinityConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144           # the dense layers' SwiGLU width
    moe_intermediate_size: int = 1024       # one expert's width
    num_hidden_layers: int = 32
    num_dense_layers: int = 2               # leading dense layers
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    #: per layer, "sliding_attention" | "full_attention" (None: the
    #: published period, `global_attn_every_n_layers` - 1 window layers
    #: and then one full layer)
    layer_types: Optional[Tuple[str, ...]] = None
    global_attn_every_n_layers: int = 4
    sliding_window: int = 2048
    router_experts: int = 128               # the router's width
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True                # embedding times sqrt(hidden)
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    #: the experts this chip holds of each layer's `router_experts`
    #: (None = all of them: the whole layer)
    first_expert: int = 0
    experts_held: Optional[int] = None
    #: std of the random `expert_bias` (a buffer of the published model
    #: whose values are not in `config`)
    expert_bias_range: float = 0.002

    #: whole prompts of a full layer through the flash kernel where its
    #: gate allows (cache_contract.KVAttention.attend_prompt)
    use_flash_attention: bool = True

    param_dtype: object = jnp.float32
    compute_dtype: object = jnp.bfloat16

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.router_experts
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            self.layer_types = tuple(
                FULL if (i + 1) % n == 0 else WINDOW
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.num_hidden_layers} layers")
        bad = set(self.layer_types) - {WINDOW, FULL}
        if bad:
            raise ValueError(f"layer_types {sorted(bad)}: known are "
                             f"{WINDOW!r} and {FULL!r}")
        if not 0 <= self.num_dense_layers < self.num_hidden_layers:
            raise ValueError("need at least one expert layer")
        if self.tie_word_embeddings:
            raise ValueError("Trinity's head is untied")

    def window_of(self, layer: int) -> Optional[int]:
        """How far back layer `layer` reads, its own position counted
        (None: everything)."""
        return (self.sliding_window
                if self.layer_types[layer] == WINDOW else None)
