"""DeepSeek-V3.2 configuration: the published keys of
https://huggingface.co/deepseek-ai/DeepSeek-V3.2/blob/main/config.json
(`model_type: deepseek_v32`) under their published names.  The block is
the one Kimi-K2 took from DeepSeek-V3 (`KimiK2Config`'s keys, at this
model's values, with the router's groups); what this model adds is
DeepSeek Sparse Attention: an indexer of `index_n_heads` heads of
`index_head_dim` scores every cached position for every query, and the
latent attention of that query attends the `index_topk` best."""
from __future__ import annotations

import dataclasses
from typing import Optional

from hetu_tpu.models.kimi_k2.config import KimiK2Config


@dataclasses.dataclass
class DeepseekV32Config(KimiK2Config):
    vocab_size: int = 129280
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    n_routed_experts: int = 256
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: the lightning indexer: heads, their width (the first
    #: `qk_rope_head_dim` values of a query and of the key are rotated),
    #: and how many positions a query's attention keeps
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    #: the std of the embedding's rows at init where it is not
    #: `initializer_range` (None: it is).  No key of the published config:
    #: with random weights an embedding as small as every other matrix
    #: makes the first layer's attention output the whole hidden state,
    #: and a swap at the selection's boundary then moves the logits as a
    #: trained model's does not (benchmarks/configs/deepseek-v3.2-ep32-
    #: depth5.json, `assumed`)
    embed_initializer_range: Optional[float] = None

    def __post_init__(self):
        super().__post_init__()
        if self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError(
                f"index_head_dim {self.index_head_dim} is narrower than the "
                f"{self.qk_rope_head_dim} values of it that are rotated")
