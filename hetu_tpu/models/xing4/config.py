"""Xing4.0 configuration: the published keys of
https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json
under their published names.  The block is Kimi-K2's (`KimiK2Config`'s
keys, at this model's values); what this model adds is the residual
stream: `hc_mult` hidden vectors a token, mixed by manifold-constrained
hyper-connections (nn/hyper_connections)."""
from __future__ import annotations

import dataclasses

from hetu_tpu.models.kimi_k2.config import KimiK2Config


@dataclasses.dataclass
class Xing4Config(KimiK2Config):
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: the residual stream: vectors a token, Sinkhorn-Knopp iterations on
    #: the stream-to-stream matrix, the term in their denominators, and
    #: the clamp on that matrix's logits before the exponential
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
