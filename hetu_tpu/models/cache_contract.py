"""The attention cache contract: what ONE token stores per layer, as the
model says it, and nothing else.

`PagePool`, `kv_bytes_per_token`, the engine's prefill scratch and
`serving/costs.py` size the cache from this one place.  A model of the
K/V kind (llama, gpt) stores two arrays of `n_kv x head_dim` a token a
layer; a latent-attention model stores one vector.  A model says what it
stores by a `cache_contract()` method; one without it is of the K/V kind
and its contract is read off its config.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple


@dataclasses.dataclass(frozen=True)
class CacheContract:
    #: layers that keep a cache (the pool's leading dim)
    num_layers: int
    #: per token, per layer: the shape of each array the pool holds
    #: ((n_kv, hd), (n_kv, hd)) for K and V; ((576,),) for a latent
    token_shapes: Tuple[Tuple[int, ...], ...]
    #: the shapes as STORED, where the device's 128 lanes force padding
    #: ((640,) for a latent of 576); None = as `token_shapes`
    stored_shapes: Tuple[Tuple[int, ...], ...] = None
    dtype: object = None
    #: "kv": K and V arrays of `n_kv x head_dim`, which the K/V programs,
    #: kernels and quantized page modes are built for; any other name
    #: ("latent"): ONE array of the model's own shape, made and attended
    #: by the model's hooks (the contract programs of
    #: models/generation.py)
    kind: str = "kv"

    def __post_init__(self):
        if self.stored_shapes is None:
            object.__setattr__(self, "stored_shapes", self.token_shapes)

    @property
    def values_per_token_layer(self) -> int:
        return sum(math.prod(s) for s in self.token_shapes)


def kv_contract(num_layers: int, num_kv_heads: int, head_dim: int,
                dtype=None) -> CacheContract:
    shape = (int(num_kv_heads), int(head_dim))
    return CacheContract(int(num_layers), (shape, shape), dtype=dtype)


def has_cache_contract(model) -> bool:
    """The model brings its own contract and the hooks that make and
    attend its entries: it is served by the contract programs of
    models/generation.py.  (Whether its programs also carry a stats
    vector is another matter: `model.STATS`.)"""
    return hasattr(model, "cache_contract")


def cache_contract(model) -> CacheContract:
    """The model's own contract, else the K/V contract of its config."""
    own = getattr(model, "cache_contract", None)
    if own is not None:
        return own()
    c = model.config
    return kv_contract(
        c.num_hidden_layers,
        getattr(c, "num_key_value_heads", c.num_attention_heads),
        c.head_dim, c.compute_dtype)
