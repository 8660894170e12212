"""The attention cache contract: what ONE token stores per layer, as the
model says it, and nothing else.

`PagePool`, `kv_bytes_per_token`, the engine's prefill scratch and
`serving/costs.py` size the cache from this one place.  A model of the
K/V kind (llama, gpt) stores two arrays of `n_kv x head_dim` a token a
layer; a latent-attention model stores one vector.  A model says what it
stores by a `cache_contract()` method; one without it is of the K/V kind
and its contract is read off its config.

How a query ATTENDS what is stored is the model's as well (the hooks of
models/generation.py).  For the K/V kind that is the same for every
family, and is written once, here: `KVAttention`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax


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
    #: "kv": K and V arrays of `n_kv x head_dim`, which the paged K/V
    #: kernels, the gather route, speculative decoding, the prefix cache
    #: and the quantized page modes are built for; any other name
    #: ("latent"): ONE array of the model's own shape, exact pages only
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


class KVAttention:
    """How a query attends a K/V cache: the `attend_paged` and
    `attend_dense` hooks of an attention module whose `project` makes
    entries (k, v), each [b, s, n_kv, head_dim], for queries
    [b, s, n_q, head_dim] (n_q a multiple of n_kv, q head j reading kv
    head j // group).  A family's own are `project` and `output`."""

    def attend_paged(self, params, q, pools, table, positions, base, *,
                     scales=None, layer=None, quant=None):
        """q: a block of C queries a slot at positions[s] + i, causal
        within the block; pools = (k pages, v pages) of ALL layers, each
        [L * P, page_size, n_kv, hd], of which this layer's P pages start
        at `base` and are read through `table` [S, max_pages] of page ids
        within a layer.  C = 1 is the decode step's kernel, C > 1 the
        verify step's (ops/pallas/paged_attention).  Quantized pages
        (`quant`: "int8" | "int4") bring `scales`, the planes
        [L, P, page_size, n_kv] of which `layer`'s is handed to the
        kernel, to read by `table` as it came
        (models/generation._paged_forward says why).
        -> [S, C, n_q * hd]."""
        from hetu_tpu.ops.pallas.paged_attention import (paged_attention,
                                                         paged_verify)
        S, C, nq, hd = q.shape
        if C == 1:
            kernel, scope, q = paged_attention, "pallas_paged_attention", \
                q[:, 0]
        else:
            kernel, scope = paged_verify, "pallas_paged_verify"
        with jax.named_scope(scope):
            ksl, vsl = ((s[layer] for s in scales) if scales
                        else (None, None))
            attn = kernel(q, *pools, table + base, positions,
                          softmax_scale=hd ** -0.5, k_scale=ksl, v_scale=vsl,
                          quant=quant, scale_table=table)
        return attn.reshape(S, C, nq * hd)

    def attend_dense(self, params, q, caches, start):
        """q: C queries a row at positions start[b] + i (start a scalar
        or [b]); caches = (k, v), each [b, M, n_kv, hd], holding every
        position the queries may see.  -> [b, C, n_q * hd]."""
        from hetu_tpu.models.generation import _attend_cached_chunk
        b, C, nq, hd = q.shape
        return _attend_cached_chunk(q, *caches, start, hd ** -0.5) \
            .reshape(b, C, nq * hd)

    def attend_prompt(self, params, q, entries):
        """Whole prompts attending their own entries, causally: the
        training forward's flash path.  -> [b, s, n_q * hd]."""
        from hetu_tpu import ops
        b, s, nq, hd = q.shape
        attn = ops.flash_attention(
            q, *entries, causal=True,
            use_pallas=None if self.config.use_flash_attention else False)
        return attn.reshape(b, s, nq * hd)
