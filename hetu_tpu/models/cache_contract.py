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

**How far back a layer reads** is part of the contract too: `windows`
gives, per layer, the number of positions a query sees, its own counted
(key j is seen by query t iff t - window < j <= t), or None for a layer
that reads everything.  Layers with the same window are one KIND: the
pool holds one set of page arrays a kind and hands pages out by kind
(serving/kv_pool.py), because what lies behind a window layer's window
is never read again and its pages go back to the free list while the
request lives.  A model all of whose layers read everything has one
kind, and is served as it always was.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


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
    #: per layer, how far back the layer reads (positions, the query's
    #: own counted); None for a layer that reads everything, and None
    #: for the whole tuple where every layer does
    windows: Tuple[Optional[int], ...] = None

    def __post_init__(self):
        if self.stored_shapes is None:
            object.__setattr__(self, "stored_shapes", self.token_shapes)
        if self.windows is None:
            object.__setattr__(self, "windows", (None,) * self.num_layers)
        if len(self.windows) != self.num_layers:
            raise ValueError(f"{len(self.windows)} windows for "
                             f"{self.num_layers} layers")

    @property
    def kinds(self) -> Tuple[Optional[int], ...]:
        """The distinct windows, layers that read everything first, then
        by width: one set of page arrays and one page table each."""
        return tuple(sorted(set(self.windows),
                            key=lambda w: (w is not None, w or 0)))

    def layers_of(self, kind: int) -> Tuple[int, ...]:
        """The layers of one kind, in the model's layer order."""
        w = self.kinds[kind]
        return tuple(l for l, x in enumerate(self.windows) if x == w)

    @property
    def values_per_token_layer(self) -> int:
        return sum(math.prod(s) for s in self.token_shapes)


def kv_contract(num_layers: int, num_kv_heads: int, head_dim: int,
                dtype=None, windows=None) -> CacheContract:
    shape = (int(num_kv_heads), int(head_dim))
    return CacheContract(int(num_layers), (shape, shape), dtype=dtype,
                         windows=windows)


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
    """How a query attends a K/V cache: the `attend_paged`,
    `attend_dense` and `attend_prompt` hooks of an attention module whose
    `project` makes entries (k, v), each [b, s, n_kv, head_dim], for
    queries [b, s, n_q, head_dim] (n_q a multiple of n_kv, q head j
    reading kv head j // group).  A family's own are `project` and
    `output`.  Each hook takes the layer's `window` (the contract's; None
    = the layer reads everything): ONE implementation for every family
    of the K/V kind."""

    def attend_paged(self, params, q, pools, table, positions, base, *,
                     scales=None, layer=None, quant=None, window=None):
        """q: a block of C queries a slot at positions[s] + i, causal
        within the block; pools = (k pages, v pages) of ALL layers of the
        layer's kind, each [L * P, page_size, n_kv, hd], of which this
        layer's P pages start at `base` and are read through `table`
        [S, max_pages] of page ids within a layer.  C = 1 is the decode
        step's kernel, C > 1 the verify step's
        (ops/pallas/paged_attention).  Under a `window` the walk starts
        at the page that holds position positions[s] - window + 1 (the
        table's entries before it are the null page: their pages were
        released).  Quantized pages (`quant`: "int8" | "int4") bring
        `scales`, the planes [L, P, page_size, n_kv] of which `layer`'s
        is handed to the kernel, to read by `table` as it came
        (models/generation._paged_forward says why).
        -> [S, C, n_q * hd]."""
        from hetu_tpu.ops.pallas import _note_route
        from hetu_tpu.ops.pallas.paged_attention import (paged_attention,
                                                         paged_verify)
        S, C, nq, hd = q.shape
        kw = {}
        if window is not None:
            if C > 1 or scales:
                raise NotImplementedError(
                    "a window layer has the single-token kernel over exact "
                    "pages; the verify block and quantized pages are not "
                    "built for it")
            kw["window"] = window
            _note_route("paged_attn_window", True,
                        f"decode: the kernel, from position - {window} + 1")
        if C == 1:
            kernel, scope, q = paged_attention, "pallas_paged_attention", \
                q[:, 0]
            if window is not None:
                scope += "_window"
        else:
            kernel, scope = paged_verify, "pallas_paged_verify"
        with jax.named_scope(scope):
            ksl, vsl = ((s[layer] for s in scales) if scales
                        else (None, None))
            attn = kernel(q, *pools, table + base, positions,
                          softmax_scale=hd ** -0.5, k_scale=ksl, v_scale=vsl,
                          quant=quant, scale_table=table, **kw)
        return attn.reshape(S, C, nq * hd)

    def attend_dense(self, params, q, caches, start, window=None):
        """q: C queries a row at positions start[b] + i (start a scalar
        or [b]); caches = (k, v), each [b, M, n_kv, hd], holding every
        position the queries may see.  Under a `window`, ONE row's
        queries (the chunk program) read the window + C positions that
        end with the chunk and no more; rows at positions of their own
        (the gather decode route) read by the window's mask.

        Which shapes take which attention (the route record
        `kernel_routes["chunk_attn"]` says it per traced layer): ONE
        row's chunk of C > 1 queries at one start, the chunk program of
        chunked prefill, takes the blockwise kernel
        (ops/pallas/chunk_attention: the scores stay on the chip, and
        only the key blocks the chunk can see are read) where
        `ops.pallas.resolve_route` and the kernel's gate allow: a TPU,
        head_dim % 128, C a multiple of the sublane tile, a cache length
        that divides into key blocks of a multiple of 128, and at least
        64 MB of float32 scores in the composition (heads x C x cache
        positions: under that the two tie on a v5e and the composition
        stays, as at InternLM2's chunk of 128 over 2,048 positions).
        Everything else keeps the XLA composition
        `models/generation._attend_cached_chunk`: any shape the gate
        refuses, every backend but a TPU, a single query (C = 1: the
        decode step over a dense cache), and rows at depths of their own
        (start [b > 1]: the gather decode route, the verify step), which
        no flag forces.  -> [b, C, n_q * hd]."""
        from hetu_tpu.models.generation import _attend_cached_chunk
        from hetu_tpu.ops.pallas import chunk_attention as _ca
        from hetu_tpu.ops.pallas import _note_route, resolve_route
        b, C, nq, hd = q.shape
        M, first = caches[0].shape[1], 0
        if window is not None and b == 1 and window + C < M:
            # keys start + C - (window + C) .. start + C - 1, kept inside
            # the cache at both ends
            R = window + C
            first = jnp.clip(jnp.reshape(start, ()) + C - R, 0, M - R)
            caches = tuple(lax.dynamic_slice_in_dim(c, first, R, axis=1)
                           for c in caches)
        if b == 1 and C > 1:
            kernel = resolve_route(
                "chunk_attn", _ca.check_route, q.shape, caches[0].shape,
                jnp.shape(start), window=window, dtype=caches[0].dtype)
        else:
            kernel = False
            _note_route("chunk_attn", False,
                        "a single query, or rows at depths of their own: "
                        "the composition")
        if kernel:
            with jax.named_scope("pallas_chunk_attention"):
                out = _ca.chunk_attention(q, *caches, start,
                                          softmax_scale=hd ** -0.5,
                                          window=window, first=first)
        else:
            out = _attend_cached_chunk(q, *caches, start, hd ** -0.5,
                                       window=window, first=first)
        return out.reshape(b, C, nq * hd)

    def attend_prompt(self, params, q, entries, window=None):
        """Whole prompts attending their own entries, causally: the
        training forward's flash path; under a `window` the XLA
        composition by the window's mask (ops/pallas/flash_attention has
        no window).  -> [b, s, n_q * hd]."""
        from hetu_tpu import ops
        b, s, nq, hd = q.shape
        if window is not None:
            from hetu_tpu.models.generation import _attend_cached_chunk
            from hetu_tpu.ops.pallas import _note_route
            _note_route("flash_attn_window", False,
                        "whole prompts under a window: the XLA composition "
                        "(no window in ops/pallas/flash_attention)")
            return _attend_cached_chunk(q, *entries, 0, hd ** -0.5,
                                        window=window).reshape(b, s, nq * hd)
        attn = ops.flash_attention(
            q, *entries, causal=True,
            use_pallas=None if self.config.use_flash_attention else False)
        return attn.reshape(b, s, nq * hd)
