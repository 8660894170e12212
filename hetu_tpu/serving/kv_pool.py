"""Paged KV cache: a block-pool allocator with per-sequence page tables.

The serving engine's cache is not one dense [b, max_len] buffer per
sequence (today's `init_cache` shape) but a POOL of fixed-size pages

    k_pool / v_pool : [L, num_pages, page_size, n_kv, hd]

plus, per decode slot, a page table row [max_pages] of page indices that
maps a sequence position p to (table[p // page_size], p % page_size).
Sequences of different lengths share the pool; a finished sequence's
pages go back on the free list and are recycled by the next admission —
the vLLM move, TPU-shaped: every device-side shape stays static (the
table is a fixed [slots, max_pages] int32 array; short sequences pad
with the null page).

Page 0 is the reserved NULL page: it is never allocated, unoccupied
table entries point at it, and inactive slots' token writes land in it.
It is never read either — gathers beyond a sequence's length are masked
by the position mask in `models/generation._attend_cached`, so null-page
garbage cannot reach attention.

Quantized page modes (``HETU_TPU_KV_QUANT=int8|int4``): pages store
blockwise values + one f32 absmax scale per head-vector (block =
head_dim).  int8 reuses `comm/compress.py`'s collective quantization
primitives; bytes per element drop 4 -> 1 + 4/hd (~3.88x smaller at
hd=128 vs fp32).  int4 packs two values per byte through the shared
`ops/quantization.pack_nibbles` storage layout (even index = LOW
nibble, +8 offset): 4 -> 0.5 + 4/hd (~7.53x smaller at hd=128), with
both paged Pallas kernels unpacking the nibbles in-VMEM.  The exact fp
path is the default and stores pages in the model's compute dtype —
byte-identical semantics to `init_cache`.

What a token stores comes from the model's cache contract
(models/cache_contract.py, `PagePool.for_contract`): K and V arrays as
above, or ONE array of the model's own token shape (a latent-attention
model: `[L, num_pages, page_size, 640]`, exact pages only).

Host side (allocator, free list) is plain Python; device side
(gather/scatter) is pure-functional jax, jitted by the engine.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu.comm.compress import dequantize_blockwise, quantize_blockwise

#: analytic bytes per element for each page mode (int8 carries one f32
#: scale per head-vector block of `head_dim` elements)
_ELEM_BYTES = {"fp32": 4.0, "bf16": 2.0, "fp16": 2.0}


def contract_bytes_per_token(contract, mode: str = "fp32") -> float:
    """Cache bytes one token position occupies over all layers, from the
    model's cache contract (models/cache_contract.py): the values a
    token STORES (1,152 B a layer for a latent of 576 in bf16), not the
    lanes the device pads them to.  K/V contracts go through
    `kv_bytes_per_token`, which knows the quantized page modes."""
    if contract.kind == "kv":
        n_kv, hd = contract.token_shapes[0]
        return kv_bytes_per_token(contract.num_layers, n_kv, hd, mode)
    if mode not in _ELEM_BYTES:
        raise ValueError(f"a {len(contract.token_shapes)}-array cache "
                         f"contract has exact pages only, not {mode!r}")
    return (contract.num_layers * contract.values_per_token_layer
            * _ELEM_BYTES[mode])


def kv_bytes_per_token(num_layers: int, num_kv_heads: int, head_dim: int,
                       mode: str = "fp32") -> float:
    """Cache bytes one token position occupies (K and V, all layers) —
    the analytic model bench.py records (same pattern as comm/wire.py:
    provable without hardware)."""
    elems = 2.0 * num_layers * num_kv_heads * head_dim
    if mode == "int8":
        return elems * (1.0 + 4.0 / head_dim)
    if mode == "int4":
        return elems * (0.5 + 4.0 / head_dim)
    try:
        return elems * _ELEM_BYTES[mode]
    except KeyError:
        raise ValueError(f"unknown kv mode {mode!r}; "
                         f"known: {sorted(_ELEM_BYTES)} + ['int8', 'int4']")


def quantize_heads(x, bits: int = 8):
    """[..., hd] f32 -> (payload, scales f32 [...]): one absmax scale
    per head-vector (block = hd).  int8 payload is [..., hd] via the
    comm/compress blockwise primitives; ``bits=4`` packs nibbles to a
    [..., hd//2] uint8 payload via the shared `ops/quantization`
    storage layout."""
    hd = x.shape[-1]
    if bits == 4:
        from hetu_tpu.ops.quantization import quantize_int4
        q, s = quantize_int4(x, block_size=hd)
        return q.reshape(x.shape[:-1] + (hd // 2,)), s.reshape(x.shape[:-1])
    q, s = quantize_blockwise(x, block_size=hd)
    return q.reshape(x.shape), s.reshape(x.shape[:-1])


def dequantize_heads(q, s, bits: int = 8):
    """Inverse of `quantize_heads`."""
    if bits == 4:
        from hetu_tpu.ops.quantization import dequantize_int4
        hd = q.shape[-1] * 2
        shape = q.shape[:-1] + (hd,)
        return dequantize_int4(q.reshape(-1, q.shape[-1]),
                               s.reshape(-1), shape)
    return dequantize_blockwise(q.reshape(-1, q.shape[-1]),
                                s.reshape(-1)).reshape(q.shape)


def _tap_kv_snr(x32, q, s, bits: int = 8):
    """Numerics SNR tap at the quantized KV-page write site
    (obs/numerics.py, HETU_TPU_NUMERICS): the exact roundtrip error of
    the tokens just written.  Only traced when the serving engine
    installed a collector around the program build."""
    from hetu_tpu.obs import numerics as _numerics
    if _numerics.active():
        _numerics.tap_quant_error("kv_pages", x32,
                                  x32 - dequantize_heads(q, s, bits))


@dataclasses.dataclass
class PoolArrays:
    """The device-side pool state threaded through the engine's jitted
    step (a pytree: quant scales are None in the exact mode)."""
    k: jnp.ndarray
    v: Optional[jnp.ndarray] = None     # None: a one-array (latent) pool
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None

    def tree(self):
        if self.v is None:
            return (self.k,)
        if self.k_scale is None:
            return (self.k, self.v)
        return (self.k, self.v, self.k_scale, self.v_scale)

    @staticmethod
    def from_tree(t) -> "PoolArrays":
        return PoolArrays(*t)


def repage_arrays(arrays: PoolArrays, mesh) -> PoolArrays:
    """Re-place a pool's device arrays onto `mesh`, replicated — the KV
    side of a LoadAdaptiveMesh tier change (HETU_TPU_SERVE_KV_REPAGE).

    Every leaf (fp payload, or int8 payload + f32 scales) rides the same
    `switch_tree` device_put program a params hot-switch uses; values
    are untouched, only placement moves, so decode after the migration
    is byte-identical to decode without it.  donate=True: the engine is
    the pool's only owner and commits the result straight back (the old
    buffers would be dead after the next donated decode step anyway),
    so the switch never holds two live copies of the cache."""
    from jax.sharding import NamedSharding, PartitionSpec

    from hetu_tpu.parallel.switch import switch_tree
    dst = NamedSharding(mesh, PartitionSpec())
    tree = arrays.tree()
    new = switch_tree(tree, tuple(dst for _ in tree), donate=True)
    return PoolArrays.from_tree(new)


class PagePool:
    """Host-side allocator + device-side page arrays.

    num_pages counts USABLE pages; one extra null page (index 0) is
    added on top, so the device arrays hold num_pages + 1 pages."""

    NULL_PAGE = 0

    @classmethod
    def for_contract(cls, contract, *, num_pages: int, page_size: int,
                     quant: str = "none", **kw) -> "PagePool":
        """The pool of a model's cache contract
        (models/cache_contract.py): what a token stores comes from the
        model, not from `num_key_value_heads` x `head_dim`."""
        if contract.kind == "kv":
            n_kv, hd = contract.token_shapes[0]
            what = dict(num_kv_heads=n_kv, head_dim=hd)
        else:
            (stored,) = contract.stored_shapes
            what = dict(token_shape=tuple(stored))
        return cls(num_layers=contract.num_layers, num_pages=num_pages,
                   page_size=page_size, dtype=contract.dtype, quant=quant,
                   **what, **kw)

    def __init__(self, *, num_layers: int, num_pages: int, page_size: int,
                 num_kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None,
                 dtype=jnp.float32, quant: str = "none",
                 device_arrays: bool = True,
                 token_shape: Optional[Tuple[int, ...]] = None):
        if quant not in ("none", "int8", "int4"):
            raise ValueError(f"kv quant mode {quant!r} invalid; "
                             "choices: ('none', 'int8', 'int4')")
        if (token_shape is None) == (num_kv_heads is None
                                     or head_dim is None):
            raise ValueError("a pool holds K and V arrays of num_kv_heads "
                             "x head_dim, or ONE array of token_shape")
        #: a ONE-array pool's stored shape per token ((640,) for a latent);
        #: None = the K and V arrays of `num_kv_heads` x `head_dim`
        self.token_shape = token_shape
        if token_shape is not None and quant != "none":
            raise ValueError(
                f"kv_quant={quant!r}: int8/int4 pages are built for K/V "
                f"pools only, not for one array of {token_shape} a token")
        if quant == "int4" and head_dim % 2:
            raise ValueError(f"int4 pages need an even head_dim, "
                             f"got {head_dim}")
        if num_pages < 1:
            raise ValueError("need at least one usable page")
        self.num_layers = num_layers
        self.num_pages = num_pages
        self.page_size = page_size
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.dtype = dtype
        self.quant = quant
        #: payload bit width of the stored pages (8 also covers fp modes)
        self.quant_bits = 4 if quant == "int4" else 8
        shape = (num_layers, num_pages + 1, page_size) + (
            token_shape or (num_kv_heads, head_dim))
        if not device_arrays:
            # host-only pool (serving/fleet.py's discrete-event sim): the
            # allocator / refcount / page-table machinery is the real
            # thing, but no device memory is ever touched — a 10^6-page
            # pool costs one numpy array, not gigabytes of jnp.zeros
            self.arrays = None
        elif token_shape is not None:
            self.arrays = PoolArrays(k=jnp.zeros(shape, dtype))
        elif quant == "int4":
            pshape = shape[:-1] + (head_dim // 2,)
            self.arrays = PoolArrays(
                k=jnp.zeros(pshape, jnp.uint8),
                v=jnp.zeros(pshape, jnp.uint8),
                k_scale=jnp.zeros(shape[:-1], jnp.float32),
                v_scale=jnp.zeros(shape[:-1], jnp.float32))
        elif quant == "int8":
            self.arrays = PoolArrays(
                k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
                k_scale=jnp.zeros(shape[:-1], jnp.float32),
                v_scale=jnp.zeros(shape[:-1], jnp.float32))
        else:
            self.arrays = PoolArrays(k=jnp.zeros(shape, dtype),
                                     v=jnp.zeros(shape, dtype))
        # LIFO free list: recently freed pages are reused first (their
        # garbage is overwritten by the next prefill/decode write before
        # any masked read can see it)
        self._free: List[int] = list(range(num_pages, 0, -1))
        # copy-on-write reference counts (serving/prefix_cache.py): a
        # freshly allocated page has one owner; the radix prefix cache
        # and every slot sharing the page each hold one more.  A page
        # returns to the free list when its LAST owner releases it —
        # `free()` is decref, not destroy.  Without sharing every count
        # stays 0/1 and the pre-COW semantics are unchanged.
        self.refcount = np.zeros(num_pages + 1, np.int64)
        self.allocs = 0
        self.frees = 0

    # ---------------------------------------------------------- allocator
    def pages_for(self, tokens: int) -> int:
        return max(1, math.ceil(tokens / self.page_size))

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def utilization(self) -> float:
        return self.used_count / self.num_pages

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop n pages off the free list (refcount 1 each), or None
        (caller queues) when the pool cannot satisfy the reservation."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self.refcount[pages] = 1
        self.allocs += n
        return pages

    def incref(self, pages: List[int]):
        """Add one owner to each live page (prefix-cache sharing)."""
        for p in pages:
            if not (0 < p <= self.num_pages):
                raise ValueError(f"incref of invalid page id {p}")
            if self.refcount[p] < 1:
                raise ValueError(f"incref of free page {p}")
        for p in pages:     # per-element (fancy indexing drops dups)
            self.refcount[p] += 1

    def free(self, pages: List[int]):
        """Release one ownership of each page (decref); a page whose
        last owner released it returns to the free list."""
        for p in pages:
            if not (0 < p <= self.num_pages):
                raise ValueError(f"freeing invalid page id {p}")
            if self.refcount[p] < 1 or p in self._free:
                raise ValueError(f"double free of page {p}")
        for p in pages:
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                self._free.append(p)
                self.frees += 1

    # ------------------------------------------------------ device ops
    # Pure functions over PoolArrays trees (the engine jits them inside
    # its step programs; `self` only contributes static shape info).

    def gather(self, arrays_tree, table):
        """Dense per-slot cache views from the pool.  table: [S, mp]
        int32 -> (ck, cv) [L, S, mp*page_size, n_kv, hd] in the compute
        dtype (int8 pages dequantize here)."""
        a = PoolArrays.from_tree(arrays_tree)
        L = self.num_layers
        S, mp = table.shape
        M = mp * self.page_size
        def dense(pool, scale):
            g = pool[:, table]       # [L, S, mp, ps, n_kv, hd(/2)]
            if self.quant == "int4":
                from hetu_tpu.ops.quantization import unpack_nibbles
                g = unpack_nibbles(g, even_high=False).astype(jnp.int32) - 8
            g = g.reshape(L, S, M, self.num_kv_heads, self.head_dim)
            if scale is None:
                return g
            sc = scale[:, table].reshape(L, S, M, self.num_kv_heads)
            return (g.astype(jnp.float32) * sc[..., None]).astype(self.dtype)

        return (dense(a.k, a.k_scale), dense(a.v, a.v_scale))

    def write_token(self, arrays_tree, table, positions, k_toks, v_toks):
        """Scatter one decoded token's K/V into the pool.  positions:
        [S] absolute write positions; k_toks/v_toks: [L, S, n_kv, hd].
        Slots whose table entry is the null page (inactive) dump their
        write harmlessly into it."""
        a = PoolArrays.from_tree(arrays_tree)
        S = positions.shape[0]
        page = table[jnp.arange(S), positions // self.page_size]
        off = positions % self.page_size

        def put(pool, scale, toks):
            if scale is None:
                return pool.at[:, page, off].set(toks.astype(pool.dtype)), None
            x32 = toks.astype(jnp.float32)
            q, s = quantize_heads(x32, self.quant_bits)
            _tap_kv_snr(x32, q, s, self.quant_bits)
            return (pool.at[:, page, off].set(q),
                    scale.at[:, page, off].set(s))

        nk, nks = put(a.k, a.k_scale, k_toks)
        nv, nvs = put(a.v, a.v_scale, v_toks)
        return PoolArrays(nk, nv, nks, nvs).tree()

    def write_tokens(self, arrays_tree, table, positions, k_toks, v_toks):
        """Scatter a BLOCK of tokens' K/V into the pool — the
        spec-decode verify step's write (k+1 tokens per slot per step).
        positions: [S, C] absolute write positions; k_toks/v_toks:
        [L, S, C, n_kv, hd].  Positions beyond a slot's table row
        (possible only for inactive rows riding along) redirect to the
        null page instead of clamp-corrupting the row's last page."""
        a = PoolArrays.from_tree(arrays_tree)
        S, C = positions.shape
        mp = table.shape[1]
        pidx = positions // self.page_size                     # [S, C]
        valid = pidx < mp
        page = jnp.where(
            valid,
            table[jnp.arange(S)[:, None], jnp.clip(pidx, 0, mp - 1)],
            PagePool.NULL_PAGE)
        off = positions % self.page_size

        def put(pool, scale, toks):
            if scale is None:
                return pool.at[:, page, off].set(toks.astype(pool.dtype)), None
            x32 = toks.astype(jnp.float32)
            q, s = quantize_heads(x32, self.quant_bits)
            _tap_kv_snr(x32, q, s, self.quant_bits)
            return (pool.at[:, page, off].set(q),
                    scale.at[:, page, off].set(s))

        nk, nks = put(a.k, a.k_scale, k_toks)
        nv, nvs = put(a.v, a.v_scale, v_toks)
        return PoolArrays(nk, nv, nks, nvs).tree()

    def write_pages(self, arrays_tree, pages_row, ks, vs=None):
        """Bulk-write a prefilled sequence's K/V into its pages.
        pages_row: [mp] int32 page ids (pad unused tail entries with the
        null page — their garbage lands in page 0); ks/vs:
        [L, mp*page_size, n_kv, hd]; a one-array pool takes its one
        dense cache [L, mp*page_size, *token_shape] as `ks`."""
        a = PoolArrays.from_tree(arrays_tree)
        L = self.num_layers
        mp = pages_row.shape[0]
        if self.token_shape is not None:
            x = ks.reshape((L, mp, self.page_size) + self.token_shape)
            return (a.k.at[:, pages_row].set(x.astype(a.k.dtype)),)
        paged_shape = (L, mp, self.page_size, self.num_kv_heads,
                       self.head_dim)

        def put(pool, scale, x):
            x = x.reshape(paged_shape)
            if scale is None:
                return pool.at[:, pages_row].set(x.astype(pool.dtype)), None
            x32 = x.astype(jnp.float32)
            q, s = quantize_heads(x32, self.quant_bits)
            _tap_kv_snr(x32, q, s, self.quant_bits)
            return (pool.at[:, pages_row].set(q),
                    scale.at[:, pages_row].set(s))

        nk, nks = put(a.k, a.k_scale, ks)
        nv, nvs = put(a.v, a.v_scale, vs)
        return PoolArrays(nk, nv, nks, nvs).tree()
