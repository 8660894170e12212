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
above, or one array a shape of the model's own token shapes (a
latent-attention model: `[L, num_pages, page_size, 640]`; one whose layers
select what they attend keeps its indexer's key beside the latent,
`[L, num_pages, page_size, 128]`, under the same page ids: a page of a
layer is that page of BOTH arrays; exact pages only).

How far back each layer READS comes from the contract too.  Layers
that read everything are one kind of layer and layers that read a window
of w positions another: the pool holds one set of page arrays, one free
list and one null page a KIND, and a page id means that page in every
layer of its kind (`PagePool` says how a window kind's page lives:
reserved, written, released behind the window, a null table entry from
then on).  One kind, every layer reading everything, is the pool as it
always was.

**State beside pages.**  A layer whose cache is a fixed state a SEQUENCE
(the contract's `state_shapes`: a linear-attention layer) holds no pages.
The pool keeps one array a state kind and a state array of it,
`[layers of the kind, slots + 1, *shape]`, zeros at the start, indexed by
SLOT and owned by whoever holds the slot: never handed out, never paged,
never released.  The last row is the NULL slot, the page pool's null page
for state: the programs' warm-up and `lower_programs` write there.  The
programs carry the state arrays behind the page arrays (`tree()`,
`commit()`); a prompt's first chunk starts from zeros
(models/generation.extend_cache), so a slot needs no reset of its own.

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
    if contract.kind == "kv" and mode in ("int8", "int4"):
        # one scale a head-vector: K and V of one width
        return sum(
            kv_bytes_per_token(len(contract.layers_of(k)),
                               *contract.token_shapes_of(k)[0], mode)
            for k in range(len(contract.kinds)))
    if mode not in _ELEM_BYTES:
        raise ValueError(
            f"a {len(contract.token_shapes)}-array cache contract has exact "
            f"pages only, not {mode!r}" if contract.kind != "kv" else
            f"unknown kv mode {mode!r}; "
            f"known: {sorted(_ELEM_BYTES)} + ['int8', 'int4']")
    # every layer its own: the kinds may differ in what a token stores
    return contract.values_per_token * _ELEM_BYTES[mode]


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
    step (a pytree: quant scales are None in the exact mode).  A pool
    with several kinds of layer (exact K/V pages) holds the first kind's
    arrays as `k`, `v` and the further kinds' as `more`, (k, v) after
    (k, v), each kind's of its own shape.  A pool of `token_shapes` holds
    its arrays, one a shape, in the same places: the first as `k`, a
    second (a sparse-attention layer's index keys beside the latents) as
    `v`, any further as `more`."""
    k: jnp.ndarray
    v: Optional[jnp.ndarray] = None     # None: a one-array (latent) pool
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None
    more: tuple = ()

    def tree(self):
        if self.v is None:
            return (self.k,)
        if self.k_scale is None:
            return (self.k, self.v) + tuple(self.more)
        return (self.k, self.v, self.k_scale, self.v_scale)

    @staticmethod
    def from_tree(t) -> "PoolArrays":
        t = tuple(t)
        if len(t) > 2 and t[2].ndim == t[0].ndim:
            # page arrays of a further kind, not a plane of scales
            return PoolArrays(t[0], t[1], more=t[2:])
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


class _PageList:
    """The free list and the reference counts of ONE kind's pages (ids
    1..num_pages; 0 is the kind's null page)."""

    def __init__(self, num_pages: int):
        if num_pages < 1:
            raise ValueError("need at least one usable page")
        self.num_pages = num_pages
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

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self.refcount[pages] = 1
        self.allocs += n
        return pages

    def incref(self, pages: List[int]):
        for p in pages:
            if not (0 < p <= self.num_pages):
                raise ValueError(f"incref of invalid page id {p}")
            if self.refcount[p] < 1:
                raise ValueError(f"incref of free page {p}")
        for p in pages:     # per-element (fancy indexing drops dups)
            self.refcount[p] += 1

    def free(self, pages: List[int]):
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


class PagePool:
    """Host-side allocator + device-side page arrays.

    num_pages counts USABLE pages; one extra null page (index 0) is
    added on top, so the device arrays hold num_pages + 1 pages.

    **Kinds of layer.**  Layers that read equally far back (the cache
    contract's `windows`) are one kind; the pool holds one set of page
    arrays `[layers of the kind, pages of the kind + 1, ...]`, one free
    list and one null page a kind, and every allocator call names the
    kind (`kind=0`: the only one of a model whose layers all read
    everything).  A page id means that page in every layer of ITS kind
    and nothing in the others.  The life of a WINDOW kind's page
    (`windows[kind]` = w): reserved at admission (a slot holds at most
    `hold_pages` of them at once: the window and one page, however long
    the request), written by the page write or a decode step, RELEASED
    to the free list as soon as every position it holds lies behind
    `pos - w + 1` (serving/scheduler.py `advance`), its table entry the
    null page from then on: nothing reads there again (the decode
    kernel starts its walk at the window's first page, the composition
    over gathered pages masks)."""

    NULL_PAGE = 0

    @classmethod
    def for_contract(cls, contract, *, num_pages, page_size: int,
                     quant: str = "none", **kw) -> "PagePool":
        """The pool of a model's cache contract
        (models/cache_contract.py): what a token stores comes from the
        model, not from `num_key_value_heads` x `head_dim`, and so does
        how far back each layer reads.  `num_pages`: usable pages, one
        number for every kind or one a kind (in the order of
        `contract.kinds`)."""
        K = len(contract.kinds)
        if contract.state_kinds:
            # what a sequence stores in each state kind, and in how many
            # layers: arrays [layers, slots + 1, ...] beside the pages
            kw["state_kinds"] = tuple(
                (len(contract.layers_of(K + i)), shapes)
                for i, shapes in enumerate(contract.state_kinds))
        if contract.kind == "kv":
            n_kv, hd = contract.stored_shapes_of(0)[0]
            what = dict(num_kv_heads=n_kv, head_dim=hd)
            stored = tuple(contract.stored_shapes_of(k) for k in range(K))
            if any(s != ((n_kv, hd), (n_kv, hd)) for s in stored):
                # what a token stores differs by kind of layer, or K and
                # V differ in width: each kind's pages of its own shapes
                what.update(kind_shapes=stored)
        else:
            what = dict(token_shapes=tuple(
                tuple(s) for s in contract.stored_shapes))
        if K > 1 or contract.kinds[0] is not None:
            # (a kind's layers numbered among the layers that hold pages:
            # not a state layer, not a layer that keeps no cache)
            paged = [l for l in range(contract.num_layers)
                     if contract.state_shapes[l] is None
                     and contract.stores(l)]
            what.update(windows=contract.kinds, layers=tuple(
                tuple(paged.index(l) for l in contract.layers_of(k))
                for k in range(K)))
        return cls(num_layers=contract.page_layers, num_pages=num_pages,
                   page_size=page_size, dtype=contract.dtype, quant=quant,
                   **what, **kw)

    def __init__(self, *, num_layers: int, num_pages, page_size: int,
                 num_kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None,
                 dtype=jnp.float32, quant: str = "none",
                 device_arrays: bool = True,
                 token_shapes: Optional[Tuple[Tuple[int, ...], ...]] = None,
                 windows: Tuple[Optional[int], ...] = (None,),
                 layers: Optional[Tuple[Tuple[int, ...], ...]] = None,
                 kind_shapes=None, state_kinds=(),
                 num_slots: Optional[int] = None):
        if quant not in ("none", "int8", "int4"):
            raise ValueError(f"kv quant mode {quant!r} invalid; "
                             "choices: ('none', 'int8', 'int4')")
        if (token_shapes is None) == (num_kv_heads is None
                                      or head_dim is None):
            raise ValueError("a pool holds K and V arrays of num_kv_heads "
                             "x head_dim, or one array a shape of "
                             "token_shapes")
        #: the stored shape a token of each array of a pool that is not
        #: K and V (((640,),) for a latent; ((640,), (128,)) for a latent
        #: and an indexer's key, both under the one page table); None =
        #: the K and V arrays of `num_kv_heads` x `head_dim`
        self.token_shapes = token_shapes and tuple(
            tuple(x) for x in token_shapes)
        if token_shapes is not None and quant != "none":
            raise ValueError(
                f"kv_quant={quant!r}: int8/int4 pages are built for K/V "
                f"pools only, not for arrays of {token_shapes} a token")
        if quant == "int4" and head_dim % 2:
            raise ValueError(f"int4 pages need an even head_dim, "
                             f"got {head_dim}")
        #: per kind of layer, how far back it reads (None: everything)
        self.windows = tuple(windows)
        #: per kind, its layers among the model's (a dense cache's order)
        self.layers = (tuple(tuple(x) for x in layers) if layers is not None
                       else (tuple(range(num_layers)),))
        K = len(self.windows)
        if len(self.layers) != K or sorted(sum(self.layers, ())) \
                != list(range(num_layers)):
            raise ValueError(f"{K} kinds of layer must part the "
                             f"{num_layers} layers, got {self.layers}")
        #: some kind reads a window only, or stores shapes of its own:
        #: tables, page lists and the page write's rows are by kind of
        #: layer (serving/scheduler.py)
        self.windowed = K > 1 or self.windows[0] is not None \
            or kind_shapes is not None
        if self.windowed and (token_shapes is not None or quant != "none"):
            raise ValueError("kinds of layer are built for exact K/V "
                             "pages")
        by_kind = (tuple(int(n) for n in num_pages)
                   if isinstance(num_pages, (tuple, list))
                   else (int(num_pages),) * K)
        if len(by_kind) != K:
            raise ValueError(f"{len(by_kind)} page counts for {K} kinds "
                             f"of layer")
        #: usable pages a kind; `num_pages` is their sum
        self.pages_by_kind = by_kind
        self.lists = [_PageList(n) for n in by_kind]
        self.num_layers = num_layers
        self.num_pages = sum(by_kind)
        self.page_size = page_size
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.dtype = dtype
        self.quant = quant
        #: payload bit width of the stored pages (8 also covers fp modes)
        self.quant_bits = 4 if quant == "int4" else 8
        # `kind_shapes`: per kind, the (K, V) shapes a token is stored in,
        # where the kinds differ or K is wider than V (exact pages);
        # None: every kind's K and V are `num_kv_heads` x `head_dim`
        if kind_shapes is not None and (quant != "none"
                                        or len(kind_shapes) != K):
            raise ValueError("shapes by kind of layer are one (K, V) pair "
                             "a kind, over exact pages")
        shape = (num_layers, by_kind[0] + 1, page_size) + (
            () if token_shapes else (num_kv_heads, head_dim))
        if not device_arrays:
            # host-only pool (serving/fleet.py's discrete-event sim): the
            # allocator / refcount / page-table machinery is the real
            # thing, but no device memory is ever touched — a 10^6-page
            # pool costs one numpy array, not gigabytes of jnp.zeros
            self.arrays = None
        elif token_shapes is not None:
            self.arrays = PoolArrays.from_tree(tuple(
                jnp.zeros(shape + one, dtype) for one in self.token_shapes))
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
            shapes = kind_shapes or (((num_kv_heads, head_dim),) * 2,) * K
            self.arrays = PoolArrays.from_tree(tuple(
                jnp.zeros((len(ls), n + 1, page_size) + tuple(one), dtype)
                for ls, n, kv in zip(self.layers, by_kind, shapes)
                for one in kv))
        #: per state kind (layers, ((shape, dtype name), ...)), and the
        #: state arrays [layers, slots + 1, *shape], kind after kind (the
        #: last row the null slot); () for a pool of pages alone
        self.state_kinds = tuple(state_kinds)
        self.state: tuple = ()
        if self.state_kinds:
            if quant != "none" or num_slots is None:
                raise ValueError("state beside pages is built for exact "
                                 "pages, and is sized by the slots "
                                 "(num_slots)")
            self.null_slot = num_slots
            if device_arrays:
                self.state = tuple(
                    jnp.zeros((n, num_slots + 1) + tuple(shape),
                              jnp.dtype(dt))
                    for n, shapes in self.state_kinds
                    for shape, dt in shapes)

    def tree(self) -> tuple:
        """What the decode program carries: the page arrays, then the
        state arrays."""
        return self.arrays.tree() + self.state

    def commit(self, tree):
        """Take back what a program that was handed `tree()` (donated)
        returned."""
        n = len(tree) - len(self.state)
        self.arrays = PoolArrays.from_tree(tree[:n])
        self.state = tuple(tree[n:])

    # ---------------------------------------------------------- allocator
    def pages_for(self, tokens: int) -> int:
        return max(1, math.ceil(tokens / self.page_size))

    def hold_pages(self, tokens: int, kind: int = 0) -> int:
        """Pages of `kind` a sequence of `tokens` positions holds at
        most at once: all of them where the kind reads everything; under
        a window w the pages that the positions t - w + 1 .. t can
        touch, whatever t."""
        w = self.windows[kind]
        n = self.pages_for(tokens)
        return n if w is None else min(n, -(-(w - 1) // self.page_size) + 1)

    # the first (for most models the only) kind's books under the names
    # they always had
    @property
    def _free(self) -> List[int]:
        return self.lists[0]._free

    @property
    def refcount(self):
        return self.lists[0].refcount

    @property
    def allocs(self) -> int:
        return sum(x.allocs for x in self.lists)

    @property
    def frees(self) -> int:
        return sum(x.frees for x in self.lists)

    @property
    def free_count(self) -> int:
        return sum(len(x._free) for x in self.lists)

    @property
    def used_count(self) -> int:
        return self.num_pages - self.free_count

    @property
    def utilization(self) -> float:
        return self.used_count / self.num_pages

    def alloc(self, n: int, kind: int = 0) -> Optional[List[int]]:
        """Pop n pages off the kind's free list (refcount 1 each), or
        None (caller queues) when it cannot satisfy the reservation."""
        return self.lists[kind].alloc(n)

    def incref(self, pages: List[int], kind: int = 0):
        """Add one owner to each live page (prefix-cache sharing)."""
        self.lists[kind].incref(pages)

    def free(self, pages: List[int], kind: int = 0):
        """Release one ownership of each page (decref); a page whose
        last owner released it returns to the kind's free list."""
        self.lists[kind].free(pages)

    # ------------------------------------------------------ device ops
    # Pure functions over PoolArrays trees (the engine jits them inside
    # its step programs; `self` only contributes static shape info).

    def gather(self, arrays_tree, table):
        """Dense per-slot cache views from the pool.  table: [S, mp]
        int32 -> (ck, cv) [L, S, mp*page_size, n_kv, hd] in the compute
        dtype (int8 pages dequantize here).  The prefix cache's prime
        (a shared prefix into a prefill scratch) and the tests'
        reference; the decode program gathers nothing through it (a
        layer whose `attend_paged` takes the composition gathers its own
        pages: models/cache_contract.KVAttention._attend_gathered)."""
        if self.windowed:
            return self._gather_kinds(arrays_tree, table)
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

    def _gather_kinds(self, arrays_tree, table):
        """`gather` by kind of layer: each kind's pages by its own table
        [kinds, S, mp] -> (k, v) of [layers of the kind, S, mp *
        page_size, ...] a kind, kind after kind: a dense cache as
        `models/generation.init_cache` lays it out.  A released page's
        entry is the null page: what is read there lies behind the
        layer's window, which the attention masks."""
        tables = table if table.ndim == 3 else table[None]
        S, mp = tables.shape[1:]
        M = mp * self.page_size
        return tuple(
            pool[:, tables[kind]].reshape(
                (pool.shape[0], S, M) + pool.shape[3:])
            for kind in range(len(self.layers))
            for pool in arrays_tree[2 * kind: 2 * kind + 2])

    def write_token(self, arrays_tree, table, positions, *toks):
        """The tests' reference of `decode_step_paged`'s write (with
        `gather` and `decode_step_slots`); no program of the engine calls
        it.  Scatter one decoded token's K/V into the pool.  positions:
        [S] absolute write positions; toks = (k_toks, v_toks), each
        [L, S, n_kv, hd]; by kind of layer (k, v) of [layers of the kind,
        S, ...] a kind, kind after kind, as `decode_step_slots` hands
        them out.  Slots whose table entry is the null page (inactive)
        dump their write harmlessly into it."""
        if self.windowed:
            # each kind's layers of the token, through the kind's table
            tables = table if table.ndim == 3 else table[None]
            rows = jnp.arange(positions.shape[0])
            off = positions % self.page_size
            return tuple(
                pool.at[:, tables[i // 2][rows, positions // self.page_size],
                        off].set(t.astype(pool.dtype))
                for i, (pool, t) in enumerate(zip(arrays_tree, toks)))
        k_toks, v_toks = toks
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
        """The tests' reference of `verify_step_paged`'s write (with
        `gather` and `verify_step_slots`); no program of the engine calls
        it.  Scatter a BLOCK of tokens' K/V into the pool — the
        spec-decode verify step's write (k+1 tokens per slot per step).
        positions: [S, C] absolute write positions; k_toks/v_toks:
        [L, S, C, n_kv, hd].  Positions beyond a slot's table row
        (possible only for inactive rows riding along) redirect to the
        null page instead of clamp-corrupting the row's last page."""
        if self.windowed:
            raise NotImplementedError(
                "a block of tokens a slot (the verify step) is not built "
                "for a pool with kinds of layer")
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

    @property
    def short_rows(self) -> bool:
        """One kind of exact K/V pages whose token holds 2 to 7 rows (two
        K/V heads of 64 a lane row, four rows a token: models/lfm2_moe).
        The rows of a token are the array's second-minor dimension and
        the device lays that out in tiles of 8 sublanes: fewer rows than
        one tile and the compiler pads every token to a tile, then takes
        the scatter over pages as a copy of the whole padded pool.  8
        rows and more fill their tiles (InternLM2's 8, Phi's 20); a
        single row (Jamba's one K/V head) keeps the scatter it has been
        measured with.  tests/test_lfm2_moe.py builds every serving
        cell's pool and finds this true of that family's alone."""
        return (not self.windowed and self.token_shapes is None
                and self.quant == "none" and 1 < self.num_kv_heads < 8)

    def write_pages(self, arrays_tree, pages_row, ks, vs=None, *more):
        """Bulk-write a prefilled sequence's K/V into its pages.
        pages_row: [mp] int32 page ids (pad unused tail entries with the
        null page — their garbage lands in page 0); ks/vs:
        [L, mp*page_size, n_kv, hd]; a pool of `token_shapes` takes one
        dense cache [L, mp*page_size, *shape] an array, in their order.

        With a window kind of layer (`windows`), the scratch comes by
        kind ((k, v) of [layers of the kind, positions, ...] a kind, kind
        after kind) and `pages_row` is one entry a kind: [mp] page ids
        for a kind that reads everything; (ids [n], first, base) for a
        window kind: the ids of the n = `hold_pages(max_len, kind)`
        pages from page `first` on, the only ones whose positions the
        layer will read again (the part of the scratch before them is
        not written anywhere), and the position the kind's scratch
        begins at (0, or where the chunk program's sliding scratch
        stood at the prompt's last chunk)."""
        if self.windowed:
            return self._write_pages_kinds(arrays_tree, pages_row,
                                           (ks, vs) + more)
        if self.short_rows:
            # the scatter below would copy the whole pool, as
            # `_write_pages_kinds` says of its own kinds
            return self._write_pages_kinds(arrays_tree, (pages_row,),
                                           (ks, vs))
        a = PoolArrays.from_tree(arrays_tree)
        L = self.num_layers
        mp = pages_row.shape[0]
        if self.token_shapes is not None:
            return tuple(
                pool.at[:, pages_row].set(
                    x.reshape((L, mp, self.page_size) + one)
                    .astype(pool.dtype))
                for pool, x, one in zip(arrays_tree, (ks, vs) + more,
                                        self.token_shapes))
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

    def _write_pages_kinds(self, arrays_tree, rows, caches):
        """A page at a time, in place (`dynamic_update_slice` in a
        loop): with few KV heads a row the compiler lays a scatter of
        whole pages out with the page's tokens second-minor and copies
        the WHOLE pool into that layout and back at every write (1.5 GB
        of temporaries at 4 KV heads beside a 5 GB pool: the compile for
        the described chip, PR 34)."""
        ps = self.page_size
        out = ()
        for kind in range(len(self.layers)):
            row, first, base = (rows[kind] if self.windows[kind] is not None
                                else (rows[kind], 0, 0))
            for pool, x in zip(arrays_tree[2 * kind: 2 * kind + 2],
                               caches[2 * kind: 2 * kind + 2]):
                def page(i, pool, x=x, row=row, first=first, base=base):
                    tokens = jax.lax.dynamic_slice_in_dim(
                        x, (first + i) * ps - base, ps, axis=1)
                    return jax.lax.dynamic_update_slice(
                        pool, tokens[:, None].astype(pool.dtype),
                        (0, row[i]) + (0,) * (pool.ndim - 2))
                out += (jax.lax.fori_loop(0, row.shape[0], page, pool),)
        return out
