"""The attention cache contract: what ONE token stores per layer, as the
model says it, and nothing else.

`PagePool`, `kv_bytes_per_token`, the engine's prefill scratch and
`serving/costs.py` size the cache from this one place.  A model of the
K/V kind (llama, gpt) stores two arrays of `n_kv x head_dim` a token a
layer; a latent-attention model stores one vector, or a vector and
whatever else its layers keep a token, each array of a shape of its own
(a sparse-attention layer's latent AND its indexer's key: models/
deepseek_v32), all under the one page table of the layer's kind.  A model
says what it stores by a `cache_contract()` method; one without it is of
the K/V kind and its contract is read off its config.

How a query ATTENDS what is stored is the model's as well (the hooks of
models/generation.py).  For the K/V kind that is the same for every
family, and is written once, here: `KVAttention`, whose `attend_paged`
is also the ONE place that chooses a K/V layer's decode attention (the
paged kernel, or the composition over the slot's gathered pages): the
serving engine builds one decode program and asks nothing.

**How far back a layer reads** is part of the contract too: `windows`
gives, per layer, the number of positions a query sees, its own counted
(key j is seen by query t iff t - window < j <= t), or None for a layer
that reads everything.  And so is **what a token stores in THAT layer**,
where the layers differ (`layer_token_shapes`: 4 KV heads on the layers
that read everything, 8 on the window layers; keys of 192 beside values
of 128).  Layers with the same window that store the same shapes are
one KIND: the pool holds one set of page arrays a kind, each of the
kind's own shape, and hands pages out by kind (serving/kv_pool.py),
because what lies behind a window layer's window is never read again
and its pages go back to the free list while the request lives.  A
model all of whose layers read everything and store alike has one kind,
and is served as it always was.

**How many of the positions it may read a query ATTENDS** is the
contract's as well (`selects`: per layer, the most positions a query's
attention reads of those its window lets it see, chosen by the layer's
own scoring of every one of them; None for a layer that attends all it
sees).  Nothing is released by it (a later query may choose any cached
position, so every page stays held): the engine counts from it what the
layers select from and read (`serve.decode_selectable_tokens`,
`serve.decode_selected_tokens`, `serve.prefill_selected_keys`), beside
what they may read.

**What a SEQUENCE stores** is the third part of a kind: a layer whose
cache is a fixed state a sequence, whatever its length (a linear-attention
layer's recurrent state and the last positions of its short convolution),
says so by `state_shapes` and stores nothing a token.  Such a layer holds
no pages: the pool keeps, beside the page arrays of the page kinds, one
array a state kind and a state array of it, `[layers of the kind, slots +
1, *shape]`, indexed by SLOT (serving/kv_pool.py), and the programs carry
it as they carry the pages (models/generation.py: `state_chunk`,
`state_step`).  `kinds` names the kinds that hold pages; the state kinds
follow them in `kind_of`'s numbering.

**A layer may keep NOTHING**: the third answer a layer gives (`reads`).
It names the layer whose entries it attends (a cross-attention layer over
the keys and values an earlier layer wrote: it has no `project` of
entries, no pages, no row in any table of its own, and the walk hands it
the kind, the place and so the page table of the layer it reads), or
nobody's (`NO_CACHE`: a gate on an activation an earlier layer handed on;
it attends nothing).  The pool, the scratch and every byte count are
sized from the layers that STORE; `kinds`, `kind_of`, `layers_of` and
`values_per_token` count those alone.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


#: `CacheContract.reads` of a layer that stores nothing and attends nothing
NO_CACHE = -1


@dataclasses.dataclass(frozen=True)
class CacheContract:
    #: the model's layers; those that keep a cache (`reads` None) are the
    #: pool's leading dims
    num_layers: int
    #: per token, per layer: the shape of each array the pool holds
    #: ((n_kv, hd), (n_kv, hd)) for K and V; ((576,),) for a latent
    token_shapes: Tuple[Tuple[int, ...], ...]
    #: the shapes as STORED, where the device's 128 lanes force padding
    #: ((640,) for a latent of 576); None = as `token_shapes`
    stored_shapes: Tuple[Tuple[int, ...], ...] = None
    dtype: object = None
    #: "kv": K and V arrays of `n_kv x head_dim`, which the paged K/V
    #: kernels, speculative decoding, the prefix cache and the quantized
    #: page modes are built for; any other name
    #: ("latent"): as many arrays as `token_shapes` names, each of the
    #: model's own shape, under one page table; exact pages only
    kind: str = "kv"
    #: per layer, how far back the layer reads (positions, the query's
    #: own counted); None for a layer that reads everything, and None
    #: for the whole tuple where every layer does
    windows: Tuple[Optional[int], ...] = None
    #: per layer, that layer's `token_shapes` / `stored_shapes`, where
    #: the layers differ in what a token stores; None: every layer
    #: stores `token_shapes` (held in `stored_shapes`)
    layer_token_shapes: Tuple[Tuple[Tuple[int, ...], ...], ...] = None
    layer_stored_shapes: Tuple[Tuple[Tuple[int, ...], ...], ...] = None
    #: per layer, what a SEQUENCE stores there whatever its length: one
    #: (shape, dtype name) per state array (((32, 128, 128), "float32"),
    #: ((3, 12288), "bfloat16")), or None for a layer of pages; None for
    #: the whole tuple where every layer is one.  A state layer stores
    #: nothing a token: its `layer_token_shapes` entry is ()
    state_shapes: Tuple[Optional[Tuple[Tuple[Tuple[int, ...], str], ...]],
                        ...] = None
    #: per layer, whose entries the layer attends: None = its own (it
    #: keeps a cache: pages or a state); k = layer k's (it stores NOTHING
    #: and reads what the earlier page layer k wrote, through k's kind,
    #: place and page table); NO_CACHE = nobody's (it stores nothing and
    #: attends nothing).  None for the whole tuple: every layer keeps its
    #: own
    reads: Tuple[Optional[int], ...] = None
    #: per layer, the most positions a query's attention reads of those
    #: the layer lets it see (2048: the best by the layer's own scores);
    #: None for a layer that attends everything it sees, and None for
    #: the whole tuple where every layer does
    selects: Tuple[Optional[int], ...] = None

    def __post_init__(self):
        if self.stored_shapes is None:
            object.__setattr__(self, "stored_shapes", self.token_shapes)
        if self.windows is None:
            object.__setattr__(self, "windows", (None,) * self.num_layers)
        by_layer = self.layer_token_shapes is not None
        if not by_layer:
            object.__setattr__(self, "layer_token_shapes",
                               (self.token_shapes,) * self.num_layers)
        if self.layer_stored_shapes is None:
            object.__setattr__(
                self, "layer_stored_shapes", self.layer_token_shapes
                if by_layer else (self.stored_shapes,) * self.num_layers)
        if self.state_shapes is None:
            object.__setattr__(self, "state_shapes",
                               (None,) * self.num_layers)
        if self.reads is None:
            object.__setattr__(self, "reads", (None,) * self.num_layers)
        if self.selects is None:
            object.__setattr__(self, "selects", (None,) * self.num_layers)
        for what in (self.windows, self.layer_token_shapes,
                     self.layer_stored_shapes, self.state_shapes,
                     self.reads, self.selects):
            if len(what) != self.num_layers:
                raise ValueError(f"{len(what)} entries for "
                                 f"{self.num_layers} layers: {what}")
        # a state layer stores nothing a token, a layer that keeps no
        # cache nothing at all, whatever was given
        for name in ("layer_token_shapes", "layer_stored_shapes"):
            object.__setattr__(self, name, tuple(
                s if st is None and r is None else ()
                for s, st, r in zip(getattr(self, name), self.state_shapes,
                                    self.reads)))
        for l, r in enumerate(self.reads):
            if r is None:
                continue
            if self.state_shapes[l] is not None or self.windows[l] is not None:
                raise ValueError(f"layer {l} keeps no cache (reads {r}): it "
                                 "has no state and no window of its own")
            if r != NO_CACHE and not (
                    0 <= r < l and self.reads[r] is None
                    and self.state_shapes[r] is None):
                raise ValueError(f"layer {l} reads layer {r}: that has to "
                                 "be an EARLIER layer that holds pages")
        paged = [s for shapes, st, r in zip(
            zip(self.layer_token_shapes, self.layer_stored_shapes),
            self.state_shapes, self.reads)
            if st is None and r is None for s in shapes]
        if not paged:
            raise ValueError("some storing layer has to hold pages: a slot "
                             "is live where it holds one "
                             "(models/generation.py)")
        if len({len(s) for s in paged} | {len(self.token_shapes)}) != 1:
            raise ValueError("every storing layer that holds pages stores "
                             "the same NUMBER of arrays a token (K and V; "
                             "one latent; a latent and an indexer's key): "
                             f"{len(self.token_shapes)} by `token_shapes`, "
                             f"{sorted({len(s) for s in paged})} by layer")
        if any(n is not None and (n < 1 or st is not None or r is not None)
               for n, st, r in zip(self.selects, self.state_shapes,
                                   self.reads)):
            raise ValueError("`selects` is a count of positions, of a "
                             f"layer that holds pages: {self.selects}")
        if any(st is not None and w is not None
               for st, w in zip(self.state_shapes, self.windows)):
            raise ValueError("a state layer reads no window: its state is "
                             "the whole sequence")

    def stores(self, layer: int) -> bool:
        """The layer keeps a cache of its own (pages or a state)."""
        return self.reads[layer] is None

    def _kind_key(self, layer: int):
        """Of a layer that stores, or of the layer a reading layer reads;
        None for a layer that keeps and reads nothing."""
        if not self.stores(layer):
            layer = self.reads[layer]
            if layer == NO_CACHE:
                return None
        return (self.windows[layer], self.layer_token_shapes[layer],
                self.layer_stored_shapes[layer], self.state_shapes[layer])

    @functools.cached_property
    def _kinds(self):
        """The distinct (window, token shapes, stored shapes, state
        shapes): the kinds that hold pages first (layers that read
        everything, then by width, then by shape), then the state
        kinds."""
        return tuple(sorted(
            {self._kind_key(l) for l in range(self.num_layers)
             if self.stores(l)},
            key=lambda k: (k[3] is not None, k[0] is not None, k[0] or 0)
            + k[1:3] + (k[3] or (),)))

    @property
    def kinds(self) -> Tuple[Optional[int], ...]:
        """The window of each KIND of layer that holds PAGES (a kind is a
        window, what a token stores there and what a sequence stores):
        one set of page arrays and one page table each."""
        return tuple(k[0] for k in self._kinds if k[3] is None)

    @property
    def state_kinds(self) -> Tuple[Tuple[Tuple[Tuple[int, ...], str], ...],
                                   ...]:
        """What a sequence stores in each STATE kind of layer, one
        (shape, dtype name) a state array; the kinds `kind_of` numbers
        from `len(kinds)` on.  Empty for a model all of whose layers
        hold pages."""
        return tuple(k[3] for k in self._kinds if k[3] is not None)

    def is_state(self, kind: int) -> bool:
        return kind >= len(self.kinds)

    @property
    def page_layers(self) -> int:
        """Layers that hold pages (the leading dim of a one-kind pool)."""
        return sum(st is None and r is None
                   for st, r in zip(self.state_shapes, self.reads))

    def readers_of(self, layer: int) -> Tuple[int, ...]:
        """The layers that keep nothing and attend `layer`'s entries."""
        return tuple(l for l, r in enumerate(self.reads) if r == layer)

    @property
    def borrows(self) -> bool:
        """Some layer keeps no cache of its own (`reads`)."""
        return any(r is not None for r in self.reads)

    def state_arrays_of(self, kind: int) -> slice:
        """Where a STATE kind's arrays lie among the state arrays, which
        hold each state kind's, kind after kind (a pool's `state`)."""
        before = self.state_kinds[:kind - len(self.kinds)]
        lo = sum(len(s) for s in before)
        return slice(lo, lo + len(self.state_kinds[len(before)]))

    def arrays_of(self, kind: int) -> slice:
        """Where `kind`'s arrays lie in a flat tuple that holds each page
        kind's arrays (as many as a token stores), kind after kind, and
        then the state arrays: a dense cache with the state arrays behind
        it, a pool's `tree()`."""
        n, K = len(self.token_shapes), len(self.kinds)
        if kind < K:
            return slice(kind * n, (kind + 1) * n)
        at = self.state_arrays_of(kind)
        return slice(n * K + at.start, n * K + at.stop)

    def state_bytes_per_slot(self, kind: int) -> int:
        """Bytes ONE sequence holds in all layers of a state kind."""
        return len(self.layers_of(kind)) * sum(
            math.prod(shape) * jnp.dtype(dt).itemsize
            for shape, dt in self._kinds[kind][3])

    @property
    def by_kind(self) -> bool:
        """Pages, tables and the prefill scratch are laid out by kind of
        layer: some kind reads a window only, the kinds differ in what a
        token stores, or K and V differ in shape.  (Of the kinds that
        hold pages: a state kind holds none.  The arrays of a contract
        that is not of the K/V kind are each of a shape of their own by
        nature, a latent beside an indexer's key: one kind of them is a
        pool of that many arrays under one table.)"""
        pages = [k for k in self._kinds if k[3] is None]
        return (len(pages) > 1 or pages[0][0] is not None
                or (self.kind == "kv" and len(set(pages[0][2])) > 1))

    def kind_of(self, layer: int) -> Optional[int]:
        """The kind of one layer: its own where it stores, that of the
        layer it reads where it stores nothing; None where it neither
        stores nor reads."""
        key = self._kind_key(layer)
        return None if key is None else self._kinds.index(key)

    def layers_of(self, kind: int) -> Tuple[int, ...]:
        """The layers of one kind that STORE, in the model's layer order:
        the leading dim of the kind's arrays."""
        key = self._kinds[kind]
        return tuple(l for l in range(self.num_layers)
                     if self.stores(l) and self._kind_key(l) == key)

    def place_of(self, layer: int) -> int:
        """Where a layer's entries lie among the layers of its kind: its
        own place where it stores, the place of the layer it reads where
        it does not."""
        at = layer if self.stores(layer) else self.reads[layer]
        return self.layers_of(self.kind_of(at)).index(at)

    def token_shapes_of(self, kind: int):
        """What a token stores in a layer of `kind`, as the model needs
        it ..."""
        return self._kinds[kind][1]

    def stored_shapes_of(self, kind: int):
        """... and as the pool and the scratch hold it."""
        return self._kinds[kind][2]

    @property
    def values_per_token(self) -> int:
        """Values one token stores over ALL layers, each layer its own."""
        return sum(math.prod(s) for shapes in self.layer_token_shapes
                   for s in shapes)


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


def _widen(q, width: int):
    """Queries as wide as the keys are STORED (zeros beyond the model's
    own key width: keys of 192 held in 256 lanes)."""
    if q.shape[-1] == width:
        return q
    return jnp.pad(q, ((0, 0),) * (q.ndim - 1)
                   + ((0, width - q.shape[-1]),))


def head_rms_norm(x, gain, eps: float):
    """RMSNorm over each head of q or k [.., heads, head_dim] with a
    learned gain [head_dim], statistics in float32, in x's dtype: the
    per-head norm of the families that norm q and k before they attend
    (models/trinity, models/lfm2_moe)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)
            * gain.astype(jnp.float32)).astype(x.dtype)


class KVAttention:
    """How a query attends a K/V cache: the `attend_paged`,
    `attend_dense` and `attend_prompt` hooks of an attention module whose
    `project` makes entries (k, v), k [b, s, n_kv, d_k] and v [b, s,
    n_kv, d_v], for queries [b, s, n_q, d_q] (n_q a multiple of n_kv, q
    head j reading kv head j // group).  d_q is the model's key width
    and sets the softmax scale; the keys may be STORED wider (the
    contract's `stored_shapes`: 192 in 256 lanes, zeros beyond), the
    queries are then widened with zeros here; the values' width is their
    own, and the hooks return n_q * d_v values a token.  A family's own
    are `project` and `output`, and `sink`.  Each hook takes the layer's
    `window` (the contract's; None = the layer reads everything): ONE
    implementation for every family of the K/V kind."""

    def sink(self, params, window):
        """A learned scalar a query head [n_q] that stands in the
        softmax's denominator as one more key would and adds no value
        (p_tj = exp(s_tj) / (exp(sink) + sum_j' exp(s_tj'))), or None:
        the family's to override."""
        return None

    def softmax_scale(self, width: int) -> float:
        """What the scores are multiplied by, for queries `width` wide as
        `project` hands them out: width^-1/2, the family's to override
        (queries of 64 laid in rows of 128 beside zeros keep 64^-1/2)."""
        return width ** -0.5

    def attend_paged(self, params, q, pools, table, positions, base, *,
                     scales=None, layer=None, quant=None, window=None):
        """q: a block of C queries a slot at positions[s] + i, causal
        within the block; pools = (k pages, v pages) of ALL layers of the
        layer's kind, [L * P, page_size, n_kv, d_k] and [.., d_v], of
        which this layer's P pages start at `base` and are read through
        `table` [S, max_pages] of page ids within a layer.  Quantized
        pages (`quant`: "int8" | "int4") bring `scales`, the planes
        [L, P, page_size, n_kv] of which `layer`'s is read by `table` as
        it came (models/generation._paged_forward says why).

        THE decider of a K/V layer's decode attention, for its own shapes
        and nobody else's (`kernel_routes["paged_attn"]`, or
        `["paged_verify"]` for a block, says it per traced layer, with
        the reason): the Pallas kernel that walks the page table
        (`_attend_paged_kernel`) where `ops.pallas.resolve_route` and the
        kernel's gate allow, else the XLA composition over the slot's
        gathered pages (`_attend_gathered`): every backend but a TPU,
        every shape the gate refuses, and what the kernels are not built
        for (a window or a sink over a block of queries or quantized
        pages).  -> [S, C, n_q * d_v]."""
        if self._paged_kernel_takes(params, q, pools, table, window, quant):
            return self._attend_paged_kernel(
                params, q, pools, table, positions, base, scales=scales,
                layer=layer, quant=quant, window=window)
        return self._attend_gathered(params, q, pools, table, positions,
                                     base, window, scales=scales,
                                     layer=layer, quant=quant)

    def _paged_kernel_takes(self, params, q, pools, table, window,
                            quant=None) -> bool:
        """The route of this layer's decode attention, by the one routing
        rule (`ops.pallas.resolve_route`: forced flags win) over the
        kernel's own gate for this layer's shapes: `paged_attention`'s
        for one query a slot, `paged_verify`'s for a block (which has no
        window, no sink and no keys wider than the values: the gate is
        told, and refuses)."""
        from hetu_tpu.ops.pallas import paged_attention as _pa
        from hetu_tpu.ops.pallas import resolve_route
        S, C, nq, hd = q.shape
        k_shape, v_shape = pools[0].shape, pools[1].shape
        sink = self.sink(params, window) is not None
        # (quantized pages store K and V alike, int4 at half the width;
        # exact keys may be STORED wider than the queries come)
        width = hd if quant else k_shape[-1]
        kw = dict(quant=quant or "none", pool_dtype=pools[0].dtype)
        if C == 1:
            return resolve_route(
                "paged_attn", _pa.check_shapes, (S, nq, width), k_shape,
                table.shape, (S,), window=window, v_shape=v_shape,
                sink=sink, **kw)

        def check(*shapes, **kw):
            if window is not None or sink or k_shape != v_shape:
                raise ValueError(
                    "a window, a sink and keys wider than the values have "
                    "the single-token kernel; the verify block is not "
                    "built for them")
            return _pa.check_shapes_verify(*shapes, **kw)
        return resolve_route("paged_verify", check, (S, C, nq, width),
                             k_shape, table.shape, (S,), **kw)

    def _attend_paged_kernel(self, params, q, pools, table, positions, base,
                             *, scales=None, layer=None, quant=None,
                             window=None):
        """`attend_paged` by the kernel (ops/pallas/paged_attention): C =
        1 is the decode step's, C > 1 the verify step's.  Under a
        `window` the walk starts at the page that holds position
        positions[s] - window + 1 (the table's entries before it are the
        null page: their pages were released)."""
        from hetu_tpu.ops.pallas import _note_route
        from hetu_tpu.ops.pallas.paged_attention import (paged_attention,
                                                         paged_verify)
        S, C, nq, hd = q.shape
        sink, kw = self.sink(params, window), {}
        # (quantized pages store K and V alike, int4 at half the width)
        d_k, d_v = ((hd, hd) if scales else
                    (pools[0].shape[-1], pools[1].shape[-1]))
        if window is not None:
            kw["window"] = window
            _note_route("paged_attn_window", True,
                        f"decode: the kernel, from position - {window} + 1")
        if sink is not None:
            kw["sink"] = sink
        if sink is not None or d_k != d_v:
            _note_route(
                "paged_attn_shapes", True,
                f"keys {hd} (held in {d_k}) against values {d_v}, groups of "
                f"{nq // pools[0].shape[-2]}, "
                f"{'a sink a head' if sink is not None else 'no sink'}")
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
            attn = kernel(_widen(q, d_k), *pools, table + base, positions,
                          softmax_scale=self.softmax_scale(hd), k_scale=ksl,
                          v_scale=vsl, quant=quant, scale_table=table, **kw)
        return attn.reshape(S, C, nq * d_v)

    def _attend_gathered(self, params, q, pools, table, positions, base,
                         window, rows=tuple, *, scales=None, layer=None,
                         quant=None):
        """`attend_paged` without the kernel: each slot's pages of this
        layer gathered through `table` into a dense [S, max_pages *
        page_size, ...] view (read as `rows` makes of the stored rows: as
        they are; quantized pages dequantized by the layer's scale plane,
        the arithmetic of `serving/kv_pool.PagePool.gather`), attended by
        the XLA composition under the causal (and the window's) mask,
        a block of C > 1 queries at positions of their own like one; a
        released or unheld page's entry is the null page, whose positions
        the masks never let through."""
        from hetu_tpu.models.generation import _attend_cached_chunk
        S, C, nq, hd = q.shape

        def dense(pool, scale):
            g = pool[table + base]              # [S, mp, ps, n_kv, hd(/2)]
            if quant == "int4":
                from hetu_tpu.ops.quantization import unpack_nibbles
                g = unpack_nibbles(g, even_high=False).astype(jnp.int32) - 8
            g = g.reshape((S, -1) + g.shape[3:])
            if scale is None:
                return g
            sc = scale[layer][table].reshape(g.shape[:-1])
            return (g.astype(jnp.float32) * sc[..., None]).astype(q.dtype)
        k, v = rows(dense(p, s) for p, s in zip(pools,
                                                scales or (None, None)))
        out = _attend_cached_chunk(
            _widen(q, k.shape[-1]), k, v, positions, self.softmax_scale(hd),
            window=window, sink=self.sink(params, window))
        return out.reshape(S, C, nq * v.shape[-1])

    def attend_dense(self, params, q, caches, start, window=None, first=0):
        """q: C queries a row at positions start[b] + i (start a scalar
        or [b]); caches = (k, v), [b, M, n_kv, d_k] and [.., d_v],
        holding the positions first .. first + M - 1 (`first` = 0: from
        the beginning; a window layer's sliding scratch starts where the
        chunk program says), every position the queries may see among
        them.  Under a `window`, ONE row's queries (the chunk program)
        read the window + C positions that end with the chunk and no
        more; rows at positions of their own (the tests' reference
        `decode_step_slots` over gathered views) read by the window's
        mask.

        Which shapes take which attention (the route record
        `kernel_routes["chunk_attn"]` says it per traced layer): ONE
        row's chunk of C > 1 queries at one start, the chunk program of
        chunked prefill, takes the blockwise kernel
        (ops/pallas/chunk_attention: the scores stay on the chip, and
        only the key blocks the chunk can see are read; under a window
        much narrower than the slice, only the band of key blocks each
        block of positions can see: `kernel_routes["chunk_attn_band"]`
        says which tiling a traced layer took and why) where
        `ops.pallas.resolve_route` and the kernel's gate allow: a TPU,
        key and value widths % 128, C a multiple of the sublane tile, a
        cache length that divides into key blocks of a multiple of 128,
        and at least 64 MB of float32 scores in the composition (heads x
        C x cache positions: under that the two tie on a v5e and the
        composition stays, as at InternLM2's chunk of 128 over 2,048
        positions).  Everything else keeps the XLA composition
        `models/generation._attend_cached_chunk`: any shape the gate
        refuses, every backend but a TPU, a single query (C = 1: the
        decode step over a dense cache), and rows at depths of their own
        (start [b > 1]: `decode_step_slots` / `verify_step_slots`), which
        no flag forces.  -> [b, C, n_q * d_v]."""
        from hetu_tpu.models.generation import _attend_cached_chunk
        from hetu_tpu.ops.pallas import chunk_attention as _ca
        from hetu_tpu.ops.pallas import _note_route, resolve_route
        b, C, nq, hd = q.shape
        d_k, d_v = caches[0].shape[-1], caches[1].shape[-1]
        sink = self.sink(params, window)
        q = _widen(q, d_k)
        M = caches[0].shape[1]
        if window is not None and b == 1 and window + C < M:
            # keys start + C - (window + C) .. start + C - 1, kept inside
            # the cache at both ends
            R = window + C
            inner = jnp.clip(jnp.reshape(start, ()) - first + C - R, 0, M - R)
            caches = tuple(lax.dynamic_slice_in_dim(c, inner, R, axis=1)
                           for c in caches)
            first = first + inner
        extra = {} if sink is None else {"sink": sink}
        if b == 1 and C > 1:
            kernel = resolve_route(
                "chunk_attn", _ca.check_route, q.shape, caches[0].shape,
                jnp.shape(start), window=window, dtype=caches[0].dtype,
                v_shape=caches[1].shape, sink=sink is not None)
        else:
            kernel = False
            _note_route("chunk_attn", False,
                        "a single query, or rows at depths of their own: "
                        "the composition")
        if sink is not None or d_k != d_v:
            _note_route(
                "chunk_attn_shapes", bool(kernel),
                f"keys {hd} (held in {d_k}) against values {d_v}, groups of "
                f"{nq // caches[0].shape[-2]}, "
                f"{'a sink a head' if sink is not None else 'no sink'}")
        if kernel:
            # which tiling the kernel takes, from the shapes it is handed
            plan = _ca.check_shapes(
                q.shape, caches[0].shape, jnp.shape(start), window=window,
                dtype=caches[0].dtype, v_shape=caches[1].shape,
                sink=sink is not None)
            _note_route("chunk_attn_band", bool(plan.pb), plan.why)
            with jax.named_scope("pallas_chunk_attention"):
                out = _ca.chunk_attention(q, *caches, start,
                                          softmax_scale=self.softmax_scale(hd),
                                          window=window, first=first,
                                          **extra)
        else:
            out = _attend_cached_chunk(q, *caches, start,
                                       self.softmax_scale(hd),
                                       window=window, first=first, **extra)
        return out.reshape(b, C, nq * d_v)

    def attend_prompt(self, params, q, entries, window=None):
        """Whole prompts attending their own entries, causally: the
        training forward's flash path; under a `window`, with a sink,
        with keys wider than the values or at a scale of the family's own
        the XLA composition by the window's mask (ops/pallas/
        flash_attention has none of the four).  -> [b, s, n_q * d_v]."""
        from hetu_tpu import ops
        b, s, nq, hd = q.shape
        d_k, d_v = entries[0].shape[-1], entries[1].shape[-1]
        sink = self.sink(params, window)
        if (window is not None or sink is not None or d_k != d_v
                or self.softmax_scale(hd) != hd ** -0.5):
            from hetu_tpu.models.generation import _attend_cached_chunk
            from hetu_tpu.ops.pallas import _note_route
            _note_route("flash_attn_window", False,
                        "whole prompts under a window, with a sink, with "
                        "keys wider than the values or at a scale of the "
                        "family's own: the XLA composition (none of them "
                        "in ops/pallas/flash_attention)")
            return _attend_cached_chunk(
                _widen(q, d_k), *entries, 0, self.softmax_scale(hd),
                window=window, sink=sink).reshape(b, s, nq * d_v)
        attn = ops.flash_attention(
            q, *entries, causal=True,
            use_pallas=None if self.config.use_flash_attention else False)
        return attn.reshape(b, s, nq * hd)
