"""Paged-attention decode Pallas kernel: attend directly over the serving
KV pool's page tables (gather-free decode).

The serving engine's decode step (PR 7 follow-up, closed here) used to
GATHER every slot's pages into a dense [S, max_len, n_kv, hd] view per
layer before attending — three passes over the cache bytes (gather read,
dense write, attention read), most of them over DEAD tail positions.
This kernel reads each slot's live pages straight from the pool, once.

**The walk** (`_walk_live_blocks`, the one page walk of `ops/pallas`:
this module's two kernels and `paged_latent_attention`'s, which walks
ONE stream, the latent pool, by it).
The grid is the slots: one grid step a slot, whatever the table's
width.  The pools stay whole in HBM (`memory_space=pl.ANY`); the page
table and the positions are scalar-prefetched.  Inside a grid step a
loop runs over BLOCKS of `pages_per_block` pages, and its trip count is
the slot's own: ``ceil(live_pages / pages_per_block)`` with
``live_pages = (positions[s] + C - 1) // page_size + 1``.  A page slot
past a slot's length costs nothing — no grid step, no copy, no
arithmetic — and an idle or prefilling slot (the engine sends it at
position 0 with a null table row) costs one grid step and one page.
A block's pages are not contiguous in the pool, so each is fetched by a
copy of the kernel's own (`pltpu.make_async_copy`, one per page and
pool array) into a VMEM buffer of ``[pages_per_block * page_size, n_kv,
hd]``; the buffers are double: while block b is computed, block b + 1
— or, in a slot's last block, the NEXT slot's first block — is in
flight, so no slot waits for its first page but slot 0.  That makes the
slot axis sequential (`"arbitrary"`): a copy started in one grid step
is waited for in the next.  In a slot's last block only the live pages
are fetched.

**A block's arithmetic** is one online-softmax update (the float32
running max, sum and accumulator of the flash forward), not one per
page.  A block ``[T, n_kv, hd]`` is, by a free reshape, ``T * n_kv``
keys of ``hd``: ALL query heads are scored against all of them in one
MXU product ``[rows, hd] x [hd, T * n_kv]``, the entries whose KV head
is not the query head's (``qh // group != kvh``) are masked together
with the positions past the slot's, and ``p . v`` runs over the same
flat axis.  It spends ``n_kv`` times the arithmetic, which is nothing
beside the bytes, and needs no relayout of a page: no per-head batched
product, no transpose (on a v5e the per-head form is 3-5 times slower:
PERF.md s6, PR 28).  The two products take the pool's dtype with
float32 accumulation (bfloat16 pools: ``q . k`` exact product for
product; float32 pools keep float32 products).  The value rows past the
slot's last position are zeroed in its last block, so that what a
buffer holds there (an earlier block, or nothing yet) cannot reach the
result through ``0 * x``; nothing past a slot's length is read from the
pool at all.  GQA, the null page (id 0) of inactive slots, the position
mask over the global key index and the softmax scale are what they were.
Decode is forward-only — no vjp (the training path keeps flash
attention).

`pages_per_block` is derived here from what the wrapper sees (page
size, heads, head dim, element size, table width, query rows): as many
pages as hold `_BLOCK_TOKENS` tokens within `_VMEM_BUDGET` of buffers
and temporaries, 1 where a page is already that large.  No caller
chooses it.

int8 pages (``HETU_TPU_KV_QUANT=int8``): the pool's per-head-vector f32
absmax scales ride in as two more HBM arrays, fetched by the same
per-page copies (through `scale_table` where their page ids differ), and
a block is dequantized IN-VMEM (``k * scale``) after its copies land —
the HBM read is the int8 payload (+ the small scale plane), ~3.9x fewer
cache bytes per decode step than fp32 pages
(ops/pallas/traffic.paged_attn_traffic prices it).  The token K/V
scattered pre-kernel quantize through the SAME blockwise primitives the
gather path uses (comm/compress -> ops/pallas/quant when routed), so
pool contents are bit-identical across the two decode programs.  int4
pages (``HETU_TPU_KV_QUANT=int4``): uint8 payloads of HALF the head dim
packed via `ops/quantization.pack_nibbles` (even index = LOW nibble,
values offset by +8) plus the same scale plane, unpacked and
dequantized in-VMEM (``(nibble - 8) * scale``).  Quantized blocks are
multiplied in float32, as they were.

**A window** (`paged_attention(window=w)`, a static width, for a layer
that reads only its last w positions, the query's own counted; exact
pages, single-token).  The walk BEGINS at the block that holds the
window's first position, `max(positions[s] - w + 1, 0)`, and ends as
before; of that block no page before the one that holds that position
is fetched (their table entries are the null page: the scheduler
released them); the positions of that page which precede the window
are masked and their value rows zeroed, in a slot's first block (and
its last, which may be the same) and in no other.  What lies behind a
window costs nothing, as what lies past a slot's length does.  It is
the same kernel: with `window=None` the walk, the block update and the
lowered program are what they were, operation for operation.

`paged_verify` is the multi-query sibling (spec-decode verification):
q carries C = k+1 query positions per slot, all attending the slot's
pages in ONE launch with per-position causal masks (query i sees keys
at global positions <= positions[s] + i).  It is the same kernel with
C * nq query rows (decode is C = 1): same walk, same block update, same
none/int8/int4 page modes.

**Keys wider than the values, and a sink** (`paged_attention`, exact
pages, single-token).  The V pool's head dim is its own (keys of 256
lanes, of which a model may use 192, beside values of 128): the K and V
buffers, the two products and the output take each its width, and
nothing else of the walk changes.  A `sink` [nq] (float32: a learned
scalar a query head) stands in the softmax as one more key that has no
value: the running maximum starts at it and the running sum at 1, where
they start at -1e30 and 0 without one; the blocks' updates are the same.
With neither, the lowered program is what it was, operation for
operation.

**One K/V head** (multi-query attention: exact pages).  A token's row
`[1, hd]` of two-byte elements is under one 32-bit word, and Mosaic
slices no page `[page_size, 1, hd]` out of such a pool (this module
refused the shape until PR 47).  But with one head the head axis says
nothing: the pool `[P, page_size, 1, hd]` IS `[P, page_size, hd]` (XLA
holds the four-dim array with the unit axis outermost of the page, at
its own bytes: no second head of zeros, and the reshape is a bitcast),
a page is a dense `[page_size, hd]` tile, and a block of the walk is by
a free reshape the same flat axis of T keys the kernel scores anyway
(column j = token j, KV head 0: `n_kv` = 1 in every mask).  So the
wrapper hands the kernel the three-dim view and buffers of `[2,
pages_per_block, page_size, hd]`; the walk, the block update and the
masks are what they are for any `n_kv`, and any group of query heads
(20 to the one head: no power of two) is the tall operand's rows.  The
pool, the contract's stored shape and the page write keep `[.., 1, hd]`.

Shape contract (drift-tested against `compatible`/`verify_compatible`):
key and value head dims % 128, q heads divide by kv heads, table/positions/q agree on the
slot count, scales present iff quant, pool head dim halved for int4,
and one page's buffers and temporaries within `_VMEM_LIMIT`."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.pallas import _interpret

NEG_INF = -1e30

# A block's size (PERF.md s6, PR 28: the sweep on a v5e at the serving
# cells' shape).  The tokens a block holds when the budget allows, the
# VMEM its two buffers per pool array and the block's temporaries may
# take of the 16 MiB a kernel gets by default, and what ONE page may need
# before the shape gate sends the shape to the gather fallback.
_BLOCK_TOKENS = 256
# ... and with ONE K/V head, whose token is one key of a block's flat
# axis where the serving cells' is 2 to 8: the same 2,048 keys a block.
# On a v5e, 128 slots of 300-4,400 tokens, 20 query heads (my chip run,
# PR 47): blocks of 256 / 512 / 1,024 / 2,048 / 4,096 tokens take 0.60 /
# 0.40 / 0.31 / 0.28 / 0.29 ms, 31 / 47 / 60 / 66 / 63% of the bytes' floor.
_BLOCK_TOKENS_ONE_HEAD = 2048
_VMEM_BUDGET = 6 << 20
_VMEM_LIMIT = 12 << 20


def _token_vmem_bytes(rows: int, n_kv: int, hd: int, itemsize: int,
                      quant: str, hd_v: Optional[int] = None) -> int:
    """VMEM bytes one cached token of a block takes: K and V in their two
    buffers each, for quantized pages their scale rows (a page's row
    padded to 8 sublanes) and the payload as float32, and the float32
    scores and probabilities of `rows` query rows against its `n_kv`
    keys."""
    hd_p = hd // 2 if quant == "int4" else hd
    hd_vp = hd_p if hd_v is None else hd_v
    nbytes = 2 * n_kv * (hd_p + hd_vp) * itemsize + 3 * rows * n_kv * 4
    if quant != "none":
        nbytes += 2 * 2 * 8 * n_kv * 4 + 2 * n_kv * hd * 4
    return nbytes


def _one_head(n_kv: int, quant: str) -> bool:
    """Exact pages of ONE K/V head: read as `[page_size, hd]` tiles
    (module docstring, "One K/V head")."""
    return n_kv == 1 and quant == "none"


def pages_per_block(rows: int, ps: int, n_kv: int, hd: int, itemsize: int,
                    max_pages: int, quant: str = "none",
                    hd_v: Optional[int] = None) -> int:
    """Pages the walk fetches and attends at once, from the shapes alone:
    `_BLOCK_TOKENS` tokens (`_BLOCK_TOKENS_ONE_HEAD` of one K/V head's
    exact pages) where `_VMEM_BUDGET` holds them, fewer where it does
    not, never more than the table is wide, at least one."""
    want = _BLOCK_TOKENS_ONE_HEAD if _one_head(n_kv, quant) else _BLOCK_TOKENS
    tokens = min(want, _VMEM_BUDGET // _token_vmem_bytes(
        rows, n_kv, hd, itemsize, quant, hd_v))
    return max(1, min(tokens // ps, max_pages))


def _check_pool(rows, q_heads_hd, pool_shape, table_shape, pos_shape, S, *,
                quant: str, pool_dtype, v_shape=None) -> Tuple[int, int, int]:
    nq, hd = q_heads_hd
    if quant not in ("none", "int8", "int4"):
        raise ValueError(f"paged-attention page mode {quant!r} "
                         "unsupported; known: ('none', 'int8', 'int4')")
    P, ps, n_kv, hd_p = pool_shape
    hd_stored = hd // 2 if quant == "int4" else hd
    if hd_p != hd_stored:
        raise ValueError(f"head dim mismatch: q {hd} expects pool "
                         f"{hd_stored} ({quant} pages), got {hd_p}")
    if nq % n_kv:
        raise ValueError(f"q heads {nq} must divide by kv heads {n_kv}")
    if len(table_shape) != 2 or table_shape[0] != S:
        raise ValueError(f"table {table_shape} must be [S={S}, max_pages]")
    if tuple(pos_shape) != (S,):
        raise ValueError(f"positions {pos_shape} must be [S={S}]")
    if hd % 128:
        raise ValueError(f"head dim {hd} is not lane-aligned (% 128); "
                         f"the gather fallback handles it")
    # element size: the pool's where the caller knows it, else the widest
    # a page mode stores (float32 pages, one-byte quantized payloads)
    itemsize = (jnp.dtype(pool_dtype).itemsize if pool_dtype is not None
                else 4 if quant == "none" else 1)
    hd_v = None
    if v_shape is not None and tuple(v_shape) != tuple(pool_shape):
        hd_v = v_shape[-1]
        if tuple(v_shape[:-1]) != (P, ps, n_kv) or hd_v % 128:
            raise ValueError(f"the V pool {tuple(v_shape)} must be the K "
                             f"pool's {tuple(pool_shape)} but for a head "
                             f"dim of its own, % 128")
        if quant != "none":
            raise ValueError("keys wider than the values read exact pages, "
                             f"not {quant!r}")
    page_bytes = ps * _token_vmem_bytes(rows, n_kv, hd, itemsize, quant,
                                        hd_v)
    if page_bytes > _VMEM_LIMIT:
        raise ValueError(f"one page of {ps} tokens needs {page_bytes} "
                         f"bytes of VMEM (limit {_VMEM_LIMIT}); the gather "
                         f"fallback handles it")
    return P, ps, n_kv


def check_shapes(q_shape, pool_shape, table_shape, pos_shape, *,
                  quant: str = "none", pool_dtype=None,
                  window: Optional[int] = None, v_shape=None,
                  sink: bool = False
                  ) -> Tuple[int, int, int, int, int, int]:
    """-> (S, nq, hd, P, ps, n_kv) of q [S, nq, hd] over a K pool [P, ps,
    n_kv, hd]; `v_shape`: the V pool's where its head dim is its own
    (None: the K pool's); `sink`: a sink a query head enters the softmax.
    Both read exact pages."""
    if len(q_shape) != 3 or len(pool_shape) != 4:
        raise ValueError(f"expected q [S, nq, hd] and pool [P, ps, n_kv, "
                         f"hd], got {q_shape} / {pool_shape}")
    if window is not None and (window < 1 or quant != "none"):
        raise ValueError(f"a window ({window}) is at least one position "
                         f"wide and reads exact pages, not {quant!r}")
    if sink and quant != "none":
        raise ValueError(f"a sink reads exact pages, not {quant!r}")
    S, nq, hd = q_shape
    P, ps, n_kv = _check_pool(nq, (nq, hd), pool_shape, table_shape,
                              pos_shape, S, quant=quant,
                              pool_dtype=pool_dtype, v_shape=v_shape)
    return S, nq, hd, P, ps, n_kv


def check_shapes_verify(q_shape, pool_shape, table_shape, pos_shape, *,
                         quant: str = "none", pool_dtype=None
                         ) -> Tuple[int, int, int, int, int, int, int]:
    if len(q_shape) != 4 or len(pool_shape) != 4:
        raise ValueError(f"expected q [S, C, nq, hd] and pool [P, ps, "
                         f"n_kv, hd], got {q_shape} / {pool_shape}")
    S, C, nq, hd = q_shape
    if C < 1:
        raise ValueError(f"verify needs at least one query position, "
                         f"got C={C}")
    P, ps, n_kv = _check_pool(C * nq, (nq, hd), pool_shape, table_shape,
                              pos_shape, S, quant=quant,
                              pool_dtype=pool_dtype)
    return S, C, nq, hd, P, ps, n_kv


def compatible(q_shape, pool_shape, table_shape, pos_shape, *,
               quant: str = "none", pool_dtype=None,
               window: Optional[int] = None, v_shape=None,
               sink: bool = False) -> bool:
    try:
        check_shapes(q_shape, pool_shape, table_shape, pos_shape,
                      quant=quant, pool_dtype=pool_dtype, window=window,
                      v_shape=v_shape, sink=sink)
        return True
    except ValueError:
        return False


def verify_compatible(q_shape, pool_shape, table_shape, pos_shape, *,
                      quant: str = "none", pool_dtype=None) -> bool:
    try:
        check_shapes_verify(q_shape, pool_shape, table_shape, pos_shape,
                             quant=quant, pool_dtype=pool_dtype)
        return True
    except ValueError:
        return False


def _walk_live_blocks(pages_of, streams, sem, parity, carry, block, *,
                      ppb, first_page_of=None):
    """The page walk: `block(b, buffer, carry, last)` over the live blocks
    of this grid step's slot, each block's pages fetched by per-page
    copies into the double buffers while the block before is computed.

    `pages_of(s)` is slot s's live page count (>= 1); `streams` is
    ``(table_ref, hbm_ref, vmem_ref)`` per pool array, `hbm_ref`
    ``[P, ...]`` read at ``table_ref[s, page_slot]`` into `vmem_ref`
    ``[2, ppb, ...]``; `sem` is DMA semaphores ``[2, len(streams)]``
    (a buffer's copies of one array share one, each waited for by its
    own size); `parity` is an SMEM word that carries, from one grid step
    to the next, which buffer the slot's first block was fetched into —
    by the LAST block of the slot before it, so the slot axis must run
    in order.  A block's copies are issued only for its live pages.
    Returns the carry after the slot's last block, which alone is called
    with ``last=True``.

    With `first_page_of(s)` (a layer that reads a WINDOW: the page that
    holds the first position slot s may see) the walk begins at the
    block that holds that page and fetches nothing before the page: a
    slot's pages behind its window cost nothing, as those past its
    length do.  A slot's first block is then called
    ``block(b, buffer, carry, last, first=True)``: its rows before the
    window's first page hold whatever they held.  None: from page 0,
    and no such argument."""
    s_idx, n_slots = pl.program_id(0), pl.num_programs(0)
    n_pages = pages_of(s_idx)
    nb = (n_pages + ppb - 1) // ppb
    windowed = first_page_of is not None
    page0 = first_page_of if windowed else (lambda s: 0)
    b0 = page0(s_idx) // ppb if windowed else 0

    def each_live_page(s, live, b, buffer, do):
        # a loop of the block's live pages, not `ppb` unrolled branches:
        # a dead page slot costs nothing, on the scalar core either, and
        # the kernel's code does not grow with the block
        def page(j, carry):
            for i, (table_ref, hbm_ref, vmem_ref) in enumerate(streams):
                do(pltpu.make_async_copy(
                    hbm_ref.at[table_ref[s, b * ppb + j]],
                    vmem_ref.at[buffer, j], sem.at[buffer, i]))
            return carry
        lo = jnp.clip(page0(s) - b * ppb, 0, ppb) if windowed else 0
        jax.lax.fori_loop(lo, jnp.clip(live - b * ppb, 0, ppb), page, 0)

    @pl.when(s_idx == 0)
    def _first_slot():
        parity[0] = 0
        each_live_page(s_idx, n_pages, b0, 0, lambda c: c.start())
    first = parity[0]

    def fetch_and(b, carry, last, **at_first):
        buffer = jax.lax.rem(first + b - b0, 2)
        # what follows this block: the slot's next one, or the next
        # slot's first (none after the last slot's last)
        s_next = s_idx if not last else jnp.minimum(s_idx + 1, n_slots - 1)
        live_next = (n_pages if not last else
                     jnp.where(s_idx + 1 < n_slots, pages_of(s_next), 0))
        b_next = (b + 1 if not last else
                  page0(s_next) // ppb if windowed else 0)
        each_live_page(s_next, live_next, b_next, 1 - buffer,
                       lambda c: c.start())
        each_live_page(s_idx, n_pages, b, buffer, lambda c: c.wait())
        return block(b, buffer, carry, last, **at_first)

    if windowed:
        # the first block apart, where it is not also the last: it alone
        # (and the last) has rows that nothing was fetched into
        mid = jnp.minimum(b0 + 1, nb - 1)
        carry = jax.lax.fori_loop(
            b0, mid, lambda b, c: fetch_and(b, c, False, first=True), carry)
        carry = jax.lax.fori_loop(
            mid, nb - 1, lambda b, c: fetch_and(b, c, False, first=False),
            carry)
        carry = fetch_and(nb - 1, carry, True, first=True)
    else:
        carry = jax.lax.fori_loop(
            0, nb - 1, lambda b, c: fetch_and(b, c, False), carry)
        carry = fetch_and(nb - 1, carry, True)
    parity[0] = jax.lax.rem(first + nb - b0, 2)
    return carry


def _load_block(page_ref, buffer, *, quant, hd):
    """A fetched block ``[ppb, ps, n_kv, hd_p]`` (one K/V head of exact
    pages: ``[ppb, ps, hd_p]``) -> ``[T * n_kv, hd]`` keys
    (or values) in the dtype they are multiplied in: the pool's own for
    exact pages, the integer payload as float32 for int8/int4 (its scales
    go on the scores and the probabilities: `_scale_row`)."""
    x = page_ref[buffer]
    if quant == "int4":
        # unpack the nibble payload [.., hd//2] (even index = LOW nibble,
        # ops/quantization.pack_nibbles layout, +8 offset)
        p8 = x.astype(jnp.uint8)
        lo = (p8 & 0xF).astype(jnp.int32) - 8
        hi = (p8 >> 4).astype(jnp.int32) - 8
        x = jnp.stack((lo, hi), axis=-1).reshape(x.shape[:-1] + (hd,))
    if quant != "none":
        x = x.astype(jnp.float32)
    return x.reshape(-1, hd)


def _scale_row(scale_ref, buffer):
    """A fetched block's scales ``[ppb, 1, ps * n_kv]`` -> the row
    ``[1, T * n_kv]`` that lies along a block's keys: one f32 absmax
    scale per head-vector (the kv_pool blockwise layout), so a key's
    scale is a factor of its score and a value's of its probability."""
    rows = scale_ref[buffer]
    return jnp.concatenate([rows[j] for j in range(rows.shape[0])], axis=1)


def _kernel(*refs, scale, C, ps, ppb, n_kv, group, mp, quant, window=None,
            sink=False):
    """One grid step = one slot: its ``C * nq`` query rows (row r is query
    position r // nq, head r % nq) against its live blocks.  `sink`: one
    more operand [rows, 1] after q, each row's sink."""
    sink_ref = None
    if sink:
        refs = list(refs)
        sink_ref = refs.pop(3)
    if quant != "none":
        (table_ref, pos_ref, stable_ref, q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm,
         o_ref, k_buf, v_buf, ks_buf, vs_buf, sem, parity) = refs
        streams = ((table_ref, k_hbm, k_buf), (table_ref, v_hbm, v_buf),
                   (stable_ref, ks_hbm, ks_buf), (stable_ref, vs_hbm, vs_buf))
    else:
        (table_ref, pos_ref, q_ref, k_hbm, v_hbm,
         o_ref, k_buf, v_buf, sem, parity) = refs
        ks_buf = vs_buf = None
        streams = ((table_ref, k_hbm, k_buf), (table_ref, v_hbm, v_buf))
    T = ppb * ps
    rows, hd = q_ref.shape[1:]
    hd_v = hd if quant != "none" else v_buf.shape[-1]
    nq = rows // C
    pos = pos_ref[pl.program_id(0)]

    def pages_of(s):
        # the LAST query position (pos + C - 1) decides which pages hold
        # any visible key
        return jnp.minimum((pos_ref[s] + C - 1) // ps + 1, mp)

    # the products take the pool's dtype (quantized payloads: float32)
    q = q_ref[0].astype(jnp.float32 if quant != "none" else k_buf.dtype)
    # column j of a block is token j // n_kv, KV head j % n_kv
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, T * n_kv), 1)
    q_head = jax.lax.rem(row, nq) if C > 1 else row
    q_pos = pos + (jax.lax.div(row, nq) if C > 1 else 0)      # [rows, 1]
    own_head = jax.lax.rem(col, n_kv) == jax.lax.div(q_head, group)

    def first_page_of(s):
        # the page that holds the first position the window lets slot s see
        return jnp.maximum(pos_ref[s] - window + 1, 0) // ps
    # the first position the window lets this slot see
    pos0 = None if window is None else jnp.maximum(pos - window + 1, 0)

    def block(b, buffer, carry, last, first=False):
        m_prev, l_prev, acc = carry
        k = _load_block(k_buf, buffer, quant=quant, hd=hd)
        v = _load_block(v_buf, buffer, quant=quant, hd=hd_v)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [rows, T * n_kv]
        if quant != "none":
            s = s * _scale_row(ks_buf, buffer)
        if last or C > 1:
            # query row r sees the keys at global positions <= q_pos[r]
            seen = own_head & (col < (q_pos - b * T + 1) * n_kv)
        else:
            seen = own_head             # a block before the last is all live
        if first:
            # ... and, under a window, at positions > q_pos[r] - window:
            # all of a block after the slot's first
            seen = seen & (col >= (q_pos - window + 1 - b * T) * n_kv)
        if last or first:
            # rows of the buffer past the slot's last token, or before
            # the window's first position, hold whatever they held (the
            # page's own older tokens, or nothing fetched): they must not
            # reach the result through 0 * x
            v_row = jax.lax.broadcasted_iota(jnp.int32, (T * n_kv, 1), 0)
            live = v_row < (pos + C - b * T) * n_kv if last else None
            if first:
                fetched = v_row >= (pos0 - b * T) * n_kv
                live = fetched if live is None else live & fetched
            v = jnp.where(live, v, jnp.zeros_like(v))
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p_ = jnp.exp(s - m_new)        # an unseen key's is exp(-1e30) = 0
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p_, axis=1, keepdims=True)
        if quant != "none":
            # (a page not fetched has no scales: 0 * x again)
            p_ = jnp.where(seen, p_ * _scale_row(vs_buf, buffer), 0.0)
        pv = jax.lax.dot_general(
            p_.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [rows, hd_v]
        return m_new, l_new, acc * corr + pv

    if sink_ref is None:
        m0 = jnp.full((rows, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((rows, 1), jnp.float32)
    else:
        # the sink as a key seen before any other: exp(sink - sink) = 1
        m0 = sink_ref[...].astype(jnp.float32)
        l0 = jnp.ones((rows, 1), jnp.float32)
    carry = (m0, l0, jnp.zeros((rows, hd_v), jnp.float32))
    _, l, acc = _walk_live_blocks(
        pages_of, streams, sem, parity, carry, block, ppb=ppb,
        **({} if window is None else {"first_page_of": first_page_of}))
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _resolve_quant(quant, k_scale, v_scale):
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    if quant is None:
        quant = "int8" if k_scale is not None else "none"
    if (quant != "none") != (k_scale is not None):
        raise ValueError(f"page mode {quant!r} needs scales iff "
                         "quantized (int8/int4)")
    return quant


def _scalar_prefetch(table, positions, scale_table, k_scale, P, ps, n_kv,
                     quant):
    """The kernel's scalar-prefetch operands: (table, positions) and,
    for quantized pages, the page ids the SCALE planes are read by —
    `scale_table` into planes of their own page count, or `table`
    itself into planes of the payload's P pages."""
    scalars = [table.astype(jnp.int32), positions.astype(jnp.int32)]
    if quant == "none":
        return scalars
    if scale_table is None:
        scale_table = table
    else:
        P = k_scale.shape[0]
    if tuple(k_scale.shape) != (P, ps, n_kv):
        raise ValueError(f"scales {k_scale.shape} must be "
                         f"[P={P}, ps={ps}, n_kv={n_kv}]")
    if scale_table.shape != table.shape:
        raise ValueError(f"scale_table {scale_table.shape} must match "
                         f"table {table.shape}")
    return scalars + [scale_table.astype(jnp.int32)]


def _attend(q, k_pool, v_pool, scalars, k_scale, v_scale, *, C, scale,
            quant, window=None, sink=None):
    """q ``[S, C * nq, hd]`` over the pools: the one `pallas_call` of
    this module (decode is C = 1).  `sink` [rows] float32 or None."""
    S, rows, hd = q.shape
    _, ps, n_kv, hd_p = k_pool.shape
    hd_vp = v_pool.shape[-1]
    # what ONE token holds in a page array: its heads' rows, or the one
    # head's row alone
    heads = () if _one_head(n_kv, quant) else (n_kv,)
    # the values' own width where it is not the keys' (exact pages)
    hd_v = hd if hd_vp == hd_p else hd_vp
    mp = scalars[0].shape[1]
    ppb = pages_per_block(rows, ps, n_kv, hd, k_pool.dtype.itemsize, mp,
                          quant, None if hd_vp == hd_p else hd_vp)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = [q] + [x.reshape(x.shape[:2] + heads + x.shape[3:])
                      for x in (k_pool, v_pool)]
    scratch = [pltpu.VMEM((2, ppb, ps) + heads + (hd_p,), k_pool.dtype),
               pltpu.VMEM((2, ppb, ps) + heads + (hd_vp,), v_pool.dtype)]
    if quant != "none":
        # a page's scales as ONE lane-dense row: Mosaic slices no page
        # out of a plane whose minor dim is the few KV heads
        operands += [x.reshape(x.shape[0], 1, ps * n_kv)
                     for x in (k_scale, v_scale)]
        scratch += [pltpu.VMEM((2, ppb, 1, ps * n_kv), x.dtype)
                    for x in (k_scale, v_scale)]
    in_specs = [pl.BlockSpec((1, rows, hd), lambda s, *_: (s, 0, 0))]
    if sink is not None:
        # after q; the same rows for every slot
        operands.insert(1, sink.astype(jnp.float32).reshape(rows, 1))
        in_specs.append(pl.BlockSpec((rows, 1), lambda s, *_: (0, 0)))
    n_hbm = len(operands) - len(in_specs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(S,),
        in_specs=in_specs + [hbm] * n_hbm,
        out_specs=pl.BlockSpec((1, rows, hd_v), lambda s, *_: (s, 0, 0)),
        scratch_shapes=scratch + [
            pltpu.SemaphoreType.DMA((2, n_hbm)),
            pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, C=C, ps=ps, ppb=ppb,
                          n_kv=n_kv, group=rows // C // n_kv, mp=mp,
                          quant=quant,
                          **({} if window is None else {"window": window}),
                          **({} if sink is None else {"sink": True})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, rows, hd_v), q.dtype),
        # in order: a slot's last block fetches the next slot's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(*scalars, *operands)


def paged_attention(q, k_pool, v_pool, table, positions, *,
                    softmax_scale: Optional[float] = None,
                    k_scale=None, v_scale=None, quant=None,
                    scale_table=None, window: Optional[int] = None,
                    sink=None):
    """Decode attention over paged KV.  q: [S, nq, hd] (one token per
    slot); k_pool/v_pool: [P, page_size, n_kv, hd] (page 0 = the null
    page; the V pool's head dim may be its own, hd_v: exact pages); table: [S, max_pages] int32 page ids; positions: [S] int32 —
    slot s attends over global positions <= positions[s], and only the
    table entries of the pages that hold them are read.  int8 pools
    pass their per-head-vector f32 scales [P, page_size, n_kv] as
    k_scale/v_scale and dequantize in-kernel; int4 pools additionally
    pass ``quant="int4"`` (uint8 nibble payloads, pool head dim hd//2).
    ``scale_table`` [S, max_pages] gives the scales page ids of their
    own, for a caller whose scale planes are not laid out like the
    payload (models/generation hands the kernel every layer's payload
    pages in one array and one layer's scales; ignored for exact pages).
    ``window`` (a static width, for a layer that reads only its last
    `window` positions, the query's own counted; exact pages): slot s
    attends over positions[s] - window < j <= positions[s]; the walk
    begins at the block that holds the window's first position, fetches
    no page before that position's and masks what precedes it in its
    page.  None is the kernel as it was, operation for operation.
    ``sink`` [nq] (exact pages): a scalar a query head that stands in
    the softmax's denominator as one more key and adds no value.
    Returns [S, nq, hd_v].  Raises ValueError on shapes outside
    `compatible` (the dense-gather fallback in models/generation
    handles those)."""
    quant = _resolve_quant(quant, k_scale, v_scale)
    S, nq, hd, P, ps, n_kv = check_shapes(
        q.shape, k_pool.shape, table.shape, positions.shape, quant=quant,
        pool_dtype=k_pool.dtype, window=window, v_shape=v_pool.shape,
        sink=sink is not None)
    scalars = _scalar_prefetch(table, positions, scale_table, k_scale,
                               P, ps, n_kv, quant)
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    return _attend(q, k_pool, v_pool, scalars, k_scale, v_scale, C=1,
                   scale=scale, quant=quant, window=window, sink=sink)


def paged_verify(q, k_pool, v_pool, table, positions, *,
                 softmax_scale: Optional[float] = None,
                 k_scale=None, v_scale=None, quant=None,
                 scale_table=None):
    """Multi-query verify attention over paged KV (spec decoding).
    q: [S, C, nq, hd] — slot s's C = k+1 query positions sit at global
    positions positions[s]..positions[s]+C-1, each attending causally
    over the slot's pages; pools/table/scales/scale_table exactly as
    `paged_attention`.  Returns [S, C, nq, hd].  Raises ValueError on
    shapes outside `verify_compatible` (the gather verify program in
    models/generation handles those)."""
    quant = _resolve_quant(quant, k_scale, v_scale)
    S, C, nq, hd, P, ps, n_kv = check_shapes_verify(
        q.shape, k_pool.shape, table.shape, positions.shape, quant=quant,
        pool_dtype=k_pool.dtype)
    scalars = _scalar_prefetch(table, positions, scale_table, k_scale,
                               P, ps, n_kv, quant)
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    out = _attend(q.reshape(S, C * nq, hd), k_pool, v_pool, scalars,
                  k_scale, v_scale, C=C, scale=scale, quant=quant)
    return out.reshape(S, C, nq, hd)
