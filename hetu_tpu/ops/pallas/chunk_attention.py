"""Chunk attention: the attention of the chunk program (chunked prefill)
for a K/V cache, blockwise, with its scores on the chip.

One row's chunk of C queries at positions start .. start + C - 1 attends
the dense cache it has just been written into (`models/generation.
extend_cache`, through `cache_contract.KVAttention.attend_dense`).  The
XLA composition (`models/generation._attend_cached_chunk`) forms the
float32 scores of EVERY position of the cache it is handed, in HBM, and
crosses them several times; that was 63% of the Trinity cell's chunk
program (PERF.md s6, PR 35).  Here:

* **the keys are walked in blocks with an online softmax** (the float32
  running max, sum and accumulator of the flash forward, in VMEM): no
  score goes to HBM;
* **only the key blocks a chunk can see are fetched and multiplied.**
  The first and the number of live blocks are scalar-prefetched from the
  chunk's traced `start`: from the block that holds position
  `max(0, start - window + 1)` (a window layer) or from block 0, up to
  the block that holds `start + C - 1`.  The key index map CLAMPS to the
  last live block, so a grid step past it names the block the step
  before already holds and moves no bytes; its arithmetic is skipped by
  `pl.when`.  What such a step costs is the grid step itself (~0.35 us
  on a v5e).  A full layer over a scratch of `max_len` positions pays
  for the prompt's length, not for `max_len`;
* **under a window far narrower than the slice, a row tile walks its OWN
  band of key blocks** (the band form, `band_plan`).  A tile of one
  head's whole chunk can see every key of the `window + C` positions a
  window layer is handed, so all of them are multiplied and masked:
  MiMo's 1,024 queries that see 128 keys each paid for 1,152 (PERF.md
  s6, PR 49).  In the band form q is laid `[n_kv, (C // pb) * g * pb,
  hd]`, row `(c // pb) * g * pb + gi * pb + c % pb`: a tile is ALL g
  query heads of the KV head at ONE block of `pb` consecutive positions,
  so its rows share one band of `pb + window - 1` keys.  The key index
  map takes the tile's index: from the block that holds position
  `q0 + r * pb - window + 1` to the block of `q0 + r * pb + pb - 1`,
  kept inside the chunk's live blocks (`_tile_blocks`, from the same
  three prefetched scalars), and the grid's third extent is the STATIC
  number of key blocks a band can touch, not `M // kb`.  A step past the
  band is clamped and skipped as a dead step is.  Same body, same
  `pallas_call`; the form is chosen from `window`, C, M and the group
  alone (the band's key blocks must fit `_BAND_PAYS` times in M), and
  `check_shapes` returns it with the reason (`Plan`);
* **the mask inside a block is by global positions**, `_attend_cached_
  chunk`'s rule: key k is seen by the query at position t iff k <= t
  and, under a window, k > t - window.  A block every entry of which is
  seen by every row of the tile (the cached prefix, inside the window)
  takes no mask at all;
* **q, K and V are multiplied in the cache's dtype** (bfloat16 in
  serving) with float32 accumulation, the probabilities cast to V's
  dtype for p.v as the paged kernels do; the softmax statistics are
  float32;
* **one KV head's whole group of query heads** is one tall operand: q
  is laid out `[n_kv, g * C, hd]`, row gi * C + c the query of head
  (kvh, gi) at position start + c (the band form's order: above), and a
  row tile of it (up to `_ROW_TILE` rows) meets each key block `[kb,
  hd]` of its KV head in one MXU product.

**This module owns the online softmax** (`softmax_init`,
`softmax_step`, `softmax_finish`: the statistics of a row tile over its
key blocks).  `latent_chunk_attention.py`, the same walk over a LATENT
cache (MLA: the keys and values of a block are made from its latents
inside that kernel), imports the three, `live_blocks` and the score
gate `require_score_bytes`; there is no second copy.

The grid is (KV heads, row tiles, key blocks), the key blocks innermost
and in order.  K and V come head-major, `[n_kv, M, hd]`, and a layer's
slab is `[M, n_kv, hd]`: Mosaic's DMA slices no single head out of a
packed bfloat16 `[n_kv, hd]` tile, so the slab is relaid first, by a
small kernel of this module (`_relay_heads`: a block of positions in,
each head's rows out, the LIVE blocks only; a load `ref[:, h, :]` is what
Mosaic does take; ONE K/V head needs none: `[M, 1, hd]` is `[1, M, hd]`
by a reshape).  Left to XLA as a `transpose`, the relayout became a
bitcast of a head-major COPY OF THE WHOLE SCRATCH of every layer (84 MB,
four times a Trinity chunk: compiled for a described v5e and measured,
PR 35), because a slice at a constant layer index hands its layout up to
what it slices.

**Keys wider than the values, and a sink.**  V's head dim is its own
(keys of 256 lanes, of which a model may use 192, beside values of 128):
the key and value blocks, the accumulator and the output take each its
width.  A `sink` [nq] (a learned scalar a query head) stands in the
softmax as one more key that has no value: a row's running maximum
starts at its head's sink and its running sum at 1.  With neither, the
lowered program is what it was.

Shape contract (`check_shapes`, drift-tested against `compatible`): ONE
row (b = 1) at ONE start, C > 1 queries, C a multiple of the sublane
tile of the dtype, the key and the value head dims multiples of 128
lanes, q heads a multiple of the KV heads, and a cache length M that
`fit_block` divides into key blocks of a multiple of 128 (a cache
shorter than that is one block, if a multiple of the sublane tile).  Rows at depths of their own (the verify
step, the gather decode route) and single queries are refused: they keep
the composition.  The ROUTE's gate (`check_route`) also refuses what the
kernel takes but does not pay for: fewer than 64 MB of float32 scores in
the composition.  Forward only (serving); no vjp.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.pallas import _interpret
from hetu_tpu.ops.pallas.flash_attention import fit_block

NEG_INF = -1e30

# Block sizes, from the shapes alone.  A key block of up to 1,024
# positions and a row tile of up to 1,024 query rows (PERF.md s6, PR 35:
# the sweep on a v5e at the serving cells' shapes): the float32 score tile
# is 4 MB, and a grid step's products (0.54 GFLOP) outweigh its ~0.35 us
# and the accumulator's rescaling.
_KEY_BLOCK = 1024
_ROW_TILE = 1024
_VMEM_LIMIT = 48 << 20
#: float32 score bytes of the composition from which the route takes the
#: kernel (`check_route`)
_MIN_SCORE_BYTES = 64 << 20
#: bytes of one array's block of positions in the relayout kernel
_RELAY_BLOCK_BYTES = 1 << 20
#: the band form (a window layer whose row tiles are blocks of positions):
#: its key block, and how many times a tile's band of key blocks has to fit
#: in the M keys the whole-chunk tile multiplies before the band is taken
_BAND_KEY_BLOCK = 128
_BAND_PAYS = 2


def _row_tile(C: int, group: int) -> int:
    """Rows of `[group * C]` a grid step takes: whole chunks of C rows
    (as many query heads of the group as `_ROW_TILE` holds) or, for a
    chunk longer than that, its largest divisor within it that keeps the
    sublane tiling (a multiple of 16).  Either way a tile's rows are at
    consecutive positions modulo the tile's span of positions (C, or the
    tile itself): row i of a tile stands at position `c_lo + i % span`,
    which holds of the band form's tiles (`band_plan`: every head of the
    group at ONE block of `pb` positions, span `pb`) as well."""
    if C <= _ROW_TILE:
        heads = max(m for m in range(1, group + 1)
                    if group % m == 0 and m * C <= _ROW_TILE)
        return heads * C
    t = _ROW_TILE - _ROW_TILE % 16
    while t and C % t:
        t -= 16
    return t


class Plan(NamedTuple):
    """What `check_shapes` makes of a call's shapes: the sizes, the row
    tile `tr` and key block `kb`, the key blocks a row tile walks
    (`steps`, the grid's third extent) and, in the band form, the
    positions a tile spans (`pb`; 0: the present tiling), with the
    reason for the form either way (`why`: the `kernel_routes` line's)."""
    C: int
    nq: int
    hd: int
    M: int
    n_kv: int
    tr: int
    kb: int
    steps: int
    pb: int
    why: str


def band_plan(C: int, M: int, group: int, window) -> Tuple[int, int, int, str]:
    """-> (pb, kb, steps, why): the band form's tiling, from the shapes
    alone, or pb = 0 where the present tiling stays.  A tile of the band
    form is every head of the group at ONE block of `pb` consecutive
    positions (the largest multiple of 128 that divides C and keeps
    `group * pb` within `_ROW_TILE`), so its rows share a band of
    `pb + window - 1` keys, which touches at most `steps` key blocks of
    `kb` wherever it begins.  It is taken where a tile's band
    (`steps * kb` keys) fits `_BAND_PAYS` times in the M keys a tile of
    the whole chunk multiplies."""
    if window is None:
        return 0, 0, 0, "no window: a tile of the whole chunk sees every " \
                        "key up to its own, the present tiling"
    pb = max((p for p in range(128, C + 1, 128)
              if C % p == 0 and group * p <= _ROW_TILE), default=0)
    kb = fit_block(_BAND_KEY_BLOCK, M)
    if not pb or kb % 128:
        return 0, 0, 0, (f"no block of positions (C {C}, groups of {group}) "
                         f"or of keys (M {M}) that is a multiple of 128")
    steps = (pb + window - 3) // kb + 2
    said = (f"{pb} positions a tile see {pb + window - 1} keys: {steps} "
            f"key blocks of {kb} against the chunk's {M}")
    if _BAND_PAYS * steps * kb > M:
        return 0, 0, 0, f"{said}, over 1/{_BAND_PAYS}: the present tiling"
    return pb, kb, steps, f"{said}, pb {pb}, kb {kb}, steps {steps}"


def check_shapes(q_shape, k_shape, start_shape=(), *, window=None,
                 dtype=None, v_shape=None, sink: bool = False) -> Plan:
    """-> the `Plan`, or ValueError with the reason the composition
    takes the shape instead.  `v_shape`: V's where its head dim is its
    own (None: K's); `sink`: a sink a query head enters the softmax (any
    shape the kernel takes, takes one)."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        raise ValueError(f"expected q [b, C, nq, hd] and k [b, M, n_kv, "
                         f"hd], got {q_shape} / {k_shape}")
    b, C, nq, hd = q_shape
    _, M, n_kv, hd_k = k_shape
    if b != 1 or k_shape[0] != 1 or math.prod(start_shape) != 1:
        raise ValueError(f"{b} rows at starts {tuple(start_shape)}: the "
                         f"kernel takes ONE row's chunk at one start; rows "
                         f"at depths of their own keep the composition")
    if C == 1:
        raise ValueError("C = 1: a single query has no chunk to block; "
                         "the composition (or the paged kernel) takes it")
    if hd_k != hd or hd % 128:
        raise ValueError(f"head dim {hd} (cache {hd_k}) is not "
                         f"lane-aligned (% 128)")
    if v_shape is not None and (tuple(v_shape[:-1]) != tuple(k_shape[:-1])
                                or v_shape[-1] % 128):
        raise ValueError(f"v {tuple(v_shape)} must be k's {tuple(k_shape)} "
                         f"but for a head dim of its own, % 128")
    if nq % n_kv:
        raise ValueError(f"q heads {nq} must divide by kv heads {n_kv}")
    if window is not None and window < 1:
        raise ValueError(f"a window ({window}) is at least one position "
                         f"wide")
    sub = 32 // (jnp.dtype(dtype).itemsize if dtype is not None else 2)
    tr = _row_tile(C, nq // n_kv)
    if C % sub or not tr:
        raise ValueError(f"a chunk of C = {C} rows does not tile by the "
                         f"{sub} sublanes of the dtype")
    kb = fit_block(_KEY_BLOCK, M)
    if kb % 128 and (M > 128 or M % sub):
        raise ValueError(f"cache length {M} has no key block that is a "
                         f"multiple of 128 (best: {kb})")
    pb, band_kb, steps, why = band_plan(C, M, nq // n_kv, window)
    if pb:
        tr, kb = nq // n_kv * pb, band_kb
    return Plan(C, nq, hd, M, n_kv, tr, kb, steps or M // kb, pb, why)


def check_route(q_shape, k_shape, start_shape=(), *, window=None,
                dtype=None, v_shape=None, sink: bool = False):
    """The gate `attend_dense` hands to `resolve_route`: the shapes the
    kernel takes (`check_shapes`) AND for which it pays.  What the
    composition pays for is the float32 scores of every position it is
    handed, crossed in HBM several times; the kernel pays two launches
    and the relayouts of q, K, V and the output whatever the size.  On a
    v5e they tie at InternLM2's chunk (16 query heads x 128 x 2,048
    positions = 16.8 MB of scores: 0.09-0.11 ms a layer either way, and
    the long-prompt cell read 1.6-4.8% slower with the kernel), and the
    kernel wins 2.7-14 times at Trinity's (168 and 537 MB): PERF.md s6,
    PR 35.  Forced flags ask neither."""
    out = check_shapes(q_shape, k_shape, start_shape, window=window,
                       dtype=dtype, v_shape=v_shape, sink=sink)
    require_score_bytes(q_shape[2], q_shape[1], k_shape[1])
    return out


def require_score_bytes(heads: int, C: int, M: int):
    """ValueError where the composition's float32 scores (heads x C
    queries x M cache positions) are under `_MIN_SCORE_BYTES`: the part
    of a route's gate this kernel and `latent_chunk_attention` share."""
    score_bytes = 4 * C * heads * M
    if score_bytes < _MIN_SCORE_BYTES:
        raise ValueError(
            f"{heads} heads x {C} queries x {M} "
            f"positions are {score_bytes >> 20} MB of float32 scores, under "
            f"the {_MIN_SCORE_BYTES >> 20} MB from which the kernel pays: "
            f"the composition keeps them")


def compatible(q_shape, k_shape, start_shape=(), *, window=None,
               dtype=None, v_shape=None, sink: bool = False) -> bool:
    try:
        check_shapes(q_shape, k_shape, start_shape, window=window,
                     dtype=dtype, v_shape=v_shape, sink=sink)
        return True
    except ValueError:
        return False


def live_blocks(start, C: int, M: int, kb: int, window=None, first=0):
    """(first live key block, number of live key blocks) of a cache that
    holds positions first .. first + M - 1, for a chunk at `start`: the
    blocks from the one that holds position max(0, start - window + 1)
    (block 0 without a window) to the one that holds start + C - 1, both
    kept inside the cache."""
    lo = 0 if window is None else jnp.clip(start - window + 1 - first,
                                           0, M - 1) // kb
    hi = jnp.clip(start + C - 1 - first, 0, M - 1) // kb
    return lo, hi - lo + 1


# -- the online softmax of a row tile over its key blocks -------------------
# The one copy: this module's kernel and `latent_chunk_attention`'s walk
# their key blocks with these three, the float32 running maximum `m_scr`
# [rows, 1], sum `l_scr` [rows, 1] and accumulator `acc_scr` [rows, dv]
# in VMEM scratch from a tile's first key block to its last.

def softmax_init(m_scr, l_scr, acc_scr, sink_ref=None):
    """Before a tile's first key block.  `sink_ref` [rows, 1]: each
    row's sink, a key seen before any other that has no value."""
    if sink_ref is None:
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
    else:
        # the sink as a key seen before any other: exp(0) = 1
        m_scr[...] = sink_ref[...].astype(jnp.float32)
        l_scr[...] = jnp.ones_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def softmax_step(s, v, m_scr, l_scr, acc_scr):
    """One key block: s [rows, kb] its float32 scores, scaled and masked
    with NEG_INF; v [kb, dv] its values, which the probabilities are
    cast to the dtype of for p.v (float32 accumulation)."""
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # a row none of whose keys it has seen yet holds m = NEG_INF and
    # p = 1 for them: the first seen key's correction (exp(NEG_INF -
    # m) = 0) wipes that, and every row sees its own position
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def softmax_finish(o_ref, m_scr, l_scr, acc_scr):
    """After a tile's last key block: the one division."""
    l = l_scr[...]
    o_ref[...] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)) \
        .astype(o_ref.dtype)


def _tile_blocks(s, r, pb: int, window, kb: int):
    """(first, number) of the key blocks row tile `r` walks, from the
    prefetched s = (b0, live, q0): the chunk's live blocks b0 .. b0 +
    live - 1 (`live_blocks`) or, in the band form (`pb`), those of them
    from the block that holds the window's first key of the tile's first
    position q0 + r * pb to the block that holds its last position.  The
    kernel and its key index map both ask here."""
    if not pb:
        return s[0], s[1]
    c_lo = s[2] + r * pb
    last = s[0] + s[1] - 1
    lo = jnp.clip(jax.lax.div(jnp.maximum(c_lo - window + 1, 0), kb),
                  s[0], last)
    hi = jnp.clip(jax.lax.div(jnp.maximum(c_lo + pb - 1, 0), kb), s[0], last)
    return lo, hi - lo + 1


def _kernel(s_ref, q_ref, *refs, scale, window, C, tr, kb, sink=False, pb=0):
    # `sink`: one more operand [tr, 1] after q, each row's sink
    sink_ref, refs = (refs[0], refs[1:]) if sink else (None, refs)
    k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    r, j = pl.program_id(1), pl.program_id(2)
    b0, live, q0 = s_ref[0], s_ref[1], s_ref[2]
    # the tile's rows sit at positions q0 + c_lo .. q0 + c_hi of the
    # cache as it was handed in (q0 = start - first), row i at
    # c_lo + i % span; it walks `live` key blocks from block b0
    if pb:
        # the band form: every head of the group at positions r * pb ..
        c_lo, span = r * pb, pb
    else:
        c_lo = (r * tr) % C if tr < C else 0
        span = min(tr, C)
    c_hi = c_lo + span - 1
    b0, live = _tile_blocks((b0, live, q0), r, pb, window, kb)

    stats = (m_scr, l_scr, acc_scr)
    pl.when(j == 0)(lambda: softmax_init(*stats, sink_ref))

    def update(masked: bool):
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            i = jax.lax.broadcasted_iota(jnp.int32, (tr, 1), 0)
            if tr > span:
                # several heads a tile: of whole chunks (c_lo = 0), or
                # of one block of positions each (the band form)
                i = jax.lax.rem(i, span)
            qpos = q0 + (i if tr > span and not pb else c_lo + i)
            kpos = (b0 + j) * kb + jax.lax.broadcasted_iota(
                jnp.int32, (1, kb), 1)
            seen = kpos <= qpos
            if window is not None:
                seen = seen & (kpos > qpos - window)
            s = jnp.where(seen, s, NEG_INF)
        softmax_step(s, v, *stats)

    k_lo = (b0 + j) * kb
    clear = k_lo + kb - 1 <= q0 + c_lo
    if window is not None:
        clear = clear & (k_lo > q0 + c_hi - window)
    pl.when((j < live) & clear)(lambda: update(False))
    pl.when((j < live) & jnp.logical_not(clear))(lambda: update(True))

    pl.when(j == pl.num_programs(2) - 1)(
        lambda: softmax_finish(o_ref, *stats))


def _relay_heads(scalars, k, v, kb: int):
    """k [M, n_kv, hd], v [M, n_kv, hd_v] -> [n_kv, M, hd], [n_kv, M,
    hd_v], the key blocks scalars[0] .. scalars[0] + scalars[1] - 1 (of
    `kb` positions) only: what lies outside them is not read, and not
    written either."""
    M, n_kv, hd = k.shape
    hd_v = v.shape[-1]
    rb = kb
    while rb % 2 == 0 and rb * n_kv * hd * k.dtype.itemsize \
            > _RELAY_BLOCK_BYTES:
        rb //= 2
    per = kb // rb          # relayout blocks a key block

    def kernel(s_ref, k_ref, v_ref, ko_ref, vo_ref):
        @pl.when(pl.program_id(0) < s_ref[1] * per)
        def _():
            for h in range(n_kv):
                ko_ref[h] = k_ref[:, h, :]
                vo_ref[h] = v_ref[:, h, :]

    def block(j, s):
        return s[0] * per + jnp.minimum(j, s[1] * per - 1)

    def ins(d):
        return pl.BlockSpec((rb, n_kv, d), lambda j, s: (block(j, s), 0, 0))

    def outs(d):
        return pl.BlockSpec((n_kv, rb, d), lambda j, s: (0, block(j, s), 0))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(M // rb,),
            in_specs=[ins(hd), ins(hd_v)], out_specs=[outs(hd), outs(hd_v)]),
        out_shape=[jax.ShapeDtypeStruct((n_kv, M, hd), k.dtype),
                   jax.ShapeDtypeStruct((n_kv, M, hd_v), v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
    )(scalars, k, v)


def chunk_attention(q, k, v, start, *, softmax_scale: Optional[float] = None,
                    window: Optional[int] = None, first=0, sink=None):
    """q [1, C, nq, hd] at positions start .. start + C - 1 (start a
    traced scalar, or [1]); k [1, M, n_kv, hd] and v [1, M, n_kv, hd_v]
    (hd_v its own, or hd) holding the positions
    first .. first + M - 1 (`first` = 0: the whole cache; a traced
    scalar: the slice a window layer reads), every one of them up to
    start + C - 1 written.  Query i sees key position j iff j <= start +
    i and, under a `window` (static), j > start + i - window.  `sink`
    [nq]: a scalar a query head in the softmax's denominator.  Returns
    [1, C, nq, hd_v].  Raises ValueError on shapes outside `compatible`
    (`models/generation._attend_cached_chunk` takes those)."""
    C, nq, hd, M, n_kv, tr, kb, steps, pb, _ = check_shapes(
        q.shape, k.shape, jnp.shape(start), window=window, dtype=k.dtype,
        v_shape=v.shape, sink=sink is not None)
    hd_v = v.shape[-1]
    g = nq // n_kv
    R = g * C
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    start = jnp.reshape(jnp.asarray(start, jnp.int32), ())
    first = jnp.asarray(first, jnp.int32)
    b0, live = live_blocks(start, C, M, kb, window, first)
    scalars = jnp.stack([b0, live, start - first]).astype(jnp.int32)
    # one KV head's group of query heads as one tall operand, [n_kv, R,
    # hd]: row gi * C + c, or in the band form (c // pb) * g * pb +
    # gi * pb + c % pb, carries head (kvh, gi) at position start + c
    if pb:
        qh = q[0].reshape(C // pb, pb, n_kv, g, hd).transpose(2, 0, 3, 1, 4)
    else:
        qh = q[0].reshape(C, n_kv, g, hd).transpose(1, 2, 0, 3)
    qh = qh.reshape(n_kv, R, hd)
    if n_kv == 1:
        # one K/V head: the slab [M, 1, hd] IS head-major
        kh, vh = k[0].reshape(1, M, hd), v[0].reshape(1, M, hd_v)
    else:
        kh, vh = _relay_heads(scalars, k[0], v[0], kb)

    def key_block(h, r, j, s):
        lo, n = _tile_blocks(s, r, pb, window, kb)
        return h, lo + jnp.minimum(j, n - 1), 0

    def rows(d):
        return pl.BlockSpec((None, tr, d), lambda h, r, j, s: (h, r, 0))

    def keys(d):
        return pl.BlockSpec((None, kb, d), key_block)

    operands, in_specs = [qh.astype(k.dtype)], [rows(hd)]
    if sink is not None:
        # a row carries its head's sink, in q's row order; the band
        # form's tiles of one KV head all hold the same column of g x pb
        # rows: one block a head, fetched once
        sk = sink.astype(jnp.float32).reshape(n_kv, g, 1, 1)
        operands.append(jnp.broadcast_to(sk, (n_kv, g, pb or C, 1))
                        .reshape(n_kv, -1, 1))
        in_specs.append(pl.BlockSpec((None, tr, 1), lambda h, r, j, s:
                                     (h, 0, 0)) if pb else rows(1))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, C=C, tr=tr,
                          kb=kb, **({} if sink is None else {"sink": True}),
                          pb=pb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_kv, R // tr, steps),
            in_specs=in_specs + [keys(hd), keys(hd_v)],
            out_specs=rows(hd_v),
            scratch_shapes=[pltpu.VMEM((tr, 1), jnp.float32),
                            pltpu.VMEM((tr, 1), jnp.float32),
                            pltpu.VMEM((tr, hd_v), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n_kv, R, hd_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
    )(scalars, *operands, kh, vh)
    if pb:
        out = out.reshape(n_kv, C // pb, g, pb, hd_v).transpose(1, 3, 0, 2, 4)
    else:
        out = out.reshape(n_kv, g, C, hd_v).transpose(2, 0, 1, 3)
    return out.reshape(1, C, nq, hd_v)
