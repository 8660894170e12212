"""Learned sparse attention (DeepSeek Sparse Attention): a layer scores
every position a query may see with a small indexer, keeps the best `k`
and attends those alone.  What is here is the part that is nobody's
model: the scores, the EXACT selection, and the attention over a
selection that was gathered.  `models/deepseek_v32` composes them.

    I[t, s] = sum_j w[t, j] * ReLU(q[t, j] . key[s])     for s <= t
    S_t     = the min(k, t + 1) positions of largest I[t, s];
              equal scores: the lower position

**The scores** (`index_scores`) are float32 whatever the operands: the
products accumulate in float32, the sum over the indexer's heads is
float32, and the selection below compares float32.  The keys are walked
in blocks up to the last one any query sees, so that the [rows, heads,
keys] products of one block are all that ever exists beside the [rows,
positions] result.

**The selection is exact**, never `lax.approx_max_k`, a window or a
block standing in for it.  `select_mask` finds each row's k-th largest
score by BISECTION over the ordered integer image of the float32 score
(33 compare-and-count passes; alone on a v5e 0.67 ms for 512 rows of
33,792 scores, 6.7 for 1,024 in one piece, against 24.8 and 49.3 ms for
`lax.top_k`, which sorts; inside the chunk program the passes cross HBM,
8.2 ms a layer for 1,024 rows of the whole scratch, which is why the
caller hands in the visible part alone, `by_width`: my chip runs, PR 58)
and keeps what lies above it and, of the
scores that equal it, the lowest positions: what a chunk of prompt rows
takes, whose attention runs under the mask.  `select_indices` hands out
the positions themselves (`lax.top_k`, whose equal elements come lower
index first; 0.76 ms for 16 rows of 33,792): what a decode step takes,
which gathers the selected entries of each slot.  A score of -inf is a
position the query may not see and is never selected.

**The attention over a gathered selection** (`attend_selected`) is the
absorbed form of latent attention over [rows, k, stored] entries: the
arithmetic of `ops/pallas/paged_latent_attention.paged_latent_attention_xla`
over the entries it is given in place of a slot's whole context.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30
#: rows a bisection pass counts at once (512 rows of 33,792 float32 scores
#: are 69 MB: the module docstring has the readings)
_SELECT_ROWS = 512
_KEY_BLOCK = 1024


def index_scores(q, w, keys, qpos):
    """q [b, C, H, D] the indexer's queries and w [b, C, H] (float32)
    their head weights, of the tokens at positions qpos [b, C]; keys
    [b, M, D] the index keys of positions 0 .. M - 1.  -> I [b, C, M]
    float32, -inf where the key's position is past the query's."""
    b, C, H, D = q.shape
    M = keys.shape[1]
    kb = math.gcd(M, _KEY_BLOCK)
    w = w.astype(jnp.float32)

    def body(i, out):
        blk = lax.dynamic_slice_in_dim(keys, i * kb, kb, axis=1)
        s = jnp.einsum("bqhd,bkd->bqhk", q, blk.astype(q.dtype),
                       preferred_element_type=jnp.float32)
        s = jnp.einsum("bqhk,bqh->bqk", jax.nn.relu(s), w)
        kpos = i * kb + jnp.arange(kb, dtype=jnp.int32)
        s = jnp.where(kpos[None, None, :] <= qpos[:, :, None], s, -jnp.inf)
        return lax.dynamic_update_slice(out, s, (0, 0, i * kb))

    blocks = jnp.minimum(jnp.max(qpos) // kb + 1, M // kb)
    return lax.fori_loop(0, blocks, body,
                         jnp.full((b, C, M), -jnp.inf, jnp.float32))


def by_width(M: int, extent, fn):
    """fn(W) for the smallest of a few static widths W <= M that holds
    `extent` positions (a traced scalar: the last position any query sees,
    + 1): the scores and the selection's passes then move the part of a
    long scratch that is visible, not all of it (a chunk at position 8k of
    33,792 reads a quarter).  Quarters of M, up to whole key blocks; a
    short scratch (or one that does not divide so) is one width.  Every
    fn(W) returns the same shapes.  What a chunk launch takes (its
    selection 4.2 ms a launch against 41.2 over the whole scratch, the
    launch 86 against 123 ms: my chip runs, PR 58); a decode pass over 16
    slots gains nothing by it (the longest slot nearly always reaches the
    last quarter: 17.8 ms either way) and reads its whole table."""
    if M < 8 * _KEY_BLOCK or M % _KEY_BLOCK:
        return fn(M)
    n = M // _KEY_BLOCK
    widths = sorted({-(-n * i // 4) * _KEY_BLOCK for i in (1, 2, 3, 4)})
    which = sum((extent > w).astype(jnp.int32) for w in widths[:-1])
    return lax.switch(which, [lambda w=w: fn(w) for w in widths])


def _ordered(x):
    """float32 -> int32, monotone: x < y iff _ordered(x) < _ordered(y),
    with -0.0 made +0.0 first (they are EQUAL scores)."""
    i = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    return jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)


def _kth_largest(key, k: int):
    """key [R, M] int32 -> [R]: each row's k-th largest, by bisection
    (the largest v with k or more keys >= v)."""
    R = key.shape[0]

    def body(_, c):
        lo, hi = c
        # ceil((lo + hi) / 2) without leaving int32
        mid = (lo >> 1) + (hi >> 1) + ((lo & 1) | (hi & 1))
        enough = jnp.sum(key >= mid[:, None], axis=-1) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1)
    info = jnp.iinfo(jnp.int32)
    lo, _ = lax.fori_loop(0, 33, body, (jnp.full((R,), info.min, jnp.int32),
                                        jnp.full((R,), info.max, jnp.int32)))
    return lo


def _select_rows(scores, k: int):
    key = _ordered(scores)
    thr = _kth_largest(key, k)[:, None]
    above, equal = key > thr, key == thr
    need = k - jnp.sum(above, axis=-1, keepdims=True)
    keep = lax.cond(
        jnp.all(jnp.sum(equal, axis=-1, keepdims=True) == need),
        lambda: above | equal,
        # scores that EQUAL the k-th largest: the lowest positions of them
        lambda: above | (equal & (jnp.cumsum(equal, axis=-1) <= need)))
    return keep & (scores > -jnp.inf)


def select_mask(scores, k: int):
    """scores [..., M] float32 (-inf: a position the row may not see) ->
    bool [..., M]: the min(k, seen) positions of each row's largest
    scores; of equal scores the lower position."""
    M = scores.shape[-1]
    if k >= M:
        return scores > -jnp.inf
    flat = scores.reshape(-1, M)
    R = flat.shape[0]
    rb = math.gcd(R, _SELECT_ROWS)
    keep = lax.map(lambda s: _select_rows(s, k),
                   flat.reshape(R // rb, rb, M))
    return keep.reshape(scores.shape)


def select_indices(scores, k: int):
    """scores [R, M] float32 -> (positions [R, min(k, M)] int32, valid
    [R, .] bool): each row's selection as `select_mask` makes it, largest
    score first; `valid` is False where a row sees fewer positions."""
    vals, idx = lax.top_k(jnp.where(scores == 0, 0.0, scores),
                          min(k, scores.shape[-1]))
    return idx.astype(jnp.int32), vals > -jnp.inf


def attend_selected(q, entries, valid, *, value_dim: int,
                    softmax_scale: float):
    """Absorbed latent attention over a gathered selection: q [S, nh,
    stored] the absorbed queries, entries [S, K, stored] each row's
    selected cache entries, valid [S, K].  -> the latent output [S, nh,
    value_dim], float32 softmax as `paged_latent_attention_xla`'s."""
    c = entries.astype(jnp.float32)
    s = jnp.einsum("snd,skd->snk", q.astype(jnp.float32), c) * softmax_scale
    p = jax.nn.softmax(jnp.where(valid[:, None, :], s, NEG_INF), axis=-1)
    return jnp.einsum("snk,skd->snd", p, c[..., :value_dim]).astype(q.dtype)
