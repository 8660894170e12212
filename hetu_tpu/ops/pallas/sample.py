"""Fused lm_head + filter + sample Pallas kernel (the decode epilogue).

The serving sampler (serving/sampling.py) used to materialize the full
``[rows, vocab]`` logits in HBM — lm_head matmul write, then a
sort-based top-k/top-p filter chain reading and writing the whole vocab
plane several times, then the categorical draw.  For speculative
verification that plane is ``[slots, k+1, vocab]`` per step, and every
byte of it is consumed exactly once.  This kernel takes the LAST-LAYER
HIDDEN rows instead: one grid step per row does the lm_head slice
matmul in-VMEM, applies temperature / top-k / top-p exactly as
``serving/sampling.filtered_logits`` does, adds Gumbel noise from a
counter-based hash of the row's (seed, absolute_position) fold_in key,
and writes back ONE int32 token — the vocab plane never touches HBM.

Determinism contract: the per-row key WORDS are
``jax.random.key_data(fold_in(jax.random.key(seed), position))`` — the
exact derivation the engine always used — and `hash_uniform` /
`gumbel` below are pure jnp, shared verbatim by the XLA fallback in
``serving/sampling.sample_tokens``.  Kernel and fallback therefore draw
the SAME noise and pick the SAME token for the same (seed, position);
rows with temperature 0 take the plain argmax of the unfiltered logits
(greedy stays greedy).

Filter equivalence without a sort: top-k's kth value and the nucleus
cutoff are found by 32-step bisection over the MONOTONE uint32 image of
the f32 logits (sign-flip bitcast), which converges to the EXACT values
the sort-based filter reads off — including the duplicate-value
semantics (a kept value keeps all its duplicates).

Shape contract (drift-tested against `compatible`): hidden [R, H] with
H % 128 == 0, head w [H, V] with V % 128 == 0, and H*V small enough
that the head slice fits VMEM (realistic full vocabularies fall back to
the XLA path; the fused win targets the draft/verify models)."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.pallas import _interpret

#: the filter mask value (matches serving/sampling and generate())
_NEG = -1e30

#: head-slice VMEM budget: H * V f32 elements must fit comfortably
_MAX_W_ELEMS = 2 * 1024 * 1024


def hash_uniform(w0, w1, idx, lane: int = 0):
    """Counter-based uniform draws in (0, 1): a murmur3-style finalizer
    over (key word pair, counter index, stream lane).  Pure jnp — the
    SAME ops run in-kernel and in the XLA fallback, so both paths draw
    identical noise for identical (seed, position) keys.  `lane` picks
    an independent stream (the stochastic accept/resample draws in
    serving/spec_decode use lanes 1 and 2)."""
    w0 = w0.astype(jnp.uint32)
    w1 = w1.astype(jnp.uint32)
    x = w0 ^ (idx.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)) \
        ^ jnp.uint32((lane * 0x85EBCA77) & 0xFFFFFFFF)
    x = x + w1
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    # 24-bit mantissa uniform, centered off 0 and 1 (log-safe)
    return (x >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24)) \
        + jnp.float32(0.5 / (1 << 24))


def gumbel(w0, w1, idx, lane: int = 0):
    """Gumbel(0, 1) noise from `hash_uniform`; argmax(logits + gumbel)
    is an exact categorical draw."""
    return -jnp.log(-jnp.log(hash_uniform(w0, w1, idx, lane)))


def check_shapes(hidden_shape, w_shape) -> Tuple[int, int, int]:
    if len(hidden_shape) != 2 or len(w_shape) != 2:
        raise ValueError(f"expected hidden [R, H] and head [H, V], got "
                         f"{hidden_shape} / {w_shape}")
    R, H = hidden_shape
    H_w, V = w_shape
    if H_w != H:
        raise ValueError(f"hidden dim mismatch: hidden {H} vs head {H_w}")
    if H % 128 or V % 128:
        raise ValueError(f"hidden {H} and vocab {V} must be lane-aligned "
                         f"(% 128); the XLA sampler handles the rest")
    if H * V > _MAX_W_ELEMS:
        raise ValueError(f"head slice {H}x{V} exceeds the VMEM budget "
                         f"({_MAX_W_ELEMS} elems); the XLA sampler "
                         f"handles it")
    return R, H, V


def compatible(hidden_shape, w_shape) -> bool:
    try:
        check_shapes(hidden_shape, w_shape)
        return True
    except ValueError:
        return False


def _sort_key(x):
    """f32 -> uint32, strictly monotone (the radix-sort trick): bisection
    over this image terminates on EXACT logit values in 32 steps."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    flip = b.astype(jnp.uint32) ^ jnp.uint32(0x80000000)
    inv = (~b).astype(jnp.uint32)
    return jnp.where(b >= 0, flip, inv)


def _first_argmax(x, iota, V):
    """First index attaining the max — jnp.argmax's tie rule."""
    m = jnp.max(x)
    return jnp.min(jnp.where(x == m, iota, V)).astype(jnp.int32)


def _kth_largest_key(keys, k_eff):
    """Largest uint32 threshold t with count(keys >= t) >= k_eff — the
    key of the k-th largest logit (duplicates counted like the sort)."""
    lo = jnp.min(keys)
    hi = jnp.max(keys)

    def body(_, c):
        lo, hi = c
        mid = lo + ((hi - lo + jnp.uint32(1)) >> 1)
        ok = jnp.sum((keys >= mid).astype(jnp.int32)) >= k_eff
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - jnp.uint32(1))

    lo, _ = jax.lax.fori_loop(0, 32, body, (lo, hi))
    return lo


def _nucleus_key(keys, e, z, top_p):
    """Smallest uint32 threshold t whose strictly-greater kept mass
    sum(e[keys > t]) / z drops below top_p — the value-duplicate-exact
    form of filtered_logits' sorted-cumsum cutoff."""
    lo = jnp.min(keys)
    hi = jnp.max(keys)

    def body(_, c):
        lo, hi = c
        mid = lo + ((hi - lo) >> 1)
        s_gt = jnp.sum(jnp.where(keys > mid, e, 0.0))
        q = s_gt / z < top_p
        return jnp.where(q, lo, mid + jnp.uint32(1)), jnp.where(q, mid, hi)

    _, hi = jax.lax.fori_loop(0, 32, body, (lo, hi))
    return hi


def _sample_kernel(h_ref, w_ref, wd_ref, t_ref, k_ref, p_ref, o_ref, *, V):
    h = h_ref[...].astype(jnp.float32)                   # [1, H]
    w = w_ref[...].astype(jnp.float32)                   # [H, V]
    logits = jnp.dot(h, w, preferred_element_type=jnp.float32)  # [1, V]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, V), 1)
    greedy = _first_argmax(logits, iota, V)

    temp = t_ref[0, 0]
    safe_t = jnp.where(temp > 0, temp, 1.0)
    scaled = logits / safe_t
    # +0.0 canonicalizes -0.0 so the uint32 image is monotone over ==
    keys = _sort_key(scaled + 0.0)

    k_in = k_ref[0, 0]
    k_eff = jnp.minimum(jnp.where(k_in > 0, k_in, V), V)
    kth_key = _kth_largest_key(keys, k_eff)
    keep = keys >= kth_key
    filt = jnp.where(keep, scaled, _NEG)

    top_p = p_ref[0, 0]
    p_on = (top_p > 0.0) & (top_p < 1.0)
    m_f = jnp.max(scaled)                     # top-1 is always kept
    e = jnp.where(keep, jnp.exp(scaled - m_f), 0.0)
    z = jnp.sum(e)
    t_star = _nucleus_key(keys, e, z, top_p)
    filt = jnp.where(p_on & (keys < t_star), _NEG, filt)

    g = gumbel(wd_ref[0, 0], wd_ref[0, 1], iota)
    sampled = _first_argmax(filt + g, iota, V)
    o_ref[0, 0] = jnp.where(temp > 0, sampled, greedy).astype(jnp.int32)


def fused_sample(hidden, w, key_words, temps, top_ks, top_ps):
    """hidden [R, H] + head w [H, V] -> sampled tokens [R] int32 in one
    launch (no [R, V] logits in HBM).  key_words: [R, 2] uint32 — the
    raw data of each row's fold_in(key(seed), position) key; temps /
    top_ks / top_ps: [R] per-row sampling params (temp 0 = greedy row).
    Raises ValueError on shapes outside `compatible`."""
    R, H, V = check_shapes(hidden.shape, w.shape)
    if tuple(key_words.shape) != (R, 2):
        raise ValueError(f"key_words {key_words.shape} must be [R={R}, 2]")
    for name, arr in (("temps", temps), ("top_ks", top_ks),
                      ("top_ps", top_ps)):
        if tuple(arr.shape) != (R,):
            raise ValueError(f"{name} {arr.shape} must be [R={R}]")
    row = pl.BlockSpec((1, H), lambda r: (r, 0))
    head = pl.BlockSpec((H, V), lambda r: (0, 0))
    words = pl.BlockSpec((1, 2), lambda r: (r, 0))
    scalar = pl.BlockSpec((1, 1), lambda r: (r, 0))
    out = pl.pallas_call(
        functools.partial(_sample_kernel, V=V),
        grid=(R,),
        in_specs=[row, head, words, scalar, scalar, scalar],
        out_specs=pl.BlockSpec((1, 1), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(hidden, w, key_words.astype(jnp.uint32),
      temps.astype(jnp.float32).reshape(R, 1),
      top_ks.astype(jnp.int32).reshape(R, 1),
      top_ps.astype(jnp.float32).reshape(R, 1))
    return out[:, 0]
