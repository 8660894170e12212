"""In-graph token sampling for the serving decode program.

The engine's decode (and spec-decode verify) programs call
:func:`sample_tokens` INSIDE the jitted step: per-slot temperature /
top-k / top-p vectors ride in as program inputs, and the PRNG key for
each sampled token is derived in-graph as

    key = fold_in(jax.random.key(seed[slot]), token_position)

— a pure function of the request's seed and the token's absolute
sequence position.  That derivation is the determinism contract: the
same request replays to the same tokens across engine restarts, slot
assignments, batch compositions AND speculative re-verification (the
spec-decode path samples the token at position p with exactly the key
the sequential path would have used, which is what makes the
sample-then-match acceptance rule distribution-exact).

Greedy stays greedy bit-for-bit: rows with temperature == 0 take the
plain ``argmax`` of the unfiltered logits (the filters never touch
them), so a mixed batch of greedy and sampling requests decodes the
greedy rows exactly like the sampling-free program.  The engine only
builds the sampling program at all under ``HETU_TPU_SERVE_SAMPLE`` —
unset, the decode program is byte-identical to the pre-sampling engine
(registered identity contract, enforced by the flag-identity sweep).

Filter semantics match ``models/generation.generate``'s sampler (HF
conventions): top-k first, nucleus over the renormalized top-k
distribution, the max-probability token always survives.  One
descending full-vocab sort serves both filters per row.

The DRAW is Gumbel-argmax over a counter-based hash of the key's raw
words (`ops/pallas/sample.hash_uniform` — shared verbatim with the
fused sampling kernel, so the in-kernel epilogue and this XLA path pick
identical tokens for identical (seed, position) keys).  `sample_hidden`
is the fused entry: it takes last-layer HIDDEN rows plus the lm_head
slice and routes the whole matmul+filter+draw to the Pallas kernel when
enabled, never materializing the [rows, vocab] logits in HBM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from hetu_tpu.ops.pallas.sample import gumbel

#: the filter mask value (matches generate()'s sampler)
_NEG = -1e30


def slot_keys(seeds, positions):
    """[S] per-slot typed PRNG keys: ``fold_in(key(seed), position)``.
    ``positions`` are the ABSOLUTE sequence positions of the tokens
    being sampled (prompt + generated index), not engine step counts —
    the restart-determinism contract."""
    def one(seed, pos):
        return jax.random.fold_in(jax.random.key(seed), pos)
    return jax.vmap(one)(seeds.astype(jnp.uint32),
                         positions.astype(jnp.uint32))


def key_words(seeds, positions):
    """[S, 2] uint32 — the raw key data of `slot_keys`, the form the
    hash-based draw (and the fused sampling kernel) consumes."""
    return jax.random.key_data(slot_keys(seeds, positions)) \
        .astype(jnp.uint32)


def filtered_logits(logits, temps, top_ks, top_ps):
    """Apply per-row temperature + top-k + top-p filtering.

    logits: [S, V] f32; temps: [S] f32 (0 = greedy row — returned
    unfiltered, the caller argmaxes it); top_ks: [S] int32 (0 =
    disabled); top_ps: [S] f32 (0 or >= 1 = disabled).  Returns the
    filtered, temperature-scaled logits [S, V]."""
    V = logits.shape[-1]
    temps = temps.astype(jnp.float32)
    safe_t = jnp.where(temps > 0, temps, 1.0)
    scaled = logits.astype(jnp.float32) / safe_t[:, None]

    # ONE descending sort per row serves both filters
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]

    # top-k: mask everything below the per-row kth value (k=0 -> V)
    k_eff = jnp.where(top_ks > 0, top_ks, V).astype(jnp.int32)
    kth = jnp.take_along_axis(
        desc, jnp.clip(k_eff[:, None] - 1, 0, V - 1), axis=-1)
    out = jnp.where(scaled < kth, _NEG, scaled)

    # nucleus over the renormalized top-k distribution (HF semantics):
    # the filtered descending view is the top-k prefix of `desc`
    p_on = (top_ps > 0.0) & (top_ps < 1.0)
    desc_f = jnp.where(jnp.arange(V)[None, :] < k_eff[:, None], desc, _NEG)
    probs = jax.nn.softmax(desc_f, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = cum - probs < top_ps[:, None]      # mass BEFORE this token
    cutoff = jnp.min(jnp.where(keep, desc_f, jnp.inf), axis=-1,
                     keepdims=True)
    out = jnp.where(p_on[:, None] & (out < cutoff), _NEG, out)
    return out


def sample_tokens(logits, seeds, positions, temps, top_ks, top_ps):
    """Sample (or argmax) one token per slot, in-graph.

    logits: [S, V]; seeds/positions/top_ks: [S] int; temps/top_ps: [S]
    f32.  ``positions`` are the sampled tokens' absolute sequence
    positions (the key-derivation input).  Rows with temperature 0 take
    ``argmax`` of the UNFILTERED logits — exactly the greedy program's
    token.  Returns [S] int32."""
    V = logits.shape[-1]
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    filt = filtered_logits(logits, temps, top_ks, top_ps)
    words = key_words(seeds, positions)
    idx = jnp.arange(V, dtype=jnp.uint32)[None, :]
    g = gumbel(words[:, 0:1], words[:, 1:2], idx)
    sampled = jnp.argmax(filt + g, axis=-1)
    return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy_tok)


def sample_token_grid(logits, seeds, positions, temps, top_ks, top_ps):
    """The spec-decode form: sample a [S, C] grid of tokens, one per
    verify position.  logits: [S, C, V]; positions: [S, C] absolute
    sequence positions of the tokens being sampled; per-slot params
    broadcast over C.  Each (slot, position) uses the same key the
    sequential path would — acceptance by sample-then-match is then the
    exact rejection rule for a deterministic drafter
    (serving/spec_decode.py)."""
    S, C, V = logits.shape
    flat = logits.reshape(S * C, V)
    rep = lambda x: jnp.repeat(x, C)  # noqa: E731 — [S] -> [S*C]
    toks = sample_tokens(flat, rep(seeds), positions.reshape(-1),
                         rep(temps), rep(top_ks), rep(top_ps))
    return toks.reshape(S, C)


def sample_hidden(hidden, w, seeds, positions, temps, top_ks, top_ps):
    """The fused last-layer epilogue: last-layer hidden rows [R, H] +
    lm_head slice w [H, V] -> one token per row, WITHOUT materializing
    the [R, V] logits in HBM when the Pallas `sample` kernel routes
    (ops/pallas/sample.py).  The XLA fallback computes the same math
    (matmul -> filtered_logits -> hash-Gumbel argmax), so the routed
    and unrouted paths pick identical tokens — the flag only moves
    bytes, never the distribution."""
    from hetu_tpu.ops import pallas as _pl
    from hetu_tpu.ops.pallas import sample as _ps
    if _pl.resolve_route("sample", _ps.check_shapes, hidden.shape, w.shape):
        words = key_words(seeds, positions)
        with jax.named_scope("pallas_fused_sample"):
            return _ps.fused_sample(hidden, w, words,
                                    temps.astype(jnp.float32),
                                    top_ks.astype(jnp.int32),
                                    top_ps.astype(jnp.float32))
    logits = hidden.astype(jnp.float32) @ w.astype(jnp.float32)
    return sample_tokens(logits, seeds, positions, temps, top_ks, top_ps)


def sample_hidden_grid(hidden, w, seeds, positions, temps, top_ks,
                       top_ps):
    """`sample_hidden` over the spec-decode verify grid: hidden
    [S, C, H], positions [S, C]; per-slot params broadcast over C.
    Returns [S, C] int32."""
    S, C, H = hidden.shape
    rep = lambda x: jnp.repeat(x, C)  # noqa: E731 — [S] -> [S*C]
    toks = sample_hidden(hidden.reshape(S * C, H), w, rep(seeds),
                         positions.reshape(-1), rep(temps), rep(top_ks),
                         rep(top_ps))
    return toks.reshape(S, C)
