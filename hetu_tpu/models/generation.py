"""Autoregressive generation with a KV cache.

Rebuild of the reference's model-generation surface (reference:
python/hetu/models/utils/model_utils.py PreTrainedModel generate path; the
reference is training-first and so are we — this is the functional decode
loop for eval/demo, TPU-shaped: static max length, lax.scan decode, cache as
a pytree carried through the scan).

Works with both model families' stacked-scan parameter layouts: the
per-layer KV caches are stacked [L, b, max_len, n_kv, hd] and the decode
step scans layers with the cache rows as per-layer xs/ys.  prefill and
decode_step dispatch on the family (LLaMA: RMSNorm/rotary/fused-GQA QKV;
GPT: LayerNorm/wpe/biased fused QKV).

Serving-facing surface (hetu_tpu/serving, docs/serving.md): the decode
step also comes in a slot-masked form — `decode_step_slots` takes a
PER-SLOT position vector (each batch row is an independent sequence at
its own depth) and returns this step's per-layer K/V so a paged cache
can scatter them into its pool — and `extend_cache` is the multi-token
(chunked-prefill) sibling that advances a cache by a whole token block.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from hetu_tpu import ops


def _attend_cached(q, ck, cv, pos, scale):
    """q: [b, 1, nq, hd]; ck/cv: [b, M, n_kv, hd]; attend over
    cache[:pos+1] (pos scalar, or [b] for per-slot depths).

    GQA attends in the GROUPED layout — q reshaped [b, C, n_kv, g, hd]
    and contracted against the cache's n_kv heads directly — instead of
    materializing a group-repeated copy of the whole cache every step
    (the old jnp.repeat path copied M*n_kv*hd*(g-1) elements per layer
    per token).  Head ordering matches the fused-QKV layout (q head
    j = kv head j // g): the q·k scores are bit-identical to the repeat
    path and the p·v output matches to float32-ulp (the weighted sum
    over the cache axis reassociates without the materialized copy) —
    regression-tested in tests/test_generation.py.

    This is exactly the single-query case of `_attend_cached_chunk`
    (one query at offset 0 from `pos`) — ONE implementation of the
    grouped contraction + causal mask, so decode and chunked prefill
    can never drift numerically."""
    return _attend_cached_chunk(q, ck, cv, pos, scale)


def _attend_cached_chunk(q, ck, cv, start, scale):
    """Multi-query cached attention for chunked prefill.  q: [b, C, nq,
    hd] sits at absolute positions start..start+C-1 (start scalar or
    [b]); key position k is visible to query i iff k <= start + i
    (causal within the chunk, full visibility of the already-cached
    prefix).  Same grouped-GQA contraction as `_attend_cached`."""
    b, M, n_kv, hd = ck.shape
    C, nq = q.shape[1], q.shape[2]
    group = nq // n_kv
    qg = q.reshape(b, C, n_kv, group, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   ck.astype(jnp.float32)) * scale
    start = jnp.asarray(start)
    if start.ndim == 0:
        start = start[None]
    qpos = start[:, None] + jnp.arange(C)[None, :]            # [b, C]
    mask = jnp.arange(M)[None, None, :] <= qpos[..., None]    # [b, C, M]
    s = jnp.where(mask[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, cv.astype(jnp.float32))
    return out.reshape(b, C, nq, hd).astype(q.dtype)


def _is_gpt(model) -> bool:
    return hasattr(model.model, "wte")


def lm_head_weight(model, params):
    """The lm_head slice as a [hidden, vocab] matrix — the weight
    operand of the fused sampling epilogue (serving/sampling.
    sample_hidden).  Matches `model.logits`: tied embeddings transpose
    the token-embedding table, untied models carry an explicit head."""
    if model.config.tie_word_embeddings:
        key = "wte" if _is_gpt(model) else "embed"
        return params["model"][key]["weight"].T
    return params["lm_head"]


def _check_context_length(config, max_len: int):
    """Past the trained context, GPT's jnp.take on wpe (and LLaMA's RoPE
    table lookup) would silently clamp to the last position — fail loudly
    instead.  One guard shared by every cache-building entry point."""
    if max_len > config.max_position_embeddings:
        raise ValueError(
            f"cache length {max_len} exceeds max_position_embeddings "
            f"{config.max_position_embeddings}")


def init_cache(model, batch: int, max_len: int):
    """Empty KV cache [L, b, max_len, n_kv, hd] (n_kv = heads for GPT)."""
    c = model.config
    _check_context_length(c, max_len)
    n_kv = getattr(c, "num_key_value_heads", c.num_attention_heads)
    shape = (c.num_hidden_layers, batch, max_len, n_kv, c.head_dim)
    return (jnp.zeros(shape, c.compute_dtype), jnp.zeros(shape, c.compute_dtype))


def _gpt_embed(model, mp, ids, pos_ids):
    x = model.model.wte(mp["wte"], ids) \
        + jnp.take(mp["wpe"], pos_ids, axis=0)
    return x.astype(model.config.compute_dtype)


def _prefill_gpt(model, params, input_ids, max_len: int):
    mp = params["model"]
    pos = jnp.arange(input_ids.shape[1], dtype=jnp.int32)
    x = _gpt_embed(model, mp, input_ids, pos)
    block = model.model.block

    def body(h, lp):
        out = block(lp, h)
        hn = block.ln1(lp["ln1"], h)
        # contract only the K/V planes for the cache (the block forward
        # above already computed full QKV for its own attention)
        kv = jnp.einsum("bsh,hngd->bsngd", hn,
                        lp["attn"]["wqkv"][:, :, 1:3, :].astype(h.dtype)) \
            + lp["attn"]["bqkv"][:, 1:3, :].astype(h.dtype)
        return out, (kv[..., 0, :], kv[..., 1, :])

    x, (ks, vs) = lax.scan(body, x, mp["blocks"])
    hidden = model.model.final_ln(mp["final_ln"], x)
    logits = model.logits(params, hidden)[:, -1, :]
    pad = max_len - input_ids.shape[1]
    cache_k = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    cache_v = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    return logits, (cache_k, cache_v)


def _cache_write_token(ck, k, positions, uniform: bool):
    """Write one token's K (or V) [b, 1, n_kv, hd] into a cache row
    [b, M, n_kv, hd] at `positions`.  Uniform (scalar) positions keep
    the old contiguous dynamic_update_slice lowering — the generate()
    hot loop must not pay batched-scatter cost for a broadcast index —
    per-slot vectors scatter per row (the serving form)."""
    if uniform:
        return lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                        (0, positions, 0, 0))
    b = ck.shape[0]
    return ck.at[jnp.arange(b), positions].set(k[:, 0].astype(ck.dtype))


def _decode_step_slots_gpt(model, params, tokens, cache, positions):
    c = model.config
    mp = params["model"]
    b = tokens.shape[0]
    uniform = jnp.ndim(positions) == 0
    pos_ids = (jnp.broadcast_to(positions, (1,)) if uniform
               else positions[:, None])
    x = _gpt_embed(model, mp, tokens[:, None], pos_ids)
    block = model.model.block
    att = block.attn
    nh, hd = c.num_attention_heads, c.head_dim
    scale = hd ** -0.5
    cache_k, cache_v = cache

    def body(h, xs):
        lp, ck, cv = xs
        hn = block.ln1(lp["ln1"], h)
        qkv = jnp.einsum("bsh,hngd->bsngd", hn,
                         lp["attn"]["wqkv"].astype(h.dtype)) \
            + lp["attn"]["bqkv"].astype(h.dtype)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        kt, vt = k[:, 0], v[:, 0]                       # [b, n_kv, hd]
        ck = _cache_write_token(ck, k, positions, uniform)
        cv = _cache_write_token(cv, v, positions, uniform)
        attn = _attend_cached(q, ck, cv, positions, scale)
        h = h + att.o_proj(lp["attn"]["o_proj"],
                           attn.reshape(b, 1, nh * hd))
        h = h + block.mlp(lp["mlp"], block.ln2(lp["ln2"], h))
        return h, (ck, cv, kt, vt)

    x, (new_k, new_v, k_toks, v_toks) = lax.scan(
        body, x, (mp["blocks"], cache_k, cache_v))
    hidden = model.model.final_ln(mp["final_ln"], x)
    logits = model.logits(params, hidden)[:, 0, :]
    return logits, (new_k, new_v), (k_toks, v_toks)


def prefill(model, params, input_ids, max_len: int):
    """Run the full forward over the prompt, returning (last_logits, cache).
    Uses the model's training forward (flash path) plus a kv-extraction pass.
    """
    c = model.config
    if not c.use_scan:
        raise ValueError("generation requires use_scan=True (stacked layer "
                         "params); rebuild the model with use_scan=True")
    _check_context_length(c, max_len)
    if _is_gpt(model):
        return _prefill_gpt(model, params, input_ids, max_len)
    b, plen = input_ids.shape
    # extract per-layer k/v by re-running the projections layer by layer —
    # one pass via the scan collecting (k, v) as ys
    mp = params["model"]
    x = model.model.embed(mp["embed"], input_ids).astype(c.compute_dtype)
    cos, sin = ops.build_rope_cache(c.max_position_embeddings, c.head_dim,
                                    c.rope_theta)
    block = model.model.layers.block

    att = block.attn

    def body(carry, layer_params):
        h = carry
        out, _aux = block(layer_params, h, cos=cos, sin=sin)
        # recompute only the K/V planes of the fused projection for the cache
        # (the q-head planes are sliced out of the weight before the einsum)
        w_kv = layer_params["attn"]["wqkv"][:, :, att.group: att.group + 2, :]
        kv = jnp.einsum("bsh,hkgd->bskgd",
                        block.input_norm(layer_params["input_norm"], h),
                        w_kv.astype(h.dtype))
        k = ops.apply_rotary(kv[..., 0, :], cos, sin, None)
        v = kv[..., 1, :]
        return out, (k, v)

    x, (ks, vs) = lax.scan(body, x, mp["layers"]["layers"])
    hidden = model.model.final_norm(mp["final_norm"], x)
    logits = model.logits(params, hidden)[:, -1, :]
    pad = max_len - plen
    cache_k = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    cache_v = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    return logits, (cache_k, cache_v)


def decode_step_slots(model, params, tokens, cache, positions):
    """One token step with PER-SLOT positions (the serving engine's form:
    each batch row is an independent sequence at its own depth).

    tokens: [b] int32; positions: [b] int32 (this token's absolute
    position per slot) — or a scalar, which keeps the old contiguous
    dynamic_update_slice cache lowering for the uniform-position
    generate() hot loop.  Returns (logits [b, vocab], new_cache,
    (k_toks, v_toks)) where k_toks/v_toks are THIS step's per-layer K/V
    [L, b, n_kv, hd] — a paged cache scatters them into its pool instead
    of carrying the dense cache."""
    c = model.config
    if not c.use_scan:
        raise ValueError("generation requires use_scan=True (stacked layer "
                         "params)")
    if _is_gpt(model):
        return _decode_step_slots_gpt(model, params, tokens, cache, positions)
    mp = params["model"]
    b = tokens.shape[0]
    uniform = jnp.ndim(positions) == 0
    x = model.model.embed(mp["embed"], tokens[:, None]).astype(c.compute_dtype)
    cos, sin = ops.build_rope_cache(c.max_position_embeddings, c.head_dim,
                                    c.rope_theta)
    block = model.model.layers.block
    att = block.attn
    scale = c.head_dim ** -0.5
    pos_ids = (jnp.broadcast_to(positions, (b, 1)) if uniform
               else positions[:, None])
    cache_k, cache_v = cache

    def body(carry, xs):
        h = carry
        layer_params, ck, cv = xs
        hn = block.input_norm(layer_params["input_norm"], h)
        qkv = jnp.einsum("bsh,hkgd->bskgd", hn,
                         layer_params["attn"]["wqkv"].astype(h.dtype))
        q = qkv[..., : att.group, :].reshape(b, 1, att.n_q, c.head_dim)
        k = qkv[..., att.group, :]
        v = qkv[..., att.group + 1, :]
        q, k = ops.apply_rotary_qk(q, k, cos, sin, pos_ids)
        kt, vt = k[:, 0], v[:, 0]                       # [b, n_kv, hd]
        ck = _cache_write_token(ck, k, positions, uniform)
        cv = _cache_write_token(cv, v, positions, uniform)
        attn = _attend_cached(q, ck, cv, positions, scale)
        h = h + att.o_proj(layer_params["attn"]["o_proj"],
                           attn.reshape(b, 1, att.n_q * c.head_dim))
        mlp_out = block.mlp(layer_params["mlp"],
                            block.post_norm(layer_params["post_norm"], h))
        if isinstance(mlp_out, tuple):  # MoE
            mlp_out = mlp_out[0]
        h = h + mlp_out
        return h, (ck, cv, kt, vt)

    x, (new_k, new_v, k_toks, v_toks) = lax.scan(
        body, x, (mp["layers"]["layers"], cache_k, cache_v))
    hidden = model.model.final_norm(mp["final_norm"], x)
    logits = model.logits(params, hidden)[:, 0, :]
    return logits, (new_k, new_v), (k_toks, v_toks)


def decode_step(model, params, token, cache, pos):
    """One token step. token: [b] int32; pos: scalar current position.
    Returns (logits [b, vocab], new_cache).  Delegates to the slot-masked
    form; the scalar position keeps the contiguous cache-update
    lowering."""
    logits, new_cache, _ = decode_step_slots(
        model, params, token, cache, jnp.asarray(pos, jnp.int32))
    return logits, new_cache


def _quantize_head_vectors(t, bits: int):
    """Quantize [..., hd] head-vectors for a paged pool: int8 through
    the SAME blockwise primitives the gather path uses (comm/compress ->
    the fused Pallas quant kernel when routed), int4 through the shared
    `ops/quantization` nibble packer — so pool contents are
    bit-identical across the decode programs.  Returns (payload
    [..., hd or hd//2], scales [...])."""
    hd = t.shape[-1]
    x32 = t.astype(jnp.float32)
    if bits == 4:
        from hetu_tpu.ops.quantization import quantize_int4
        q, s = quantize_int4(x32, block_size=hd)
        q = q.reshape(t.shape[:-1] + (hd // 2,))
    else:
        from hetu_tpu.comm.compress import quantize_blockwise
        q, s = quantize_blockwise(x32, block_size=hd)
        q = q.reshape(t.shape)
    return q, s.reshape(t.shape[:-1])


def _paged_put(pool, scale, page, off, t, layer, base, bits):
    """Scatter head-vectors `t` at (page, off) of ONE layer into the
    carried pool (`_scan_layers_paged`): the payload into the flat page
    array [L * P, ps, n_kv, hd] at page `base + page` (base = l * P,
    added after any null-page redirect), and for quantized pages
    (`scale` not None: int8, or int4 nibble payloads with ``bits=4``)
    the per-head-vector f32 scale into the layer's plane of
    [L, P, ps, n_kv].  Returns (pool, scale)."""
    if scale is None:
        return pool.at[base + page, off].set(t.astype(pool.dtype)), None
    q, s = _quantize_head_vectors(t, bits)
    return pool.at[base + page, off].set(q.astype(pool.dtype)), \
        scale.at[layer, page, off].set(s)


def _paged_write(pool, scale, table, positions, t, layer, base, bits):
    """Write one token's K (or V) [S, n_kv, hd] at each slot's
    (table[pos // ps], pos % ps).  Inactive slots' tables point at the
    null page (the layer's id 0) — their write lands there harmlessly
    (serving/kv_pool.py)."""
    ps = pool.shape[1]
    page = table[jnp.arange(positions.shape[0]), positions // ps]
    return _paged_put(pool, scale, page, positions % ps, t, layer, base,
                      bits)


def _token_block_pages(table, positions, C, ps):
    """Page ids + offsets for a C-token block at positions[s] + i.
    Block positions past the table's reach land in the null page (id 0)
    — the same redirect `serving/kv_pool.write_tokens` applies — and
    inactive slots' zeroed table rows point there already."""
    S = positions.shape[0]
    mp = table.shape[1]
    pos = positions[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    pidx = pos // ps
    safe = pidx < mp
    page = jnp.where(
        safe, table[jnp.arange(S)[:, None], jnp.clip(pidx, 0, mp - 1)], 0)
    return page, pos % ps


def _paged_write_tokens(pool, scale, table, positions, t, layer, base,
                        bits):
    """Write a C-token block's K (or V) [S, C, n_kv, hd] — the
    verify-step sibling of `_paged_write`."""
    page, off = _token_block_pages(table, positions, t.shape[1],
                                   pool.shape[1])
    return _paged_put(pool, scale, page, off, t, layer, base, bits)


def _scan_layers_paged(layer, x, layer_params, pools):
    """Scan `layer` over the stacked layers with the KV pool as a loop
    CARRY that is updated in place — never an xs or a ys of the scan,
    which would slice every layer's slab out and stack it back into a
    fresh buffer (three full-pool copies a step: PERF.md, PR 25).

    pools: (k_pool, v_pool, k_scale, v_scale), each [L, P, ...], the
    scales None for exact pages.  The scan carries the page arrays as
    their flat views [L * P, ps, n_kv, hd] (a bitcast): layer l's page
    p is page l * P + p, so with `table + l * P` as its page table the
    same scatter and the same kernel walk the same bytes, and l * P is
    the layer's null page.  The scale planes stay [L, P, ps, n_kv]: the
    kernel reads a page's scales as a block of a page-major array whose
    rows are padded to 128 lanes, which is not how the planes are
    stored, so it is handed ONE layer's plane, `scale[l]`, to read by
    the engine's own page ids (`scale_table`), and what is converted
    for it is P pages a layer, never L * P.

    layer(h, layer_params, pools, l, base) -> (h, pools), base = l * P.
    Returns (x, pools): the arrays that came in (no None), in their
    [L, P, ...] shapes and, when the caller donated them, their
    buffers."""
    k_pool, v_pool, k_scale, v_scale = pools
    L, P = k_pool.shape[:2]

    def body(carry, xs):
        h, pools = carry
        lp, l = xs
        h, pools = layer(h, lp, pools, l, l * P)
        return (h, tuple(pools)), None

    flat = (L * P,) + k_pool.shape[2:]
    (x, (k_flat, v_flat, k_scale, v_scale)), _ = lax.scan(
        body,
        (x, (k_pool.reshape(flat), v_pool.reshape(flat), k_scale, v_scale)),
        (layer_params, jnp.arange(L, dtype=jnp.int32)))
    pools = (k_flat.reshape(k_pool.shape), v_flat.reshape(v_pool.shape),
             k_scale, v_scale)
    return x, tuple(p for p in pools if p is not None)


def _decode_step_paged_gpt(model, params, tokens, k_pool, v_pool, table,
                           positions, k_scale, v_scale, kv_quant):
    from hetu_tpu.ops.pallas.paged_attention import paged_attention
    c = model.config
    mp_ = params["model"]
    b = tokens.shape[0]
    quant = k_scale is not None
    bits = 4 if kv_quant == "int4" else 8
    x = _gpt_embed(model, mp_, tokens[:, None], positions[:, None])
    block = model.model.block
    att = block.attn
    nh, hd = c.num_attention_heads, c.head_dim
    scale = hd ** -0.5

    def layer(h, lp, pools, l, base):
        kp, vp, ksc, vsc = pools
        hn = block.ln1(lp["ln1"], h)
        qkv = jnp.einsum("bsh,hngd->bsngd", hn,
                         lp["attn"]["wqkv"].astype(h.dtype)) \
            + lp["attn"]["bqkv"].astype(h.dtype)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        with jax.named_scope("kv_write"):
            kp, ksc = _paged_write(kp, ksc, table, positions, k[:, 0],
                                   l, base, bits)
            vp, vsc = _paged_write(vp, vsc, table, positions, v[:, 0],
                                   l, base, bits)
        with jax.named_scope("pallas_paged_attention"):
            ksl, vsl = (ksc[l], vsc[l]) if quant else (None, None)
            attn = paged_attention(q[:, 0], kp, vp, table + base, positions,
                                   softmax_scale=scale,
                                   k_scale=ksl, v_scale=vsl,
                                   quant=kv_quant, scale_table=table)
        h = h + att.o_proj(lp["attn"]["o_proj"],
                           attn.reshape(b, 1, nh * hd))
        h = h + block.mlp(lp["mlp"], block.ln2(lp["ln2"], h))
        return h, (kp, vp, ksc, vsc)

    x, pools = _scan_layers_paged(
        layer, x, mp_["blocks"], (k_pool, v_pool, k_scale, v_scale))
    hidden = model.model.final_ln(mp_["final_ln"], x)
    logits = model.logits(params, hidden)[:, 0, :]
    return (logits,) + pools


def decode_step_paged(model, params, tokens, k_pool, v_pool, table,
                      positions, *, k_scale=None, v_scale=None,
                      kv_quant=None):
    """One decode step attending DIRECTLY over a paged KV pool — the
    gather-free form of `decode_step_slots` (ops/pallas/paged_attention;
    serving engine's HETU_TPU_PALLAS decode program).

    k_pool/v_pool: [L, P, page_size, n_kv, hd] (page 0 = the null page);
    table: [S, max_pages] int32; positions: [S] int32 — slot s's current
    token sits at positions[s] and attends over everything at or before
    it.  This step's K/V are scattered into each slot's page BEFORE the
    kernel runs (so the token sees itself, exactly like the dense path's
    write-then-attend), and the updated pools are returned:
    (logits [S, vocab], new_k_pool, new_v_pool).  The pools are a carry
    of the layer loop, written IN PLACE (`_scan_layers_paged`): a caller
    that donates them (the engine does) gets its own buffers back, with
    no second pool among the program's temporaries.

    int8 pools (``HETU_TPU_KV_QUANT=int8``) pass their per-head-vector
    f32 scales [L, P, page_size, n_kv] as k_scale/v_scale: the token
    write quantizes through the shared blockwise primitives and the
    kernel dequantizes pages in-VMEM; the return gains
    (..., new_k_scale, new_v_scale).  int4 pools
    (``HETU_TPU_KV_QUANT=int4``) additionally pass ``kv_quant="int4"``
    — uint8 nibble payloads of head dim hd//2, the
    `ops/quantization.pack_nibbles` storage layout."""
    c = model.config
    if not c.use_scan:
        raise ValueError("generation requires use_scan=True (stacked layer "
                         "params)")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    quant = k_scale is not None
    if kv_quant is None:
        kv_quant = "int8" if quant else None
    bits = 4 if kv_quant == "int4" else 8
    positions = positions.astype(jnp.int32)
    table = table.astype(jnp.int32)
    if _is_gpt(model):
        return _decode_step_paged_gpt(model, params, tokens, k_pool,
                                      v_pool, table, positions,
                                      k_scale, v_scale, kv_quant)
    from hetu_tpu.ops.pallas.paged_attention import paged_attention
    mp_ = params["model"]
    b = tokens.shape[0]
    # the scopes the training programs carry, so that a device trace of
    # the serving programs is summed under the same names (obs.scope_map)
    with jax.named_scope("embed"):
        x = model.model.embed(mp_["embed"], tokens[:, None]).astype(
            c.compute_dtype)
    cos, sin = ops.build_rope_cache(c.max_position_embeddings, c.head_dim,
                                    c.rope_theta)
    block = model.model.layers.block
    att = block.attn
    scale = c.head_dim ** -0.5

    def layer(h, layer_params, pools, l, base):
        kp, vp, ksc, vsc = pools
        with jax.named_scope("attn"):
            hn = block.input_norm(layer_params["input_norm"], h)
            qkv = jnp.einsum("bsh,hkgd->bskgd", hn,
                             layer_params["attn"]["wqkv"].astype(h.dtype))
            q = qkv[..., : att.group, :].reshape(b, 1, att.n_q,
                                                 c.head_dim)
            k = qkv[..., att.group, :]
            v = qkv[..., att.group + 1, :]
            q, k = ops.apply_rotary_qk(q, k, cos, sin, positions[:, None])
            with jax.named_scope("kv_write"):
                kp, ksc = _paged_write(kp, ksc, table, positions, k[:, 0],
                                       l, base, bits)
                vp, vsc = _paged_write(vp, vsc, table, positions, v[:, 0],
                                       l, base, bits)
            with jax.named_scope("pallas_paged_attention"):
                ksl, vsl = (ksc[l], vsc[l]) if quant else (None, None)
                attn = paged_attention(
                    q[:, 0], kp, vp, table + base, positions,
                    softmax_scale=scale, k_scale=ksl, v_scale=vsl,
                    quant=kv_quant, scale_table=table)
            h = h + att.o_proj(layer_params["attn"]["o_proj"],
                               attn.reshape(b, 1, att.n_q * c.head_dim))
        with jax.named_scope("mlp"):
            mlp_out = block.mlp(
                layer_params["mlp"],
                block.post_norm(layer_params["post_norm"], h))
            if isinstance(mlp_out, tuple):  # MoE
                mlp_out = mlp_out[0]
            h = h + mlp_out
        return h, (kp, vp, ksc, vsc)

    with jax.named_scope("layer"):
        x, pools = _scan_layers_paged(
            layer, x, mp_["layers"]["layers"],
            (k_pool, v_pool, k_scale, v_scale))
    hidden = model.model.final_norm(mp_["final_norm"], x)
    logits = model.logits(params, hidden)[:, 0, :]
    return (logits,) + pools


# ---------------------------------------------------------------------------
# The serving programs written against the model's attention-cache contract
# ---------------------------------------------------------------------------
# A model that says what a token stores (`cache_contract()`:
# models/cache_contract.py) and how a query attends it brings no copy of
# the paged decode, chunk or page-write programs: the three below take
# from the model
#
#   embed_tokens(params, ids)            -> x [b, s, hidden]
#   rope_tables(max_len)                 -> whatever its `project` takes
#   serving_layers(params)               -> [(block, layer params)]
#       in the pool's layer order, run one after the other
#   block.input_norm / post_norm / mlp_stats(params, x) -> (y, stats)
#   block.attn.project(p, hn, rope, pos_ids) -> (q, entries)
#       HOW A TOKEN'S CACHE ENTRY IS MADE: one array per pool array,
#       [b, s, *stored shape]
#   block.attn.attend_paged(p, q, pools, table, positions)
#   block.attn.attend_dense(p, q, caches, start)
#       HOW A QUERY ATTENDS IT, over pages and over a dense per-slot cache
#   block.attn.output(p, attn), final_hidden(params, x), logits(params, h)
#
#   STATS, zero_stats(), add_stats(a, b)
#       `stats` is a small int32 vector a layer counts of itself (an
#       expert layer's assignments: models/kimi_k2.MOE_STATS); `STATS`
#       names each entry's counter and says whether executions add up or
#       take the maximum (empty for a model that counts nothing); the
#       programs take the running vector in and hand it on, so the
#       engine reads it with the tokens and nowhere else.
# models/llama and models/gpt keep the bodies above: their compiled
# programs are held byte-for-byte (PR 27), and moving them onto the
# contract is ROADMAP's.

def _contract_layers(model, params, x, state, stats, layer):
    """Walk `serving_layers` in the pool's layer order.
    layer(block, h, lp, state, l) -> (h, state, stats of the layer);
    `state` is the cache (pages or dense), handed from layer to layer
    and updated in place (never an xs -> ys of a scan: PR 25)."""
    with jax.named_scope("layer"):
        for l, (block, lp) in enumerate(model.serving_layers(params)):
            x, state, st = layer(block, x, lp, state, jnp.int32(l))
            stats = model.add_stats(stats, st)
    return x, state, stats


def decode_step_paged_contract(model, params, tokens, pools, table,
                               positions, stats):
    """`decode_step_paged` for a model with a cache contract.  pools: a
    tuple of page arrays [L, P, page_size, *stored shape], one per array
    of the contract; the rest as `decode_step_paged`.  This step's
    entries are scattered into each slot's page BEFORE the query attends
    (write-then-attend), the pools are carried as their flat views
    [L * P, ...] and written in place (`_scan_layers_paged` says why).
    Returns (logits [S, vocab], pools, stats)."""
    positions = positions.astype(jnp.int32)
    table = table.astype(jnp.int32)
    L, P, ps = pools[0].shape[:3]
    rope = model.rope_tables(table.shape[1] * ps)
    with jax.named_scope("embed"):
        x = model.embed_tokens(params, tokens[:, None])

    def layer(block, h, lp, flat, l):
        base = l * P
        with jax.named_scope("attn"):
            hn = block.input_norm(lp["input_norm"], h)
            q, entries = block.attn.project(lp["attn"], hn, rope,
                                            positions[:, None])
            with jax.named_scope("kv_write"):
                flat = tuple(
                    _paged_write(pool, None, table, positions, e[:, 0], l,
                                 base, 8)[0]
                    for pool, e in zip(flat, entries))
            attn = block.attn.attend_paged(lp["attn"], q, flat,
                                           table + base, positions)
            h = h + block.attn.output(lp["attn"], attn)
        with jax.named_scope("mlp"):
            y, st = block.mlp_stats(
                lp["mlp"], block.post_norm(lp["post_norm"], h))
        return h + y, flat, st

    flat = tuple(p.reshape((L * P,) + p.shape[2:]) for p in pools)
    x, flat, stats = _contract_layers(model, params, x, flat, stats, layer)
    logits = model.logits(params, model.final_hidden(params, x))[:, 0, :]
    return (logits, tuple(f.reshape(p.shape) for f, p in zip(flat, pools)),
            stats)


def extend_cache_contract(model, params, tokens, cache, start, stats):
    """`extend_cache` for a model with a cache contract.  cache: a tuple
    of dense per-slot caches [L, b, M, *stored shape]; tokens [b, C] at
    positions start..start+C-1.  Returns (logits [b, C, vocab], cache,
    stats)."""
    b, C = tokens.shape
    rows = jnp.arange(b)
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
    qpos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    rope = model.rope_tables(cache[0].shape[2])
    with jax.named_scope("embed"):
        x = model.embed_tokens(params, tokens)

    def layer(block, h, lp, cache, l):
        with jax.named_scope("attn"):
            hn = block.input_norm(lp["input_norm"], h)
            q, entries = block.attn.project(lp["attn"], hn, rope, qpos)
            with jax.named_scope("kv_write"):
                cache = tuple(
                    c.at[l, rows[:, None], qpos].set(e.astype(c.dtype))
                    for c, e in zip(cache, entries))
            attn = block.attn.attend_dense(
                lp["attn"], q, tuple(c[l] for c in cache), start)
            h = h + block.attn.output(lp["attn"], attn)
        with jax.named_scope("mlp"):
            y, st = block.mlp_stats(
                lp["mlp"], block.post_norm(lp["post_norm"], h))
        return h + y, cache, st

    x, cache, stats = _contract_layers(model, params, x, tuple(cache),
                                       stats, layer)
    return model.logits(params, model.final_hidden(params, x)), cache, stats


def _verify_step_paged_gpt(model, params, tokens, k_pool, v_pool, table,
                           positions, k_scale, v_scale, kv_quant,
                           return_hidden):
    from hetu_tpu.ops.pallas.paged_attention import paged_verify
    c = model.config
    mp_ = params["model"]
    S, C = tokens.shape
    quant = k_scale is not None
    bits = 4 if kv_quant == "int4" else 8
    qpos = positions[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    x = _gpt_embed(model, mp_, tokens, qpos)
    block = model.model.block
    att = block.attn
    nh, hd = c.num_attention_heads, c.head_dim
    scale = hd ** -0.5

    def layer(h, lp, pools, l, base):
        kp, vp, ksc, vsc = pools
        hn = block.ln1(lp["ln1"], h)
        qkv = jnp.einsum("bsh,hngd->bsngd", hn,
                         lp["attn"]["wqkv"].astype(h.dtype)) \
            + lp["attn"]["bqkv"].astype(h.dtype)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        with jax.named_scope("kv_write"):
            kp, ksc = _paged_write_tokens(kp, ksc, table, positions, k,
                                          l, base, bits)
            vp, vsc = _paged_write_tokens(vp, vsc, table, positions, v,
                                          l, base, bits)
        with jax.named_scope("pallas_paged_verify"):
            ksl, vsl = (ksc[l], vsc[l]) if quant else (None, None)
            attn = paged_verify(
                q.reshape(S, C, nh, hd), kp, vp, table + base, positions,
                softmax_scale=scale, k_scale=ksl, v_scale=vsl,
                quant=kv_quant, scale_table=table)
        h = h + att.o_proj(lp["attn"]["o_proj"],
                           attn.reshape(S, C, nh * hd))
        h = h + block.mlp(lp["mlp"], block.ln2(lp["ln2"], h))
        return h, (kp, vp, ksc, vsc)

    x, pools = _scan_layers_paged(
        layer, x, mp_["blocks"], (k_pool, v_pool, k_scale, v_scale))
    hidden = model.model.final_ln(mp_["final_ln"], x)
    if return_hidden:
        return (hidden,) + pools
    return (model.logits(params, hidden),) + pools


def verify_step_paged(model, params, tokens, k_pool, v_pool, table,
                      positions, *, k_scale=None, v_scale=None,
                      kv_quant=None, return_hidden: bool = False):
    """The speculative VERIFY step attending DIRECTLY over a paged KV
    pool — `verify_step_slots` without the gather (ops/pallas/
    paged_attention.paged_verify: all k+1 query positions walk the
    slot's pages in one launch with per-position causal masks).

    tokens: [S, C] int32 (last emitted token + k drafts per slot);
    positions: [S] int32 — token i of the block sits at positions[s]+i.
    The block's K/V are scattered into each slot's pages BEFORE the
    kernel runs (write-then-attend, exactly like the dense path), and
    the updated pools return: (logits [S, C, vocab], *new_pools) — in
    place, as in `decode_step_paged` (`_scan_layers_paged`).
    Quantized pools pass scales (+ ``kv_quant="int4"`` for nibble
    pages) exactly as `decode_step_paged`.

    ``return_hidden=True`` returns the final-norm HIDDEN states
    [S, C, hidden] instead of logits — the fused sampling epilogue
    (serving/sampling.sample_hidden_grid) consumes them directly so the
    [S, C, vocab] logits plane never materializes in HBM."""
    c = model.config
    if not c.use_scan:
        raise ValueError("generation requires use_scan=True (stacked layer "
                         "params)")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    quant = k_scale is not None
    if kv_quant is None:
        kv_quant = "int8" if quant else None
    bits = 4 if kv_quant == "int4" else 8
    positions = positions.astype(jnp.int32)
    table = table.astype(jnp.int32)
    if _is_gpt(model):
        return _verify_step_paged_gpt(model, params, tokens, k_pool,
                                      v_pool, table, positions, k_scale,
                                      v_scale, kv_quant, return_hidden)
    from hetu_tpu.ops.pallas.paged_attention import paged_verify
    mp_ = params["model"]
    S, C = tokens.shape
    qpos = positions[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    x = model.model.embed(mp_["embed"], tokens).astype(c.compute_dtype)
    cos, sin = ops.build_rope_cache(c.max_position_embeddings, c.head_dim,
                                    c.rope_theta)
    block = model.model.layers.block
    att = block.attn
    scale = c.head_dim ** -0.5

    def layer(h, layer_params, pools, l, base):
        kp, vp, ksc, vsc = pools
        hn = block.input_norm(layer_params["input_norm"], h)
        qkv = jnp.einsum("bsh,hkgd->bskgd", hn,
                         layer_params["attn"]["wqkv"].astype(h.dtype))
        q = qkv[..., : att.group, :].reshape(S, C, att.n_q, c.head_dim)
        k = qkv[..., att.group, :]
        v = qkv[..., att.group + 1, :]
        q, k = ops.apply_rotary_qk(q, k, cos, sin, qpos)
        with jax.named_scope("kv_write"):
            kp, ksc = _paged_write_tokens(kp, ksc, table, positions, k,
                                          l, base, bits)
            vp, vsc = _paged_write_tokens(vp, vsc, table, positions, v,
                                          l, base, bits)
        with jax.named_scope("pallas_paged_verify"):
            ksl, vsl = (ksc[l], vsc[l]) if quant else (None, None)
            attn = paged_verify(q, kp, vp, table + base, positions,
                                softmax_scale=scale, k_scale=ksl,
                                v_scale=vsl, quant=kv_quant,
                                scale_table=table)
        h = h + att.o_proj(layer_params["attn"]["o_proj"],
                           attn.reshape(S, C, att.n_q * c.head_dim))
        mlp_out = block.mlp(layer_params["mlp"],
                            block.post_norm(layer_params["post_norm"], h))
        if isinstance(mlp_out, tuple):  # MoE
            mlp_out = mlp_out[0]
        h = h + mlp_out
        return h, (kp, vp, ksc, vsc)

    x, pools = _scan_layers_paged(
        layer, x, mp_["layers"]["layers"],
        (k_pool, v_pool, k_scale, v_scale))
    hidden = model.model.final_norm(mp_["final_norm"], x)
    if return_hidden:
        return (hidden,) + pools
    return (model.logits(params, hidden),) + pools


def _extend_cache_gpt(model, params, tokens, cache, start,
                      collect: bool = False):
    c = model.config
    mp = params["model"]
    b, C = tokens.shape
    rows = jnp.arange(b)
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
    qpos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # [b, C]
    x = _gpt_embed(model, mp, tokens, qpos)
    block = model.model.block
    att = block.attn
    nh, hd = c.num_attention_heads, c.head_dim
    scale = hd ** -0.5
    cache_k, cache_v = cache

    def body(h, xs):
        lp, ck, cv = xs
        hn = block.ln1(lp["ln1"], h)
        qkv = jnp.einsum("bsh,hngd->bsngd", hn,
                         lp["attn"]["wqkv"].astype(h.dtype)) \
            + lp["attn"]["bqkv"].astype(h.dtype)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        ck = ck.at[rows[:, None], qpos].set(k.astype(ck.dtype))
        cv = cv.at[rows[:, None], qpos].set(v.astype(cv.dtype))
        attn = _attend_cached_chunk(q, ck, cv, start, scale)
        h = h + att.o_proj(lp["attn"]["o_proj"],
                           attn.reshape(b, C, nh * hd))
        h = h + block.mlp(lp["mlp"], block.ln2(lp["ln2"], h))
        return h, ((ck, cv, k, v) if collect else (ck, cv))

    x, ys = lax.scan(body, x, (mp["blocks"], cache_k, cache_v))
    hidden = model.model.final_ln(mp["final_ln"], x)
    logits = model.logits(params, hidden)
    if collect:
        new_k, new_v, k_chunk, v_chunk = ys
        return logits, (new_k, new_v), (k_chunk, v_chunk)
    return logits, ys


def extend_cache(model, params, tokens, cache, start, *,
                 collect_token_kv: bool = False):
    """Advance a KV cache by a whole token block (chunked prefill).

    tokens: [b, C] int32 at absolute positions start..start+C-1 (start
    scalar or [b]); the chunk's K/V are written into the cache and each
    query attends causally over cache[:start+i+1].  Returns
    (logits [b, C, vocab], new_cache).  Running consecutive chunks
    through this is numerically the incremental form of `prefill` — the
    serving engine uses it so one long prompt never stalls the decode
    batch (docs/serving.md).

    ``collect_token_kv=True`` (the `verify_step_slots` path) also
    returns the chunk's per-layer K/V [L, b, C, n_kv, hd] so a paged
    cache can scatter them into its pool; the default False traces
    exactly the pre-speculative chunk program."""
    c = model.config
    if not c.use_scan:
        raise ValueError("generation requires use_scan=True (stacked layer "
                         "params)")
    if _is_gpt(model):
        return _extend_cache_gpt(model, params, tokens, cache, start,
                                 collect=collect_token_kv)
    mp = params["model"]
    b, C = tokens.shape
    rows = jnp.arange(b)
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (b,))
    qpos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # [b, C]
    with jax.named_scope("embed"):
        x = model.model.embed(mp["embed"], tokens).astype(c.compute_dtype)
    cos, sin = ops.build_rope_cache(c.max_position_embeddings, c.head_dim,
                                    c.rope_theta)
    block = model.model.layers.block
    att = block.attn
    scale = c.head_dim ** -0.5

    cache_k, cache_v = cache

    def body(carry, xs):
        h = carry
        layer_params, ck, cv = xs
        with jax.named_scope("attn"):
            hn = block.input_norm(layer_params["input_norm"], h)
            qkv = jnp.einsum("bsh,hkgd->bskgd", hn,
                             layer_params["attn"]["wqkv"].astype(h.dtype))
            q = qkv[..., : att.group, :].reshape(b, C, att.n_q,
                                                 c.head_dim)
            k = qkv[..., att.group, :]
            v = qkv[..., att.group + 1, :]
            q, k = ops.apply_rotary_qk(q, k, cos, sin, qpos)
            with jax.named_scope("kv_write"):
                ck = ck.at[rows[:, None], qpos].set(k.astype(ck.dtype))
                cv = cv.at[rows[:, None], qpos].set(v.astype(cv.dtype))
            attn = _attend_cached_chunk(q, ck, cv, start, scale)
            h = h + att.o_proj(layer_params["attn"]["o_proj"],
                               attn.reshape(b, C, att.n_q * c.head_dim))
        with jax.named_scope("mlp"):
            mlp_out = block.mlp(
                layer_params["mlp"],
                block.post_norm(layer_params["post_norm"], h))
            if isinstance(mlp_out, tuple):  # MoE
                mlp_out = mlp_out[0]
            h = h + mlp_out
        return h, ((ck, cv, k, v) if collect_token_kv else (ck, cv))

    with jax.named_scope("layer"):
        x, ys = lax.scan(
            body, x, (mp["layers"]["layers"], cache_k, cache_v))
    hidden = model.model.final_norm(mp["final_norm"], x)
    logits = model.logits(params, hidden)
    if collect_token_kv:
        new_k, new_v, k_chunk, v_chunk = ys
        return logits, (new_k, new_v), (k_chunk, v_chunk)
    return logits, ys


def verify_step_slots(model, params, tokens, cache, positions):
    """The speculative-decoding VERIFY step: advance every slot by a
    whole [k+1]-token block in ONE forward (serving/spec_decode.py).

    tokens: [S, k+1] int32 — per slot, the last emitted token followed
    by the k draft tokens; positions: [S] int32 — the slot's current
    write position (token i of the block sits at positions[s] + i).
    This is exactly `extend_cache` with PER-SLOT start positions (each
    batch row an independent sequence at its own depth, the
    `decode_step_slots` convention) plus the block's per-layer K/V
    handed out for the paged-pool scatter.

    Returns (logits [S, k+1, vocab], new_cache, (k_chunk, v_chunk))
    with k_chunk/v_chunk [L, S, k+1, n_kv, hd].  logits[:, i] is the
    next-token distribution AFTER input token i — the verification
    targets: greedy acceptance compares draft i+1 against
    argmax(logits[:, i]), bit-identical to what the sequential
    single-token path would have computed at that depth (same
    chunk-causal grouped-GQA attention as chunked prefill — one
    implementation, so spec-decode and sequential decode cannot drift
    numerically)."""
    return extend_cache(model, params, tokens, cache,
                        positions.astype(jnp.int32),
                        collect_token_kv=True)


def generate(model, params, input_ids, *, max_new_tokens: int,
             temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             rng: Optional[jax.Array] = None,
             eos_id: Optional[int] = None,
             eos_token_id: Optional[int] = None,
             pad_token_id: Optional[int] = None):
    """Autoregressive generation (greedy when temperature == 0; top_k
    and/or top_p (nucleus) filtering when sampling).
    input_ids: [b, plen] int32 -> [b, plen + max_new_tokens].

    EOS handling: with eos_token_id (alias: eos_id) set, a sequence that
    emits EOS is done — it keeps emitting `pad_token_id` (default: the
    EOS id itself, the pre-serving behavior) and, once EVERY sequence in
    the batch is done, the remaining scan iterations skip the decode
    computation entirely via lax.cond (the same active-mask early-exit
    the serving scheduler uses per slot)."""
    b, plen = input_ids.shape
    max_len = plen + max_new_tokens
    # context-length validation happens in prefill (_check_context_length)
    logits, cache = prefill(model, params, input_ids, max_len)
    rng = rng if rng is not None else jax.random.key(0)
    eos = eos_token_id if eos_token_id is not None else eos_id
    fill = pad_token_id if pad_token_id is not None else eos

    def sample(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / temperature
        if top_k is not None or (top_p is not None and top_p > 0.0):
            # ONE descending full-vocab sort serves both filters (the sort
            # is the sampler's dominant cost inside the decode scan)
            desc = jnp.sort(logits, axis=-1)[:, ::-1]
        if top_k is not None:
            kth = desc[:, top_k - 1][:, None]
            logits = jnp.where(logits < kth, -1e30, logits)
        if top_p is not None and top_p > 0.0:
            # nucleus: keep the smallest prefix of the sorted distribution
            # whose mass exceeds top_p; the max-prob token always survives
            # (its preceding mass is 0 < top_p), so small top_p degenerates
            # to greedy.  top_p in (None, 0.0) = filter disabled.  With
            # top_k set, the nucleus is computed over the RENORMALIZED
            # top-k distribution (HF semantics: top_k filters first); the
            # filtered descending view is just the top-k prefix of `desc`,
            # so no second sort is needed.
            desc_f = desc if top_k is None else jnp.where(
                jnp.arange(desc.shape[-1]) < top_k, desc, -1e30)
            probs = jax.nn.softmax(desc_f, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            keep = cum - probs < top_p          # mass BEFORE this token
            cutoff = jnp.min(jnp.where(keep, desc_f, jnp.inf),
                             axis=-1, keepdims=True)
            logits = jnp.where(logits < cutoff, -1e30, logits)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    def step(carry, i):
        logits, cache, key, done = carry
        key, sub = jax.random.split(key)
        tok = sample(logits, sub)
        if eos is not None:
            tok = jnp.where(done, fill, tok)
            done = done | (tok == eos)
            # all sequences finished -> skip the whole decode computation
            # (a real branch under the scan: only the taken side runs)
            logits, cache = lax.cond(
                jnp.all(done),
                lambda c: c,
                lambda c: decode_step(model, params, tok, c[1], plen + i),
                (logits, cache))
        else:
            logits, cache = decode_step(model, params, tok, cache, plen + i)
        return (logits, cache, key, done), tok

    done0 = jnp.zeros((b,), bool)
    (_, _, _, _), toks = lax.scan(
        step, (logits, cache, rng, done0), jnp.arange(max_new_tokens))
    return jnp.concatenate([input_ids, toks.T], axis=1)
