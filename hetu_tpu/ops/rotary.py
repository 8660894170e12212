"""Rotary position embeddings (reference: hetu/impl/kernel/rotary.cu +
python/hetu/models/llama/llama_model.py:10 RotaryEmbedding).

Supports packed varlen batches via per-token position ids (the TPU analog of
the reference's cu_seqlens-aware fused rotary): the data pipeline emits
position ids that restart at each packed-sequence boundary, so one gather
replaces the cu_seqlens offset logic.
"""
from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu.dstates import DistributedStates


def build_rope_cache(max_len: int, head_dim: int, base: float = 10000.0,
                     dtype=jnp.float32):
    """Precompute cos/sin tables [max_len, head_dim//2]."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rotary(x, cos, sin, position_ids: Optional[jnp.ndarray] = None):
    """Apply RoPE. x: [..., seq, heads, head_dim]; cos/sin: [max_len, hd//2];
    position_ids: [..., seq] int32 (defaults to arange)."""
    seq = x.shape[-3]
    if position_ids is None:
        cos_t = cos[:seq]
        sin_t = sin[:seq]
        # [seq, 1, hd/2] broadcasting over heads
        cos_t = cos_t[:, None, :]
        sin_t = sin_t[:, None, :]
    else:
        cos_t = cos[position_ids][..., None, :]
        sin_t = sin[position_ids][..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out1 = xf1 * cos_t - xf2 * sin_t
    out2 = xf2 * cos_t + xf1 * sin_t
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def apply_rotary_qk(q, k, cos, sin, position_ids: Optional[jnp.ndarray] = None,
                    use_pallas: Optional[bool] = None, layout=None):
    """Apply RoPE to q [b, s, nq, hd] AND k [b, s, nk, hd] in one fused
    Pallas pass (ops/pallas/rotary — the tables are gathered once and
    both tensors rotate in VMEM; the rotation's vjp is the same kernel
    with -sin).  Falls back to two `apply_rotary` calls — the exact seed
    composition — when the kernel is gated off or the shape gate
    rejects.  `layout` (a DistributedStates) declares how q/k and both
    results lie over the mesh: under a multi-device mesh the kernel runs
    once per shard of it, the gathered tables sharded like q's batch and
    seq dims.  Returns (q_rotated, k_rotated)."""
    from hetu_tpu.ops import pallas as _pl
    from hetu_tpu.ops.pallas import rotary as _pr
    layouts = None
    if layout is not None:
        tables = DistributedStates(layout.spec[:2] + ((),))
        layouts = (layout, layout, tables, tables)
    if use_pallas is None:
        use_pallas = _pl.resolve_route(
            "rotary", _pr.check_shapes, q.shape, k.shape,
            layouts=None if layouts is None else layouts[:2])
    if use_pallas:
        b, s = q.shape[0], q.shape[1]
        d2 = cos.shape[-1]
        if position_ids is None:
            cos_t = jnp.broadcast_to(cos[:s][None], (b, s, d2))
            sin_t = jnp.broadcast_to(sin[:s][None], (b, s, d2))
        else:
            cos_t = jnp.broadcast_to(cos[position_ids], (b, s, d2))
            sin_t = jnp.broadcast_to(sin[position_ids], (b, s, d2))
        with jax.named_scope("pallas_rotary"):
            return _pl.per_shard(_pr.fused_rotary_qk, layouts,
                                 (layout, layout))(
                q, k, cos_t.astype(jnp.float32), sin_t.astype(jnp.float32))
    return (apply_rotary(q, cos, sin, position_ids),
            apply_rotary(k, cos, sin, position_ids))


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude correction m(mscale) = 0.1 mscale ln(factor) + 1
    (1 for factor <= 1): attention scores are multiplied by its square
    where the model's `mscale_all_dim` is set (DeepSeek-V3, Kimi-K2)."""
    import math
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(head_dim: int, base: float, *, factor: float,
                  original_max_position_embeddings: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """YaRN's per-pair inverse frequencies [head_dim // 2] (Peng et al.
    2023, as DeepSeek-V3's and Kimi-K2's published rotary code computes
    them): pair i keeps base^(-2i/d) where it makes more than
    `beta_fast` rotations over the original context, takes that over
    `factor` where it makes fewer than `beta_slow`, and a linear blend
    over the pair index between the two correction dimensions."""
    import math
    d, L = head_dim, original_max_position_embeddings

    def correction_dim(rotations):
        return d * math.log(L / (rotations * 2 * math.pi)) / (
            2 * math.log(base))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), d - 1)
    extra = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp                  # 1: extrapolate (unscaled pair)
    return extra / factor * (1.0 - keep) + extra * keep


def build_yarn_rope_cache(max_len: int, head_dim: int, base: float, *,
                          factor: float,
                          original_max_position_embeddings: int,
                          beta_fast: float = 32.0, beta_slow: float = 1.0,
                          mscale: float = 1.0, mscale_all_dim: float = 0.0,
                          dtype=jnp.float32):
    """cos/sin tables [max_len, head_dim // 2] under YaRN scaling, built
    to `max_len` positions (the caller's, not the model's 262,144).  The
    tables carry the factor m(mscale) / m(mscale_all_dim), which is 1
    where the two are equal."""
    inv_freq = yarn_inv_freq(
        head_dim, base, factor=factor,
        original_max_position_embeddings=original_max_position_embeddings,
        beta_fast=beta_fast, beta_slow=beta_slow)
    freqs = jnp.outer(jnp.arange(max_len, dtype=jnp.float32), inv_freq)
    m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    return ((jnp.cos(freqs) * m).astype(dtype),
            (jnp.sin(freqs) * m).astype(dtype))
