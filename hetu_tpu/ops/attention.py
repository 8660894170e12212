"""Attention ops.

`attention` is the reference composition (reference: hetu/graph/ops/Attention.cc)
— a pure-XLA softmax attention used for golden tests and small models.

`flash_attention` is the dispatcher for the fused path (reference:
hetu/impl/kernel/FlashAttention.cu wrapping flash-attn 2): on TPU it routes to
the Pallas flash kernel (hetu_tpu.ops.pallas.flash_attention) when shapes
permit, else falls back to the XLA composition — XLA's own fusion of this
pattern is already strong on TPU, so the fallback is safe, just more HBM
traffic for long sequences.
"""
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from hetu_tpu.dstates import DistributedStates


def attention(q, k, v, *, causal: bool = True, bias: Optional[jnp.ndarray] = None,
              segment_ids: Optional[jnp.ndarray] = None, softmax_scale: Optional[float] = None,
              dropout_rate: float = 0.0, dropout_rng: Optional[jnp.ndarray] = None):
    """Softmax attention. q,k,v: [batch, seq, heads, head_dim] (kv heads may be
    fewer for GQA — broadcast here). Returns [batch, seq, heads, head_dim]."""
    orig_dtype = q.dtype
    b, sq, hq, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if hk != hq:
        rep = hq // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    # [b, h, sq, sk]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if bias is not None:
        scores = scores + bias
    neg = jnp.finfo(jnp.float32).min
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        scores = jnp.where(mask[None, None], scores, neg)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        scores = jnp.where(seg_mask[:, None], scores, neg)
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = 1.0 - dropout_rate
        mask2 = jax.random.bernoulli(dropout_rng, keep, probs.shape)
        probs = jnp.where(mask2, probs / keep, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    # what the "dots_attn" remat policy keeps of this route (nn/remat.py)
    return checkpoint_name(out.astype(orig_dtype), "attn_out")


def flash_attention(q, k, v, *, causal: bool = True,
                    segment_ids: Optional[jnp.ndarray] = None,
                    softmax_scale: Optional[float] = None,
                    use_pallas: Optional[bool] = None, layout=None):
    """Fused attention entry point. Routes to the Pallas TPU kernel when
    running on TPU with compatible shapes; XLA composition otherwise.

    `layout` (a DistributedStates) declares how q/k/v [b, s, heads, hd]
    and the result lie over the mesh: under a multi-device mesh the
    kernel runs once per shard of it — per batch row and per (kv) head.
    A layout that shards the SEQUENCE is ring attention's business
    (parallel/ring_attention) and is refused here."""
    from hetu_tpu.ops import pallas as _pl
    from hetu_tpu.ops.pallas import flash_attention as _fa
    layouts = None
    if layout is not None:
        tokens = DistributedStates(layout.spec[:2])
        layouts = ((layout,) * 3
                   + (() if segment_ids is None else (tokens,)))
    if use_pallas is None:
        # HETU_TPU_PALLAS=1/0 force-routes; "auto" keeps the shape gate
        # (reference: the HETU_PARALLEL_ATTN env family, GetExecEnvs);
        # HETU_TPU_PALLAS_KERNELS can exclude just this kernel.  The gate
        # is the kernel module's own entry validation, so the two can
        # never silently diverge (the drift test in
        # tests/test_pallas_kernels.py pins the contract)
        use_pallas = _pl.resolve_route(
            "flash", _fa.check_shapes, q.shape, k.shape,
            layouts=None if layouts is None else layouts[:2])
    if use_pallas:
        if layout is not None and layout.spec[1]:
            raise ValueError(
                f"flash_attention: layout {layout} shards the sequence dim "
                f"over {layout.spec[1]}; attention is not per-shard there "
                f"(use parallel.ring_attention)")

        def kernel(q, k, v, *seg):
            return _fa.flash_attention(
                q, k, v, causal=causal, segment_ids=seg[0] if seg else None,
                softmax_scale=softmax_scale)
        # named so obs.hlo_profile attributes the custom-call to its
        # kernel group (layer_table `.../pallas_flash_attention` rows)
        with jax.named_scope("pallas_flash_attention"):
            return _pl.per_shard(kernel, layouts, layout)(
                q, k, v, *(() if segment_ids is None else (segment_ids,)))
    return attention(q, k, v, causal=causal, segment_ids=segment_ids,
                     softmax_scale=softmax_scale)
