"""Ring-attention context parallelism.

Rebuild of the reference CP engine (reference: hetu/graph/ops/
ParallelAttention.{h,cc} — AttnCommRing ring KV-passing :945, online-softmax
LSE merge ExecCorr :606, comm/compute overlap, piggyback dKV on the backward
ring AttnBlock :172, causal balance via head+tail splits).

TPU mapping:
- the ring lives inside a shard_map over the `cp` mesh axis; KV blocks rotate
  with `lax.ppermute` (XLA compiles async collective-permutes that overlap
  the per-block flash kernel — the reference overlaps rounds by hand on a
  dedicated stream, ExecComm :849).
- per-block attention is the Pallas flash kernel with **global positions +
  segment ids** doing all masking, so arbitrary CP layouts (the head+tail
  symmetric split of hetu_tpu.data.bucket.cp_split_batch, packed varlen rows)
  need no special ring-step mask enumeration (the reference precomputes
  per-rank-pair AttnInfo mask kinds :212 — positions subsume that table).
- backward is a second ring: each rank computes its (dq; dk,dv-of-the-passing
  -block) with the flash-attn2 global-LSE trick, and dk/dv accumulate ON the
  rotating block until it returns home — exactly the reference's
  piggyback_grad.
- merge numerics follow ExecCorr: out = sum_i out_i * exp(lse_i - lse_tot),
  lse_tot = logsumexp_i lse_i, with empty blocks at lse = -inf.

`ring_attention` is the shard_map-internal function; `ring_attention_gspmd`
wraps it for use from global-view (jit) model code.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from hetu_tpu.ops.pallas.flash_attention import (NEG_INF, _bwd, _fwd,
                                                 causal_block_mask,
                                                 fit_block, full_block_mask)
from hetu_tpu.parallel.strategy import ParallelStrategy


def _merge(o_acc, lse_acc, o_i, lse_i):
    """Online-softmax merge of two partial attentions (ExecCorr :606).
    o: [b, h, s, d]; lse: [b, h, s]."""
    lse_new = jnp.logaddexp(lse_acc, lse_i)
    # exp(-inf - -inf) -> nan; empty rows keep weight 0
    w_acc = jnp.where(lse_acc == NEG_INF, 0.0, jnp.exp(lse_acc - lse_new))
    w_i = jnp.where(lse_i == NEG_INF, 0.0, jnp.exp(lse_i - lse_new))
    o_new = o_acc * w_acc[..., None] + o_i * w_i[..., None]
    return o_new, lse_new


def _rotate(xs, axis_name):
    n = lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    return [lax.ppermute(x, axis_name, perm) for x in xs]


def _pick_block(seq: int, want: int) -> int:
    """Largest block <= want that divides seq — the kernel's fit_block
    rule (one shared block-geometry policy; avoids the silent-tail-drop
    hazard of a non-dividing block)."""
    return fit_block(want, seq)


# ---------------------------------------------------------------------------
# Ring-step live-tile masks (AttnInfo analog).
#
# The reference precomputes per-(rank, origin) mask kinds — causal / full /
# EMPTY — so dead blocks never execute (ParallelAttention.cc:212
# GenerateAttnInfo). In one-program SPMD the per-rank mask choice becomes a
# lax.cond on the rank index: for each ring step i>0 there are at most two
# mask patterns across ranks ("origin before me" vs "origin after me",
# predicate r >= i), each branch running the Pallas kernel on a compressed
# tile grid. The in-kernel position masks stay on as the exact per-token
# guard; the static masks only bound which TILES get scheduled, so they must
# be (and are) conservative supersets.
#
# Per split pattern (data/bucket.py cp_split_batch):
#   normal — step 0 is the within-chunk causal triangle; steps from later
#            chunks are fully dead (skipped without running the kernel).
#            No lockstep wall-clock win (the ring waits on the busiest
#            rank), but dead steps stop burning MXU.
#   stripe — every (rank, origin) pair reduces to the SAME stripe-granular
#            triangle: uniform mask, no cond, ~2x tile reduction per step.
#   sym    — head+tail chunks: 2 of 4 quadrants are dead at every step
#            (which 2 depends on r vs origin -> the cond), so every rank
#            schedules exactly half the tiles every step: a true 2x.
# ---------------------------------------------------------------------------

# The process-wide declared CP data layout (the analog of the reference's
# HETU_PARALLEL_ATTN_SPLIT env flag, ParallelAttention.cc:196-204). Set by
# whoever reorders the data (the Trainer); consulted by ring_attention_gspmd
# when the strategy doesn't declare cp_split explicitly. None = undeclared =
# no static skipping.
_DECLARED_CP_SPLIT: Optional[str] = None


def declare_cp_split(split: Optional[str]):
    """Declare the CP split pattern of the batches this process feeds to
    ring attention (must match the actual seq reorder, or tiles holding live
    scores get skipped)."""
    global _DECLARED_CP_SPLIT
    if split not in (None, "normal", "stripe", "sym"):
        raise ValueError(f"split must be sym|stripe|normal|None, got {split!r}")
    _DECLARED_CP_SPLIT = split


@contextlib.contextmanager
def declared_cp_split(split: Optional[str]):
    """Scoped declare_cp_split — the Trainer wraps its (traced) step calls
    so its declaration cannot leak onto unrelated ring users in the same
    process (mask choice is captured at trace time)."""
    global _DECLARED_CP_SPLIT
    prev = _DECLARED_CP_SPLIT
    declare_cp_split(split)
    try:
        yield
    finally:
        _DECLARED_CP_SPLIT = prev


def _stripe_mask(s: int, bq: int, bk: int, g: int):
    """Union-over-ranks live tiles for the stripe split at granularity g:
    tile (qi, ki) can contain a visible pair for SOME (rank, origin) iff its
    max q stripe is >= its min k stripe."""
    return tuple(
        tuple((qi * bq + bq - 1) // g >= (ki * bk) // g
              for ki in range(s // bk))
        for qi in range(s // bq))


def _stripe_granularity(s_loc: int, cp: int):
    """cp_split_batch's stripe granularity, from the shared rule (which
    takes the GLOBAL seq = s_loc * cp)."""
    from hetu_tpu.data.bucket import stripe_granularity
    return stripe_granularity(s_loc * cp, cp)


def ring_step_masks(split, s_loc: int, bq: int, bk: int, cp: int,
                    causal: bool):
    """(mask_step0, mask_origin_before, mask_origin_after) static tile grids,
    or None to disable skipping. mask_origin_after=None = step fully dead."""
    if not causal or split is None or cp == 1:
        return None
    if s_loc % bq or s_loc % bk:
        return None
    tri = causal_block_mask(s_loc, s_loc, bq, bk, q_offset=0, k_offset=0)
    if split == "normal":
        return (tri, full_block_mask(s_loc, s_loc, bq, bk), None)
    if split == "stripe":
        g = _stripe_granularity(s_loc, cp)
        if g is None:
            return None
        m = _stripe_mask(s_loc, bq, bk, g)
        return (m, m, m)
    if split == "sym":
        half = s_loc // 2
        if s_loc % 2 or half % bq or half % bk:
            return None
        nk, hk = s_loc // bk, half // bk
        hq = half // bq
        tri_h = causal_block_mask(half, half, bq, bk, q_offset=0, k_offset=0)
        # step 0 (origin == me): [qh|kh] diag, [qh|kt] dead, [qt|kh] full,
        # [qt|kt] diag
        c = tuple(tri_h[qi] + (False,) * (nk - hk) for qi in range(hq)) + \
            tuple((True,) * hk + tri_h[qi] for qi in range(hq))
        # origin strictly before me: k head chunk fully visible, k tail dead
        a = tuple((True,) * hk + (False,) * (nk - hk)
                  for _ in range(s_loc // bq))
        # origin strictly after me: my head rows dead, my tail rows full
        b = tuple((False,) * nk for _ in range(hq)) + \
            tuple((True,) * nk for _ in range(hq))
        return (c, a, b)
    raise ValueError(f"split must be sym|stripe|normal|None, got {split!r}")


def _masked_fwd(i, masks, axis_name, q, k_i, v_i, q_pos, kpos_i, q_seg,
                kseg_i, *, scale, causal, block_q, block_k):
    """One ring step's forward with static tile skipping (cond on rank)."""
    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k)
    if masks is None:
        return _fwd(q, k_i, v_i, q_pos, kpos_i, q_seg, kseg_i, **kw)
    if i == 0:
        return _fwd(q, k_i, v_i, q_pos, kpos_i, q_seg, kseg_i,
                    block_mask=masks[0], **kw)
    if masks[1] == masks[2]:            # uniform across ranks (stripe)
        return _fwd(q, k_i, v_i, q_pos, kpos_i, q_seg, kseg_i,
                    block_mask=masks[1], **kw)
    b, h, sq, d = q.shape

    def before():
        return _fwd(q, k_i, v_i, q_pos, kpos_i, q_seg, kseg_i,
                    block_mask=masks[1], **kw)

    def after():
        if masks[2] is None:            # entirely dead step for these ranks
            return (jnp.zeros((b, h, sq, d), q.dtype),
                    jnp.full((b, h, sq), NEG_INF, jnp.float32))
        return _fwd(q, k_i, v_i, q_pos, kpos_i, q_seg, kseg_i,
                    block_mask=masks[2], **kw)

    r = lax.axis_index(axis_name)
    return lax.cond(r >= i, before, after)


def _masked_bwd(i, masks, axis_name, q, k_i, v_i, o, lse, do, q_pos, kpos_i,
                q_seg, kseg_i, *, scale, causal, block_q, block_k, delta):
    """One ring step's backward with static tile skipping (cond on rank)."""
    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k)
    if masks is None:
        return _bwd(q, k_i, v_i, o, lse, do, q_pos, kpos_i, q_seg, kseg_i,
                    delta=delta, **kw)
    if i == 0:
        return _bwd(q, k_i, v_i, o, lse, do, q_pos, kpos_i, q_seg, kseg_i,
                    delta=delta, block_mask=masks[0], **kw)
    if masks[1] == masks[2]:
        return _bwd(q, k_i, v_i, o, lse, do, q_pos, kpos_i, q_seg, kseg_i,
                    delta=delta, block_mask=masks[1], **kw)

    def before():
        return _bwd(q, k_i, v_i, o, lse, do, q_pos, kpos_i, q_seg, kseg_i,
                    delta=delta, block_mask=masks[1], **kw)

    def after():
        if masks[2] is None:
            return (jnp.zeros(q.shape, jnp.float32),
                    jnp.zeros(k_i.shape, jnp.float32),
                    jnp.zeros(v_i.shape, jnp.float32))
        return _bwd(q, k_i, v_i, o, lse, do, q_pos, kpos_i, q_seg, kseg_i,
                    delta=delta, block_mask=masks[2], **kw)

    r = lax.axis_index(axis_name)
    return lax.cond(r >= i, before, after)


# All arrays here are LOCAL shards: q/k/v [b, h, s_loc, d] (head-major, the
# kernel's native layout); positions/segments [b, s_loc].

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _ring(q, k, v, q_pos, kv_pos, q_seg, kv_seg, axis_name, scale, causal,
          block_sizes, masks):
    o, _ = _ring_fwd_impl(q, k, v, q_pos, kv_pos, q_seg, kv_seg, axis_name,
                          scale, causal, block_sizes, masks)
    return o


def _ring_fwd_impl(q, k, v, q_pos, kv_pos, q_seg, kv_seg, axis_name, scale,
                   causal, block_sizes, masks):
    b, h, sq, d = q.shape
    cp = lax.axis_size(axis_name)
    block_q = _pick_block(sq, block_sizes[0])
    block_k = _pick_block(k.shape[2], block_sizes[1])
    use_seg = q_seg is not None
    o = jnp.zeros((b, h, sq, d), jnp.float32)
    lse = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    k_i, v_i, kpos_i = k, v, kv_pos
    kseg_i = kv_seg
    for i in range(cp):
        o_i, lse_i = _masked_fwd(
            i, masks, axis_name, q, k_i, v_i, q_pos, kpos_i,
            q_seg if use_seg else None, kseg_i if use_seg else None,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k)
        o, lse = _merge(o, lse, o_i.astype(jnp.float32), lse_i)
        if i != cp - 1:
            if use_seg:
                k_i, v_i, kpos_i, kseg_i = _rotate(
                    [k_i, v_i, kpos_i, kseg_i], axis_name)
            else:
                k_i, v_i, kpos_i = _rotate([k_i, v_i, kpos_i], axis_name)
    return o.astype(q.dtype), lse


def _ring_vjp_fwd(q, k, v, q_pos, kv_pos, q_seg, kv_seg, axis_name, scale,
                  causal, block_sizes, masks):
    o, lse = _ring_fwd_impl(q, k, v, q_pos, kv_pos, q_seg, kv_seg, axis_name,
                            scale, causal, block_sizes, masks)
    return o, (q, k, v, o, lse, q_pos, kv_pos, q_seg, kv_seg)


def _ring_vjp_bwd(axis_name, scale, causal, block_sizes, masks, res, do):
    q, k, v, o, lse, q_pos, kv_pos, q_seg, kv_seg = res
    b, h, sq, d = q.shape
    cp = lax.axis_size(axis_name)
    block_q = _pick_block(sq, block_sizes[0])
    block_k = _pick_block(k.shape[2], block_sizes[1])
    use_seg = q_seg is not None
    # loop-invariant across ring steps: compute once
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dq = jnp.zeros(q.shape, jnp.float32)
    # the rotating block: (k, v, their metadata, their accumulating grads)
    k_i, v_i, kpos_i, kseg_i = k, v, kv_pos, kv_seg
    dk_i = jnp.zeros(k.shape, jnp.float32)
    dv_i = jnp.zeros(v.shape, jnp.float32)
    for i in range(cp):
        dq_c, dk_c, dv_c = _masked_bwd(
            i, masks, axis_name, q, k_i, v_i, o, lse, do, q_pos, kpos_i,
            q_seg if use_seg else None, kseg_i if use_seg else None,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            delta=delta)
        dq = dq + dq_c
        dk_i = dk_i + dk_c
        dv_i = dv_i + dv_c
        # rotate the block + piggybacked grads; after cp rotations total the
        # block (with its full dk/dv) is home again
        rot = [k_i, v_i, kpos_i, dk_i, dv_i] + ([kseg_i] if use_seg else [])
        rot = _rotate(rot, axis_name)
        if use_seg:
            k_i, v_i, kpos_i, dk_i, dv_i, kseg_i = rot
        else:
            k_i, v_i, kpos_i, dk_i, dv_i = rot
    return (dq.astype(q.dtype), dk_i.astype(k.dtype), dv_i.astype(v.dtype),
            None, None, None, None)


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention(q, k, v, *, axis_name: str = "cp",
                   q_positions=None, kv_positions=None,
                   segment_ids=None, kv_segment_ids=None,
                   causal: bool = True, softmax_scale: Optional[float] = None,
                   block_q: int = 512, block_k: int = 512,
                   split: Optional[str] = "auto"):
    """Ring attention over `axis_name`. shard_map-internal: all args are the
    LOCAL shard, layout [b, s_loc, heads_loc, d]; positions are GLOBAL token
    positions of the local tokens (per-segment positions for packed rows).

    `split` names the CP split pattern the data pipeline used
    (data/bucket.py cp_split_batch: normal|stripe|sym) and turns on static
    ring-step tile skipping (the AttnInfo analog — see ring_step_masks).
    "auto": "normal" when positions are generated here (contiguous chunks),
    no skipping when the caller supplied positions (their layout is unknown).
    The positions remain the exact mask; a wrong `split` can only be wrong
    by skipping live tiles, so pass None if unsure."""
    b, s, hh, d = q.shape
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    cp_rank = lax.axis_index(axis_name)
    if split == "auto":
        split = "normal" if (q_positions is None and kv_positions is None) \
            else None
    if q_positions is None:
        # contiguous chunks: global offset = rank * s_loc
        base = cp_rank * s + jnp.arange(s, dtype=jnp.int32)
        q_positions = jnp.broadcast_to(base, (b, s))
    if kv_positions is None:
        kv_positions = q_positions
    if kv_segment_ids is None:
        kv_segment_ids = segment_ids
    cp = lax.axis_size(axis_name)
    bq = _pick_block(s, block_q)
    bk = _pick_block(s, block_k)
    if split == "sym" and s % 2 == 0:
        # blocks must respect the head/tail chunk boundary
        bq = _pick_block(s // 2, block_q)
        bk = _pick_block(s // 2, block_k)
    masks = ring_step_masks(split, s, bq, bk, cp, causal)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = _ring(qt, kt, vt, q_positions.astype(jnp.int32),
              kv_positions.astype(jnp.int32),
              segment_ids.astype(jnp.int32) if segment_ids is not None else None,
              kv_segment_ids.astype(jnp.int32) if kv_segment_ids is not None else None,
              axis_name, scale, causal, (bq, bk), masks)
    return o.transpose(0, 2, 1, 3)


def ring_attention_gspmd(q, k, v, *, strategy: ParallelStrategy,
                         segment_ids=None, position_ids=None,
                         causal: bool = True, mesh=None,
                         split: Optional[str] = "auto"):
    """Global-view wrapper: q/k/v [b, s, h, d] logically sharded
    (dp, cp, tp, -) — runs the ring inside a shard_map over the strategy mesh
    (reference: ParallelAttentionOpImpl::DoCompute dispatching AttnCommRing).

    position_ids: per-segment positions (packed rows) or None for contiguous;
    combined with segment_ids they encode exactly the causal+membership mask.

    split: CP split pattern for static ring-step tile skipping. "auto" =
    the HETU_TPU_CP_SPLIT flag when position_ids came from the data pipeline
    (whose cp_split_batch uses the same flag default — the single source of
    truth, like the reference's HETU_PARALLEL_ATTN_SPLIT), "normal" when
    positions are contiguous. Pass None for custom position layouts.
    """
    from hetu_tpu.core.mesh import current_mesh
    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError("ring_attention_gspmd needs a mesh "
                         "(use hetu_tpu.use_mesh)")
    # inside a partial-manual region (e.g. the hetero-exec pipeline's
    # shard_map over pp) the inner shard_map must be built against the
    # tracing context's AbstractMesh — its axis_types record which axes are
    # already Manual; handing it the concrete Mesh is a mesh mismatch
    abstract = jax.sharding.get_abstract_mesh()
    if abstract is not None and any(
            "Manual" in str(t) for t in getattr(abstract, "axis_types", ())):
        mesh = abstract

    # layouts come from the strategy — one source of truth with the model
    qkv_spec = strategy.act_attn().partition_spec()
    tok_spec = strategy.act_tokens().partition_spec()
    use_seg = segment_ids is not None
    use_pos = position_ids is not None
    if split == "auto":
        # the split must DESCRIBE the caller's data layout (None = not
        # declared -> no static skipping); internally-generated positions
        # are contiguous chunks = "normal" by construction.  The SCOPED
        # declaration wins over strategy.cp_split: it is set by whoever
        # actually reordered the data (the Trainer, incl. its
        # incompatible-seq fallback to 'normal'), so it is the ground truth
        # about the layout even when the strategy asked for another split.
        split = ((_DECLARED_CP_SPLIT or strategy.cp_split) if use_pos
                 else "normal")

    tp_eff = strategy.cp_tp_eff

    def local(q, k, v, seg, pos):
        if tp_eff is not None:
            # hetero ring: no static step masks yet (uneven per-member
            # shapes make the tile grids per-origin; positions still mask)
            return hetero_ring_attention(
                q, k, v, tp_eff=tp_eff, axis_name="cp", tp_axis="tp",
                segment_ids=seg if use_seg else None,
                q_positions=pos if use_pos else None,
                kv_positions=pos if use_pos else None,
                causal=causal)
        return ring_attention(
            q, k, v, axis_name="cp",
            segment_ids=seg if use_seg else None,
            q_positions=pos if use_pos else None,
            kv_positions=pos if use_pos else None,
            causal=causal, split=split)

    if not use_seg:
        segment_ids = jnp.zeros(q.shape[:2], jnp.int32)
    if not use_pos:
        position_ids = jnp.zeros(q.shape[:2], jnp.int32)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, tok_spec, tok_spec),
        out_specs=qkv_spec, check_vma=False)
    # what the "dots_attn" remat policy keeps of this route (nn/remat.py):
    # the result, for o_proj's backward.  `_ring`'s own residuals carry no
    # name, so a checkpointed block still runs the ring forward again
    return checkpoint_name(fn(q, k, v, segment_ids, position_ids),
                           "attn_out")


def ring_attention_fallback(q, k, v, *, strategy: ParallelStrategy,
                            segment_ids=None, position_ids=None,
                            causal: bool = True):
    """Global-view CP attention: GSPMD materializes KV via all-gather over
    cp — O(seq) KV memory per shard.  An explicit alternative to the ring
    (the ring is the default everywhere, including inside the pipeline);
    useful when ring latency loses to one big all-gather (short sequences).

    position_ids (per-segment positions, e.g. from cp_split_batch's
    reordered layout) drive the causal mask exactly like the ring path —
    masking by array index would let reordered tokens see their future."""
    from hetu_tpu import ops
    import jax.numpy as jnp
    if position_ids is not None and causal:
        neg = jnp.finfo(jnp.float32).min
        bias = jnp.where(
            position_ids[:, :, None] >= position_ids[:, None, :], 0.0, neg)
        out = ops.attention(q, k, v, causal=False, bias=bias[:, None],
                            segment_ids=segment_ids)
    else:
        out = ops.attention(q, k, v, causal=causal, segment_ids=segment_ids)
    return strategy.constrain(out, strategy.act_attn())


# ---------------------------------------------------------------------------
# Hetero ring: ring members with UNEQUAL effective TP degrees
# (reference: ParallelAttention.cc:949-1050 — kv head-dim resplit between
# ring neighbors with different tp).
#
# TPU mapping: the mesh stays rectangular (cp, tp); a rank with effective
# degree e < tp physically holds its kv heads e-way sharded with tp/e-fold
# replication, BLOCK-MAJOR: device t of that rank stores sender-block
# t // (tp/e) (heads [blk*H/e, (blk+1)*H/e)).  Block-major assignment makes
# every device's stored block a SUPERSET of its own q-head block, so the
# reference's head-resplit all-to-all at each ring hop degenerates into a
# LOCAL head slice: for a block of origin rank o, device (r, t) computes
# with heads at sub-offset (t % (tp/e_o)) * H/tp of the traveling buffer.
# The price is the same one the reference pays: blocks of low-tp ranks are
# tp/e-fold larger on the wire (replication) — bandwidth, not correctness.
#
# Backward: dk/dv piggyback on the rotating (padded) buffer; each device
# column t only ever touches the head range of q-block t, so when a block
# arrives home it carries the COMPLETE grads for the owner's q-block heads
# at one known sub-offset — sliced back out to the uniform [H/tp] layout
# with no grouped collectives.
# ---------------------------------------------------------------------------

def _head_slice(x, off, n):
    """dynamic_slice of n heads at (traced) head-offset `off`; x [b,h,s,d]."""
    return lax.dynamic_slice_in_dim(x, off, n, axis=1)


def _head_add(buf, upd, off):
    cur = lax.dynamic_slice_in_dim(buf, off, upd.shape[1], axis=1)
    return lax.dynamic_update_slice_in_dim(buf, cur + upd, off, axis=1)


def _hetero_pad(full, h_loc, m_max):
    pad = h_loc * m_max
    return jnp.pad(full, ((0, 0), (0, pad), (0, 0), (0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def _hetero_ring(q, k, v, q_pos, kv_pos, q_seg, kv_seg, axis_name, tp_axis,
                 scale, causal, block_sizes, tp_eff):
    o, _ = _hetero_ring_fwd_impl(q, k, v, q_pos, kv_pos, q_seg, kv_seg,
                                 axis_name, tp_axis, scale, causal,
                                 block_sizes, tp_eff)
    return o


def _hetero_geometry(axis_name, tp_axis, tp_eff):
    cp = lax.axis_size(axis_name)
    tp = lax.axis_size(tp_axis)
    if len(tp_eff) != cp:
        raise ValueError(f"tp_eff has {len(tp_eff)} entries for cp={cp}")
    for e in tp_eff:
        if tp % e:
            raise ValueError(f"tp_eff {e} must divide tp={tp}")
    m = tuple(tp // e for e in tp_eff)          # replication per rank
    return cp, tp, m, max(m)


def _hetero_blk_build(x, t, m_r, m_max, h_loc, tp_axis):
    if m_max == 1:      # fully homogeneous: the block IS the local shard
        return x
    full = lax.all_gather(x, tp_axis, axis=1, tiled=True)
    full = _hetero_pad(full, h_loc, m_max)
    return _head_slice(full, (t // m_r) * (h_loc * m_r), h_loc * m_max)


def _hetero_ring_fwd_impl(q, k, v, q_pos, kv_pos, q_seg, kv_seg, axis_name,
                          tp_axis, scale, causal, block_sizes, tp_eff):
    b, h_loc, sq, d = q.shape
    h_kv = k.shape[1]        # GQA: kv heads per device can differ from q's
    cp, tp, m, m_max = _hetero_geometry(axis_name, tp_axis, tp_eff)
    r = lax.axis_index(axis_name)
    t = lax.axis_index(tp_axis)
    m_arr = jnp.asarray(m, jnp.int32)
    m_r = m_arr[r]
    block_q = _pick_block(sq, block_sizes[0])
    block_k = _pick_block(k.shape[2], block_sizes[1])
    use_seg = q_seg is not None

    k_blk = _hetero_blk_build(k, t, m_r, m_max, h_kv, tp_axis)
    v_blk = _hetero_blk_build(v, t, m_r, m_max, h_kv, tp_axis)
    kpos_i, kseg_i = kv_pos, kv_seg

    o = jnp.zeros((b, h_loc, sq, d), jnp.float32)
    lse = jnp.full((b, h_loc, sq), NEG_INF, jnp.float32)
    k_i, v_i = k_blk, v_blk
    for i in range(cp):
        origin = (r - i) % cp
        sub = (t % m_arr[origin]) * h_kv        # head-resplit = local slice
        k_c = _head_slice(k_i, sub, h_kv)
        v_c = _head_slice(v_i, sub, h_kv)
        o_i, lse_i = _fwd(q, k_c, v_c, q_pos, kpos_i,
                          q_seg if use_seg else None,
                          kseg_i if use_seg else None,
                          scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k)
        o, lse = _merge(o, lse, o_i.astype(jnp.float32), lse_i)
        if i != cp - 1:
            rot = [k_i, v_i, kpos_i] + ([kseg_i] if use_seg else [])
            rot = _rotate(rot, axis_name)
            if use_seg:
                k_i, v_i, kpos_i, kseg_i = rot
            else:
                k_i, v_i, kpos_i = rot
    return o.astype(q.dtype), lse


def _hetero_vjp_fwd(q, k, v, q_pos, kv_pos, q_seg, kv_seg, axis_name,
                    tp_axis, scale, causal, block_sizes, tp_eff):
    o, lse = _hetero_ring_fwd_impl(q, k, v, q_pos, kv_pos, q_seg, kv_seg,
                                   axis_name, tp_axis, scale, causal,
                                   block_sizes, tp_eff)
    return o, (q, k, v, o, lse, q_pos, kv_pos, q_seg, kv_seg)


def _hetero_vjp_bwd(axis_name, tp_axis, scale, causal, block_sizes, tp_eff,
                    res, do):
    q, k, v, o, lse, q_pos, kv_pos, q_seg, kv_seg = res
    b, h_loc, sq, d = q.shape
    h_kv = k.shape[1]        # GQA: kv heads per device can differ from q's
    cp, tp, m, m_max = _hetero_geometry(axis_name, tp_axis, tp_eff)
    r = lax.axis_index(axis_name)
    t = lax.axis_index(tp_axis)
    m_arr = jnp.asarray(m, jnp.int32)
    m_r = m_arr[r]
    block_q = _pick_block(sq, block_sizes[0])
    block_k = _pick_block(k.shape[2], block_sizes[1])
    use_seg = q_seg is not None

    k_blk = _hetero_blk_build(k, t, m_r, m_max, h_kv, tp_axis)
    v_blk = _hetero_blk_build(v, t, m_r, m_max, h_kv, tp_axis)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dq = jnp.zeros(q.shape, jnp.float32)
    dk_blk = jnp.zeros(k_blk.shape, jnp.float32)
    dv_blk = jnp.zeros(v_blk.shape, jnp.float32)
    k_i, v_i, kpos_i, kseg_i = k_blk, v_blk, kv_pos, kv_seg
    for i in range(cp):
        origin = (r - i) % cp
        sub = (t % m_arr[origin]) * h_kv
        k_c = _head_slice(k_i, sub, h_kv)
        v_c = _head_slice(v_i, sub, h_kv)
        dq_c, dk_c, dv_c = _bwd(
            q, k_c, v_c, o, lse, do, q_pos, kpos_i,
            q_seg if use_seg else None, kseg_i if use_seg else None,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            delta=delta)
        dq = dq + dq_c
        dk_blk = _head_add(dk_blk, dk_c, sub)
        dv_blk = _head_add(dv_blk, dv_c, sub)
        rot = [k_i, v_i, kpos_i, dk_blk, dv_blk] + \
            ([kseg_i] if use_seg else [])
        rot = _rotate(rot, axis_name)
        if use_seg:
            k_i, v_i, kpos_i, dk_blk, dv_blk, kseg_i = rot
        else:
            k_i, v_i, kpos_i, dk_blk, dv_blk = rot
    # home again: this device column only ever touched q-block t's head
    # range, whose complete grads sit at sub-offset (t % m_r) * h_loc
    sub_home = (t % m_r) * h_kv
    dk = _head_slice(dk_blk, sub_home, h_kv)
    dv = _head_slice(dv_blk, sub_home, h_kv)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None, None, None)


_hetero_ring.defvjp(_hetero_vjp_fwd, _hetero_vjp_bwd)


def hetero_ring_attention(q, k, v, *, tp_eff, axis_name: str = "cp",
                          tp_axis: str = "tp", q_positions=None,
                          kv_positions=None, segment_ids=None,
                          kv_segment_ids=None, causal: bool = True,
                          softmax_scale: Optional[float] = None,
                          block_q: int = 512, block_k: int = 512):
    """Ring attention where ring member r runs at effective TP degree
    tp_eff[r] (each a divisor of the mesh tp size).  shard_map-internal;
    local layout [b, s_loc, heads_loc, d] like ring_attention.  With all
    tp_eff == tp this is numerically the homogeneous ring."""
    b, s, hh, d = q.shape
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    cp_rank = lax.axis_index(axis_name)
    if q_positions is None:
        base = cp_rank * s + jnp.arange(s, dtype=jnp.int32)
        q_positions = jnp.broadcast_to(base, (b, s))
    if kv_positions is None:
        kv_positions = q_positions
    if kv_segment_ids is None:
        kv_segment_ids = segment_ids
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = _hetero_ring(
        qt, kt, vt, q_positions.astype(jnp.int32),
        kv_positions.astype(jnp.int32),
        segment_ids.astype(jnp.int32) if segment_ids is not None else None,
        kv_segment_ids.astype(jnp.int32) if kv_segment_ids is not None
        else None,
        axis_name, tp_axis, scale, causal, (block_q, block_k),
        tuple(int(e) for e in tp_eff))
    return o.transpose(0, 2, 1, 3)
