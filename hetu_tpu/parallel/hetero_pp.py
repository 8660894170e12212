"""Per-stage sub-mesh heterogeneity inside ONE pipeline program.

The last structural hetero capability of the reference: a pipeline whose
stages run at UNEQUAL tensor-parallel degrees, expressed as
DistributedStatesUnions over unequal device groups and deduced per stage
(reference: hetu/graph/distributed_states.h:158-321 + define_and_run_graph.cc
:159 DeducePipeline). On a rectangular TPU mesh the per-stage degree becomes
an EFFECTIVE degree e_s (a divisor of the mesh tp extent) with
m_s = tp/e_s-fold block-major replication — the same trick the hetero CP
ring uses for unequal-TP ring members (parallel/ring_attention.py
_hetero_blk_build): device t of a stage computes head/channel block
t // m_s, so every needed weight block is a LOCAL slice of an all-gathered
buffer, and the row-parallel reduction is psum(partial)/m_s (each distinct
block contributes m_s identical copies).

Execution model: ONE jit program, `jax.shard_map` manual over (pp, tp) —
dp/cp stay automatic — with a `lax.switch` on the stage index choosing that
stage's static (e_s, layer_count) branch. Stage layer counts compose with
the degree heterogeneity (a Malleus plan sets both).

The price is the reference's own price for hetero TP: replicated compute on
low-degree stages (m_s-fold) + the per-layer weight all-gather. The planner
weighs that against what it buys (e.g. smaller TP collectives on the
latency-bound stages); this module only makes the layout EXECUTABLE in one
program.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from hetu_tpu.parallel.pipeline import build_stage_stack


# check_vma=True is load-bearing here, not just a lint: with it off, JAX
# wraps every op in the manual body in unspecified-sharding constraints,
# and the one landing INSIDE a bf16 psum's reducer region becomes a `copy`
# HLO that crashes XLA:CPU's AllReducePromotion pass (CloneAllReduce ->
# CreateBinary(copy) check-fail) under the full dp+ZeRO+remat train step.
# The pvary/align/16-bit-widening idiom lives in core.vma (shared with the
# pipeline stage bodies).
from hetu_tpu.core.vma import align as _al
from hetu_tpu.core.vma import pvary_missing as _pv
from hetu_tpu.core.vma import vma_of as _vma_of
from hetu_tpu.core.vma import _widen_16bit


def _psum_wide(x, axis):
    """psum with f32 accumulation for 16-bit inputs.

    Two birds: wider reduction numerics, and a hard guarantee that no 16-bit
    all-reduce is emitted from this partial-manual region — XLA:CPU's
    AllReducePromotion pass check-fails (CreateBinary on a `copy` reducer
    root) on 16-bit all-reduces whose reducer carries the partial-manual
    sdy constraint (see _pv docstring; minimal repro: bf16 psum inside a
    shard_map with any auto axis)."""
    if _widen_16bit() and x.dtype in (jnp.bfloat16, jnp.float16):
        return lax.psum(x.astype(jnp.float32), axis).astype(x.dtype)
    return lax.psum(x, axis)


def _sp_compress_mode() -> str:
    """HETU_TPU_SP_COMPRESS routing for the SP edges below: int8/int4
    move the seq gathers/scatters as quantized payloads
    (comm/collectives.py custom-vjp collectives — backward transports
    quantize too); "none" keeps the exact lax calls byte-identical."""
    from hetu_tpu.comm.collectives import sp_mode
    return sp_mode()


def _reduce_out(x, axis, *, sp: bool, seq_dim: int = 1):
    """The row-parallel output reduction: all-reduce (plain TP) or
    reduce-scatter onto the seq dim (Megatron-SP) — same 16-bit widening
    guard as _psum_wide."""
    if not sp:
        return _psum_wide(x, axis)
    mode = _sp_compress_mode()
    if mode != "none":
        # the quantized scatter is f32-wire by construction (int payload,
        # f32 scales, f32 dequant) so the 16-bit widening guard below is
        # moot on this path
        from hetu_tpu.comm.collectives import reduce_scatter_q
        return reduce_scatter_q(
            x.astype(jnp.float32), axis, scatter_dimension=seq_dim,
            tiled=True, mode=mode).astype(x.dtype)
    if _widen_16bit() and x.dtype in (jnp.bfloat16, jnp.float16):
        return lax.psum_scatter(
            x.astype(jnp.float32), axis, scatter_dimension=seq_dim,
            tiled=True).astype(x.dtype)
    return lax.psum_scatter(x, axis, scatter_dimension=seq_dim, tiled=True)


def _gather_seq(x, axis, *, sp: bool, seq_dim: int = 1):
    """SP regions enter the projections through a seq all-gather.

    On the cpu backend 16-bit inputs gather in f32: the TRANSPOSE of a
    tiled all-gather is a psum_scatter of the cotangent, and a 16-bit
    reduce-scatter from a partial-manual region hits the same XLA:CPU
    AllReducePromotion check-fail as 16-bit psums (see _psum_wide) —
    widening around the gather keeps that transpose f32."""
    if not sp:
        return x
    mode = _sp_compress_mode()
    if mode != "none":
        from hetu_tpu.comm.collectives import all_gather_q
        return all_gather_q(
            x.astype(jnp.float32), axis, axis=seq_dim, tiled=True,
            mode=mode).astype(x.dtype)
    if _widen_16bit() and x.dtype in (jnp.bfloat16, jnp.float16):
        return lax.all_gather(x.astype(jnp.float32), axis, axis=seq_dim,
                              tiled=True).astype(x.dtype)
    return lax.all_gather(x, axis, axis=seq_dim, tiled=True)


def _blk(w, dim: int, t, e: int, m: int, tp_axis: str):
    """Block-major effective-degree weight slice: the [dim]-sharded weight's
    block t//m of e, as a LOCAL slice of the tp all-gather (m==1: the local
    shard IS the block)."""
    if m == 1:
        return w
    full = lax.all_gather(w, tp_axis, axis=dim, tiled=True)
    size_e = full.shape[dim] // e
    idx = _pv((t // m) * size_e, jax.typeof(full).vma)
    return lax.dynamic_slice_in_dim(full, idx, size_e, axis=dim)


def llama_block_maker(cfg, cos, sin, *, tp: int, tp_axis: str = "tp",
                      sequence_parallel: bool = False):
    """block_maker(e, m) -> block_fn(layer_params, x, pos, seg) -> (x, aux)
    running the LLaMA block manual-over-tp at effective degree e.

    Mirrors models/llama/model.py LlamaBlock exactly (pre-norm, fused qkv
    [h, n_kv, group+2, hd], RoPE, flash attention, row o_proj, SwiGLU MLP)
    — golden-parity tested against it. Dense only (no MoE/dropout here).
    sequence_parallel: between-block activations arrive seq-sharded over
    the FULL tp axis (Megatron-SP in manual form — all-gather into the
    projections, reduce-scatter out of the row-parallel matmuls; weight
    blocks still replicate m-fold at effective degree e)."""
    from hetu_tpu import ops

    hd = cfg.head_dim
    n_q, n_kv = cfg.num_attention_heads, cfg.num_key_value_heads
    group = n_q // n_kv
    sp = sequence_parallel

    def maker(e: int, m: int) -> Callable:
        if n_kv % e:
            raise ValueError(f"num_key_value_heads={n_kv} must divide by "
                             f"effective tp degree {e}")
        kv_e = n_kv // e

        def block(lp, x, pos, seg, rng=None):
            t = lax.axis_index(tp_axis)
            nw, nw2 = _al(lp["input_norm"]["weight"], lp["post_norm"]["weight"],
                          x)[:2]
            xin = _gather_seq(ops.rms_norm(x, nw, cfg.rms_norm_eps),
                              tp_axis, sp=sp)
            b, s, h = xin.shape
            wqkv = _blk(lp["attn"]["wqkv"], 1, t, e, m, tp_axis)
            xin_t, wqkv = _al(xin, wqkv)
            qkv = jnp.einsum("bsh,hkgd->bskgd", xin_t,
                             wqkv.astype(x.dtype))
            q = qkv[..., :group, :].reshape(b, s, kv_e * group, hd)
            k = qkv[..., group, :]
            v = qkv[..., group + 1, :]
            q, k, cos_a, sin_a, pos_a = _al(q, k, cos, sin,
                                            jnp.zeros((), jnp.int32)
                                            if pos is None else pos)
            pos_a = None if pos is None else pos_a
            q = ops.apply_rotary(q, cos_a, sin_a, pos_a)
            k = ops.apply_rotary(k, cos_a, sin_a, pos_a)
            if seg is not None:
                q, k, v, seg = _al(q, k, v, seg)
            else:
                q, k, v = _al(q, k, v)
            attn = ops.flash_attention(
                q, k, v, causal=True, segment_ids=seg,
                use_pallas=None if cfg.use_flash_attention else False)
            wo = _blk(lp["attn"]["o_proj"]["weight"], 0, t, e, m, tp_axis)
            attn2, wo = _al(attn.reshape(b, s, kv_e * group * hd), wo)
            if rng is not None and sp:
                # SP: each tp rank holds a DISTINCT seq chunk — fold the
                # rank in so masks are independent per token (non-SP keeps
                # the shared key: replicated activations need identical
                # masks across the m-fold block replicas)
                rng = jax.random.fold_in(rng, t)
            h1 = attn2 @ wo.astype(x.dtype)
            h1, x = _al(_reduce_out(h1, tp_axis, sp=sp) / m, x)
            if rng is not None and cfg.hidden_dropout > 0.0:
                # same (micro, layer)-keyed folds as LlamaBlock.forward
                h1 = ops.dropout(h1, cfg.hidden_dropout,
                                 jax.random.fold_in(rng, 2), False)
            x = x + h1
            xin2 = _gather_seq(
                ops.rms_norm(x, _al(nw2, x)[0], cfg.rms_norm_eps),
                tp_axis, sp=sp)
            wgu = _blk(lp["mlp"]["w_gate_up"], 2, t, e, m, tp_axis)
            xin2_t, wgu = _al(xin2, wgu)
            gu = jnp.einsum("bsh,hci->bsci", xin2_t, wgu.astype(x.dtype))
            hidden = ops.swiglu(gu[:, :, 0, :], gu[:, :, 1, :])
            wd = _blk(lp["mlp"]["down_proj"]["weight"], 0, t, e, m, tp_axis)
            hidden, wd = _al(hidden, wd)
            h2 = hidden @ wd.astype(x.dtype)
            h2, x = _al(_reduce_out(h2, tp_axis, sp=sp) / m, x)
            if rng is not None and cfg.hidden_dropout > 0.0:
                h2 = ops.dropout(h2, cfg.hidden_dropout,
                                 jax.random.fold_in(rng, 3), False)
            return x + h2, jnp.zeros((), jnp.float32)

        return block

    return maker


def gpt_block_maker(cfg, *, tp: int, tp_axis: str = "tp",
                    sequence_parallel: bool = False):
    """block_maker(e, m) -> block_fn(layer_params, x, pos, seg) -> (x, 0)
    running the GPT block manual-over-tp at effective degree e.

    Mirrors models/gpt/model.py GPTBlock exactly (pre-LN, fused qkv
    [h, n, 3, hd] + bias, flash attention, row o_proj + bias, GELU MLP
    with biases) — golden-parity tested against it.  Dense, no dropout
    (the hetero envelope ParallelStrategy.validate enforces).
    sequence_parallel: see llama_block_maker."""
    from hetu_tpu import ops

    hd = cfg.head_dim
    n_heads = cfg.num_attention_heads
    sp = sequence_parallel

    def maker(e: int, m: int) -> Callable:
        if n_heads % e:
            raise ValueError(f"num_attention_heads={n_heads} must divide "
                             f"by effective tp degree {e}")
        n_e = n_heads // e

        def block(lp, x, pos, seg, rng=None):
            t = lax.axis_index(tp_axis)
            ln1w, ln1b, ln2w, ln2b = _al(
                lp["ln1"]["weight"], lp["ln1"]["bias"],
                lp["ln2"]["weight"], lp["ln2"]["bias"], x)[:4]
            xin = _gather_seq(
                ops.layer_norm(x, ln1w, ln1b, cfg.layer_norm_eps),
                tp_axis, sp=sp)
            b, s, h = xin.shape
            wqkv = _blk(lp["attn"]["wqkv"], 1, t, e, m, tp_axis)
            bqkv = _blk(lp["attn"]["bqkv"], 0, t, e, m, tp_axis)
            xin_t, wqkv, bqkv = _al(xin, wqkv, bqkv)
            qkv = jnp.einsum("bsh,hngd->bsngd", xin_t,
                             wqkv.astype(x.dtype)) + bqkv.astype(x.dtype)
            q = qkv[..., 0, :]
            k = qkv[..., 1, :]
            v = qkv[..., 2, :]
            if seg is not None:
                q, k, v, seg = _al(q, k, v, seg)
            else:
                q, k, v = _al(q, k, v)
            attn = ops.flash_attention(
                q, k, v, causal=True, segment_ids=seg,
                use_pallas=None if cfg.use_flash_attention else False)
            wo = _blk(lp["attn"]["o_proj"]["weight"], 0, t, e, m, tp_axis)
            attn2, wo = _al(attn.reshape(b, s, n_e * hd), wo)
            h1 = attn2 @ wo.astype(x.dtype)
            # row-parallel bias adds ONCE, after the reduction
            if rng is not None and sp:
                # per-rank fold under SP (see llama counterpart)
                rng = jax.random.fold_in(rng, t)
            h1, ob, x = _al(_reduce_out(h1, tp_axis, sp=sp) / m,
                            lp["attn"]["o_proj"]["bias"], x)
            h1 = h1 + ob.astype(x.dtype)
            if rng is not None and cfg.hidden_dropout > 0.0:
                # same folds as GPTBlock.forward (bias included, like the
                # homogeneous RowParallelLinear output)
                h1 = ops.dropout(h1, cfg.hidden_dropout,
                                 jax.random.fold_in(rng, 2), False)
            x = x + h1
            xin2 = _gather_seq(
                ops.layer_norm(x, ln2w, ln2b, cfg.layer_norm_eps),
                tp_axis, sp=sp)
            w_up = _blk(lp["mlp"]["w_up"], 1, t, e, m, tp_axis)
            b_up = _blk(lp["mlp"]["b_up"], 0, t, e, m, tp_axis)
            xin2_t, w_up, b_up = _al(xin2, w_up, b_up)
            y = xin2_t @ w_up.astype(x.dtype) + b_up.astype(x.dtype)
            y = ops.gelu(y)
            wd = _blk(lp["mlp"]["down"]["weight"], 0, t, e, m, tp_axis)
            y, wd = _al(y, wd)
            h2 = y @ wd.astype(x.dtype)
            h2, db, x = _al(_reduce_out(h2, tp_axis, sp=sp) / m,
                            lp["mlp"]["down"]["bias"], x)
            h2 = h2 + db.astype(x.dtype)
            if rng is not None and cfg.hidden_dropout > 0.0:
                h2 = ops.dropout(h2, cfg.hidden_dropout,
                                 jax.random.fold_in(rng, 3), False)
            x = x + h2
            return x, jnp.zeros((), jnp.float32)

        return block

    return maker


def _manual_specs(param_spec_tree, keep=("pp", "tp"), lead=("pp", None)):
    """Model ParamSpec tree (one layer) -> PartitionSpecs naming ONLY the
    manual axes (auto axes like dp must stay unmentioned), with the stacked
    (pp, layer) lead dims prepended."""
    from hetu_tpu.nn.module import ParamSpec

    def one(psp):
        ds = getattr(psp, "ds", None)
        if ds is None:
            return P(*lead)
        ent = []
        for axes in ds.spec:
            ax = [a for a in (axes or ()) if a in keep]
            ent.append(ax[0] if len(ax) == 1 else (tuple(ax) or None))
        return P(*(lead + tuple(ent)))
    return jax.tree.map(one, param_spec_tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def _hetero_switch_stack(block_maker: Callable, param_ds_tree, mesh, *,
                         pp: int, tp: int, tp_eff: Sequence[int],
                         stage_layers: Sequence[int], remat: bool,
                         remat_policy: str, token_keys=(),
                         pp_axis: str = "pp", tp_axis: str = "tp",
                         sequence_parallel: bool = False):
    """shard_map'ed (stage_params, x_buf [pp, mb, s, h], tok_buf) ->
    (y_buf, aux_row [pp]): manual over (pp, tp) with a `lax.switch` on the
    stage index choosing that stage's static (tp_eff, layer-count) branch.
    ONE builder shared by the GPipe hetero pipeline and the 1F1B hetero
    round bodies.  Under SP the x buffer enters/leaves seq-sharded over
    the tp axis (the block maker must be built sequence_parallel too).

    Dropout: when a "dropout_rng" rider is present (the build_dropout_ride
    scheme — per-micro uint32 bits on the token stream), each layer's key
    is fold_in(key(bits), global_layer_id) with the stage's STATIC layer
    offset, and the block is called with rng=key.  The rider is replicated
    over tp, so tp replicas draw identical masks (consistency under
    block-major replication); the 1F1B backward visit replays exactly
    because the saved rider re-derives the same keys inside the vjp."""
    import numpy as np

    offs = np.concatenate([[0], np.cumsum(list(stage_layers))[:-1]])
    has_rng = "dropout_rng" in token_keys

    def stage_branch(stage_i: int):
        e = tp_eff[stage_i]
        m = tp // e
        k_s = stage_layers[stage_i]
        block = block_maker(e, m)
        off = int(offs[stage_i])

        def run(sp1, x_mb, tok1):
            micro_key = (jax.random.key(tok1["dropout_rng"][0, 0])
                         if has_rng else None)

            def body(carry, xs):
                lp, gid = xs
                x_c, aux_c = carry
                kw = {}
                if has_rng:
                    kw["rng"] = jax.random.fold_in(micro_key, gid)
                out, aux = block(lp, x_c, tok1.get("position_ids"),
                                 tok1.get("segment_ids"), **kw)
                return (out, aux_c + aux), None

            fn = body
            if remat:
                from hetu_tpu.nn.remat import remat_policy as _policy
                fn = jax.checkpoint(body, policy=_policy(remat_policy))
            sliced = jax.tree.map(lambda a: a[:k_s], sp1)
            gids = jnp.arange(off, off + k_s, dtype=jnp.uint32)
            (y, aux), _ = lax.scan(
                fn, (x_mb, jnp.zeros((), jnp.float32)), (sliced, gids))
            return y, aux

        return run

    pspecs = _manual_specs(param_ds_tree, keep=(pp_axis, tp_axis),
                           lead=(pp_axis, None))

    def manual(sp, x_b, tok_b):
        # local views: stage dim extent 1, weights local tp shards
        sp1 = jax.tree.map(lambda a: a[0], sp)
        tok1 = {k: v[0] for k, v in tok_b.items()}
        p = lax.axis_index(pp_axis)
        branches = [stage_branch(i) for i in range(pp)]
        y, aux = lax.switch(p, branches, sp1, x_b[0], tok1)
        return y[None], jnp.reshape(aux, (1,)).astype(jnp.float32)

    Ppp = P(pp_axis)
    # [pp, mb, s, h] buffers: seq dim manual-sharded over tp under SP
    Px = P(pp_axis, None, tp_axis) if sequence_parallel else Ppp
    return jax.shard_map(
        manual, mesh=mesh,
        in_specs=(pspecs, Px, {k: Ppp for k in token_keys}),
        out_specs=(Px, Ppp),
        axis_names=frozenset({pp_axis, tp_axis}), check_vma=True)


def hetero_tp_1f1b_rounds(block_maker: Callable, param_ds_tree, embed_fn,
                          head_fn, *, mesh, pp: int, tp: int,
                          tp_eff: Sequence[int], stage_layers: Sequence[int],
                          remat: bool, remat_policy: str, compute_dtype,
                          token_keys=(), pp_axis: str = "pp",
                          tp_axis: str = "tp",
                          sequence_parallel: bool = False):
    """(vfwd, vbwd) round bodies for `pipeline_train_1f1b(custom_rounds=...)`
    running each stage at effective TP degree tp_eff[s].

    Design: the decoder stack runs under the manual-(pp, tp) switch body
    (_hetero_switch_stack), while the EDGES — the tp-sharded vocab embedding
    and the loss head — run in auto (GSPMD) mode outside the manual region,
    composed per round:

        y = switch_stack(where(stage==0, embed(ids), x_in))
        ce = head(y[last], labels)

    That keeps the known partitioner crash (a sharded gather partitioned
    inside a partial-manual region, see pipeline_1f1b.py skip_dead_halves)
    out of the program: the embedding gather is a plain auto-mode op, and
    the manual region contains only the block math the GPipe hetero path
    already differentiates (topology-8 dryrun).  The backward round is a
    `jax.vjp` of the composed round function, seeded with the engine's
    per-stage cotangent rows — exact 1F1B semantics because the round
    function is row-wise independent across stages.

    embed_fn(edge_params, feed_b, feed_s) -> [mb, s, h] hidden (auto mode;
      feed_b carries "ids"/"labels", feed_s the token riders — GPT's wpe
      needs the positions);
    head_fn(edge_params, y [mb, s, h], labels) -> summed CE scalar.
    """
    import numpy as np

    vstack = _hetero_switch_stack(
        block_maker, param_ds_tree, mesh, pp=pp, tp=tp, tp_eff=tp_eff,
        stage_layers=stage_layers, remat=remat, remat_policy=remat_policy,
        token_keys=token_keys, pp_axis=pp_axis, tp_axis=tp_axis,
        sequence_parallel=sequence_parallel)

    first = jnp.asarray(np.arange(pp) == 0)
    last_idx = pp - 1

    def round_fn(sp, ep, x_in, feed_b, feed_s):
        emb = embed_fn(ep, feed_b, feed_s).astype(compute_dtype)
        x0 = jnp.where(first[:, None, None, None], emb[None], x_in)
        y, aux_row = vstack(sp, x0, feed_s)
        ce = head_fn(ep, y[last_idx], feed_b["labels"])
        ce_row = jnp.zeros((pp,), jnp.float32).at[last_idx].set(
            jnp.asarray(ce, jnp.float32))
        return y, ce_row, aux_row

    def vfwd(sp, ep, x, fb, fs, fl, fv):
        return round_fn(sp, ep, x, fb, fs)

    def vbwd(sp, ep, x, fb, fs, fl, dy, dce, daux, bv):
        fn = lambda sp_, ep_, x_: round_fn(sp_, ep_, x_, fb, fs)
        _, vjp = jax.vjp(fn, sp, ep, x)
        dsp, dep, dx = vjp((dy, dce, daux))
        # the engine accumulates edge grads with a leading pp dim (one row
        # per stage); the composed round used the edges once — record the
        # whole contribution on row 0
        dep = jax.tree.map(
            lambda g: jnp.zeros((pp,) + g.shape, jnp.float32)
            .at[0].set(g.astype(jnp.float32)), dep)
        return dsp, dep, dx

    return vfwd, vbwd


def staged_stack_forward_hetero_tp(
        block_maker: Callable, param_ds_tree, stack_params, x, *,
        num_layers: int, pp: int, tp: int, tp_eff: Sequence[int], mesh,
        position_ids=None, segment_ids=None, stage_layers=None,
        n_micro: Optional[int] = None, remat: bool = True,
        remat_policy: str = "nothing", state_spec=None,
        pp_axis: str = "pp", tp_axis: str = "tp",
        sequence_parallel: bool = False, rng=None):
    """GPipe pipeline where stage s runs at effective TP degree tp_eff[s].

    block_maker(e, m) -> block_fn(local_layer_params, x_mb, pos, seg[, rng]);
    param_ds_tree: the model's per-layer DS tree (for the manual in_specs).
    rng enables hidden dropout inside the hetero pipeline (the
    build_dropout_ride per-micro-bits scheme; see _hetero_switch_stack).
    Everything else mirrors pipeline.staged_stack_forward."""
    tp_eff = tuple(int(e) for e in tp_eff)
    if len(tp_eff) != pp:
        raise ValueError(f"tp_eff has {len(tp_eff)} entries for pp={pp}")
    for e in tp_eff:
        if e < 1 or tp % e:
            raise ValueError(f"tp_eff {e} must divide mesh tp={tp}")

    B, s, h = x.shape
    if n_micro is None:
        n_micro = pp
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro
    T = n_micro + pp - 1
    pad = pp - 1
    spec = state_spec if state_spec is not None else P(pp_axis)
    tok_spec = P(*((spec[0],) + tuple(spec[1:3])))

    stage_params, _, stage_layers = build_stage_stack(
        stack_params, num_layers, pp, stage_layers)

    token_data = {}
    if position_ids is not None:
        token_data["position_ids"] = position_ids
    if segment_ids is not None:
        token_data["segment_ids"] = segment_ids
    if rng is not None:
        from hetu_tpu.parallel.pipeline_1f1b import build_dropout_ride
        token_data["dropout_rng"], _ = build_dropout_ride(
            rng, n_micro, (B, s), stage_layers)

    xm = x.reshape(n_micro, mb, s, h)
    tok = {k: v.reshape(n_micro, mb, s) for k, v in token_data.items()}

    vbody = _hetero_switch_stack(
        block_maker, param_ds_tree, mesh, pp=pp, tp=tp, tp_eff=tp_eff,
        stage_layers=stage_layers, remat=remat, remat_policy=remat_policy,
        token_keys=tuple(token_data), pp_axis=pp_axis, tp_axis=tp_axis,
        sequence_parallel=sequence_parallel)

    def shift_in(new, state, sp=None):
        out = jnp.concatenate([new[None], state[:-1]], axis=0)
        return lax.with_sharding_constraint(
            out, sp if sp is not None else spec)

    if pad:
        xs_x = jnp.concatenate(
            [xm, jnp.zeros((pad,) + xm.shape[1:], xm.dtype)])
        xs_tok = {k: jnp.concatenate(
            [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)])
            for k, v in tok.items()}
    else:
        xs_x, xs_tok = xm, tok

    init_x = lax.with_sharding_constraint(
        jnp.zeros((pp, mb, s, h), x.dtype), spec)
    init_tok = {k: jnp.zeros((pp, mb, s), v.dtype) for k, v in tok.items()}

    ticks = jnp.arange(T)
    stages = jnp.arange(pp)
    micro_idx = ticks[:, None] - stages[None, :]
    aux_mask = ((micro_idx >= 0) & (micro_idx < n_micro)).astype(jnp.float32)

    def step(carry, xs_t):
        state_x, state_tok = carry
        in_x, in_tok, mask_t = xs_t
        cur_x = shift_in(in_x, state_x)
        cur_tok = {k: shift_in(in_tok[k], state_tok[k], tok_spec)
                   for k in state_tok}
        out_x, aux = vbody(stage_params, cur_x, cur_tok)
        aux = jnp.sum(aux * mask_t)
        out_x = lax.with_sharding_constraint(out_x, spec)
        return (out_x, cur_tok), (out_x[-1], aux)

    _, (ys, auxs) = lax.scan(step, (init_x, init_tok),
                             (xs_x, xs_tok, aux_mask))
    outs = ys[pad:] if pad else ys
    return outs.reshape(B, s, h), jnp.sum(auxs)