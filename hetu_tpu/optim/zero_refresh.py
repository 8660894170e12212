"""Quantized ZeRO-1/2 parameter refresh (HETU_TPU_ZERO_COMPRESS).

Under ZeRO the optimizer state is dp-sharded (`optim.zero_shardings`)
and GSPMD's lowering of the update ends in an **f32 all-gather of the
fresh parameters** — the param-refresh bytes ROADMAP item 3 names as
still-uncompressed.  This module replaces that implicit gather with an
explicit one that ships the parameter **delta** quantized:

    shard_map over dp:
      slice params + grads to my opt-state shard      (local, no comm)
      run the optimizer update on the shard           (exact, f32)
      delta = new_shard - old_shard                   (lr-magnitude values)
      all-gather delta as blockwise int8/int4 + f32 scales
      params += dequantized delta                     (replicated again)

Gathering the DELTA instead of the parameters is the load-bearing
choice: updates are lr-scale, so the absmax/qmax quantization error is
relative to the *step*, not the weight — a naive quantized-params gather
would freeze weights whose per-step movement is smaller than their int8
grid step (absmax/127 of the weight).  Every rank applies the SAME
dequantized delta (its own shard included), so replicas stay bitwise
identical and no master-state divergence can accumulate across ranks.

Envelope: the same homogeneous DP one as the compressed grad sync
(dp > 1, tp = cp = pp = ep = 1, zero_stage 1-2) — `Trainer` enforces it
loudly.  Refresh bytes drop 4/(1+4/B) ~ 3.94x (int8) or ~7.76x (int4)
vs the f32 param all-gather (comm/wire.py), verified from lowered HLO by
the obs.comm analyzer.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from hetu_tpu.comm.collectives import all_gather_q
from hetu_tpu.comm.wire import DEFAULT_BLOCK

#: leaf marker for "this leaf's opt state is not dp-sharded"
UNSHARDED = -1


def refresh_dims(opt_shardings, axis: str = "dp"):
    """Per-leaf index of the dim `zero_shardings` split over `axis`
    (UNSHARDED when the leaf stayed replicated) — the static slicing
    plan of the quantized refresh."""
    def one(ns):
        for d, entry in enumerate(ns.spec):
            axes = (entry,) if isinstance(entry, str) else tuple(entry or ())
            if axis in axes:
                return d
        return UNSHARDED
    return jax.tree.map(one, opt_shardings)


def refresh_specs(opt_shardings):
    """Per-leaf PartitionSpecs of the dp-sharded opt state (shard_map
    in/out specs for the m/v trees)."""
    return jax.tree.map(lambda ns: ns.spec, opt_shardings)


def quantized_zero_update(optimizer, grads, opt_state, params, *, mesh,
                          dims, specs, mode: str,
                          block_size: int = DEFAULT_BLOCK,
                          axis: str = "dp", grads_sharded: bool = False):
    """Drop-in for `optimizer.update(grads, opt_state, params)` under the
    quantized ZeRO refresh: returns (new_params replicated, new opt state
    still dp-sharded).  `dims`/`specs` from `refresh_dims`/`refresh_specs`
    of the m-tree shardings; `grads_sharded=True` when the caller already
    constrained grads to the opt-state sharding (ZeRO-2)."""
    from jax import shard_map

    if not {"step", "m", "v"} <= set(opt_state):
        # the body threads the AdamW slot layout explicitly; a different
        # optimizer's slots would be silently dropped — refuse instead
        raise ValueError(
            "quantized_zero_update supports the AdamW optimizer-state "
            "layout {step, m, v}; got "
            f"{sorted(opt_state)} — extend the body's slot threading "
            "before enabling HETU_TPU_ZERO_COMPRESS with this optimizer")
    dp = int(mesh.shape[axis])
    from hetu_tpu.obs import numerics as _numerics

    def body(params, grads, m, v, step):
        i = lax.axis_index(axis)

        def shard(x, d):
            if d == UNSHARDED:
                return x
            size = x.shape[d] // dp
            return lax.dynamic_slice_in_dim(x, i * size, size, axis=d)

        with _numerics.frame() as nf:
            p_sh = jax.tree.map(shard, params, dims)
            g_sh = (grads if grads_sharded
                    else jax.tree.map(shard, grads, dims))
            new_p_sh, new_state = optimizer.update(
                g_sh, {"step": step, "m": m, "v": v}, p_sh)

            def refresh(p_full, p_s, np_s, d):
                if d == UNSHARDED:
                    return np_s  # updated exactly, replicated
                delta = (np_s.astype(jnp.float32)
                         - p_s.astype(jnp.float32))
                dfull = all_gather_q(delta, axis, axis=d, tiled=True,
                                     mode=mode, block_size=block_size)
                if _numerics.active():
                    # exact delta-gather quantization error: my shard's
                    # reconstruction is my slice of the gathered full
                    size = delta.shape[d]
                    mine = lax.dynamic_slice_in_dim(
                        dfull, i * size, size, axis=d)
                    _numerics.tap_quant_error("zero_refresh", delta,
                                              delta - mine)
                return (p_full.astype(jnp.float32)
                        + dfull).astype(p_full.dtype)

            new_params = jax.tree.map(refresh, params, p_sh, new_p_sh,
                                      dims)
        nstats = _numerics.reduce_axis(nf.stats, axis)
        return (new_params, new_state["m"], new_state["v"],
                new_state["step"], nstats)

    gspec: Any = specs if grads_sharded else P()
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(), gspec, specs, specs, P()),
        out_specs=(P(), specs, specs, P(), P()),
        # the gathered params ARE replicated over dp but the checker
        # cannot infer that through the quantized gather
        check_vma=False)
    new_params, new_m, new_v, new_step, nstats = fn(
        params, grads, opt_state["m"], opt_state["v"], opt_state["step"])
    # stats folded across dp inside the body are step-level values here:
    # hand them back to the ambient collector (no-op when inactive)
    _numerics.merge(nstats)
    return new_params, {"step": new_step, "m": new_m, "v": new_v}
