"""Explicit expert-parallel MoE dispatch (HetuMoE's HAllToAll made ours).

The default MoE path (`nn/moe.py`, `HETU_TPU_MOE_DISPATCH` unset or
"gspmd") expresses token->expert movement as sharding constraints and
lets GSPMD choose the collectives: full-width fp32/bf16 transports,
invisible to the cost model and replicated routing work over the `ep`
axis.  This module is the flag's explicit alternative: one `shard_map`
over the mesh that

  1. routes IDENTICALLY to the GSPMD path (same `sort_routing` plan per
     data group — the bit-compare contract the goldens pin), with each
     `ep` rank scattering only its 1/ep share of the (token, slot)
     pairs into a partial `[E, C, h]` buffer (the replicated scatter
     work the GSPMD path pays is split ep-ways),
  2. delivers expert buffers with a dispatch ALL-TO-ALL + sum over `ep`
     (`comm/collectives.all_to_all_q` — int8/int4 blockwise payloads
     with f32 block scales under the quantized modes, exact `lax`
     collectives under "fp32"; the custom-vjp transpose quantizes the
     backward transport too),
  3. runs the local expert shard's SwiGLU, and
  4. combines with an ALL-GATHER of expert outputs over `ep`
     (`all_gather_q`, same mode).

With `HETU_TPU_COMM_TOPOLOGY=two_level` and a topology that
`applies(ep)` (comm/topology.py), both transports run HIERARCHICALLY —
the HetuMoE HAllToAll schedule: an intra-slice a2a exchanges
position-keyed bundles at intra rates, then only the 1/slice
slice-aggregated bundles cross the strided inter-slice transversals
(byte math in `comm/wire.py::moe_two_level_dispatch_bytes`; the
analyzer obs.comm prices the lowered groups at the two rates).

Envelope: ep > 1, tp == 1, pp == 1, sort dispatch, (tokens * slots)
divisible by ep — anything else raises loudly at trace time (the
grad-compress pattern).  ep == 1 or the dense parity dispatcher keep
the GSPMD path regardless of the flag.  See docs/moe.md.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from hetu_tpu.comm.collectives import all_gather_q, all_to_all_q
from hetu_tpu.comm.topology import Topology, load_topology, topology_mode
from hetu_tpu.comm.wire import DEFAULT_BLOCK
from hetu_tpu.core.mesh import EP_AXIS, current_mesh

#: HETU_TPU_MOE_DISPATCH values; "gspmd" = the constraint-based path
MODES = ("gspmd", "fp32", "int8", "int4")


def dispatch_mode() -> str:
    """The HETU_TPU_MOE_DISPATCH flag value."""
    from hetu_tpu.utils import flags
    return flags.str_flag("HETU_TPU_MOE_DISPATCH")


def resolved_mode(strategy) -> str:
    """The dispatch mode this trace actually takes: the flag, demoted to
    "gspmd" when there is no ep axis to dispatch over (the flag is a
    no-op at ep=1 — single-device serving decode, the canonical MoE
    program)."""
    mode = dispatch_mode()
    if mode != "gspmd" and strategy.ep <= 1:
        return "gspmd"
    return mode


def two_level_topology(ep: int) -> Optional[Topology]:
    """The slice topology the hierarchical schedule routes over, or None
    for the flat schedule: requires HETU_TPU_COMM_TOPOLOGY=two_level AND
    a profile topology that applies to an ep-rank group (the same
    opt-in pair the DP grad sync uses)."""
    if topology_mode() != "two_level":
        return None
    topo = load_topology()
    if topo is None or not topo.applies(ep):
        return None
    return topo


def validate_envelope(strategy, moe, num_pairs: int) -> None:
    """Loud trace-time envelope check for the explicit path (the
    grad-compress pattern: refuse instead of silently degrading)."""
    ep = strategy.ep
    if strategy.tp > 1 or strategy.pp > 1:
        raise ValueError(
            "HETU_TPU_MOE_DISPATCH explicit modes compose with tp=1, "
            f"pp=1 (got tp={strategy.tp}, pp={strategy.pp}); the tp-"
            "sharded expert einsum and the pipeline's partial-manual "
            "stage bodies cannot host the dispatch shard_map — unset "
            "the flag for those meshes")
    if moe.dispatch != "sort":
        raise ValueError(
            "HETU_TPU_MOE_DISPATCH explicit modes require the sort "
            f"dispatcher (got dispatch={moe.dispatch!r}); the dense "
            "[T,E,C] parity path stays on GSPMD")
    if num_pairs % ep:
        raise ValueError(
            f"explicit MoE dispatch splits the {num_pairs} (token, "
            f"slot) pairs per group over ep={ep}, which must divide "
            "evenly — adjust batch/seq/top_k or unset "
            "HETU_TPU_MOE_DISPATCH")


# ---------------------------------------------------------------------------
# the two transports (flat + hierarchical), over the bound `ep` axis
# ---------------------------------------------------------------------------

def _dispatch_reduce(partial, ep: int, mode: str, topo: Optional[Topology],
                     block: int = DEFAULT_BLOCK):
    """partial [G_loc, E, C, h] (this rank's token share scattered into
    the FULL expert range) -> buf [G_loc, E_loc, C, h] (this rank's
    expert block, summed over every rank's contribution).  The dispatch
    half of HAllToAll: a2a + sum == reduce-scatter by expert block."""
    g, E, C, h = partial.shape
    e_loc = E // ep
    bloc = e_loc * C * h
    if topo is None:
        x = partial.reshape(g, ep, bloc)
        recv = all_to_all_q(x, EP_AXIS, split_axis=1, concat_axis=1,
                            mode=mode, block_size=block)
        buf = jnp.sum(recv.reshape(g, ep, bloc), axis=1)
        return buf.reshape(g, e_loc, C, h)
    k, s = topo.slice_devices, ep // topo.slice_devices
    intra, inter = topo.groups(ep)
    # stage 1 (intra, fast): exchange position-keyed bundles inside the
    # slice — bundle i holds this rank's partials for the position-i
    # rank of EVERY slice
    x = partial.reshape(g, s, k, bloc).transpose(0, 2, 1, 3)
    recv = all_to_all_q(x.reshape(g, k, s * bloc), EP_AXIS,
                        split_axis=1, concat_axis=1, mode=mode,
                        block_size=block, axis_index_groups=intra)
    agg = jnp.sum(recv.reshape(g, k, s, bloc), axis=1)   # slice-aggregated
    # stage 2 (inter, slow): only the 1/k aggregated bundles cross the
    # strided transversal — the HetCCL/HAllToAll saving
    recv2 = all_to_all_q(agg, EP_AXIS, split_axis=1, concat_axis=1,
                         mode=mode, block_size=block,
                         axis_index_groups=inter)
    buf = jnp.sum(recv2.reshape(g, s, bloc), axis=1)
    return buf.reshape(g, e_loc, C, h)


def _combine_gather(out_loc, ep: int, mode: str, topo: Optional[Topology],
                    block: int = DEFAULT_BLOCK):
    """out_loc [G_loc, E_loc, C, h] -> [G_loc, E, C, h]: every rank
    receives every expert block (rank-major order matches the expert
    index).  Hierarchical form: inter-slice gather of the 1/k blocks
    first, then the intra-slice gather at fast rates."""
    g, e_loc, C, h = out_loc.shape
    if topo is None:
        return all_gather_q(out_loc, EP_AXIS, axis=1, tiled=True,
                            mode=mode, block_size=block)
    k, s = topo.slice_devices, ep // topo.slice_devices
    intra, inter = topo.groups(ep)
    g1 = all_gather_q(out_loc, EP_AXIS, axis=1, tiled=True, mode=mode,
                      block_size=block, axis_index_groups=inter)
    g2 = all_gather_q(g1, EP_AXIS, axis=1, tiled=True, mode=mode,
                      block_size=block, axis_index_groups=intra)
    # received layout (i, b, e_loc) -> expert id (b*k + i)*E_loc + e
    out = g2.reshape(g, k, s, e_loc, C, h).transpose(0, 2, 1, 3, 4, 5)
    return out.reshape(g, k * s * e_loc, C, h)


# ---------------------------------------------------------------------------
# the explicit forward
# ---------------------------------------------------------------------------

def explicit_forward(layer, params, xg, ig, capacity: int,
                     group_axes: Tuple[str, ...], Tg: int):
    """The shard_map dispatch path: xg [G, Tg, h] grouped over
    (dp, cp) -> (yg [G, Tg, h], aux [G]).  Routing, capacity semantics
    and the combine arithmetic are IDENTICAL to the GSPMD path (same
    helpers, same plan) — only the transport differs."""
    from hetu_tpu.nn.moe import (_numerics_active, _router_stats,
                                 aux_losses, gather_from_experts,
                                 scatter_to_experts, select_experts,
                                 sort_routing)

    moe, st = layer.moe, layer.strategy
    ep, E = st.ep, moe.num_experts
    mode = resolved_mode(st)
    qmode = "none" if mode == "fp32" else mode
    n_slots = 1 if moe.gate in ("hash", "top1") else max(moe.top_k, 1)
    validate_envelope(st, moe, Tg * n_slots)
    mesh = current_mesh()
    if mesh is None:
        raise ValueError(
            "explicit MoE dispatch needs an active mesh (use_mesh) so "
            "the dispatch shard_map can bind the ep axis")
    topo = two_level_topology(ep)
    active = _numerics_active()
    gs = tuple(group_axes) if group_axes else None

    def body(xg_l, ig_l, router, wgu, wdn):
        r = lax.axis_index(EP_AXIS)

        def route_one(xt, ids):
            logits = xt.astype(jnp.float32) @ router
            eidx, gv = select_experts(logits, ids, moe)
            plan = sort_routing(eidx, gv, E, capacity)
            aux = aux_losses(logits, eidx, moe)
            # token-share split: pair j of the slot-major sorted order
            # belongs to rank j // (TK/ep); pairs not mine scatter to
            # the trash row, so the cross-rank sum reassembles the
            # GSPMD buffer EXACTLY (disjoint destinations)
            TK = plan["dest"].shape[0]
            share = TK // ep
            j = jnp.arange(TK, dtype=jnp.int32)
            mine = (j >= r * share) & (j < (r + 1) * share)
            dest = jnp.where(mine, plan["dest"], E * capacity)
            partial = scatter_to_experts(xt, dict(plan, dest=dest), E,
                                         capacity)
            rst = (_router_stats(logits, plan["load"], plan["dropped"])
                   if active else {})
            return partial, plan, aux, rst

        partial, plan, aux, rst = jax.vmap(route_one)(xg_l, ig_l)
        buf = _dispatch_reduce(partial, ep, qmode, topo)
        out = layer._experts({"w_gate_up": wgu, "w_down": wdn}, buf)
        out_full = _combine_gather(out, ep, qmode, topo)
        yg = jax.vmap(lambda o, p: gather_from_experts(o, p, Tg))(
            out_full, plan)
        return yg, aux, rst

    from jax import shard_map
    rst_spec = ({"load": P(gs, None), "load_max": P(gs),
                 "entropy": P(gs), "dropped": P(gs), "drop_frac": P(gs)}
                if active else {})
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(gs, None, None), P(gs, None), P(), P(EP_AXIS),
                  P(EP_AXIS)),
        out_specs=(P(gs, None, None), P(gs), rst_spec),
        # routing (hence yg/aux) is replicated over ep by construction,
        # but the checker cannot see that through the a2a
        check_vma=False)
    yg, aux, rst = fn(xg, ig, params["router"],
                      params["w_gate_up"], params["w_down"])
    if rst:
        # same per-group -> scope reduction as the GSPMD path
        from hetu_tpu.obs import numerics as _numerics
        _numerics.merge(_numerics.reduce_stacked({"moe": rst}))
    return yg, aux
