"""Mixture-of-Experts with expert parallelism.

Rebuild of the reference MoE (reference: hetu/v1/python/hetu/layers/
moe_layer.py + gates Top/KTop1/Hash/Balance + Dispatch.py and hierarchical
all-to-all HAllToAll.py — v1-only features per SURVEY.md §2.4 EP row).

TPU-first design (GShard/Switch style):
- experts are ONE stacked parameter [E, ...] sharded over the `ep` mesh axis.
- the DEFAULT dispatch is sort-based with O(T·k) index tensors: (token, slot)
  pairs are argsorted by expert, position-in-expert comes from an exclusive
  count prefix, and tokens scatter-add into the per-expert capacity buffers.
  No [T, E, C] one-hot masks are ever materialized (at gbs·seq ≈ 1M tokens
  and E=64 those are tens of GB), so MoE scales to the reference's
  benchmark sizes.  dispatch="dense" keeps the einsum-against-one-hot path
  for parity tests and tiny ablations.
- routing is computed PER DATA SHARD (the [G, Tg, h] group dim is laid out
  over dp×cp): each shard's position-in-expert prefix only scans its own
  tokens, so dispatch never serializes across data shards (GShard's
  per-group capacity semantics).  GSPMD lowers the group->expert buffer
  movement to all-to-all over ep (the reference's explicit HAllToAll becomes
  compiler-inserted; mesh axis order already makes it hierarchical: ICI
  within a slice, DCN across).
- gates: "topk" (GShard, default), "top1" (Switch), "ktop1" (k sequential
  top-1 picks with renormalized leftovers — reference KTop1Gate),
  "balance" (Sinkhorn-balanced assignment — reference BalanceAssignmentGate
  / BASE-style), "hash" (token_id % E).  All share the Switch load-balance
  aux loss + router z-loss.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu import ops
from hetu_tpu.dstates import DistributedStates as DS
from hetu_tpu.nn import initializers as init
from hetu_tpu.nn.module import Module
from hetu_tpu.parallel.strategy import ParallelStrategy

GATES = ("topk", "top1", "ktop1", "balance", "hash", "sam")


@dataclasses.dataclass
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_z_loss_coef: float = 1e-3
    load_balance_coef: float = 1e-2
    gate: str = "topk"      # one of GATES
    dispatch: str = "sort"  # "sort" (O(T·k) indices) | "dense" ([T,E,C] masks)
    sinkhorn_iters: int = 4  # balance gate only
    # SAM gate (reference: v1 layers/SAMGate.py — locality-aware routing):
    # experts are grouped (one group per host/ICI neighborhood); all k picks
    # land in the token's best group so the dispatch all-to-all stays local.
    # 0 = auto (largest divisor of num_experts <= 8, the reference's
    # num_local_gpus default)
    sam_group_size: int = 0
    # weight of the SAM group-alignment hinge loss, separate from the
    # load-balance coefficient (reference: SAMGate.py keeps distinct
    # balance_loss/alignment_loss weights); None = follow load_balance_coef
    sam_alignment_coef: float | None = None

    def resolved_sam_alignment_coef(self) -> float:
        return (self.load_balance_coef if self.sam_alignment_coef is None
                else self.sam_alignment_coef)

    def resolved_sam_group_size(self) -> int:
        """Experts per SAM locality group (NOT the group count — that is
        num_experts // this).  Validates divisibility and that top_k fits
        inside one group (SAM picks all k experts from a single group)."""
        gs = self.sam_group_size
        if gs == 0:
            gs = next(g for g in range(min(8, self.num_experts), 0, -1)
                      if self.num_experts % g == 0)
        if self.num_experts % gs:
            raise ValueError(f"sam_group_size {gs} must divide "
                             f"num_experts {self.num_experts}")
        if max(self.top_k, 1) > gs:
            raise ValueError(
                f"sam gate needs top_k ({self.top_k}) <= group size ({gs}):"
                " all k picks come from one group")
        return gs


def _router_probs(logits):
    return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)


def _sinkhorn(logits, iters: int):
    """Sinkhorn normalization toward a doubly-'stochastic' plan: rows sum to
    1, columns to T/E — the balanced-assignment relaxation the reference's
    BalanceAssignmentGate solves with an LP."""
    log_p = jax.nn.log_softmax(logits, axis=-1)
    T, E = logits.shape
    log_col_target = jnp.log(jnp.asarray(T / E, jnp.float32))
    for _ in range(iters):
        log_p = log_p - jax.nn.logsumexp(log_p, axis=0, keepdims=True) \
            + log_col_target
        log_p = log_p - jax.nn.logsumexp(log_p, axis=1, keepdims=True)
    return jnp.exp(log_p)


def select_experts(logits, ids, moe: MoEConfig
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Gate selection: logits [T, E] -> (expert_idx [T, k], gate_vals [T, k]).

    Shared by the sort and dense dispatchers so they route identically."""
    T, E = logits.shape
    probs = _router_probs(logits)

    if moe.gate == "hash":
        expert_idx = (ids % E)[:, None]
        gate_vals = jnp.ones((T, 1), jnp.float32)
    elif moe.gate == "top1":
        # Switch: scale by the RAW router prob (the gate gradient signal)
        gate_vals, expert_idx = jax.lax.top_k(probs, 1)
    elif moe.gate == "ktop1":
        # k sequential top-1 picks; each pick's gate is its probability
        # renormalized over the experts still available (reference KTop1Gate)
        picks, gates = [], []
        remaining = probs
        for _ in range(max(moe.top_k, 1)):
            g, e = jax.lax.top_k(remaining, 1)
            denom = jnp.sum(remaining, axis=-1, keepdims=True)
            gates.append(g / jnp.maximum(denom, 1e-9))
            picks.append(e)
            remaining = remaining * (1.0 - jax.nn.one_hot(e[:, 0], E))
        expert_idx = jnp.concatenate(picks, axis=1)
        gate_vals = jnp.concatenate(gates, axis=1)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    elif moe.gate == "balance":
        plan = _sinkhorn(logits.astype(jnp.float32), moe.sinkhorn_iters)
        _, expert_idx = jax.lax.top_k(plan, max(moe.top_k, 1))
        gate_vals = jnp.take_along_axis(probs, expert_idx, axis=1)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    elif moe.gate == "sam":
        # SAM (reference: SAMGate.py samgating): pick the single best GROUP
        # by total gate mass, then top-k experts WITHIN that group — all of
        # a token's experts share one locality domain.  Gate values are the
        # raw probs of the picks (the reference does not renormalize).
        gs = moe.resolved_sam_group_size()
        G = E // gs
        k = max(moe.top_k, 1)
        grouped = probs.reshape(T, G, gs)
        top1_group = jnp.argmax(jnp.sum(grouped, axis=-1), axis=-1)  # [T]
        group_probs = jnp.take_along_axis(
            grouped, top1_group[:, None, None], axis=1)[:, 0]        # [T, gs]
        gate_vals, local_idx = jax.lax.top_k(group_probs, k)
        expert_idx = top1_group[:, None] * gs + local_idx
    else:  # topk (GShard)
        gate_vals, expert_idx = jax.lax.top_k(probs, moe.top_k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    return expert_idx, gate_vals


def aux_losses(logits, expert_idx, moe: MoEConfig):
    """Switch load-balance loss + router z-loss."""
    E = logits.shape[-1]
    probs = _router_probs(logits)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32),
                  axis=0)
    load_balance = E * jnp.sum(me * ce)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits.astype(jnp.float32),
                                             axis=-1)))
    aux = (moe.load_balance_coef * load_balance
           + moe.router_z_loss_coef * z)
    if moe.gate == "sam":
        # alignment loss (reference: SamMax.cu — hinge on every expert
        # OUTSIDE the chosen group whose gate exceeds the weakest chosen
        # in-group expert): pushes gate mass INTO one locality group
        gs = moe.resolved_sam_group_size()
        T = logits.shape[0]
        chosen = jnp.take_along_axis(probs, expert_idx, axis=1)
        tmp = jnp.min(chosen, axis=-1, keepdims=True)       # k-th pick
        group_of = expert_idx[:, :1] // gs                  # [T, 1]
        outside = (jnp.arange(E)[None, :] // gs) != group_of
        hinge = jnp.where(outside, jnp.maximum(probs - tmp, 0.0), 0.0)
        aux = aux + moe.resolved_sam_alignment_coef() * jnp.sum(hinge) / T
    return aux


def _numerics_active() -> bool:
    """Is a numerics collector installed (host-level check, static
    during one trace)?  Lazy import keeps nn free of obs at load."""
    from hetu_tpu.obs import numerics
    return numerics.active()


def _router_stats(logits, load_counts, dropped):
    """Router-health stats for the numerics observatory: per-expert load
    (fraction of TOKENS carrying each expert — sums to ~k, so a
    collapsed router reads load_max -> 1.0 whatever k is), its max,
    mean token routing entropy (nats), and capacity drops.
    ``load_counts``: [E] int assignment counts; ``dropped``: scalar
    int.  Only traced when a collector is active."""
    probs = _router_probs(logits)
    tokens = jnp.asarray(float(max(logits.shape[0], 1)), jnp.float32)
    load = load_counts.astype(jnp.float32) / tokens
    pairs = jnp.maximum(jnp.sum(load_counts).astype(jnp.float32), 1.0)
    entropy = jnp.mean(
        -jnp.sum(probs * jnp.log(probs + 1e-9), axis=-1))
    return {"load": load, "load_max": jnp.max(load), "entropy": entropy,
            "dropped": dropped.astype(jnp.float32),
            "drop_frac": dropped.astype(jnp.float32) / pairs}


def sort_routing(expert_idx, gate_vals, num_experts: int, capacity: int):
    """Sort-based routing plan with O(T·k) index tensors.

    (token, slot) pairs are flattened SLOT-major (all slot-0 picks first, in
    token order) so drop priority matches the dense path's sequential-slot
    semantics, stably argsorted by expert, and positioned via an exclusive
    per-expert count prefix.  Returns dict of [T*k] arrays:
      dest: flat index into [E*C] buffers (E*C = trash for dropped entries)
      tok:  source token index
      gate: combine weight
      keep: survived capacity
    plus the routing-plan telemetry (the live expert-load/capacity-drop
    surface ROADMAP item 1 names — free here, the counts already exist):
      load:    [E] int32 routed (pre-drop) assignments per expert
      dropped: scalar int32 count of capacity-dropped (token, slot) pairs
    """
    T, k = expert_idx.shape
    TK = T * k
    e_flat = expert_idx.T.reshape(TK)       # slot-major
    g_flat = gate_vals.T.reshape(TK)
    order = jnp.argsort(e_flat, stable=True)
    e_s = e_flat[order]
    counts = jnp.zeros((num_experts,), jnp.int32).at[e_flat].add(1)
    starts = jnp.cumsum(counts) - counts    # exclusive prefix
    pos = jnp.arange(TK, dtype=jnp.int32) - starts[e_s]
    keep = pos < capacity
    dest = jnp.where(keep, e_s * capacity + pos, num_experts * capacity)
    tok = order % T                         # slot-major: f = slot*T + t
    return {"dest": dest, "tok": tok, "gate": g_flat[order], "keep": keep,
            "load": counts,
            "dropped": TK - jnp.sum(keep.astype(jnp.int32))}


def scatter_to_experts(xt, plan, num_experts: int, capacity: int):
    """xt [T, h] --scatter-add--> [E, C, h].  Dropped entries land in (and
    are discarded with) a trash row, so they contribute exactly-zero output
    and gradient."""
    T, h = xt.shape
    E, C = num_experts, capacity
    buf = jnp.zeros((E * C + 1, h), xt.dtype)
    buf = buf.at[plan["dest"]].add(xt[plan["tok"]])
    return buf[: E * C].reshape(E, C, h)


def gather_from_experts(out_ec, plan, num_tokens: int):
    """[E, C, h'] --gate-weighted gather--> [T, h'] (dropped entries gather
    through a clamped index but are zeroed by the keep mask)."""
    E, C, h = out_ec.shape
    out_flat = out_ec.reshape(E * C, h)
    safe = jnp.minimum(plan["dest"], E * C - 1)
    w = (plan["keep"] * plan["gate"]).astype(out_flat.dtype)
    contrib = out_flat[safe] * w[:, None]
    y = jnp.zeros((num_tokens, h), out_flat.dtype)
    return y.at[plan["tok"]].add(contrib)


def sort_dispatch_combine(xt, plan, expert_fn, num_experts: int,
                          capacity: int):
    """xt [T, h] --scatter--> [E, C, h] --expert_fn--> [E, C, h'] --gather-->
    [T, h']."""
    out = expert_fn(scatter_to_experts(xt, plan, num_experts, capacity))
    return gather_from_experts(out, plan, xt.shape[0])


def topk_routing(logits, ids, moe: MoEConfig, capacity: int):
    """DENSE routing (parity/ablation path): returns (dispatch [T, E, C]
    bool, combine [T, E, C] f32, aux_loss, dropped) where ``dropped`` is
    the scalar int32 count of capacity-dropped (token, slot) pairs —
    the same accounting ``sort_routing`` carries in its plan.  Memory
    O(T·E·C) — use dispatch="sort" beyond toy sizes.

    Single cumsum-based construction (no per-slot Python loop): the
    (token, slot) pairs flatten SLOT-MAJOR — all slot-0 picks in token
    order, then slot-1 — exactly ``sort_routing``'s drop priority, so
    position-in-expert is one exclusive cumsum of the one-hot pair
    matrix and the [T, E, C] masks assemble from one einsum over the
    pair dim (the routing-parity regression test pins the plans
    identical to the sort path's)."""
    T, E = logits.shape
    expert_idx, gate_vals = select_experts(logits, ids, moe)
    k = expert_idx.shape[1]
    TK = T * k

    e_flat = expert_idx.T.reshape(TK)           # slot-major pair order
    g_flat = gate_vals.T.reshape(TK)
    onehot_e = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)       # [TK, E]
    pos_in_e = jnp.cumsum(onehot_e, axis=0) - onehot_e          # exclusive
    pos = jnp.take_along_axis(pos_in_e, e_flat[:, None], axis=1)[:, 0]
    keep = pos < capacity
    pos_c = jnp.clip(pos, 0, capacity - 1)
    w = jnp.where(keep, 1.0, 0.0)
    pair = (jax.nn.one_hot(e_flat, E, dtype=jnp.float32) * w[:, None],
            jax.nn.one_hot(pos_c, capacity, dtype=jnp.float32))
    # [TK, E] x [TK, C] -> [TK, E, C], folded back to tokens slot-major
    combine_f = jnp.einsum("se,sc->sec", pair[0] * g_flat[:, None], pair[1])
    combine = combine_f.reshape(k, T, E, capacity).sum(axis=0)
    disp_f = jnp.einsum("se,sc->sec", pair[0], pair[1])
    dispatch = disp_f.reshape(k, T, E, capacity).sum(axis=0) > 0

    dropped = TK - jnp.sum(keep.astype(jnp.int32))
    return dispatch, combine, aux_losses(logits, expert_idx, moe), dropped


class MoELayer(Module):
    """Sparse SwiGLU FFN: router + E experts, expert dim sharded over ep
    (reference: v1 moe_layer.py MoELayer; dense path = LlamaMLP)."""

    def __init__(self, hidden_size: int, intermediate_size: int,
                 moe: MoEConfig, strategy: ParallelStrategy,
                 param_dtype=jnp.float32, initializer_range: float = 0.02):
        super().__init__()
        if moe.gate not in GATES:
            raise ValueError(f"gate={moe.gate!r} not in {GATES}")
        if moe.dispatch not in ("sort", "dense"):
            raise ValueError(f"dispatch={moe.dispatch!r}")
        self.moe, self.strategy = moe, strategy
        self.hidden, self.inter = hidden_size, intermediate_size
        E = moe.num_experts
        if E % max(strategy.ep, 1):
            raise ValueError(f"num_experts={E} must divide by ep={strategy.ep}")
        ep_ds = DS.make(4, {0: "ep", 3: "tp"}) if strategy.ep > 1 or strategy.tp > 1 else None
        dn_ds = DS.make(3, {0: "ep", 1: "tp"}) if strategy.ep > 1 or strategy.tp > 1 else None
        self.param("router", (hidden_size, E), init.normal(initializer_range),
                   dtype=jnp.float32)
        self.param("w_gate_up", (E, hidden_size, 2, intermediate_size),
                   init.normal(initializer_range), dtype=param_dtype, ds=ep_ds)
        self.param("w_down", (E, intermediate_size, hidden_size),
                   init.normal(initializer_range), dtype=param_dtype, ds=dn_ds)

    # -- expert compute (shared by both dispatchers) ------------------------
    def _experts(self, params, buf):
        """buf [..., E, C, h] -> [..., E, C, h] (leading dims broadcast)."""
        x = buf
        gu = jnp.einsum("...ecd,edki->...ecki", x,
                        params["w_gate_up"].astype(x.dtype))
        hidden = ops.swiglu(gu[..., 0, :], gu[..., 1, :])
        return jnp.einsum("...eci,eih->...ech", hidden,
                          params["w_down"].astype(x.dtype))

    def _group_dims(self, b: int, s: int) -> Tuple[int, int]:
        """(db, cs) — how many shards the batch/seq dims split into for
        shard-local routing; 1 when the dim does not divide evenly (falls
        back to one global group, still correct just not shard-local)."""
        st = self.strategy
        db = st.dp if st.dp > 1 and b % st.dp == 0 else 1
        cs = st.cp if st.cp > 1 and s % st.cp == 0 else 1
        return db, cs

    def forward(self, params, x, *, token_ids: Optional[jnp.ndarray] = None):
        """x: [b, s, h] -> ([b, s, h], aux_loss)."""
        moe, st = self.moe, self.strategy
        b, s, h = x.shape
        E = moe.num_experts

        if moe.dispatch == "dense":
            return self._forward_dense(params, x, token_ids)

        # ---- grouped sort dispatch: G = dp*cp data shards route locally ----
        db, cs = self._group_dims(b, s)
        G = db * cs
        Tg = (b // db) * (s // cs)
        capacity = int(moe.capacity_factor * Tg * max(moe.top_k, 1) / E)
        capacity = max(8, min(Tg, -(-capacity // 8) * 8))  # mult of 8

        # [b, s, h] -> [G, Tg, h], group dim laid out over (dp, cp) so the
        # regroup is data-movement-free under the activation sharding
        xg = x.reshape(db, b // db, cs, s // cs, h)
        xg = xg.transpose(0, 2, 1, 3, 4).reshape(G, Tg, h)
        if token_ids is None:
            # hash-gate default ids are the GLOBAL flat index (the dense
            # path's convention) — group-local arange would re-route tokens
            token_ids = jnp.arange(b * s, dtype=jnp.int32).reshape(b, s)
        ig = token_ids.reshape(db, b // db, cs, s // cs)
        ig = ig.transpose(0, 2, 1, 3).reshape(G, Tg)
        group_axes = tuple(a for a, n in (("dp", db), ("cp", cs)) if n > 1)
        if group_axes:
            xg = DS.make(3, {0: group_axes}).constrain(xg)

        # explicit expert-parallel dispatch (HETU_TPU_MOE_DISPATCH,
        # nn/moe_dispatch.py): same routing plan, transport through a
        # shard_map over ep (quantized a2a + all-gather, hierarchical
        # under a two-level topology).  "gspmd" — the unset default —
        # takes the constraint-based path below, byte-identical to the
        # flag not existing (registered identity contract).
        from hetu_tpu.nn import moe_dispatch as _md
        if _md.resolved_mode(st) != "gspmd":
            yg, aux = _md.explicit_forward(self, params, xg, ig,
                                           capacity, group_axes, Tg)
            y = yg.reshape(db, cs, b // db, s // cs, h)
            y = y.transpose(0, 2, 1, 3, 4).reshape(b, s, h)
            return y, jnp.mean(aux)

        def route_one(xt, ids):
            logits = xt.astype(jnp.float32) @ params["router"]
            expert_idx, gate_vals = select_experts(logits, ids, moe)
            plan = sort_routing(expert_idx, gate_vals, E, capacity)
            aux = aux_losses(logits, expert_idx, moe)
            # router telemetry (obs.numerics): only COMPUTED when a
            # collector is active, so the unset-flag trace is untouched
            rstats = (_router_stats(logits, plan["load"], plan["dropped"])
                      if _numerics_active() else {})
            return scatter_to_experts(xt, plan, E, capacity), plan, aux, \
                rstats

        buf, plan, aux, rstats = jax.vmap(route_one)(xg, ig)  # [G, E, C, h]
        if rstats:
            # per-group stats stacked [G, ...] by vmap -> reduce with
            # each stat's own rule, tap under the "moe" scope (repeated
            # MoE layers accumulate into the same scope)
            from hetu_tpu.obs import numerics as _numerics
            _numerics.merge(_numerics.reduce_stacked({"moe": rstats}))
        ep_spec = {1: "ep"} if st.ep > 1 else {}
        if group_axes or ep_spec:
            buf = DS.make(4, {0: group_axes, **ep_spec}).constrain(buf)
        out = self._experts(params, buf)               # [G, E, C, h]
        if group_axes or ep_spec:
            out = DS.make(4, {0: group_axes, **ep_spec}).constrain(out)

        yg = jax.vmap(lambda o, p: gather_from_experts(o, p, Tg))(
            out, plan)                                 # [G, Tg, h]
        if group_axes:
            yg = DS.make(3, {0: group_axes}).constrain(yg)
        y = yg.reshape(db, cs, b // db, s // cs, h)
        y = y.transpose(0, 2, 1, 3, 4).reshape(b, s, h)
        return y, jnp.mean(aux)

    def _forward_dense(self, params, x, token_ids):
        moe, st = self.moe, self.strategy
        b, s, h = x.shape
        T = b * s
        E = moe.num_experts
        capacity = int(moe.capacity_factor * T * max(moe.top_k, 1) / E)
        capacity = max(8, min(T, -(-capacity // 8) * 8))  # mult of 8

        xt = x.reshape(T, h)
        logits = xt.astype(jnp.float32) @ params["router"]
        ids = (token_ids.reshape(T) if token_ids is not None
               else jnp.arange(T, dtype=jnp.int32))
        dispatch, combine, aux, dropped = topk_routing(logits, ids, moe,
                                                       capacity)
        if _numerics_active():
            from hetu_tpu.obs import numerics as _numerics
            # PRE-drop routing intent, same definition as the sort
            # plan's `load` (post-drop counts would both understate a
            # collapsed router's load_max and push drop_frac past 1).
            # select_experts runs a second time here, but it is pure on
            # identical inputs — XLA CSEs the duplicate — and only
            # traced when the numerics flag opted in.
            e_idx, _gv = select_experts(logits, ids, moe)
            counts = jnp.zeros((E,), jnp.int32).at[e_idx.reshape(-1)].add(1)
            _numerics.merge({"moe": _router_stats(logits, counts, dropped)})

        buf = jnp.einsum("th,tec->ech", xt, dispatch.astype(x.dtype))
        if st.ep > 1:
            buf = DS.make(3, {0: "ep"}).constrain(buf)
        out = self._experts(params, buf)
        if st.ep > 1:
            out = DS.make(3, {0: "ep"}).constrain(out)
        y = jnp.einsum("ech,tec->th", out, combine.astype(x.dtype))
        return y.reshape(b, s, h), aux


# ---------------------------------------------------------------------------
# dropless routing over the experts ONE chip holds (DeepSeek-V3 / Kimi-K2)
# ---------------------------------------------------------------------------

def noaux_tc_gate(x, w_gate, bias, *, top_k: int, norm_topk_prob: bool,
                  routed_scaling_factor: float, n_group: int = 1,
                  topk_group: int = 1, norm_eps: float = 1e-20):
    """The published `noaux_tc` gate, in float32 as the published code
    computes it.  x [T, h]; w_gate [h, E]; bias [E]
    (`e_score_correction_bias`, a buffer).  The `top_k` experts of a
    token are the largest of sigmoid(x W_g) + b; their weights are the
    sigmoid scores WITHOUT b at those experts, divided by their sum
    (`norm_topk_prob`), times the scaling factor.  With `n_group` > 1 the
    experts lie in `n_group` groups of E / n_group neighbours; a group's
    score is the sum of its two largest s + b, the `topk_group` best
    groups stay, and the `top_k` are chosen inside them (a deployment
    that holds a group a chip sends a token to `topk_group` chips at
    most).  `n_group` = `topk_group` = 1 is the gate as it always was.
    `norm_eps` stands beside the chosen scores' sum (1e-20 for the
    families that publish that; 1e-6 in `lfm2_moe`).
    Returns (expert ids [T, k] int32, weights [T, k] float32)."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "th,he->te", x.astype(jnp.float32), w_gate.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    choice = scores + bias.astype(jnp.float32)
    if n_group > 1:
        T, E = choice.shape
        by_group = choice.reshape(T, n_group, E // n_group)
        group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(group_score, topk_group)        # [T, kg]
        kept = jnp.any(keep[:, :, None] == jnp.arange(n_group), axis=1)
        choice = jnp.where(kept[:, :, None], by_group,
                           -jnp.inf).reshape(T, E)
    return _top_k_weights(scores, choice, top_k, norm_topk_prob,
                          routed_scaling_factor, norm_eps)


def _top_k_weights(scores, choice, top_k, norm_topk_prob, factor,
                   eps: float = 1e-20):
    """The `top_k` largest of `choice` [T, E] and their weights: `scores`
    there, over (their sum + `eps`) where `norm_topk_prob`, times
    `factor`."""
    _, idx = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return idx.astype(jnp.int32), w * factor


def softmax_gate(x, w_gate, bias, *, top_k: int, norm_topk_prob: bool,
                 routed_scaling_factor: float):
    """`noaux_tc_gate` scored by SOFTMAX over all of the router's outputs
    (LongCat-Flash: 512 routed + 256 identity experts in one softmax), in
    float32 as the published code computes it.  The `top_k` outputs of a
    token are the largest of softmax(x W_g) + b; their weights are the
    softmax scores WITHOUT b at those outputs, over their sum where
    `norm_topk_prob`, times the scaling factor.  No groups: a softmax
    over all outputs has none in any published model
    (`SharedRoutedExperts` refuses them).
    Returns (output ids [T, k] int32, weights [T, k] float32)."""
    scores = jax.nn.softmax(jnp.einsum(
        "th,he->te", x.astype(jnp.float32), w_gate.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    return _top_k_weights(scores, scores + bias.astype(jnp.float32), top_k,
                          norm_topk_prob, routed_scaling_factor)


#: the router's scoring function by its published name
GATES_BY_SCORING = {"sigmoid": noaux_tc_gate, "softmax": softmax_gate}


#: The rows of a block that `dropless_local_experts` walks, from the chip
#: (TPU v5e: 197 TFLOP/s over 819 GB/s = 240 rows, the RIDGE: an expert's
#: weights take as long to stream as 240 rows take to multiply; the MXU
#: multiplies 128 rows at once, so the ridge in tiles is 256 and no tile
#: under 128 multiplies less).  `jax.lax.ragged_dot` pays every expert it
#: visits its weights' bytes AND a tile of the block's rows (of 512 where
#: the block has more), nearly one after the other (my chip runs, PR 63:
#: LFM2's decode pass, 512 pairs on 32 experts, the two products 2.30 ms
#: in one block, 1.43 in four of 128, 1.21 in blocks of 64 that end at
#: whole experts; visits x (bytes / 819 GB/s + rows x FLOPs / 197
#: TFLOP/s) gives 2.7 / 1.44 / 1.09).  So a block over the ridge is cut
#: to a tile that holds ONE expert's expected rows and a quarter more,
#: between these two, and ends where its last whole expert ends.  The
#: sweep (`python tools_bench_kernels.py --grouped-product` on the chip
#: reads it again; my chip run, PR 63), ms a layer's walk at each cell's
#: shapes with blocks of 64 / 128 / 192 / 256 / 512 / one of 1.5 x the
#: expected rows as before, the rule's in brackets:
#:   LFM2 decode (512)    1.33 [1.41] 1.50  1.66   -    2.39
#:   LFM2 chunk (6,144)   6.63  3.83  5.07 [2.44] 2.93  3.89
#:   Xing chunk (4,096)   5.78 [3.59] 4.63  3.63  5.12  5.52
#:   Ling chunk (3,072)   2.43 [2.18] 2.42  2.34  6.96  4.10
#:   MiMo chunk (768)     1.61 [1.67] 2.17  2.04  2.91  2.13
#:   Trinity chunk (768)  1.47 [0.90] 0.77  0.72  0.85  0.63
#:   DeepSeek chunk (384) 1.40 [1.49] 2.76  3.06   -    2.48
#:   Kimi chunk (192)     1.91  2.08 [2.30]; LongCat (192) 2.15 2.33 [2.32]
#: (192 rows are tiled worse than 128 or 256; Trinity reads worse alone,
#: where the compiler copies its 67 MB of down weights near every block,
#: and better in its cell: `experts` 4.09 -> 3.45 ms a chunk launch.)
MXU_ROWS = 128
RIDGE_ROWS = 256


def row_block(pairs: int, share: float, held: int):
    """(rows of a block that `dropless_local_experts` walks, rows that
    one and a half times the pairs expected here fill: `expected`), for
    `pairs` (token, expert) pairs of which `share` are expected on the
    `held` experts here.  `expected` at or under the ridge IS the block;
    over it the block is a tile for one expert's expected rows."""
    expected = min(pairs, -(-int(1.5 * share * pairs) // 64) * 64)
    if expected <= RIDGE_ROWS:
        return expected, expected
    tile = -(-int(1.25 * share * pairs / held) // MXU_ROWS) * MXU_ROWS
    return min(max(tile, MXU_ROWS), RIDGE_ROWS), expected


def dropless_local_experts(x, idx, weights, w_gate_up, w_down, *,
                           first_expert: int, share: float = 1.0):
    """sum_i w_i E_i(x) over the experts HELD here: experts
    first_expert .. first_expert + held - 1 of the router's range, each a
    SwiGLU (w_gate_up [held, h, 2 I]: gate columns then up columns;
    w_down [held, I, h]).  No capacity: the (token, expert) pairs are
    sorted by expert, pairs whose expert is elsewhere go last and belong
    to no group, and one grouped matrix product (`jax.lax.ragged_dot`)
    walks the groups, so every pair on a held expert is computed however
    uneven the load.  A token none of whose experts is here gets 0.

    `share` is the part of all pairs expected here (held / router
    width).  The grouped product pays each expert it visits a whole tile
    of the rows it is given (of 512 where they are more: compiler,
    PR 27), so a product over all rows would multiply mostly padding.
    The sorted pairs on held experts are walked in BLOCKS (`row_block`:
    one and a half times their expected number of rows, rounded up to
    64; where that is over the chip's ridge, a tile of 128 or 256 rows
    for one expert's expected rows), as many as they fill (a loop whose
    trip count the device computes: none where no pair is here).  A
    block ends where the last whole expert inside it ends, so an expert
    is visited twice only where it alone has more rows than a block.
    The cost follows the pairs and no pair is dropped.  Pairs are not
    independent (a chunk's padding rows are one token and route alike):
    0.6-8% of executions hold more than 1.5 x the expected (my chip run,
    PR 27), so nothing here assumes that they fit.

    A block's rows reach `y` one of two ways.  With `share` under 1 few
    of the T k pairs are here: each block's rows are added to their
    tokens' where the block is computed (a scatter of at most 256 rows;
    of more rows it is the larger part of the walk: 0.78 of DeepSeek's
    2.45 ms at 384, my chip run, PR 63).  With every pair here the blocks
    fill a buffer in sorted order, and each token gathers its k rows
    from it once, behind the loop (Xing's chunk: 0.65 ms of scatter and
    two copies of `y` a block against 0.16 ms).

    Returns (y [T, h] in x's dtype, counts [held] int32: pairs per held
    expert, extra int32: blocks of `expected` rows that the pairs here
    fill beyond the first (what was walked before the block had a cap),
    blocks int32: the blocks walked)."""
    T, h = x.shape
    k = idx.shape[1]
    held, inter = w_gate_up.shape[0], w_gate_up.shape[-1] // 2
    local = idx - first_expert                              # [T, k]
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held).reshape(T * k)       # elsewhere last
    order = jnp.argsort(key, stable=True)
    counts = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    ends = jnp.cumsum(counts)
    n_here = ends[-1]
    rows, expected = row_block(T * k, share, held)
    # a block of rows behind the last pair, so that no slice below is
    # shifted back at the end
    token = jnp.pad((order // k).astype(jnp.int32), (0, rows))
    w_here = jnp.where(here, weights, 0.0)

    def product(lo):
        """The pairs of sorted rows lo .. hi - 1 through their experts:
        (hi, their tokens, their rows; a row past hi is 0)."""
        hi = lo + rows
        whole = jnp.max(jnp.where(ends <= hi, ends, 0))
        hi = jnp.where(whole > lo, whole, jnp.minimum(hi, n_here))
        # each expert's pairs that fall in rows lo .. hi - 1
        sizes = jnp.clip(ends, lo, hi) - jnp.clip(ends - counts, lo, hi)
        tok = jax.lax.dynamic_slice_in_dim(token, lo, rows)
        gu = jax.lax.ragged_dot(x[tok], w_gate_up.astype(x.dtype), sizes)
        act = jax.nn.silu(gu[:, :inter]) * gu[:, inter:]
        out = jax.lax.ragged_dot(act, w_down.astype(x.dtype), sizes)
        # rows past the last group are not computed: take them as 0
        return hi, tok, jnp.where((lo + jnp.arange(rows) < hi)[:, None],
                                  out, 0)

    def walk(step, start):
        _, carried, blocks = jax.lax.while_loop(
            lambda c: c[0] < n_here, step, (jnp.int32(0), start,
                                            jnp.int32(0)))
        return carried, blocks

    if share < 1:
        w_sorted = jnp.pad(w_here.reshape(T * k)[order], (0, rows))

        def add(c):
            lo, y, blocks = c
            hi, tok, out = product(lo)
            out = out.astype(jnp.float32) \
                * jax.lax.dynamic_slice_in_dim(w_sorted, lo, rows)[:, None]
            return hi, y.at[tok].add(out), blocks + 1

        y, blocks = walk(add, jnp.zeros((T, h), jnp.float32))
    else:
        def put(c):
            lo, sorted_out, blocks = c
            hi, _, out = product(lo)
            return hi, jax.lax.dynamic_update_slice_in_dim(
                sorted_out, out, lo, 0), blocks + 1

        sorted_out, blocks = walk(put, jnp.zeros((T * k + rows, h), x.dtype))
        # pair (t, j) lies at row argsort(order)[t k + j]
        mine = sorted_out[jnp.argsort(order)].reshape(T, k, h)
        y = jnp.sum(mine.astype(jnp.float32) * w_here[:, :, None], axis=1)
    extra = jnp.maximum((n_here + expected - 1) // expected - 1, 0)
    return y.astype(x.dtype), counts, extra, blocks


class SharedRoutedExperts(Module):
    """The expert layer of DeepSeek-V3 / Kimi-K2 on ONE chip of an
    expert-parallel deployment: it is TOLD which experts it holds
    (`first_expert`, `experts_held`), routes every token over all
    `n_routed_experts` (the router keeps its published width), computes
    its own experts' part of sum_i w_i E_i(x) without dropping a token,
    and adds the shared expert, which every chip computes alike (with
    `n_shared_experts` 0 the layer has none: no weights, no product, no
    `shared_expert` scope, and a token none of whose experts is held
    here gets 0).  With all experts held it is the whole layer; across chips the parts would
    be summed by the exchange this layer does not do (no code stands in
    for absent chips).

    forward(params, x [b, s, h]) -> (y [b, s, h], stats int32 [6] in the
    order of `STATS`: (token, expert) pairs chosen, pairs on held
    experts, held experts with at least one token, blocks of one and a
    half times the expected rows that the pairs here fill beyond the
    first (`extra_row_blocks`: what the grouped product walked before
    its block had a cap, 0 where the pairs fit as expected), the blocks
    it walked (`row_blocks`: `dropless_local_experts`, `row_block`), the
    largest load of a held expert).

    **Identity experts** (`n_zero_experts` > 0: LongCat-Flash's
    zero-compute experts): the router has that many outputs MORE, behind
    the routed ones (ids `n_routed_experts` ..), chosen and weighted in
    the same top-k; a chosen identity expert returns its input, so a
    token's addend is (the sum of its weights on them) x the token,
    computed where the token lives, under the scope `zero_experts`: no
    weights, no exchange, all of them "held" by every chip.  `share`,
    which sizes the grouped product's row blocks and says whether every
    pair is here (1: each token gathers its rows; under 1: a block's rows
    are added where it is computed), is then the held experts over ALL
    the router's outputs, and `stats` has one entry more at its end
    (`ZERO_STATS`): the pairs on identity experts."""

    STATS = ("assignments", "local_assignments", "expert_hits",
             "extra_row_blocks", "row_blocks", "max_expert_load")
    ZERO_STATS = STATS + ("zero_assignments",)

    def __init__(self, hidden: int, inter: int, *, n_routed_experts: int,
                 experts_held: int, first_expert: int, top_k: int,
                 n_shared_experts: int, norm_topk_prob: bool,
                 routed_scaling_factor: float, param_dtype=jnp.float32,
                 initializer_range: float = 0.02,
                 bias_range: float = 0.02, n_group: int = 1,
                 topk_group: int = 1, scoring: str = "sigmoid",
                 n_zero_experts: int = 0, norm_eps: Optional[float] = None,
                 bias_mean: float = 0.0):
        super().__init__()
        self.gate = GATES_BY_SCORING[scoring]
        self.n_routed, self.n_zero = n_routed_experts, n_zero_experts
        outputs = n_routed_experts + n_zero_experts
        if n_routed_experts % n_group or not 0 < topk_group <= n_group:
            raise ValueError(f"{n_routed_experts} experts in {n_group} "
                             f"groups, {topk_group} of them kept")
        #: what the gate is told beyond the four every family tells it:
        #: the groups, and what stands beside the chosen scores' sum where
        #: the family publishes another than the gate's own 1e-20 (a layer
        #: told neither hands the gate nothing: its program is unchanged)
        self.groups = (dict(n_group=n_group, topk_group=topk_group)
                           if n_group > 1 else {})
        if norm_eps is not None:
            self.groups["norm_eps"] = norm_eps
        if self.groups and scoring != "sigmoid":
            raise ValueError(f"a {scoring} gate limited to groups or with "
                             "an epsilon of its own")
        if not 0 <= first_expert <= n_routed_experts - experts_held:
            raise ValueError(
                f"experts {first_expert}..{first_expert + experts_held - 1}"
                f" are not among the router's {n_routed_experts}")
        self.first_expert, self.held = first_expert, experts_held
        self.share = experts_held / outputs
        self.top_k, self.norm = top_k, norm_topk_prob
        self.scaling = routed_scaling_factor
        w = init.normal(initializer_range)
        # the router's weights are float32 whatever the model's dtype:
        # the published gate is computed in float32
        self.param("w_gate", (hidden, outputs), w, dtype=jnp.float32)
        # a buffer of the published model (its update rule is not part of
        # `config`); random, small and non-zero here so that choosing by
        # s + b and weighting by s differ (`bias_mean`: an offset common
        # to every expert, which the choice cannot see)
        self.param("e_score_correction_bias", (outputs,),
                   init.normal(bias_range, bias_mean), dtype=jnp.float32)
        # gate|up fused as [.., hidden, 2 I] (gate columns, then up): a
        # [.., hidden, 2, I] weight is tiled (2, 128) on the chip and
        # copied whole into matmul layout at every step (compiler, PR 27)
        self.param("w_gate_up", (experts_held, hidden, 2 * inter), w,
                   dtype=param_dtype)
        self.param("w_down", (experts_held, inter, hidden), w,
                   dtype=param_dtype)
        self.shared = n_shared_experts > 0
        if self.shared:
            si = inter * n_shared_experts
            self.param("shared_gate_up", (hidden, 2 * si), w,
                       dtype=param_dtype)
            self.param("shared_down", (si, hidden), w, dtype=param_dtype)

    def route(self, params, xt):
        return self.gate(
            xt, params["w_gate"], params["e_score_correction_bias"],
            top_k=self.top_k, norm_topk_prob=self.norm,
            routed_scaling_factor=self.scaling, **self.groups)

    def forward(self, params, x):
        b, s, h = x.shape
        xt = x.reshape(b * s, h)
        with jax.named_scope("router"):
            idx, weights = self.route(params, xt)
        with jax.named_scope("experts"):
            y, counts, extra, blocks = dropless_local_experts(
                xt, idx, weights, params["w_gate_up"], params["w_down"],
                first_expert=self.first_expert, share=self.share)
        if self.shared:
            with jax.named_scope("shared_expert"):
                gu = xt @ params["shared_gate_up"].astype(x.dtype)
                si = gu.shape[-1] // 2
                y = y + (jax.nn.silu(gu[:, :si]) * gu[:, si:]) \
                    @ params["shared_down"].astype(x.dtype)
        stats = [jnp.int32(idx.size), jnp.sum(counts),
                 jnp.sum((counts > 0).astype(jnp.int32)), extra, blocks,
                 jnp.max(counts)]
        if self.n_zero:
            with jax.named_scope("zero_experts"):
                zero = idx >= self.n_routed
                w_zero = jnp.sum(jnp.where(zero, weights, 0.0), axis=-1)
                y = y + (w_zero[:, None] * xt.astype(jnp.float32)) \
                    .astype(x.dtype)
            stats.append(jnp.sum(zero.astype(jnp.int32)))
        stats = jnp.stack(stats)
        return y.reshape(b, s, h), stats


#: what the serving programs of a model with such layers count of
#: themselves, in the order of the int32 vector they carry
#: (models/generation.py `STATS`): (the engine's counter, how executions
#: combine).  An expert layer's own counts (`SharedRoutedExperts.STATS`)
#: and the number of expert-layer executions they are over.
MOE_STATS = tuple(
    [(f"serve.moe_{name}", "sum")
     for name in SharedRoutedExperts.STATS[:-1] + ("layer_steps",)]
    + [(f"serve.moe_{SharedRoutedExperts.STATS[-1]}", "max")])
#: the same with identity experts (`SharedRoutedExperts.ZERO_STATS`): the
#: pairs on them, behind the others
ZERO_MOE_STATS = MOE_STATS + (
    (f"serve.moe_{SharedRoutedExperts.ZERO_STATS[-1]}", "sum"),)


def stats_ops(stats):
    """(zero_stats, add_stats) of a model whose `STATS` is `stats`."""
    is_max = np.array([how == "max" for _, how in stats])

    def zero():
        return jnp.zeros((len(stats),), jnp.int32)

    def add(a, b):
        return jnp.where(is_max, jnp.maximum(a, b), a + b)
    return zero, add


zero_moe_stats, add_moe_stats = stats_ops(MOE_STATS)


def moe_layer_stats(st):
    """One execution of a `SharedRoutedExperts` layer (its `stats`, with
    or without the identity experts' count at the end) as a MOE_STATS /
    ZERO_MOE_STATS vector: one layer step, behind the layer's sums."""
    sums = len(SharedRoutedExperts.STATS) - 1
    return jnp.concatenate([st[:sums], jnp.ones((1,), jnp.int32),
                            st[sums:]])
