"""Optimizers (reference: hetu/graph/optim/optimizer.h:13-159 SGD/Adam +
ops/optimizer_update.h fused update ops).

Functional: `opt.init(params)` -> state pytree, `opt.update(grads, state,
params)` -> (new_params, new_state).  The update math runs in float32 on the
float32 master params regardless of compute dtype (AMP), matching the
reference's fused Adam (hetu/impl/kernel/Optimizers.cu).

ZeRO-1 (optimizer-state sharding over dp, reference: distributed_states.h:15
`zero` + the OPTIMIZE_COMPUTE_BRIDGE subgraphs) is expressed through shardings:
`zero_shardings()` returns NamedShardings that additionally shard every state
leaf (and master param copy) over the dp axis; GSPMD then turns the grad
all-reduce into reduce-scatter + the param refresh into all-gather — the same
comm pattern the reference builds explicitly with Split* collectives.  The
scatter lands only where the split is INSIDE a scanned layer (see there);
`Trainer`'s `trainer.grad_sync_*` gauges say which form a compile took.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


def clip_by_global_norm(grads, max_norm: float):
    """Global-norm clip (used by the trainer; reference clips via
    GradScaler/CheckFinite pipeline).

    The per-leaf squared sums are stacked and reduced with ONE jnp.sum —
    a python `sum(...)` over the leaf scalars lowers to a serial chain of
    O(n_leaves) scalar adds in HLO (each dependent on the last), which on
    a scan-free 100+-leaf model is a visible critical path; the stacked
    reduction is a single tree-reduce."""
    leaves = jax.tree.leaves(grads)
    sq = jnp.stack([jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in leaves])
    gnorm = jnp.sqrt(jnp.sum(sq))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gnorm, 1e-12))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads), gnorm


class Optimizer:
    def init(self, params):
        raise NotImplementedError

    def update(self, grads, state, params):
        """-> (new_params, new_state)."""
        raise NotImplementedError


@dataclasses.dataclass
class SGD(Optimizer):
    lr: float = 0.01
    momentum: float = 0.0
    weight_decay: float = 0.0

    def init(self, params):
        if self.momentum == 0.0:
            return {"step": jnp.zeros((), jnp.int32)}
        return {
            "step": jnp.zeros((), jnp.int32),
            "velocity": jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params),
        }

    def update(self, grads, state, params):
        step = state["step"] + 1

        def upd(p, g, v=None):
            g = g.astype(jnp.float32)
            if self.weight_decay:
                g = g + self.weight_decay * p.astype(jnp.float32)
            if v is not None:
                v = self.momentum * v + g
                g = v
            newp = p.astype(jnp.float32) - self.lr * g
            return newp.astype(p.dtype), v

        if self.momentum == 0.0:
            new_params = jax.tree.map(lambda p, g: upd(p, g)[0], params, grads)
            return new_params, {"step": step}
        out = jax.tree.map(lambda p, g, v: upd(p, g, v), params, grads, state["velocity"])
        new_params = jax.tree.map(lambda t: t[0], out, is_leaf=lambda t: isinstance(t, tuple))
        new_vel = jax.tree.map(lambda t: t[1], out, is_leaf=lambda t: isinstance(t, tuple))
        return new_params, {"step": step, "velocity": new_vel}


@dataclasses.dataclass
class AdamW(Optimizer):
    """AdamW with bias correction (reference MakeAdamOp semantics,
    ops/optimizer_update.h:207 + Optimizers.cu fused kernel)."""

    lr: float | Callable[[jnp.ndarray], jnp.ndarray] = 1e-3
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0

    def init(self, params):
        zeros_like = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {
            "step": jnp.zeros((), jnp.int32),
            "m": jax.tree.map(zeros_like, params),
            "v": jax.tree.map(zeros_like, params),
        }

    def _lr(self, step):
        return self.lr(step) if callable(self.lr) else self.lr

    def update(self, grads, state, params):
        step = state["step"] + 1
        lr = self._lr(step)
        c1 = 1.0 - self.b1 ** step.astype(jnp.float32)
        c2 = 1.0 - self.b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            # an op chain, left to XLA: it fuses the chain with the
            # rescale that made `g` and reads each leaf where it lies,
            # which the chip read faster than a fused kernel behind a
            # call boundary in both train cells (PERF.md s6, PR 39)
            g = g.astype(jnp.float32)
            m = self.b1 * m + (1.0 - self.b1) * g
            v = self.b2 * v + (1.0 - self.b2) * jnp.square(g)
            mhat = m / c1
            vhat = v / c2
            pf = p.astype(jnp.float32)
            newp = pf - lr * (mhat / (jnp.sqrt(vhat) + self.eps) + self.weight_decay * pf)
            return newp.astype(p.dtype), m, v

        triples = jax.tree.map(upd, params, grads, state["m"], state["v"])
        is_t = lambda t: isinstance(t, tuple)
        new_params = jax.tree.map(lambda t: t[0], triples, is_leaf=is_t)
        new_m = jax.tree.map(lambda t: t[1], triples, is_leaf=is_t)
        new_v = jax.tree.map(lambda t: t[2], triples, is_leaf=is_t)
        from hetu_tpu.obs import numerics as _numerics
        if _numerics.active():
            # numerics observatory (HETU_TPU_NUMERICS): watch the update
            # magnitude (lr-scale — where int8 delta-gather error lives)
            # and the first moment.  Only traced when a collector is on.
            deltas = jax.tree.map(
                lambda n, p: n.astype(jnp.float32) - p.astype(jnp.float32),
                new_params, params)
            _numerics.tap_tree("update", deltas)
            _numerics.tap_tree("adam_m", new_m)
        return new_params, {"step": step, "m": new_m, "v": new_v}


def Adam(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8):
    return AdamW(lr=lr, b1=b1, b2=b2, eps=eps, weight_decay=0.0)


# ---------------------------------------------------------------------------
# ZeRO-1 sharding helpers
# ---------------------------------------------------------------------------

def zero_shardings(param_shardings, param_specs, mesh, axis: str = "dp"):
    """Derive optimizer-state shardings: each state leaf inherits its param's
    sharding plus an extra split of one free, divisible dim over `axis`
    (ZeRO-1; the comm consequences — reduce-scatter of grads, all-gather of
    fresh params — are inserted by GSPMD).  Scalars and indivisible params
    stay replicated.

    WHICH dim: the first free, divisible one INSIDE a layer.  A leaf stacked
    for a scan over layers (`ParamSpec.stack_dims` leading dims, set by
    `nn.module.stacked_spec`) has its gradient made one layer at a time by
    the backward scan, and no reduce-scatter of ONE layer's gradient lands
    in a split BETWEEN layers: split there, GSPMD all-reduces the whole
    gradient inside the loop and slices after it (twice the bytes; PR 59,
    63 MB a layer on the four-chip cell).  Split inside the layer, the
    state's sharding propagates back through the update into the loop and
    the sync is a reduce-scatter into the shard this rank updates.  A
    stacked leaf with no such inner dim falls back to its stack dims.

    `param_specs` supplies shapes — the model's `param_specs()`, or params /
    ShapeDtypeStructs (a NamedSharding's spec alone does not know the tensor
    rank), which know of no stack and are split on their first free dim.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    size = mesh.shape.get(axis, 1)
    if size <= 1:
        return param_shardings

    def shard_one(ns, ref):
        shape = ref.shape
        spec = list(ns.spec) + [None] * (len(shape) - len(ns.spec))
        flat = [a for s in spec if s is not None
                for a in ((s,) if isinstance(s, str) else s)]
        if axis in flat:
            return ns  # already sharded over this axis (e.g. FSDP weights)
        stack = getattr(ref, "stack_dims", 0)
        for i in (*range(stack, len(shape)), *range(stack)):
            if spec[i] is None and shape[i] % size == 0 and shape[i] >= size:
                spec[i] = axis
                return NamedSharding(mesh, P(*spec))
        return ns

    return jax.tree.map(shard_one, param_shardings, param_specs)


def state_shardings(model, mesh, zero: bool, axis: str = "dp"):
    """({param shardings}, {step, m, v} shardings) of AdamW's state for
    `model` on `mesh`: the moments laid out as the params, split once more
    over `axis` under ZeRO (`zero_shardings`)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    pshard = model.shardings(mesh)
    moments = (zero_shardings(pshard, model.param_specs(), mesh, axis)
               if zero else pshard)
    return pshard, {"step": NamedSharding(mesh, P()),
                    "m": moments, "v": moments}


# ---------------------------------------------------------------------------
# compressed-grad-sync error-feedback state (HETU_TPU_GRAD_COMPRESS=int8-ef)
# ---------------------------------------------------------------------------

def ef_state_entry(bucket_plan, mesh, dp: int, axis: str = "dp",
                   topology=None):
    """(initial EF residuals, their shardings) for the optimizer-state
    pytree's "ef" entry — the quantized DP sync's error-feedback memory
    (comm/grad_sync.py) rides in the SAME state dict as Adam's moments so
    it checkpoints, donates and reshards with them.  Residual layout:
    per-replica [dp, L] (split over dp) + per-shard [L] (split over dp)
    per bucket; a routing two-level `topology` adds the hierarchical
    schedule's two chunk-sized per-replica residuals."""
    from hetu_tpu.comm.grad_sync import ef_init, ef_shardings
    shardings = ef_shardings(bucket_plan, mesh, axis, topology)
    state = jax.jit(lambda: ef_init(bucket_plan, dp, topology),
                    out_shardings=shardings)()
    return state, shardings


# ---------------------------------------------------------------------------
# LR schedules (reference trainer passes scalar lr; schedules are the TPU-side
# convenience so the jitted update closes over a step->lr function)
# ---------------------------------------------------------------------------

def cosine_schedule(peak_lr: float, warmup_steps: int, total_steps: int,
                    min_ratio: float = 0.1):
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = peak_lr * step / max(warmup_steps, 1)
        prog = jnp.clip((step - warmup_steps) /
                        max(total_steps - warmup_steps, 1), 0.0, 1.0)
        cos = peak_lr * (min_ratio + (1 - min_ratio) * 0.5 *
                         (1.0 + jnp.cos(jnp.pi * prog)))
        return jnp.where(step < warmup_steps, warm, cos)

    return lr


def constant_schedule(lr_value: float):
    def lr(step):
        return jnp.full((), lr_value, jnp.float32)
    return lr
