"""Hot-switching trainer.

Rebuild of the reference's multi-strategy training flow
(reference: examples/hotspa/llama_hot_switch_trainer.py — per-seq-len-bucket
strategies selected per batch, --hot_switch :58; DefineAndRunGraph's plan
pool + SwitchExecGraph under the hood, define_and_run_graph.cc:1258-1272).

The trainer keeps one compiled train step per strategy (the plan pool) and
reshards (params, opt_state) with the switch engine whenever the requested
strategy differs from the live one.  Switch latency is one resharding
device_put — the reference's batched-P2P ParamSlice program, compiler-planned.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import numpy as np

import hetu_tpu  # noqa: F401  (package context)
from hetu_tpu.core.mesh import use_mesh
from hetu_tpu.engine.trainer import Trainer
from hetu_tpu.engine.trainer_config import TrainingConfig
from hetu_tpu.parallel.strategy import ParallelStrategy
from hetu_tpu.parallel.switch import StrategyHandle, StrategySwitcher, SwitchMode
from hetu_tpu.utils.logging import get_logger

logger = get_logger("hot_switch")


def param_handle(model_factory, strategy: ParallelStrategy) -> StrategyHandle:
    """Params-only plan-pool entry: a StrategyHandle with the strategy's
    mesh + param shardings and NO optimizer-state shardings.  The serving
    engine's reuse shim over the hot-switch machinery
    (hetu_tpu/serving/reshard.py) — inference moves params, never
    moments, so the handle stays cheap to build per load tier."""
    model = model_factory(strategy)
    mesh = strategy.build_mesh()
    return StrategyHandle(strategy, model, mesh, model.shardings(mesh), None)


class HotSwitchTrainer(Trainer):
    """Trainer over a pool of strategies (one model instance per strategy,
    same architecture/config, different layouts)."""

    def __init__(self, model_factory, config: TrainingConfig,
                 strategies: List[ParallelStrategy], **kw):
        """model_factory(strategy) -> model instance."""
        self.model_factory = model_factory
        self.strategies = list(strategies)
        self.active_id = 0
        self.last_switch_profile = None
        self._handles: Dict[int, StrategyHandle] = {}
        self._steps: Dict[int, object] = {}
        model0 = model_factory(strategies[0])
        super().__init__(model0, config, strategies[0], **kw)

    # ------------------------------------------------------------------
    def _handle(self, sid: int) -> StrategyHandle:
        h = self._handles.get(sid)
        if h is None:
            st = self.strategies[sid]
            model = (self.model if sid == self.active_id and self.params is not None
                     else self.model_factory(st))
            mesh = st.build_mesh()
            from hetu_tpu.optim.optimizer import state_shardings
            pshard, sshard = state_shardings(model, mesh, st.zero)
            h = StrategyHandle(st, model, mesh, pshard, sshard)
            self._handles[sid] = h
        return h

    def switch_to(self, sid: int,
                  mode: SwitchMode = SwitchMode.PARAM_AND_OPTIMIZER):
        """Hot-switch the live training state to strategy `sid`
        (reference: SwitchExecGraph::SwitchParams)."""
        if sid == self.active_id:
            return self
        if self.params is None:
            raise RuntimeError("HotSwitchTrainer.build() must run before "
                               "switching strategies")
        t0 = time.perf_counter()
        from_id = self.active_id
        dst = self._handle(sid)
        # byte accounting BEFORE the move (needs the live src shardings) —
        # the reference's ProfileRunningDetails (switch_exec_graph.cc:1904)
        from hetu_tpu.parallel.switch import profile_switch
        from hetu_tpu.utils import flags
        prof = None
        if flags.bool_flag("HETU_TPU_SWITCH_PROFILE"):
            try:
                prof = profile_switch(
                    self.params,
                    jax.tree.map(lambda x: x.sharding, self.params),
                    dst.param_shardings)
            except Exception as e:
                logger.warning(f"switch byte profiling failed: {e!r}")
        self.last_switch_profile = prof  # reset even on failure (no stale reads)
        switcher = StrategySwitcher(self._handles)
        self.params, new_state = switcher.switch(
            self.params, self.opt_state, sid, mode=mode)
        if new_state is None:  # PARAM mode: rebuild optimizer moments
            old_step = self.opt_state["step"] if self.opt_state else None
            with use_mesh(dst.mesh):
                self.opt_state = jax.jit(
                    self.optimizer.init,
                    out_shardings=dst.state_shardings)(self.params)
            if old_step is not None:
                # keep the schedule position (the reference's param-mode
                # switch does not rewind training progress)
                self.opt_state["step"] = jax.device_put(
                    old_step, dst.state_shardings["step"])
        else:
            self.opt_state = new_state
        # eval pools are per strategy too: a plan compiled for the old
        # mesh/model would otherwise be fetched for a same-shape batch
        # (stash under the OLD id before active_id flips)
        if not hasattr(self, "_evals"):
            self._evals = {}
        if hasattr(self, "_eval_fn"):
            self._evals[self.active_id] = self._eval_fn
            del self._eval_fn
        if sid in self._evals:
            self._eval_fn = self._evals[sid]
        self.active_id = sid
        self.model = dst.model
        self.strategy = dst.strategy
        self.mesh = dst.mesh
        self._pshard, self._sshard = dst.param_shardings, dst.state_shardings
        self._step_fn = self._steps.get(sid)
        if self._step_fn is None:
            # one plan POOL per strategy (out_shardings differ): within it,
            # one compiled plan per batch-shape bucket — the full
            # (strategy, shape-plan) pool of define_and_run_graph.cc:1174
            with use_mesh(dst.mesh):
                self._step_fn = self._make_step_pool(
                    dst.param_shardings, dst.state_shardings)
            self._steps[sid] = self._step_fn
        detail = ""
        if prof is not None:
            prof.wall_s = time.perf_counter() - t0
            self.last_switch_profile = prof
            detail = f"; params {prof.describe()}"
        wall_s = time.perf_counter() - t0
        self._registry.inc("switch.count")
        self._registry.observe("switch.wall_s", wall_s)
        if self.run_log is not None:
            # switch phases become timeline spans via obs.trace_from_runlog
            self.run_log.log(
                "switch", from_id=from_id, to_id=sid, wall_s=wall_s,
                mode=mode.value,
                moved_bytes=(prof.moved_bytes if prof else None),
                total_bytes=(prof.total_bytes if prof else None))
        logger.info(f"hot-switch -> strategy {sid} ({dst.strategy.describe()}) "
                    f"in {wall_s:.3f}s{detail}")
        return self

    def build(self, rng=None):
        super().build(rng)
        self._handles[self.active_id] = StrategyHandle(
            self.strategy, self.model, self.mesh, self._pshard, self._sshard)
        self._steps[self.active_id] = self._step_fn
        return self

    def train_step(self, host_batch, strategy_id: Optional[int] = None):
        """Per-batch strategy dispatch (the Hydraulis/HotSPa pattern:
        pick the strategy for this batch's seq-len bucket, switch if needed,
        then step)."""
        if strategy_id is not None:
            self.switch_to(strategy_id)
        return super().train_step(host_batch)
