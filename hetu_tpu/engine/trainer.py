"""Trainer: the end-to-end training engine.

Rebuild of the reference Trainer (reference: python/hetu/engine/trainer.py:67 —
build :187 create graph under contexts, train :655 step loop,
prepare_feed_dict :465 bucketing/packing/cp-split, _train :305 graph.run).
The graph-compile machinery collapses into jit: `build()` materializes sharded
params + ZeRO-sharded optimizer state; the train step (micro-batch
grad-accumulation scan -> clip -> AdamW) is one compiled program per shape
plan, cached in the PlanPool.

Micro-batching: the reference's PipeDream-flush interpreter consumes micro
batches sequentially (executable_graph.cc:1354-1374 CrucialRun); without
pipeline stages the TPU equivalent is a lax.scan over the micro dim
accumulating grads — identical arithmetic, one XLA program.  With pipeline
stages the pipeline engine (hetu_tpu.parallel.pipeline) replaces the scan.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import hetu_tpu as ht
from hetu_tpu import optim
from hetu_tpu.core.mesh import use_mesh
from hetu_tpu.engine.trainer_config import TrainingConfig
from hetu_tpu.optim.optimizer import state_shardings
from hetu_tpu.parallel.strategy import ParallelStrategy
from hetu_tpu.utils.checkpoint import CheckpointManager
from hetu_tpu.utils.logging import get_logger

logger = get_logger("trainer")


from hetu_tpu.utils.profiling import device_mem_bytes as _device_mem_bytes
from hetu_tpu.utils.profiling import StepRecorder, phase_span


class Trainer:
    def __init__(self, model, config: TrainingConfig,
                 strategy: Optional[ParallelStrategy] = None,
                 mesh=None):
        self.model = model
        self.config = config
        self.strategy = strategy or getattr(model, "strategy", ParallelStrategy())
        self._cp_split = None
        if self.strategy.cp > 1:
            # the trainer owns the data layout: resolve the CP split pattern
            # once (reference: HETU_PARALLEL_ATTN_SPLIT drives both the data
            # split and the ring's AttnInfo masks), reorder batches to match
            # (prepare_batch) and declare it around the traced step calls so
            # the ring schedules only live tiles (_declared scope below).
            from hetu_tpu.utils import flags as _flags
            self._cp_split = (self.strategy.cp_split
                              or _flags.str_flag("HETU_TPU_CP_SPLIT"))
            if self._cp_split != "normal":
                # the default differs from the reference's NORMAL: make the
                # host-side seq permutation + label pre-shift visible so
                # tooling that assumes positional order isn't surprised
                logger.info(
                    f"cp={self.strategy.cp}: seq axis host-permuted to the "
                    f"'{self._cp_split}' split (labels pre-shifted); set "
                    f"strategy.cp_split or HETU_TPU_CP_SPLIT to change")
        self._cp_perm_cache = {}
        self._cp_layout_used = False   # a step traced under this layout?
        # non-contiguous CP layouts require host pre-shifted labels
        # (_cp_reorder) — array adjacency stops meaning token adjacency
        self._labels_shifted = self._cp_split not in (None, "normal")
        self.mesh = mesh if mesh is not None else self.strategy.build_mesh()
        self.params = None
        self.opt_state = None
        self._step_fn = None
        self.kernel_routes: dict = {}   # of the last plan traced
        self._ckpt = (CheckpointManager(config.ckpt_dir, config.ckpt_keep)
                      if config.ckpt_dir else None)
        self.global_step = 0

        if config.pp_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"pp_schedule must be 'gpipe' or '1f1b', got "
                f"{config.pp_schedule!r}")
        if (config.pp_schedule == "1f1b" and self.strategy.pp > 1
                and not hasattr(model, "pipeline_train_grads")):
            raise ValueError(
                f"pp_schedule='1f1b' needs {type(model).__name__}"
                ".pipeline_train_grads (use 'gpipe')")

        if config.loss_scale not in ("auto", "dynamic", "none"):
            raise ValueError(f"loss_scale must be auto|dynamic|none, got "
                             f"{config.loss_scale!r}")

        # the ONE plan-time envelope chokepoint (StrategyValidationError
        # here, not a trace-time surprise later) — shared with the
        # searcher, Malleus/Ampelos and the batch dispatcher
        self.strategy.validate(
            getattr(model, "config", None),
            pp_schedule=config.pp_schedule,
            n_micro=config.num_micro_batches(max(self.strategy.dp, 1)),
            global_batch=config.global_batch_size,
            seq_len=config.seq_len,
            deterministic=config.dropout_deterministic)
        compute_dtype = getattr(getattr(model, "config", None),
                                "compute_dtype", None)
        use_scaler = (config.loss_scale == "dynamic"
                      or (config.loss_scale == "auto"
                          and compute_dtype == jnp.float16))
        from hetu_tpu.optim.grad_scaler import GradScaler
        self._scaler = GradScaler() if use_scaler else None
        self.scaler_state = None

        # -- compressed DP grad sync (hetu_tpu/comm, HETU_TPU_GRAD_COMPRESS;
        # docs/comm_compression.md).  "none" is the byte-identical default:
        # the branch below is python-level, so no traced program changes.
        from hetu_tpu.utils import flags as _flags
        self._grad_compress = _flags.str_flag("HETU_TPU_GRAD_COMPRESS")
        self._bucket_plan = None
        if self._grad_compress != "none":
            st = self.strategy
            if (st.tp > 1 or st.cp > 1 or st.pp > 1 or st.ep > 1
                    or st.zero_stage >= 3):
                # the quantized sync runs the per-replica grad computation
                # inside a shard_map over dp with replicated params — only
                # homogeneous DP/ZeRO-1/2 fits that envelope (the hetero-DP
                # BRIDGE compresses independently in parallel/hetero_dp.py)
                raise ValueError(
                    f"HETU_TPU_GRAD_COMPRESS={self._grad_compress!r} "
                    f"supports homogeneous DP/ZeRO-1/2 only (dp>1, "
                    f"tp=cp=pp=ep=1, zero_stage<3); got "
                    f"{self.strategy.describe()}")
            if st.dp <= 1:
                logger.info(
                    f"HETU_TPU_GRAD_COMPRESS={self._grad_compress} ignored: "
                    f"dp=1 has no grad sync to compress")
                self._grad_compress = "none"
        # -- two-level (HetCCL) routing of the compressed sync's ring
        # schedule (HETU_TPU_COMM_TOPOLOGY + the hardware profile's
        # `topology` section, comm/topology.py).  "flat" = byte-identical.
        self._comm_topology = None
        if (self._grad_compress == "none"
                and _flags.str_flag("HETU_TPU_COMM_TOPOLOGY") == "two_level"):
            # the flag only routes the COMPRESSED sync's ring schedule —
            # without grad compression nothing changes; say so loudly
            logger.warning(
                "HETU_TPU_COMM_TOPOLOGY=two_level has no effect without "
                "HETU_TPU_GRAD_COMPRESS (the flag routes the compressed "
                "DP sync's ring schedule); running the plain f32 sync")
        if (self._grad_compress != "none"
                and _flags.str_flag("HETU_TPU_COMM_TOPOLOGY") == "two_level"):
            from hetu_tpu.comm.topology import load_topology
            topo = load_topology()
            if topo is None:
                raise ValueError(
                    "HETU_TPU_COMM_TOPOLOGY=two_level needs a `topology` "
                    "section in the hardware profile "
                    "(hardware_profile_v5e.json / HETU_TPU_HW_PROFILE)")
            if topo.applies(self.strategy.dp):
                self._comm_topology = topo
            else:
                logger.info(
                    f"two-level topology (slice_devices="
                    f"{topo.slice_devices}) does not apply to dp="
                    f"{self.strategy.dp}; using the flat ring")
        # -- quantized ZeRO-1/2 param refresh (optim/zero_refresh.py,
        # HETU_TPU_ZERO_COMPRESS): the explicit delta-gather replaces
        # GSPMD's f32 param all-gather.  Same envelope as the grad sync.
        self._zero_compress = _flags.str_flag("HETU_TPU_ZERO_COMPRESS")
        if self._zero_compress != "none":
            st = self.strategy
            if (st.tp > 1 or st.cp > 1 or st.pp > 1 or st.ep > 1
                    or st.zero_stage >= 3):
                raise ValueError(
                    f"HETU_TPU_ZERO_COMPRESS={self._zero_compress!r} "
                    f"supports homogeneous DP ZeRO-1/2 only (dp>1, "
                    f"tp=cp=pp=ep=1, zero_stage<3); got "
                    f"{self.strategy.describe()}")
            if st.dp > 1 and not st.zero:
                raise ValueError(
                    f"HETU_TPU_ZERO_COMPRESS={self._zero_compress!r} "
                    f"compresses the ZeRO param refresh, but this strategy "
                    f"has zero=False (no refresh exists); enable ZeRO or "
                    f"unset the flag")
            if st.dp <= 1:
                logger.info(
                    f"HETU_TPU_ZERO_COMPRESS={self._zero_compress} ignored: "
                    f"dp=1 has no param refresh to compress")
                self._zero_compress = "none"

        from hetu_tpu.utils.profiling import StepProfiler
        self.profiler = StepProfiler()
        # -- telemetry (hetu_tpu.obs): the metrics registry is process-
        # global (rpc/elastic write into the same one); the RunLog lives
        # next to the checkpoints so every run leaves a machine-readable
        # trace (docs/observability.md)
        from hetu_tpu.obs.metrics import get_registry
        from hetu_tpu.obs.runlog import RunLog, default_runlog_path
        self._registry = get_registry()
        #: the record `train_step` keeps of itself, as the serving
        #: engine's does (utils/profiling.StepRecorder: counters
        #: `trainer.steps`, `.step_wall_s`, `.phase_s{phase}`,
        #: `.caller_s`, `.stalled_steps{phase}`, ...); the full records
        #: of its last stalled steps, and of its slowest so far (None
        #: before the first; a caller may set it to None again)
        self._step_record = StepRecorder("trainer", self._registry)
        self.slow_steps = self._step_record.slow_steps
        self.slowest_step: Optional[dict] = None
        rl_path = default_runlog_path(config.ckpt_dir)
        # one writer per run: in multi-process runs only process 0 logs
        # (the same gate the checkpoint writer uses) — N appenders to one
        # JSONL would duplicate every record Nx and can tear lines on
        # shared filesystems
        if rl_path and jax.process_index() != 0:
            rl_path = None
        # the RunLog keeps an in-memory tail for the cluster telemetry
        # push only when pushing is on (obs.aggregate drains it)
        from hetu_tpu.obs.aggregate import push_interval
        tail = 256 if push_interval() > 0 else 0
        self.run_log = (RunLog(rl_path, tail_records=tail)
                        if rl_path else None)
        # -- training health monitor (obs.health, HETU_TPU_HEALTH): None
        # unless the flag is set — the per-step cost of "off" is one None
        # check.  On anomalies of the severe kinds it emergency-saves
        # through the PR 3 checkpoint path (best-effort, never raises).
        from hetu_tpu.obs.health import maybe_health_monitor
        self._health = maybe_health_monitor(
            runlog=self.run_log,
            emergency_hook=(self._health_emergency_save
                            if self._ckpt is not None else None))
        # -- numerics observatory (obs/numerics.py, HETU_TPU_NUMERICS):
        # read ONCE at build — the identity contract is that unset means
        # the step wrapper never runs and the traced program is
        # byte-identical to the seed.  The numerics health detectors
        # (underflow_creep, quant_snr_collapse, ef_residual_blowup,
        # router_collapse) ride the same HETU_TPU_HEALTH gate as the
        # scalar monitor above.
        from hetu_tpu.obs.numerics import numerics_enabled, record_every
        self._numerics = numerics_enabled()
        self._numerics_every = record_every()
        from hetu_tpu.obs.health import maybe_numerics_health_monitor
        self._num_health = (maybe_numerics_health_monitor(
            runlog=self.run_log) if self._numerics else None)
        # loss-scale transition tracking (scaler RunLog events +
        # scaler.loss_scale gauge — active whenever AMP is, numerics or
        # not: scale dynamics were previously unobservable)
        self._last_loss_scale = None
        self._pending_scale = None
        c = config
        self.optimizer = optim.AdamW(
            lr=optim.cosine_schedule(c.lr, c.warmup_steps, c.total_steps,
                                     c.min_lr_ratio),
            b1=c.beta1, b2=c.beta2, eps=c.eps, weight_decay=c.weight_decay)

    def _health_emergency_save(self):
        """Bank state NOW (the HealthMonitor's emergency hook for NaN
        anomalies): a synchronous save so a dying run loses at most the
        poisoned step, not a checkpoint interval."""
        self.save(wait=True)

    def _declared(self):
        """Context declaring this trainer's CP data layout to the ring for
        the duration of a (possibly tracing) step call."""
        from hetu_tpu.parallel.ring_attention import declared_cp_split
        return declared_cp_split(self._cp_split)

    # ------------------------------------------------------------------
    def _make_shardings(self):
        """(param_shardings, opt_state_shardings) — overridable (e.g. the
        LoRA SFT trainer replicates its tiny adapter tree)."""
        return state_shardings(self.model, self.mesh, self.strategy.zero)

    def lower_abstract(self):
        """The train step lowered for ABSTRACT arguments laid out as
        `build` lays out the real ones (`.compile()` gives the program
        `train_step` runs for the configured batch).  Nothing is
        materialised, so it lowers over a mesh of described devices too:
        tests/test_chip_compile.py compiles it for a TPU that is not
        attached.  The default step only — no compressed sync, no loss
        scaler (their state is built by `build`)."""
        if (self._grad_compress != "none" or self._zero_compress != "none"
                or self._scaler is not None):
            raise NotImplementedError(
                "lower_abstract covers the default train step only")
        c, mesh = self.config, self.mesh

        def placed(tree, shardings):
            return jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=s),
                tree, shardings)
        with use_mesh(mesh), self._declared():
            self._pshard, self._sshard = self._make_shardings()
            abstract = self.model.abstract_params()
            params = placed(abstract, self._pshard)
            opt_state = placed(jax.eval_shape(self.optimizer.init, abstract),
                               self._sshard)
            n_micro = c.num_micro_batches(max(self.strategy.dp, 1))
            tokens = jax.ShapeDtypeStruct(
                (n_micro, c.global_batch_size // n_micro, c.seq_len),
                jnp.int32, sharding=self._batch_sharding(3))
            key = jax.eval_shape(lambda: jax.random.key(0))
            key = jax.ShapeDtypeStruct(key.shape, key.dtype,
                                       sharding=NamedSharding(mesh, P()))
            return self._make_step_pool(self._pshard, self._sshard).lower(
                params, opt_state, {"input_ids": tokens, "labels": tokens},
                key, None)

    def build(self, rng: Optional[jax.Array] = None):
        """Materialize sharded params/opt state and compile the step."""
        c, mesh = self.config, self.mesh
        # a loop that starts again: its first step follows no other
        self._step_record.reset()
        rng = rng if rng is not None else jax.random.key(c.seed)

        with use_mesh(mesh):
            self.params = self.model.init(rng, mesh=mesh)
            self._pshard, self._sshard = self._make_shardings()
            self.opt_state = jax.jit(
                self.optimizer.init, out_shardings=self._sshard)(self.params)
            if self._grad_compress != "none":
                # bucket layout is a compile-time constant: one plan from
                # the abstract grad shapes, padded so every bucket chunks
                # cleanly into dp rows of whole quantization blocks
                from hetu_tpu.comm import DEFAULT_BLOCK, BucketPlan
                dp = self.strategy.dp
                self._bucket_plan = BucketPlan.build(
                    jax.tree.map(
                        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                        self.model.abstract_params()),
                    multiple=dp * DEFAULT_BLOCK)
                from hetu_tpu.comm.grad_sync import uses_error_feedback
                if uses_error_feedback(self._grad_compress):
                    # the EF residuals ride in the optimizer-state pytree:
                    # they checkpoint, donate and reshard with the moments
                    from hetu_tpu.optim.optimizer import ef_state_entry
                    ef0, ef_sh = ef_state_entry(
                        self._bucket_plan, mesh, dp,
                        topology=self._comm_topology)
                    self.opt_state["ef"] = ef0
                    self._sshard = dict(self._sshard, ef=ef_sh)
            if self._zero_compress != "none":
                # static slicing/gather plan of the quantized refresh:
                # which dim zero_shardings split over dp, per leaf
                from hetu_tpu.optim.zero_refresh import (refresh_dims,
                                                         refresh_specs)
                self._zr_dims = refresh_dims(self._sshard["m"])
                self._zr_specs = refresh_specs(self._sshard["m"])
            if self._scaler is not None:
                self.scaler_state = jax.device_put(
                    self._scaler.init(), NamedSharding(mesh, P()))
            self._step_fn = self._make_step_pool(self._pshard, self._sshard)
        from hetu_tpu.utils import flags
        sched_path = flags.str_flag("HETU_TPU_TRACE_SCHEDULE")
        if sched_path and self.strategy.pp > 1:
            # render THIS run's micro-batch schedule (per-stage fwd/bwd/
            # bubble lanes) for Perfetto — hardware-free, from the same
            # validity masks the pipeline engines scan over
            from hetu_tpu.obs.trace import pipeline_schedule_trace
            n_micro = c.num_micro_batches(max(self.strategy.dp, 1))
            try:
                pipeline_schedule_trace(
                    self.strategy.pp, n_micro,
                    schedule=c.pp_schedule).save(sched_path)
                logger.info(
                    f"pipeline schedule trace written to {sched_path}")
            except OSError as e:
                # telemetry must not be fatal: a bad trace path costs the
                # render, never the run
                logger.warning(f"schedule trace to {sched_path} "
                               f"failed: {e!r}")
        return self

    def _make_step_pool(self, pshard, sshard):
        """One compiled train step per batch-shape signature (the
        reference's ExecGraphPlan pool, define_and_run_graph.cc:1174/:303):
        multi-bucket training compiles once per bucket length and dispatches
        per batch, with the pool's retrace guard replacing jit's silent
        recompiles."""
        from hetu_tpu.engine.plan_pool import PlanPool
        return PlanPool(
            self._train_step,
            jit_kwargs=dict(out_shardings=(pshard, sshard, None, None),
                            donate_argnums=(0, 1)),
            max_plans=self._plan_cap(),
            name="train_step",
            # dispatch keys hash the BATCHES pytree only — params/opt_state
            # shapes never change within one pool
            key_argnums=(2,),
            on_compile=self._on_plan_compile)

    @staticmethod
    def _plan_cap():
        """HETU_TPU_MAX_PLANS resolution — one source of truth for the
        train and eval pools."""
        from hetu_tpu.utils import flags
        return flags.int_flag("HETU_TPU_MAX_PLANS") or None

    def _plan_dispatch_key(self):
        """Traced-behavior inputs that are NOT visible in the batch shapes:
        the CP data layout declared around the trace (it changes the ring's
        static tile masks and the label convention)."""
        return (self._cp_split, self._labels_shifted)

    def _on_plan_compile(self, pool_name, key, plan, compile_s):
        """PlanPool hook: every fresh XLA compile leaves a run-event record
        with XLA's FLOP count and a hardware-free estimated MFU (the
        roofline over cost_analysis — obs.mfu), so BENCH tooling can
        attribute cost even when the step never executes on hardware."""
        self._registry.inc("trainer.compiles", pool=pool_name)
        self._registry.observe("trainer.compile_s", compile_s,
                               pool=pool_name)
        from hetu_tpu.utils import flags as _flags
        comm_analyze = _flags.bool_flag("HETU_TPU_COMM_ANALYZE")
        est, comm = {}, {}
        # ONE lazy as_text() shared by the comm analysis and the
        # profiler — stringifying a large module twice per compile is
        # the cost HETU_TPU_COMM_ANALYZE=0 exists to avoid
        hlo_txt = [None]

        def _hlo_text():
            if hlo_txt[0] is None:
                hlo_txt[0] = plan.as_text()
            return hlo_txt[0]
        # the est/comm numbers feed BOTH the compile run-event and the
        # declared-budget check — a budget with no RunLog still needs
        # them (enforcement must not depend on where the log lives)
        if (self.run_log is not None
                or _flags.str_flag("HETU_TPU_BUDGETS")):
            from hetu_tpu.obs.mfu import estimate_from_compiled
            try:
                # phase attribution parses the full HLO text — too heavy
                # for a per-compile hook on big programs; mfu_report()
                # does the phase-resolved version on demand
                est = estimate_from_compiled(plan, with_phases=False)
            except Exception:
                est = {}
            try:
                # bytes-on-wire of this plan's collectives (obs.comm) —
                # this is where a HETU_TPU_GRAD_COMPRESS win becomes a
                # RunLog fact.  It costs the one shared as_text() per
                # fresh compile; that is once per plan, not per step,
                # but very large programs can opt out via
                # HETU_TPU_COMM_ANALYZE=0
                if comm_analyze:
                    from hetu_tpu.obs.comm import collective_report
                    comm = collective_report(_hlo_text())
            except Exception:
                comm = {}
        sync = self._grad_sync_form(_hlo_text) if comm_analyze else {}
        if self.run_log is not None:
            self.run_log.log(
                "compile", name=pool_name, plan=str(key)[:500],
                compile_s=compile_s, flops=est.get("flops_per_step"),
                kernel_routes=self.kernel_routes,
                estimated_mfu=est.get("estimated_mfu"),
                estimated_step_s=est.get("estimated_step_s"),
                comm_bytes=comm.get("total_wire_bytes"),
                comm_s_est=comm.get("predicted_comm_s"),
                collectives={op: rec["count"] for op, rec in
                             (comm.get("collectives") or {}).items()}
                or None,
                grad_sync=sync or None,
                grad_compress=(self._grad_compress
                               if self._grad_compress != "none"
                               else None),
                zero_compress=(self._zero_compress
                               if self._zero_compress != "none"
                               else None),
                comm_topology=("two_level"
                               if self._comm_topology is not None
                               else None))
        # analytic step profile (HETU_TPU_PROFILE): per-layer HLO
        # attribution + peak-HBM -> a schema-versioned `profile` record
        # next to the compile event, then the declared-budget check
        # (both run with or without a RunLog — enforcement must not
        # depend on where the log lives)
        prof = self._maybe_profile(plan, _hlo_text)
        if prof is not None and self.run_log is not None:
            self.run_log.log("profile", name=pool_name,
                             plan=str(key)[:500], **prof)
        # graph-contract lints (HETU_TPU_LINT): donation / replication /
        # dtype / scope-coverage over this plan's optimized HLO — same
        # shared as_text, pure post-compile analysis
        lint_rec = self._maybe_lint(pool_name, _hlo_text)
        if lint_rec is not None and self.run_log is not None:
            self.run_log.log("lint", name=pool_name,
                             plan=str(key)[:500], **lint_rec)
        self._check_budgets(pool_name, prof, est, comm)

    def _grad_sync_form(self, hlo_text_fn) -> Dict[str, float]:
        """The form the dp gradient sync took in the program just
        compiled (`obs.comm.grad_sync_report`), as gauges
        `trainer.grad_sync_collectives{form=all_reduce|reduce_scatter}`
        (collectives a step) and `trainer.grad_sync_bytes_step` (their
        bytes on the wire a step a chip) — whether ZeRO's split let the
        backward reduce-scatter into the state's shards
        (`optim.zero_shardings`) is a fact of the compiled text, not of
        the strategy.  Without a dp axis there is no sync: nothing is
        read and nothing set."""
        if self.strategy.dp <= 1:
            return {}
        try:
            from hetu_tpu.core.mesh import mesh_axis_group
            from hetu_tpu.obs.comm import grad_sync_report
            sync = grad_sync_report(
                hlo_text_fn(), mesh_axis_group(self.mesh, "dp"),
                default_world=self.mesh.devices.size)
        except Exception as e:
            logger.warning(f"per-compile grad-sync count failed: {e!r}")
            return {}
        for form in ("all_reduce", "reduce_scatter"):
            self._registry.set_gauge("trainer.grad_sync_collectives",
                                     sync[form], form=form)
        self._registry.set_gauge("trainer.grad_sync_bytes_step",
                                 sync["wire_bytes"])
        return sync

    def _maybe_profile(self, plan, hlo_text_fn=None):
        """The flag-gated per-compile analytic profile
        (obs.hlo_profile.profile_record), or None.  Costs one more walk
        of the HLO text per FRESH compile; pure post-compile analysis —
        the traced program is identical with the flag on or off."""
        from hetu_tpu.utils import flags as _flags
        if not _flags.bool_flag("HETU_TPU_PROFILE"):
            return None
        try:
            from hetu_tpu.obs.hlo_profile import (layer_profile,
                                                  profile_record)
            # ONE as_text (shared with the hook's comm analysis) and ONE
            # attribution walk
            txt = hlo_text_fn() if hlo_text_fn is not None \
                else plan.as_text()
            prof = profile_record(
                plan, top_k=_flags.int_flag("HETU_TPU_PROFILE_TOPK"),
                profile=layer_profile(txt), text=txt)
            return prof
        except Exception as e:
            logger.warning(f"per-compile profile failed: {e!r}")
            return None

    def _maybe_lint(self, pool_name, hlo_text_fn):
        """The flag-gated per-compile graph-contract lint record
        (hetu_tpu/analysis/hlo_lints over this plan's optimized HLO), or
        None.  Error findings log loudly and count `lint.errors` but
        NEVER fail the step — tools_lint.py / the tier-1 acceptance test
        are the enforcing surfaces; a training run only observes.  Pure
        post-compile HLO-text analysis: the traced program is identical
        with the flag on or off (identity contract in utils/flags.py)."""
        from hetu_tpu.utils import flags as _flags
        if not _flags.bool_flag("HETU_TPU_LINT"):
            return None
        try:
            from hetu_tpu.analysis.findings import lint_record
            from hetu_tpu.analysis.hlo_lints import dtype_token, lint_hlo
            expected = dtype_token(getattr(
                getattr(self.model, "config", None), "compute_dtype", None))
            findings = lint_hlo(hlo_text_fn(), expected_dtype=expected,
                                program=pool_name)
            rec = lint_record(findings)
            if rec["findings"]:
                self._registry.inc("lint.findings", rec["findings"],
                                   pool=pool_name)
            if rec["errors"]:
                self._registry.inc("lint.errors", rec["errors"],
                                   pool=pool_name)
                for msg in rec.get("messages", []):
                    logger.warning(f"lint ({pool_name}): {msg}")
            if rec["warnings"]:
                self._registry.inc("lint.warnings", rec["warnings"],
                                   pool=pool_name)
            return rec
        except Exception as e:
            logger.warning(f"per-compile lint failed: {e!r}")
            return None

    def _check_budgets(self, pool_name, prof, est, comm):
        """Check this compile's hardware-free metrics against the
        declared perf budget (HETU_TPU_BUDGETS): breaches count
        `budget.breaches`, leave a `budget` run event, log loudly, and
        — only when the budget file declares `"enforce": true` — raise
        BudgetError.  Unset flag = one str check, nothing else."""
        from hetu_tpu.utils import flags as _flags
        if not _flags.str_flag("HETU_TPU_BUDGETS"):
            return
        from hetu_tpu.obs.budget import (BudgetError, PerfBudget,
                                         check_absolute, enforce,
                                         extract_metrics,
                                         summarize_breaches)
        try:
            budget = PerfBudget.load()
        except (OSError, ValueError) as e:
            # a typo'd budget must not silently watch nothing (the
            # loader's own contract): surface it as the one hook error
            # the PlanPool lets through
            raise BudgetError(
                f"invalid perf budget "
                f"({_flags.str_flag('HETU_TPU_BUDGETS')}): {e}") from e
        try:
            # estimator precedence is FIXED so a budget verdict cannot
            # flip with HETU_TPU_PROFILE: step time always comes from
            # the whole-program roofline (est) and comm bytes from the
            # analyzer — the profile only contributes the metrics no
            # other estimator produces (peak HBM)
            metrics = {}
            if est:
                metrics["estimated_mfu"] = est.get("estimated_mfu")
                metrics["step_time_s"] = est.get("estimated_step_s")
            if comm:
                metrics["comm_bytes"] = comm.get("total_wire_bytes")
            for k, v in (extract_metrics(prof) if prof else {}).items():
                if metrics.get(k) is None:
                    metrics[k] = v
            metrics = {k: v for k, v in metrics.items() if v is not None}
            breaches = check_absolute(metrics, budget)
            from hetu_tpu.obs.budget import ABSOLUTE_CEILINGS
            missing = [k for k, attr, _kind in ABSOLUTE_CEILINGS
                       if getattr(budget, attr) is not None
                       and k not in metrics]
            if missing:
                # a declared ceiling that silently goes unchecked is the
                # failure mode the sentinel exists to prevent — say so
                logger.warning(
                    f"budget ceilings on {missing} could not be checked "
                    f"for compile {pool_name} (metric unavailable; "
                    f"peak_hbm_bytes needs HETU_TPU_PROFILE=1)")
        except Exception as e:
            logger.warning(f"budget check failed: {e!r}")
            return
        self._registry.inc("budget.checks")
        if breaches:
            self._registry.inc("budget.breaches", len(breaches))
            logger.error(f"perf budget breached (compile {pool_name}):\n"
                         + summarize_breaches(breaches))
        if self.run_log is not None:
            self.run_log.log("budget", name=pool_name, ok=not breaches,
                             breaches=breaches or None,
                             budget=budget.source)
        enforce(breaches, budget)

    # ------------------------------------------------------------------
    def _loss_fn(self, params, batch, rng):
        """Returns (sum_loss, token_count): micro batches are weighted by
        their true (non-pad) token counts so accumulation == full batch."""
        c = self.config
        return self.model(
            params, batch["input_ids"], labels=batch["labels"],
            position_ids=batch.get("position_ids"),
            segment_ids=batch.get("segment_ids"),
            rng=rng, deterministic=c.dropout_deterministic,
            loss_reduction="sum", labels_shifted=self._labels_shifted)

    def _train_step(self, params, opt_state, batches, rng, scaler_state):
        """The traced step the PlanPool jits.  With HETU_TPU_NUMERICS on
        it wraps the real step in a numerics collector: taps anywhere in
        the step's trace accumulate into an auxiliary stats pytree that
        rides out under ``metrics["numerics"]`` (donation-safe — metrics
        are never donated; host-fetched only on record boundaries).
        Flag unset: the wrapper never runs, the trace is byte-identical
        (registered identity contract, swept by tools_lint --flags)."""
        from hetu_tpu.ops.pallas import record_routes
        with record_routes() as routes:
            out = self._train_step_traced(params, opt_state, batches, rng,
                                          scaler_state)
        # which kernels this plan runs, and why (trace-time fact; the
        # compile run-event carries it)
        self.kernel_routes = routes
        return out

    def _train_step_traced(self, params, opt_state, batches, rng,
                           scaler_state):
        if not self._numerics:
            return self._train_step_impl(params, opt_state, batches, rng,
                                         scaler_state)
        from hetu_tpu.obs import numerics as _numerics
        with _numerics.collecting() as col:
            params, opt_state, metrics, scaler_state = \
                self._train_step_impl(params, opt_state, batches, rng,
                                      scaler_state)
            stats = col.finalize()
            if stats:
                metrics = dict(metrics, numerics=stats)
        return params, opt_state, metrics, scaler_state

    def _train_step_impl(self, params, opt_state, batches, rng,
                         scaler_state):
        """batches: pytree with leading micro-batch dim [n_micro, mb, seq]."""
        c = self.config
        lead = jax.tree.leaves(batches)[0]
        n_micro = lead.shape[0]
        # the EF residuals ride in opt_state but belong to the SYNC, not
        # the optimizer update: lift them out here, reattach updated ones
        # below ({} when mode "int8" carries no residuals)
        ef_state, new_ef = {}, {}
        if self._grad_compress != "none":
            ef_state = opt_state.pop("ef", {})
        if self._scaler is not None:
            # normalize the scale by the STATIC token-slot count so fp16
            # cotangent magnitudes are batch-size-independent (the torch
            # mean-loss convention) — the sum-loss would push the effective
            # scale up by O(tokens) and overflow before calibrating
            slots = float(n_micro * lead.shape[1] * max(lead.shape[2] - 1, 1))
            scale = scaler_state["scale"] / slots
        else:
            scale = jnp.asarray(1.0, jnp.float32)

        if self.strategy.pp > 1:
            # pipeline mode: micro-batching happens INSIDE the model's
            # circular pipeline (reference CrucialRun micro loop); feed the
            # whole global batch at once
            flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in batches.items()}

            if c.pp_schedule == "1f1b":
                # PipeDream-flush manual-VJP schedule (reference:
                # executable_graph.cc:836) — grads come back directly;
                # dropout masks replay exactly in the backward visit (the
                # rng rides the saved token stream)
                (lsum, csum), grads = self.model.pipeline_train_grads(
                    params, flat["input_ids"], flat["labels"],
                    position_ids=flat.get("position_ids"),
                    segment_ids=flat.get("segment_ids"), n_micro=n_micro,
                    labels_shifted=self._labels_shifted,
                    loss_scale=scale,
                    rng=None if c.dropout_deterministic else rng)
            else:
                def pp_loss(p):
                    lsum_, csum_ = self.model(
                        p, flat["input_ids"], labels=flat["labels"],
                        position_ids=flat.get("position_ids"),
                        segment_ids=flat.get("segment_ids"),
                        rng=None if c.dropout_deterministic else rng,
                        deterministic=c.dropout_deterministic,
                        loss_reduction="sum", n_micro=n_micro,
                        labels_shifted=self._labels_shifted)
                    # loss SCALING happens on the fp32 sum (gradscaler.h:33)
                    return lsum_.astype(jnp.float32) * scale, (lsum_, csum_)

                (_, (lsum, csum)), grads = jax.value_and_grad(
                    pp_loss, has_aux=True)(params)
        elif self._grad_compress != "none":
            # quantized DP sync (comm/grad_sync.py): per-replica grads in a
            # shard_map over dp, then int8 all-to-all/all-gather instead of
            # the f32 all-reduce GSPMD would insert
            keys = jax.random.split(rng, n_micro)
            grads, lsum, csum, new_ef = self._compressed_grads(
                params, batches, keys, scale, ef_state)
        else:
            keys = jax.random.split(rng, n_micro)
            grads, lsum, csum, mstats = self._accumulate_grads(
                params, batches, keys, scale)
            if mstats:
                # model-scope taps drained inside the micro scan, stacked
                # [n_micro, ...] by its ys — fold per stat rule and hand
                # to the ambient collector (no-op when numerics is off)
                from hetu_tpu.obs import numerics as _numerics
                _numerics.merge(_numerics.reduce_stacked(mstats))

        denom = jnp.maximum(csum, 1.0)
        # fold the unscale into the token normalize (one pass over grads)
        grads = jax.tree.map(lambda g: g / (denom * scale), grads)
        if self._numerics:
            from hetu_tpu.obs import numerics as _numerics
            _numerics.tap_tree("params", params)
            _numerics.tap_tree("grads", grads)
            if self._scaler is not None:
                _numerics.tap_stats("scaler",
                                    scale=scaler_state["scale"])
        grads_sharded = False
        if getattr(self.strategy, "zero_stage", 1) >= 2 and self.strategy.dp > 1:
            # ZeRO-2: keep grads dp-sharded through clip+update (GSPMD turns
            # the grad sync into reduce-scatter; params re-gather after)
            grads = jax.tree.map(
                lambda g, sh: jax.lax.with_sharding_constraint(g, sh),
                grads, self._sshard["m"])
            grads_sharded = True
        with jax.named_scope("optimizer"):
            grads, gnorm = optim.clip_by_global_norm(grads, c.grad_clip)
        metrics = {"loss": lsum / denom}
        if self._scaler is None:
            params, opt_state = self._apply_update(
                grads, opt_state, params, grads_sharded)
            if new_ef:
                opt_state["ef"] = new_ef
            metrics["grad_norm"] = gnorm
            metrics["lr"] = self.optimizer._lr(opt_state["step"])
            return params, opt_state, metrics, scaler_state

        # AMP: skip the update on non-finite grads, back the scale off
        # (reference: CheckFinite.cc + update_scale.cc semantics)
        finite = self._scaler.all_finite(grads)
        safe_grads = jax.tree.map(jnp.nan_to_num, grads)
        new_params, new_opt = self._apply_update(
            safe_grads, opt_state, params, grads_sharded)
        params = jax.tree.map(lambda n, o: jnp.where(finite, n, o),
                              new_params, params)
        opt_state = jax.tree.map(lambda n, o: jnp.where(finite, n, o),
                                 new_opt, opt_state)
        if new_ef:
            # a skipped step keeps the previous residuals too: the grads
            # that produced new_ef never entered the params
            opt_state["ef"] = jax.tree.map(
                lambda n, o: jnp.where(finite, n, o), new_ef, ef_state)
        new_scaler_state = self._scaler.update(scaler_state, finite)
        if new_ef:
            # EF residuals live in SCALED-grad units (the sync quantizes
            # grads of scale * loss).  When the dynamic scale moves —
            # growth streak or non-finite backoff — last step's residuals
            # would be off by old/new scale at the next quantize (the
            # PR 2 known limit: one step of stale error feedback per
            # scale change).  Rescaling by new/old keeps them exact;
            # the ratio is 1 on scale-stable steps.
            ratio = new_scaler_state["scale"] / scaler_state["scale"]
            opt_state["ef"] = jax.tree.map(lambda r: r * ratio,
                                           opt_state["ef"])
        scaler_state = new_scaler_state
        metrics["grad_norm"] = jnp.where(finite, gnorm, jnp.nan)
        metrics["lr"] = self.optimizer._lr(opt_state["step"])
        metrics["loss_scale"] = scaler_state["scale"]
        metrics["amp_skipped"] = 1.0 - finite.astype(jnp.float32)
        return params, opt_state, metrics, scaler_state

    def _apply_update(self, grads, opt_state, params,
                      grads_sharded: bool = False):
        """The optimizer update, routed through the quantized ZeRO
        refresh when HETU_TPU_ZERO_COMPRESS is on: the update math runs
        on each rank's dp shard of the opt state and the param DELTA
        all-gathers as int8/int4 + scales instead of GSPMD's f32 param
        all-gather (optim/zero_refresh.py).  "none" calls the plain
        update — traced program unchanged."""
        # the "optimizer" scope marks the update region in HLO metadata
        # so obs.hlo_profile attributes its FLOPs/bytes separately from
        # the model layers (the GSPMD-inserted ZeRO param all-gather
        # lands here too — it consumes the updated shards)
        with jax.named_scope("optimizer"):
            if self._zero_compress == "none":
                return self.optimizer.update(grads, opt_state, params)
            from hetu_tpu.optim.zero_refresh import quantized_zero_update
            return quantized_zero_update(
                self.optimizer, grads, opt_state, params, mesh=self.mesh,
                dims=self._zr_dims, specs=self._zr_specs,
                mode=self._zero_compress, grads_sharded=grads_sharded)

    # ------------------------------------------------------------------
    def _accumulate_grads(self, params, batches, keys, scale):
        """The micro-batch grad-accumulation scan -> (sum-grads, loss
        sum, token count, per-micro numerics stats).  ONE definition
        shared by the GSPMD path and the compressed shard_map body —
        fp32/int8 loss parity is defined by these being the same
        arithmetic, so they must not drift apart.

        The stats frame opens INSIDE the grad-traced loss so the model's
        boundary taps (embed/hidden/logits, MoE router) can escape the
        transform legally via value_and_grad's aux channel; the scan
        stacks them [n_micro, ...] into its ys (an empty pytree — and an
        unchanged trace — when numerics is off)."""
        from hetu_tpu.obs import numerics as _numerics

        def micro(acc, xs):
            batch, key = xs

            def scaled_loss(p):
                with _numerics.frame() as nf:
                    l, count = self._loss_fn(p, batch, key)
                return l.astype(jnp.float32) * scale, (l, count, nf.stats)

            (_, (l, count, ns)), g = jax.value_and_grad(
                scaled_loss, has_aux=True)(params)
            acc_g, acc_l, acc_c = acc
            return (jax.tree.map(jnp.add, acc_g, g), acc_l + l,
                    acc_c + count), ns

        zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
        zero = jnp.zeros((), jnp.float32)
        (grads, lsum, csum), mstats = jax.lax.scan(
            micro, (zero_g, zero, zero), (batches, keys))
        return grads, lsum, csum, mstats

    def _compressed_grads(self, params, batches, keys, scale, ef_state):
        """Per-replica grad accumulation + quantized DP sync, as ONE
        shard_map over the dp axis (comm/grad_sync.py).

        Inside the manual region each replica runs the same micro-batch
        scan as the GSPMD path over its local batch rows, then the sync
        replaces GSPMD's f32 grad all-reduce with int8/int4 all-to-all +
        all-gather (~3.94x / ~7.76x fewer bytes on wire, comm/wire.py),
        hierarchically routed when a two-level topology applies.
        Loss/token sums psum as f32 scalars.  Dropout keys fold in the
        replica's axis index (grad_sync.per_replica_keys) so each replica
        draws independent masks — matching the per-row independence of
        the GSPMD lowering."""
        from jax import shard_map
        from hetu_tpu.comm.grad_sync import (ef_specs, per_replica_keys,
                                             quantized_grad_sync)
        from hetu_tpu.obs import numerics as _numerics
        dp = self.strategy.dp

        def body(params, batches, keys, scale, ef_state):
            keys = per_replica_keys(keys, "dp")
            grads, lsum, csum, mstats = self._accumulate_grads(
                params, batches, keys, scale)
            # "grad_sync" scope: the explicit quantized collectives are
            # individually attributable in the per-layer HLO profile
            # (the GSPMD path's implicit all-reduce cannot be scoped —
            # it inherits its producing layer's scope; documented limit)
            with jax.named_scope("grad_sync"):
                with _numerics.frame() as nf:
                    grads, new_ef = quantized_grad_sync(
                        grads, "dp", dp, self._bucket_plan,
                        self._grad_compress, ef_state,
                        topology=self._comm_topology)
            nstats = {}
            if _numerics.active():
                # micro-stacked model stats + the sync's SNR taps + EF
                # residual norms, folded across dp inside the manual
                # region so the body can return replicated stats
                nstats = dict(_numerics.reduce_stacked(mstats))
                nstats.update(nf.stats)
                if new_ef:
                    nstats["ef"] = _numerics.tree_stats(new_ef)
                nstats = _numerics.reduce_axis(nstats, "dp")
            return (grads, jax.lax.psum(lsum, "dp"),
                    jax.lax.psum(csum, "dp"), new_ef, nstats)

        batch_specs = jax.tree.map(
            lambda v: P(*([None, "dp"] + [None] * (v.ndim - 2))), batches)
        especs = (ef_specs(self._bucket_plan,
                           topology=self._comm_topology)
                  if ef_state else {})
        fn = shard_map(
            body, mesh=self.mesh,
            in_specs=(P(), batch_specs, P(), P(), especs),
            out_specs=(P(), P(), P(), especs, P()),
            # the gathered grads ARE replicated over dp but the checker
            # cannot infer that through all-to-all
            check_vma=False)
        from hetu_tpu.dstates import suppress_constraints
        with suppress_constraints():
            # the model's activation constraints (strategy.constrain) are
            # illegal AND vacuous inside the fully-manual region
            grads, lsum, csum, new_ef, nstats = fn(
                params, batches, keys, scale, ef_state)
        _numerics.merge(nstats)
        return grads, lsum, csum, new_ef

    # ------------------------------------------------------------------
    def _batch_sharding(self, ndim: int):
        """[n_micro, mb, seq(, ...)]: mb over dp, seq over cp."""
        st = self.strategy
        spec = [None] * ndim
        if st.dp > 1:
            spec[1] = "dp"
        if st.cp > 1:
            spec[2] = "cp"
        return NamedSharding(self.mesh, P(*spec))

    def _cp_reorder(self, host_batch: Dict[str, np.ndarray]):
        """Apply the declared CP split's seq permutation (reference:
        bucket.py:193 generate_cp_pack_data — pre-shift labels, then deal
        the seq across ranks for causal balance).

        Pre-shifting labels (labels[t] := labels[t+1], tail -100) makes the
        next-token objective permutation-safe; the models consume them with
        labels_shifted=True. position_ids are synthesized when absent so
        rotary + ring masking see true token positions after the reorder."""
        split = self._cp_split
        if split in (None, "normal"):
            return host_batch
        seq = host_batch["input_ids"].shape[1]
        perm = self._cp_perm_cache.get(seq)
        if perm is None:
            from hetu_tpu.data.bucket import cp_split_indices
            try:
                perm = np.concatenate(
                    cp_split_indices(seq, self.strategy.cp, split))
            except (AssertionError, ValueError) as e:
                if not self._cp_layout_used:
                    # nothing traced yet: fall back to the contiguous layout
                    # instead of failing runs whose seq doesn't divide the
                    # fancier split (flag defaults are not an opt-in wall)
                    logger.warning(
                        f"seq {seq} incompatible with cp_split={split!r} at "
                        f"cp={self.strategy.cp} ({e}); falling back to "
                        f"'normal'")
                    self._cp_split = "normal"
                    self._labels_shifted = False
                    return host_batch
                raise ValueError(
                    f"seq {seq} incompatible with cp_split={split!r} at "
                    f"cp={self.strategy.cp} after steps already ran under "
                    f"this layout: {e}; pad the bucket ladder or set "
                    f"HETU_TPU_CP_SPLIT=normal") from None
            self._cp_perm_cache[seq] = perm
        self._cp_layout_used = True
        out = dict(host_batch)
        if "labels" in out:
            lab = out["labels"]
            shifted = np.full_like(lab, -100)
            shifted[:, :-1] = lab[:, 1:]
            out["labels"] = shifted
        if "position_ids" not in out:
            out["position_ids"] = np.broadcast_to(
                np.arange(seq, dtype=np.int32),
                out["input_ids"].shape).copy()
        for k, v in out.items():
            if v.ndim >= 2 and v.shape[1] == seq:
                out[k] = np.ascontiguousarray(v[:, perm])
        return out

    def prepare_batch(self, host_batch: Dict[str, np.ndarray]):
        """Reshape [gbs, seq] -> [n_micro, mb*dp, seq], device_put sharded.
        (reference: trainer.py:465 prepare_feed_dict)"""
        c, st = self.config, self.strategy
        host_batch = self._cp_reorder(host_batch)
        n_micro = c.num_micro_batches(st.dp)
        out = {}
        for k, v in host_batch.items():
            g = v.shape[0]
            assert g == c.global_batch_size, (k, v.shape)
            v = v.reshape(n_micro, g // n_micro, *v.shape[1:])
            out[k] = jax.device_put(v, self._batch_sharding(v.ndim))
        return out

    @staticmethod
    def _shape_key(host_batch):
        """THE per-batch-shape cache key — one construction shared by
        _memo_by_shape and lowered_step so the report caches and the
        linter's compiled-text path can never diverge."""
        return tuple(sorted((k, tuple(np.asarray(v).shape))
                            for k, v in host_batch.items()))

    def _memo_by_shape(self, attr: str, host_batch, compute):
        """Per-batch-shape memo shared by the report surfaces (memory/
        phase/mfu): ONE key construction so the three caches can never
        diverge.  `compute(key)` runs on miss."""
        key = self._shape_key(host_batch)
        cache = self.__dict__.setdefault(attr, {})
        if key not in cache:
            cache[key] = compute(key)
        return cache[key]

    def memory_report(self, host_batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        """XLA's compiled-memory breakdown of the train step for this batch
        shape — the per-plan analog of the reference's micro-batch memory
        profiler (reference: hetu/graph/profiler.h:15-39 memory records;
        GetCUDAProfiler).  AOT lower().compile() does NOT share jit's
        dispatch cache, so the first call per batch shape pays one full XLA
        compile; results are memoized per shape here."""
        def compute(key):
            mem = self._compiled_for_shape(host_batch, key).memory_analysis()
            out = {}
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
                v = getattr(mem, k, None)
                if v is not None:
                    out[k.replace("_in_bytes", "")] = int(v)
            # donated params/opt aliasing means live peak ~ args + temp
            out["peak_estimate"] = (out.get("argument_size", 0)
                                    + out.get("temp_size", 0))
            return out
        return self._memo_by_shape("_memory_reports", host_batch, compute)

    def lowered_step(self, host_batch, *, optimized: bool = False) -> str:
        """The train step's lowered module text for this batch shape.

        optimized=False (default) returns the TRACED pre-optimization
        module — one trace, no XLA compile: the flag-identity sweep's
        fingerprint surface (hetu_tpu/analysis/flag_identity.py; every
        flag contract acts at trace/build time, so trace-level identity
        implies compiled identity).  optimized=True returns the
        post-optimization text of the AOT compile, shared with
        memory_report/phase_report via the per-shape memo — what the
        HLO lints (tools_lint.py --hlo) walk."""
        if optimized:
            return self._compiled_for_shape(
                host_batch, self._shape_key(host_batch)).as_text()
        batches = self.prepare_batch(host_batch)
        rng = jax.random.key(0)
        with use_mesh(self.mesh), self._declared():
            return self._step_fn.lower(
                self.params, self.opt_state, batches, rng,
                self.scaler_state).as_text()

    def _compiled_for_shape(self, host_batch, key):
        """AOT lower().compile() of the step for this batch shape — ONE
        compile shared by memory_report and phase_report (it does not
        share jit's dispatch cache, so it costs a full XLA compile)."""
        cache = getattr(self, "_compiled_steps", None)
        if cache is None:
            cache = self._compiled_steps = {}
        if key not in cache:
            batches = self.prepare_batch(host_batch)
            rng = jax.random.key(0)
            with use_mesh(self.mesh), self._declared():
                cache[key] = self._step_fn.lower(
                    self.params, self.opt_state, batches, rng,
                    self.scaler_state).compile()
        return cache[key]

    def phase_report(self, host_batch: Dict[str, np.ndarray]):
        """Per-phase (embed/attn/moe/mlp/lm_head) attribution of the
        compiled train step from the named-scope HLO metadata — the
        reference's per-op cost records (profiler.h:25), hardware-free.
        Pairs with memory_report (shares its one AOT compile per shape)."""
        from hetu_tpu.utils.profiling import phase_breakdown
        return self._memo_by_shape(
            "_phase_reports", host_batch,
            lambda key: phase_breakdown(
                self._compiled_for_shape(host_batch, key)))

    def mfu_report(self, host_batch: Dict[str, np.ndarray]):
        """Hardware-free estimated MFU + per-phase roofline bound for the
        compiled train step at this batch shape (obs.mfu: cost_analysis
        FLOPs x hardware-profile peaks x phase_breakdown traffic).  Shares
        the one AOT compile per shape with memory_report/phase_report."""
        from hetu_tpu.obs.mfu import estimate_from_compiled
        return self._memo_by_shape(
            "_mfu_reports", host_batch,
            lambda key: estimate_from_compiled(
                self._compiled_for_shape(host_batch, key)))

    def profile_report(self, host_batch: Dict[str, np.ndarray]):
        """On-demand per-layer analytic profile of the compiled train
        step at this batch shape (obs.hlo_profile): the full roofline
        attribution per named layer/op-group plus the liveness-based
        peak-HBM estimate under "peak_hbm".  Shares the one AOT compile
        per shape with memory_report/phase_report/mfu_report; the
        flag-gated per-compile `profile` RunLog record is the compact
        top-k version of this."""
        from hetu_tpu.obs.hlo_profile import (layer_profile,
                                              peak_hbm_estimate)

        def compute(key):
            compiled = self._compiled_for_shape(host_batch, key)
            rep = layer_profile(compiled)
            rep["peak_hbm"] = peak_hbm_estimate(compiled)
            return rep
        return self._memo_by_shape("_profile_reports", host_batch, compute)

    def train_step(self, host_batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        """One step, dispatched and not waited for.  Its two host phases
        are spans on the profiler's clock (`trainer.prepare_batch`,
        `trainer.dispatch`, inside a `trainer.step` step annotation) and
        seconds in the step's record (`self._step_record`: the counters
        `trainer.steps`, `trainer.tokens`, `trainer.phase_s{phase}`, ...,
        the histogram `trainer.step_phase_s{phase}`, `slow_steps`,
        `slowest_step`; docs/observability.md).  The step only
        dispatches, so the record judges the interval from the step
        before's return to this one's (`caller`: the time between them,
        where a loop waits for a step in flight)."""
        phases = self._step_record.begin()
        with jax.profiler.StepTraceAnnotation("trainer.step",
                                              step_num=self.global_step):
            with phase_span("trainer.prepare_batch", phases):
                batches = self.prepare_batch(host_batch)
            with phase_span("trainer.dispatch", phases):
                rng = jax.random.fold_in(
                    jax.random.key(self.config.seed + 1), self.global_step)
                with use_mesh(self.mesh), self._declared():
                    self.params, self.opt_state, metrics, \
                        self.scaler_state = self._step_fn(
                            self.params, self.opt_state, batches, rng,
                            self.scaler_state,
                            strategy_id=self._plan_dispatch_key())
        self.global_step += 1
        self._registry.inc("trainer.tokens", host_batch["input_ids"].size)
        self.slowest_step = self._step_record.end(
            self.global_step, time.time(), self.slowest_step)
        return metrics

    def train(self, batches: Iterable[Dict[str, np.ndarray]],
              num_steps: Optional[int] = None) -> Dict[str, float]:
        """Main loop (reference: trainer.py:655). Returns last metrics."""
        c = self.config
        if self.params is None:
            self.build()
        t0 = time.perf_counter()
        tokens = 0
        metrics = {}
        for i, host_batch in enumerate(batches):
            if num_steps is not None and i >= num_steps:
                break
            with self.profiler.step(self.global_step):
                metrics = self.train_step(host_batch)
                self.profiler.in_flight(metrics["loss"])
            # the interval between step completions, read one step late
            # (StepProfiler): never the enqueue of the step just sent
            step_s = self.profiler.last_step_s
            batch_tokens = int(np.prod(host_batch["input_ids"].shape))
            tokens += batch_tokens
            self._registry.observe("trainer.step_time_s", step_s)
            log_boundary = (self.global_step % c.log_every) == 0
            loss = None
            self._note_scaler(metrics)
            nstats = (metrics.pop("numerics", None)
                      if isinstance(metrics, dict) else None)
            if (nstats is not None
                    and self.global_step % self._numerics_every == 0):
                self._record_numerics(nstats)
            if self._health is not None:
                # the monitor needs loss/grad_norm PER STEP — a device
                # sync the HETU_TPU_HEALTH flag explicitly opts into
                loss = float(metrics["loss"])
                gn = metrics.get("grad_norm")
                self._health.observe_step(
                    self.global_step, step_s, loss=loss,
                    grad_norm=None if gn is None else float(gn))
            if log_boundary:
                loss = float(metrics["loss"])  # forces device sync
                dt = time.perf_counter() - t0
                logger.info(
                    f"step {self.global_step} loss {loss:.4f} "
                    f"lr {float(metrics['lr']):.2e} "
                    f"grad_norm {float(metrics['grad_norm']):.3f} "
                    f"tokens/s {tokens / max(dt, 1e-9):,.0f}")
                t0, tokens = time.perf_counter(), 0
            if self.run_log is not None:
                # loss AND the device memory probe ride only on
                # log-boundary steps — float(loss) is a device sync and
                # memory_stats() a runtime query (a host round-trip on the
                # remote-TPU backend) the hot path must not pay per step.
                # With HETU_TPU_MEMORY_PROFILE on, the profiler already
                # probed this step — reuse its value so EVERY step record
                # carries memory (the flag opted into the per-step query).
                if self.profiler.mem_profile:
                    mem = self.profiler.last_mem_bytes
                else:
                    # the probe stays on log boundaries even when the
                    # health monitor synced loss on this step
                    mem = _device_mem_bytes() if log_boundary else None
                self.run_log.step(
                    self.global_step, step_s, loss=loss,
                    tokens_per_s=batch_tokens / max(step_s, 1e-9),
                    device_mem_bytes=mem,
                    plan=self._plan_fingerprint(host_batch))
            if self._ckpt and (self.global_step % c.ckpt_every) == 0:
                # the loop's own work, not a stall between two steps:
                # `trainer.empty_s`, and the step after it is not judged
                # by this gap
                self._step_record.idle()
                self.save()
        self._flush_scaler()
        self.profiler.close()
        self._obs_summary()
        return metrics

    def _note_scaler(self, metrics):
        """Loss-scale observability (docs/observability.md): with AMP on,
        every step updates the ``scaler.loss_scale`` gauge and every
        growth/backoff transition leaves ONE ``scaler`` RunLog event +
        a ``scaler.growth``/``scaler.backoff`` counter.

        The loop's hot-path invariant (per-step device syncs need an
        explicit opt-in) is preserved by reading each step's scale one
        step LATE: the device scalar is stashed here and converted on
        the next call — by then the producing step has long finished
        (the device queue is serial), so float() never blocks the host
        out of its overlap with the running step.  train() flushes the
        last pending scale at loop exit."""
        if self._scaler is None or "loss_scale" not in metrics:
            return
        self._flush_scaler()
        self._pending_scale = (self.global_step, metrics["loss_scale"])

    def _flush_scaler(self):
        """Convert-and-record the stashed loss scale (no-op when none)."""
        if self._pending_scale is None:
            return
        step, dev_scale = self._pending_scale
        self._pending_scale = None
        try:
            scale = float(dev_scale)
        except Exception:   # telemetry never kills a step
            return
        self._registry.set_gauge("scaler.loss_scale", scale)
        from hetu_tpu.optim.grad_scaler import classify_transition
        event = classify_transition(self._last_loss_scale, scale)
        if event is not None:
            self._registry.inc(f"scaler.{event}")
            if self.run_log is not None:
                self.run_log.log("scaler", event=event, scale=scale,
                                 prev=self._last_loss_scale, step=step)
        self._last_loss_scale = scale

    def _record_numerics(self, stats):
        """Host-fetch one step's numerics pytree (a handful of scalars,
        every HETU_TPU_NUMERICS_EVERY steps) and fan it out through the
        one sink: RunLog `numerics` record, numerics.* gauges (riding
        the cluster telemetry push), moe.* gauges/counters, and the
        numerics health detectors when HETU_TPU_HEALTH is on."""
        from hetu_tpu.obs import numerics as _numerics
        try:
            host = jax.device_get(stats)
        except Exception as e:   # telemetry never kills a step
            logger.warning(f"numerics fetch failed: {e!r}")
            return
        _numerics.record(host, step=self.global_step,
                         registry=self._registry, runlog=self.run_log)
        if self._num_health is not None:
            self._num_health.observe(self.global_step, host)

    def _plan_fingerprint(self, host_batch) -> str:
        """Stable id of (strategy, batch shapes) — which compiled plan a
        step dispatched to, readable across runs."""
        shapes = ",".join(f"{k}:{'x'.join(map(str, v.shape))}"
                          for k, v in sorted(host_batch.items()))
        return f"{self.strategy.describe()}|{shapes}"

    def _obs_summary(self):
        """Flush telemetry at a loop boundary: one 'summary' run-event
        (registry snapshot + step-time summary) and the optional
        HETU_TPU_METRICS_EXPORT registry dump.  Idempotent — a later
        close() appends another snapshot, never corrupts."""
        from hetu_tpu.utils import flags
        if self.run_log is not None:
            self.run_log.log("summary", profiler=self.profiler.summary(),
                             metrics=self._registry.snapshot())
        path = flags.str_flag("HETU_TPU_METRICS_EXPORT")
        if path:
            try:
                self._registry.export_jsonl(path)
            except OSError as e:
                logger.warning(f"metrics export to {path} failed: {e!r}")

    def close(self):
        """Release observability sinks (flush + close the RunLog).  Safe to
        call more than once; training after close() still runs, it just
        stops leaving run events."""
        self.profiler.close()
        self._obs_summary()
        if self.run_log is not None:
            self.run_log.close()

    # ------------------------------------------------------------------
    def evaluate(self, batches: Iterable[Dict[str, np.ndarray]],
                 max_batches: Optional[int] = None) -> Dict[str, float]:
        """Evaluation loop: token-weighted CE (router aux excluded) and
        perplexity (reference: trainer eval path)."""
        if self.params is None:
            self.build()
        if not hasattr(self, "_eval_fn"):
            def eval_step(params, batch):
                return self.model(
                    params, batch["input_ids"], labels=batch["labels"],
                    position_ids=batch.get("position_ids"),
                    segment_ids=batch.get("segment_ids"),
                    deterministic=True, loss_reduction="sum",
                    include_aux_loss=False,
                    labels_shifted=self._labels_shifted)
            from hetu_tpu.engine.plan_pool import PlanPool
            # eval over the bucket ladder gets the same plan-pool
            # bookkeeping as training (one compile per shape, loud past
            # the cap) instead of jit's silent retraces; compilation
            # happens at call time inside the loop's mesh context.
            # (HotSwitchTrainer stashes/restores this per strategy —
            # plans compiled for one mesh/model must not serve another.)
            self._eval_fn = PlanPool(
                eval_step,
                max_plans=self._plan_cap(),
                name="eval_step", key_argnums=(1,))
        total, count = 0.0, 0.0
        for i, host_batch in enumerate(batches):
            if max_batches is not None and i >= max_batches:
                break
            # same dp/cp input sharding as training (batches here have no
            # micro dim: [gbs, seq])
            st = self.strategy
            spec = [None, None]
            if st.dp > 1:
                spec[0] = "dp"
            if st.cp > 1:
                spec[1] = "cp"
            sh = NamedSharding(self.mesh, P(*spec))
            host_batch = self._cp_reorder(host_batch)
            batch = {k: jax.device_put(v, sh) for k, v in host_batch.items()}
            with use_mesh(self.mesh), self._declared():
                lsum, csum = self._eval_fn(
                    self.params, batch,
                    strategy_id=self._plan_dispatch_key())
            total += float(lsum)
            count += float(csum)
        loss = total / max(count, 1.0)
        return {"loss": loss, "perplexity": float(np.exp(min(loss, 30.0))),
                "tokens": count}

    # ------------------------------------------------------------------
    def state(self):
        opt_state = self.opt_state
        if isinstance(opt_state, dict) and "ef" in opt_state:
            # the EF residuals ("ef") deliberately do NOT checkpoint: they
            # are a bounded one-step quantization memory, zero is a correct
            # cold start, and their [dp, L] layout would pin resumes to the
            # exact compress mode + dp degree — an elastic re-mesh or a
            # flag change must never brick a restore
            opt_state = {k: v for k, v in opt_state.items() if k != "ef"}
        s = {"params": self.params, "opt_state": opt_state,
             "step": self.global_step}
        if self.scaler_state is not None:
            s["scaler"] = self.scaler_state
        return s

    def save(self, wait: bool = False):
        assert self._ckpt is not None, "no ckpt_dir configured"
        self._ckpt.save(self.global_step, self.state(), wait=wait)

    def restore(self, step: Optional[int] = None):
        """Resume; reshards into the CURRENT strategy's shardings even if the
        checkpoint was written under a different one (reference:
        temp_load_split ht_safetensors.py:1147)."""
        assert self._ckpt is not None, "no ckpt_dir configured"
        if self.params is None:
            self.build()
        target = self.state()   # never carries "ef" — see state()
        fresh_ef = (self.opt_state.get("ef")
                    if isinstance(self.opt_state, dict) else None)
        try:
            restored = self._ckpt.restore(step, target=target)
        except ValueError:
            # scaler presence differs between the checkpoint and the current
            # config (bf16-saved -> fp16 resume or vice versa): retry with
            # the presence toggled; a missing scaler keeps its fresh init
            if "scaler" in target:
                target = {k: v for k, v in target.items() if k != "scaler"}
            else:
                from hetu_tpu.optim.grad_scaler import GradScaler
                target = dict(target, scaler=GradScaler().init())
            restored = self._ckpt.restore(step, target=target)
        self.params = restored["params"]
        self.opt_state = restored["opt_state"]
        if fresh_ef is not None:
            # re-attach build()'s zero EF residuals (cold start; the
            # checkpoint intentionally excludes them — see state())
            self.opt_state["ef"] = fresh_ef
        self.global_step = int(restored["step"])
        if "scaler" in restored and self._scaler is not None:
            self.scaler_state = restored["scaler"]
        return self

    def restore_latest_valid(self):
        """Resume from the newest checkpoint whose manifest verifies,
        walking back past corrupt/torn saves (each skipped step counts
        `ckpt.fallbacks`; checksum-failed steps are quarantined so they
        cannot shadow later re-saves).  The walk is the CheckpointManager's
        (one copy of the fallback logic); each step restores through
        restore() so the scaler-presence retry and EF residual re-attach
        apply.  Raises FileNotFoundError when the directory has no
        checkpoints (fresh start) and CheckpointCorruptError when
        checkpoints exist but none is restorable."""
        assert self._ckpt is not None, "no ckpt_dir configured"

        def note_fallback(step, why):
            if self.run_log is not None:
                self.run_log.log("fault", fault="ckpt_corrupt",
                                 step=step, detail=why)

        _step, me = self._ckpt.restore_latest_valid(
            restore_fn=self.restore, on_fallback=note_fallback)
        return me
