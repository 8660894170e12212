"""Structured run-event log.

Every training run leaves a machine-readable trace: one JSONL record per
step / compile / switch / elastic epoch, written next to the checkpoints
(reference: the profiler cost records persisted per run — hetu/impl/
profiler/; here the schema is stable and versioned so BENCH tooling and
tools_obs_report.py can read logs across repo revisions).

Record shape (all kinds):

    {"schema": 1, "kind": "step", "t": <unix wall time>, ...kind fields}

Kind fields:
    step          step, step_time_s, loss, tokens_per_s, device_mem_bytes,
                  plan (fingerprint of the dispatched plan)
    compile       name, plan, compile_s, flops, estimated_mfu,
                  kernel_routes (ops/pallas.record_routes: per kernel, how
                  many dispatches took Pallas / XLA while the plan was
                  traced, and why)
    switch        from_id, to_id, wall_s, moved_bytes, total_bytes
    elastic_epoch epoch, alive, strategy
    fault         fault (ckpt_corrupt | step_exception |
                  restore_unrecoverable), generation, detail/error —
                  observed-fault accounting (docs/fault_tolerance.md)
    anomaly       anomaly (obs.health.HealthMonitor.KINDS), step, value,
                  baseline — online health-detector firings
    straggler     stragglers (flagged ranks), workers (per-rank
                  ratio/z) — the cluster straggler report transitions
    serve         event (admit | done | preempt | reshard | report |
                  failover | retry | evict | expired | shed | ship |
                  degraded | replica | hedge | hedge_win | hedge_dupe |
                  dispatch) + the serving SLO fields (hetu_tpu/serving,
                  docs/serving.md); every event also stamps `now`
                  (driver-clock seconds — the engine's virtual clock,
                  matching span t0/t1) and `clock` (the timestamp
                  basis, driver | wall — `FleetTrace.stitch` refuses
                  to mix bases); per-request events (admit/done/
                  preempt/retry/evict/expired/shed) carry `tenant` and,
                  on a sampled RunLog
                  (HETU_TPU_RUNLOG_SERVE_SAMPLE > 1), `sample_weight`
                  (how many requests the sampled record stands for —
                  slo_report re-weights by it):
                  admit: req, slot, prompt_len, chunks, ttft_s,
                  queue_wait_s, slo_class, tenant, shared_tokens (prompt
                  tokens resident via the radix prefix cache — 0 on a
                  miss), queue_depth, page_util;
                  done: req, reason, tokens, ttft_s, e2e_s, tokens_per_s,
                  slo_class, tenant, slo_ttft_s, slo_token_gap_s,
                  spec_proposed/spec_accepted (speculative-decoding
                  draft counts), shared_prefix_tokens, prompt_len,
                  preemptions, queue_depth, slot_occupancy, page_util,
                  + the cost-ledger fields when the run priced requests
                  (serving/costs.py COST_FIELDS: cost_prefill_flops,
                  cost_decode_flops, cost_page_s, cost_kv_byte_s,
                  cost_wire_bytes);
                  preempt: req, slot, by (the preemptor rid), by_class,
                  slo_class (the victim's), tenant, tokens_discarded,
                  queue_depth — one per HETU_TPU_SERVE_PREEMPT
                  evict-and-requeue;
                  reshard: tier, strategy, pause_s (+ kv_repage=true
                  when HETU_TPU_SERVE_KV_REPAGE migrated the pool);
                  report: requests, tokens, elapsed_s, tokens_per_s,
                  kernel_routes (as on a compile record);
                  failover: requeued, exhausted, queue_depth — one per
                  engine fail_over (chaos engine_kill);
                  retry: req, slot, attempt, tokens_discarded — a
                  request requeued under HETU_TPU_SERVE_RETRY
                  (stall reason replica_lost); disaggregated
                  re-prefills stamp ship=true (the shipment was lost/
                  timed out, stall reason shipment_wait);
                  evict/expired/shed: req, reason (retry_exhausted |
                  deadline_exceeded | brownout_shed), tokens, e2e_s,
                  retries, preemptions, queue_depth (+ the cost fields
                  for live casualties) — fault terminations
                  (HETU_TPU_SERVE_RETRY / _DEADLINE / _BROWNOUT);
                  ship: req, seq, attempt, resend, quant — one per KV
                  shipment sent on the prefill->decode wire
                  (HETU_TPU_SERVE_DISAGG, serving/disagg.py);
                  degraded: state (enter | exit), queue_depth on enter,
                  degraded_s on exit — the colocated-fallback window
                  while the prefill tier is down;
                  replica: replica, state (drain | rejoin | down) —
                  frontend replica health transitions
                  (serving/frontend.py);
                  hedge: req, primary, hedge, waited_steps — a hedged
                  re-dispatch fired (HETU_TPU_SERVE_HEDGE);
                  hedge_win: req, primary, hedge, tokens — the hedge
                  copy finished first (the primary's duplicate stream
                  is withdrawn and its tokens discarded);
                  hedge_dupe: req, replica, tokens — a hedge LOSER ran
                  to completion before withdrawal (the stitcher
                  discounts its duplicate terminal);
                  dispatch: req, tier (prefill | decode), replica,
                  attempt, fallback/rerouted_from when applicable — a
                  frontend/coordinator routing decision, the stitched
                  DAG's dispatch edge (obs/spans.py FleetTrace)
    span          the serving flight recorder (HETU_TPU_SERVE_TRACE,
                  hetu_tpu/serving/tracing.py, schema owned by
                  obs/spans.py): span_schema (version), span (queued |
                  prefill | decode | reshard_pause | done | evicted |
                  deadline_exceeded | hedge_withdrawn), trace (trace
                  id), req, slot, slo_class, t0, t1, clock (timestamp
                  basis: driver | wall — every span record stamps it;
                  stitch refuses mixed bases), tier (prefill | decode,
                  only when stamped) and replica (engine index, only
                  when stamped) — the hop identity fleet stitching
                  keys on
                  (driver-clock seconds; spans of one request tile
                  [arrival, done] — durations sum to its e2e_s;
                  requeued attempts stamp attempt >= 2), plus
                  per-kind attrs: queued carries reason
                  (none|no_slot|no_pages|preempted|quota_exceeded|
                  replica_lost|brownout_shed|prefill_tier_down|
                  shipment_wait — the scheduler's
                  reserve-on-admit stall attribution,
                  obs/spans.py STALL_REASONS), prefill carries
                  chunk (+ last on the TTFT chunk), decode carries
                  tokens/segment/end, reshard_pause carries tier, the
                  zero-duration terminals carry reason/tokens/e2e_s
    profile       name, plan, profile_schema, top (top-k layers/op-groups
                  by predicted roofline time), estimated_step_s,
                  total_flops, total_wire_bytes, peak_hbm_bytes,
                  peak_hbm_vs_xla, hbm_headroom_frac — the per-compile
                  analytic step profile (obs.hlo_profile,
                  HETU_TPU_PROFILE=1)
    budget        name, ok, breaches, budget — declared-perf-budget
                  check per fresh compile (obs.budget,
                  HETU_TPU_BUDGETS)
    lint          name, plan, findings, errors, warnings, lints (per-lint
                  counts), messages (first error/warning lines) — the
                  per-compile graph-contract lint record
                  (hetu_tpu/analysis, HETU_TPU_LINT=1,
                  docs/static_analysis.md)
    numerics      numerics_schema (version), step, scopes — the numerics
                  observatory's per-step stats pytree (obs/numerics.py,
                  HETU_TPU_NUMERICS): {scope: {stat: value}} with
                  absmax/rms/l2/nonfinite/underflow_frac/overflow_frac
                  per tensor scope (params, grads, update, adam_m,
                  embed, hidden, logits, ef), snr_db (+ sig_pow/err_pow)
                  per compressed path (grad_sync/a2a, grad_sync/ag,
                  grad_sync/two_level, zero_refresh, sp/<op>, kv_pages)
                  and the moe scope's load/load_max/entropy/dropped/
                  drop_frac; one record per HETU_TPU_NUMERICS_EVERY
                  steps
    scaler        event (growth | backoff), scale, prev, step — one
                  record per dynamic-loss-scale transition (AMP runs;
                  optim/grad_scaler.classify_transition); the per-step
                  value lives in the scaler.loss_scale gauge
    rotated       segment, records — the size-cap rotation marker (the
                  last record of a rotated segment)
    summary       metrics (a MetricsRegistry snapshot), profiler summary

The writer is append-only and flushes per record by default: a preempted
TPU worker's log is valid up to its last completed step.

Long runs can size-cap the log: with ``HETU_TPU_RUNLOG_MAX_MB`` set (or
``max_bytes`` passed), a segment that overflows the cap is closed with a
``rotated`` marker record and renamed to ``<path>.<n>`` (n increasing —
``<path>.1`` is the OLDEST segment), and a fresh segment opens at
``path``.  ``iter_records``/``read`` follow the whole chain in
chronological order, so downstream tooling (tools_obs_report,
trace_from_runlog) never notices the rotation.

An optional in-memory tail buffer (``tail_records``) keeps the last N
records for the cluster telemetry push (obs.aggregate drains it with
``drain_tail()``); it works even after a disk-write failure disabled the
file writer — telemetry keeps flowing when the disk does not.
"""
from __future__ import annotations

import collections
import json
import os
import re
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

SCHEMA_VERSION = 1

#: field names every record carries — the stability contract tested by
#: tests/test_obs.py (extend with new OPTIONAL fields; never rename these)
REQUIRED_FIELDS = ("schema", "kind", "t")


class RunLog:
    """Append-only JSONL run-event writer."""

    def __init__(self, path: str, flush_every: int = 1,
                 max_bytes: Optional[int] = None, tail_records: int = 0):
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(path, "a")
        self._lock = threading.Lock()
        self._flush_every = max(1, flush_every)
        self._since_flush = 0
        self.records_written = 0
        if max_bytes is None:
            from hetu_tpu.utils import flags
            mb = flags.int_flag("HETU_TPU_RUNLOG_MAX_MB")
            max_bytes = mb * (1 << 20) if mb > 0 else None
        self._max_bytes = max_bytes
        try:
            self._bytes = os.path.getsize(path)
        except OSError:
            self._bytes = 0
        self.rotations = 0
        self._tail = (collections.deque(maxlen=tail_records)
                      if tail_records > 0 else None)

    # ------------------------------------------------------------------
    def log(self, kind: str, **fields) -> Dict[str, Any]:
        rec = {"schema": SCHEMA_VERSION, "kind": kind, "t": time.time()}
        rec.update(fields)
        line = json.dumps(rec, default=_jsonable)
        with self._lock:
            if self._tail is not None:
                # the telemetry tail rides even when the file writer is
                # disabled/closed — cluster visibility outlives the disk
                self._tail.append(json.loads(line))
            if self._f.closed:
                return rec   # post-close stragglers (daemon threads) drop
            try:
                self._f.write(line + "\n")
                self._bytes += len(line) + 1
                self._since_flush += 1
                self.records_written += 1
                if self._since_flush >= self._flush_every:
                    self._f.flush()
                    self._since_flush = 0
                if self._max_bytes and self._bytes >= self._max_bytes:
                    self._rotate_locked()
            except OSError as e:
                # telemetry must not kill a step: a full disk / dead mount
                # under the runlog disables the writer (warn once) while
                # the training loop — and its checkpoints, possibly on a
                # different path — carry on
                try:
                    self._f.close()
                except OSError:
                    pass
                from hetu_tpu.utils.logging import get_logger
                get_logger("obs.runlog").warning(
                    f"run log write to {self.path} failed ({e!r}); "
                    "disabling run-event logging for this run")
        return rec

    def step(self, step: int, step_time_s: float, *,
             loss: Optional[float] = None,
             tokens_per_s: Optional[float] = None,
             device_mem_bytes: Optional[int] = None,
             plan: Optional[str] = None, **extra) -> Dict[str, Any]:
        return self.log("step", step=step, step_time_s=step_time_s,
                        loss=loss, tokens_per_s=tokens_per_s,
                        device_mem_bytes=device_mem_bytes, plan=plan,
                        **extra)

    def _rotate_locked(self):
        """Close the overflowing segment (ending it with a `rotated`
        marker so readers can SEE the cut), rename it to the next
        `<path>.<n>`, and start a fresh segment at `path`.  A rename
        failure (exotic filesystems) disables rotation rather than the
        log."""
        idx = _max_segment_index(self.path) + 1
        marker = {"schema": SCHEMA_VERSION, "kind": "rotated",
                  "t": time.time(), "segment": idx,
                  "records": self.records_written}
        try:
            self._f.write(json.dumps(marker) + "\n")
            self._f.flush()
            self._f.close()
            os.replace(self.path, f"{self.path}.{idx}")
            self._f = open(self.path, "a")
            self._bytes = 0
            self._since_flush = 0
            self.rotations += 1
        except OSError as e:
            from hetu_tpu.utils.logging import get_logger
            get_logger("obs.runlog").warning(
                f"run log rotation of {self.path} failed ({e!r}); "
                "disabling rotation for this run")
            self._max_bytes = None
            if self._f.closed:
                # reopen append on whichever file survived the failure
                self._f = open(self.path, "a")

    def drain_tail(self) -> List[Dict[str, Any]]:
        """Return-and-clear the in-memory tail (the telemetry push feed);
        [] when the tail buffer is disabled."""
        with self._lock:
            if not self._tail:
                return []
            out = list(self._tail)
            self._tail.clear()
            return out

    def close(self):
        with self._lock:
            if not self._f.closed:
                self._f.flush()
                self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # ------------------------------------------------------------------
    @staticmethod
    def read(path: str) -> List[Dict[str, Any]]:
        return list(RunLog.iter_records(path))

    @staticmethod
    def segments(path: str) -> List[str]:
        """All on-disk segments of a (possibly rotated) run log, oldest
        first: `<path>.1`, `<path>.2`, ..., then `path` itself."""
        out = [f"{path}.{n}" for n in _segment_indices(path)]
        if os.path.exists(path) or not out:
            out.append(path)
        return out

    @staticmethod
    def iter_records(path: str) -> Iterator[Dict[str, Any]]:
        """Yields records across ALL rotated segments in chronological
        order, skipping torn trailing lines (a preempted writer's final
        partial write must not poison the whole log)."""
        for seg in RunLog.segments(path):
            with open(seg) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(rec, dict) and rec.get("kind"):
                        yield rec


def _segment_indices(path: str) -> List[int]:
    """Sorted rotation indices n for which `<path>.<n>` exists."""
    d, base = os.path.split(path)
    pat = re.compile(re.escape(base) + r"\.(\d+)$")
    out = []
    try:
        for name in os.listdir(d or "."):
            m = pat.match(name)
            if m:
                out.append(int(m.group(1)))
    except OSError:
        pass
    return sorted(out)


def _max_segment_index(path: str) -> int:
    idx = _segment_indices(path)
    return idx[-1] if idx else 0


def _jsonable(obj):
    """Fallback encoder: numpy / jax scalars -> python numbers."""
    for attr in ("item", "tolist"):
        fn = getattr(obj, attr, None)
        if callable(fn):
            try:
                return fn()
            except Exception:
                pass
    return str(obj)


def default_runlog_path(ckpt_dir: Optional[str]) -> Optional[str]:
    """Resolve where a trainer's run log goes: the HETU_TPU_RUNLOG flag
    wins; else next to the checkpoints; else no log."""
    from hetu_tpu.utils import flags
    explicit = flags.str_flag("HETU_TPU_RUNLOG")
    if explicit:
        return explicit
    if ckpt_dir:
        # keep local-path semantics only — remote URIs (gs://) are the
        # checkpointer's business, not a line-buffered JSONL writer's
        if "://" not in ckpt_dir:
            return os.path.join(ckpt_dir, "runlog.jsonl")
    return None
