"""The runtime env-flag surface — one typed registry for every
`HETU_TPU_*` variable, with defaults and docs.

Rebuild of the reference's env-driven runtime controls (reference:
hetu/graph/executable_graph.cc:1163-1313 GetExecEnvs — HETU_STRAGGLER,
HETU_MEMORY_PROFILE, HETU_PARALLEL_ATTN_SPLIT_PATTERN, event timing...;
SURVEY §5.6 layer 3).  XLA owns op scheduling, so the TPU flag set controls
the layers above it: profiling, kernel routing, CP split mode, switch
accounting, and the multi-process bootstrap.

Usage:
    from hetu_tpu.utils import flags
    if flags.bool_flag("HETU_TPU_EVENT_TIMING"): ...
    mode = flags.str_flag("HETU_TPU_CP_SPLIT")      # validated default
    flags.describe()                                # the full surface
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Dict, Iterator, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Flag:
    name: str
    kind: str            # "bool" | "str" | "int"
    default: object
    doc: str
    choices: Optional[Tuple[str, ...]] = None
    #: the BYTE-IDENTITY contract, declared where the flag lives: setting
    #: the flag to this value must lower the canonical train-step AND
    #: serving-decode programs to exactly the text an unset environment
    #: lowers (for routing flags that is the neutral value — "none",
    #: "flat", "0"; for post-compile analysis flags it is "1": turning the
    #: analysis ON must not perturb the traced program).  None = no such
    #: contract (the flag legitimately changes shapes/routing).  Enforced
    #: systematically by the graph-contract linter's flag-identity sweep
    #: (hetu_tpu/analysis/flag_identity.py, tools_lint.py --flags), which
    #: replaced the per-flag hand-written byte-identity tests.
    identity: Optional[str] = None


REGISTRY: Dict[str, Flag] = {f.name: f for f in [
    # -- profiling / observability (reference: HETU_EVENT_TIMING,
    #    HETU_MEMORY_PROFILE, profiler.h) --------------------------------
    Flag("HETU_TPU_EVENT_TIMING", "bool", False,
         "log per-step wall time from the trainer loop", identity="1"),
    Flag("HETU_TPU_TRACE_DIR", "str", "",
         "capture a jax.profiler trace of a step window into this dir"),
    Flag("HETU_TPU_MEMORY_PROFILE", "bool", False,
         "log per-step device memory stats + compiled-plan memory analysis",
         identity="1"),
    Flag("HETU_TPU_SWITCH_PROFILE", "bool", False,
         "per-hot-switch byte accounting (ProfileRunningDetails analog); "
         "off by default — the tree walk costs host time per switch"),
    Flag("HETU_TPU_LOG_LEVEL", "str", "INFO",
         "root log level for hetu_tpu loggers"),
    Flag("HETU_TPU_RUNLOG", "str", "",
         "write the structured run-event JSONL (obs.RunLog) to this path; "
         "default: <ckpt_dir>/runlog.jsonl when checkpointing, else off"),
    Flag("HETU_TPU_METRICS_EXPORT", "str", "",
         "export the metrics-registry snapshot as JSONL to this path when "
         "the trainer loop ends"),
    Flag("HETU_TPU_TRACE_SCHEDULE", "str", "",
         "write a Chrome-trace render of the pipeline micro-batch schedule "
         "(obs.pipeline_schedule_trace) to this path at build time when "
         "pp > 1; open in Perfetto / chrome://tracing"),
    Flag("HETU_TPU_RUNLOG_MAX_MB", "int", 0,
         "size-cap one RunLog segment to this many MiB; on overflow the "
         "writer appends a 'rotated' marker record, renames the file to "
         "<path>.<n> and starts a fresh segment (iter_records follows the "
         "whole chain in order).  0 (default) = no rotation"),
    Flag("HETU_TPU_TELEMETRY_PUSH", "str", "",
         "cluster telemetry push interval in seconds (e.g. '2.0'): each "
         "worker's control-plane client ships a delta-encoded metrics "
         "snapshot + recent RunLog tail to the coordination server, which "
         "folds them into the time-windowed ClusterSnapshot "
         "(hetu_tpu/obs/aggregate.py, docs/observability.md).  Unset/empty "
         "= off: no telemetry_push op ever hits the wire"),
    Flag("HETU_TPU_HEALTH", "bool", False,
         "run the training health monitor (obs.health.HealthMonitor) in "
         "the trainer loop: EWMA+MAD detectors for loss spikes, NaN/Inf "
         "grads, grad-norm blowups, step-time regressions and data-pipeline "
         "stalls -> health.* counters + 'anomaly' RunLog events.  Costs a "
         "per-step device sync for loss/grad_norm; off (default) = zero "
         "per-step work", identity="1"),
    Flag("HETU_TPU_HW_PROFILE", "str", "",
         "hardware profile JSON for the MFU/roofline reporter (obs.mfu); "
         "default: repo-root hardware_profile_v5e.json; the chosen file "
         "must exist"),
    Flag("HETU_TPU_PROFILE", "bool", False,
         "per-compile analytic step profile (obs.hlo_profile): per-layer "
         "HLO attribution (FLOPs/HBM bytes/wire bytes per named "
         "layer/op-group) + liveness-based peak-HBM estimate -> a "
         "schema-versioned 'profile' RunLog record per fresh compile.  "
         "Pure post-compile HLO-text analysis: the traced program is "
         "byte-identical with the flag on or off", identity="1"),
    Flag("HETU_TPU_PROFILE_TOPK", "int", 8,
         "how many top layers/op-groups (by predicted roofline time) the "
         "'profile' RunLog record and BENCH detail.profile carry"),
    Flag("HETU_TPU_BUDGETS", "str", "",
         "declared perf-budget JSON (obs/budget.py PerfBudget: absolute "
         "ceilings for step time / comm bytes / peak HBM / MFU plus "
         "relative regression thresholds).  The trainer checks each "
         "fresh compile's profile against it (budget RunLog events, "
         "budget.breaches counter; 'enforce': true raises), and "
         "tools_bench_diff.py diffs BENCH rounds with its thresholds"),
    Flag("HETU_TPU_COMM_ANALYZE", "bool", True,
         "per-compile bytes-on-wire analysis (obs.comm) in RunLog compile "
         "events, and with a dp axis the gradient sync's form in the "
         "trainer.grad_sync_* gauges; costs one as_text() of the optimized "
         "HLO per fresh "
         "compile — set 0 on very large programs where stringifying the "
         "module is noticeable next to the compile itself", identity="0"),
    Flag("HETU_TPU_LINT", "bool", False,
         "per-compile graph-contract lints (hetu_tpu/analysis/hlo_lints): "
         "run the donation / replication / dtype-drift / scope-coverage "
         "lints over each fresh compile's optimized HLO -> a 'lint' "
         "RunLog record + lint.* counters (error findings log loudly but "
         "never fail the step — tools_lint.py is the enforcing surface).  "
         "Pure post-compile HLO-text analysis: the traced program is "
         "byte-identical with the flag on or off; see "
         "docs/static_analysis.md", identity="1"),
    Flag("HETU_TPU_NUMERICS", "bool", False,
         "the numerics observatory (obs/numerics.py, "
         "docs/observability.md): compute per-tensor absmax/rms/norm, "
         "nonfinite counts and bf16 underflow/overflow fractions at "
         "named scopes INSIDE the jitted step, exact quantization-error "
         "SNR at every compressed path (DP grad sync, SP collectives, "
         "ZeRO delta-gather, int8 KV pages), EF-residual norms, "
         "loss-scale dynamics and MoE router stats (per-expert load, "
         "entropy, capacity drops) -> an auxiliary stats pytree per "
         "step, recorded as schema-versioned 'numerics' RunLog records "
         "+ numerics.* registry gauges, feeding the numerics health "
         "detectors (HETU_TPU_HEALTH).  Unset (default) = the step "
         "wrapper never runs: the traced program is byte-identical to "
         "the flag not existing (registered identity contract)",
         identity="0"),
    Flag("HETU_TPU_NUMERICS_EVERY", "int", 1,
         "numerics host-fetch sampling interval in steps: record the "
         "stats pytree every N-th step (the in-graph stats are traced "
         "either way — only the device fetch + RunLog/registry write is "
         "sampled).  Raise on hot loops where a per-step scalar fetch "
         "is noticeable"),
    Flag("HETU_TPU_MAX_PLANS", "int", 8,
         "max compiled train-step plans per strategy (one per batch-shape "
         "bucket); a new shape past the cap is a loud error instead of a "
         "silent recompile (HETU_SHAPE_MISMATCH analog); 0 = unbounded"),
    # -- kernel / execution routing (reference: HETU_PARALLEL_ATTN*) -----
    Flag("HETU_TPU_GRAD_COMPRESS", "str", "none",
         "compressed DP grad sync (hetu_tpu/comm/): none = f32 collectives "
         "(byte-identical default), int8 = blockwise-int8 quantized "
         "reduce-scatter/all-gather (+ quantized hetero-DP bridge), "
         "int4 = packed two-per-byte (~7.8x fewer bytes), -ef variants "
         "carry error-feedback residuals in the optimizer state; see "
         "docs/comm_compression.md",
         choices=("none", "int8", "int8-ef", "int4", "int4-ef"),
         identity="none"),
    Flag("HETU_TPU_SP_COMPRESS", "str", "none",
         "quantized SP/TP activation collectives (comm/collectives.py): "
         "the explicit shard_map paths' all-gathers/reduce-scatters/"
         "all-to-alls (dstates.convert, hetero-TP pipeline SP edges) move "
         "blockwise int8/int4 + f32 scales instead of full-width floats; "
         "backward transports quantize too (custom_vjp transpose).  none "
         "(default) is HLO-byte-identical to unset",
         choices=("none", "int8", "int4"), identity="none"),
    Flag("HETU_TPU_ZERO_COMPRESS", "str", "none",
         "quantized ZeRO-1/2 param refresh (optim/zero_refresh.py): the "
         "optimizer update runs on dp-sharded state inside a shard_map "
         "and the param DELTA all-gathers as int8/int4 + scales instead "
         "of GSPMD's f32 param all-gather (~3.9x/7.8x fewer refresh "
         "bytes).  Same homogeneous-DP envelope as GRAD_COMPRESS; none "
         "(default) is HLO-byte-identical to unset",
         choices=("none", "int8", "int4"), identity="none"),
    Flag("HETU_TPU_MOE_DISPATCH", "str", "gspmd",
         "MoE expert-parallel token dispatch (nn/moe_dispatch.py, "
         "docs/moe.md): gspmd (default) keeps the compiler-chosen "
         "collectives — byte-identical to unset; fp32/int8/int4 route the "
         "sort dispatch through an explicit shard_map over the ep axis "
         "(HetuMoE HAllToAll): each ep rank scatters its token share, an "
         "all-to-all (comm/collectives.all_to_all_q — quantized custom-vjp "
         "both directions for int8/int4) delivers expert buffers, and the "
         "combine all-gathers expert outputs.  With "
         "HETU_TPU_COMM_TOPOLOGY=two_level and an applicable topology the "
         "dispatch runs hierarchically (intra-slice a2a at intra rates, "
         "strided inter-slice transversal at inter rates).  No-op at "
         "ep=1; explicit modes require tp=1, pp=1 (loud error otherwise)",
         choices=("gspmd", "fp32", "int8", "int4"), identity="gspmd"),
    Flag("HETU_TPU_COMM_TOPOLOGY", "str", "flat",
         "collective routing over the hardware profile's `topology` "
         "section (comm/topology.py): two_level runs the DP grad sync "
         "hierarchically (intra-slice reduce-scatter -> inter-slice "
         "exchange of the 1/slice shard -> intra-slice all-gather, "
         "HetCCL-style) so inter-slice links move slice_devices-fold "
         "fewer bytes.  flat (default) is HLO-byte-identical to unset",
         choices=("flat", "two_level"), identity="flat"),
    # -- serving (hetu_tpu/serving, docs/serving.md) ---------------------
    Flag("HETU_TPU_KV_QUANT", "str", "none",
         "paged-KV-cache page mode (serving/kv_pool.py): int8 stores "
         "pages as blockwise int8 + one f32 absmax scale per head-vector "
         "(comm/compress primitives; ~3.9x smaller than the fp32 exact "
         "cache at hd=128, ~1.9x vs bf16); int4 packs two values per "
         "byte under the same per-head-vector scale (~7.5x vs fp32 at "
         "hd=128 — decode parity within the documented tolerance, "
         "docs/serving.md).  none (default) stores exact pages in the "
         "model compute dtype — byte-identical semantics to "
         "models/generation.init_cache",
         choices=("none", "int8", "int4"), identity="none"),
    Flag("HETU_TPU_SERVE_SLOTS", "int", 8,
         "serving engine decode-slot count (the static batch dimension "
         "of the continuous-batching decode program)"),
    Flag("HETU_TPU_SERVE_PAGE", "int", 16,
         "KV-cache page size in tokens (serving/kv_pool.py block size)"),
    Flag("HETU_TPU_SERVE_MAX_LEN", "int", 256,
         "per-sequence serving cap (prompt + decode budget); must be a "
         "multiple of HETU_TPU_SERVE_PAGE and <= the model's "
         "max_position_embeddings"),
    Flag("HETU_TPU_SERVE_PREFILL_CHUNK", "int", 32,
         "chunked-prefill token budget per engine step (one chunk per "
         "step, interleaved with decode, so long prompts never stall "
         "the decode batch); SERVE_MAX_LEN must be a multiple of it"),
    Flag("HETU_TPU_SERVE_PAGES", "int", 0,
         "usable KV pages in the pool; 0 (default) = full reservation "
         "(slots * max_len / page), i.e. admission never waits on pages"),
    Flag("HETU_TPU_SERVE_SAMPLE", "bool", False,
         "in-graph serving sampler (serving/sampling.py): the decode "
         "program takes per-slot temperature/top-k/top-p vectors and "
         "seeded PRNG keys derived as fold_in(key(seed), position) — "
         "same seed => same tokens across engine restarts and batch "
         "compositions; greedy rows (temperature 0) stay argmax.  "
         "Unset (default) builds the greedy-only decode program "
         "byte-identical to the flag not existing (registered identity "
         "contract); SamplingParams on a Request then raise loudly",
         identity="0"),
    Flag("HETU_TPU_SPEC_DECODE", "str", "none",
         "speculative decoding (serving/spec_decode.py): ngram drafts "
         "HETU_TPU_SPEC_K tokens per slot per step (prompt-lookup, "
         "host-side, model-free) and ONE batched verify forward "
         "(models/generation.verify_step_paged) scores all k+1 "
         "positions; acceptance is sample-then-match — the exact "
         "rejection rule for a deterministic drafter, so greedy output "
         "is token-identical to sequential generate() and sampled "
         "output matches the non-speculative distribution (and seed).  "
         "model runs a resident-quantized draft model (the engine's "
         "draft_model/draft_params kwargs) with the full stochastic p/q "
         "rejection rule: accept with prob min(1, p/q), residual "
         "resample on rejection — the output distribution is exactly "
         "the target's for ANY drafter.  none (default) builds the "
         "single-token decode program byte-identical to unset",
         choices=("none", "ngram", "model"), identity="none"),
    Flag("HETU_TPU_SPEC_K", "int", 4,
         "draft tokens per speculative decode step (the verify "
         "program's static width is k+1); also widens every page "
         "reservation by k positions (reserve-on-admit must cover the "
         "draft writes).  Read only when HETU_TPU_SPEC_DECODE is set — "
         "the registered identity contract pins that setting it alone "
         "leaves the decode program byte-identical",
         identity="4"),
    Flag("HETU_TPU_SERVE_PREFIX_CACHE", "bool", False,
         "radix prefix cache (serving/prefix_cache.py): finished "
         "prompts' page-aligned KV pages stay resident in a radix tree "
         "keyed by token blocks, with copy-on-write refcounts in the "
         "page pool — a request sharing the prefix admits with those "
         "pages already in its page table and prefill runs only the "
         "unshared suffix (>= 90% of prefill FLOPs eliminated for a "
         "fully-shared system prompt, bench.py detail.serving).  "
         "Host-side bookkeeping only: the decode program is "
         "byte-identical either way (registered identity contract)",
         identity="0"),
    Flag("HETU_TPU_SERVE_PREFIX_PAGES", "int", 0,
         "radix-cache page budget (0 = bounded only by pool pressure: "
         "the scheduler evicts LRU cache entries on demand when an "
         "admission's reservation comes up short, so cached pages are "
         "best-effort slack and can never deadlock admission)",
         identity="0"),
    Flag("HETU_TPU_SERVE_PREEMPT", "bool", False,
         "SLO-class-aware preemptive admission: when the queue head's "
         "class priority strictly outranks the lowest-priority live "
         "slot and admission stalls (no_slot/no_pages), that slot is "
         "evicted-and-requeued (pages released, 'preempted' stall "
         "reason span, serve 'preempt' event) and the head admits.  "
         "Equal priorities never preempt (no thrash).  Host-side "
         "policy only — decode program byte-identical (registered "
         "identity contract)",
         identity="0"),
    Flag("HETU_TPU_SERVE_QUOTAS", "str", "",
         "per-tenant admission quotas (serving/request.py parse_quotas): "
         "comma list of tenant[:max_slots[:max_pages]] specs, e.g. "
         "'acme:2:16,free:1:4' — the scheduler caps how many decode "
         "slots / KV pages each tenant's LIVE requests may hold, "
         "stalling the queue head with the 'quota_exceeded' reason when "
         "its tenant is over (docs/serving.md).  Unset/empty (default) "
         "= quota-free: the admission path is byte-identical to the "
         "flag not existing (registered identity contract; host-side "
         "policy only — the decode program never sees tenants)",
         identity=""),
    Flag("HETU_TPU_RUNLOG_SERVE_SAMPLE", "int", 1,
         "serve-event/span RunLog sampling: only a deterministic hashed "
         "1-in-N of request ids (serving/request.py rid_sampled — "
         "decorrelated from round-robin tenant/class assignment) emit "
         "their 'serve'/'span' records, stamped with "
         "sample_weight=N so serving/slo_report.py re-weights rates and "
         "goodput unbiasedly (exact registry counters are never "
         "sampled).  1 (default) logs every request — the RunLog is "
         "byte-identical to the flag not existing (registered identity "
         "contract); raise to ~1000 for 10^6-request fleet runs",
         identity="1"),
    Flag("HETU_TPU_SERVE_TRACE", "bool", False,
         "serving flight recorder (serving/tracing.py): record every "
         "request's lifecycle as schema-versioned 'span' RunLog records "
         "— queued (with the scheduler's no_slot/no_pages stall "
         "attribution), one span per prefill chunk, decode segments "
         "split at evictions/reshard pauses, terminal "
         "done/evicted/hedge_withdrawn — each span stamped with its "
         "clock basis (driver|wall) and, on fleet tiers, tier/replica "
         "trace context, so obs/spans.py FleetTrace.stitch can assemble "
         "the per-engine hops plus frontend dispatch/hedge/ship events "
         "into one causal per-request DAG and obs/critpath.py can "
         "decompose TTFT/e2e with zero residual.  Pure host-side "
         "bookkeeping: the compiled prefill/decode programs are "
         "byte-identical with the flag on or off (registered identity "
         "contract, decode program — reads are serving-confined)",
         identity="1"),
    Flag("HETU_TPU_SERVE_RETRY", "int", 0,
         "per-request retry budget after a serving replica death (chaos "
         "engine_kill): in-flight requests re-enter the queue with the "
         "'replica_lost' stall reason and a bumped attempt index, up to "
         "this many times; past the budget they terminate as "
         "'retry_exhausted'.  Seeded sampling replays each survivor to "
         "the exact token stream of the undisturbed run "
         "(docs/fault_tolerance.md).  0 (default) = no retries: a "
         "killed replica's in-flight requests terminate.  Host-side "
         "failover policy only — the decode program is byte-identical "
         "at any value (registered identity contract)",
         identity="3"),
    Flag("HETU_TPU_SERVE_DEADLINE", "bool", False,
         "enforce SLOClass deadlines (serving/request.py deadline_s, "
         "the 5th --slo-class field): each engine step sweeps queued "
         "AND live requests, terminating any older than its class "
         "deadline as 'deadline_exceeded' — a real terminal span, "
         "costed in the ledger and reported by slo_report.  Unset "
         "(default) = deadlines never inspected.  Host-side policy "
         "only — decode program byte-identical (registered identity "
         "contract)",
         identity="1"),
    Flag("HETU_TPU_SERVE_BROWNOUT", "bool", False,
         "sustained-pressure brownout shedding: when KV page "
         "utilization sits at the high watermark with a backed-up "
         "queue for a streak of steps (the page_exhaustion_imminent "
         "detector's signals), the engine sheds the lowest-priority "
         "queued requests ('brownout_shed' stall reason, 'evicted' "
         "terminal span), lowest-priority tenants first, and meters "
         "the shed through the HETU_TPU_HEALTH serving detectors.  "
         "Unset (default) = never shed.  Host-side policy only — "
         "decode program byte-identical (registered identity "
         "contract)",
         identity="1"),
    Flag("HETU_TPU_SERVE_KV_REPAGE", "bool", False,
         "migrate the paged KV pool through a LoadAdaptiveMesh tier "
         "change (serving/reshard.py reshard_pool): the pool arrays "
         "(fp or int8 payload+scales) are device_put onto the "
         "destination tier's mesh alongside the params, so in-flight "
         "requests survive a scale-up/down token-identically; page "
         "tables are host-resident and re-uploaded each step, so they "
         "migrate for free.  Unset (default) keeps the pre-existing "
         "params-only reshard (the pool stays on its original "
         "placement).  Pure data movement between steps — the decode "
         "program is byte-identical (registered identity contract)",
         identity="1"),
    Flag("HETU_TPU_SERVE_DISAGG", "bool", False,
         "disaggregated prefill/decode serving (serving/disagg.py): "
         "prompts prefill on a separate tier running the SAME chunk "
         "program, and the finished scratch KV ships to the decode "
         "tier over an acked at-least-once channel (seq-numbered "
         "shipments, receiver-side dedupe before any page allocation, "
         "timeout -> resend -> re-prefill under HETU_TPU_SERVE_RETRY). "
         "A dead prefill tier degrades to colocated chunked prefill "
         "('prefill_tier_down' stall reason, metered degraded-mode "
         "seconds), auto-recovering.  Host-side orchestration only: "
         "chunk, write, and decode programs are the engine's own, so "
         "the decode program is byte-identical with the flag on or "
         "off (registered identity contract) and exact-wire streams "
         "are token-identical to the colocated run",
         identity="1"),
    Flag("HETU_TPU_SERVE_SHIP_QUANT", "str", "none",
         "wire quantization for prefill->decode KV shipments "
         "(serving/disagg.py pack_shipment): int8/int4 ship blockwise "
         "payloads + f32 scale planes through the same "
         "quantize_heads format the KV pool and re-paging use (~4x / "
         "~7.5x fewer wire bytes vs fp32); none (default) ships the "
         "exact scratch — the mode that preserves token byte-identity "
         "to the colocated run.  A host-side wire transform: the "
         "decode program is byte-identical at any value (registered "
         "identity contract)",
         choices=("none", "int8", "int4"),
         identity="int8"),
    Flag("HETU_TPU_SERVE_HEDGE", "int", 0,
         "frontend hedged re-dispatch (serving/frontend.py): a request "
         "queued on its replica for more than this many router steps "
         "is speculatively re-submitted to the next-best healthy "
         "replica; the first replica to finish wins ('hedge_win' "
         "serve event) and the loser's copy is withdrawn, deduped by "
         "rid — duplicate results never reach the client, and loser "
         "tokens are accounted as discarded work.  0 (default) = "
         "never hedge.  Host-side routing policy only — the decode "
         "program is byte-identical at any value (registered "
         "identity contract)",
         identity="2"),
    Flag("HETU_TPU_PALLAS", "str", "auto",
         "Pallas fused-kernel layer routing (ops/pallas: flash attention, "
         "residual+RMS/LayerNorm, SwiGLU, rotary, blockwise quantize, "
         "paged-attention decode, multi-query verify, fused sampling "
         "epilogue — docs/kernels.md): auto (shape-gated, "
         "TPU only), 1 (force the kernels; unsupported shapes raise), "
         "0 (force the XLA compositions — byte-identical to the seed "
         "lowering, tested)",
         choices=("auto", "1", "0"), identity="0"),
    Flag("HETU_TPU_PALLAS_KERNELS", "str", "",
         "restrict WHICH Pallas kernels participate in HETU_TPU_PALLAS "
         "routing: comma list over {flash, norm, swiglu, rotary, quant, "
         "paged_attn, paged_verify, sample, paged_latent, "
         "chunk_attn}, or 'all' (default: "
         "empty = all) / 'none' — lets one kernel be bisected out "
         "without losing the rest",
         identity="all"),
    Flag("HETU_TPU_CP_SPLIT", "str", "sym",
         "default context-parallel split pattern "
         "(reference: HETU_PARALLEL_ATTN_SPLIT_PATTERN SYM/STRIPE/NORMAL)",
         choices=("sym", "stripe", "normal")),
    # -- robustness / chaos (hetu_tpu/chaos, docs/fault_tolerance.md) ----
    Flag("HETU_TPU_CHAOS", "str", "",
         "path to a deterministic fault-injection schedule JSON "
         "(hetu_tpu.chaos.FaultPlan: seeded rpc drop/delay/dup, heartbeat "
         "stalls, worker kills, checkpoint corruption).  Unset = chaos "
         "off: the rpc wire layer is identity and nothing else changes"),
    # -- multi-process bootstrap (core/distributed.py) -------------------
    Flag("HETU_TPU_COORDINATOR", "str", "",
         "jax.distributed coordinator address host:port"),
    Flag("HETU_TPU_NUM_PROCESSES", "int", 0,
         "world size for multi-process init (0 = single process)"),
    Flag("HETU_TPU_PROCESS_ID", "int", 0,
         "this process's rank for multi-process init"),
    Flag("HETU_TPU_CONTROL", "str", "",
         "coordination-server address host:port (KV/barrier/elastic)"),
    # -- launcher-injected worker env (rpc/launcher.py sets these in each
    #    spawned worker; workers read them back for slot identity) --------
    Flag("HETU_TPU_COORD", "str", "",
         "coordination-server host:port handed to launcher-spawned workers"),
    Flag("HETU_TPU_WORKER_ID", "int", 0,
         "stable launcher slot id (0..n-1); a relaunched worker keeps it"),
    Flag("HETU_TPU_NUM_WORKERS", "int", 0,
         "launcher world size handed to spawned workers"),
]}


#: the names the accessors were asked for while `recorded_reads` is
#: open (None: nobody is recording).  Every read of a flag goes through
#: an accessor (the env-bypass lint, analysis/ast_lints.py) and the
#: accessors read the environment at every call, so a build that never
#: asked for a flag cannot depend on it: what the identity sweep
#: (analysis/flag_identity.py) skips.
_reads: Optional[set] = None


@contextlib.contextmanager
def recorded_reads() -> Iterator[set]:
    global _reads
    outer, _reads = _reads, set()
    try:
        yield _reads
    finally:
        _reads = outer


def _lookup(name: str) -> Flag:
    if _reads is not None:
        _reads.add(name)
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown flag {name!r}; known: {sorted(REGISTRY)}")


_TRUE = ("1", "true", "True", "TRUE", "yes", "on")
_FALSE = ("0", "false", "False", "FALSE", "no", "off", "")


def bool_flag(name: str) -> bool:
    f = _lookup(name)
    raw = os.environ.get(name)
    if raw is None:
        return bool(f.default)
    if raw in _TRUE:
        return True
    if raw in _FALSE:
        return False
    raise ValueError(
        f"{name}={raw!r} is not a boolean; use one of {_TRUE + _FALSE}")


def str_flag(name: str) -> str:
    f = _lookup(name)
    val = os.environ.get(name, f.default)
    if f.choices and val not in f.choices:
        raise ValueError(
            f"{name}={val!r} invalid; choices: {f.choices}")
    return val


def int_flag(name: str) -> int:
    f = _lookup(name)
    raw = os.environ.get(name)
    return int(raw) if raw else int(f.default)


def identity_flags() -> Dict[str, str]:
    """{flag name: identity value} for every registered flag carrying a
    byte-identity contract — THE declarative contract table the
    flag-identity sweep (hetu_tpu/analysis/flag_identity.py) enforces
    against the canonical train-step and serving-decode programs.
    Registering a flag with `identity=` here is all it takes to put it
    under systematic enforcement; there are no per-flag tests to write."""
    return {f.name: f.identity for f in REGISTRY.values()
            if f.identity is not None}


def describe() -> str:
    """Human-readable flag table (the GetExecEnvs surface, documented)."""
    lines = []
    for f in REGISTRY.values():
        cur = os.environ.get(f.name)
        cur_s = f" [set: {cur}]" if cur is not None else ""
        lines.append(f"{f.name} ({f.kind}, default {f.default!r}){cur_s}\n"
                     f"    {f.doc}")
    return "\n".join(lines)


def active() -> Dict[str, str]:
    """The HETU_TPU_* vars actually set in this environment
    (reference: GetExecEnvs logging)."""
    return {k: v for k, v in os.environ.items() if k.startswith("HETU_TPU_")}
