"""Unified telemetry: metrics registry, structured run events, Chrome-trace
timelines, the hardware-free MFU/roofline reporter, the bytes-on-wire
collective analyzer, the per-layer analytic step profiler (+ peak-HBM
and perf budgets), cluster-scope aggregation, and the training health
monitor.

One import surface:

    from hetu_tpu import obs
    obs.get_registry().inc("elastic.replans")
    log = obs.RunLog("/ckpts/runlog.jsonl"); log.step(1, 0.42, loss=2.3)
    obs.pipeline_schedule_trace(4, 8, schedule="1f1b").save("sched.json")
    obs.estimate_from_compiled(compiled)["estimated_mfu"]
    obs.collective_report(compiled)["total_wire_bytes"]
    obs.layer_profile(compiled)["top"]               # per-layer roofline
    obs.peak_hbm_estimate(compiled)["peak_bytes"]    # liveness peak HBM
    obs.diff_metrics(old, new, obs.PerfBudget.load())["breaches"]
    obs.straggler_report(snapshot)["stragglers"]     # cluster scope
    obs.HealthMonitor(runlog=log).observe_step(1, 0.42, loss=2.3)
    obs.summarize_numerics(obs.RunLog.read(path))["worst"]  # numerics

See docs/observability.md for the env flags, the RunLog schema, the
telemetry-push wire format and the ClusterSnapshot fields;
docs/comm_compression.md for the collective analyzer's wire-byte model.
"""
from hetu_tpu.obs.aggregate import (ClusterAggregator,  # noqa: F401
                                    ClusterSnapshot, TelemetryPusher,
                                    TelemetrySource, merge_offsets,
                                    snapshot_straggler_hook,
                                    straggler_report)
from hetu_tpu.obs.budget import (BudgetError, PerfBudget,  # noqa: F401
                                 check_absolute, diff_metrics,
                                 extract_metrics)
from hetu_tpu.obs.comm import (collective_report,  # noqa: F401
                               collective_table)
from hetu_tpu.obs.hlo_profile import (PROFILE_SCHEMA,  # noqa: F401
                                      analytic_peak_hbm, layer_profile,
                                      layer_table, peak_hbm_estimate,
                                      profile_record, scope_map,
                                      scope_sources)
from hetu_tpu.obs.health import (HealthMonitor,  # noqa: F401
                                 NumericsHealthMonitor,
                                 ServingHealthMonitor,
                                 maybe_health_monitor,
                                 maybe_numerics_health_monitor,
                                 maybe_serving_health_monitor)
from hetu_tpu.obs.numerics import (NUMERICS_SCHEMA,  # noqa: F401
                                   summarize_numerics, tree_stats)
from hetu_tpu.obs.metrics import (Histogram, MetricsRegistry,  # noqa: F401
                                  get_registry)
from hetu_tpu.obs.mfu import (analytic_transformer_estimate,  # noqa: F401
                              estimate_from_compiled, estimate_mfu,
                              flops_of_compiled, load_hardware_profile)
from hetu_tpu.obs.runlog import (SCHEMA_VERSION, RunLog,  # noqa: F401
                                 default_runlog_path)
from hetu_tpu.obs.spans import (SPAN_SCHEMA, RequestTrace,  # noqa: F401
                                Span, collect_traces)
from hetu_tpu.obs.trace import (ChromeTrace,  # noqa: F401
                                merge_runlogs, numerics_trace,
                                pipeline_schedule_trace,
                                schedule_bubble_fraction, serving_trace,
                                trace_from_runlog)

__all__ = [
    "MetricsRegistry", "Histogram", "get_registry",
    "RunLog", "SCHEMA_VERSION", "default_runlog_path",
    "ChromeTrace", "pipeline_schedule_trace", "schedule_bubble_fraction",
    "trace_from_runlog", "merge_runlogs", "serving_trace",
    "Span", "RequestTrace", "collect_traces", "SPAN_SCHEMA",
    "estimate_mfu", "estimate_from_compiled", "flops_of_compiled",
    "analytic_transformer_estimate", "load_hardware_profile",
    "collective_report", "collective_table",
    "layer_table", "layer_profile", "peak_hbm_estimate",
    "analytic_peak_hbm", "profile_record", "scope_map",
    "scope_sources",
    "PROFILE_SCHEMA",
    "PerfBudget", "BudgetError", "check_absolute", "diff_metrics",
    "extract_metrics",
    "ClusterAggregator", "ClusterSnapshot", "TelemetrySource",
    "TelemetryPusher", "straggler_report", "snapshot_straggler_hook",
    "merge_offsets",
    "HealthMonitor", "maybe_health_monitor",
    "ServingHealthMonitor", "maybe_serving_health_monitor",
    "NumericsHealthMonitor", "maybe_numerics_health_monitor",
    "NUMERICS_SCHEMA", "summarize_numerics", "tree_stats",
    "numerics_trace",
]
