"""Summarize a RunLog JSONL for BENCH records.

Reads the structured run-event log a training run leaves next to its
checkpoints (hetu_tpu.obs.RunLog, see docs/observability.md) and prints
one JSON summary: step count, median/p95 step time, aggregate tokens/s,
compile stats, hot-switch/elastic counts, and the hardware-free
estimated MFU recorded at compile time — the numbers a BENCH record
wants, without re-running anything.

    python tools_obs_report.py /ckpts/runlog.jsonl
    python tools_obs_report.py runlog.jsonl --trace timeline.json

--trace additionally renders the run as a Chrome-trace timeline
(open at https://ui.perfetto.dev).  Pure host-side file munging: no jax,
no device contact.
"""
from __future__ import annotations

import argparse
import json
import sys


def _percentile(sorted_vals, p):
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(p / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def summarize(records) -> dict:
    """Aggregate RunLog records (any iterable of dicts) into the BENCH
    summary shape.  Tolerates partial logs: a preempted run still reports
    everything up to its last completed step."""
    records = list(records)
    steps = [r for r in records if r.get("kind") == "step"]
    compiles = [r for r in records if r.get("kind") == "compile"]
    switches = [r for r in records if r.get("kind") == "switch"]
    epochs = [r for r in records if r.get("kind") == "elastic_epoch"]
    faults = [r for r in records if r.get("kind") == "fault"]
    anomalies = [r for r in records if r.get("kind") == "anomaly"]
    stragglers = [r for r in records if r.get("kind") == "straggler"]
    serves = [r for r in records if r.get("kind") == "serve"]

    out: dict = {"steps": len(steps), "compiles": len(compiles),
                 "switches": len(switches), "elastic_epochs": len(epochs)}
    if faults:
        by_kind: dict = {}
        for r in faults:
            k = str(r.get("fault", "unknown"))
            by_kind[k] = by_kind.get(k, 0) + 1
        out["faults"] = by_kind

    # health-monitor anomalies (obs.health): counts by kind + the span a
    # BENCH regression hunt needs (when did it start, did it recover)
    if anomalies:
        by_kind = {}
        for r in anomalies:
            k = str(r.get("anomaly", "unknown"))
            by_kind[k] = by_kind.get(k, 0) + 1
        out["anomalies"] = {
            "total": len(anomalies), "by_kind": by_kind,
            "first": {k: anomalies[0].get(k)
                      for k in ("anomaly", "step", "t")},
            "last": {k: anomalies[-1].get(k)
                     for k in ("anomaly", "step", "t")},
        }

    # cluster straggler reports (obs.aggregate): flag-transition events —
    # counts per worker plus the worst observed ratio
    if stragglers:
        by_rank: dict = {}
        top_ratio, top_rank = None, None
        for r in stragglers:
            for rank in r.get("stragglers") or []:
                by_rank[str(rank)] = by_rank.get(str(rank), 0) + 1
            for rank_s, w in (r.get("workers") or {}).items():
                ratio = w.get("ratio")
                if ratio is not None and (top_ratio is None
                                          or ratio > top_ratio):
                    top_ratio, top_rank = ratio, rank_s
        out["stragglers"] = {"events": len(stragglers),
                             "flagged_by_rank": by_rank}
        if top_ratio is not None:
            out["stragglers"]["top_ratio"] = top_ratio
            out["stragglers"]["top_rank"] = top_rank

    # serving runs (hetu_tpu/serving `serve` events + `span` records):
    # per-request SLO percentiles, the per-class attainment/goodput
    # table and stall attribution — all read through the ONE serving
    # RunLog reader (hetu_tpu/serving/slo_report.py; no second parser)
    if serves:
        from hetu_tpu.serving import slo_report as _slo
        collected = _slo.collect(records)
        dones = collected["dones"]
        reshards = collected["reshards"]
        reports = collected["reports"]
        srv: dict = {"events": len(serves), "requests_done": len(dones)}
        ttfts = sorted(float(r["ttft_s"]) for r in dones
                       if r.get("ttft_s") is not None)
        if ttfts:
            srv["ttft_s"] = {"median": _percentile(ttfts, 50),
                             "p95": _percentile(ttfts, 95)}
        e2es = sorted(float(r["e2e_s"]) for r in dones
                      if r.get("e2e_s") is not None)
        if e2es:
            srv["e2e_s"] = {"median": _percentile(e2es, 50),
                            "p95": _percentile(e2es, 95)}
        toks = [int(r["tokens"]) for r in dones if r.get("tokens")]
        if toks:
            srv["tokens_out"] = sum(toks)
        if reports:
            last = reports[-1]
            for k in ("tokens_per_s", "elapsed_s", "requests"):
                if last.get(k) is not None:
                    srv[k] = last[k]
        if reshards:
            srv["reshards"] = len(reshards)
            srv["final_tier"] = reshards[-1].get("tier")
        reasons: dict = {}
        for r in dones:
            k = str(r.get("reason", "unknown"))
            reasons[k] = reasons.get(k, 0) + 1
        if reasons:
            srv["finished_by"] = reasons
        if dones:
            rep = _slo.serving_report(records, collected=collected)
            srv["classes"] = rep["classes"]
            srv["slo_attainment"] = rep["slo_attainment"]
            for k in ("goodput_tokens_per_s", "stall_breakdown",
                      "reconciliation", "critical_path", "spec_decode",
                      "prefix_cache", "preemptions", "tenants", "costs",
                      "failover", "deadline", "brownout",
                      "disagg", "frontend"):
                if rep.get(k) is not None:
                    srv[k] = rep[k]
        out["serving"] = srv

    # numerics observatory (obs/numerics.py, HETU_TPU_NUMERICS=1): the
    # per-scope tensor/SNR summary + scaler dynamics, read through THE
    # one numerics reader shared with tools_numerics.py (no second
    # parser)
    if any(r.get("kind") == "numerics" for r in records):
        from hetu_tpu.obs.numerics import summarize_numerics
        from tools_numerics import numerics_anomalies
        num = summarize_numerics(records)
        num_out: dict = {"records": num["records"], "worst": num["worst"],
                         "scopes": num["scopes"]}
        anom = numerics_anomalies(records)
        if anom:
            num_out["anomalies"] = anom
        out["numerics"] = num_out
    if any(r.get("kind") == "scaler" for r in records):
        from tools_numerics import scaler_section
        out["scaler"] = scaler_section(records)

    # analytic step profiles (obs.hlo_profile, HETU_TPU_PROFILE=1): the
    # newest profile record matches the plan the run actually stepped
    # with — top-k layers by predicted time + peak HBM vs the chip
    profiles = [r for r in records if r.get("kind") == "profile"]
    budgets = [r for r in records if r.get("kind") == "budget"]
    if profiles:
        last = profiles[-1]
        prof: dict = {"records": len(profiles)}
        for k in ("estimated_step_s", "total_flops", "total_wire_bytes",
                  "peak_hbm_bytes", "peak_hbm_vs_xla",
                  "hbm_headroom_frac"):
            if last.get(k) is not None:
                prof[k] = last[k]
        top = last.get("top") or []
        if top:
            prof["top_layers"] = [
                {"group": t.get("group"), "time_s": t.get("time_s"),
                 "bound": t.get("bound")} for t in top[:5]]
        # peak-HBM vs the chip: hbm_headroom_frac was stamped at RECORD
        # time against the profile the run actually used — re-deriving
        # it from the report machine's hardware profile would let two
        # keys for one quantity disagree
        out["profile"] = prof
        # how obs.scope_map placed each program's instructions (a
        # trainer's step by its plan's name, a serving engine's programs
        # by theirs, the newest record of each): what the program said
        # itself ("own") beside what was inferred from a fusion's body,
        # a reader or an operand, and what no rule placed ("none")
        scopes = {}
        for r in profiles:
            if r.get("scope_sources"):
                scopes[r.get("name") or "step"] = {
                    **{k: r[k] for k in ("instructions", "fingerprint")
                       if r.get(k) is not None},
                    "placed_by": r["scope_sources"]}
        if scopes:
            out["scopes"] = scopes
    if budgets:
        fails = [r for r in budgets if not r.get("ok")]
        out["budget"] = {"checks": len(budgets), "failed": len(fails),
                         "ok": not fails}
        if fails:
            last_breaches = fails[-1].get("breaches") or []
            out["budget"]["last_breaches"] = [
                b.get("metric") for b in last_breaches]

    # per-compile graph-contract lints (hetu_tpu/analysis,
    # HETU_TPU_LINT=1): totals across the run + the latest record's
    # per-lint counts and first messages — a run that compiled a plan
    # with an error-severity finding is visible from the summary alone
    lints = [r for r in records if r.get("kind") == "lint"]
    if lints:
        last = lints[-1]
        lint_sec: dict = {
            "records": len(lints),
            "findings": sum(int(r.get("findings") or 0) for r in lints),
            "errors": sum(int(r.get("errors") or 0) for r in lints),
            "warnings": sum(int(r.get("warnings") or 0) for r in lints),
        }
        if last.get("lints"):
            lint_sec["last_by_lint"] = last["lints"]
        if last.get("messages"):
            lint_sec["last_messages"] = last["messages"][:5]
        out["lint"] = lint_sec

    times = sorted(float(r["step_time_s"]) for r in steps
                   if r.get("step_time_s"))
    if times:
        out["step_time_s"] = {
            "median": _percentile(times, 50),
            "p95": _percentile(times, 95),
            "min": times[0], "max": times[-1],
        }
    tps = [float(r["tokens_per_s"]) for r in steps if r.get("tokens_per_s")]
    if tps:
        out["tokens_per_s_median"] = _percentile(sorted(tps), 50)
    losses = [float(r["loss"]) for r in steps if r.get("loss") is not None]
    if losses:
        out["loss_first"], out["loss_last"] = losses[0], losses[-1]
    mems = [int(r["device_mem_bytes"]) for r in steps
            if r.get("device_mem_bytes")]
    if mems:
        out["device_mem_bytes_max"] = max(mems)

    # the hardware-free perf signal: estimated MFU stamped per compile
    # (obs.mfu roofline) — report the latest, which matches the plan the
    # run actually stepped with
    est = [r for r in compiles if r.get("estimated_mfu")]
    if est:
        last = est[-1]
        out["estimated_mfu"] = float(last["estimated_mfu"])
        if last.get("flops"):
            out["flops_per_step"] = float(last["flops"])
    compile_s = sorted(float(r["compile_s"]) for r in compiles
                       if r.get("compile_s"))
    if compile_s:
        out["compile_s_total"] = sum(compile_s)

    plans = {r.get("plan") for r in steps if r.get("plan")}
    if plans:
        out["plans"] = sorted(plans)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Summarize a RunLog JSONL (steps, step-time "
                    "percentiles, tokens/s, estimated MFU) for BENCH.")
    ap.add_argument("runlog", help="path to a runlog.jsonl")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="also render the run as Chrome-trace JSON "
                         "(open in Perfetto / chrome://tracing)")
    ap.add_argument("--kernels", action="store_true",
                    help="attach the analytic Pallas fused-kernel "
                         "traffic section (tools_bench_kernels.py's "
                         "byte model — the bench detail.kernels record)")
    args = ap.parse_args(argv)

    from hetu_tpu.obs.runlog import RunLog
    records = RunLog.read(args.runlog)
    if not records:
        print(f"no records in {args.runlog}", file=sys.stderr)
        return 1
    out = summarize(records)
    if args.kernels:
        from tools_bench_kernels import kernel_section
        out["kernels"] = kernel_section()
    print(json.dumps(out, indent=2))

    if args.trace:
        from hetu_tpu.obs.trace import trace_from_runlog
        trace_from_runlog(records).save(args.trace)
        print(f"# timeline written to {args.trace}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
