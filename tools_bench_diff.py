"""Perf-regression sentinel: diff two BENCH records / profiles against
declared budgets.

Nothing watched the BENCH_r*.json trajectory: a change that quietly
regressed predicted step time or peak HBM shipped unless a human diffed
the JSON.  This tool is the watcher — point it at two consecutive
records and it compares every metric both carry (measured/estimated
MFU, step time, bytes-on-wire, peak HBM) against the relative
thresholds of the active perf budget (`obs/budget.py`; defaults +5%
step time, +10% comm bytes, +10% peak HBM, -5% MFU; override with
`--budgets file.json` or `HETU_TPU_BUDGETS`), checks the NEW record
against the budget's absolute ceilings, and **exits nonzero on any
breach**:

    python tools_bench_diff.py BENCH_r04.json BENCH_r05.json
    python tools_bench_diff.py old_runlog.jsonl new_runlog.jsonl
    python tools_bench_diff.py r04.json r05.json --budgets budgets.json
    python tools_bench_diff.py r04.json r05.json --json   # machine report

Inputs may be driver-wrapped BENCH records ({"cmd", "rc", "tail"}), raw
bench metric lines, or RunLog JSONLs (the newest `profile` record wins,
falling back to the newest `compile` record — the per-compile numbers
`HETU_TPU_PROFILE=1` leaves).  Metrics present in only one record are
reported as skipped, never breached — two old-format records with
nothing comparable pass (exit 0) with a warning.

Exit codes: 0 = pass, 1 = budget/regression breach, 2 = unreadable
input.  Host-side file munging only — no device contact.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, Optional


def _load_record(path: str):
    """(record, source_kind) from `path`: a JSON object (BENCH record,
    kind "bench") or a RunLog JSONL — newest `profile` record, else
    newest `compile` record with an estimate.  (None, None) when
    nothing is parseable."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        print(f"# cannot read {path}: {e}", file=sys.stderr)
        return None, None
    try:
        rec = json.loads(text)
        if isinstance(rec, dict):
            # a one-record RunLog parses as whole-file JSON too —
            # classify by SHAPE, not by how many lines the file had
            if rec.get("kind") == "profile" or "profile_schema" in rec:
                return rec, "profile"
            if rec.get("kind") == "compile":
                return rec, "compile"
            return rec, "bench"
    except ValueError:
        pass
    # JSONL (RunLog): scan for the newest profile / compile record
    profile, compile_rec = None, None
    any_record = False
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if not isinstance(rec, dict):
            continue
        any_record = True
        if rec.get("kind") == "profile" or "profile_schema" in rec:
            profile = rec
        elif rec.get("kind") == "compile" and (
                rec.get("estimated_mfu") or rec.get("estimated_step_s")):
            compile_rec = rec
    if profile is not None:
        return profile, "profile"
    if compile_rec is not None:
        return compile_rec, "compile"
    if any_record:
        # a READABLE runlog that just carries nothing comparable (no
        # profile, no compile estimate) takes the skip-never-breach
        # path — an empty metric set passes with a warning, it must
        # not hard-fail the gate as "unreadable"
        return {}, "empty"
    return None, None


def _bench_detail(rec: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The `detail` dict of a (possibly driver-wrapped) BENCH record."""
    from hetu_tpu.obs.budget import _bench_metric_record
    m = _bench_metric_record(rec)
    return (m or {}).get("detail")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Diff two BENCH records / RunLog profiles against "
                    "declared perf budgets; exit nonzero on a breach.")
    ap.add_argument("old", help="baseline record (BENCH_r*.json or a "
                                "runlog.jsonl)")
    ap.add_argument("new", help="candidate record to gate")
    ap.add_argument("--budgets", default=None, metavar="FILE",
                    help="perf-budget JSON (default: HETU_TPU_BUDGETS "
                         "env, else built-in thresholds)")
    ap.add_argument("--json", action="store_true",
                    help="print the full machine-readable report")
    args = ap.parse_args(argv)

    from hetu_tpu.obs.budget import (PerfBudget, check_absolute,
                                     diff_metrics, extract_metrics,
                                     summarize_breaches)
    try:
        budget = PerfBudget.load(args.budgets)
    except (OSError, ValueError) as e:
        print(f"# budget load failed: {e}", file=sys.stderr)
        return 2

    old_rec, old_kind = _load_record(args.old)
    new_rec, new_kind = _load_record(args.new)
    if old_rec is None or new_rec is None:
        print(f"# unreadable record: "
              f"{args.old if old_rec is None else args.new}",
              file=sys.stderr)
        return 2

    old_m = extract_metrics(old_rec)
    new_m = extract_metrics(new_rec)
    if old_kind != new_kind:
        # metrics from DIFFERENT record kinds come from different
        # estimators (a profile's per-group roofline sum vs a compile's
        # whole-program roofline; a bench record's analytic dp=8 comm
        # model and config-twin peak HBM vs a profile's measured wire
        # bytes and liveness peak) — comparing them would flag
        # estimator skew as a regression (or mask a real one); drop
        # every skewed metric rather than fabricate a diff
        skewed = ("step_time_s", "comm_bytes", "peak_hbm_bytes")
        for m in (old_m, new_m):
            for k in skewed:
                m.pop(k, None)
        print(f"# records come from different estimators "
              f"({old_kind} vs {new_kind}); {', '.join(skewed)} "
              f"not compared", file=sys.stderr)

    def _analytic_profile(rec):
        detail = (_bench_detail(rec) or {})
        return bool((detail.get("profile") or {}).get("analytic"))

    def _step_time_kind(rec):
        detail = (_bench_detail(rec) or {})
        if detail.get("step_time_s"):
            return "measured"
        if (detail.get("predicted_step_s")
                or (detail.get("estimate") or {}).get("estimated_step_s")):
            return "analytic"
        return None
    if old_kind == new_kind == "bench":
        # estimator-skew guards for BENCH rounds of mixed provenance:
        # the analytic twins (config-model peak HBM, roofline
        # step time) legitimately differ from their measured
        # counterparts by more than any regression threshold
        if _analytic_profile(old_rec) != _analytic_profile(new_rec):
            for m in (old_m, new_m):
                m.pop("peak_hbm_bytes", None)
            print("# one record's profile is analytic, the other "
                  "measured; peak_hbm_bytes not compared",
                  file=sys.stderr)
        ok, nk = _step_time_kind(old_rec), _step_time_kind(new_rec)
        if ok and nk and ok != nk:
            for m in (old_m, new_m):
                m.pop("step_time_s", None)
            print(f"# step time is {ok} in one record, {nk} in the "
                  f"other; step_time_s not compared", file=sys.stderr)
    report = diff_metrics(old_m, new_m, budget)
    report["absolute_breaches"] = check_absolute(new_m, budget)
    breaches = report["breaches"] + report["absolute_breaches"]
    report.update(old=args.old, new=args.new, budget=budget.source,
                  metrics_old=old_m, metrics_new=new_m,
                  ok=not breaches)

    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        for k, d in sorted(report["deltas"].items()):
            print(f"{k:18s} {d['old']:.6g} -> {d['new']:.6g} "
                  f"({d['rel']:+.2%})")
        for k in report["skipped"]:
            print(f"{k:18s} (present on one side only — skipped)")
    if not report["compared"] and not breaches:
        print("# warning: no comparable metrics between the two records",
              file=sys.stderr)
    if breaches:
        print(summarize_breaches(breaches), file=sys.stderr)
        print(f"FAIL: {len(breaches)} budget breach(es) "
              f"({args.old} -> {args.new})", file=sys.stderr)
        return 1
    print(f"OK: no budget breaches ({args.old} -> {args.new})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
