"""Numerics observatory report: per-scope tensor/SNR stats, worst-offender
ranking, detector context — from a run's ``numerics`` RunLog records.

    HETU_TPU_NUMERICS=1 python your_training.py       # leaves the records
    python tools_numerics.py /ckpts/runlog.jsonl
    python tools_numerics.py /ckpts/runlog.jsonl --json
    python tools_numerics.py /ckpts/runlog.jsonl --chrome-trace num.json

Reads through THE one reader (`hetu_tpu.obs.numerics.summarize_numerics`
— the same function behind ``tools_obs_report.py``'s numerics section;
there is no second parser).  The text view is a per-scope table (last
rms/absmax, worst underflow fraction, min SNR, nonfinite total) ranked
most-alarming first, plus the scaler-transition and numerics-anomaly
context lines.  ``--json`` emits the pinned schema below;
``--chrome-trace`` renders the per-scope counter lanes
(`obs.trace.numerics_trace`) for Perfetto.

--json schema (stable; extend with new optional keys only):

    {"numerics_schema": 1,
     "summary": <summarize_numerics output>,
     "scaler": {"events", "growth", "backoff", "last_scale"} | null,
     "anomalies": {<kind>: count} | null}

Pure host-side file munging: no device contact.  Stat definitions and
detector thresholds:
docs/observability.md.
"""
from __future__ import annotations

import argparse
import json
import sys


def scaler_section(records) -> dict | None:
    """Loss-scale dynamics from ``scaler`` RunLog records (None when the
    run never transitioned — bf16 runs have no scaler at all)."""
    evs = [r for r in records if r.get("kind") == "scaler"]
    if not evs:
        return None
    return {"events": len(evs),
            "growth": sum(1 for r in evs if r.get("event") == "growth"),
            "backoff": sum(1 for r in evs if r.get("event") == "backoff"),
            "last_scale": evs[-1].get("scale")}


def numerics_anomalies(records) -> dict | None:
    """Counts of the numerics detector kinds among anomaly records."""
    from hetu_tpu.obs.health import NumericsHealthMonitor
    kinds = set(NumericsHealthMonitor.KINDS)
    out: dict = {}
    for r in records:
        if r.get("kind") == "anomaly" and r.get("anomaly") in kinds:
            k = r["anomaly"]
            out[k] = out.get(k, 0) + 1
    return out or None


def _fmt(v, spec=".3g") -> str:
    return "-" if v is None else format(v, spec)


def render_text(summary: dict, scaler: dict | None,
                anomalies: dict | None) -> str:
    lines = []
    n, span = summary["records"], summary["steps"]
    lines.append(f"numerics records: {n}"
                 + (f"  (steps {span[0]}..{span[1]})" if span else ""))
    if summary["scopes"]:
        lines.append(f"{'scope':>20} {'rms':>9} {'absmax':>9} "
                     f"{'max_uf':>8} {'min_snr':>8} {'nonfin':>7}")
        for scope in summary["worst"]:
            agg = summary["scopes"][scope]
            last = agg["last"]
            lines.append(
                f"{scope:>20} {_fmt(last.get('rms')):>9} "
                f"{_fmt(last.get('absmax')):>9} "
                f"{_fmt(agg['max_underflow_frac']):>8} "
                f"{_fmt(agg['min_snr_db'], '.1f'):>8} "
                f"{agg['nonfinite']:>7}")
        lines.append(f"(ranked worst-first: nonfinite count, then min "
                     f"SNR, then underflow fraction)")
    if scaler:
        lines.append(f"scaler: {scaler['events']} transitions "
                     f"({scaler['growth']} growth / {scaler['backoff']} "
                     f"backoff), last scale {_fmt(scaler['last_scale'])}")
    if anomalies:
        lines.append("numerics anomalies: "
                     + ", ".join(f"{k}={v}"
                                 for k, v in sorted(anomalies.items())))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Per-scope numerics report (tensor stats, "
                    "quantization SNR, worst-offender ranking) over a "
                    "RunLog's numerics records.")
    ap.add_argument("runlog", help="path to a runlog.jsonl written with "
                                   "HETU_TPU_NUMERICS=1")
    ap.add_argument("--json", action="store_true",
                    help="emit the pinned-schema JSON instead of text")
    ap.add_argument("--chrome-trace", metavar="OUT.json", default=None,
                    help="also render the per-scope counter lanes as "
                         "Chrome-trace JSON (Perfetto)")
    args = ap.parse_args(argv)

    from hetu_tpu.obs.numerics import NUMERICS_SCHEMA, summarize_numerics
    from hetu_tpu.obs.runlog import RunLog
    records = RunLog.read(args.runlog)
    summary = summarize_numerics(records)
    if not summary["records"]:
        print(f"no numerics records in {args.runlog} "
              f"(run with HETU_TPU_NUMERICS=1)", file=sys.stderr)
        return 1
    scaler = scaler_section(records)
    anomalies = numerics_anomalies(records)
    if args.json:
        print(json.dumps({"numerics_schema": NUMERICS_SCHEMA,
                          "summary": summary, "scaler": scaler,
                          "anomalies": anomalies}, indent=2))
    else:
        print(render_text(summary, scaler, anomalies))
    if args.chrome_trace:
        from hetu_tpu.obs.trace import numerics_trace
        numerics_trace(records).save(args.chrome_trace)
        print(f"# numerics timeline written to {args.chrome_trace}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
