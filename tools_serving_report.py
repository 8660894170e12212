"""SLO-class serving report: per-class latency percentiles, SLO
attainment, goodput and stall attribution from a serving RunLog.

    python tools_serving.py --requests 32 --runlog /tmp/serve.jsonl \
        --slo-class gold:0.2:0.05 --slo-class bulk
    python tools_serving_report.py /tmp/serve.jsonl
    python tools_serving_report.py /tmp/serve.jsonl --json
    python tools_serving_report.py /tmp/serve.jsonl --per-request --json
    python tools_serving_report.py /tmp/serve.jsonl --request 17

Reads the ``serve`` events (admit/done/preempt/reshard/report plus the
fault kinds failover/retry/evict/expired/shed) and — when the run
traced with ``HETU_TPU_SERVE_TRACE`` — the ``span`` records, all
through the ONE reader in `hetu_tpu/serving/slo_report.py`
(the same module `tools_obs_report.py`'s serving section uses; there is
no second RunLog parser).  With spans present the report adds stall
attribution (`no_slot` vs `no_pages` vs `preempted` queue time) and the
span-vs-e2e reconciliation check; without them it degrades to the
done-event percentile and attainment tables.  Runs that used the
decoding subsystem gain their sections automatically: speculative
decoding prints the **acceptance-rate** line (drafts accepted /
proposed, from the done events), the radix prefix cache prints the
**cache-hit** line (admissions hit + prefill tokens eliminated, from
the admit events), and preemptive admission prints victim/preemptor
class counts.  Multi-tenant runs (Request.tenant stamped on the serve
events) add the per-tenant attainment/goodput table and — when the
engine priced requests through a `serving/costs.py` CostLedger — the
per-tenant cost roll-up (prefill/decode FLOPs, KV page-seconds,
resident byte-seconds, wire bytes).  Runs that took faults add the
fault sections: **failover** (replica deaths, requeues under the retry
budget, retry exhaustion, requests that finished after a retry),
**deadline** (``deadline_exceeded`` terminations per class, tokens
discarded) and **brownout** (sustained-pressure sheds per class) — the
`tools_chaos.py` serve-failover / serve-brownout recovery reports carry
the same sections.  Disaggregated runs (HETU_TPU_SERVE_DISAGG /
serving/disagg.py) add the **disagg** section (KV shipments + resends
on the prefill->decode wire, re-prefills per class, degraded-mode
colocated-fallback seconds) and frontend-routed runs
(serving/frontend.py) the **frontend** section (replica down/drain/
rejoin events, hedged re-dispatches, hedge wins) — the disagg-storm /
frontend-partition recovery reports carry them too.  Traced runs also
gain the **critical path** lines (stitched FleetTraces decomposed into
exclusive latency segments per class/tenant, obs/critpath.py), and
``--request RID`` drills into ONE request: its stitched hop tree
(prefill/decode/hedge hops, causal edges, per-attempt span timelines)
with the critical path and its dominant segment highlighted
(``--json`` emits the pinned ``request_tree_schema`` shape).  Sampled RunLogs
(HETU_TPU_RUNLOG_SERVE_SAMPLE > 1) are re-weighted by the stamped
``sample_weight`` so totals and attainment stay unbiased.

Pure host-side file munging: no device contact.  See docs/serving.md
(SLO classes) and
docs/observability.md (span schema).
"""
from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Per-class SLO report (attainment, goodput, stall "
                    "attribution, span reconciliation) over a serving "
                    "RunLog.")
    ap.add_argument("runlog", help="path to a runlog.jsonl with serve "
                                   "events (tools_serving.py --runlog)")
    ap.add_argument("--json", action="store_true",
                    help="print the full report as JSON instead of the "
                         "text table")
    ap.add_argument("--per-request", action="store_true",
                    help="include the per-request rows (implies detail "
                         "in --json; appended as a table otherwise)")
    ap.add_argument("--request", type=int, default=None, metavar="RID",
                    help="print ONE request's stitched hop tree "
                         "(fleet hops + causal edges + critical path) "
                         "instead of the aggregate report; needs span "
                         "records (HETU_TPU_SERVE_TRACE)")
    args = ap.parse_args(argv)

    from hetu_tpu.obs.runlog import RunLog
    from hetu_tpu.serving import slo_report

    records = RunLog.read(args.runlog)
    if not any(r.get("kind") in ("serve", "span") for r in records):
        print(f"no serving records in {args.runlog}", file=sys.stderr)
        return 1
    if args.request is not None:
        tree = slo_report.request_tree(slo_report.collect(records),
                                       args.request)
        if tree is None:
            print(f"rid {args.request} has no stitchable spans in "
                  f"{args.runlog} (sampled out, or "
                  f"HETU_TPU_SERVE_TRACE unset?)", file=sys.stderr)
            return 1
        print(json.dumps(tree, indent=2) if args.json
              else slo_report.render_request_tree(tree))
        return 0
    rep = slo_report.serving_report(records, per_request=args.per_request)
    if args.json:
        print(json.dumps(rep, indent=2))
        return 0
    rows = rep.pop("per_request", None)
    print(slo_report.render_text(rep))
    if rows:
        hdr = (f"{'rid':>5} {'tenant':>10} {'class':>10} {'ttft':>8} "
               f"{'e2e':>8} {'toks':>5} {'stall':>9} {'slo':>4}")
        print(hdr)
        print("-" * len(hdr))
        for r in rows:
            print(f"{r['rid']:>5} {str(r.get('tenant') or '-'):>10} "
                  f"{r['slo_class']:>10} "
                  f"{(r['ttft_s'] or 0):>8.4f} {(r['e2e_s'] or 0):>8.4f} "
                  f"{r['tokens']:>5} {str(r.get('stall_reason') or '-'):>9} "
                  f"{'ok' if r['slo_ok'] else 'MISS':>4}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
