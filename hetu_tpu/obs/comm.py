"""Bytes-on-wire analyzer: count collectives in a compiled step's HLO.

What a comm optimization puts on the wire can be counted from the
program: this module walks the POST-OPTIMIZATION HLO text of a compiled
train step
(available on any backend, incl. the 8-device CPU test mesh) and reports,
per collective opcode —  all-reduce / reduce-scatter / all-gather /
all-to-all / collective-permute — the op count and the bytes each puts on
the wire per participant under the standard ring algorithms:

    all-reduce          2 (n-1)/n * payload
    all-gather            (n-1)/n * gathered output
    reduce-scatter        (n-1)   * scattered output   (= (n-1)/n * input)
    all-to-all            (n-1)/n * local buffer
    collective-permute              output             (one hop)

(the same formulas comm/wire.py prices analytically — the
cross-validation test pins the two together).  `n` is parsed from each
op's replica_groups.

ALL text parsing lives in `hetu_tpu.obs.hlo_text` — the one tokenizer
shared with the step profiler (obs/hlo_profile.py) and the
graph-contract linter (hetu_tpu/analysis/): line anatomy, payload
resolution (sync vs async "-start" forms), replica_groups (explicit and
iota), and the while-trip machinery below.  This module owns only the
aggregation and the topology-aware pricing.

Scanned layers: a collective inside a `while` body (scan-over-layers,
grad-accumulation) executes TRIP-COUNT times per step, not once.  The
analyzer resolves each while's trip count from its condition computation
(`compare(induction, constant), direction=LT` — the 0-based unit-step
form every lax.scan lowers to) and multiplies the enclosed collectives'
count and bytes through, nested whiles composing multiplicatively.  When
the comparison bound is NOT a literal constant the enclosed rows are
counted once and the report carries `dynamic_trip_count: true` — lower
with `use_scan=False` for exact accounting in that case.

Predicted comm time prices all-reduce-class ops at the profile's
`ici_allreduce_gbps` bus bandwidth and permutes at `ici_p2p_gbps`.  When
the profile carries a `topology` section (comm/topology.py), each
collective's replica group is CLASSIFIED: groups confined to one slice
ride `topology.intra_gbps`, groups spanning slices ride the (slower)
`topology.inter_gbps` — so a flat ring over the whole pod is priced at
the inter rate while a two-level schedule's intra stages keep the fast
rate, and the report splits `predicted_comm_s_intra` / `_inter`.

Consumers: Trainer compile run-events (RunLog `comm_bytes`), bench.py
(`comm_bytes_per_step` even when the backend is unreachable, via the
analytic twin in comm/wire.py), tools_comm_report.py (the per-collective
and per-path tables), and the ZeRO-1 HLO-assertion test (reduce-scatter
+ all-gather tripwire for GSPMD regressions).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from hetu_tpu.comm.wire import analytic_dp_sync  # noqa: F401  (re-export)
from hetu_tpu.obs.hlo_text import (CALLEE_PAT, COLLECTIVE_OPS,  # noqa: F401
                                   OP_NAME_PAT, OUT_PAT, as_hlo_text,
                                   first_group, maybe_collective,
                                   payload_bytes, ring_wire_bytes,
                                   shape_bytes, split_computations,
                                   while_multipliers)


# ---------------------------------------------------------------------------
# the table / report
# ---------------------------------------------------------------------------

def collective_table(compiled_or_text, default_world: int = 1
                     ) -> List[Dict[str, Any]]:
    """One row per collective instruction in the optimized HLO:
    {op, out_bytes, group_size, wire_bytes, trip_count, dynamic_trip,
    group_ranks, line}.  wire_bytes is PER EXECUTION; multiply by
    trip_count for per-step totals (collective_report does).  Accepts a
    compiled executable (as_text()) or the HLO text itself."""
    txt = as_hlo_text(compiled_or_text)
    comps = split_computations(txt)
    mults = while_multipliers(comps)
    rows = []
    for cname, lines in comps.items():
        trip, dynamic = mults.get(cname, (1, False))
        for line in lines:
            found = maybe_collective(line)
            if found is None:
                continue
            base, is_start, m = found
            out_bytes = payload_bytes(m.group("out"), is_start)
            n, ranks = first_group(line, default_world)
            rows.append({
                "op": base,
                "out_bytes": out_bytes,
                "group_size": n,
                "wire_bytes": ring_wire_bytes(base, out_bytes, n, is_start),
                "trip_count": trip,
                "dynamic_trip": dynamic,
                "group_ranks": ranks,
                "line": line.strip()[:200],
            })
    return rows


def grad_sync_report(compiled_or_text, dp_group, default_world: int = 1
                     ) -> Dict[str, float]:
    """The FORM of a compiled train step's data-parallel gradient sync:
    {all_reduce, reduce_scatter: collectives a step, wire_bytes: their
    ring bytes a step a participant}, while trips multiplied through.

    A gradient sync is a reduction (`all-reduce` / `reduce-scatter`) of
    the BACKWARD pass (`transpose(` in its op_name, as
    `hlo_profile.pass_of` reads it) over a replica group that holds
    `dp_group` — the ranks, in the mesh's device order, of the dp group
    of rank 0; a reduction over dp x tp jointly (a norm gain's gradient
    under sequence parallelism) is one too.  The TPU compiler writes a
    reduce-scatter as an `all-reduce` inside a fusion whose output is
    the shard (`all-reduce-scatter`; a device trace shows the FUSION's
    name, not a collective's): such a one counts as `reduce_scatter`, at
    half an all-reduce's bytes, and takes its op_name and its trips from
    the fusion's line.  What says whether ZeRO's split let the sync land
    in its shards (`optim.zero_shardings`): under ZeRO, `all_reduce`
    bytes beyond the norm gains' are bytes sent for nothing."""
    comps = split_computations(as_hlo_text(compiled_or_text))
    mults = while_multipliers(comps)
    callers = {}            # a fused computation -> (where, its fusion)
    for cname, lines in comps.items():
        for line in lines:
            if " fusion(" in line:
                callee = CALLEE_PAT.search(line)
                if callee is not None:
                    callers[callee.group(1)] = (cname, line)
    out = {"all_reduce": 0, "reduce_scatter": 0, "wire_bytes": 0.0}
    for cname, lines in comps.items():
        for line in lines:
            found = maybe_collective(line)
            if found is None or found[0] not in ("all-reduce",
                                                 "reduce-scatter"):
                continue
            base, is_start, m = found
            n, ranks = first_group(line, default_world)
            if ranks is not None and not set(dp_group) <= set(ranks):
                continue
            where, at = callers.get(cname, (cname, line))
            op_name = OP_NAME_PAT.search(at)
            if op_name is None or "transpose(" not in op_name.group(1):
                continue
            payload = payload_bytes(m.group("out"), is_start)
            if (at is not line and base == "all-reduce"
                    and shape_bytes(OUT_PAT.search(at).group(1)) * n
                    == payload):
                base, payload = "reduce-scatter", payload // n
            trips = mults[where][0]
            out[base.replace("-", "_")] += trips
            out["wire_bytes"] += trips * ring_wire_bytes(base, payload, n,
                                                         is_start)
    return out


def _row_rate_class(row, topo) -> str:
    """"intra" | "inter" | "p2p" — which bandwidth prices this row."""
    if row["op"] == "collective-permute":
        return "p2p"
    if topo is None:
        return "intra"
    ranks = row.get("group_ranks")
    if not ranks:
        return "intra"
    return topo.classify_group(ranks)


def collective_report(compiled_or_text, *, hw: Optional[Dict] = None,
                      default_world: int = 1) -> Dict[str, Any]:
    """Aggregate bytes-on-wire report for one compiled step.

    {collectives: {op: {count, wire_bytes}}, num_collectives,
     total_wire_bytes, predicted_comm_s, predicted_comm_s_intra,
     predicted_comm_s_inter, dynamic_trip_count, chip} — counts and bytes
    include while-loop trip multipliers; predicted_comm_s is the serial
    ring-time estimate over the profile's rates (an upper bound: real
    collectives overlap compute), with slice-spanning groups priced at
    the topology's inter-slice rate when the profile declares one."""
    rows = collective_table(compiled_or_text, default_world)
    if hw is None:
        from hetu_tpu.obs.mfu import load_hardware_profile
        hw = load_hardware_profile()
    from hetu_tpu.comm.topology import Topology
    topo = Topology.from_profile(hw)
    ar_bw = float(hw.get("ici_allreduce_gbps", 45.0)) * 1e9
    p2p_bw = float(hw.get("ici_p2p_gbps", 90.0)) * 1e9
    intra_bw = topo.intra_gbps * 1e9 if topo else ar_bw
    inter_bw = topo.inter_gbps * 1e9 if topo else ar_bw
    per_op: Dict[str, Dict[str, float]] = {}
    t_intra = t_inter = t_p2p = 0.0
    b_inter = 0.0
    total = 0.0
    dynamic = False
    for r in rows:
        trip = max(int(r["trip_count"]), 1)
        dynamic = dynamic or r["dynamic_trip"]
        wb = r["wire_bytes"] * trip
        rec = per_op.setdefault(r["op"], {"count": 0, "wire_bytes": 0.0})
        rec["count"] += trip
        rec["wire_bytes"] += wb
        total += wb
        cls = _row_rate_class(r, topo)
        if cls == "p2p":
            t_p2p += wb / p2p_bw
        elif cls == "inter":
            t_inter += wb / inter_bw
            b_inter += wb
        else:
            t_intra += wb / intra_bw
    report: Dict[str, Any] = {
        "collectives": per_op,
        "num_collectives": sum(int(rec["count"])
                               for rec in per_op.values()),
        "total_wire_bytes": total,
        "predicted_comm_s": t_intra + t_inter + t_p2p,
        "predicted_comm_s_intra": t_intra,
        "predicted_comm_s_inter": t_inter,
        # the intra/inter BYTE split (p2p rows count as intra here):
        # a flat slice-spanning collective lands its whole payload in
        # wire_bytes_inter, a two-level schedule only its 1/slice
        # exchange — the measurable half of the HetCCL/HAllToAll claim
        "wire_bytes_intra": total - b_inter,
        "wire_bytes_inter": b_inter,
        "chip": hw.get("chip", "unknown"),
    }
    if dynamic:
        report["dynamic_trip_count"] = True
    return report
