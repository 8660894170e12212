"""HLO lints: distributed-correctness invariants checked statically over
one compiled program's post-optimization text.

One walk surface (every parsing primitive comes from
`hetu_tpu.obs.hlo_text` — the tokenizer shared with obs/comm.py and
obs/hlo_profile.py, so a parse fix lands once):

* **donation** (error) — an entry parameter that DIES (its value is not
  part of the program's root output) while an equally-sized output
  buffer exists that aliases nothing: XLA could have written the output
  over the dying input (`input_output_alias`) and instead allocates
  both — avoidable peak HBM, the exact miss `obs/hlo_profile.
  peak_hbm_estimate` models when `donated` args reuse storage.  Sized
  buffers only (`min_bytes`): donating a scalar is noise.

* **replica-groups** (error) — the same collective opcode appears in
  sibling conditional branches with DIFFERENT `replica_groups`: if the
  branch predicate ever diverges across participants (and nothing in
  HLO forbids that), the mismatched groups deadlock the ring.  Sibling
  branches must agree on their collective signature.

* **replication** (warning) — a parameter-sized all-gather: some rank's
  full copy of a parameter-shaped buffer is re-materialized over the
  wire each step (a ZeRO refresh is the legitimate form — the lint
  surfaces it so the wire cost is a decision, not an accident).

* **dtype-drift** (warning) — `dot` instructions computing in f32
  inside model scopes (`layer_*` / embed / lm_head) of a program the
  caller declares bf16: a silent upcast doubles MXU time and HBM
  traffic.  Optimizer / grad-sync scopes are exempt (fp32 master math
  is intended there).

* **scope-coverage** (warning below the floor, info always) — the
  fraction of parsed dot FLOPs attributed to named scope groups
  (`group_of` != "other").  The analytic profiler is blind to
  unattributed FLOPs; this lint keeps the blind spot from growing
  silently.

* **moe-dispatch** (warning) — an all-to-all over one FLAT replica
  group that spans topology slices (size > slice_devices, divisible
  into slices): every hop is paced by the slow inter-slice links while
  the two-level schedule (HETU_TPU_COMM_TOPOLOGY=two_level — the MoE
  dispatch's HAllToAll and the DP grad sync both route through it) was
  available.  Vacuous without a profile topology.

`lint_hlo` runs them all; each lint is also callable alone (the fixture
tests pin one positive and one negative program per lint).
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from hetu_tpu.analysis.findings import ERROR, INFO, WARNING, Finding
from hetu_tpu.obs.hlo_text import (BRANCH_PAT, GROUPS_ATTR_PAT, LINE_PAT,
                                   OP_NAME_PAT, REF_PAT,
                                   alias_attribute_body, as_hlo_text,
                                   call_multipliers, definitions,
                                   donated_parameters, dot_flops,
                                   entry_computation,
                                   entry_parameters, first_group,
                                   maybe_collective, payload_bytes,
                                   split_computations)

#: "donating a scalar is noise" — buffers below this size are outside
#: the donation/replication accounting by default (64 KiB)
MIN_BYTES = 1 << 16


def dtype_token(compute_dtype) -> Optional[str]:
    """A model's declared compute dtype as the dtype-drift lint's HLO
    token ("bf16"/"f16"; None for full precision / unknown) — THE one
    mapping, shared by the HETU_TPU_LINT trainer hook and
    tools_lint --hlo (via analysis.programs.canonical_compute_dtype) so
    the two enforcement surfaces can never derive differently."""
    import jax.numpy as jnp
    return {jnp.bfloat16: "bf16", jnp.float16: "f16"}.get(compute_dtype)

_COND_CALLEES = re.compile(r'(?:true|false)_computation=%?([\w.\-]+)')
_ALIASED_OUT_PAT = re.compile(r'\{([\d,\s]*)\}\s*:')


def _root_components(lines: Sequence[str]) -> Tuple[List[int], str]:
    """(byte size of each root-output component, the root line)."""
    for ln in lines:
        if ln.lstrip().startswith("ROOT "):
            m = LINE_PAT.search(ln)
            if m is None:
                return [], ln
            from hetu_tpu.obs.hlo_text import component_bytes
            return component_bytes(m.group("out")), ln
    return [], ""


def _aliased_output_indices(txt: str) -> frozenset:
    """Leading output-component indices named on the LEFT side of
    input_output_alias entries (`{1}: (2, {})` -> 1; `{}: (0, {})` ->
    -1, the whole-output alias).  Reads the attribute through the same
    brace-balanced extractor `donated_parameters` uses, so both sides
    of the alias parse identically on TPU same-line headers."""
    body = alias_attribute_body(txt)
    if body is None:
        return frozenset()
    out = set()
    for idx in _ALIASED_OUT_PAT.findall(body):
        first = idx.split(",")[0].strip()
        out.add(int(first) if first else -1)
    return frozenset(out)


def lint_donation(compiled_or_text, *, min_bytes: int = MIN_BYTES,
                  program: str = "hlo") -> List[Finding]:
    """Dying, donatable, not donated ⇒ avoidable peak HBM."""
    txt = as_hlo_text(compiled_or_text)
    comps = split_computations(txt)
    entry = entry_computation(txt, comps)
    lines = comps.get(entry, [])
    _has_alias, donated = donated_parameters(txt)
    params = entry_parameters(lines)
    root_comps, root_line = _root_components(lines)
    aliased_out = _aliased_output_indices(txt)
    # output components free to take over a dying input's storage
    free_out = [b for i, b in enumerate(root_comps)
                if i not in aliased_out and -1 not in aliased_out
                and b >= min_bytes]
    findings: List[Finding] = []
    for p in params:
        if p["number"] in donated or p["bytes"] < min_bytes:
            continue
        name = str(p["name"])
        # live-out parameters (threaded through to the root) cannot be
        # donated away — only buffers that DIE inside the program count
        if re.search(r'%' + re.escape(name) + r'\b', root_line):
            continue
        take = next((b for b in free_out if b == p["bytes"]), None)
        if take is None:
            continue
        # each free output can absorb exactly ONE dying input — without
        # consuming it, one undonated output would yield an unfixable
        # second error per additional equal-sized dying parameter
        free_out.remove(take)
        findings.append(Finding(
            "donation", ERROR, f"{program}:{entry}",
            f"entry parameter %{name} ({p['bytes']} bytes, "
            f"parameter({p['number']})) dies but is not donated while an "
            f"equal-sized undonated output exists — input_output_alias "
            f"would save {p['bytes']} bytes of peak HBM",
            {"parameter": p["number"], "name": name,
             "bytes": int(p["bytes"])}))
    return findings


def _descendants(comps: Dict[str, List[str]], root: str) -> List[str]:
    """root + every computation reachable from it through call edges."""
    children: Dict[str, List[str]] = {name: [] for name in comps}
    callee_pat = re.compile(
        r'(?:calls|body|condition|to_apply|'
        r'(?:true|false)_computation)=%?([\w.\-]+)')
    for cname, lines in comps.items():
        for ln in lines:
            for m in callee_pat.finditer(ln):
                if m.group(1) in comps:
                    children[cname].append(m.group(1))
            bm = BRANCH_PAT.search(ln)
            if bm:
                for callee in REF_PAT.findall(bm.group(1)):
                    if callee in comps:
                        children[cname].append(callee)
    seen: List[str] = []
    stack = [root]
    while stack:
        cur = stack.pop()
        if cur in seen:
            continue
        seen.append(cur)
        stack.extend(children.get(cur, []))
    return seen


def _collective_signature(comps: Dict[str, List[str]], branch: str
                          ) -> List[Tuple[str, str]]:
    """Sorted (opcode, replica_groups text) of every collective reachable
    from `branch` — what sibling conditional branches must agree on."""
    sig = []
    for cname in _descendants(comps, branch):
        for ln in comps.get(cname, []):
            found = maybe_collective(ln)
            if found is None:
                continue
            gm = GROUPS_ATTR_PAT.search(ln)
            sig.append((found[0], gm.group(1) if gm else ""))
    return sorted(sig)


def lint_replica_groups(compiled_or_text, *, program: str = "hlo"
                        ) -> List[Finding]:
    """Sibling conditional branches whose collectives disagree on
    replica_groups — a deadlock hazard under divergent predicates."""
    txt = as_hlo_text(compiled_or_text)
    comps = split_computations(txt)
    findings: List[Finding] = []
    for cname, lines in comps.items():
        for ln in lines:
            if " conditional(" not in ln:
                continue
            branches = _COND_CALLEES.findall(ln)
            bm = BRANCH_PAT.search(ln)
            if bm:
                branches += [b for b in REF_PAT.findall(bm.group(1))
                             if b in comps]
            branches = [b for b in dict.fromkeys(branches) if b in comps]
            if len(branches) < 2:
                continue
            sigs = {b: _collective_signature(comps, b) for b in branches}
            base = sigs[branches[0]]
            diverged = [b for b in branches[1:] if sigs[b] != base]
            if not diverged:
                continue
            findings.append(Finding(
                "replica-groups", ERROR, f"{program}:{cname}",
                f"conditional branches {branches[0]} vs "
                f"{', '.join(diverged)} disagree on collective "
                f"replica_groups — divergent predicates would deadlock "
                f"the ring",
                {"branches": {b: [list(t) for t in sigs[b]]
                              for b in branches}}))
    return findings


def lint_replication(compiled_or_text, *, min_bytes: int = MIN_BYTES,
                     program: str = "hlo") -> List[Finding]:
    """Parameter-sized all-gathers: full parameter copies re-materialized
    on the wire (intended under a ZeRO refresh — surfaced so it is a
    decision, not an accident)."""
    txt = as_hlo_text(compiled_or_text)
    comps = split_computations(txt)
    entry = entry_computation(txt, comps)
    param_bytes = {int(p["bytes"]) for p in
                   entry_parameters(comps.get(entry, []))
                   if int(p["bytes"]) >= min_bytes}
    findings: List[Finding] = []
    for cname, lines in comps.items():
        for ln in lines:
            found = maybe_collective(ln)
            if found is None or found[0] != "all-gather":
                continue
            out_b = payload_bytes(found[2].group("out"), found[1])
            if out_b in param_bytes:
                findings.append(Finding(
                    "replication", WARNING, f"{program}:{cname}",
                    f"parameter-sized all-gather ({out_b} bytes) "
                    f"re-materializes a full parameter copy on the wire "
                    f"each execution — intended for a ZeRO refresh, "
                    f"otherwise a replicated-layout leak",
                    {"bytes": int(out_b)}))
    return findings


#: scopes where f32 dots are INTENDED even in a bf16 program
_F32_OK_HEADS = ("optimizer", "grad_sync", "other")


def lint_dtype_drift(compiled_or_text, expected_dtype: Optional[str],
                     *, program: str = "hlo") -> List[Finding]:
    """f32/f64 dots inside model scopes of a program declared bf16/f16."""
    if expected_dtype not in ("bf16", "f16"):
        return []
    from hetu_tpu.obs.hlo_text import SHAPE_PAT
    from hetu_tpu.obs.hlo_profile import group_of
    txt = as_hlo_text(compiled_or_text)
    offenders: Dict[str, Dict[str, object]] = {}
    for ln in txt.splitlines():
        if " dot(" not in ln:
            continue
        m = LINE_PAT.search(ln)
        om = OP_NAME_PAT.search(ln)
        if m is None or om is None:
            continue
        group = group_of(om.group(1))
        if group.split("/")[0] in _F32_OK_HEADS:
            continue
        dts = [dt for dt, _dims in SHAPE_PAT.findall(m.group("out"))]
        if not dts or dts[0] not in ("f32", "f64"):
            continue
        rec = offenders.setdefault(group, {"count": 0, "example": ""})
        rec["count"] = int(rec["count"]) + 1
        rec["example"] = rec["example"] or ln.strip()[:160]
    return [Finding(
        "dtype-drift", WARNING, f"{program}:{group}",
        f"{rec['count']} f32-upcast dot(s) inside a "
        f"{expected_dtype}-declared program (e.g. {rec['example']!r}) — "
        f"silent f32 math doubles MXU time and HBM traffic",
        {"count": rec["count"]})
        for group, rec in sorted(offenders.items())]


def lint_scope_coverage(compiled_or_text, *, floor: float = 0.90,
                        program: str = "hlo") -> List[Finding]:
    """Fraction of dot FLOPs attributed to named scope groups."""
    from hetu_tpu.obs.hlo_profile import group_of
    txt = as_hlo_text(compiled_or_text)
    comps = split_computations(txt)
    defs = definitions(comps)
    mults = call_multipliers(comps)
    total = named = 0.0
    for cname, lines in comps.items():
        mult, _dyn = mults.get(cname, (1.0, False))
        for ln in lines:
            if " dot(" not in ln:
                continue
            fl = dot_flops(ln, defs) * mult
            if fl <= 0:
                continue
            total += fl
            om = OP_NAME_PAT.search(ln)
            if om is not None and group_of(om.group(1)) != "other":
                named += fl
    if total <= 0:
        return []
    cov = named / total
    findings = [Finding(
        "scope-coverage", INFO, program,
        f"{cov:.1%} of parsed dot FLOPs attributed to named scope "
        f"groups", {"coverage": cov, "total_flops": total})]
    if cov < floor:
        findings.append(Finding(
            "scope-coverage", WARNING, program,
            f"scope coverage {cov:.1%} is below the {floor:.0%} floor — "
            f"{total - named:.3g} FLOPs are invisible to the analytic "
            f"profiler (obs.hlo_profile attributes them to 'other')",
            {"coverage": cov, "floor": floor}))
    return findings


def lint_moe_dispatch(compiled_or_text, *, topology=None,
                      program: str = "hlo") -> List[Finding]:
    """Flat slice-spanning dispatch all-to-alls: a program that lowers
    an all-to-all whose replica group crosses slice boundaries in ONE
    flat group (size > slice_devices, divisible into slices) is paying
    inter-slice rates for every hop when the two-level schedule
    (comm/topology groups; HETU_TPU_COMM_TOPOLOGY=two_level routes the
    MoE dispatch and the DP grad sync through it) was available.
    Vacuous when the profile declares no topology or nothing lowers an
    all-to-all."""
    if topology is None:
        from hetu_tpu.comm.topology import load_topology
        topology = load_topology()
    if topology is None or topology.slice_devices <= 1:
        return []
    k = topology.slice_devices
    txt = as_hlo_text(compiled_or_text)
    comps = split_computations(txt)
    findings: List[Finding] = []
    for cname, lines in comps.items():
        for ln in lines:
            found = maybe_collective(ln)
            if found is None or found[0] != "all-to-all":
                continue
            n, ranks = first_group(ln, 1)
            if not ranks or n <= k or n % k:
                continue
            if topology.classify_group(ranks) != "inter":
                continue
            # a group with at most ONE rank per slice is the two-level
            # schedule's own strided inter transversal — exactly the
            # shape this lint recommends, never a finding.  FLAT
            # slice-spanning groups put whole slices (>1 rank each) in
            # one group.
            per_slice: Dict[int, int] = {}
            for r in ranks:
                s = int(r) // k
                per_slice[s] = per_slice.get(s, 0) + 1
            if max(per_slice.values()) <= 1:
                continue
            findings.append(Finding(
                "moe-dispatch", WARNING, f"{program}:{cname}",
                f"all-to-all over a flat {n}-rank group spanning "
                f"{n // k} slices of {k} — every hop pays the "
                f"inter-slice rate; the two-level schedule "
                f"(HETU_TPU_COMM_TOPOLOGY=two_level) was available but "
                f"not taken",
                {"group_size": n, "slice_devices": k,
                 "line": ln.strip()[:200]}))
    return findings


def lint_hlo(compiled_or_text, *, expected_dtype: Optional[str] = None,
             min_bytes: int = MIN_BYTES, coverage_floor: float = 0.90,
             program: str = "hlo") -> List[Finding]:
    """All HLO lints over one program; the text stringifies once."""
    txt = as_hlo_text(compiled_or_text)
    out: List[Finding] = []
    out += lint_donation(txt, min_bytes=min_bytes, program=program)
    out += lint_replica_groups(txt, program=program)
    out += lint_replication(txt, min_bytes=min_bytes, program=program)
    out += lint_dtype_drift(txt, expected_dtype, program=program)
    out += lint_scope_coverage(txt, floor=coverage_floor, program=program)
    out += lint_moe_dispatch(txt, program=program)
    return out
