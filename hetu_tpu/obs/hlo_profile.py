"""Analytic step profiler: per-layer HLO attribution + peak-HBM accounting.

Attribution finer than coarse phases, computed from counts: this module
walks the POST-OPTIMIZATION HLO of a compiled train step (any backend,
incl. the 8-device CPU test mesh) and attributes
FLOPs, HBM traffic (output bytes), and bytes-on-wire **per named
layer/op-group** — the `jax.named_scope` names the model stack emits
(`layer_3/attn`, `layer_3/mlp`, `embed`, `lm_head`, `optimizer`,
`grad_sync`; scanned stacks collapse to one `layer/...` group whose
while-loop trip count multiplies through).  Three measurements, one
text walk:

* **per-group attribution** (`layer_table`) — the same line scan
  `utils.profiling.phase_breakdown` does, refined to full scope paths
  and extended with parsed dot FLOPs (2 * out_elems * contraction from
  each `dot(...)` line's operand shapes) and the ring wire bytes of any
  collective in the group (`obs.comm`'s formulas — ONE byte model).
  Sums reconcile with the coarse phases by construction: both walks
  count the same `op_name=` lines (tested).

* **roofline per group** (`layer_profile`) — each group bounded by
  max(flops/compute_rate, out_bytes/hbm_rate) + wire_bytes/ici_rate
  over the hardware profile: PREDICTED times.

* **the join key for MEASURED times** (`scope_map`) — {instruction
  name: (group, pass)} over every instruction of the module.  A
  profiler trace names each device event by its HLO instruction and
  carries no `op_name`; `benchmarks/trace.py` looks the instruction up
  here and sums measured device time per scope, forward, backward and
  recomputed forward apart.  ONE resolver (`_resolve`) places every
  instruction in exactly one group, in a fixed order:

  (i)   its own `op_name`, where the path names a scope;
  (ii)  a `fusion`, `call`, `while` or `conditional` that (i) leaves
        without a group (the compiler's `.clone` fusions carry no
        metadata at all): its called computations' instructions — in a
        fusion the `convolution` / `dot` / `custom-call`'s group (a
        product sets a fusion's cost), else the group most scoped
        instructions of the body name, ties to the innermost scope;
  (iii) what is still without (`copy`, `copy-start` / `-done`,
        `bitcast_fusion`, a scan's slices, the gathers ZeRO's refresh
        becomes): the group of its first scoped USER in the same
        computation, seen through a `bitcast`; else, for an instruction
        the compiler made (no `op_name`) and for a product that lost its
        path (`ragged-dot-none`), of its first scoped OPERAND;
  (iv)  else `unscoped`.

  The pass comes from the instruction that gave the group.  What runs
  nothing (`parameter`, `constant`, `tuple`, `get-tuple-element`,
  `bitcast`) keeps what (i) gave it.  An inferred group is never a
  kernel's row (`.../pallas_<kernel>`): only a kernel's own
  instructions enter it, so a kernel's measured time and roofline share
  mean what they meant.  `scope_sources` says which step placed each
  instruction ("own" | "body" | "user" | "operand" | "none"): "own" is
  what the program said, the others are INFERRED and can be wrong in
  known ways — a relayout two scopes read goes to the first reader in
  the text's order; a fusion whose body mixes two scopes goes whole to
  the product's (or the majority's); a value that reaches its reader
  through a `tuple` into a `while` (a weight copied once before a layer
  scan) finds no reader in its own computation and stays `unscoped`;
  an operation with a path but no scope (the final norm, a scan's own
  stacking) may go to its reader but never to what fed it.  Programs
  whose instructions all name their scope map exactly as by (i) alone.
  `layer_table` takes its groups from the same resolver.

ALL HLO-text parsing primitives (line anatomy, shapes, collectives,
while-trip/call-graph multipliers, dot FLOPs, donation contracts) live
in `hetu_tpu.obs.hlo_text` — one tokenizer shared with the bytes-on-wire
analyzer (obs/comm.py) and the graph-contract linter
(hetu_tpu/analysis/).  This module owns only the attribution, roofline
and liveness ACCOUNTING layered on top.

* **peak-HBM estimate** (`peak_hbm_estimate`) — a liveness sweep over
  the HLO: every non-parameter instruction's output buffer is live from
  its definition to its last use; while bodies contribute their own
  internal peak (buffers REUSED across trips — which is exactly why a
  remat'd scanned stack peaks at one layer's working set, not L of
  them); fusion internals never materialize.  peak = entry argument
  bytes (params + optimizer state + batch) + the sweep's max live set,
  cross-checked against `compiled.memory_analysis()` when the backend
  exposes it (the `search/calibrate.py` source of truth).  The analytic
  twin (`analytic_peak_hbm`) prices params + Adam moments + grads +
  remat-aware activations from a model config alone — the bench
  fallback when nothing can even lower, and the cost model's
  feasibility term (`search/cost_model.py` `fits_hbm`).

Consumers: Trainer compile run-events (`HETU_TPU_PROFILE=1` -> a
schema-versioned `profile` RunLog record, `profile_record`),
`Trainer.profile_report`, bench.py (`detail.profile`: top-k groups +
peak HBM), tools_obs_report.py (the `profile` section), and the
regression sentinel (`obs/budget.py` + tools_bench_diff.py) that diffs
these numbers across rounds against declared budgets.

Known limits: GSPMD-inserted collectives (the implicit DP grad
all-reduce) carry the scope of the op that PRODUCED their operand, so
the explicit-comm paths (`grad_sync`) attribute exactly while implicit
ones attribute to their producing layer; `dynamic_trip_count` loops
count once (same caveat as obs/comm, surfaced in the report).
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

from hetu_tpu.obs.hlo_text import (BRANCH_PAT, CALLEE_PAT, DEF_PAT,
                                   INSTR_PAT, OP_NAME_PAT, OUT_PAT, REF_PAT,
                                   as_hlo_text,
                                   call_multipliers, definitions,
                                   dot_flops,
                                   entry_computation, line_wire_bytes,
                                   shape_bytes, split_computations)
from hetu_tpu.utils.profiling import PHASES

#: version stamp of the `profile` RunLog record / BENCH detail.profile
#: payload (the same stability contract as obs.runlog.SCHEMA_VERSION:
#: new optional fields may be added within a version, none renamed)
PROFILE_SCHEMA = 1

#: scope names that form an op-group on their own (next to the
#: per-layer `layer_<i>` scopes and the model phases)
EXTRA_GROUPS = ("optimizer", "grad_sync")

#: every dispatcher in the fused-kernel layer enters its Pallas call
#: under a `pallas_<kernel>` named scope (ops/pallas, docs/kernels.md);
#: instructions under one — the custom-call on TPU, the interpreted
#: kernel body on the CPU test mesh — are attributed to that kernel
#: group: `layer_3/attn/pallas_flash_attention` rows in `layer_table`,
#: aggregated across groups by `kernel_table`
KERNEL_SCOPE_PREFIX = "pallas_"

# scope-path patterns (the profiler's own layer — everything below the
# line/shape level comes from obs.hlo_text)
_LAYER_SEG_PAT = re.compile(r'^layer(_\d+)?$')
_TRANSFORM_PAT = re.compile(r'^[\w.\-]+\((.*)\)$')


# ---------------------------------------------------------------------------
# scope-path parsing
# ---------------------------------------------------------------------------

def scope_segments(op_name: str) -> List[str]:
    """`jit(f)/jit(main)/transpose(jvp(layer_1))/attn/dot_general` ->
    ["f", "main", "layer_1", "attn", "dot_general"]: each '/'-separated
    token unwrapped of its transform wrappers (jvp/transpose/jit/remat
    ...), so forward AND backward instructions land in the same group."""
    out = []
    for tok in op_name.split("/"):
        while True:
            m = _TRANSFORM_PAT.match(tok)
            if m is None or not m.group(1):
                break
            tok = m.group(1)
        if tok:
            out.append(tok)
    return out


def group_of(op_name: str, phases: Tuple[str, ...] = PHASES) -> str:
    """The attribution group of one instruction's scope path:
    `layer_<i>/<phase>` when both a layer scope and a phase scope are
    present, the layer alone, the phase alone (embed / lm_head /
    optimizer / grad_sync live outside layers), else "other".  A
    `pallas_<kernel>` scope (the fused-kernel layer's dispatchers)
    appends its kernel name, so the kernel's instructions form their own
    row WITHIN their layer/phase (`layer_0/attn/pallas_flash_attention`)
    instead of blending into the surrounding group."""
    segs = scope_segments(op_name)
    layer = next((s for s in reversed(segs)
                  if _LAYER_SEG_PAT.match(s)), None)
    known = (*phases, *EXTRA_GROUPS)
    phase = next((s for s in reversed(segs) if s in known), None)
    # not the primitive's own name: on TPU the kernel is ONE custom call
    # whose path ends `.../pallas_flash_attention/pallas_call`
    kernel = next((s for s in reversed(segs)
                   if s.startswith(KERNEL_SCOPE_PREFIX)
                   and s != "pallas_call"), None)
    if layer and phase:
        base = f"{layer}/{phase}"
    elif layer:
        base = layer
    elif phase:
        base = phase
    elif kernel:
        return kernel
    else:
        return "other"
    return f"{base}/{kernel}" if kernel else base


#: scopes that name a group in `scope_map` beside the model phases and
#: EXTRA_GROUPS: the paged pool's token and page writes
#: (models/generation.py, serving/engine.py) and the cross-entropy after
#: the head (models/llama `forward`); the parts of latent attention
#: (`attn` > `mla_q`, `mla_kv`, `mla_out`) and of a routed expert layer
#: (`mlp` > `router`, `experts`, `shared_expert`) of models/kimi_k2 and
#: nn/moe.SharedRoutedExperts.  The innermost known name is the group,
#: so these split their parent's time and leave in `layer/attn` and
#: `layer/mlp` what is outside them; the attention of a layer that reads
#: a window only and of one that reads everything, in a model that has
#: both (`attn` > `attn_window`, `attn_full`: models/generation.py
#: `_layer`, models/trinity); a linear-attention layer and its parts
#: (`attn` > `kda` > `kda_proj`, `kda_conv`, `kda_scan` in the chunk
#: program / `kda_step` in the decode program, `kda_out`:
#: models/bailing_hybrid; `kda` alone keeps the input norm and the
#: state's rows written back in place); the attention of a layer that
#: keeps no cache and reads another layer's pages (`attn_cross`), a
#: Mamba-1 layer and its parts (`attn` > `ssm` > `ssm_proj`, `ssm_conv`,
#: `ssm_norm` where the family norms dt, B and C: models/jamba,
#: `ssm_scan` in the chunk program / `ssm_step` in the decode program,
#: `ssm_out`: nn/mamba), a gated memory unit (`gmu`: models/phi4_flash), and what
#: the chunk program runs for the rows whose logits are read alone and
#: that no inner scope names (`tail`: models/generation.extend_cache; a
#: layer's own scopes inside the tail keep their groups); the addend of
#: the identity experts a token chose (`mlp` > `zero_experts`:
#: nn/moe.SharedRoutedExperts told of them, models/longcat_flash); the mixes
#: of a residual stream around a sublayer, SIBLINGS of `attn` and `mlp`
#: under `layer` (`mhc_pre`, `mhc_sinkhorn`, `mhc_post`:
#: nn/hyper_connections, models/xing4); the parts of a latent-attention
#: layer that SELECTS what it attends, beside MLA's own (`attn` >
#: `dsa_index_q`, `dsa_index_k`: the indexer's projections; `dsa_score`,
#: `dsa_select`, `dsa_attend`: the scores of every position a query sees,
#: the exact selection, and the attention over it: models/deepseek_v32,
#: ops/sparse_attention); a gated short convolution and its parts (`attn`
#: > `short_conv` > `short_conv_proj`: W_in and W_out; `short_conv_mix`:
#: the two gates, the taps and the tail; `short_conv` alone keeps the
#: input norm and the state's rows taken out and written back:
#: models/lfm2_moe).  `diff_out`,
#: what follows the attention kernel in a differential-attention layer,
#: is a scope of the operations' paths and NOT a group: its time stays
#: with its kind of layer.  No program without these scopes changes its
#: groups.
SCOPE_MAP_GROUPS = ("kv_write", "loss", "mla_q", "mla_kv", "mla_out",
                    "router", "experts", "shared_expert",
                    "attn_window", "attn_full",
                    "kda", "kda_proj", "kda_conv", "kda_scan", "kda_step",
                    "kda_out",
                    "attn_cross", "ssm", "ssm_proj", "ssm_conv", "ssm_scan",
                    "ssm_step", "ssm_out", "gmu", "tail", "ssm_norm",
                    "zero_experts", "mhc_pre", "mhc_sinkhorn", "mhc_post",
                    "dsa_index_q", "dsa_index_k", "dsa_score", "dsa_select",
                    "dsa_attend",
                    "short_conv", "short_conv_proj", "short_conv_mix")
UNSCOPED = "unscoped"
#: what `scope_sources` says of an instruction: its own `op_name` named
#: the group; its called computation's instructions did; its first
#: scoped user did; its first scoped operand did; nothing did
OWN, BODY, USER, OPERAND, NONE = "own", "body", "user", "operand", "none"
#: opcodes whose called computations decide their group in step (ii)
_CALLERS = ("fusion", "call", "while", "conditional")
#: a product sets its fusion's cost, so it names the fusion's group;
#: and a product that lost its path (`ragged-dot-none`) belongs with
#: the rows it multiplies where nothing reads it under a scope
_PRODUCTS = ("convolution", "dot", "custom-call")
#: opcodes that run nothing: a trace never shows them and steps (ii)
#: and (iii) leave them as (i) left them
_ALIASES = ("parameter", "constant", "get-tuple-element", "tuple",
            "bitcast")


def pass_of(op_name: str) -> str:
    """`recompute` for a forward that `jax.checkpoint` runs again inside
    the backward pass (`.../checkpoint/rematted_computation/...`), `bwd`
    for the transposed program (`transpose(jvp(...))` somewhere in the
    path), else `fwd`.  Read from compiled v5e and CPU HLO alike: both
    carry the two markers (tests/test_step_spans.py)."""
    if "rematted_computation" in op_name:
        return "recompute"
    return "bwd" if "transpose(" in op_name else "fwd"


def _layer_part(group: str) -> str:
    """`group` without a kernel's segment: what an INFERRED placement
    may enter (a copy that a Pallas call reads is the layer part's; only
    a kernel's own instructions are its row)."""
    return "/".join(seg for seg in group.split("/")
                    if not seg.startswith(KERNEL_SCOPE_PREFIX)) or group


class _Instr:
    """One instruction line as the resolver reads it, and where it ends:
    `group`, `ps` (the pass), `source`, `via` (the `op_name` that named
    the group: its own, or that of the instruction it took it from) and
    `depth`, how far down that path the scope that named the group
    stands (a tie's innermost)."""
    __slots__ = ("name", "opcode", "op_name", "operands", "callees",
                 "group", "ps", "source", "via", "depth")

    def __init__(self, name: str, line: str):
        self.name = name
        dm = DEF_PAT.search(line)
        self.opcode = dm.group(3) if dm is not None else ""
        om = OP_NAME_PAT.search(line)
        self.op_name = om.group(1) if om is not None else ""
        # the operands end where the attributes begin: `calls=%...`
        # names a computation, never a value
        rest = line[dm.end():] if dm is not None else ""
        cut = rest.find("), ")
        self.operands = REF_PAT.findall(rest if cut < 0 else rest[:cut])
        self.callees = [c.group(1) for c in CALLEE_PAT.finditer(line)]
        bm = BRANCH_PAT.search(line)
        if bm is not None:
            self.callees += REF_PAT.findall(bm.group(1))
        self.group, self.source, self.depth = UNSCOPED, NONE, 0
        self.ps, self.via = pass_of(self.op_name), self.op_name

    def take(self, other: "_Instr", source: str):
        """The group and pass of `other`, inferred: never its kernel's
        row (`_layer_part`)."""
        self.group = _layer_part(other.group)
        self.ps, self.depth, self.source = other.ps, other.depth, source
        self.via = other.via

    def group_under(self, phases: Tuple[str, ...]) -> str:
        """The group as `group_of` names it with `phases` alone known
        (the static profile's coarser rows; "other" for `unscoped`):
        read from the SAME path that named `group`, so the two cannot
        disagree on where the instruction belongs."""
        if self.source == NONE:
            return "other"
        group = group_of(self.via, phases)
        return group if self.source == OWN else _layer_part(group)


#: every scope the resolver knows: the measured join's groups
_KNOWN = (*PHASES, *SCOPE_MAP_GROUPS)


def _resolve(compiled_or_text,
             comps: Optional[Dict[str, List[str]]] = None
             ) -> Dict[str, _Instr]:
    """THE attribution of every instruction of a module to one group:
    `scope_map`, `scope_sources`, `layer_table` and `profile_record`
    read this one walk, in the module docstring's fixed order, with
    every group the measured join knows (`_KNOWN`).  A computation is
    resolved whole, its callees first, before an instruction that calls
    it looks at its body."""
    comps = comps if comps is not None else split_computations(
        as_hlo_text(compiled_or_text))
    known = (*_KNOWN, *EXTRA_GROUPS)
    parsed: Dict[str, List[_Instr]] = {}
    for cname, lines in comps.items():
        rows = parsed[cname] = []
        for line in lines:
            m = INSTR_PAT.match(line)
            if m is not None:
                rows.append(_Instr(m.group(1), line))
    out: Dict[str, _Instr] = {}
    done: set = set()

    def from_body(ins: _Instr):
        """(ii): the product's group where a fusion's body holds one,
        else the group most scoped instructions of the called
        computations name, ties to the innermost scope."""
        body = [b for c in ins.callees for b in parsed.get(c, ())
                if b.source in (OWN, BODY)]
        pick = next((b for b in body if b.opcode in _PRODUCTS), None) \
            if ins.opcode == "fusion" else None
        if pick is None and body:
            votes: Dict[str, List[_Instr]] = {}
            for b in body:
                votes.setdefault(b.group, []).append(b)
            pick = max(votes.values(), key=lambda v: (
                len(v), max(b.depth for b in v)))[0]
        if pick is not None:
            ins.take(pick, BODY)

    def resolve(cname: str):
        if cname in done:
            return
        done.add(cname)
        rows = parsed.get(cname, ())
        for ins in rows:
            group = group_of(ins.op_name, _KNOWN)       # (i)
            if group != "other":
                ins.group, ins.source = group, OWN
                ins.depth = max(i for i, s in enumerate(
                    scope_segments(ins.op_name))
                    if s in known or _LAYER_SEG_PAT.match(s)
                    or s.startswith(KERNEL_SCOPE_PREFIX))
            elif ins.opcode in _CALLERS:
                for c in ins.callees:
                    resolve(c)
                from_body(ins)
        # (iii) the first scoped user, the last instruction first (a
        # module's text lists a value before what reads it, so a
        # `copy-start` finds its `copy-done` placed), seen through a
        # bitcast; then, for what the compiler made (no `op_name`) and
        # for a product, the first scoped operand, first to last
        by_name = {ins.name: ins for ins in rows}
        users: Dict[str, List[_Instr]] = {}
        for ins in rows:
            for o in ins.operands:
                if o in by_name and o != ins.name:
                    users.setdefault(o, []).append(ins)

        def readers(ins: _Instr):
            for u in users.get(ins.name, ()):
                if u.opcode == "bitcast" and u.source == NONE:
                    yield from readers(u)
                else:
                    yield u

        for ins in reversed(rows):
            if ins.source == NONE and ins.opcode not in _ALIASES:
                hit = next((u for u in readers(ins) if u.source != NONE),
                           None)
                if hit is not None:
                    ins.take(hit, USER)
        for ins in rows:
            if ins.source == NONE and ins.opcode not in _ALIASES and (
                    not ins.op_name or ins.opcode in _PRODUCTS):
                hit = next((by_name[o] for o in ins.operands
                            if o in by_name
                            and by_name[o].source != NONE), None)
                if hit is not None:
                    ins.take(hit, OPERAND)
        for ins in rows:
            out[ins.name] = ins

    for cname in comps:
        resolve(cname)
    return out


def scope_map(compiled_or_text) -> Dict[str, Tuple[str, str]]:
    """{instruction name: (group, pass)} over EVERY instruction of the
    post-optimization HLO module, fused ones included.  `group` is
    `group_of`'s key with the groups of SCOPE_MAP_GROUPS known too --
    `layer/attn`, `layer/attn/pallas_flash_attention`,
    `layer/kv_write`, `lm_head`, `optimizer`, ... -- found
    in the module docstring's fixed order: the instruction's own
    `op_name`; for a fusion, call, while or conditional the compiler
    left without one, its body's; for what is still without (the
    compiler's copies and relayouts, a scan's slices, the collectives
    of a ZeRO gather, the custom calls `jax.lax.ragged_dot` becomes,
    whose `op_name` is "ragged-dot-none", the jit path dropped) its
    first scoped user's, else its first scoped operand's; and
    `unscoped` where none of these names a scope (the loop counters,
    the loss scaling around the micro-batch loop).  `pass` is `pass_of`
    of the instruction that gave the group.  `scope_sources` says which
    step placed each.
    Instruction names are unique within a module, not across modules:
    keep one map per program."""
    return {name: (ins.group, ins.ps)
            for name, ins in _resolve(compiled_or_text).items()}


def scope_sources(compiled_or_text) -> Dict[str, str]:
    """{instruction name: "own" | "body" | "user" | "operand" | "none"}:
    the step of `scope_map`'s order that placed the instruction.  "own"
    is what the program said itself; "body", "user" and "operand" are
    inferred from the instructions around it (a relayout two scopes
    read goes to the first reader), and "none" is `unscoped`."""
    return {name: ins.source
            for name, ins in _resolve(compiled_or_text).items()}


# ---------------------------------------------------------------------------
# per-group attribution
# ---------------------------------------------------------------------------

def layer_table(compiled_or_text, *, phases: Tuple[str, ...] = PHASES,
                default_world: int = 1,
                apply_multipliers: bool = True,
                placed: Optional[Dict[str, _Instr]] = None
                ) -> Dict[str, Dict[str, float]]:
    """{group: {"instructions", "dots", "flops", "out_bytes",
    "wire_bytes"}} over the optimized HLO, execution multipliers
    applied (scanned layers count trip-count times).  Groups are
    `group_of` keys with `phases` known, each read from the path that
    placed the instruction in `scope_map`'s resolver (an `op_name`
    whose path names no scope goes to its body's or its reader's
    group, else to "other"; `placed`: that walk's result, where the
    caller holds it already); an extra
    "_meta" entry carries
    {"dynamic_trip_count"} when some loop's trip was unresolvable.

    apply_multipliers=False counts each instruction ONCE (static) —
    exactly `utils.profiling.phase_breakdown`'s accounting (same lines,
    same output-shape anchoring), so per-group sums reconcile with the
    coarse per-phase totals; with multipliers on, wire-byte sums
    reconcile with `obs.comm.collective_report` instead (which resolves
    the same trip counts) — both are the attribution-consistency
    contract the tests pin."""
    txt = as_hlo_text(compiled_or_text)
    comps = split_computations(txt)
    defs = definitions(comps)
    mults = (call_multipliers(comps) if apply_multipliers
             else {name: (1.0, False) for name in comps})
    placed = placed if placed is not None else _resolve(txt, comps)
    out: Dict[str, Dict[str, float]] = {}
    under: Dict[Tuple[str, str], str] = {}      # (path, source) -> group

    def group_at(line: str) -> str:
        """Where the ONE resolver put the line's instruction (`other`
        is this table's name for `unscoped`): the static profile and
        the measured join agree on where an instruction belongs."""
        m = INSTR_PAT.match(line)
        ins = placed.get(m.group(1)) if m is not None else None
        if ins is None:
            return "other"
        key = (ins.via, ins.source)
        if key not in under:
            under[key] = ins.group_under(phases)
        return under[key]
    dynamic = False
    conv_unparsed = False

    def new_row():
        return {"instructions": 0.0, "dots": 0.0, "flops": 0.0,
                "out_bytes": 0.0, "wire_bytes": 0.0}
    for cname, lines in comps.items():
        mult, dyn = mults.get(cname, (1.0, False))
        for line in lines:
            m = OP_NAME_PAT.search(line)
            if m is None:
                # instructions without op_name metadata are outside the
                # phase accounting (phase_breakdown skips them too — the
                # static-sum contract), but a GSPMD-inserted collective
                # without metadata still moves real bytes: count its
                # wire bytes where the resolver put it ("other" where
                # nothing places it) so wire sums reconcile with
                # obs.comm.collective_report on EVERY program
                wb = line_wire_bytes(line, default_world)
                if wb > 0:
                    out.setdefault(group_at(line), new_row())[
                        "wire_bytes"] += wb * mult
                    dynamic = dynamic or dyn
                continue
            dynamic = dynamic or dyn
            rec = out.setdefault(group_at(line), new_row())
            rec["instructions"] += mult
            if " dot(" in line or " convolution(" in line:
                rec["dots"] += mult
                rec["flops"] += dot_flops(line, defs) * mult
                if " convolution(" in line:
                    # conv FLOPs are not statically parsed (no conv in
                    # the model zoo today) — surface the undercount
                    # instead of silently attributing 0
                    conv_unparsed = True
            om = OUT_PAT.search(line)
            if om is not None:
                rec["out_bytes"] += shape_bytes(om.group(1)) * mult
            rec["wire_bytes"] += line_wire_bytes(line, default_world) * mult
    meta = {}
    if dynamic:
        meta["dynamic_trip_count"] = True
    if conv_unparsed:
        meta["conv_flops_unparsed"] = True
    if meta:
        out["_meta"] = meta
    return out


def kernel_table(compiled_or_text, *, phases: Tuple[str, ...] = PHASES,
                 default_world: int = 1) -> Dict[str, Dict[str, float]]:
    """Aggregate `layer_table` rows by Pallas kernel: every group whose
    path carries a `pallas_<kernel>` segment contributes to that
    kernel's totals ({kernel: {"instructions", "dots", "flops",
    "out_bytes", "wire_bytes", "groups"}}).  Empty when the program has
    no routed Pallas kernels — e.g. with HETU_TPU_PALLAS=0, which is
    exactly what the flag-off identity test leans on."""
    table = layer_table(compiled_or_text, phases=phases,
                        default_world=default_world)
    out: Dict[str, Dict[str, float]] = {}
    for group, row in table.items():
        if group == "_meta":
            continue
        kern = next((seg for seg in group.split("/")
                     if seg.startswith(KERNEL_SCOPE_PREFIX)), None)
        if kern is None:
            continue
        rec = out.setdefault(kern, {"instructions": 0.0, "dots": 0.0,
                                    "flops": 0.0, "out_bytes": 0.0,
                                    "wire_bytes": 0.0, "groups": []})
        for k in ("instructions", "dots", "flops", "out_bytes",
                  "wire_bytes"):
            rec[k] += row[k]
        rec["groups"].append(group)
    return out


def _layer_sort_key(group: str):
    """Model order: embed, layer_0..layer_n (or the scanned "layer"),
    lm_head, grad_sync, optimizer, unknown scopes, other."""
    head = group.split("/")[0]
    m = re.match(r'layer_(\d+)$', head)
    if m:
        return (1, int(m.group(1)), group)
    if head == "layer":
        return (1, -1, group)
    order = {"embed": 0, "lm_head": 2, "grad_sync": 3,
             "optimizer": 4, "other": 6}
    return (order.get(head, 5), 0, group)


def layer_profile(compiled_or_text, *, hw: Optional[Dict] = None,
                  phases: Tuple[str, ...] = PHASES,
                  default_world: int = 1,
                  placed: Optional[Dict[str, _Instr]] = None
                  ) -> Dict[str, Any]:
    """Roofline-price the per-group attribution: each group's predicted
    time is max(flops/compute, out_bytes/hbm) + wire_bytes/ici over the
    hardware profile's rates.  Returns {"groups": {group: {...,
    "time_s", "bound"}}, "totals", "estimated_step_s", "top"} with
    groups in model order (embed, layer_0..n / scanned layer, lm_head,
    grad_sync, optimizer, other).  `placed` as in `layer_table`."""
    from hetu_tpu.obs.mfu import _rates, load_hardware_profile
    hw = hw if hw is not None else load_hardware_profile()
    compute, hbm, _peak = _rates(hw)
    ici = float(hw.get("ici_allreduce_gbps", 45.0)) * 1e9
    table = layer_table(compiled_or_text, phases=phases,
                        default_world=default_world, placed=placed)
    meta = table.pop("_meta", None)
    groups: Dict[str, Dict[str, float]] = {}
    totals = {"instructions": 0.0, "dots": 0.0, "flops": 0.0,
              "out_bytes": 0.0, "wire_bytes": 0.0}
    t_total = 0.0
    for g in sorted(table, key=_layer_sort_key):
        rec = dict(table[g])
        t_c = rec["flops"] / compute
        t_m = rec["out_bytes"] / hbm
        t_w = rec["wire_bytes"] / ici
        rec["time_s"] = max(t_c, t_m) + t_w
        rec["bound"] = ("wire" if t_w > max(t_c, t_m)
                        else "memory" if t_m > t_c else "compute")
        groups[g] = rec
        t_total += rec["time_s"]
        for k in totals:
            totals[k] += rec[k]
    top = sorted(groups.items(), key=lambda kv: -kv[1]["time_s"])
    report: Dict[str, Any] = {
        "groups": groups,
        "totals": totals,
        "estimated_step_s": t_total,
        "top": [{"group": g, "time_s": r["time_s"], "flops": r["flops"],
                 "out_bytes": r["out_bytes"], "bound": r["bound"]}
                for g, r in top],
        "chip": hw.get("chip", "unknown"),
    }
    if meta:
        report.update(meta)
    return report


# ---------------------------------------------------------------------------
# peak-HBM accounting
# ---------------------------------------------------------------------------

#: opcodes whose output ALIASES their operands' storage 1:1 — counting
#: them as new buffers would double every while carry (tuple in,
#: get-tuple-element out) and inflate the liveness peak severalfold
_ALIAS_OPS = ("get-tuple-element", "tuple", "bitcast", "while",
              "optimization-barrier")


def _comp_peak(comps: Dict[str, List[str]], name: str,
               memo: Dict[str, float], seen: Tuple[str, ...] = (),
               donated: bool = False) -> float:
    """Liveness peak (bytes) of one computation's internal buffers —
    the analytic twin of XLA buffer assignment's temp arena, which
    packs buffers with disjoint live ranges into shared offsets:

    * each real def is live [def line, last use of it or any alias];
    * structural aliases (`_ALIAS_OPS` — gte/tuple/bitcast/while) add
      no storage and extend their roots' lifetimes;
    * in-place sharing: when a def's byte size equals a root that DIES
      at that very line, XLA's elementwise/fusion in-place reuse writes
      the output over the operand — modeled by extending the dying
      root's lifetime instead of allocating; with `donated=True` (the
      module declares input_output_alias) a dying entry PARAMETER's
      storage is reusable the same way — how a donated train step
      writes new params over old ones;
    * a `while` line additionally holds its body's peak while it runs
      (the body REUSES its buffers across trips — exactly why a
      remat'd scanned stack peaks at ONE layer's working set, not L);
      conditionals hold the max branch; fusion internals never
      materialize."""
    if name in memo:
        return memo[name]
    if name in seen or name not in comps:
        return 0.0
    lines = comps[name]
    parsed: List[Optional[Tuple[str, int, str, List[str]]]] = []
    roots: Dict[str, Tuple[str, ...]] = {}   # name -> storage roots
    transient: Dict[int, float] = {}         # line -> callee peak bytes
    persistent: Dict[str, int] = {}          # donated entry params

    def root_of(nm: str) -> Tuple[str, ...]:
        return roots.get(nm, (nm,))

    for i, ln in enumerate(lines):
        m = DEF_PAT.search(ln)
        if m is None:
            parsed.append(None)
            continue
        nm, op = m.group(1), m.group(3)
        operands = [r for r in REF_PAT.findall(ln) if r != nm]
        b = 0 if op in ("parameter",) + _ALIAS_OPS \
            else shape_bytes(m.group(2))
        if op == "parameter" and donated:
            persistent[nm] = shape_bytes(m.group(2))
        if op in _ALIAS_OPS:
            rs: Tuple[str, ...] = ()
            for o in operands:
                rs += root_of(o)
            roots[nm] = tuple(dict.fromkeys(rs)) or (nm,)
        parsed.append((nm, b, op, operands))
        if op == "while":
            bm = re.search(r'body=%?([\w.\-]+)', ln)
            if bm is not None:
                transient[i] = _comp_peak(comps, bm.group(1), memo,
                                          seen + (name,))
        elif op == "conditional":
            bm = BRANCH_PAT.search(ln)
            branches = (REF_PAT.findall(bm.group(1)) if bm else [])
            for cm in re.finditer(r'(?:true|false)_computation='
                                  r'%?([\w.\-]+)', ln):
                branches.append(cm.group(1))
            if branches:
                transient[i] = max(
                    _comp_peak(comps, b_, memo, seen + (name,))
                    for b_ in branches)
        elif op in ("call", "custom-call"):
            cm = re.search(r'to_apply=%?([\w.\-]+)', ln)
            if cm is not None:
                # the callee's ROOT buffer is the call's output — the
                # caller already counts it as this def, so the callee
                # peak contributes only its EXCESS over the output
                transient[i] = max(
                    _comp_peak(comps, cm.group(1), memo,
                               seen + (name,)) - b, 0.0)

    bytes_of: Dict[str, int] = {}
    def_line: Dict[str, int] = {}
    last_use: Dict[str, int] = {}
    for i, rec in enumerate(parsed):
        if rec is None:
            continue
        nm, b, op, operands = rec
        if b > 0:
            bytes_of[nm] = b
            def_line[nm] = i
            last_use[nm] = i
        for o in operands:
            for r in root_of(o):
                last_use[r] = i

    # sequential sweep with the in-place sharing heuristic
    events: List[Tuple[int, float]] = []
    for i, rec in enumerate(parsed):
        if rec is None:
            continue
        nm, b, op, operands = rec
        if b <= 0:
            continue
        reused = None
        if op not in ("constant", "iota", "parameter"):
            for o in operands:
                for r in root_of(o):
                    if ((bytes_of.get(r) == b or persistent.get(r) == b)
                            and last_use.get(r) == i and r != nm):
                        reused = r
                        break
                if reused:
                    break
        if reused is not None:
            # output takes over the dying operand's storage: fold this
            # def into the operand's buffer (alias) instead of a fresh
            # allocation, and let the operand's lifetime carry on
            roots[nm] = (reused,)
            last_use[reused] = max(last_use.get(reused, i),
                                   last_use.get(nm, i))
            bytes_of.pop(nm, None)
    for nm, b in bytes_of.items():
        events.append((def_line[nm], float(b)))
        events.append((last_use.get(nm, 0) + 1, -float(b)))
    for i, b in transient.items():
        if b > 0:
            events.append((i, float(b)))
            events.append((i + 1, -float(b)))
    events.sort(key=lambda e: (e[0], -e[1]))
    live = peak = 0.0
    for _, d in events:
        live += d
        peak = max(peak, live)
    memo[name] = peak
    return peak


def peak_hbm_estimate(compiled_or_text, *,
                      hw: Optional[Dict] = None,
                      text: Optional[str] = None) -> Dict[str, Any]:
    """Liveness-based peak-HBM estimate of one compiled step.

    peak_bytes = entry argument bytes (params + optimizer state + batch;
    donated args alias outputs, so they are NOT double-counted) + the
    liveness sweep's max concurrent non-parameter buffer set.  When the
    executable exposes `memory_analysis()` the XLA buffer-assignment
    numbers ride along as the cross-check (`xla_peak_bytes`,
    `vs_xla` ratio — the acceptance gate pins it within 20% on the
    tier-1 models).  `headroom_frac` prices the estimate against the
    profile's `hbm_gbytes` (>1.0 = the step does not fit).  `text` lets
    a caller that already materialized as_text() (profile_record) skip
    a second stringification of a large module."""
    txt = text if text is not None else (
        compiled_or_text if isinstance(compiled_or_text, str)
        else compiled_or_text.as_text())
    comps = split_computations(txt)
    entry = entry_computation(txt, comps)
    args_bytes = 0.0
    for ln in comps.get(entry, []):
        m = DEF_PAT.search(ln)
        if m is not None and m.group(3) == "parameter":
            args_bytes += shape_bytes(m.group(2))
    # a module that declares input_output_alias writes (some) outputs
    # over its donated argument buffers — the entry sweep may model
    # in-place reuse of dying parameter storage
    donated = "input_output_alias" in txt
    memo: Dict[str, float] = {}
    temp_peak = _comp_peak(comps, entry, memo, donated=donated)
    report: Dict[str, Any] = {
        "args_bytes": args_bytes,
        "temp_peak_bytes": temp_peak,
        "peak_bytes": args_bytes + temp_peak,
        "donated": donated,
    }
    ma = None
    if not isinstance(compiled_or_text, str):
        try:
            ma = compiled_or_text.memory_analysis()
        except Exception:
            ma = None
    if ma is not None:
        try:
            # XLA's live peak: arguments + the temp arena + outputs that
            # do NOT alias (donate into) an argument buffer
            xla_args = float(ma.argument_size_in_bytes)
            xla_temp = float(ma.temp_size_in_bytes)
            xla_out = float(getattr(ma, "output_size_in_bytes", 0.0) or 0.0)
            xla_alias = float(getattr(ma, "alias_size_in_bytes", 0.0) or 0.0)
            report["xla_args_bytes"] = xla_args
            report["xla_temp_bytes"] = xla_temp
            report["xla_peak_bytes"] = (xla_args + xla_temp
                                        + max(xla_out - xla_alias, 0.0))
            if report["xla_peak_bytes"] > 0:
                report["vs_xla"] = (report["peak_bytes"]
                                    / report["xla_peak_bytes"])
        except Exception:
            pass
    from hetu_tpu.obs.mfu import load_hardware_profile
    hw = hw if hw is not None else load_hardware_profile()
    hbm = float(hw.get("hbm_gbytes", 0.0) or 0.0) * 1e9
    if hbm > 0:
        report["hbm_gbytes"] = hw["hbm_gbytes"]
        report["headroom_frac"] = report["peak_bytes"] / hbm
    return report


def analytic_peak_hbm(num_params: float, *, batch: int, seq: int,
                      hidden: int, num_layers: int, vocab: int,
                      dp: int = 1, tp: int = 1, pp: int = 1, cp: int = 1,
                      zero: bool = False, remat: bool = True,
                      sequence_parallel: bool = False,
                      act_boundary_units: float = 1.0,
                      act_full_units: float = 12.0,
                      param_bytes: int = 4) -> Dict[str, float]:
    """Jax-free per-device peak-HBM model: master params + grads at
    `param_bytes` each (4 = the fp32-master default matching
    `search/cost_model.py.per_device_memory`; 2 prices bf16-weight
    training), Adam m/v always fp32 (dp-sharded under ZeRO),
    remat-aware activations (boundary buffers only under remat, the
    calibrated full working set otherwise) + fp32 logits.  This is the
    bench fallback when nothing can even lower, and the term the
    searcher's feasibility gate rejects OOM plans by."""
    shard = max(tp * pp, 1)
    params = float(param_bytes) * num_params / shard
    opt = 8.0 * num_params / shard
    if zero and dp > 1:
        opt /= dp
    grads = float(param_bytes) * num_params / shard
    b_local = batch / max(dp * cp, 1)
    seq_local = seq / max(cp, 1)
    layers_local = num_layers / max(pp, 1)
    act_per_layer = b_local * seq_local * hidden * 2.0
    if sequence_parallel and tp > 1:
        act_per_layer /= tp
    units = act_boundary_units if remat else act_full_units
    acts = act_per_layer * layers_local * units
    logits = b_local * seq_local * vocab * 4.0 / max(tp, 1)
    total = params + opt + grads + acts + logits
    return {"params_bytes": params, "opt_state_bytes": opt,
            "grads_bytes": grads, "activation_bytes": acts,
            "logits_bytes": logits, "peak_bytes": total,
            "param_bytes": float(param_bytes), "remat": bool(remat)}


# ---------------------------------------------------------------------------
# the schema-versioned profile record
# ---------------------------------------------------------------------------

def _sources_summary(placed: Dict[str, _Instr]
                     ) -> Dict[str, Dict[str, Any]]:
    """{source: {"instructions": n, "groups": [...]}} over a module's
    `_resolve`: how much of `scope_map` the program said itself ("own")
    and how much was inferred, for a reader of a run's record."""
    out: Dict[str, Dict[str, Any]] = {}
    for ins in placed.values():
        rec = out.setdefault(ins.source, {"instructions": 0, "groups": set()})
        rec["instructions"] += 1
        rec["groups"].add(ins.group)
    return {src: {"instructions": rec["instructions"],
                  "groups": sorted(rec["groups"])}
            for src, rec in sorted(out.items())}


def profile_record(compiled_or_text, *, hw: Optional[Dict] = None,
                   top_k: int = 8, default_world: int = 1,
                   profile: Optional[Dict[str, Any]] = None,
                   text: Optional[str] = None) -> Dict[str, Any]:
    """The `profile` RunLog payload (and BENCH `detail.profile` shape):
    {"profile_schema": 1, "top": top-k groups by predicted time,
    "groups": <count>, "estimated_step_s", "total_flops",
    "total_wire_bytes", "peak_hbm_bytes", "peak_hbm_vs_xla",
    "hbm_headroom_frac", "scope_sources": per source of
    `scope_sources` how many instructions it placed and in which
    groups} — small enough to ride every fresh compile.

    The HLO text is materialized ONCE and resolved ONCE: the attribution
    and the sources read the same walk, the peak walk the same text;
    callers that already hold a `layer_profile` report and/or the text
    (the trainer's compile hook) pass them in to skip the re-walk."""
    txt = text if text is not None else (
        compiled_or_text if isinstance(compiled_or_text, str)
        else compiled_or_text.as_text())
    placed = _resolve(txt)
    prof = profile if profile is not None else layer_profile(
        txt, hw=hw, default_world=default_world, placed=placed)
    peak = peak_hbm_estimate(compiled_or_text, hw=hw, text=txt)
    rec: Dict[str, Any] = {
        "profile_schema": PROFILE_SCHEMA,
        "groups": len(prof["groups"]),
        "top": [
            {k: (round(v, 9) if isinstance(v, float) else v)
             for k, v in row.items()}
            for row in prof["top"][:max(top_k, 1)]],
        "estimated_step_s": prof["estimated_step_s"],
        "total_flops": prof["totals"]["flops"],
        "total_out_bytes": prof["totals"]["out_bytes"],
        "total_wire_bytes": prof["totals"]["wire_bytes"],
        "peak_hbm_bytes": peak["peak_bytes"],
        "scope_sources": _sources_summary(placed),
    }
    for caveat in ("dynamic_trip_count", "conv_flops_unparsed"):
        if prof.get(caveat):
            rec[caveat] = True
    if "vs_xla" in peak:
        rec["peak_hbm_vs_xla"] = peak["vs_xla"]
    if "headroom_frac" in peak:
        rec["hbm_headroom_frac"] = peak["headroom_frac"]
    return rec
