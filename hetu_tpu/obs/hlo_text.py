"""The ONE post-optimization-HLO text tokenizer.

Three consumers walk compiled HLO text in this repo — the bytes-on-wire
analyzer (`obs/comm.py`), the per-layer step profiler
(`obs/hlo_profile.py`), and the graph-contract linter
(`hetu_tpu/analysis/hlo_lints.py`).  They used to each carry their own
regex set; a parse fix (tuple outputs, iota replica_groups, async
`-start` payloads, nested while trips) had to land three times or the
byte models silently drifted apart.  This module owns the shared layer:

* **line anatomy** — `INSTR_PAT` finds an instruction's own line and
  name; `parse_def` splits `%name = <shapes> opcode(...)`
  into (name, output-shape section, opcode); `shape_bytes` /
  `component_bytes` price a shape section (operand shapes live INSIDE
  the call parens and must never count — summing them overcounts
  traffic by the instruction fan-in);
* **collectives** — `first_group` parses `replica_groups` (both the
  explicit `{{0,1},{2,3}}` and iota `[2,2]<=[4]` forms),
  `payload_bytes` resolves sync vs async `-start` payloads,
  `ring_wire_bytes` prices one op under the standard ring algorithms,
  `line_wire_bytes` composes all three for one instruction line;
* **structure** — `split_computations` maps the module into
  {computation: lines}, `entry_computation` finds the ENTRY,
  `cond_trip_count` recovers a while's static trip count from its
  condition computation, `while_multipliers` (while bodies only — the
  comm accounting) and `call_multipliers` (EVERY call edge: fusions,
  calls, conditional branches — the profiler's accounting) turn those
  into per-computation execution multipliers;
* **FLOPs** — `dot_flops` prices one `dot(...)` line from its operand
  shapes x `lhs_contracting_dims`;
* **module contracts** — `donated_parameters` parses
  `input_output_alias`, `entry_parameters` lists the entry computation's
  parameter buffers — what the donation lint checks against liveness.

Behavioral contracts (pinned by tests/test_comm.py,
tests/test_hlo_profile.py and tests/test_hlo_text.py): the wire
formulas match `comm/wire.py` analytically; static per-group sums match
`utils.profiling.phase_breakdown`; while-trip resolution follows the
`compare(induction, constant), direction=LT` form every lax.scan lowers
to, with `dynamic=True` surfaced when a bound is not a literal.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

#: collective opcodes accounted by every consumer (async "-start" forms
#: fold into these; "-done" lines carry no payload)
COLLECTIVE_OPS = ("all-reduce", "reduce-scatter", "all-gather",
                  "all-to-all", "collective-permute")

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
               "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
               "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
               "c128": 16}

# `%x = <shapes> opcode(...)` — output-section anchoring: shapes AFTER
# '=' and BEFORE the opcode token; operand shapes (inside the parens)
# must not count.  Tuple outputs `(f32[..], f32[..])` and tiled layouts
# `{1,0:T(8,128)}` stay in the group: `T(` starts uppercase, dtype
# tokens are followed by `[` not `(`.
LINE_PAT = re.compile(r'=\s*(?P<out>.*?)\s*(?P<op>[a-z][a-z0-9_.-]*)\(')
DEF_PAT = re.compile(r'%([\w.\-]+)\s*=\s*(.*?)\s*([a-z][a-z0-9_.-]*)\(')
SHAPE_PAT = re.compile(r'\b([a-z][a-z0-9]*)\[([0-9,]*)\]')
OUT_PAT = re.compile(r'=\s*(.*?)\s*[a-z][a-z0-9_.-]*\(')
REF_PAT = re.compile(r'%([\w.\-]+)')
#: an instruction's own line, `[ROOT] %name = ...` (never a computation's
#: header, which has no `=` after its name): group 1 is the name
INSTR_PAT = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=')
OP_NAME_PAT = re.compile(r'op_name="([^"]+)"')
GROUPS_PAT = re.compile(r'replica_groups=\{(\{[0-9,{} ]*\})\}')
IOTA_GROUPS_PAT = re.compile(
    r'replica_groups=\[(\d+),(\d+)\]<=(?:\[[\d,]+\])(T\([\d,]+\))?')
#: the raw replica_groups attribute text (either form) — what the
#: replication lint compares across conditional branches
GROUPS_ATTR_PAT = re.compile(r'replica_groups=(\{[0-9,{} ]*\}|'
                             r'\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)')

# computation structure
COMP_HEAD_PAT = re.compile(
    r'^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{')
WHILE_PAT = re.compile(r'=\s*[^=]*\bwhile\(')
COND_REF_PAT = re.compile(r'condition=%?([\w.\-]+)')
BODY_REF_PAT = re.compile(r'body=%?([\w.\-]+)')
# the TPU compiler's text gives a scalar a layout (`s32[]{:T(128)}`) and
# leaves the operands' types out (`compare(%i, %n)`); XLA:CPU's does neither
CONST_PAT = re.compile(
    r'%?([\w.\-]+)\s*=\s*[su]\d+\[\](?:\{[^}]*\})?\s+constant\((\d+)\)')
COMPARE_PAT = re.compile(
    r'compare\(\s*(?:\S+\s+)?%?([\w.\-]+),\s*(?:\S+\s+)?%?([\w.\-]+)\s*\)')
DIRECTION_PAT = re.compile(r'direction=(\w+)')
CALLEE_PAT = re.compile(r'(?:calls|body|condition|to_apply)=%?([\w.\-]+)')
BRANCH_PAT = re.compile(r'branch_computations=\{([^}]*)\}')
ENTRY_PAT = re.compile(r'^ENTRY\s+%?([\w.\-]+)', re.M)
DOT_CONTRACT_PAT = re.compile(r'lhs_contracting_dims=\{([0-9,]*)\}')
ALIAS_ENTRY_PAT = re.compile(r'\(\s*(\d+)\s*,')


def as_hlo_text(compiled_or_text) -> str:
    """The post-optimization HLO text of a compiled executable, or the
    argument itself when it is already text — every consumer's first
    line, so large modules stringify once per caller, not per helper."""
    return (compiled_or_text if isinstance(compiled_or_text, str)
            else compiled_or_text.as_text())


# ---------------------------------------------------------------------------
# shapes / payloads
# ---------------------------------------------------------------------------

def component_bytes(section: str) -> List[int]:
    """Byte size of each shape component in one output-shape section."""
    out = []
    for dt, dims in SHAPE_PAT.findall(section):
        numel = 1
        for d in dims.split(","):
            if d:
                numel *= int(d)
        out.append(numel * DTYPE_BYTES.get(dt, 4))
    return out


def shape_bytes(section: str) -> int:
    """Total bytes of one output-shape section (tuple components sum)."""
    return sum(component_bytes(section))


def payload_bytes(section: str, is_start: bool) -> int:
    """Payload of one collective from its output-shape section.

    Sync forms: the output IS the payload (sum tuple components — a tuple
    all-to-all's components add up to the local buffer).  Async "-start"
    forms output a tuple carrying the OPERAND buffer(s) too —
    (operand, result, context...) — so summing would double-count; the
    largest component is the full transfer buffer for every async
    collective (result for all-gather, operand for reduce-scatter, either
    for all-reduce/permute), and `ring_wire_bytes` applies full-buffer
    formulas for starts."""
    comps = component_bytes(section)
    if not comps:
        return 0
    return max(comps) if is_start else sum(comps)


def first_group(line: str, default_world: int
                ) -> Tuple[int, Optional[Tuple[int, ...]]]:
    """(group size, first group's rank list when recoverable) of a
    collective instruction."""
    m = GROUPS_PAT.search(line)
    if m:
        first = m.group(1).split("}")[0].lstrip("{")
        ranks = tuple(int(t) for t in first.split(",") if t.strip())
        return max(len(ranks), 1), (ranks or None)
    m = IOTA_GROUPS_PAT.search(line)
    if m:  # iota form [num_groups, group_size]<=[world](T(perm))?
        g, s = int(m.group(1)), int(m.group(2))
        if m.group(3):  # transposed iota: group 0 strides by num_groups
            ranks = tuple(range(0, g * s, g))[:s]
        else:           # contiguous iota: group 0 = [0, s)
            ranks = tuple(range(s))
        return max(s, 1), ranks
    return max(default_world, 1), None


def ring_wire_bytes(op: str, payload: int, n: int, is_start: bool) -> float:
    """Per-participant ring wire bytes.  `payload` is the output-section
    payload (payload_bytes): for sync reduce-scatter that is the SHARD
    (output), for async starts it is the FULL buffer — hence the two
    reduce-scatter formulas."""
    if op == "collective-permute":
        # point-to-point: one hop, group size does not apply (the op
        # carries source_target_pairs, not replica_groups)
        return float(payload)
    if n <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (n - 1) / n * payload
    if op == "all-gather":
        return (n - 1) / n * payload
    if op == "reduce-scatter":
        if is_start:  # payload = full input buffer
            return (n - 1) / n * payload
        return float(n - 1) * payload  # payload = the output shard
    if op == "all-to-all":
        return (n - 1) / n * payload
    return 0.0


def maybe_collective(line: str
                     ) -> Optional[Tuple[str, bool, "re.Match"]]:
    """(base opcode, is_start, LINE_PAT match) when the line defines a
    collective that carries payload, else None ("-done" forms carry
    none).  The cheap substring prefilter runs before any regex work;
    the match rides along so callers read the payload group without a
    second LINE_PAT scan of the same line."""
    if ("all-" not in line and "reduce-scatter" not in line
            and "collective-permute" not in line):
        return None
    m = LINE_PAT.search(line)
    if m is None:
        return None
    op = m.group("op")
    if op.endswith("-done"):
        return None
    is_start = op.endswith("-start")
    base = op[:-6] if is_start else op
    if base not in COLLECTIVE_OPS:
        return None
    return base, is_start, m


def line_wire_bytes(line: str, default_world: int) -> float:
    """Ring wire bytes of one instruction line (0 for non-collectives)."""
    found = maybe_collective(line)
    if found is None:
        return 0.0
    base, is_start, m = found
    payload = payload_bytes(m.group("out"), is_start)
    n, _ranks = first_group(line, default_world)
    return ring_wire_bytes(base, payload, n, is_start)


# ---------------------------------------------------------------------------
# computation structure
# ---------------------------------------------------------------------------

def split_computations(txt: str) -> Dict[str, List[str]]:
    """HLO text -> {computation name: its instruction lines}.  Text with
    no computation headers (synthetic snippets) maps to one anonymous
    computation holding every line."""
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    loose: List[str] = []
    for line in txt.splitlines():
        m = COMP_HEAD_PAT.match(line)
        if m:
            cur = m.group(1)
            comps[cur] = []
            continue
        if line.strip() == "}":
            cur = None
            continue
        (comps[cur] if cur is not None else loose).append(line)
    if loose:
        comps[""] = loose
    return comps


def entry_computation(txt: str, comps: Optional[Dict[str, List[str]]] = None
                      ) -> str:
    """Name of the ENTRY computation (first computation as fallback for
    synthetic snippets without an ENTRY marker)."""
    m = ENTRY_PAT.search(txt)
    if m is not None:
        return m.group(1)
    if comps is None:
        comps = split_computations(txt)
    return next(iter(comps), "")


def cond_trip_count(lines: List[str]) -> Optional[int]:
    """Trip count from a while condition computation: the
    `compare(induction, constant), direction=LT` form lax.scan lowers to
    (0-based, unit step).  Non-zero-start loops (fori_loop(2, 10, ...))
    are safe too: XLA's while canonicalization rebases the induction to
    0 and folds the start into the bound BEFORE the post-optimization
    text this module parses (regression-pinned in test_comm).  None =
    not statically recoverable."""
    consts = {name: int(val)
              for name, val in (CONST_PAT.search(ln).groups()
                                for ln in lines if CONST_PAT.search(ln))}
    for ln in lines:
        cm = COMPARE_PAT.search(ln)
        if cm is None:
            continue
        dm = DIRECTION_PAT.search(ln)
        direction = dm.group(1) if dm else ""
        lhs, rhs = cm.group(1), cm.group(2)
        if direction == "LT" and rhs in consts:
            return consts[rhs]
        if direction == "GT" and lhs in consts:
            return consts[lhs]
    return None


def while_multipliers(comps: Dict[str, List[str]]
                      ) -> Dict[str, Tuple[int, bool]]:
    """{computation: (effective trip multiplier, dynamic?)} — body
    computations inherit their parent's multiplier times their while's
    trip count; nested whiles compose.  dynamic=True marks an enclosing
    while whose trip could not be resolved (multiplier stays 1 for it).
    Only while-body edges count — the bytes-on-wire accounting, where a
    collective inside a fusion is still top-level in its computation."""
    parent: Dict[str, Tuple[str, Optional[int]]] = {}
    for cname, lines in comps.items():
        for ln in lines:
            if " while(" not in ln and not WHILE_PAT.search(ln):
                continue
            bm = BODY_REF_PAT.search(ln)
            cm = COND_REF_PAT.search(ln)
            if bm is None:
                continue
            trip = None
            if cm is not None and cm.group(1) in comps:
                trip = cond_trip_count(comps[cm.group(1)])
            parent[bm.group(1)] = (cname, trip)

    memo: Dict[str, Tuple[int, bool]] = {}

    def mult(name: str, seen=()) -> Tuple[int, bool]:
        if name in memo:
            return memo[name]
        if name not in parent or name in seen:
            return (1, False)
        pname, trip = parent[name]
        pm, pdyn = mult(pname, seen + (name,))
        out = (pm * (trip if trip else 1), pdyn or trip is None)
        memo[name] = out
        return out

    return {name: mult(name) for name in comps}


def call_multipliers(comps: Dict[str, List[str]]
                     ) -> Dict[str, Tuple[float, bool]]:
    """{computation: (execution multiplier, dynamic?)} — like
    `while_multipliers` but following EVERY call edge (fusion `calls=`,
    `to_apply=`, conditional branches at x1; while bodies at their
    resolved trip count), so a dot inside a fusion inside a scanned
    layer still multiplies by the layer count — the profiler's
    accounting."""
    parent: Dict[str, Tuple[str, Optional[float]]] = {}
    for cname, lines in comps.items():
        for ln in lines:
            is_while = " while(" in ln
            trip: Optional[float] = 1.0
            if is_while:
                cm = COND_REF_PAT.search(ln)
                trip = None
                if cm is not None and cm.group(1) in comps:
                    t = cond_trip_count(comps[cm.group(1)])
                    trip = float(t) if t else None
            for m in CALLEE_PAT.finditer(ln):
                callee = m.group(1)
                if callee not in comps:
                    continue
                # while body multiplies by trip; its condition (and any
                # plain call/fusion) executes with the caller's cadence
                t = trip if (is_while and ln[m.start():m.start() + 4]
                             == "body") else 1.0
                # first caller wins; HLO computations have one caller
                parent.setdefault(callee, (cname, t))
            bm = BRANCH_PAT.search(ln)
            if bm:
                for callee in REF_PAT.findall(bm.group(1)):
                    if callee in comps:
                        parent.setdefault(callee, (cname, 1.0))

    memo: Dict[str, Tuple[float, bool]] = {}

    def mult(name: str, seen=()) -> Tuple[float, bool]:
        if name in memo:
            return memo[name]
        if name not in parent or name in seen:
            return (1.0, False)
        pname, trip = parent[name]
        pm, pdyn = mult(pname, seen + (name,))
        out = (pm * (trip if trip else 1.0), pdyn or trip is None)
        memo[name] = out
        return out

    return {name: mult(name) for name in comps}


# ---------------------------------------------------------------------------
# FLOPs
# ---------------------------------------------------------------------------

def dot_flops(line: str) -> float:
    """FLOPs of one `dot(...)` line: 2 * out_elems * contraction size,
    contraction parsed from the FIRST operand shape (inside the parens)
    and `lhs_contracting_dims`.  0.0 when not statically parseable."""
    om = OUT_PAT.search(line)
    if om is None:
        return 0.0
    out_elems = 0
    for dt, dims in SHAPE_PAT.findall(om.group(1)):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out_elems += n
    paren = line.find(" dot(")
    if paren < 0:
        return 0.0
    operands = line[paren + 5:]
    lhs = SHAPE_PAT.search(operands)
    cm = DOT_CONTRACT_PAT.search(line)
    if lhs is None or cm is None:
        return 0.0
    lhs_dims = [int(d) for d in lhs.group(2).split(",") if d]
    contract = 1
    for idx in cm.group(1).split(","):
        if idx and int(idx) < len(lhs_dims):
            contract *= lhs_dims[int(idx)]
    return 2.0 * out_elems * contract


# ---------------------------------------------------------------------------
# module contracts (donation / entry parameters) — the linter's surface
# ---------------------------------------------------------------------------

def alias_attribute_body(txt: str) -> Optional[str]:
    """The input_output_alias attribute's body (inside its outer
    braces), or None when the module declares no alias.  Extracted by
    brace balancing, NOT a line regex: TPU module headers put
    entry_computation_layout (with tiled layouts like `{1,0:T(8,128)}`)
    after the alias attribute on the same line, and a greedy or
    line-anchored match would capture far past the alias body —
    harvesting `T(8,` as a bogus donated parameter 8.  ONE extractor
    shared by `donated_parameters` and the donation lint's
    aliased-output scan so the two sides of the attribute can never
    parse differently."""
    marker = "input_output_alias={"
    start = txt.find(marker)
    if start < 0:
        return None
    i = start + len(marker)
    depth, j = 1, i
    while j < len(txt) and depth:
        c = txt[j]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
        j += 1
    return txt[i:j - 1]


def donated_parameters(txt: str) -> Tuple[bool, frozenset]:
    """(module declares input_output_alias?, donated entry-parameter
    numbers).  The attribute prints as
    `input_output_alias={ {0}: (0, {}, may-alias), {1}: (2, {}) }` —
    each value tuple leads with the parameter number."""
    body = alias_attribute_body(txt)
    if body is None:
        return False, frozenset()
    return True, frozenset(int(p) for p in ALIAS_ENTRY_PAT.findall(body))


def entry_parameters(lines: List[str]) -> List[Dict[str, object]]:
    """The entry computation's parameter buffers:
    [{"name", "number", "bytes", "line"}] in definition order."""
    out: List[Dict[str, object]] = []
    num_pat = re.compile(r'parameter\((\d+)\)')
    for i, ln in enumerate(lines):
        m = DEF_PAT.search(ln)
        if m is None or m.group(3) != "parameter":
            continue
        nm = num_pat.search(ln)
        out.append({"name": m.group(1), "number":
                    int(nm.group(1)) if nm else len(out),
                    "bytes": shape_bytes(m.group(2)), "line": i})
    return out
