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
* **operands** — a compiler prints an operand as `f32[8,32]{1,0} %x` or
  as `%x` alone (jax 0.9's XLA:CPU and the TPU compiler both leave the
  shape out); `call_operands` names them in order and `definitions`
  maps a name to the shape section of the line that DEFINES it, so a
  walker that needs an operand's shape finds it under either print;
* **FLOPs** — `dot_flops` prices one `dot(...)` line from its left
  operand's shape x `lhs_contracting_dims`;
* **identity** — `without_source_positions` takes out the tables of
  file / function / line / column that follow the module header and the
  `stack_frame_id` that points into them: two lowers of one program
  from two lines of a file differ in nothing else;
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
PARAM_PAT = re.compile(r'%([\w.\-]+)\s*=.*\sparameter\((\d+)\)')
DOT_CONTRACT_PAT = re.compile(r'lhs_contracting_dims=\{([0-9,]*)\}')
ALIAS_ENTRY_PAT = re.compile(r'\(\s*(\d+)\s*,')


#: the tables jax 0.9 appends under the module header, one numbered row a
#: line, and the id an instruction's metadata carries into them
SOURCE_TABLES_PAT = re.compile(
    r'^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n'
    r'(?:\d+ .*\n)*\n*', re.M)
FRAME_ID_PAT = re.compile(r' stack_frame_id=\d+')


def as_hlo_text(compiled_or_text) -> str:
    """The post-optimization HLO text of a compiled executable, or the
    argument itself when it is already text — every consumer's first
    line, so large modules stringify once per caller, not per helper."""
    return (compiled_or_text if isinstance(compiled_or_text, str)
            else compiled_or_text.as_text())


def without_source_positions(txt: str) -> str:
    """The text less WHERE in the source each instruction was lowered
    from — what "the same program" is compared on
    (`analysis.flag_identity.fingerprint`).  A traced module's text
    carries no positions and comes back as it went in."""
    return FRAME_ID_PAT.sub("", SOURCE_TABLES_PAT.sub("", txt))


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


def call_operands(line: str) -> List[str]:
    """Names of an instruction's operands, in order: the `%name`s between
    its opcode's parens (a tuple-typed operand printed with its shape
    nests parens of its own)."""
    m = LINE_PAT.search(line)
    if m is None:
        return []
    depth, j = 1, m.end()
    while j < len(line) and depth:
        depth += {"(": 1, ")": -1}.get(line[j], 0)
        j += 1
    return REF_PAT.findall(line[m.end():j - 1])


def definitions(comps: Dict[str, List[str]]) -> Dict[str, str]:
    """{instruction name: its output-shape section} over a module's
    computations, parameters included (a module names an instruction
    once) — where an operand printed by name alone has its shape."""
    defs: Dict[str, str] = {}
    for lines in comps.values():
        for ln in lines:
            m = DEF_PAT.search(ln)
            if m is not None:
                defs[m.group(1)] = m.group(2)
    return defs


def _compare(line: str, comps: Dict[str, List[str]]
             ) -> Optional[Tuple[str, str, str, Dict[str, int]]]:
    """(direction, lhs, rhs, constants defined beside the compare) of the
    comparison a condition's line makes: its own `compare(`, or the one
    inside the fusion it calls (XLA:CPU wraps a lone compare as
    `fusion(%i, %n), calls=%wrapped_compare_computation`), whose
    parameters are then named as the fusion's operands."""
    inner: List[str] = []
    if " fusion(" in line:
        callee = CALLEE_PAT.search(line)
        inner = comps.get(callee.group(1), []) if callee else []
    for ln in inner or [line]:
        cm = COMPARE_PAT.search(ln)
        if cm is None:
            continue
        outer = call_operands(line)
        names = {}
        for pl in inner:
            pm = PARAM_PAT.search(pl)
            if pm is not None and int(pm.group(2)) < len(outer):
                names[pm.group(1)] = outer[int(pm.group(2))]
        dm = DIRECTION_PAT.search(ln)
        return (dm.group(1) if dm else "",
                names.get(cm.group(1), cm.group(1)),
                names.get(cm.group(2), cm.group(2)), _constants(inner))
    return None


def _constants(lines: List[str]) -> Dict[str, int]:
    return {m.group(1): int(m.group(2))
            for m in map(CONST_PAT.search, lines) if m}


def cond_trip_count(lines: List[str],
                    comps: Optional[Dict[str, List[str]]] = None
                    ) -> Optional[int]:
    """Trip count from a while condition computation: the
    `compare(induction, constant), direction=LT` form lax.scan lowers to
    (0-based, unit step), made on the condition's own line or inside a
    fusion of `comps` that it calls.  Non-zero-start loops
    (fori_loop(2, 10, ...)) are safe too: XLA's while canonicalization
    rebases the induction to 0 and folds the start into the bound BEFORE
    the post-optimization text this module parses (regression-pinned in
    test_comm).  None = not statically recoverable."""
    consts = _constants(lines)
    for ln in lines:
        found = _compare(ln, comps or {})
        if found is None:
            continue
        direction, lhs, rhs, fused = found
        bound = {**consts, **fused}
        if direction == "LT" and rhs in bound:
            return bound[rhs]
        if direction == "GT" and lhs in bound:
            return bound[lhs]
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
                trip = cond_trip_count(comps[cm.group(1)], comps)
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
                    t = cond_trip_count(comps[cm.group(1)], comps)
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

def dot_flops(line: str, defs: Dict[str, str]) -> float:
    """FLOPs of one `dot(...)` line: 2 * out_elems * contraction size,
    the contraction from `lhs_contracting_dims` over the LEFT operand's
    shape: printed before its name inside the parens, or else the one
    `defs` (`definitions`) holds for that name.  0.0 when not statically
    parseable."""
    om = OUT_PAT.search(line)
    paren = line.find(" dot(")
    cm = DOT_CONTRACT_PAT.search(line)
    if om is None or paren < 0 or cm is None:
        return 0.0
    out_elems = 0
    for dt, dims in SHAPE_PAT.findall(om.group(1)):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out_elems += n
    operands = line[paren + 5:]
    name = REF_PAT.search(operands)
    lhs = SHAPE_PAT.search(operands[:name.start()] if name else operands)
    if lhs is None and name is not None:
        lhs = SHAPE_PAT.search(defs.get(name.group(1), ""))
    if lhs is None:
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
