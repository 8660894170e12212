"""From a profiler trace (`.xplane.pb`), the program's own spans, scopes
and counters to per-layer metrics.

`read_xplane` turns the file into plain lists of events; everything after
that is interval arithmetic on those lists, so it is tested on a small
recorded trace and on hand-made events alike (`benchmarks/tests`).

What is read, from outside the program: the benchmark's own spans around
`engine.step` and `train_step`, device time under the names the compiler
gave.  From inside it (PR 24):

* the host phase spans of `ServingEngine.step` (`serve.*`) and
  `Trainer.train_step` (`trainer.*`), which are TraceAnnotations on the
  device trace's clock.  They stand BEFORE the benchmark's own in
  `HOST_SPANS` (innermost wins), so `attribute_gaps` names the phase an
  idle gap fell in; the rules `span_self_ms` / `span_idle_ms` read them;
* device time by the program's own `jax.named_scope` names: an `XLA Ops`
  event belongs to the program whose `XLA Modules` execution contains it
  (instruction names repeat across programs), its `short_name` is looked
  up in that program's `hetu_tpu.obs.hlo_profile.scope_map`, and device
  time is summed per (group, pass, kind): `scope_table`, and the rules
  `scope_ms` / `scope_pct` / `scope_roofline_pct`.  `kind` is
  `collective` for an operation that `COLLECTIVE` names (the test
  `collective_exposed_ms_step` uses) and `compute` for every other: the
  partitioner's all-reduce carries the scope of what it reduces, so
  without the kind a layer's time on four chips would hold its
  communication and on one chip not;
* the program's `MetricsRegistry`: the loop snapshots it at the start and
  the end of the window, and the rule `counter` reads the difference of
  any counter by its name and labels (`counter_values`, `counter_key`).

A per-layer metric is a data file `benchmarks/metrics/<name>.json` whose
`reduce` names one of the rules in `RULES` and gives its parameters.  A
rule that finds nothing to read (a program that has no such span, counter
or scope) returns None, never an error, and the metric is left out of the
line.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

#: the two phases of an engine step in which the host waits for the device
SYNC_SPANS = ("serve.token_fetch", "serve.first_token")
#: the program's own spans, innermost first; all of `serve.*` nest in
#: `serve.step`, which nests in the benchmark's `engine.step`;
#: `trainer.*` in `train_step.dispatch`
PROGRAM_SPANS = SYNC_SPANS + (
    "serve.admit", "serve.prefill_chunk", "serve.page_write",
    "serve.decode_build", "serve.decode_dispatch", "serve.emit",
    "serve.housekeeping", "serve.step",
    "trainer.prepare_batch", "trainer.dispatch", "trainer.step")
#: every host span read, innermost first where they nest: the program's,
#: then the benchmark's own
HOST_SPANS = PROGRAM_SPANS + (
    "train_step.wait", "train_step.dispatch", "feed_batch", "engine.step",
    "submit")
WINDOW_START, WINDOW_END = "bench.window_start", "bench.window_end"
NO_SPAN = "_no_span_"
UNSCOPED = "unscoped"
COMPUTE, COLLECTIVE_KIND = "compute", "collective"

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast")


class Event(NamedTuple):
    name: str
    start: float      # seconds on the trace's clock
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


_HLO = re.compile(r"%?([\w.\-]+) = \(?([a-z]+\d*\[[\d,]*\])?")


def short_name(name: str) -> str:
    """The device lines name an event by the whole text of its HLO
    instruction, operands included.  Keep the instruction's own name and
    its (first) result shape: `fusion.222_bf16_2_4096_2_14336_`.  Matching
    on this cannot hit an instruction that merely consumes a kernel's
    output."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    return re.sub(r"[^\w.\-]", "_", m.group(1) + "_" + (m.group(2) or ""))


class Trace:
    """What one `.xplane.pb` holds that a rule reads.  `launches` is what
    `eager_dispatches` is given: (name, start, end) of the program's spans
    and of every `PjitFunction(...)` launch on the host planes."""

    def __init__(self, ops: Dict[str, List[Event]],
                 modules: Dict[str, List[Event]], host: List[Event],
                 launches: Sequence[Tuple[str, float, float]] = ()):
        self.ops = ops            # device plane -> leaf op events
        self.modules = modules    # device plane -> program executions
        self.host = host          # the benchmark's spans and marks
        self.launches = launches
        self._memo: dict = {}

    def memo(self, key, make):
        """`make()`, computed once for this trace under `key`."""
        if key not in self._memo:
            self._memo[key] = make()
        return self._memo[key]

    def busy(self, plane: str) -> List["Interval"]:
        """The intervals in which an operation ran on the plane, sorted
        and disjoint: every rule that asks where the device was idle reads
        this one list and cuts it to its window by bisection (`clip`)."""
        return self.memo(("busy", plane),
                         lambda: union(spans_of(self.ops[plane])))

    def counts(self) -> Dict[str, int]:
        return {"device_op_events": sum(map(len, self.ops.values())),
                "module_executions": sum(map(len, self.modules.values())),
                "host_events_kept": len(self.host) + len(self.launches)}


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------

def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def leaves(events: Sequence[Event]) -> List[Event]:
    """Events that contain no other event of the same line: a `while` or
    a `call` spans its body's operations, which are on the line too."""
    ev = sorted(events, key=lambda e: (e.start, -e.dur))
    out = []
    for i, e in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        if nxt is not None and nxt.start < e.end - 1e-12 and nxt.end <= e.end + 1e-9:
            continue                      # e contains nxt
        out.append(e)
    return out


def read_xplane(path: str) -> Trace:
    """Device planes are `/device:TPU:<n>`; on each, the line `XLA Ops`
    holds one event per executed HLO operation (named by `short_name`) and
    `XLA Modules` one per program execution.  Host planes hold the benchmark's
    `TraceAnnotation`s, the program's and the `PjitFunction(...)` launches
    among everything else; only those are kept.  The file is walked once,
    and an event's name is worked on once per distinct name: a 24-layer
    scan names ~800 operations and runs them hundreds of times."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    keep = set(HOST_SPANS) | {WINDOW_START, WINDOW_END}
    program = set(PROGRAM_SPANS)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    launches: List[Tuple[str, float, float]] = []
    short: Dict[str, str] = {}
    for plane in data.planes:
        pname = plane.name
        is_dev = re.fullmatch(r"/device:TPU:\d+", pname) is not None
        for line in plane.lines:
            if is_dev and line.name == "XLA Ops":
                evs = ops.setdefault(pname, [])
                for e in line.events:
                    raw = e.name
                    name = short.get(raw)
                    if name is None:
                        name = short[raw] = short_name(raw)
                    evs.append(Event(name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9))
            elif is_dev and line.name == "XLA Modules":
                modules.setdefault(pname, []).extend(
                    Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events)
            elif not is_dev and pname.startswith("/host:"):
                for e in line.events:
                    name = e.name
                    if name in keep:
                        host.append(Event(name, e.start_ns * 1e-9,
                                          e.duration_ns * 1e-9))
                    if name in program or name.startswith("PjitFunction("):
                        launches.append((name, e.start_ns * 1e-9,
                                         (e.start_ns + e.duration_ns) * 1e-9))
    ops = {p: leaves(v) for p, v in ops.items()}
    modules = {p: sorted(v, key=lambda e: e.start)
               for p, v in modules.items()}
    host.sort(key=lambda e: e.start)
    return Trace(ops, modules, host, launches)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

Interval = Tuple[float, float]
# `union` makes a list sorted and disjoint; `clip`, `subtract` and
# `gaps_in_spans` take such lists and give such lists, in one sweep.


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The part of a sorted, disjoint list inside [lo, hi]: its ends are
    found by bisection, and only the first and the last interval of it
    can reach over an edge."""
    i = bisect.bisect_right(intervals, lo, key=lambda iv: iv[1])
    j = bisect.bisect_left(intervals, hi, key=lambda iv: iv[0])
    if i >= j:
        return []
    out = list(intervals[i:j])
    out[0] = (max(out[0][0], lo), out[0][1])
    out[-1] = (out[-1][0], min(out[-1][1], hi))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps_in_spans(spans: Sequence[Interval], b: Sequence[Interval]):
    """(i, start, end) for every piece of `spans[i]` that `b` does not
    cover, in order.  `b` is sorted and disjoint; `spans` need only be in
    order of their starts (spans of one name do not overlap, but nothing
    here leans on it).  One pass over both: the pointer into `b` never
    goes back past an interval that ended before the current span began."""
    j, nb = 0, len(b)
    for i, (s, e) in enumerate(spans):
        while j < nb and b[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < nb and cur < e:
            bs, be = b[k]
            if bs >= e:
                break
            if bs > cur:
                yield i, cur, bs
            if be > cur:
                cur = be
            k += 1
        if cur < e:
            yield i, cur, e


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """The part of `a` that `b` does not cover; both sorted and disjoint
    (as `union` gives them), in O(|a| + |b|)."""
    return [(s, e) for _, s, e in gaps_in_spans(a, b)]


def spans_of(events: Sequence[Event]) -> List[Interval]:
    return [(e.start, e.end) for e in events]


def window_of(trace: Trace) -> Optional[Interval]:
    """[start of the `bench.window_start` mark, start of the
    `bench.window_end` mark]."""
    s = [e.start for e in trace.host if e.name == WINDOW_START]
    e = [e.start for e in trace.host if e.name == WINDOW_END]
    if not s or not e or e[-1] <= s[0]:
        return None
    return (s[0], e[-1])


def snap_to_modules(trace: Trace, window: Interval, pattern: str
                    ) -> Tuple[Optional[Interval], int]:
    """The window cut to whole executions of the program whose name
    matches (first device): from the start of the first one that begins
    inside to the start of the last one, and how many that is.  A train
    step runs a step behind the host's marks, so without this the edges
    would hold parts of steps."""
    if not trace.modules:
        return None, 0
    rx = re.compile(pattern)
    starts = [e.start for e in trace.modules[sorted(trace.modules)[0]]
              if rx.search(e.name) and window[0] <= e.start <= window[1]]
    if len(starts) < 2:
        return None, 0
    return (starts[0], starts[-1]), len(starts) - 1


def busy_seconds(trace: Trace, window: Interval) -> Optional[float]:
    """Seconds in which an operation ran on the device, averaged over the
    device planes."""
    if not trace.ops:
        return None
    per = [total(clip(trace.busy(plane), *window)) for plane in trace.ops]
    return sum(per) / len(per)


def attribute_gaps(trace: Trace, window: Interval) -> List[Tuple[str, float]]:
    """Idle time of the first device plane, split by which of the
    benchmark's host spans covered it (a span listed earlier in
    `HOST_SPANS` wins where two overlap); the rest is `_no_span_`."""
    if not trace.ops:
        return []
    idle = subtract([window], clip(trace.busy(sorted(trace.ops)[0]), *window))
    left = total(idle)
    by_name: Dict[str, List[Interval]] = {}
    for e in trace.host:
        by_name.setdefault(e.name, []).append((e.start, e.end))
    out = []
    for name in HOST_SPANS:
        cover = clip(union(by_name.get(name, ())), *window)
        if not cover:
            continue
        idle = subtract(idle, cover)
        rest = total(idle)
        inside, left = left - rest, rest
        if inside > 0:
            out.append((name, inside))
    if left > 0:
        out.append((NO_SPAN, left))
    return sorted(out, key=lambda kv: -kv[1])


def top_device_ops(trace: Trace, window: Interval, n: int = 10
                   ) -> List[Tuple[str, float]]:
    """The operations of the first device plane that took most time in
    the window, by summed duration under the trace's own names."""
    if not trace.ops:
        return []
    sums: Dict[str, float] = {}
    for name, start, dur in trace.ops[sorted(trace.ops)[0]]:
        if start >= window[0] and start + dur <= window[1]:
            sums[name] = sums.get(name, 0.0) + dur
    return sorted(sums.items(), key=lambda kv: -kv[1])[:n]


def _named(pattern: str):
    """name -> whether the pattern is found in it, asked of the pattern
    once per distinct name: the events of a trace are many, their names
    few."""
    rx, known = re.compile(pattern), {}

    def hit(name):
        if name not in known:
            known[name] = rx.search(name) is not None
        return known[name]
    return hit


def _matching(events, pattern, window):
    hit = _named(pattern)
    return [e for e in events if e[1] >= window[0]
            and e[1] + e[2] <= window[1] and hit(e[0])]


# ---------------------------------------------------------------------------
# host phase spans
# ---------------------------------------------------------------------------

def _spans(trace: Trace, names: Sequence[str], window: Interval
           ) -> List[Event]:
    return [e for e in trace.host if e.name in names
            and e.start >= window[0] and e.end <= window[1]]


def rule_span_self_ms(p, trace, window, ctx):
    """Mean over the spans named `span` of the span's duration minus the
    spans named in `minus` that lie inside it: with the two sync phases
    taken off `serve.step`, the Python time a step costs, hidden behind
    the device or not."""
    outer = _spans(trace, [p["span"]], window) if trace else []
    if not outer:
        return None
    own = subtract(
        union(spans_of(outer)),
        union(spans_of(_spans(trace, p.get("minus", ()), window))))
    return 1e3 * total(own) / len(outer)


def rule_span_idle_ms(p, trace, window, ctx):
    """Device idle (first device plane) inside the spans named `span`,
    per span: with `only`, just the part inside the spans it names (the
    device is done and the host has not yet got its answer); with
    `exclude`, the part outside them (idle the host causes while it
    works)."""
    if not trace or not trace.ops:
        return None
    outer = _spans(trace, [p["span"]], window)
    if not outer:
        return None
    plane = sorted(trace.ops)[0]
    idle = trace.memo(("idle_in", p["span"], window), lambda: subtract(
        union(spans_of(outer)), trace.busy(plane)))
    if "only" in p:
        inner = union(spans_of(_spans(trace, p["only"], window)))
        idle = subtract(idle, subtract(idle, inner))
    if "exclude" in p:
        idle = subtract(
            idle, union(spans_of(_spans(trace, p["exclude"], window))))
    return 1e3 * total(idle) / len(outer)


def idle_position_ms(trace: Trace, window: Interval, span: str
                     ) -> Optional[Dict[str, float]]:
    """Where in the spans named `span` the device's idle lies, ms per
    span: `head` from the span's start to the first operation (what was
    dispatched has not started), `tail` from the last operation to the
    span's end (the device is done, the host does not know yet),
    `middle` between operations."""
    if not trace or not trace.ops:
        return None
    outer = _spans(trace, [span], window)
    if not outer:
        return None
    out = {"head": 0.0, "middle": 0.0, "tail": 0.0}
    for i, s, t in gaps_in_spans(spans_of(outer),
                                 trace.busy(sorted(trace.ops)[0])):
        e = outer[i]
        where = ("head" if s <= e.start + 1e-9 else
                 "tail" if t >= e.end - 1e-9 else "middle")
        out[where] += t - s
    return {k: 1e3 * v / len(outer) for k, v in out.items()}


# ---------------------------------------------------------------------------
# device time by scope
# ---------------------------------------------------------------------------

_LAYER = re.compile(r"^layer(_\d+)?$")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+ = .*)$")
_INSTR_NAME = re.compile(r"%([\w.\-]+) = ")


def program_name(module_event_name: str) -> str:
    """`jit_decode_fn(729022835887181817)` -> `jit_decode_fn`."""
    return module_event_name.split("(", 1)[0]


def scope_index(hlo_text: str) -> Optional[Tuple[str, Dict[str, Tuple[str, str]]]]:
    """(module name, {trace.short_name of an instruction: (group, pass)})
    for one compiled program's text, or None where the program has no
    `scope_map` (the parent of PR 24).  The key is computed from the
    text's own instruction line by the function that names the trace's
    events, so the two sides cannot drift apart."""
    try:
        from hetu_tpu.obs.hlo_profile import scope_map
    except ImportError:
        return None
    head = _MODULE.search(hlo_text)
    if head is None:
        return None
    by_name = scope_map(hlo_text)
    index = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name = _INSTR_NAME.match(m.group(1)).group(1)
        if name in by_name:
            index[short_name(m.group(1))] = by_name[name]
    return head.group(1), index


def phase_of(group: str) -> str:
    """`layer/attn/pallas_flash_attention` -> `attn`; `layer_3/mlp` ->
    `mlp`; `optimizer/pallas_adam` -> `optimizer`; `layer` (a scan's own
    slicing and stacking of its operands) -> `layer`."""
    segs = group.split("/")
    named = [s for s in segs
             if not _LAYER.match(s) and not s.startswith("pallas_")]
    return named[0] if named else segs[0]


def kernel_of(group: str) -> Optional[str]:
    return next((s for s in group.split("/") if s.startswith("pallas_")),
                None)


def scope_table(trace: Trace, window: Interval, ctx: dict) -> Optional[dict]:
    """{program: {"executions": n, "device_s": all its operations' time,
    "rows": {(group, pass, kind): seconds}, "ops": {group: {name:
    seconds}}}} over the first device plane's program executions that
    lie wholly in the window, each operation given to the execution that
    contains it (instruction names repeat across programs); `kind` tells
    a collective operation from computation.  Programs with
    no text in `ctx["hlo_texts"]` (eager one-operation programs) get one
    `unscoped` row.  Cached in `ctx`; on the first build the table goes to
    `ctx["emit"]`, if there is one, as the run's `scopes` line."""
    if "scope_table" in ctx:
        return ctx["scope_table"]
    if not trace or not trace.ops or not trace.modules:
        return None
    indexes = dict(filter(None, map(scope_index,
                                    ctx.get("hlo_texts") or ())))
    if not indexes:
        return None
    plane = sorted(trace.ops)[0]
    mods = sorted(trace.modules.get(plane, ()), key=lambda e: e.start)
    starts = [m.start for m in mods]
    table: Dict[str, dict] = {}
    for m in mods:
        if window[0] <= m.start and m.end <= window[1]:
            table.setdefault(program_name(m.name), {
                "executions": 0, "device_s": 0.0, "rows": {},
                "ops": {}})["executions"] += 1
    keys: Dict[Tuple[str, str], tuple] = {}   # (program, op) -> its row
    # the program of each execution, or None where the window cuts it
    progs = [program_name(m.name) if window[0] <= m.start
             and m.end <= window[1] else None for m in mods]
    ends = [m.end + 1e-9 for m in mods]
    for name, start, dur in trace.ops[plane]:
        if start < window[0] or start + dur > window[1]:
            continue
        i = bisect.bisect_right(starts, start + 1e-12) - 1
        if i < 0 or start + dur > ends[i]:
            prog = "_no_program_"
        else:
            prog = progs[i]
            if prog is None:
                continue
        rec = table.get(prog)
        if rec is None:
            rec = table[prog] = {"executions": 0, "device_s": 0.0,
                                 "rows": {}, "ops": {}}
        key = keys.get((prog, name))
        if key is None:
            key = keys[prog, name] = indexes.get(prog, {}).get(
                name, (UNSCOPED, "fwd")) + (
                COLLECTIVE_KIND if COLLECTIVE.search(name) else COMPUTE,)
        rec["device_s"] += dur
        rec["rows"][key] = rec["rows"].get(key, 0.0) + dur
        ops = rec["ops"].setdefault(key[0], {})
        ops[name] = ops.get(name, 0.0) + dur
    ctx["scope_table"] = table
    if ctx.get("emit"):
        ctx["emit"](phase="scopes", programs=printable(table))
    return table


def printable(table: dict) -> dict:
    """The table as JSON: per program its executions, device ms per
    execution, every (group, pass, kind) row as ms per execution and share,
    the eight largest operations of each group that names no phase
    (`unscoped`, and `layer`: a scan's own slicing and stacking), and
    what `unscoped` holds by kind of operation."""
    out = {}
    for prog, rec in sorted(table.items(),
                            key=lambda kv: -kv[1]["device_s"]):
        n = max(rec["executions"], 1)
        dev = max(rec["device_s"], 1e-30)
        out[prog] = {
            "executions": rec["executions"],
            "device_ms_per_execution": 1e3 * rec["device_s"] / n,
            "rows": [{"group": g, "pass": ps, "kind": kind,
                      "ms": 1e3 * s / n, "pct": 100.0 * s / dev}
                     for (g, ps, kind), s in sorted(
                         rec["rows"].items(), key=lambda kv: -kv[1])],
            "top_ops": {
                group: [[k, 1e3 * v / n] for k, v in sorted(
                    ops.items(), key=lambda kv: -kv[1])[:8]]
                for group, ops in rec["ops"].items()
                if phase_of(group) in (UNSCOPED, "layer")},
            "unscoped_by_opcode": by_opcode(
                rec["ops"].get(UNSCOPED, {}), n)}
    return out


_OPCODE = re.compile(r"^(.*?)(?:\.\d+)?_(?:(?:[a-z]+\d+|pred)_.*)?$")


def by_opcode(ops: Dict[str, float], n: int) -> List[list]:
    """[[kind, ms per execution]], the ten largest: `all-gather.172_bf16_..`
    and `all-gather.169_bf16_..` are both `all-gather`, and so is a
    tuple-shaped `slice-start.51_`, whose short name has no shape."""
    sums: Dict[str, float] = {}
    for name, s in ops.items():
        m = _OPCODE.match(name)
        kind = m.group(1) if m else name
        sums[kind] = sums.get(kind, 0.0) + s
    return [[k, 1e3 * v / n] for k, v in sorted(
        sums.items(), key=lambda kv: -kv[1])[:10]]


def _selected(rec: dict, p: dict) -> Optional[float]:
    """Seconds of the rows of one program that `p` selects: by `phase`
    (a list), `kernel`, `pass`, `kind`, or `group` (exact); None where
    the program has no such row (one chip's step has no collective)."""
    total = None
    for (group, ps, kind), s in rec["rows"].items():
        if "kind" in p and kind != p["kind"]:
            continue
        if "phase" in p and phase_of(group) not in p["phase"]:
            continue
        if "kernel" in p and kernel_of(group) != p["kernel"]:
            continue
        if "pass" in p and ps != p["pass"]:
            continue
        if "group" in p and group != p["group"]:
            continue
        total = (total or 0.0) + s
    return total


def _program(table: dict, pattern: str) -> Optional[dict]:
    rx = re.compile(pattern)
    hit = [rec for name, rec in table.items()
           if rx.search(name) and rec["executions"]]
    return hit[0] if len(hit) == 1 else None


def rule_scope_ms(p, trace, window, ctx):
    """Device ms of the selected scopes of the program matching
    `program`, per execution of it (the window of a train cell is cut to
    whole executions of the step program, so that is per step)."""
    table = scope_table(trace, window, ctx)
    rec = _program(table, p["program"]) if table else None
    hit = _selected(rec, p) if rec else None
    return None if hit is None else 1e3 * hit / rec["executions"]


def rule_scope_pct(p, trace, window, ctx):
    """The selected scopes' share of the program's device time."""
    table = scope_table(trace, window, ctx)
    rec = _program(table, p["program"]) if table else None
    hit = _selected(rec, p) if rec and rec["device_s"] else None
    return None if hit is None else 100.0 * hit / rec["device_s"]


def eager_dispatches(host_events, window: Interval) -> Dict[str, Dict[str, int]]:
    """{program span: {`PjitFunction(<name>)`: launches inside it}}:
    which line of a step issues the one-operation programs a trace shows
    beside the engine's three.  `host_events` is `Trace.launches`.  A
    launch is recorded at two depths; the inner one is not counted again.
    Where several spans hold a launch, the first of them in the list
    names it.  Launches and spans are each walked once, in order of their
    starts: a span that ended before a launch began holds no later one."""
    skip = ("serve.step", "trainer.step")
    spans = sorted(((s, e, i, n) for i, (n, s, e) in enumerate(host_events)
                    if n in PROGRAM_SPANS and n not in skip))
    launches = sorted(((n, s, e) for n, s, e in host_events
                       if n.startswith("PjitFunction(")
                       and s >= window[0] and e <= window[1]),
                      key=lambda x: (x[1], -x[2]))
    out: Dict[str, Dict[str, int]] = {}
    last: Dict[str, Tuple[float, float]] = {}
    opened: List[tuple] = []       # spans begun and not yet ended
    nxt = 0
    for n, s, e in launches:
        if n in last and last[n][0] <= s and e <= last[n][1]:
            continue
        last[n] = (s, e)
        while nxt < len(spans) and spans[nxt][0] <= s:
            opened.append(spans[nxt])
            nxt += 1
        opened = [sp for sp in opened if sp[1] >= s]
        holding = [(i, sn) for ss, se, i, sn in opened if e <= se]
        where = min(holding)[1] if holding else NO_SPAN
        out.setdefault(where, {})
        out[where][n] = out[where].get(n, 0) + 1
    return out


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------
# rule(params, trace, window, ctx) -> float | None.  `ctx` is what the
# runner's loop hands over: {"steps": engine or train steps inside the
# traced window, "counters": the loop's own values by name, "registry":
# the differences of the program's counters over the window
# (`counter_values`), "family": the configuration's family module,
# "config", "window_counts": what the cost functions are given, "peaks",
# "hlo_texts", "emit"}.

def counter_key(name: str, labels: Optional[dict] = None) -> str:
    """`serve.admission_stalls{reason=pages}`; the bare name stands for
    the sum over every label set of that name."""
    if not labels:
        return name
    return name + "{" + ",".join(
        f"{k}={v}" for k, v in sorted(labels.items())) + "}"


def counter_values(snapshot: dict) -> Dict[str, float]:
    """Every counter of a `MetricsRegistry.snapshot()`, flat: each series
    under `counter_key(name, labels)` and each name's sum under the name."""
    out: Dict[str, float] = {}
    for c in snapshot["counters"]:
        if c["labels"]:
            out[counter_key(c["name"], c["labels"])] = c["value"]
        out[c["name"]] = out.get(c["name"], 0.0) + c["value"]
    return out


def counter_diff(start: Dict[str, float], end: Dict[str, float]
                 ) -> Dict[str, float]:
    """What each counter of `end` counted since `start` (a counter that
    did not exist then counted from 0)."""
    return {k: v - start.get(k, 0.0) for k, v in end.items()}


def _count(ctx, name, labels=None):
    """A count of the window by name: one the loop keeps itself
    (`ctx["counters"]`), else the program's counter of that name (and
    labels) over the window (`ctx["registry"]`)."""
    if not labels and name in ctx.get("counters", {}):
        return ctx["counters"][name]
    return ctx.get("registry", {}).get(counter_key(name, labels))


def rule_counter(p, trace, window, ctx):
    """`counter` (with `labels`, that series alone), over the sum of the
    counts named in `over` (a name or a list), times `scale`.  Under
    `over` a counter the program never touched counts 0; a ratio over
    nothing, or a lone counter that is not there, is nothing to read."""
    value = _count(ctx, p["counter"], p.get("labels"))
    if "over" in p:
        names = [p["over"]] if isinstance(p["over"], str) else p["over"]
        over = sum(_count(ctx, n) or 0.0 for n in names)
        if not over:
            return None
        value = (value or 0.0) / over
    return None if value is None else p.get("scale", 1.0) * value


def rule_idle_pct(p, trace, window, ctx):
    busy = busy_seconds(trace, window) if trace else None
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / (window[1] - window[0]))


def rule_busy_ms_per_step(p, trace, window, ctx):
    busy = busy_seconds(trace, window) if trace else None
    if busy is None or not ctx.get("steps"):
        return None
    return 1e3 * busy / ctx["steps"]


def rule_module_median_ms(p, trace, window, ctx):
    """Sum over `match` patterns of the median device time of the program
    executions whose name matches."""
    if not trace or not trace.modules:
        return None
    evs = trace.modules[sorted(trace.modules)[0]]
    out = 0.0
    for pattern in p["match"]:
        hit = _matching(evs, pattern, window)
        if not hit:
            return None
        out += statistics.median(e.dur for e in hit)
    return 1e3 * out


def rule_module_gap_median_ms(p, trace, window, ctx):
    """Median device gap between consecutive executions of the programs
    whose name matches."""
    if not trace or not trace.modules:
        return None
    hit = _matching(trace.modules[sorted(trace.modules)[0]], p["match"],
                    window)
    gaps = [b.start - a.end for a, b in zip(hit, hit[1:])]
    return 1e3 * statistics.median(gaps) if gaps else None


def rule_host_self_ms(p, trace, window, ctx):
    """Mean over the host spans named `span` of the span's duration minus
    the time the device was busy inside it."""
    if not trace or not trace.ops:
        return None
    spans = [e for e in trace.host if e.name == p["span"]
             and e.start >= window[0] and e.end <= window[1]]
    if not spans:
        return None
    self_s = total(subtract(union(spans_of(spans)),
                            trace.busy(sorted(trace.ops)[0])))
    return 1e3 * self_s / len(spans)


def rule_roofline_pct(p, trace, window, ctx):
    """Least time the chip could take for the required operations and
    bytes of the kernel's calls in the window (the function `cost` of the
    configuration's family module, given the configuration and the traced
    window's counts) over the summed device time of the events whose name
    matches, on the first device."""
    from benchmarks import peaks
    if not trace or not trace.ops:
        return None
    cost_fn = getattr(ctx["family"], p["cost"], None)
    if cost_fn is None:
        raise KeyError(f"cost function {p['cost']!r} is not in "
                       f"{ctx['family'].__name__}")
    hit = _matching(trace.ops[sorted(trace.ops)[0]], p["match"], window)
    cost = cost_fn(ctx["config"], ctx["window_counts"]) if hit else None
    if not cost:
        return None
    least = peaks.roofline_seconds(cost, ctx["peaks"])
    ctx.setdefault("notes", {})[p["cost"]] = dict(
        least, events=len(hit), device_s=sum(e.dur for e in hit), **cost)
    return 100.0 * least["seconds"] / sum(e.dur for e in hit)


def rule_scope_roofline_pct(p, trace, window, ctx):
    """`roofline_pct` for a computation that is an XLA composition: least
    time for the cost function's operations and bytes over the device
    time of the selected scopes (`rule_scope_ms`'s selection: `program`
    and `phase` / `group`) of the program's executions in the window.
    None where the program has no such scope (the parent) or the
    function finds nothing counted."""
    from benchmarks import peaks
    table = scope_table(trace, window, ctx)
    rec = _program(table, p["program"]) if table else None
    device_s = _selected(rec, p) if rec else None
    cost_fn = getattr(ctx["family"], p["cost"], None)
    if not device_s or cost_fn is None:
        return None
    cost = cost_fn(ctx["config"], ctx["window_counts"])
    if not cost:
        return None
    least = peaks.roofline_seconds(cost, ctx["peaks"])
    ctx.setdefault("notes", {})[p["cost"]] = dict(
        least, executions=rec["executions"], device_s=device_s, **cost)
    return 100.0 * least["seconds"] / device_s


def _collective_spans(trace, window):
    """[(seconds of collective operations, seconds of them during which
    no other operation ran)] per device plane; made once per window, both
    metrics of it read it."""
    def make():
        per, is_coll = [], _named(COLLECTIVE.pattern)
        for evs in trace.ops.values():
            coll = [e for e in evs if is_coll(e.name)]
            rest = [e for e in evs if not is_coll(e.name)]
            c = clip(union(spans_of(coll)), *window)
            per.append((total(c),
                        total(subtract(c, union(spans_of(rest))))))
        return per
    return trace.memo(("collective", window), make)


def rule_collective_ms_per_step(p, trace, window, ctx):
    """Device time of collective operations per step, averaged over the
    devices; with `exposed`, only the part during which no other
    operation ran on that device."""
    if not trace or not trace.ops or not ctx.get("steps"):
        return None
    per = _collective_spans(trace, window)
    idx = 1 if p.get("exposed") else 0
    return 1e3 * sum(x[idx] for x in per) / len(per) / ctx["steps"]




RULES = {
    "counter": rule_counter,
    "idle_pct": rule_idle_pct,
    "busy_ms_per_step": rule_busy_ms_per_step,
    "module_median_ms": rule_module_median_ms,
    "module_gap_median_ms": rule_module_gap_median_ms,
    "host_self_ms": rule_host_self_ms,
    "roofline_pct": rule_roofline_pct,
    "collective_ms_per_step": rule_collective_ms_per_step,
    "span_self_ms": rule_span_self_ms,
    "span_idle_ms": rule_span_idle_ms,
    "scope_ms": rule_scope_ms,
    "scope_pct": rule_scope_pct,
    "scope_roofline_pct": rule_scope_roofline_pct,
}


def reduce_metric(spec: dict, trace: Optional[Trace],
                  window: Optional[Interval], ctx: dict) -> Optional[float]:
    red = spec["reduce"]
    if red["rule"] not in RULES:
        raise KeyError(f"metric {spec.get('name')}: unknown rule "
                       f"{red['rule']!r}; known: {sorted(RULES)}")
    if red["rule"] != "counter" and (trace is None or window is None):
        return None
    value = RULES[red["rule"]](red, trace, window, ctx)
    return None if value is None else float(value)
