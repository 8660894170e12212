"""Per-layer readings taken from INSIDE the program (PR 24).

`trace.py` reads what the benchmark can see from outside: its own spans
around `engine.step` and `train_step`, device time under the names the
compiler gave.  This module reads what the program now says itself:

* the host phase spans of `ServingEngine.step` (`serve.*`) and
  `Trainer.train_step` (`trainer.*`), which are TraceAnnotations on the
  device trace's clock: `PROGRAM_SPANS`, to be put BEFORE
  `trace.HOST_SPANS` (innermost wins) so that `attribute_gaps` names the
  phase an idle gap fell in, and the rules `span_self_ms` /
  `span_idle_ms`;
* device time by the program's own `jax.named_scope` names: an `XLA Ops`
  event belongs to the program whose `XLA Modules` execution contains it
  (instruction names repeat across programs), its `short_name` is looked
  up in that program's `hetu_tpu.obs.hlo_profile.scope_map`, and device
  time is summed per (group, pass, kind): `scope_table`, and the rules
  `scope_ms` / `scope_pct`.  `kind` is `collective` for an operation
  that `trace.COLLECTIVE` names (the test `collective_ms_step` uses) and
  `compute` for every other: the partitioner's all-reduce carries the
  scope of what it reduces, so without the kind a layer's time on four
  chips would hold its communication and on one chip not.

The rules have `trace.RULES`' signature.  Nothing here is registered by
`run.py` yet: `run_inside.py` is the entry point that registers them (see
its docstring for why), and `inside_metrics.json` the entries a later
`benchmark` PR moves into `BENCHMARK.json`.  A program that has no such
span, counter or scope gives None, never an error.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks import trace as trace_mod
from benchmarks.trace import Interval, Trace

#: the two phases of an engine step in which the host waits for the device
SYNC_SPANS = ("serve.token_fetch", "serve.first_token")
#: innermost first; all of `serve.*` nest in `serve.step`, which nests in
#: the benchmark's `engine.step`; `trainer.*` in `train_step.dispatch`
PROGRAM_SPANS = SYNC_SPANS + (
    "serve.admit", "serve.prefill_chunk", "serve.page_write",
    "serve.decode_build", "serve.decode_dispatch", "serve.emit",
    "serve.housekeeping", "serve.step",
    "trainer.prepare_batch", "trainer.dispatch", "trainer.step")
UNSCOPED = "unscoped"
COMPUTE, COLLECTIVE = "compute", "collective"

_LAYER = re.compile(r"^layer(_\d+)?$")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+ = .*)$")
_INSTR_NAME = re.compile(r"%([\w.\-]+) = ")


# ---------------------------------------------------------------------------
# host phase spans
# ---------------------------------------------------------------------------

def _spans(trace: Trace, names: Sequence[str], window: Interval
           ) -> List[trace_mod.Event]:
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
    own = trace_mod.subtract(
        trace_mod.spans_of(outer),
        trace_mod.spans_of(_spans(trace, p.get("minus", ()), window)))
    return 1e3 * trace_mod.total(own) / len(outer)


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
    busy = trace_mod.spans_of(trace.ops[sorted(trace.ops)[0]])
    idle = trace_mod.subtract(trace_mod.spans_of(outer), busy)
    if "only" in p:
        inner = trace_mod.spans_of(_spans(trace, p["only"], window))
        idle = trace_mod.subtract(idle, trace_mod.subtract(idle, inner))
    if "exclude" in p:
        idle = trace_mod.subtract(
            idle, trace_mod.spans_of(_spans(trace, p["exclude"], window)))
    return 1e3 * trace_mod.total(idle) / len(outer)


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
    busy = trace_mod.union(
        trace_mod.spans_of(trace.ops[sorted(trace.ops)[0]]))
    out = {"head": 0.0, "middle": 0.0, "tail": 0.0}
    for e in outer:
        for s, t in trace_mod.subtract([(e.start, e.end)], busy):
            where = ("head" if s <= e.start + 1e-9 else
                     "tail" if t >= e.end - 1e-9 else "middle")
            out[where] += t - s
    return {k: 1e3 * v / len(outer) for k, v in out.items()}


# ---------------------------------------------------------------------------
# device time by scope
# ---------------------------------------------------------------------------

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
            index[trace_mod.short_name(m.group(1))] = by_name[name]
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
    for op in trace.ops[plane]:
        if op.start < window[0] or op.end > window[1]:
            continue
        i = bisect.bisect_right(starts, op.start + 1e-12) - 1
        if i < 0 or op.end > mods[i].end + 1e-9:
            prog = "_no_program_"
        elif mods[i].start < window[0] or mods[i].end > window[1]:
            continue        # an execution cut by the window's edge
        else:
            prog = program_name(mods[i].name)
        rec = table.setdefault(prog, {"executions": 0, "device_s": 0.0,
                                      "rows": {}, "ops": {}})
        key = indexes.get(prog, {}).get(op.name, (UNSCOPED, "fwd")) + (
            COLLECTIVE if trace_mod.COLLECTIVE.search(op.name) else COMPUTE,)
        rec["device_s"] += op.dur
        rec["rows"][key] = rec["rows"].get(key, 0.0) + op.dur
        ops = rec["ops"].setdefault(key[0], {})
        ops[op.name] = ops.get(op.name, 0.0) + op.dur
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


def read_host_events(path: str) -> List[Tuple[str, float, float]]:
    """(name, start, end) in seconds of the host planes' program spans
    and `PjitFunction(...)` launch events of an `.xplane.pb` file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in PROGRAM_SPANS or \
                        e.name.startswith("PjitFunction("):
                    out.append((e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9))
    return out


def eager_dispatches(host_events, window: Interval) -> Dict[str, Dict[str, int]]:
    """{program span: {`PjitFunction(<name>)`: launches inside it}}:
    which line of a step issues the one-operation programs a trace shows
    beside the engine's three.  A launch is recorded at two depths; the
    inner one is not counted again."""
    spans = [(n, s, e) for n, s, e in host_events
             if n in PROGRAM_SPANS and n not in ("serve.step",
                                                 "trainer.step")]
    out: Dict[str, Dict[str, int]] = {}
    last: Dict[str, Tuple[float, float]] = {}
    for n, s, e in sorted(host_events, key=lambda x: (x[1], -x[2])):
        if not n.startswith("PjitFunction(") or s < window[0] \
                or e > window[1]:
            continue
        if n in last and last[n][0] <= s and e <= last[n][1]:
            continue
        last[n] = (s, e)
        where = next((sn for sn, ss, se in spans if ss <= s and e <= se),
                     trace_mod.NO_SPAN)
        out.setdefault(where, {})
        out[where][n] = out[where].get(n, 0) + 1
    return out


RULES = {
    "span_self_ms": rule_span_self_ms,
    "span_idle_ms": rule_span_idle_ms,
    "scope_ms": rule_scope_ms,
    "scope_pct": rule_scope_pct,
}
