"""From a profiler trace (`.xplane.pb`) to per-layer metrics.

`read_xplane` turns the file into plain lists of events; everything after
that is interval arithmetic on those lists, so it is tested on a small
recorded trace and on hand-made events alike
(`benchmarks/tests/test_trace.py`).

A per-layer metric is a data file `benchmarks/metrics/<name>.json` whose
`reduce` names one of the rules in `RULES` and gives its parameters.  A
rule that finds nothing to read returns None and the metric is left out
of the line.
"""
from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

#: the benchmark's own host spans, innermost first where they nest
HOST_SPANS = ("train_step.wait", "train_step.dispatch", "feed_batch",
              "engine.step", "submit")
WINDOW_START, WINDOW_END = "bench.window_start", "bench.window_end"
NO_SPAN = "_no_span_"

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


class Trace(NamedTuple):
    ops: Dict[str, List[Event]]       # device plane -> leaf op events
    modules: Dict[str, List[Event]]   # device plane -> program executions
    host: List[Event]                 # the benchmark's spans and marks


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
    `TraceAnnotation`s among everything else; only those are kept."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    keep = set(HOST_SPANS) | {WINDOW_START, WINDOW_END}
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        pname = plane.name
        is_dev = re.fullmatch(r"/device:TPU:\d+", pname) is not None
        for line in plane.lines:
            if is_dev and line.name in ("XLA Ops", "XLA Modules"):
                short = short_name if line.name == "XLA Ops" else str
                evs = [Event(short(e.name), e.start_ns * 1e-9,
                             e.duration_ns * 1e-9) for e in line.events]
                (ops if line.name == "XLA Ops" else modules).setdefault(
                    pname, []).extend(evs)
            elif not is_dev and pname.startswith("/host:"):
                for e in line.events:
                    if e.name in keep:
                        host.append(Event(e.name, e.start_ns * 1e-9,
                                          e.duration_ns * 1e-9))
    ops = {p: leaves(v) for p, v in ops.items()}
    modules = {p: sorted(v, key=lambda e: e.start)
               for p, v in modules.items()}
    host.sort(key=lambda e: e.start)
    return Trace(ops, modules, host)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

Interval = Tuple[float, float]


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
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """The part of union(a) that union(b) does not cover."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


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
    per = [total(clip(union(spans_of(evs)), *window))
           for evs in trace.ops.values()]
    return sum(per) / len(per)


def attribute_gaps(trace: Trace, window: Interval) -> List[Tuple[str, float]]:
    """Idle time of the first device plane, split by which of the
    benchmark's host spans covered it (a span listed earlier in
    `HOST_SPANS` wins where two overlap); the rest is `_no_span_`."""
    if not trace.ops:
        return []
    plane = sorted(trace.ops)[0]
    idle = subtract([window], spans_of(trace.ops[plane]))
    out = []
    for name in HOST_SPANS:
        cover = clip(union(spans_of([e for e in trace.host
                                     if e.name == name])), *window)
        inside = total(idle) - total(subtract(idle, cover))
        idle = subtract(idle, cover)
        if inside > 0:
            out.append((name, inside))
    if total(idle) > 0:
        out.append((NO_SPAN, total(idle)))
    return sorted(out, key=lambda kv: -kv[1])


def top_device_ops(trace: Trace, window: Interval, n: int = 10
                   ) -> List[Tuple[str, float]]:
    """The operations of the first device plane that took most time in
    the window, by summed duration under the trace's own names."""
    if not trace.ops:
        return []
    sums: Dict[str, float] = {}
    for e in trace.ops[sorted(trace.ops)[0]]:
        if e.start >= window[0] and e.end <= window[1]:
            sums[e.name] = sums.get(e.name, 0.0) + e.dur
    return sorted(sums.items(), key=lambda kv: -kv[1])[:n]


def _matching(events, pattern, window):
    rx = re.compile(pattern)
    return [e for e in events
            if rx.search(e.name) and e.start >= window[0]
            and e.end <= window[1]]


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------
# rule(params, trace, window, ctx) -> float | None.  `ctx` is what the
# runner's loop counted: {"steps": engine or train steps inside the traced
# window, "counters": {...}, "cost_args": {...}, "peaks": {...},
# "config": {...}}.

def rule_counter(p, trace, window, ctx):
    return ctx["counters"].get(p["counter"])


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
    busy = union(spans_of(trace.ops[sorted(trace.ops)[0]]))
    self_s = total(subtract(spans_of(spans), busy))
    return 1e3 * self_s / len(spans)


def rule_roofline_pct(p, trace, window, ctx):
    """Least time the chip could take for the required operations and
    bytes of the kernel's calls in the window (function `cost` of
    peaks.py, arguments counted by the runner's loop) over the summed
    device time of the events whose name matches, on the first device."""
    from benchmarks import peaks
    if not trace or not trace.ops:
        return None
    args = ctx["cost_args"].get(p["cost"])
    hit = _matching(trace.ops[sorted(trace.ops)[0]], p["match"], window)
    if not hit or not args:
        return None
    cost = peaks.COST_FUNCTIONS[p["cost"]](ctx["config"], **args)
    least = peaks.roofline_seconds(cost, ctx["peaks"])
    ctx.setdefault("notes", {})[p["cost"]] = dict(
        least, events=len(hit), device_s=sum(e.dur for e in hit), **cost)
    return 100.0 * least["seconds"] / sum(e.dur for e in hit)


def _collective_spans(trace, window):
    per = []
    for evs in trace.ops.values():
        coll = [e for e in evs if COLLECTIVE.search(e.name)]
        rest = [e for e in evs if not COLLECTIVE.search(e.name)]
        c = clip(union(spans_of(coll)), *window)
        per.append((total(c), total(subtract(c, spans_of(rest)))))
    return per


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
