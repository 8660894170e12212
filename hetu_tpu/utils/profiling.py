"""Profiling / observability surface.

Rebuild of the reference's env-flag-driven profiling (reference: SURVEY §5.1,
§5.6 layer 3 — HETU_EVENT_TIMING records per-op events,
HETU_MEMORY_PROFILE per-micro-batch memory, HETU_PARALLEL_ATTN attn timing,
executable_graph.cc:1163-1313 GetExecEnvs).

TPU mapping: XLA owns op scheduling, so per-op timing comes from
jax.profiler traces; this module keeps the reference's ENV-FLAG CONTRACT and
provides step-level timing + trace capture:

    HETU_TPU_EVENT_TIMING=1        step timing logged per step (the interval
                                   between step completions, `StepProfiler`)
    HETU_TPU_TRACE_DIR=/tmp/trace  capture a jax.profiler trace (step window)
    HETU_TPU_MEMORY_PROFILE=1      per-step device memory stats (if exposed)
"""
from __future__ import annotations

import collections
import contextlib
import gc
import json
import threading
import time
from time import perf_counter as _perf_counter, thread_time as _thread_time
from typing import Dict, Optional

import jax
import jax.monitoring

from hetu_tpu.utils.logging import get_logger

logger = get_logger("profiling")


def env_flags() -> Dict[str, str]:
    """The runtime-behavior env surface (reference: GetExecEnvs); the full
    typed registry with docs lives in hetu_tpu.utils.flags."""
    from hetu_tpu.utils import flags
    return flags.active()


def device_memory() -> Dict[str, Optional[int]]:
    """`bytes_in_use` and `peak_bytes_in_use` on device 0, each None
    where the backend hides it (CPU).  ONE definition shared by the
    trainer's RunLog probe, the HETU_TPU_MEMORY_PROFILE step stats and a
    stalled step's record (`StepRecorder`)."""
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:
        stats = {}
    return {k: None if stats.get(k) is None else int(stats[k])
            for k in ("bytes_in_use", "peak_bytes_in_use")}


def device_mem_bytes() -> Optional[int]:
    """`device_memory()`'s `bytes_in_use` alone."""
    return device_memory()["bytes_in_use"]


class _Open(threading.local):
    """What this thread has open, for the process-wide listeners: the
    `StepRecorder` whose step runs and the innermost `phase_span`'s key."""
    step = None
    phase = None


_open = _Open()


class phase_span:
    """One step-scoped span with two sinks: a `jax.profiler.TraceAnnotation`
    (on the device trace's clock by construction; read when a profiler
    session runs, a flag check when none does) and the span's
    `perf_counter` duration added to the caller's per-step phase record
    under the name's last dotted part (`serve.emit` -> `emit`; a phase
    entered twice in one step accumulates).  Used inside
    `ServingEngine.step` and `Trainer.train_step`, whose record is a
    `StepRecorder`'s: a compile that comes while the span is open is
    counted under its key.  "Tracing off" is "no profiler session" --
    there is no flag."""
    __slots__ = ("_ann", "_record", "_key", "_t0", "_outer")

    def __init__(self, name: str, record: Dict[str, float]):
        self._ann = jax.profiler.TraceAnnotation(name)
        self._record = record
        self._key = name.rsplit(".", 1)[-1]

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._outer, _open.phase = _open.phase, self._key
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        _open.phase = self._outer
        rec = self._record
        rec[self._key] = (rec.get(self._key, 0.0)
                          + time.perf_counter() - self._t0)
        return False


#: a step is STALLED when it takes more than `STALL_FACTOR` x the median
#: of the `STALL_WINDOW` steps of its kind before it (a kind is judged
#: by its own steps from `STALL_MIN_STEPS` of them on: a run's first
#: steps compile; before that by the nearest kind that has them)
STALL_FACTOR, STALL_WINDOW, STALL_MIN_STEPS = 8.0, 64, 16
#: how many stalled steps' full records a recorder keeps
SLOW_STEPS_KEPT = 16
#: a step that begins within this of the step before's end takes that
#: end's reading of the thread's CPU clock for its own start (off by the
#: gap at most): the clock is a system call, which on the chip's host
#: costs 18 us where `perf_counter` costs none (PERF.md s6)
CPU_CLOCK_REUSE_S = 0.5e-3

#: the one per compile REQUEST, with the persistent cache or without it
#: (`pxla` wraps `compile_or_get_cached` in it: its seconds hold the
#: cache's lookup or the backend's compile).  `benchmarks/run.py`'s
#: `CompileCounter` reads `/jax/compilation_cache/compile_requests_use_
#: cache`, which comes once per request too, but only where a cache
#: directory is set.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# process-wide: collections so far, their seconds, the running one's start
_gc_seen = [0, 0.0, 0.0]
_listening = False


def _on_gc(phase, info):
    if phase == "start":
        _gc_seen[2] = _perf_counter()
    else:
        _gc_seen[0] += 1
        _gc_seen[1] += _perf_counter() - _gc_seen[2]


def _on_compile(event, duration_secs, **_kw):
    rec = _open.step
    if rec is not None and event == COMPILE_EVENT:
        phase = _open.phase or "none"
        rec._compiles[phase] = rec._compiles.get(phase, 0) + 1
        rec._compile_s[phase] = (rec._compile_s.get(phase, 0.0)
                                 + duration_secs)


def _listen():
    """One `gc.callbacks` entry and one `jax.monitoring` listener for the
    process, from the first recorder on; each is paid only when a
    collection or a compile happens."""
    global _listening
    if not _listening:
        _listening = True
        gc.callbacks.append(_on_gc)
        jax.monitoring.register_event_duration_secs_listener(_on_compile)


def stall_cause(rec: dict) -> str:
    """What a stalled step's record says took the time, in the order a
    reader asks (docs/serving.md "Reading a stalled step"): `caller`,
    the time went between two steps and not inside one; `compile`, a
    program compiled inside the step (whatever its share: a warm loop
    compiles nothing); `gc`, a garbage collection paused it for most of
    it; `host`, the thread was running for most of it (the host's own
    work); else `blocked`: the thread waited, in a sync phase for the
    device or the runtime, in a dispatch phase for the dispatch itself.
    Whether the DEVICE was busy meanwhile only a trace says."""
    if rec["stalled"] == "caller":
        return "caller"
    if rec["compiles"]:
        return "compile"
    if rec["gc"]["pause_s"] >= 0.5 * rec["step_s"]:
        return "gc"
    return "host" if rec["cpu_s"] >= 0.5 * rec["step_s"] else "blocked"


class StepRecorder:
    """The record a step loop keeps of its own steps: `ServingEngine.step`
    (family `serve`) and `Trainer.train_step` (`trainer`) open a step with
    `begin()`, run their `phase_span`s over the record it returns, and
    close it with `end()`.  Always on and no switch: it costs 4 us a
    step and one reading of the thread's CPU clock (a system call: 0.3 us
    here, 18 on the chip's host; PERF.md s6).

    Beside the phases' seconds a step takes: its wall and the thread's
    CPU seconds (`time.thread_time`: `step_s - cpu_s` is the time the
    host was NOT running; read at a step's end, and at its start only
    where a gap lies before it: `CPU_CLOCK_REUSE_S`; a loop's steps run
    on one thread), the garbage collections of the process inside
    it and their pause, the compile requests and their seconds by the
    phase open when they came (`COMPILE_EVENT`), and the gap since the
    step before ended: `empty` where that step left its loop with no work
    (`end(empty=True)`) or the loop turned to work of its own (`idle()`:
    the trainer's checkpoint), else the `caller`'s.

    All of it goes to COUNTERS, so that a difference of two snapshots
    reads a window's (`<f>` the family): `<f>.steps`, `.step_wall_s`,
    `.step_cpu_s`, `.phase_s{phase}`, `.caller_s`, `.empty_s`,
    `.gc_collections`, `.gc_pause_s`, `.step_compiles{phase}`,
    `.step_compile_s{phase}`, `.stalled_steps{phase}`, `.stalled_s{phase}`,
    `.unjudged_steps`; and the histograms `<f>.step_phase_s{phase}` and `<f>.step_s` keep the
    distribution.  `step_wall_s + empty_s + caller_s` is the wall time
    from the first step's entry to the last one's end.

    **The stall rule**, the one place it is written: a step is stalled
    when its time, with the caller's gap before it, is more than
    `STALL_FACTOR` x the median of the `STALL_WINDOW` such times before
    it AMONG THE STEPS OF ITS KIND (`end(kind=...)`: what the caller
    dispatched; the engine gives its number of chunk launches, since a
    step that launches three chunk programs of 39 ms is no stalled
    decode step of 8 ms: on the chip one window for all steps counted a
    hundred such steps in 50 s, PERF.md s6).  An engine step's gap is a
    tenth of a millisecond, so this is the step; a `train_step` only
    dispatches, so it is the interval from one return to the next, which
    in a loop that keeps a fixed number of steps in flight is the step
    time.  An empty gap is not judged.  A kind with fewer than
    `STALL_MIN_STEPS` steps so far (a rare one for a whole run: a chat
    step with three chunk launches) is judged against the nearest kind
    BELOW it that has them, whose median it is allowed once more for
    each launch it has more (a kind is a COUNT of what the step
    launches beside the least; a launch that takes `STALL_FACTOR` x a
    whole step of the kind below is not met: MiMo's chunk program is
    5 x its decode step); with none below, against the nearest kind
    ABOVE as it is (a step that launches less is no slower); where no
    kind has them yet (a loop's first steps) the step is not judged
    and `<f>.unjudged_steps` counts it, so that "no stalled step" can
    be told from "nobody looked".
    Kinds are few (the engine's: at most one a slot and one); a kind's
    window is its last `STALL_WINDOW` steps however long ago they were.
    A stalled step is counted under the phase with the most seconds
    (`caller`: the gap) with its excess over the median, its full record
    is kept in `slow_steps` (the last `SLOW_STEPS_KEPT`) with the device's
    `bytes_in_use` and its `cause` (`stall_cause`), and it logs itself,
    one JSON line."""

    def __init__(self, family: str, registry):
        _listen()
        self.family, self._registry = family, registry
        self.slow_steps = collections.deque(maxlen=SLOW_STEPS_KEPT)
        self._keys: Dict[tuple, tuple] = {}
        self.reset()

    def _key(self, name: str, phase: Optional[str] = None) -> tuple:
        """The registry's key of `<family>.<name>` (of `{phase}`), formed
        once."""
        key = self._keys.get((name, phase))
        if key is None:
            labels = {} if phase is None else {"phase": phase}
            key = self._keys[name, phase] = self._registry.series(
                f"{self.family}.{name}", **labels)
        return key

    def reset(self):
        """Forget the steps so far (not the counters): the next step has
        no gap before it and is judged against none.  For a loop that
        starts again (`Trainer.build`)."""
        self._recent: Dict[object, collections.deque] = {}
        self._t_end: Optional[float] = None
        self._ended_empty = False

    def idle(self):
        """The loop turns to work of its own until the next step (the
        trainer: a checkpoint): the gap is nobody's delay, `empty_s`, and
        is not judged; as `end(empty=True)`, for what a loop learns after
        the step has closed."""
        self._ended_empty = True

    def begin(self) -> Dict[str, float]:
        """Open a step; -> its phase record, for `phase_span`.  The step's
        clock starts as this returns: opening the record is in the gap
        before the step, not in the step."""
        self._gc0 = (_gc_seen[0], _gc_seen[1])
        self._compiles: Dict[str, int] = {}
        self._compile_s: Dict[str, float] = {}
        phases = self._phases = {}
        _open.step = self
        t = _perf_counter()
        if self._t_end is None or t - self._t_end > CPU_CLOCK_REUSE_S:
            self._cpu0 = _thread_time()
            t = _perf_counter()
        self._gap = 0.0 if self._t_end is None else t - self._t_end
        self._t0 = t
        return phases

    def end(self, step: int, now: float, slowest: Optional[dict] = None,
            empty: bool = False, kind=None, **detail) -> Optional[dict]:
        """Close the step `begin()` opened.  `step`, `now`: the caller's
        index and clock for the record; `empty`: the loop holds no work
        now, so the gap to the next step is nobody's delay; `kind`: the
        steps this one is judged against (a count of what it launches;
        None, from every call: all);
        `detail`: what the caller adds to a full record.  -> the slowest step's
        record: this one's if it took longer than `slowest` (None: any),
        else `slowest`."""
        t_end = _perf_counter()
        cpu = _thread_time()
        _open.step = None
        step_s, cpu_s = t_end - self._t0, cpu - self._cpu0
        self._cpu0 = cpu        # the next step's, if it follows at once
        key = self._key
        phases, gap = self._phases, self._gap
        held = not self._ended_empty
        # what follows is in the gap before the next step: the record's
        # own cost shows as the caller's (or an empty loop's) time
        self._t_end, self._ended_empty = t_end, empty
        counts = [(key("steps"), 1.0), (key("step_wall_s"), step_s),
                  (key("step_cpu_s"), cpu_s)]
        counts += [(key("phase_s", name), dt) for name, dt in phases.items()]
        if gap:
            counts.append((key("caller_s" if held else "empty_s"), gap))
        gc_n = _gc_seen[0] - self._gc0[0]
        gc_s = _gc_seen[1] - self._gc0[1]
        if gc_n:
            counts += [(key("gc_collections"), gc_n),
                       (key("gc_pause_s"), gc_s)]
        for name, n in self._compiles.items():
            counts += [(key("step_compiles", name), n),
                       (key("step_compile_s", name), self._compile_s[name])]

        judged = step_s + gap if held else step_s
        recent = self._recent.get(kind)
        if recent is None:
            recent = self._recent[kind] = collections.deque(
                maxlen=STALL_WINDOW)
        against, allowed = recent, 1
        if len(recent) < STALL_MIN_STEPS:
            # a rare kind: the nearest kind below that has its steps,
            # once more for each launch this step has more; none below,
            # the nearest above as it is
            full = [] if kind is None else [
                k for k, window in self._recent.items()
                if len(window) >= STALL_MIN_STEPS]
            below = [k for k in full if k < kind]
            against = ()
            if below:
                near = max(below)
                against, allowed = self._recent[near], 1 + kind - near
            elif full:
                against = self._recent[min(full)]
        median = (allowed * sorted(against)[len(against) // 2]
                  if against else None)
        recent.append(judged)
        if median is None:
            counts.append((key("unjudged_steps"), 1.0))
        stalled = median is not None and judged > STALL_FACTOR * median
        slower = slowest is None or step_s > slowest["step_s"]
        if stalled:
            parts = dict(phases, caller=gap) if held else phases
            where = max(parts, key=parts.get)
            counts += [(key("stalled_steps", where), 1.0),
                       (key("stalled_s", where), judged - median)]
        self._registry.record(
            counts, [(key("step_phase_s", name), dt)
                     for name, dt in phases.items()]
            + [(key("step_s"), step_s)])
        if not (stalled or slower):
            return slowest
        rec = {"step": step, "now": now, "step_s": step_s, "cpu_s": cpu_s,
               "gap_s": gap, "phases": phases,
               "gc": {"collections": gc_n, "pause_s": gc_s},
               "compiles": self._compiles, "compile_s": self._compile_s,
               **detail, "median_s": median, "stalled": None,
               "bytes_in_use": None, "peak_bytes_in_use": None}
        if stalled:
            rec.update(device_memory(), stalled=where)
            rec["cause"] = stall_cause(rec)
            self.slow_steps.append(rec)
            logger.warning(f"{self.family}: stalled step "
                           f"{json.dumps(rec, sort_keys=True, default=str)}")
        return rec if slower else slowest


class StepProfiler:
    """Step-level timing/trace hooks for the trainer loop.

    A step's time is the interval between consecutive step COMPLETIONS,
    observed one step late (the `Trainer._note_scaler` discipline): the
    loop hands the step it just dispatched to `in_flight`, and on
    leaving `step()` the profiler waits for the step dispatched BEFORE
    it, which has had a whole dispatch to finish, and stamps the clock.
    So the loop never waits on the step it just dispatched, the
    intervals add up to the loop's wall time, and in steady state each
    is one step's device time -- not the enqueue, which returns while
    the device still works.  The first interval holds the first step's
    trace, compile and dispatch."""

    def __init__(self):
        from hetu_tpu.utils import flags
        self.event_timing = flags.bool_flag("HETU_TPU_EVENT_TIMING")
        self.trace_dir = flags.str_flag("HETU_TPU_TRACE_DIR") or None
        self.mem_profile = flags.bool_flag("HETU_TPU_MEMORY_PROFILE")
        self._trace_active = False
        self._trace_done = False
        self._first_step: Optional[int] = None
        self._times = []
        self._t_mark: Optional[float] = None
        self._dispatched = self._waiting_on = None
        #: most recent HETU_TPU_MEMORY_PROFILE probe (bytes_in_use), so
        #: the RunLog step record and merged cluster traces see memory
        #: too, not just the log line (None: profiling off / backend
        #: hides memory_stats)
        self.last_mem_bytes: Optional[int] = None

    def _stop_trace(self):
        if self._trace_active:
            try:
                jax.profiler.stop_trace()
                logger.info(f"trace written to {self.trace_dir}")
            finally:
                self._trace_active = False
                self._trace_done = True

    def in_flight(self, value):
        """`value`: a device array of the step being dispatched (its
        loss); its readiness is that step's completion."""
        self._dispatched = value

    @contextlib.contextmanager
    def step(self, step_idx: int, trace_steps=(2, 4)):
        """trace_steps are RELATIVE to the first profiled step, so traces
        fire on checkpoint-resumed runs too."""
        if self._first_step is None:
            self._first_step = step_idx
        rel = step_idx - self._first_step
        if (self.trace_dir and not self._trace_active and not self._trace_done
                and rel >= trace_steps[0]):
            jax.profiler.start_trace(self.trace_dir)
            self._trace_active = True
        if self._t_mark is None:
            self._t_mark = time.perf_counter()
        try:
            yield
        finally:
            prev, self._waiting_on = self._waiting_on, self._dispatched
            self._dispatched = None
            if prev is not None:
                jax.block_until_ready(prev)
            now = time.perf_counter()
            dt, self._t_mark = now - self._t_mark, now
            self._times.append(dt)
            if self.event_timing:
                logger.info(f"step {step_idx}: {dt * 1000:.1f} ms")
            if self.mem_profile:
                used = device_mem_bytes()
                self.last_mem_bytes = used
                if used is not None:
                    logger.info(
                        f"step {step_idx}: {used / 1e9:.2f} GB in use")
            if self._trace_active and rel >= trace_steps[1]:
                self._stop_trace()

    @property
    def last_step_s(self) -> float:
        """The most recent completion interval (0.0 before the first) --
        the trainer's RunLog step records, `trainer.step_time_s` and the
        health monitor read it."""
        return self._times[-1] if self._times else 0.0

    def close(self):
        """Flush an in-flight trace (called by the trainer when the loop
        ends before the trace window closes) and let go of the last
        step's array."""
        self._waiting_on = self._dispatched = None
        self._stop_trace()

    def summary(self) -> Dict[str, float]:
        if not self._times:
            return {}
        ts = sorted(self._times)
        return {"steps": len(ts), "min_s": ts[0],
                "median_s": ts[len(ts) // 2], "max_s": ts[-1]}


# ---------------------------------------------------------------------------
# per-phase HLO attribution (reference: hetu/impl/profiler/profiler.h:25
# per-op cost records + HETU_EVENT_TIMING executable_graph.cc:1303)
# ---------------------------------------------------------------------------

PHASES = ("embed", "attn", "moe", "mlp", "lm_head", "ring")

# ONE byte-pricing table for every HLO text walker (obs/hlo_text.py is
# its home so a dtype addition lands once); imported here — after PHASES
# — because obs.hlo_profile imports PHASES from this module.
from hetu_tpu.obs.hlo_text import DTYPE_BYTES as _DTYPE_BYTES  # noqa: E402


def phase_breakdown(compiled_or_text, phases=PHASES):
    """Attribute the optimized HLO's instructions to the model's
    jax.named_scope phases (models annotate embed/attn/moe/mlp/lm_head).

    The scopes survive into instruction metadata (op_name="jit(f)/.../attn/
    dot_general"), INCLUDING the autodiff transpose ops, so forward and
    backward both attribute.  Returns {phase: {"instructions", "dots",
    "out_bytes"}} plus an "other" bucket — a hardware-free compute-split
    estimate (dots ~ MXU work, out_bytes ~ HBM traffic) that calibrates the
    cost model's per-phase terms; a jax.profiler trace over the same step
    shows the identical scope names on the timeline for wall-clock truth."""
    import re

    txt = (compiled_or_text if isinstance(compiled_or_text, str)
           else compiled_or_text.as_text())
    op_pat = re.compile(r'op_name="([^"]+)"')
    shape_pat = re.compile(r'\b([a-z][a-z0-9]*)\[([0-9,]*)\]')
    # the OUTPUT-shape section of `%name = <shapes> opcode(...)`: the
    # non-greedy group is everything between the assignment and the first
    # lowercase opcode token followed by '(' (operand shapes live INSIDE
    # the parens and must not count — summing them overcounts traffic by
    # the instruction fan-in).  Tuple outputs `(f32[..]{..}, f32[..]{..})`
    # and tiled layouts `{1,0:T(8,128)}` stay in the group: `T(` starts
    # uppercase, dtype tokens are followed by `[` not `(`.
    out_pat = re.compile(r'=\s*(.*?)\s*[a-z][a-z0-9_.-]*\(')
    # a scope segment may be wrapped by transform names — "attn",
    # "jvp(embed)", "transpose(jvp(mlp))" — so match the phase bounded by
    # path separators or transform parens
    seg_pats = {p: re.compile(r'(?:^|[/(])' + re.escape(p) + r'(?:[)/]|$)')
                for p in phases}
    # NOTE: hetu_tpu.obs.hlo_profile.layer_table is the per-LAYER
    # refinement of this walk (full scope paths, parsed dot FLOPs, wire
    # bytes, while-loop trip counts); with static counting its sums
    # equal these phase totals exactly — a tested contract, so the two
    # walks must not drift apart.
    out = {p: {"instructions": 0, "dots": 0, "out_bytes": 0}
           for p in (*phases, "other")}
    for line in txt.splitlines():
        m = op_pat.search(line)
        if m is None:
            continue
        opname = m.group(1)
        seg = next((p for p in phases if seg_pats[p].search(opname)),
                   "other")
        rec = out[seg]
        rec["instructions"] += 1
        if " dot(" in line or " convolution(" in line:
            rec["dots"] += 1
        # output shape(s): scalar `= f32[8,16]{...}` or tuple-shaped
        # multi-output fusions `= (f32[8,128]{...}, f32[8]{...})`.  HLO
        # text ALSO prints operand shapes inside the call parens, so the
        # scan is anchored to the output section only (out_pat) — every
        # component of a tuple output counts, no operand double-counts.
        om = out_pat.search(line)
        out_section = om.group(1) if om is not None else ""
        for dt, dims in shape_pat.findall(out_section):
            numel = 1
            for d in dims.split(","):
                if d:
                    numel *= int(d)
            rec["out_bytes"] += numel * _DTYPE_BYTES.get(dt, 4)
    return out
