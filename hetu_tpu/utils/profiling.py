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

import contextlib
import time
from typing import Dict, Optional

import jax

from hetu_tpu.utils.logging import get_logger

logger = get_logger("profiling")


def env_flags() -> Dict[str, str]:
    """The runtime-behavior env surface (reference: GetExecEnvs); the full
    typed registry with docs lives in hetu_tpu.utils.flags."""
    from hetu_tpu.utils import flags
    return flags.active()


def device_mem_bytes() -> Optional[int]:
    """bytes_in_use on device 0, or None where the backend hides it (CPU).
    ONE definition shared by the trainer's RunLog probe and the
    HETU_TPU_MEMORY_PROFILE step stats."""
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
        v = stats.get("bytes_in_use")
        return int(v) if v is not None else None
    except Exception:
        return None


class phase_span:
    """One step-scoped span with two sinks: a `jax.profiler.TraceAnnotation`
    (on the device trace's clock by construction; read when a profiler
    session runs, a flag check when none does) and the span's
    `perf_counter` duration added to the caller's per-step phase record
    under the name's last dotted part (`serve.emit` -> `emit`; a phase
    entered twice in one step accumulates).  Used inside
    `ServingEngine.step` and `Trainer.train_step`; "tracing off" is "no
    profiler session" -- there is no flag."""
    __slots__ = ("_ann", "_record", "_key", "_t0")

    def __init__(self, name: str, record: Dict[str, float]):
        self._ann = jax.profiler.TraceAnnotation(name)
        self._record = record
        self._key = name.rsplit(".", 1)[-1]

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        rec = self._record
        rec[self._key] = (rec.get(self._key, 0.0)
                          + time.perf_counter() - self._t0)
        return False


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
