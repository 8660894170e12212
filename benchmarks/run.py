#!/usr/bin/env python3
"""One run of one cell of hetu_tpu's benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: loads the cell's configuration and traffic files (found by
the names `BENCHMARK.json` gives), makes weights and inputs from `--seed`,
warms every shape the cell uses (all of that is `setup_s`), measures for
`--seconds`, checks the outputs against the plain reference outside the
window, and prints the contract's JSON object as the last line of its
standard output.  Earlier lines (also JSON) carry the set-up phases, the
kernel routes, `check_s`, the path of the file the run's readings went to
and, last before the result, where the run's own seconds went after the
window (`{"phase": "post"}`: stopping the profiler, reading its file,
lowering and compiling for the sizes, the check, the reduction, the whole
run, and how many events the trace held).  With `--trace 0` the metrics
are the cell's end-to-end metrics, with `--trace 1` its per-layer metrics, read from a profiler trace of the last
seconds of the window, from the loop's own counts and from the
differences of the program's `MetricsRegistry` over the window; the
`summary` line (and the readings file) carry what the program says of
itself beside them: each step phase's largest duration, the slowest
engine step's record and, in a traced run, where in the sync spans the
device's idle lies and which phase launched the one-operation programs.

Everything the runner knows about a model architecture comes from the
module `benchmarks/families/<family>.py` that the configuration file
names: the program's model, the plain reference's forward, the counts.

There is no CPU fallback: without a TPU, with a device kind that
`peaks.py` does not list, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.  `--rehearse` (used by
`benchmarks/tests` and by a builder before spending chip time) runs a
tiny configuration on whatever backend is there and prints every metric
under the prefix `cpu_rehearsal.`, so that no number of such a run can be
taken for a device metric.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse        # noqa: E402
import gc              # noqa: E402
import importlib       # noqa: E402
import json            # noqa: E402
import math            # noqa: E402
import os              # noqa: E402
import shutil          # noqa: E402
import sys             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

import numpy as np     # noqa: E402

from benchmarks import peaks, trace as trace_mod   # noqa: E402
from benchmarks import traffic as traffic_mod      # noqa: E402

OUT_DIR = os.path.join(HERE, "out")
REHEARSAL_PREFIX = "cpu_rehearsal."


def emit(**record):
    print(json.dumps(record), flush=True)


class Refused(Exception):
    """The run cannot be a measurement; exit non-zero, print no result."""


# ---------------------------------------------------------------------------
# the cell, from data
# ---------------------------------------------------------------------------

def load_cell(benchmark_file: str, workload: str) -> dict:
    with open(benchmark_file) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"workload {workload!r} is not in {benchmark_file}; "
                      f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(ROOT, configs[cell["config"]]["file"])) as f:
        config = json.load(f)

    family = importlib.import_module(
        "benchmarks.families." + config["family"])

    def reported_here(metric):
        return workload in metric.get("workloads", [workload])
    return {"cell": cell, "config": config, "family": family,
            "traffic": traffic_mod.load_traffic(cell["traffic"]),
            "end_to_end": [m for m in bench["end_to_end"] if reported_here(m)],
            "per_layer": [m for m in bench["per_layer"] if reported_here(m)]}


def metric_spec(name: str) -> dict:
    return traffic_mod.load_json("metrics", name)


# ---------------------------------------------------------------------------
# device, compile counting, memory, tracing
# ---------------------------------------------------------------------------

def device_info(chips: int, rehearse: bool) -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not rehearse:
        raise Refused(f"no TPU: jax.devices()[0] is {dev.platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chip(s), JAX sees "
                      f"{len(devices)}")
    if not rehearse:
        peaks.peaks_for(dev.device_kind)      # KeyError -> refused below
    return {"platform": dev.platform, "kind": dev.device_kind, "count": chips}


class CompileCounter:
    """Counts compile requests (cache hit or not) while `armed`."""
    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        import jax.monitoring
        self.count, self.armed = 0, False
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_kw):
        if self.armed and event == self.EVENT:
            self.count += 1


def peak_memory(devices, program_bytes: int) -> dict:
    """Peak on the fullest chip: the larger of the runtime's counter and
    arguments + temporaries of the largest compiled program the run used
    (the counter leaves out the train step's temporaries, PERF.md s7)."""
    counter = 0
    for d in devices:
        counter = max(counter, (d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0))
    return {"counter_bytes": int(counter), "program_bytes": int(program_bytes),
            "peak_bytes": int(max(counter, program_bytes)),
            "from": "program" if program_bytes > counter else "counter"}


def program_bytes_of(compiled) -> int:
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.temp_size_in_bytes)


class Tracer:
    """Starts the profiler when the loop says so, marks the traced part
    of the window, and stops after the loop has ended."""

    def __init__(self, on: bool, run_dir: str):
        self.on, self.dir = on, os.path.join(run_dir, "trace")
        self.started = False
        self.t_start = self.t_end = None
        # for the `post` line: what stopping and reading cost, what was read
        self.seconds = {"stop_trace_s": 0.0, "read_trace_s": 0.0}
        self.events = {}

    def maybe_start(self, now: float, window_end: float, trace_s: float):
        if not self.on or self.started or now < window_end - trace_s:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        # the Python tracer would record every call of the host loop and
        # slow it; the benchmark's spans are TraceAnnotations (host tracer)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = True

    def mark_start(self):
        """Called by the loop once the work it had in flight when the
        profiler started has passed: the traced window begins here."""
        if self.started and self.t_start is None:
            import jax
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_START):
                self.t_start = time.perf_counter()

    def mark_end(self):
        if self.started and self.t_end is None:
            import jax
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_END):
                self.t_end = time.perf_counter()

    def stop(self):
        """-> (Trace, window) or (None, None)."""
        if not self.started:
            return None, None
        import jax
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.seconds["stop_trace_s"] = time.perf_counter() - t0
        path = trace_mod.find_xplane(self.dir)
        if path is None:
            return None, None
        t0 = time.perf_counter()
        tr = trace_mod.read_xplane(path)
        self.seconds["read_trace_s"] = time.perf_counter() - t0
        self.events = tr.counts()
        return tr, trace_mod.window_of(tr)


def span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def counters_now(registry) -> dict:
    """Every counter of the program's registry, flat, as of now.  Taken
    where the window and its traced part begin and end (a few
    milliseconds each: the snapshot summarises the histograms too; the
    loops take it just outside the traced part)."""
    return trace_mod.counter_values(registry.snapshot())


def program_readings(registry, phase_family, tr, tr_window) -> dict:
    """What the program says of itself that is no metric: the largest
    duration any step spent in each phase (the whole run's, warm-up
    included) and, from a traced run, where in the two sync spans the
    device's idle lies and which phase launched which one-operation
    program."""
    out = {"phase_max_ms": {
        h["labels"]["phase"]: 1e3 * h["max"]
        for h in registry.snapshot()["histograms"]
        if h["name"] == phase_family and h.get("max") is not None}}
    if tr is not None and tr_window:
        out["sync_idle_position_ms"] = {
            name: trace_mod.idle_position_ms(tr, tr_window, name)
            for name in trace_mod.SYNC_SPANS}
        out["eager_dispatches"] = trace_mod.eager_dispatches(
            tr.launches, tr_window)
    return out


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def run_train(cell, args, dev, tracer, compiles, phases):
    import jax
    from hetu_tpu.core.mesh import MeshConfig
    from hetu_tpu.engine.trainer import Trainer
    from hetu_tpu.engine.trainer_config import TrainingConfig
    from hetu_tpu.obs.metrics import get_registry
    from hetu_tpu.parallel import ParallelStrategy

    phases["program_import_s"] = time.perf_counter() - phases.pop("_t_loop")
    config, tf, family = cell["config"], cell["traffic"], cell["family"]
    chips = cell["cell"]["chips"]
    mesh = tf.get("mesh", {})
    dp, tp = int(mesh.get("dp", 1)), int(mesh.get("tp", 1))
    if dp * tp != chips:
        raise Refused(f"traffic mesh dp{dp} x tp{tp} is not {chips} chip(s)")
    strategy = (ParallelStrategy(
        mesh=MeshConfig(dp=dp, tp=tp),
        sequence_parallel=bool(tf.get("sequence_parallel", False)),
        zero=bool(tf.get("zero", False))) if chips > 1 else ParallelStrategy())
    model = family.build_model(config, tf, strategy)
    B, S = int(tf["global_batch"]), int(tf["seq_len"])
    tc = TrainingConfig(
        global_batch_size=B, micro_batch_size=int(tf["micro_batch"]),
        seq_len=S, lr=float(tf["lr"]), warmup_steps=0, min_lr_ratio=1.0,
        total_steps=10 ** 9, weight_decay=float(tf["weight_decay"]),
        grad_clip=float(tf["grad_clip"]),
        seed=traffic_mod.jax_seed(args.seed), log_every=10 ** 9)
    trainer = Trainer(model, tc, strategy)
    registry = get_registry()       # the process's, which the Trainer uses
    t0 = time.perf_counter()
    trainer.build()
    jax.block_until_ready((trainer.params, trainer.opt_state))
    phases["build_s"] = time.perf_counter() - t0

    batches = traffic_mod.train_batches(tf, args.seed, config["vocab_size"])

    def dispatch():
        with span("feed_batch"):
            hb = next(batches)
        with span("train_step.dispatch"):
            return trainer.train_step(hb)["loss"]

    def wait(loss):
        with span("train_step.wait"):
            jax.block_until_ready(loss)
        return time.perf_counter()

    # warm-up: the first step compiles (or loads from the cache); two more
    # reach the steady state.  One step is always in flight: the host
    # dispatches step k+1, then waits for step k, as a training loop that
    # reads its loss one step late does.
    t0 = time.perf_counter()
    wait(dispatch())
    phases["first_step_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(int(tf.get("warm_steps", 2))):
        wait(dispatch())
    phases["warm_steps_s"] = time.perf_counter() - t0
    emit(phase="kernel_routes", routes=trainer.kernel_routes,
         strategy=strategy.describe())
    in_flight = dispatch()
    gc.collect()
    gc.freeze()

    # ---- the window: whole steps, from one completion to another
    losses, completions = [], []
    nxt = dispatch()
    completions.append(wait(in_flight))       # the window starts here
    compiles.armed = True
    marks = {"window_start": counters_now(registry)}
    losses.append(in_flight)
    in_flight = nxt
    t_begin = completions[0]
    setup_s = t_begin - T_PROCESS_START
    t_stop = t_begin + args.seconds
    started_at = None
    while completions[-1] < t_stop:
        tracer.maybe_start(completions[-1], t_stop,
                           float(tf.get("trace_s", 4.0)))
        if tracer.started and started_at is None:
            started_at = len(completions)
        if started_at is not None and len(completions) >= started_at + 2 \
                and "trace_start" not in marks:
            marks["trace_start"] = counters_now(registry)
            tracer.mark_start()      # two steps on: the pipeline is full
        nxt = dispatch()
        completions.append(wait(in_flight))
        losses.append(in_flight)
        in_flight = nxt
    tracer.mark_end()
    compiles.armed = False
    marks["window_end"] = counters_now(registry)
    wait(in_flight)                          # the step beyond the window
    tr, tr_window = tracer.stop()

    loss_values = [float(x) for x in losses[1:]]
    steps = len(completions) - 1
    # all the tokens of the window's whole steps over all their time;
    # the first interval after warm-up is dropped
    stats = traffic_mod.step_intervals(completions[1:])
    tokens_per_s_chip = B * S * stats["rate_hz"] / chips

    # ---- outside the window: correctness and sizes
    t0 = time.perf_counter()
    hb = next(batches)
    rep = trainer.memory_report(hb)      # an AOT compile: a cache hit
    mem = peak_memory(list(trainer.mesh.devices.flat),
                      rep["argument_size"] + rep["temp_size"])
    # the text of the step program `memory_report` compiled, for the
    # scope join; taken after the window and on traced runs only
    hlo_texts = ([trainer.lowered_step(hb, optimized=True)]
                 if args.trace else [])
    lower_compile_s = time.perf_counter() - t0
    check = check_training(trainer, family, config, tf, args.seed)
    check_s = time.perf_counter() - t0
    failed = sum(not math.isfinite(x) for x in loss_values)
    correct = bool(check["ok"] and failed == 0)

    readings = {"kind": "train_job", "completions_s":
                [c - t_begin for c in completions],
                "intervals_s": stats["intervals"], "losses": loss_values}
    counters = {
        "train_stall_pct": stats["stall_pct"],
        "train_step_median_ms": 1e3 * stats["median_s"],
        "compiles_in_window": compiles.count,
        "peak_hbm_gb": mem["peak_bytes"] / 1e9,
    }
    if dev["platform"] == "tpu":
        counters["model_flops_util"] = (
            100.0 * peaks.train_flops_per_token(family.counts(config), S)
            * tokens_per_s_chip
            / peaks.peaks_for(dev["kind"])["flops_per_s"])
    # main() cuts the traced window to whole executions of the step
    # program; their number is the `steps` of the window's counts
    ctx = {"snap_to_module": "train_step", "counters": counters,
           "registry": trace_mod.counter_diff(marks["window_start"],
                                              marks["window_end"]),
           "hlo_texts": hlo_texts,
           "window_counts": {
               "batch": B, "seq": S, "shards": chips,
               "counters": trace_mod.counter_diff(
                   marks.get("trace_start", marks["window_end"]),
                   marks["window_end"])}}
    trainer.close()
    t0 = time.perf_counter()
    inside = dict(program_readings(registry, "trainer.step_phase_s", tr,
                                   tr_window),
                  end_to_end={"train_tokens_per_s_chip": tokens_per_s_chip})
    readings["inside"] = inside
    return {
        "setup_s": setup_s, "attempted": steps,
        "failed": failed, "correct": correct, "check": check,
        "check_s": check_s, "lower_compile_s": lower_compile_s,
        "readings_s": time.perf_counter() - t0,
        "memory": mem, "readings": readings,
        "end_to_end": {"train_tokens_per_s_chip": tokens_per_s_chip},
        "ctx": ctx, "trace": tr, "trace_window": tr_window,
        "summary": dict({"steps": steps, "window_steps_s": stats["span_s"],
                         "median_step_s": stats["median_s"],
                         "stall_pct": stats["stall_pct"],
                         "first_loss": loss_values[0],
                         "last_loss": loss_values[-1]}, **inside),
    }


def check_training(trainer, family, config, tf, seed):
    """Two comparisons with the float32 reference, which runs on device 0
    with the trainer's own weights gathered there.  (1) At the weights the
    window left: the forward of the model the trainer built (its sharded
    weights, kernels and mesh) on one seeded sequence, logits and the loss
    they give.  (2) At the seed's fresh weights (`Trainer.build()` again):
    one real `Trainer.train_step` on a seeded batch of the cell's own
    shape, so that the step program the window ran is the one checked: the
    loss and the gradient norm it returns against the reference's loss and
    `jax.grad`, and the optimizer's second moment after it against that
    gradient.  Fresh weights, because after ~150 steps on random tokens
    the bfloat16 backward itself can be far off: the step's norm was
    0.04%, 1.4% and 22% off the reference's in three seeds, the last from
    the second layer's attention backward on (my chip runs, PR 23;
    PERF.md s6), where the forward agreed to 0.4% in all three.  The
    batch's labels are masked (-100) from `check_step_seq` (else
    `check_seq`) tokens on; attention is causal, so loss and gradients are
    exactly those of the first so many tokens of every sequence, which is
    what the reference is given (a float32 reference of 4096 tokens does
    not fit beside the trainer's state).  The reference runs first: the
    step donates the weights it is compared on."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P, \
        SingleDeviceSharding
    from hetu_tpu.core.mesh import use_mesh
    from benchmarks import reference

    n = int(tf.get("check_seq", 1024))
    dp = max(trainer.strategy.dp, 1)
    rng = traffic_mod.rng_for(seed, "check")
    vocab, forward = config["vocab_size"], family.logits_at
    ids = rng.integers(0, vocab, size=(dp, n), dtype=np.int32)
    mesh = trainer.mesh
    spec = P("dp", None) if dp > 1 else P()
    first = SingleDeviceSharding(list(mesh.devices.flat)[0])
    with use_mesh(mesh):
        dev_ids = jax.device_put(ids, NamedSharding(mesh, spec))
        logits = jax.jit(lambda p, i: trainer.model(p, i))(
            trainer.params, dev_ids)
        sys_logits = jax.device_put(logits[0], first)
    del logits
    params0 = jax.device_put(trainer.params, first)
    out = reference.check_training(forward, params0, config, ids[0],
                                   sys_logits)
    del sys_logits, params0
    out["sequence_tokens"] = n

    trainer.params = trainer.opt_state = None     # free them, then anew
    trainer.build()
    params0 = jax.device_put(trainer.params, first)
    B, S = int(tf["global_batch"]), int(tf["seq_len"])
    batch = rng.integers(0, vocab, size=(B, S), dtype=np.int32)
    n = int(tf.get("check_step_seq", n))
    labels = batch.copy()
    labels[:, n:] = -100
    ref = reference.loss_and_grad_norm(forward, params0, config,
                                       batch[:, :n])
    del params0

    def v_sum():
        v = trainer.opt_state.get("v")
        if v is None:
            return None
        with use_mesh(mesh):
            return float(jax.jit(lambda t: jnp.sum(jnp.stack([
                jnp.sum(x.astype(jnp.float32))
                for x in jax.tree.leaves(t)])))(v))
    system = {"v_sum_before": v_sum()}
    metrics = trainer.train_step({"input_ids": batch, "labels": labels})
    system.update(loss=float(metrics["loss"]),
                  grad_norm=float(metrics["grad_norm"]),
                  v_sum_after=v_sum())
    step = reference.check_train_step(
        ref, system, float(tf["grad_clip"]),
        float(getattr(trainer.optimizer, "b2", 0.0)))
    out["ok"] = bool(out["ok"] and step.pop("ok"))
    out.update(step, step_tokens=B * (n - 1))
    return out


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def run_serve(cell, args, dev, tracer, compiles, phases):
    import jax
    from hetu_tpu.obs.metrics import MetricsRegistry
    from hetu_tpu.serving.engine import ServingEngine
    from hetu_tpu.serving.request import Request

    phases["program_import_s"] = time.perf_counter() - phases.pop("_t_loop")
    config, tf, family = cell["config"], cell["traffic"], cell["family"]
    sv, vocab = config["serving"], config["vocab_size"]
    model = family.build_model(config, sv)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(
        jax.random.key(traffic_mod.jax_seed(args.seed)))
    jax.block_until_ready(params)
    phases["build_s"] = time.perf_counter() - t0
    registry = MetricsRegistry()
    engine = ServingEngine(model, params, family.serve_config(config),
                           registry=registry)
    t0 = time.perf_counter()
    engine.warmup()
    phases["warmup_s"] = time.perf_counter() - t0
    emit(phase="kernel_routes", routes=engine.kernel_routes)

    open_loop = tf["kind"] == "open_loop"
    ramp_s = float(tf.get("ramp_s", 0.0))
    drain_limit = float(tf.get("drain_limit_s", 30.0))
    seconds = float(args.seconds)
    if open_loop:
        plan = traffic_mod.plan_requests(
            tf, args.seed, vocab, ramp_s=ramp_s,
            until_s=seconds + drain_limit)
    else:
        # more requests than the loop can complete in any window
        plan = traffic_mod.plan_requests(
            tf, args.seed, vocab, count=int(tf["plan_requests"]))
    outstanding_target = int(tf.get("outstanding", 0))
    phases["plan_s"] = time.perf_counter() - t0 - phases["warmup_s"]
    phases["ramp_s"] = ramp_s

    # ---- the loop, on the wall clock; t = 0 is the start of the window
    t_origin = time.perf_counter() + ramp_s

    def clock():
        return time.perf_counter() - t_origin

    nxt = 0                 # next planned request to submit
    submitted = {}          # rid -> (PlannedRequest, submit_t, due)
    results = {}            # rid -> RequestResult
    first_seen = {}         # rid -> (admit_t, first_token_t)
    steps = []              # (t_end, prompt tokens prefilled, tokens
    #                          generated): the program's own counters
    counted = {"serve.prefill_tokens": 0.0, "serve.tokens_out": 0.0}
    marks = {}              # moment -> (engine steps so far, counters)
    in_engine = 0
    window_open = gc_frozen = False
    setup_s = None
    slowest_step = None
    t_window_end = None

    def mark(moment):
        marks[moment] = (len(steps), counters_now(registry))

    def submit_due(now):
        nonlocal nxt, in_engine
        while nxt < len(plan):
            pr = plan[nxt]
            if open_loop:
                if pr.due > now:
                    break
                due = pr.due
            else:
                if in_engine >= outstanding_target:
                    break
                due = now
            with span("submit"):
                engine.submit(Request(rid=pr.rid, prompt=pr.prompt,
                                      max_new_tokens=pr.max_new,
                                      arrival_t=due))
            submitted[pr.rid] = (pr, clock(), due)
            in_engine += 1
            nxt += 1

    def account(finished, t_end):
        """What this engine step did: the tokens it prefilled and
        generated, by the program's counters; and what no counter gives,
        the admit and first-token times of requests still in a slot (a
        slot past its prefill has its first token)."""
        nonlocal in_engine
        for st in engine.scheduler.slots:
            if st is not None and not st.prefilling \
                    and st.request.rid not in first_seen:
                first_seen[st.request.rid] = (st.stats.admit_t,
                                              st.stats.first_token_t)
        for r in finished:
            results[r.rid] = r
            in_engine -= 1
            first_seen.setdefault(r.rid, (r.stats.admit_t,
                                          r.stats.first_token_t))
        read = {name: registry.counter_value(name) for name in counted}
        steps.append((t_end,) + tuple(int(read[name] - counted[name])
                                      for name in counted))
        counted.update(read)

    def first_tokens_pending():
        """Requests due inside the window that have no first token yet."""
        return any(0.0 <= due < seconds and rid not in first_seen
                   for rid, (_, _, due) in submitted.items())

    while True:
        now = clock()
        if not gc_frozen and now >= -1.0:
            # in the ramp's last second, not on the window's first request
            gc_frozen = True
            gc.collect()
            gc.freeze()
        if not window_open and now >= 0.0:
            window_open = True
            setup_s = time.perf_counter() - T_PROCESS_START
            compiles.armed = True
            engine.slowest_step = None          # of the window, not the ramp
            mark("window_start")
        if window_open and t_window_end is None and now >= seconds:
            t_window_end = now
            compiles.armed = False
            tracer.mark_end()
            slowest_step = engine.slowest_step
            mark("window_end")
        if t_window_end is not None and (
                not first_tokens_pending() or now >= seconds + drain_limit):
            break
        if window_open and t_window_end is None:
            if tracer.started and "trace_start" not in marks:
                mark("trace_start")
                tracer.mark_start()  # one engine step after the start
            tracer.maybe_start(now, seconds, float(tf.get("trace_s", 5.0)))
        submit_due(now)
        if not (engine.scheduler.active_slots() or engine.scheduler.queue):
            if not open_loop and nxt >= len(plan):
                raise Refused("the traffic plan ran out before the window "
                              "ended; raise plan_requests")
            pause = ((plan[nxt].due if nxt < len(plan)
                      else seconds + drain_limit) - clock()
                     if open_loop else 0.0)
            if pause > 0:
                time.sleep(min(pause, 0.05))
            continue
        with span("engine.step"):
            finished = engine.step(clock())
        account(finished, clock())
    tr, tr_window = tracer.stop()
    t_end_loop = clock()

    # ---- the window's numbers.  TTFT and queue wait: every request DUE
    # in the window.  TPOT: every request that COMPLETED in the window (so
    # the run need not wait out the longest answer).  The judged
    # normalised latency, (done - due) / tokens as in the Orca and vLLM
    # papers: every request due AND completed in the window (PERF.md s2).
    due_in = [(rid, pr, sub, due) for rid, (pr, sub, due)
              in submitted.items() if 0.0 <= due < seconds]
    ttft, tpot, norm, waits, late = [], [], [], [], []
    failed = 0
    per_request = []
    for rid, pr, sub, due in due_in:
        late.append(1e3 * (sub - due))
        r = results.get(rid)
        wrong_end = r is not None and (r.finished_reason != "length"
                                       or len(r.tokens) != pr.max_new)
        if rid not in first_seen or wrong_end:
            failed += 1
            per_request.append({"rid": rid, "due": due, "failed": True})
            continue
        admit_t, first_t = first_seen[rid]
        ttft.append(1e3 * (first_t - due))
        waits.append(1e3 * (admit_t - due))
        per_request.append({
            "rid": rid, "due": due, "submit": sub, "admit": admit_t,
            "first": first_t, "done": r.stats.done_t if r else None,
            "prompt": len(pr.prompt), "out": pr.max_new})
    done_in = [r for r in results.values()
               if 0.0 <= r.stats.done_t < seconds]
    for r in done_in:
        if len(r.tokens) >= 2:
            tpot.append(1e3 * (r.stats.done_t - r.stats.first_token_t)
                        / (len(r.tokens) - 1))
        due = submitted[r.rid][2]
        if 0.0 <= due < seconds and r.tokens:
            norm.append(1e3 * (r.stats.done_t - due) / len(r.tokens))
    if not open_loop:
        # a closed loop's requests are "due" when a client is free: the
        # work attempted in the window is the requests it completed
        failed = sum(r.finished_reason != "length"
                     or len(r.tokens) != submitted[r.rid][0].max_new
                     for r in done_in)
        due_in = [(r.rid,) + submitted[r.rid] for r in done_in]
    in_window = [s for s in steps if 0.0 <= s[0] < seconds]
    if len(in_window) < 2 or not ttft or not tpot or not norm:
        raise Refused("the window holds no work: "
                      f"{len(in_window)} engine steps, {len(ttft)} requests")
    before = [s[0] for s in steps if s[0] < 0.0]
    sl = traffic_mod.slice_rates(
        [s[0] for s in in_window], [s[1] + s[2] for s in in_window],
        before[-1] if before else 0.0, int(tf["slice_steps"]))
    token_gaps = [1e3 * (b - a) for r in done_in for a, b in
                  zip(r.stats.token_ts, r.stats.token_ts[1:])]

    end_to_end = {
        "norm_latency_mean_ms": float(np.mean(norm)),
        "serve_tokens_per_s": sl["rate"],
    }

    # ---- outside the window: sizes, then correctness (the engine's pool
    # is freed first so that the reference has room)
    t0 = time.perf_counter()
    compiled = [low.compile() for low in engine.lower_programs().values()]
    mem = peak_memory([jax.devices()[0]],
                      max(program_bytes_of(c) for c in compiled))
    # the programs' texts, for the scope join: on traced runs only
    hlo_texts = [c.as_text() for c in compiled] if args.trace else []
    del compiled
    lower_compile_s = time.perf_counter() - t0
    # the collector was frozen in the ramp's last second, the engine with
    # it: thawed, or `del engine` and `collect()` free no pool (the engine
    # and its scheduler, cache and recorder refer to each other) and the
    # reference runs beside it
    gc.unfreeze()
    engine.close()
    del engine
    gc.collect()
    in_use_at_check = int((jax.devices()[0].memory_stats() or {}).get(
        "bytes_in_use", 0))
    from benchmarks import reference
    rng = traffic_mod.rng_for(args.seed, "check")
    good = [r.rid for r in done_in
            if len(r.tokens) == submitted[r.rid][0].max_new]
    picks = rng.choice(len(good), size=min(int(tf.get("check_requests", 4)),
                                           len(good)), replace=False)
    streams = []
    for i in picks:
        rid = good[int(i)]
        pr = submitted[rid][0]
        streams.append(dict(
            rid=rid, prompt=len(pr.prompt), **reference.check_stream(
                family.logits_at, params, config, pr.prompt,
                results[rid].tokens, sv["max_len"])))
    check = {"ok": bool(streams) and all(s["ok"] for s in streams),
             "streams": streams,
             # what the device held when the reference began: the
             # weights (and the runtime's own), the pool freed
             "device_bytes_in_use_at_start": in_use_at_check,
             "rule": "each served token's reference logit within 16 bf16 "
                     "ulps of the reference's maximum given the stream's "
                     "own prefix, and 70% of a stream's tokens the "
                     "reference's argmax"}
    check_s = time.perf_counter() - t0

    # the loop's own counts: what no counter of the program gives.  The
    # program's counters are read as their difference between the marks,
    # which stand at the top of the loop: over the engine steps that
    # BEGAN in the window (`engine_steps` of them)
    (n0, at_start), (n1, at_end) = marks["window_start"], marks["window_end"]
    n_traced, at_trace = marks.get("trace_start", (n1, at_end))
    counters = {
        "engine_steps": n1 - n0,
        "loadgen_late_p99_ms": traffic_mod.percentile(late, 99),
        "queue_wait_p90_ms": traffic_mod.percentile(waits, 90),
        "ttft_mean_ms": float(np.mean(ttft)),
        "ttft_p90_ms": traffic_mod.percentile(ttft, 90),
        "tpot_mean_ms": float(np.mean(tpot)),
        "tpot_p90_ms": traffic_mod.percentile(tpot, 90),
        "stall_pct": sl["stall_pct"],
        "compiles_in_window": compiles.count,
        "peak_hbm_gb": mem["peak_bytes"] / 1e9,
    }
    if token_gaps:
        counters["token_gap_p99_ms"] = traffic_mod.percentile(token_gaps, 99)
    ctx = {"steps": n1 - n_traced, "counters": counters,
           "registry": trace_mod.counter_diff(at_start, at_end),
           "hlo_texts": hlo_texts,
           "window_counts": {
               "counters": trace_mod.counter_diff(at_trace, at_end)}}
    # a traced run's line holds no end-to-end metric: kept here so that
    # what the profiler costs can be read (same seed, --trace 0)
    t0 = time.perf_counter()
    inside = dict(program_readings(registry, "serve.step_phase_s", tr,
                                   tr_window),
                  end_to_end=end_to_end, token_gaps=len(token_gaps),
                  slowest_step=slowest_step)
    readings = {"kind": tf["kind"], "requests": per_request,
                "steps": [list(s) for s in in_window],
                "registry": ctx["registry"], "inside": inside,
                "slice_rates": sl["slice_rates"], "slice_s": sl["slice_s"]}
    return {
        "setup_s": setup_s, "attempted": len(due_in),
        "failed": failed, "correct": bool(check["ok"] and failed == 0),
        "check": check, "check_s": check_s,
        "lower_compile_s": lower_compile_s,
        "readings_s": time.perf_counter() - t0, "memory": mem,
        "readings": readings, "end_to_end": end_to_end, "ctx": ctx,
        "trace": tr, "trace_window": tr_window,
        "summary": dict({"engine_steps": len(in_window),
                         "requests_due_in_window": len(ttft) + failed
                         if open_loop else None,
                         "requests_done_in_window": len(done_in),
                         "requests_submitted": len(submitted),
                         "ttft_p50_ms": traffic_mod.percentile(ttft, 50),
                         "tpot_p50_ms": traffic_mod.percentile(tpot, 50),
                         "median_slice_tokens_per_s": float(np.median(
                             sl["slice_rates"])),
                         "slices": len(sl["slice_rates"]),
                         "loop_end_s": t_end_loop}, **inside),
    }


LOOPS = {"train_job": run_train, "open_loop": run_serve,
         "closed_loop": run_serve}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny configuration on any backend; metrics are "
                         "printed under 'cpu_rehearsal.'")
    ap.add_argument("--benchmark-file",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)

    try:
        cell = load_cell(args.benchmark_file, args.workload)
        if args.seconds is None:
            with open(args.benchmark_file) as f:
                args.seconds = float(json.load(f)["run_seconds"])
        from hetu_tpu.utils.device import enable_compile_cache
        dev = device_info(cell["cell"]["chips"], args.rehearse)
        cache_dir = enable_compile_cache()
    except (Refused, KeyError, ImportError) as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2
    phases = {"import_s": time.perf_counter() - T_PROCESS_START}
    compiles = CompileCounter()
    run_dir = os.path.join(OUT_DIR, args.workload)
    os.makedirs(run_dir, exist_ok=True)
    tracer = Tracer(bool(args.trace), run_dir)
    emit(phase="start", workload=args.workload, seed=args.seed,
         seconds=args.seconds, trace=args.trace, device=dev,
         compile_cache_dir=cache_dir, rehearsal=args.rehearse)
    phases["_t_loop"] = time.perf_counter()
    try:
        res = LOOPS[cell["traffic"]["kind"]](
            cell, args, dev, tracer, compiles, phases)
    except Refused as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2

    emit(phase="setup", setup_s=res["setup_s"], **phases)
    emit(phase="summary", **res["summary"])
    emit(phase="check", check_s=res["check_s"], **res["check"])
    emit(phase="memory", **res["memory"])
    readings_path = os.path.join(
        run_dir, f"readings-seed{args.seed}-trace{args.trace}.json")
    with open(readings_path, "w") as f:
        json.dump(dict(res["readings"], workload=args.workload,
                       seed=args.seed, seconds=args.seconds), f)
    emit(phase="readings", path=os.path.relpath(readings_path, ROOT))

    on_chip = dev["platform"] == "tpu" and not args.rehearse
    prefix = "" if on_chip else REHEARSAL_PREFIX
    device = dict(dev, memory_peak_bytes=res["memory"]["peak_bytes"])
    metrics, breakdown = {}, None
    t_reduce = time.perf_counter()
    if not args.trace:
        values = dict(res["end_to_end"], setup_s=res["setup_s"])
        for m in cell["end_to_end"]:
            metrics[prefix + m["name"]] = {"value": values[m["name"]],
                                           "unit": m["unit"]}
    else:
        tr, window = res["trace"], res["trace_window"]
        ctx = dict(res["ctx"], config=cell["config"], family=cell["family"],
                   emit=emit)
        if on_chip:
            ctx["peaks"] = peaks.peaks_for(dev["kind"])
        if tr is not None and window is not None and \
                ctx.get("snap_to_module"):
            window, ctx["steps"] = trace_mod.snap_to_modules(
                tr, window, ctx["snap_to_module"])
        ctx["window_counts"]["steps"] = ctx.get("steps")
        busy = (trace_mod.busy_seconds(tr, window)
                if tr is not None and window is not None else None)
        if on_chip and not busy:
            print("benchmarks/run.py: the trace shows no operation on the "
                  "device", file=sys.stderr)
            return 3
        for m in cell["per_layer"]:
            spec = metric_spec(m["name"])
            if spec.get("device") and not on_chip:
                continue
            value = trace_mod.reduce_metric(spec, tr, window, ctx)
            if value is not None:
                metrics[prefix + m["name"]] = {"value": value,
                                               "unit": m["unit"]}
        if ctx.get("notes"):
            emit(phase="roofline", **ctx["notes"])
        if busy:
            device["busy_s"] = busy
            device["window_s"] = window[1] - window[0]
            breakdown = {
                "device_ops": [list(x) for x in
                               trace_mod.top_device_ops(tr, window)],
                "idle_gaps": [list(x) for x in
                              trace_mod.attribute_gaps(tr, window)][:10]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    # where the run's own seconds went after the window (PERF.md s2):
    # `lower_compile_s` is the part of `check_s` before the reference;
    # `reduce_s` is all that was made of the trace once it was read
    emit(phase="post", **tracer.seconds,
         lower_compile_s=res["lower_compile_s"], check_s=res["check_s"],
         reduce_s=res["readings_s"] + time.perf_counter() - t_reduce,
         run_s=time.perf_counter() - T_PROCESS_START, **tracer.events)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
