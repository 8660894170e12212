"""Step-scoped spans, counters and scope names from inside the engine and
the trainer (tier-1, CPU).

A tiny engine run and a tiny trainer run are made under a `jax.profiler`
trace on the CPU backend and read back with `ProfileData`: the spans of
`serving.engine.STEP_PHASES` / `Trainer.train_step` are on the profiler's
clock, nest in `serve.step` / `trainer.step`, and the program's own
counters, per-token times and per-phase seconds agree with what the
submitted requests say.  `obs.hlo_profile.scope_map` is checked on the
compiled tiny train step and decode program.  No time read here is a
device time: the tests hold structure, counts and sums only.
"""
import collections
import gc
import glob
import logging
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu import serving
from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.obs import hlo_profile as hp
from hetu_tpu.obs.metrics import MetricsRegistry
from hetu_tpu.serving.engine import STEP_PHASES
from hetu_tpu.utils import profiling
from hetu_tpu.utils.profiling import StepProfiler, phase_span

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks import trace as bench_trace  # noqa: E402  (read, not edited)

Span = collections.namedtuple("Span", "name start end")
TRAINER_SPANS = ("trainer.prepare_batch", "trainer.dispatch")
MAX_NEW = 6


def traced(tmp_dir, fn):
    """Run `fn` under a profiler session; -> (fn's result, the program's
    `serve.*` / `trainer.*` spans on the host plane)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path, = glob.glob(str(tmp_dir / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans += [Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events
                      if e.name.startswith(("serve.", "trainer."))]
    return out, spans


def nested(child, parents):
    return any(p.start <= child.start and child.end <= p.end
               for p in parents)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_llama():
    cfg = LlamaConfig.tiny(remat=False, compute_dtype=jnp.float32,
                           use_flash_attention=False)
    model = LlamaLMHeadModel(cfg)
    return model, model.init(jax.random.key(0))


def _requests(vocab):
    rng = np.random.default_rng(3)
    return [serving.Request(
        rid=i, max_new_tokens=MAX_NEW,
        prompt=rng.integers(0, vocab, size=int(rng.integers(4, 20))
                            ).astype(np.int32)) for i in range(4)]


def _engine(model, params):
    registry = MetricsRegistry()
    engine = serving.ServingEngine(
        model, params, serving.ServeConfig(num_slots=3, page_size=8,
                                           max_len=64, prefill_chunk=8),
        registry=registry)
    return engine.warmup(), registry


@pytest.fixture(scope="module")
def served(tiny_llama, tmp_path_factory):
    """The same four requests served twice: under a profiler session
    (spans, registry, results) and with none running (results)."""
    model, params = tiny_llama
    vocab = model.config.vocab_size
    engine, registry = _engine(model, params)
    results, spans = traced(tmp_path_factory.mktemp("serve_trace"),
                            lambda: engine.run(_requests(vocab)))
    quiet_engine, _ = _engine(model, params)
    quiet = quiet_engine.run(_requests(vocab))
    return dict(engine=engine, registry=registry, results=results,
                spans=spans, quiet=quiet, requests=_requests(vocab))


@pytest.mark.parametrize("name", STEP_PHASES)
def test_serve_phase_span_nests_in_step(served, name):
    steps = [s for s in served["spans"] if s.name == "serve.step"]
    mine = [s for s in served["spans"] if s.name == name]
    assert len(steps) == served["engine"].steps_done
    assert mine, f"no {name} span in the trace"
    assert all(nested(s, steps) for s in mine)


def test_serve_phases_fit_their_step(served):
    """The phases are siblings: inside each `serve.step` they do not
    overlap and their time is at most the step's."""
    spans = served["spans"]
    for step in (s for s in spans if s.name == "serve.step"):
        kids = sorted((s for s in spans if s.name in STEP_PHASES
                       and nested(s, [step])), key=lambda s: s.start)
        assert kids[0].name == "serve.admit"
        assert kids[-1].name == "serve.housekeeping"
        assert all(a.end <= b.start for a, b in zip(kids, kids[1:]))
        assert sum(k.end - k.start for k in kids) <= step.end - step.start


def _expected_counts(requests):
    """From the requests alone: every prompt token is prefilled once; a
    request decodes MAX_NEW - 1 times (its first token comes from the
    prefill), and its j-th decode step attends over prompt + j tokens."""
    steps = MAX_NEW - 1
    return {
        "serve.prefill_tokens": sum(r.prompt_len for r in requests),
        "serve.decode_slot_steps": steps * len(requests),
        "serve.decode_context_tokens": sum(
            steps * r.prompt_len + steps * (steps + 1) // 2
            for r in requests),
    }


@pytest.mark.parametrize("counter", ["serve.prefill_tokens",
                                     "serve.decode_slot_steps",
                                     "serve.decode_context_tokens"])
def test_serve_work_counters_match_the_requests(served, counter):
    assert served["registry"].counter_value(counter) == \
        _expected_counts(served["requests"])[counter]


def test_token_ts_one_time_per_token(served):
    for r in served["results"]:
        ts = r.stats.token_ts
        assert len(ts) == len(r.tokens) == MAX_NEW
        assert all(a <= b for a, b in zip(ts, ts[1:]))
        assert ts[0] == r.stats.first_token_t
        assert ts[-1] == r.stats.done_t


@pytest.mark.parametrize("disagg", [False, True],
                         ids=["colocated", "disaggregated"])
def test_token_ts_in_the_fleet_simulator(monkeypatch, disagg):
    """The simulator's engine twin emits modeled tokens at three places
    (the last chunk, an adopted shipment, the decode step): each keeps
    `token_ts` as long as the tokens, from `first_token_t` to `done_t`."""
    from hetu_tpu.serving import fleet
    svc, cost = fleet.analytic_models(
        num_params=1e8, num_layers=4, hidden_size=256, num_kv_heads=2,
        head_dim=32, page_size=8,
        hw={"bf16_tflops": 100.0, "hbm_gbps": 800.0})
    seen = []
    finish = fleet.FleetSimulator._finish

    def checked(self, slot_idx, st, now):
        finish(self, slot_idx, st, now)
        seen.append((list(st.stats.token_ts), len(st.generated),
                     st.stats.first_token_t, st.stats.done_t))
    monkeypatch.setattr(fleet.FleetSimulator, "_finish", checked)
    n = 300
    extra = dict(disagg=True, prefill_slots=4) if disagg else {}
    sim = fleet.FleetSimulator(svc, cost_model=cost, config=fleet.FleetConfig(
        num_slots=8, page_size=8, max_len=64, prefill_chunk=8, sample=1,
        **extra))
    rep = sim.run(fleet.fleet_workload(
        n, rate_per_s=500.0, burst=8, tenants=("acme",),
        slo_classes=[serving.SLOClass("bulk")], prompt_lens=(4, 24),
        max_new=(2, 8), seed=0))
    assert rep["completed"] == n == len(seen)
    for ts, tokens, first_t, done_t in seen:
        assert len(ts) == tokens
        assert all(a <= b for a, b in zip(ts, ts[1:]))
        assert ts[0] == first_t and ts[-1] == done_t
    if disagg:
        assert sim.adoptions > 0


def test_tokens_identical_with_and_without_a_profiler(served):
    assert [r.tokens for r in served["results"]] == \
        [r.tokens for r in served["quiet"]]


def test_step_phase_seconds_sum_to_the_step(served):
    reg = served["registry"]
    whole = reg.histogram("serve.step_s")
    assert whole.count == served["engine"].steps_done
    parts = [reg.histogram("serve.step_phase_s",
                           phase=name.split(".", 1)[1])
             for name in STEP_PHASES]
    assert all(h is not None for h in parts)
    # the phases never sum to more than the step.  What lies in none is
    # the plan, the loops over the slots and the record's own
    # bookkeeping: host work of the kind the admit and housekeeping
    # phases do, so it is held to THEIR seconds in these same steps, on
    # this machine under this load, and not to a share of a step as
    # short as this model's
    in_phases = sum(h.total for h in parts)
    assert in_phases <= whole.total
    assert whole.total - in_phases < 3 * (parts[0].total + parts[-1].total)
    # every step is admitted to and tidied once
    assert parts[0].count == parts[-1].count == whole.count


def test_slowest_step_names_its_phases(served):
    slow = served["engine"].slowest_step
    assert 1 <= slow["step"] <= served["engine"].steps_done
    assert slow["step_s"] == served["registry"].histogram(
        "serve.step_s").vmax
    assert set(slow["phases"]) <= {n.split(".", 1)[1] for n in STEP_PHASES}
    assert sum(slow["phases"].values()) <= slow["step_s"]


def test_phase_span_accumulates_by_last_name_part():
    record = {}
    for _ in range(2):
        with phase_span("serve.emit", record):
            time.sleep(0.001)
    assert list(record) == ["emit"] and record["emit"] >= 0.002


# ---------------------------------------------------------------------------
# the step record: counters a window's difference reads, gaps, stalls
# ---------------------------------------------------------------------------

def _window(registry, fn):
    """What every counter counted while `fn` ran, as `benchmarks/run.py`
    takes a window's: `counter_values` of a snapshot at each end, and
    their `counter_diff`."""
    start = bench_trace.counter_values(registry.snapshot())
    out = fn()
    return out, bench_trace.counter_diff(
        start, bench_trace.counter_values(registry.snapshot()))


def _long_requests(vocab, n, rid0=0, new=40):
    rng = np.random.default_rng(5)
    return [serving.Request(
        rid=rid0 + i, max_new_tokens=new,
        prompt=rng.integers(0, vocab, size=12).astype(np.int32))
        for i in range(n)]


def _busy(engine):
    return bool(engine.scheduler.queue or engine.scheduler.active_slots())


def _step_until_idle(engine, between=None):
    n = 0
    while _busy(engine):
        engine.step(0.001 * engine.steps_done)
        n += 1
        if between is not None and _busy(engine):
            between()
    return n


@pytest.fixture()
def quiet_engine(tiny_llama):
    engine, registry = _engine(*tiny_llama)
    return engine, registry, tiny_llama[0].config.vocab_size


def test_step_counters_come_out_windowed(quiet_engine):
    """The recorder's counters through `benchmarks.trace.counter_values`
    / `counter_diff`, as `run.py` writes them to every run's readings
    file: a window's difference counts the window's steps alone."""
    engine, registry, vocab = quiet_engine
    for r in _long_requests(vocab, 3):
        engine.submit(r)
    before = _step_until_idle(engine)
    for r in _long_requests(vocab, 3, rid0=10):
        engine.submit(r)
    steps, diff = _window(registry, lambda: _step_until_idle(engine))
    assert before > 0 and diff["serve.steps"] == steps
    assert registry.counter_value("serve.steps") == before + steps
    for name in ("serve.step_wall_s", "serve.step_cpu_s", "serve.phase_s",
                 "serve.phase_s{phase=admit}", "serve.caller_s",
                 "serve.phase_s{phase=token_fetch}",
                 "serve.fetch_wait_total_s"):
        assert diff[name] > 0, name
    assert diff["serve.step_cpu_s"] <= 1.5 * diff["serve.step_wall_s"]
    # the phases' counters are the sums their histograms observe
    for name in STEP_PHASES:
        phase = name.split(".", 1)[1]
        h = registry.histogram("serve.step_phase_s", phase=phase)
        if h is not None:
            assert registry.counter_value("serve.phase_s", phase=phase) \
                == pytest.approx(h.total)
    # the deferred fetch waits inside `token_fetch`
    assert diff["serve.fetch_wait_total_s"] <= \
        diff["serve.phase_s{phase=token_fetch}"]
    assert registry.histogram("serve.fetch_wait_s") is None


def test_step_wall_empty_and_caller_account_for_the_loop(quiet_engine):
    """`step_wall_s + empty_s + caller_s` is the wall time from the first
    step's entry to the last one's end; a gap behind a step that left the
    engine empty is `empty_s`, one while work is held `caller_s`."""
    engine, registry, vocab = quiet_engine

    slept = {}

    def sleep(gap):
        """30 ms asked for; what the machine gave is what the gap is
        held to (under six workers a 30 ms sleep has taken 55)."""
        t0 = time.perf_counter()
        time.sleep(0.03)
        slept[gap] = time.perf_counter() - t0

    def loop():
        engine.submit(_long_requests(vocab, 1, new=4)[0])
        t0 = time.perf_counter()        # the first step's entry, nearly
        _step_until_idle(engine)
        sleep("empty")                                # an empty engine
        for r in _long_requests(vocab, 2, rid0=5, new=20):
            engine.submit(r)
        _step_until_idle(
            engine,
            between=lambda: "caller" in slept or sleep("caller"))
        return time.perf_counter() - t0
    wall, diff = _window(registry, loop)
    accounted = (diff["serve.step_wall_s"] + diff["serve.empty_s"]
                 + diff["serve.caller_s"])
    assert accounted <= wall
    assert accounted == pytest.approx(wall, rel=0.02)
    # each sleep is in the gap of its own name and in no other: a gap is
    # at least the sleep made in it, and at most what the loop spent
    # outside its steps less the OTHER gap's sleep.  Limits from this
    # run's own clock: another process that holds the core between two
    # steps lengthens a gap (70 ms where 30 were slept, under a load of
    # 13 on 8 cores) and both limits with it
    between = wall - diff["serve.step_wall_s"]
    assert slept["empty"] <= diff["serve.empty_s"] \
        <= between - slept["caller"]
    assert slept["caller"] <= diff["serve.caller_s"] \
        <= between - slept["empty"]


def _sleep():
    time.sleep(0.08)


def _spin():
    """80 ms of the thread's OWN time, and on until that is most of the
    wall time (another process may hold the core meanwhile)."""
    c0, w0 = time.thread_time(), time.perf_counter()
    while True:
        cpu, wall = time.thread_time() - c0, time.perf_counter() - w0
        if cpu >= 0.08 and (cpu >= 0.75 * wall or wall > 2.0):
            break


_JUNK = []


def _make_junk():
    """Cycles for the collector to find, made before the step."""
    gc.collect()
    for _ in range(300_000):
        a = []
        a.append(a)
        _JUNK.append(a)


def _collect():
    _JUNK.clear()
    gc.collect()


def _compile():
    jax.jit(lambda x: jnp.tanh(x @ x.T).sum() * 3.0)(
        jnp.ones((int(time.time_ns() % 50) + 3, 5)))


@pytest.mark.parametrize("cause,work", [
    ("blocked", _sleep), ("host", _spin), ("gc", _collect),
    ("compile", _compile)])
def test_a_stalled_step_is_counted_kept_logged_and_says_why(
        quiet_engine, cause, work, caplog):
    """A step forced long inside `serve.admit`, each way a step can be:
    `serve.stalled_steps{phase=admit}` counts it, `slow_steps` keeps its
    record, the logger carries it, and the record ALONE tells the cause."""
    engine, registry, vocab = quiet_engine
    if cause == "gc":
        _make_junk()                # before the loop: a gap would be its
    for r in _long_requests(vocab, 3, new=50):
        engine.submit(r)
    for _ in range(profiling.STALL_MIN_STEPS + 4):
        engine.step(0.001 * engine.steps_done)
    assert not [r for r in engine.slow_steps if r["stalled"] == "admit"]
    admit = engine._admit

    def slow_admit(clock, finished):
        work()
        return admit(clock, finished)
    engine._admit = slow_admit
    logger = logging.getLogger("hetu_tpu.profiling")
    logger.addHandler(caplog.handler)
    try:
        _, diff = _window(
            registry, lambda: engine.step(0.001 * engine.steps_done))
    finally:
        logger.removeHandler(caplog.handler)
        engine._admit = admit
    assert diff["serve.stalled_steps{phase=admit}"] == 1
    rec = engine.slow_steps[-1]
    assert rec["step"] == engine.steps_done and rec["stalled"] == "admit"
    assert rec is engine.slowest_step
    assert diff["serve.stalled_s{phase=admit}"] == pytest.approx(
        rec["step_s"] + rec["gap_s"] - rec["median_s"])
    assert rec["step_s"] > profiling.STALL_FACTOR * rec["median_s"]
    assert max(rec["phases"], key=rec["phases"].get) == "admit"
    assert set(rec["dispatched"]) == {"chunk_launches", "prefill_chunks",
                                      "decode_batch", "fetch_behind"}
    assert rec["dispatched"]["decode_batch"] == 3
    assert {"cpu_s", "gap_s", "gc", "compiles", "compile_s",
            "fetch_wait_s", "bytes_in_use", "median_s"} <= set(rec)
    assert rec["cause"] == profiling.stall_cause(rec) == cause
    if cause == "blocked":
        assert rec["cpu_s"] < 0.2 * rec["step_s"]
    elif cause == "host":
        assert rec["cpu_s"] > 0.5 * rec["step_s"]
    elif cause == "gc":
        assert rec["gc"]["collections"] >= 1
        assert rec["gc"]["pause_s"] > 0.5 * rec["step_s"]
        assert diff["serve.gc_collections"] >= 1
        assert diff["serve.gc_pause_s"] == pytest.approx(
            rec["gc"]["pause_s"])
    else:
        assert rec["compiles"] == {"admit": diff[
            "serve.step_compiles{phase=admit}"]}
        assert diff["serve.step_compiles"] >= 1
        assert diff["serve.step_compile_s{phase=admit}"] == pytest.approx(
            rec["compile_s"]["admit"]) and rec["compile_s"]["admit"] > 0
    logged = [r.getMessage() for r in caplog.records
              if "stalled step" in r.getMessage()]
    assert len(logged) == 1 and f'"step": {rec["step"]}' in logged[0]
    # the steps after it are not stalled, and nothing of them is logged
    _, after = _window(registry, lambda: [
        engine.step(0.001 * engine.steps_done) for _ in range(5)])
    assert after.get("serve.stalled_steps{phase=admit}", 0) == 0


def _timed_steps(registry=None):
    """-> (a recorder, `step(kind, seconds)`: one step of that kind that
    sleeps so long in `serve.prefill_chunk`; -> its record or None)."""
    rec = profiling.StepRecorder("serve", registry or MetricsRegistry())

    def step(kind, seconds):
        phases = rec.begin()
        with phase_span("serve.prefill_chunk", phases):
            time.sleep(seconds)
        return rec.end(0, 0.0, None, kind=kind)
    return rec, step


def test_a_step_is_judged_among_the_steps_of_its_kind():
    """Steps that dispatch more take longer and are not stalled for it
    (on the chip one window for all steps counted a hundred three-chunk
    steps of a run as stalled decode steps): a step is judged against
    the steps of its `kind`, the engine's being the chunks of prompt rows
    its chunk launches carried."""
    registry = MetricsRegistry()
    rec, step = _timed_steps(registry)
    for _ in range(profiling.STALL_MIN_STEPS + 2):
        step(0, 0.001)
        step(3, 0.015)
    assert not rec.slow_steps
    assert step(3, 0.015)["stalled"] is None       # 12 x a kind-0 step
    slow = step(0, 0.015)
    assert slow["stalled"] == "prefill_chunk" and list(rec.slow_steps) == [
        slow]
    assert slow["median_s"] < 0.015 / profiling.STALL_FACTOR
    assert registry.counter_value("serve.stalled_steps",
                                  phase="prefill_chunk") == 1


def test_a_rare_kind_is_judged_by_the_nearest_kind_below():
    """A kind with fewer than `STALL_MIN_STEPS` steps so far (for a whole
    run, a chat step with three chunk launches) is no blind spot: its
    step is judged against the nearest kind below that has them, whose
    median it is allowed once more for each launch it has more."""
    registry = MetricsRegistry()
    rec, step = _timed_steps(registry)
    for _ in range(profiling.STALL_MIN_STEPS + 2):
        step(0, 0.001)
        step(1, 0.003)
    assert not rec.slow_steps
    # three launches more than kind 1: 4 x its median allowed, 8 x that
    # is a stall; 12 x a kind-0 step is none
    fine = step(4, 0.012)
    assert fine["stalled"] is None
    assert 4 * 0.003 <= fine["median_s"] < 4 * 0.006
    slow = step(4, 0.25)
    assert slow["stalled"] == "prefill_chunk" and list(rec.slow_steps) == [
        slow]
    assert slow["cause"] == "blocked" and slow["cpu_s"] < 0.1 * slow["step_s"]
    assert slow["step_s"] > profiling.STALL_FACTOR * slow["median_s"]
    assert registry.counter_value("serve.stalled_steps",
                                  phase="prefill_chunk") == 1
    assert registry.counter_value("serve.stalled_s", phase="prefill_chunk") \
        == pytest.approx(slow["step_s"] + slow["gap_s"] - slow["median_s"])
    # every step since the first kind had its steps was judged
    assert registry.counter_value("serve.unjudged_steps") == \
        2 * profiling.STALL_MIN_STEPS - 1


def test_a_step_nobody_can_judge_is_counted():
    """Before any kind has `STALL_MIN_STEPS` steps a step is not judged,
    however long, and `unjudged_steps` says so: "no stalled step" is
    told from "nobody looked".  From then on every step is judged: a
    kind with none below it against the nearest kind ABOVE, as it is (a
    step that launches less is no slower)."""
    registry = MetricsRegistry()
    rec, step = _timed_steps(registry)
    assert step(0, 0.1)["median_s"] is None
    for _ in range(profiling.STALL_MIN_STEPS - 1):
        assert step(2, 0.002)["median_s"] is None
    assert step(2, 0.1)["median_s"] is None
    assert not rec.slow_steps
    unjudged = profiling.STALL_MIN_STEPS + 1
    assert registry.counter_value("serve.unjudged_steps") == unjudged
    assert registry.counter_value("serve.stalled_steps") == 0
    fine = step(0, 0.004)
    assert fine["stalled"] is None
    assert 0.002 <= fine["median_s"] < 0.004            # kind 2's own
    slow = step(0, 0.1)
    assert slow["stalled"] == "prefill_chunk" and list(rec.slow_steps) == [
        slow]
    assert registry.counter_value("serve.unjudged_steps") == unjudged


def test_the_cpu_clock_is_read_once_a_step_and_a_gap_is_not_the_steps(
        monkeypatch):
    """The thread's CPU clock is a system call (18 us on the chip's host),
    so a step that follows the one before at once takes that one's last
    reading for its start; one that follows a gap reads the clock itself:
    the CPU seconds a caller burns BETWEEN two steps are never a step's."""
    reads = []

    def clock():
        reads.append(1)
        return time.thread_time()
    monkeypatch.setattr(profiling, "_thread_time", clock)
    rec = profiling.StepRecorder("serve", MetricsRegistry())

    def step():
        n = len(reads)
        rec.begin()
        out = rec.end(0, 0.0, None)
        return out, len(reads) - n
    assert step()[1] == 2                       # the first: start and end
    assert [step()[1] for _ in range(5)] == [1] * 5
    _spin()                                     # the caller's own 80 ms
    out, n = step()
    assert n == 2 and out["gap_s"] > 0.08 > 0.01 > out["cpu_s"] >= 0


def test_a_warm_engine_compiles_nothing_inside_a_step(quiet_engine):
    """After `warmup()` and a first request (whose eager one-operation
    programs compile inside its steps and are counted there, by phase),
    the same shapes again compile nothing."""
    engine, registry, vocab = quiet_engine
    for r in _long_requests(vocab, 2, new=8):
        engine.submit(r)
    _step_until_idle(engine)
    for r in _long_requests(vocab, 2, rid0=7, new=8):
        engine.submit(r)
    _, diff = _window(registry, lambda: _step_until_idle(engine))
    assert diff.get("serve.step_compiles", 0) == 0


def test_held_bytes_gauges(quiet_engine, tiny_llama):
    engine, registry, _ = quiet_engine
    held = {what: registry.gauge_value("serve.held_bytes", what=what)
            for what in ("weights", "pool", "scratch")}
    assert held["weights"] == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(engine.params))
    assert held["pool"] == sum(
        a.size * a.dtype.itemsize
        for a in jax.tree.leaves(engine.pool.arrays.tree()))
    assert held["scratch"] == sum(
        a.size * a.dtype.itemsize
        for a in jax.tree.leaves(engine._fresh_scratch()))


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def _trainer(tmp_path=None, ckpt_every=10 ** 9, **cfg_kw):
    from hetu_tpu.engine import Trainer, TrainingConfig
    from hetu_tpu.parallel import ParallelStrategy
    st = ParallelStrategy()
    tc = TrainingConfig(global_batch_size=2, micro_batch_size=2, seq_len=32,
                        lr=1e-3, warmup_steps=0, total_steps=8,
                        log_every=10 ** 9,
                        **({"ckpt_dir": str(tmp_path),
                            "ckpt_every": ckpt_every} if tmp_path else {}))
    cfg = LlamaConfig.tiny(**cfg_kw)
    return Trainer(LlamaLMHeadModel(cfg, st), tc, st)


BATCH = {"input_ids": np.ones((2, 32), np.int32),
         "labels": np.ones((2, 32), np.int32)}


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    trainer = _trainer(remat=False).build()
    trainer.train_step(BATCH)                       # compile outside
    _, spans = traced(
        tmp_path_factory.mktemp("train_trace"),
        lambda: [jax.block_until_ready(trainer.train_step(BATCH)["loss"])
                 for _ in range(2)])
    trainer.close()
    return spans


@pytest.mark.parametrize("name", TRAINER_SPANS)
def test_trainer_span_nests_in_step(trained, name):
    steps = [s for s in trained if s.name == "trainer.step"]
    mine = [s for s in trained if s.name == name]
    assert len(steps) == len(mine) == 2
    assert all(nested(s, steps) for s in mine)
    for step in steps:
        kids = [s for s in trained if s.name in TRAINER_SPANS
                and nested(s, [step])]
        assert sum(k.end - k.start for k in kids) <= step.end - step.start


@pytest.mark.parametrize("driver", ["train_step", "train"])
def test_trainer_counts_its_steps_once(driver):
    """`trainer.steps` / `trainer.tokens` are counted in `train_step`,
    once, whichever loop drives it (the benchmark drives `train_step`
    itself); the step's record is the serving engine's: wall, CPU and
    phase seconds as counters, the first step's compile under the phase
    it came in, and `step_wall_s + caller_s` the loop's wall time."""
    from hetu_tpu.obs.metrics import get_registry
    trainer = _trainer(remat=False).build()

    def loop():
        t0 = t1 = time.perf_counter()
        if driver == "train":
            trainer.train([BATCH] * 5)
            t1 = time.perf_counter()
        else:
            for _ in range(5):
                out = trainer.train_step(BATCH)
                t1 = time.perf_counter()    # the last step's wait is not
                jax.block_until_ready(out["loss"])  # between two steps
        return t1 - t0
    wall, diff = _window(get_registry(), loop)
    trainer.close()
    assert diff["trainer.steps"] == 5
    assert diff["trainer.tokens"] == 5 * BATCH["input_ids"].size
    assert diff["trainer.step_compiles{phase=dispatch}"] >= 1
    assert diff["trainer.step_compile_s{phase=dispatch}"] > 0
    for name in ("trainer.step_wall_s", "trainer.step_cpu_s",
                 "trainer.phase_s{phase=prepare_batch}",
                 "trainer.phase_s{phase=dispatch}", "trainer.caller_s"):
        assert diff[name] > 0, name
    assert diff.get("trainer.empty_s", 0) == 0
    accounted = diff["trainer.step_wall_s"] + diff["trainer.caller_s"]
    assert accounted <= wall
    if driver == "train_step":      # `train` ends with its own tidying
        # (what `train_step` does before its record begins is outside:
        # 6 ms of a 1.4 s loop on a machine other processes load)
        assert accounted == pytest.approx(wall, rel=0.02)
    slow = trainer.slowest_step
    assert slow["step"] == 1 and slow["compiles"] == {"dispatch": diff[
        "trainer.step_compiles{phase=dispatch}"]}
    assert {"now", "step_s", "phases", "cpu_s", "gap_s", "gc", "compile_s",
            "median_s", "bytes_in_use"} <= set(slow)
    assert not trainer.slow_steps       # five steps: none is judged yet


def test_trainer_interval_stalled_in_the_caller(caplog):
    """`train_step` only dispatches, so the rule is on the interval from
    one return to the next: a loop that stands still BETWEEN two steps
    holds a stalled step whose phase is `caller`.  A trainer built again
    starts a new loop: its first step follows no other."""
    from hetu_tpu.obs.metrics import get_registry
    trainer = _trainer(remat=False).build()

    def one():
        jax.block_until_ready(trainer.train_step(BATCH)["loss"])
    for _ in range(profiling.STALL_MIN_STEPS + 4):
        one()
    assert not trainer.slow_steps
    logger = logging.getLogger("hetu_tpu.profiling")
    logger.addHandler(caplog.handler)
    try:
        time.sleep(max(0.3, 12 * trainer._step_record._recent[None][-1]))
        _, diff = _window(get_registry(), one)
    finally:
        logger.removeHandler(caplog.handler)
    assert diff["trainer.stalled_steps{phase=caller}"] == 1
    rec, = trainer.slow_steps
    assert rec["stalled"] == rec["cause"] == "caller"
    assert rec["step"] == trainer.global_step
    assert rec["gap_s"] + rec["step_s"] > \
        profiling.STALL_FACTOR * rec["median_s"]
    assert diff["trainer.stalled_s{phase=caller}"] == pytest.approx(
        rec["gap_s"] + rec["step_s"] - rec["median_s"])
    assert sum("trainer: stalled step" in r.getMessage()
               for r in caplog.records) == 1
    time.sleep(0.3)
    trainer.build()
    _, diff = _window(get_registry(), one)
    trainer.close()
    assert diff.get("trainer.stalled_steps", 0) == 0
    assert len(trainer.slow_steps) == 1


def test_a_checkpoint_between_two_steps_is_the_loops_own(tmp_path,
                                                         monkeypatch):
    """`Trainer.train` saves BETWEEN two steps: that gap is the loop's
    own work (`trainer.empty_s`), the step after it is not judged by it,
    and `trainer.stalled_steps{phase=caller}` counts stalls and not the
    checkpoint cadence."""
    from hetu_tpu.obs.metrics import get_registry
    every = profiling.STALL_MIN_STEPS + 4
    trainer = _trainer(tmp_path, ckpt_every=every, remat=False).build()
    saves = []

    def save():
        saves.append(trainer.global_step)
        time.sleep(0.5)                 # hundreds of this trainer's steps
    monkeypatch.setattr(trainer, "save", save)
    _, diff = _window(get_registry(),
                      lambda: trainer.train([BATCH] * (every + 3)))
    trainer.close()
    assert saves == [every]
    assert 0.5 <= diff["trainer.empty_s"] < 0.6
    assert diff["trainer.steps"] == every + 3
    assert diff.get("trainer.stalled_steps", 0) == 0
    assert not trainer.slow_steps


class _Clock:
    """Stands in for the `time` module inside `utils.profiling`."""
    t = 0.0

    def perf_counter(self):
        return self.t


class _SerialDevice:
    """A device queue on the fake clock: a dispatch costs the host 1 ms,
    a step finishes 30 ms after the one before it (or after its own
    dispatch, if the device was idle)."""

    def __init__(self, clock):
        self.clock, self.free_at, self.waited = clock, 0.0, []

    def dispatch(self, idx):
        self.clock.t += 0.001
        self.free_at = max(self.free_at, self.clock.t) + 0.030
        return _Pending(self, idx, self.free_at)


class _Pending:
    def __init__(self, device, idx, done_at):
        self.device, self.idx, self.done_at = device, idx, done_at

    def block_until_ready(self):
        self.device.waited.append(self.idx)
        self.device.clock.t = max(self.device.clock.t, self.done_at)
        return self


def test_step_profiler_times_completions_one_step_late(monkeypatch):
    """The enqueue returns after 1 ms; the device takes 30 ms a step.
    The profiler's intervals are the completions', it waits only for the
    step BEFORE the one just dispatched, and they add up to the loop's
    wall time."""
    from hetu_tpu.utils import profiling
    clock = _Clock()
    monkeypatch.setattr(profiling, "time", clock)
    device, prof = _SerialDevice(clock), StepProfiler()
    for i in range(5):
        with prof.step(i):
            prof.in_flight(device.dispatch(i))
        assert device.waited == list(range(i))      # never step i itself
    assert prof._times == pytest.approx([0.001, 0.030, 0.030, 0.030, 0.030])
    assert sum(prof._times) == pytest.approx(clock.t)
    assert prof.last_step_s == pytest.approx(0.030)
    prof.close()


def test_train_loop_step_s_is_the_completion_interval(tmp_path):
    """`RunLog.step`, `trainer.step_time_s` and the throughput derived
    from them read the profiler's completion interval: over the loop
    they add up to its wall time up to the last wait, which no sum of
    enqueue times does."""
    from hetu_tpu.obs.metrics import get_registry
    from hetu_tpu.obs.runlog import RunLog
    trainer = _trainer(tmp_path, remat=False).build()
    hist0 = get_registry().histogram("trainer.step_time_s")
    n0, total0 = (hist0.count, hist0.total) if hist0 else (0, 0.0)
    t0 = time.perf_counter()
    trainer.train([BATCH] * 4)
    wall = time.perf_counter() - t0
    trainer.close()
    steps = [r for r in RunLog.read(str(tmp_path / "runlog.jsonl"))
             if r["kind"] == "step"]
    assert [r["step"] for r in steps] == [1, 2, 3, 4]
    times = [r["step_time_s"] for r in steps]
    assert times == trainer.profiler._times
    assert all(t > 0 for t in times) and sum(times) <= wall
    assert all(r["tokens_per_s"] == pytest.approx(64 / r["step_time_s"])
               for r in steps)
    hist = get_registry().histogram("trainer.step_time_s")
    assert hist.count - n0 == 4
    assert hist.total - total0 == pytest.approx(sum(times))


# ---------------------------------------------------------------------------
# scope_map
# ---------------------------------------------------------------------------

def _instruction_names(text):
    return [m.group(1) for m in map(hp.INSTR_PAT.match, text.splitlines())
            if m]


@pytest.fixture(scope="module")
def train_step_text():
    """The tiny trainer's compiled step with every kernel routed to
    Pallas (interpret mode on this backend) and `dots_attn` remat."""
    patch = pytest.MonkeyPatch()
    patch.setenv("HETU_TPU_PALLAS", "1")
    try:
        trainer = _trainer(
            remat_policy="dots_attn", vocab_size=256, hidden_size=256,
            intermediate_size=256, num_attention_heads=2,
            num_key_value_heads=2,
            compute_dtype=jnp.float32, param_dtype=jnp.float32)
        return trainer.lower_abstract().compile().as_text()
    finally:
        patch.undo()


@pytest.mark.parametrize("group", ["embed", "layer/attn", "layer/mlp",
                                   "lm_head", "loss", "optimizer"])
def test_scope_map_train_step_groups(train_step_text, group):
    groups = {g for g, _ in hp.scope_map(train_step_text).values()}
    assert group in groups
    assert hp.UNSCOPED in groups


def test_scope_map_train_step_kernels_and_passes(train_step_text):
    smap = hp.scope_map(train_step_text)
    kernels = {g.rsplit("/", 1)[-1] for g, _ in smap.values()
               if "pallas_" in g}
    assert {"pallas_flash_attention", "pallas_swiglu",
            "pallas_rotary"} <= kernels
    passes = collections.Counter(p for _, p in smap.values())
    assert passes["fwd"] and passes["bwd"] and passes["recompute"]
    # the join tells the three passes of a kernel's scope apart.  The
    # flash kernel ITSELF (interpret mode: a `while` under its scope) runs
    # forward and backward and not again in the recomputed forward, as the
    # swiglu kernel does: `dots_attn` keeps the `o` and `lse` its forward
    # rule names, and what the scope recomputes are the relayouts of q, k,
    # v on their way in and of `o` on its way to `o_proj`
    flash = {p for g, p in smap.values()
             if g.endswith("pallas_flash_attention")}
    assert flash == {"fwd", "bwd", "recompute"}
    remade = set(re.findall(r"rematted_computation/[^\"]*?(pallas_\w+)/while",
                            train_step_text))
    assert "pallas_swiglu" in remade
    assert "pallas_flash_attention" not in remade


def test_scope_map_names_are_unique_within_a_program(train_step_text):
    names = _instruction_names(train_step_text)
    assert len(names) == len(set(names)) == len(hp.scope_map(
        train_step_text))


def test_scope_map_decode_program(monkeypatch):
    """The serving programs of the main path (the paged Pallas decode,
    interpret mode here) carry the training programs' scope names plus
    `kv_write` for the pool's updates."""
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    cfg = LlamaConfig.tiny(
        vocab_size=256, hidden_size=256, intermediate_size=256,
        num_attention_heads=2, num_key_value_heads=2, remat=False,
        compute_dtype=jnp.float32, param_dtype=jnp.float32)
    model = LlamaLMHeadModel(cfg)
    engine = serving.ServingEngine(
        model, model.abstract_params(),
        serving.ServeConfig(num_slots=8, page_size=8, max_len=64,
                            prefill_chunk=8), registry=MetricsRegistry())
    texts = {name: low.compile().as_text()
             for name, low in engine.lower_programs().items()}
    took = engine.kernel_routes["paged_attn"]
    assert took["pallas"] and not took["xla"]
    decode = hp.scope_map(texts["decode"])
    names = _instruction_names(texts["decode"])
    assert len(names) == len(set(names)) == len(decode)
    groups = {g for g, _ in decode.values()}
    assert {"embed", "layer/attn", "layer/mlp", "layer/kv_write",
            "layer/attn/pallas_paged_attention", "lm_head"} <= groups
    assert {p for _, p in decode.values()} == {"fwd"}
    chunk = {g for g, _ in hp.scope_map(texts["prefill_chunk"]).values()}
    assert {"layer/attn", "layer/mlp", "layer/kv_write"} <= chunk
    assert "kv_write" in {g for g, _ in hp.scope_map(
        texts["write_pages"]).values()}


def test_lower_programs_hands_out_the_executables_that_ran():
    """A miss is not a scope (PR 53): the text a reader joins a device
    trace with is the text of the executable the engine's own jitted
    function built in `warmup`, not a second lowering that the compiler
    may number otherwise.  Nothing compiles again, and the
    `kernel_routes` line says per program which text that is."""
    import hashlib
    cfg = LlamaConfig.tiny(
        vocab_size=256, hidden_size=64, intermediate_size=64,
        num_attention_heads=2, num_key_value_heads=2, remat=False)
    model = LlamaLMHeadModel(cfg)
    engine = serving.ServingEngine(
        model, model.init(jax.random.key(0)),
        serving.ServeConfig(num_slots=4, page_size=8, max_len=32,
                            prefill_chunk=8), registry=MetricsRegistry())
    engine.warmup()
    compiles = []

    def on_compile(event, duration_secs, **_kw):
        if event == profiling.COMPILE_EVENT:
            compiles.append(duration_secs)
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        texts = {name: low.compile().as_text()
                 for name, low in engine.lower_programs().items()}
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert not compiles
    noted = engine.kernel_routes["programs"]
    assert set(noted) == set(texts) >= {"decode", "prefill_chunk",
                                        "write_pages"}
    for name, text in texts.items():
        assert noted[name] == {
            "instructions": len(hp.scope_map(text)),
            "fingerprint": hashlib.sha1(text.encode()).hexdigest()[:12]}


def test_obs_report_says_how_each_program_was_placed(tmp_path, monkeypatch):
    """With HETU_TPU_PROFILE and a RunLog the engine leaves a `profile`
    record a program at warm-up; `tools_obs_report.py` prints per
    program its instructions, its text's fingerprint (the one on the
    `kernel_routes` line) and what each source of `scope_sources`
    placed."""
    import tools_obs_report
    from hetu_tpu.obs.runlog import RunLog
    monkeypatch.setenv("HETU_TPU_PROFILE", "1")
    path = str(tmp_path / "runlog.jsonl")
    cfg = LlamaConfig.tiny(
        vocab_size=256, hidden_size=64, intermediate_size=64,
        num_attention_heads=2, num_key_value_heads=2, remat=False)
    model = LlamaLMHeadModel(cfg)
    engine = serving.ServingEngine(
        model, model.init(jax.random.key(0)),
        serving.ServeConfig(num_slots=4, page_size=8, max_len=32,
                            prefill_chunk=8), registry=MetricsRegistry(),
        run_log=RunLog(path))
    engine.warmup()
    noted = engine.kernel_routes["programs"]
    engine.close()
    engine.run_log.close()
    scopes = tools_obs_report.summarize(RunLog.read(path))["scopes"]
    assert set(scopes) == set(noted)
    for name, rec in scopes.items():
        assert {k: rec[k] for k in noted[name]} == noted[name]
        assert sum(r["instructions"] for r in rec["placed_by"].values()) \
            == rec["instructions"]
    assert "layer/attn" in scopes["decode"]["placed_by"]["own"]["groups"]
    assert scopes["decode"]["placed_by"]["none"]["groups"] == [hp.UNSCOPED]


@pytest.mark.parametrize("op_name,expect", [
    ("jit(_train_step)/while/body/closed_call/jvp()/while/body/"
     "closed_call/layer/attn/pallas_flash_attention/pallas_call",
     ("layer/attn/pallas_flash_attention", "fwd")),
    ("jit(_train_step)/while/body/closed_call/transpose(jvp())/while/body/"
     "closed_call/checkpoint/layer/mlp/dot_general", ("layer/mlp", "bwd")),
    ("jit(_train_step)/while/body/closed_call/transpose(jvp())/while/body/"
     "closed_call/checkpoint/rematted_computation/layer/attn/"
     "pallas_flash_attention/shard_map/pallas_call",
     ("layer/attn/pallas_flash_attention", "recompute")),
    ("jit(decode_fn)/layer/while/body/closed_call/attn/kv_write/scatter",
     ("layer/kv_write", "fwd")),
    ("jit(decode_fn)/layer/while/body/dynamic_update_slice",
     ("layer", "fwd")),
    ("jit(_train_step)/while/body/closed_call/jvp()/loss/reduce_sum",
     ("loss", "fwd")),
    ("jit(_train_step)/while/body/add", (hp.UNSCOPED, "fwd")),
])
def test_scope_map_reads_compiled_v5e_op_names(op_name, expect):
    """Paths copied from programs compiled for a described v5e
    (one chip and dp2 x tp2): the same text rules hold there."""
    line = (f'  %fusion.7 = f32[8]{{0}} fusion(%p), kind=kLoop, '
            f'metadata={{op_name="{op_name}"}}')
    assert hp.scope_map(line) == {"fusion.7": expect}


# what the compiler left without a name: lines as programs compiled for a
# described v5e hold them (Phi's and Jamba's decode programs, the dp2 x
# tp2 step), cut to what a rule reads
_D = "jit(decode_fn)/layer/while/body/closed_call"
_T = "jit(_train_step)/while/body/closed_call/transpose(jvp())/while/body"


def _meta(path):
    return f', metadata={{op_name="{path}"}}'


RESOLVED = f"""HloModule jit_decode_fn, is_scheduled=true

%fused_computation.286.clone (p0: bf16[32,2560], p1: bf16[2560,10240]) -> bf16[32,10240] {{
  %p0 = bf16[32,2560]{{1,0}} parameter(0)
  %p1 = bf16[2560,10240]{{1,0}} parameter(1)
  %convolution.9 = bf16[32,10240]{{1,0}} convolution(%p0, %p1), dim_labels=bf_io->bf{_meta(_D + "/mlp/dot_general")}
  %multiply.3 = bf16[32,10240]{{1,0}} multiply(%convolution.9, %convolution.9){_meta(_D + "/attn/ssm/ssm_out/mul")}
  ROOT %add.4 = bf16[32,10240]{{1,0}} add(%multiply.3, %multiply.3){_meta(_D + "/attn/ssm/ssm_out/add")}
}}

%fused_computation.7 (p0.1: f32[32,5120]) -> f32[32,5120] {{
  %p0.1 = f32[32,5120]{{1,0}} parameter(0)
  %exp.1 = f32[32,5120]{{1,0}} exponential(%p0.1){_meta(_D + "/attn/ssm/ssm_conv/exp")}
  %mul.1 = f32[32,5120]{{1,0}} multiply(%exp.1, %p0.1){_meta(_D + "/attn/ssm/ssm_conv/mul")}
  ROOT %add.1 = f32[32,5120]{{1,0}} add(%mul.1, %p0.1){_meta(_D + "/attn/ssm/add")}
}}

%fused_computation.8 (p0.2: f32[32,5120]) -> f32[32,5120] {{
  %p0.2 = f32[32,5120]{{1,0}} parameter(0)
  %mul.2 = f32[32,5120]{{1,0}} multiply(%p0.2, %p0.2){_meta(_D + "/attn/ssm/mul")}
  ROOT %add.2 = f32[32,5120]{{1,0}} add(%mul.2, %p0.2){_meta(_D + "/attn/ssm/ssm_proj/add")}
}}

%bitcast_fusion.2 (bitcast_input.2: bf16[2560,10240]) -> bf16[2560,10240] {{
  %bitcast_input.2 = bf16[2560,10240]{{1,0}} parameter(0)
  ROOT %bitcast.2 = bf16[2560,10240]{{1,0}} bitcast(%bitcast_input.2)
}}

ENTRY %main (w: bf16[2560,10240], x: bf16[32,2560], w2: bf16[2560,2560], q: bf16[16,2560], pool: bf16[2,65,8,2,16]) -> (bf16[32,10240], s32[]) {{
  %w = bf16[2560,10240]{{1,0}} parameter(0){_meta("params['mlp']['w_gate_up']")}
  %x = bf16[32,2560]{{1,0}} parameter(1)
  %w2 = bf16[2560,2560]{{1,0}} parameter(2)
  %q = bf16[16,2560]{{1,0}} parameter(3)
  %pool = bf16[2,65,8,2,16]{{4,3,2,1,0}} parameter(4)
  %fusion.352 = bf16[2560,10240]{{1,0}} fusion(%w), kind=kLoop, calls=%bitcast_fusion.2
  %copy.5 = bf16[32,2560]{{0,1}} copy(%x)
  %fusion.441.clone.1 = bf16[32,10240]{{1,0}} fusion(%copy.5, %fusion.352), kind=kOutput, calls=%fused_computation.286.clone
  %fusion.70 = f32[32,5120]{{1,0}} fusion(%fusion.441.clone.1), kind=kLoop, calls=%fused_computation.7
  %fusion.80 = f32[32,5120]{{1,0}} fusion(%fusion.70), kind=kLoop, calls=%fused_computation.8
  %copy-start.1 = (bf16[2560,2560]{{1,0:S(1)}}, bf16[2560,2560]{{1,0}}, u32[]{{:S(2)}}) copy-start(%w2)
  %copy-done.1 = bf16[2560,2560]{{1,0:S(1)}} copy-done(%copy-start.1)
  %fusion.9 = bf16[32,2560]{{1,0}} fusion(%fusion.80, %copy-done.1), kind=kOutput{_meta(_D + "/attn/attn_full/dot_general")}
  %all-gather.3 = bf16[32,2560]{{1,0}} all-gather(%q), channel_id=1, replica_groups=[2,2]<=[4], dimensions={{0}}
  %bitcast.7 = bf16[32,1,2560]{{2,1,0}} bitcast(%all-gather.3)
  %fusion.10 = bf16[32,2560]{{1,0}} fusion(%fusion.9, %bitcast.7), kind=kOutput{_meta(_T + "/layer/attn/dot_general")}
  %copy.6 = bf16[2,65,8,2,16]{{4,3,2,1,0}} copy(%pool)
  %pallas_paged_attention.3 = bf16[32,2560]{{1,0}} custom-call(%fusion.10, %copy.6), custom_call_target="tpu_custom_call"{_meta(_D + "/attn/pallas_paged_attention/pallas_call")}
  %constant.1 = s32[] constant(1)
  %add.99 = s32[] add(%constant.1, %constant.1)
  ROOT %tuple.1 = (bf16[32,2560]{{1,0}}, s32[]) tuple(%pallas_paged_attention.3, %add.99)
}}
"""


@pytest.mark.parametrize("case,name,expect,source", [
    # (i) a `.clone` fusion without metadata: its body's PRODUCT names the
    # group, not the two elementwise instructions of another scope
    ("product", "fusion.441.clone.1", ("layer/mlp", "fwd"), "body"),
    # (ii) no product in the body: the group most instructions name ...
    ("majority", "fusion.70", ("layer/ssm_conv", "fwd"), "body"),
    # ... and the innermost scope on a tie
    ("innermost", "fusion.80", ("layer/ssm_proj", "fwd"), "body"),
    # (iii) a relayout of a parameter: the group of what reads it
    ("bitcast_fusion", "fusion.352", ("layer/mlp", "fwd"), "user"),
    ("copy", "copy.5", ("layer/mlp", "fwd"), "user"),
    # (iv) an asynchronous pair: the done finds its reader, the start
    # its done
    ("copy-done", "copy-done.1", ("layer/attn_full", "fwd"), "user"),
    ("copy-start", "copy-start.1", ("layer/attn_full", "fwd"), "user"),
    # (v) a ZeRO gather, read through a bitcast, the pass included
    ("all-gather", "all-gather.3", ("layer/attn", "bwd"), "user"),
    # a kernel's row is its own instructions' alone: what the kernel
    # reads goes to the layer part around it
    ("kernel", "copy.6", ("layer/attn", "fwd"), "user"),
    # (vi) nothing places a loop counter; a parameter runs nothing
    ("residue", "add.99", (hp.UNSCOPED, "fwd"), "none"),
    ("parameter", "w", (hp.UNSCOPED, "fwd"), "none"),
    # what names its own scope is never moved
    ("own", "fusion.9", ("layer/attn_full", "fwd"), "own"),
])
def test_scope_map_resolves_what_the_compiler_left_unnamed(
        case, name, expect, source):
    assert hp.scope_map(RESOLVED)[name] == expect
    assert hp.scope_sources(RESOLVED)[name] == source


def test_a_gather_placed_by_its_reader_is_still_a_collective():
    """(v) in the benchmark's join: the group comes from the program,
    the kind from the operation's name."""
    _, index = bench_trace.scope_index(RESOLVED)
    short = next(k for k in index if k.startswith("all-gather.3"))
    assert index[short] == ("layer/attn", "bwd")
    assert bench_trace.COLLECTIVE.search(short)


def _own(text):
    """The parent's rule (PR 52): an instruction's own `op_name`, nothing
    else."""
    phases = (*hp.PHASES, *hp.SCOPE_MAP_GROUPS)
    out = {}
    for line in text.splitlines():
        m = hp.INSTR_PAT.match(line)
        if m:
            om = hp.OP_NAME_PAT.search(line)
            op = om.group(1) if om else ""
            g = hp.group_of(op, phases)
            out[m.group(1)] = (hp.UNSCOPED if g == "other" else g,
                               hp.pass_of(op))
    return out


def test_scope_map_moves_nothing_that_names_its_scope(train_step_text):
    """(vii) every instruction of the train step that named a scope
    itself maps exactly as before; only what the parent left `unscoped`
    may be placed, and every instruction ends in one group with one
    source."""
    new, src, old = (hp.scope_map(train_step_text),
                     hp.scope_sources(train_step_text), _own(train_step_text))
    assert set(new) == set(src) == set(old)
    assert {k: v for k, v in new.items() if src[k] in ("own", "none")} == \
        {k: v for k, v in old.items() if src[k] in ("own", "none")}
    assert all(old[k][0] == hp.UNSCOPED for k in new if new[k] != old[k])
    assert all((src[k] == "none") == (new[k][0] == hp.UNSCOPED) for k in new)
    assert {"own", "none"} <= set(src.values())
    # and a program every instruction of which names its scope is the
    # parent's map, key for key
    scoped = "\n".join(ln for ln in train_step_text.splitlines()
                       if (m := hp.INSTR_PAT.match(ln))
                       and src[m.group(1)] == "own")
    assert hp.scope_map(scoped) == _own(scoped)
    assert set(hp.scope_sources(scoped).values()) == {"own"}


def test_layer_table_places_an_instruction_where_scope_map_does():
    """The static profile takes its groups from the one resolver: the
    gather without a name moves its wire bytes into the layer that
    reads it, and `other` is what `scope_map` calls `unscoped`."""
    table = hp.layer_table(RESOLVED, phases=(*hp.PHASES,
                                             *hp.SCOPE_MAP_GROUPS),
                           default_world=4)
    assert table["layer/attn"]["wire_bytes"] > 0
    assert "other" in table and not table["other"]["wire_bytes"]
    assert "layer/attn/pallas_paged_attention" in table


def test_profile_record_says_how_the_map_knew():
    got = hp.profile_record(RESOLVED)["scope_sources"]
    assert got["body"] == {"instructions": 3, "groups": [
        "layer/mlp", "layer/ssm_conv", "layer/ssm_proj"]}
    assert got["user"]["instructions"] == 6
    assert hp.UNSCOPED in got["none"]["groups"]
    assert sum(r["instructions"] for r in got.values()) == len(
        hp.scope_map(RESOLVED))
