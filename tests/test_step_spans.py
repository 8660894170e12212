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
import glob
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
from hetu_tpu.utils.profiling import StepProfiler, phase_span

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
    assert sum(h.total for h in parts) == pytest.approx(whole.total,
                                                        rel=0.05)
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
# trainer
# ---------------------------------------------------------------------------

def _trainer(tmp_path=None, **cfg_kw):
    from hetu_tpu.engine import Trainer, TrainingConfig
    from hetu_tpu.parallel import ParallelStrategy
    st = ParallelStrategy()
    tc = TrainingConfig(global_batch_size=2, micro_batch_size=2, seq_len=32,
                        lr=1e-3, warmup_steps=0, total_steps=8,
                        log_every=10 ** 9,
                        **({"ckpt_dir": str(tmp_path),
                            "ckpt_every": 10 ** 9} if tmp_path else {}))
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
    return [m.group(1) for m in map(hp._INSTR_PAT.match, text.splitlines())
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
    assert {"pallas_flash_attention", "pallas_adam",
            "pallas_rotary"} <= kernels
    passes = collections.Counter(p for _, p in smap.values())
    assert passes["fwd"] and passes["bwd"] and passes["recompute"]
    # the flash kernel runs forward, again in the recomputed forward,
    # and backward: the join can tell the three apart
    flash = {p for g, p in smap.values()
             if g.endswith("pallas_flash_attention")}
    assert flash == {"fwd", "bwd", "recompute"}


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
    assert engine.decode_paged
    texts = {name: low.compile().as_text()
             for name, low in engine.lower_programs().items()}
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
