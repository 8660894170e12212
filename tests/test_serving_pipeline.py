"""The serving engine keeps one step queued on the device (tier-1, CPU).

`ServingEngine.step` dispatches step k's programs and only then fetches
decode k-1's tokens and step k's first tokens; where what comes next
needs values the host has not seen, the same loop drains first
(docs/serving.md, "Engine loop").  Held here, for a dense llama and the
tiny Kimi, Trinity and MiMo configurations: the results are those of the
same engine drained after every step; a length finish computes no row
past its end and an EOS discards exactly one; what `benchmarks/run.py`
reads of the scheduler at a step's return; the counters the mechanism
brings; and that each drain happens, is counted and changes no token.

The driver below steps an engine on a virtual clock of one second a
step, so that admissions and deadlines fall on the same steps in every
run.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from hetu_tpu import serving  # noqa: E402
from conftest import generate  # noqa: E402
from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel  # noqa: E402
from hetu_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from hetu_tpu.serving.request import SamplingParams, SLOClass  # noqa: E402

DRAINS = ("spec", "sampling", "preempt", "fail_over", "deadline", "brownout",
          "reshard", "adopt", "idle", "close", "test")


def _llama():
    model = LlamaLMHeadModel(LlamaConfig.tiny(
        remat=False, compute_dtype=jnp.float32, use_flash_attention=False))
    return model, model.init(jax.random.key(0))


def _family(module: str, config: str):
    import importlib
    fam = importlib.import_module(f"benchmarks.families.{module}")
    with open(os.path.join(ROOT, "benchmarks", "configs", config)) as f:
        cfg = json.load(f)
    del cfg["router_tie_logit"]
    model = fam.build_model(cfg, cfg["serving"])
    return model, model.init(jax.random.key(7))


FAMILIES = {
    "llama": _llama,
    "kimi": lambda: _family("kimi_k2", "tiny-kimi-k2.json"),
    "trinity": lambda: _family("afmoe", "tiny-trinity.json"),
    "mimo": lambda: _family("mimo_v2", "tiny-mimo.json"),
}


def _engine(model, params, registry=None, **serve):
    kw = dict(num_slots=4, page_size=8, max_len=128, prefill_chunk=16)
    kw.update(serve)
    return serving.ServingEngine(
        model, params, serving.ServeConfig(**kw),
        registry=registry or MetricsRegistry())


@pytest.fixture(scope="module", params=list(FAMILIES))
def served(request):
    """(engine, its registry, the model's vocabulary): one engine a
    family, its programs compiled once for the module."""
    model, params = FAMILIES[request.param]()
    registry = MetricsRegistry()
    return (_engine(model, params, registry), registry,
            model.config.vocab_size)


@pytest.fixture(scope="module")
def tiny_llama():
    return _llama()


def _requests(vocab, n=7, seed=3, gap=2.0, **kw):
    """Mixed prompt lengths (one to three chunks) and answer lengths,
    one arrival every `gap` steps: admissions and finishes in mid-run."""
    rng = np.random.default_rng(seed)
    return [serving.Request(
        rid=i, arrival_t=gap * i,
        prompt=rng.integers(1, vocab, size=int(rng.integers(3, 40))
                            ).astype(np.int32),
        max_new_tokens=int(rng.integers(1, 10)), **kw) for i in range(n)]


def drive(eng, requests, *, drained=False, at_step=None, max_steps=400):
    """Step `eng` over `requests` on a virtual clock (one second a
    step); `drained`: fetch everything after every step, as an engine
    that queues nothing would.  Returns the results in the order they
    were returned.  At every step's return: a slot past its prefill has
    its first token, and a request that is returned was in a slot until
    that step."""
    pending = sorted(requests, key=lambda r: (r.arrival_t, r.rid))
    done, step = [], 0
    sched = eng.scheduler
    while (pending or sched.active_slots() or sched.queue
           or eng._fault_results):
        now = float(step)
        while pending and pending[0].arrival_t <= now:
            eng.submit(pending.pop(0))
        if at_step is not None:
            at_step(step, now)
        seated = {sched.slots[i].request.rid for i in sched.active_slots()}
        parked = {r.rid for r in eng._fault_results}
        out = eng.step(now)
        for st in sched.slots:
            if st is not None and not st.prefilling:
                assert st.stats.first_token_t is not None
                assert len(st.generated) >= 1
        admitted = {sched.slots[i].request.rid for i in sched.active_slots()}
        for r in out:
            if r.finished_reason in ("length", "eos"):
                # in a slot until its last token was in a result (or
                # seated and finished within this step: one chunk, one
                # token)
                assert r.rid in seated | parked or len(r.tokens) == 1
            assert r.rid not in admitted
        done += out
        if drained:
            eng._drain("test", lambda: now)
        step += 1
        assert step < max_steps
    eng._drain("idle", lambda: float(step))
    assert eng._inflight is None
    return done


def _counts(registry):
    c = {name: registry.counter_value(name) for name in (
        "serve.decode_steps", "serve.decode_steps_overlapped",
        "serve.overrun_rows", "serve.decode_slot_steps", "serve.tokens_out")}
    c["drains"] = {w: registry.counter_value("serve.pipeline_drains", why=w)
                   for w in DRAINS}
    return c


def _diff(a, b):
    return {k: ({w: b[k][w] - a[k][w] for w in b[k]} if isinstance(b[k], dict)
                else b[k] - a[k]) for k in b}


def _summary(results):
    return [(r.rid, r.tokens, r.finished_reason) for r in results]


# ------------------------------------------------- the queued step itself

def test_results_equal_the_engine_drained_every_step(served):
    """Tokens, reasons and the ORDER of completion, with a slot for
    every request in flight (a request leaves its slot one step later
    with a step queued, so under contention for slots the order may
    differ: the next test)."""
    eng, registry, vocab = served
    c0 = _counts(registry)
    queued = drive(eng, _requests(vocab))
    c1 = _counts(registry)
    drained = drive(eng, _requests(vocab), drained=True)
    c2 = _counts(registry)
    assert _summary(queued) == _summary(drained)
    assert len(queued) == 7 and {r.finished_reason for r in queued} == {
        "length"}
    # the first run queued a step wherever it could; the second none
    q, d = _diff(c0, c1), _diff(c1, c2)
    assert q["serve.decode_steps_overlapped"] > 0
    assert d["serve.decode_steps_overlapped"] == 0
    assert d["drains"]["test"] == d["serve.decode_steps"]
    assert q["serve.decode_slot_steps"] == d["serve.decode_slot_steps"]


def test_more_requests_than_slots_same_tokens(served):
    eng, registry, vocab = served
    reqs = lambda: _requests(vocab, n=12, seed=5, gap=0.0)  # noqa: E731
    queued = drive(eng, reqs())
    drained = drive(eng, reqs(), drained=True)
    assert sorted(_summary(queued)) == sorted(_summary(drained))
    assert len(queued) == 12
    eng.scheduler.check_invariants()
    assert eng.pool.utilization == 0.0


def test_a_length_finish_computes_no_row_past_its_end(served):
    eng, registry, vocab = served
    reqs = _requests(vocab, n=9, seed=11, gap=1.0)
    c0 = _counts(registry)
    out = drive(eng, reqs)
    c = _diff(c0, _counts(registry))
    assert all(len(r.tokens) == q.max_new_tokens
               for r, q in zip(sorted(out, key=lambda r: r.rid), reqs))
    assert c["serve.overrun_rows"] == 0
    # a request's first token is its prompt's; every other is one row
    assert c["serve.decode_slot_steps"] == sum(
        q.max_new_tokens - 1 for q in reqs)
    assert c["serve.tokens_out"] == sum(q.max_new_tokens for q in reqs)


def test_overlapped_is_decode_steps_less_the_drains(served):
    eng, registry, vocab = served
    c0 = _counts(registry)
    drive(eng, _requests(vocab, n=8, seed=13, gap=3.0))
    c = _diff(c0, _counts(registry))
    assert c["serve.decode_steps"] > c["serve.decode_steps_overlapped"] > 0
    assert c["serve.decode_steps"] - c["serve.decode_steps_overlapped"] == \
        sum(c["drains"].values())
    # nothing but gaps in the arrivals drained this run
    assert sum(c["drains"].values()) == c["drains"]["idle"]
    assert registry.counter_value("serve.fetch_wait_total_s") > 0


def test_an_eos_discards_one_row_and_leaves_the_neighbours_intact(served):
    """The stream's j-th token as EOS: seen one fetch late, the row
    dispatched behind it is computed and discarded; the neighbours
    decoding beside it, and the request that is admitted into the pages
    it frees, generate what they generate alone."""
    eng, registry, vocab = served
    rng = np.random.default_rng(17)
    prompts = [rng.integers(1, vocab, size=n).astype(np.int32)
               for n in (21, 9, 30, 12, 18)]

    def reqs(eos=None):
        return [serving.Request(
            rid=i, prompt=p, max_new_tokens=9, arrival_t=float(i),
            eos_token_id=eos if i == 1 else None)
            for i, p in enumerate(prompts)]
    alone = {r.rid: r.tokens for r in drive(eng, reqs())}
    # a token the stream has not shown before, in mid-stream
    stream = alone[1]
    j = next(j for j in range(1, 7) if stream[j] not in stream[:j])
    c0 = _counts(registry)
    out = {r.rid: r for r in drive(eng, reqs(eos=stream[j]))}
    c = _diff(c0, _counts(registry))
    assert out[1].finished_reason == "eos"
    assert out[1].tokens == stream[:j + 1]
    assert c["serve.overrun_rows"] == 1
    for rid in (0, 2, 3, 4):
        assert out[rid].tokens == alone[rid], rid
        assert out[rid].finished_reason == "length"
    eng.scheduler.check_invariants()


def test_run_and_close_leave_nothing_unfetched(served):
    eng, registry, vocab = served
    reqs = _requests(vocab, n=5, seed=19, gap=0.0)
    for r in reqs:
        r.arrival_t = 0.0
    out = eng.run(reqs)
    assert eng._inflight is None
    assert [len(r.tokens) for r in out] == [q.max_new_tokens for q in reqs]
    # a step queued, then closed: fetched, counted, the finish parked
    eng.submit(serving.Request(rid=99, max_new_tokens=2,
                               prompt=np.arange(1, 6, dtype=np.int32)))
    while eng._inflight is None:
        eng.step(0.0)
    closes = registry.counter_value("serve.pipeline_drains", why="close")
    eng.close()
    assert eng._inflight is None
    assert registry.counter_value("serve.pipeline_drains",
                                  why="close") == closes + 1
    assert [r.rid for r in eng.step(1.0)] == [99]


# --------------------------------------------------------------- the drains

def _drains(registry, why):
    return registry.counter_value("serve.pipeline_drains", why=why)


def _reference(model, params, req):
    out = generate(model, params, jnp.asarray(req.prompt)[None],
                   max_new_tokens=req.max_new_tokens)
    return [int(t) for t in np.asarray(out)[0][req.prompt_len:]]


@pytest.mark.parametrize("mode", ["ngram", "model"])
def test_speculative_decoding_drains_every_step(tiny_llama, mode):
    """The accepted count sets the next positions: nothing is queued."""
    model, params = tiny_llama
    vocab = model.config.vocab_size
    kw = {}
    if mode == "model":
        draft = LlamaLMHeadModel(LlamaConfig.tiny(
            vocab_size=vocab, hidden_size=32, intermediate_size=64,
            num_hidden_layers=1, num_attention_heads=2,
            num_key_value_heads=1, remat=False, compute_dtype=jnp.float32,
            use_flash_attention=False))
        kw = dict(draft_model=draft, draft_params=draft.init(
            jax.random.key(7)))
    registry = MetricsRegistry()
    eng = serving.ServingEngine(
        model, params, serving.ServeConfig(
            num_slots=3, page_size=8, max_len=64, prefill_chunk=8,
            sampling=mode == "model", spec_decode=mode, spec_k=2),
        registry=registry, **kw)
    reqs = _requests(vocab, n=4, seed=23, gap=1.0)
    out = drive(eng, reqs)
    for r in out:
        assert r.tokens == _reference(model, params, reqs[r.rid]), r.rid
    steps = registry.counter_value("serve.decode_steps")
    assert steps > 0 and _drains(registry, "spec") == steps
    assert registry.counter_value("serve.decode_steps_overlapped") == 0


def test_a_sampled_first_token_drains(tiny_llama):
    """The seeded sampler draws a first token on the host, behind what
    is queued: the wait is a drain by name; a greedy request on the same
    engine takes the chunk program's argmax and drains nothing; the
    tokens are those of the engine drained every step."""
    model, params = tiny_llama
    vocab = model.config.vocab_size
    registry = MetricsRegistry()
    eng = _engine(model, params, registry, sampling=True)

    def reqs(sampled):
        rs = _requests(vocab, n=5, seed=29, gap=2.0)
        for r in rs:
            if sampled and r.rid % 2:
                r.sampling = SamplingParams(temperature=0.8, top_k=12,
                                            seed=70 + r.rid)
        return rs
    greedy = drive(eng, reqs(False))
    assert _drains(registry, "sampling") == 0
    for r in greedy:
        assert r.tokens == _reference(model, params, reqs(False)[r.rid])
    queued = drive(eng, reqs(True))
    # requests 1 and 3 arrive while request 0 / 2 decode
    assert _drains(registry, "sampling") >= 1
    assert _summary(queued) == _summary(drive(eng, reqs(True), drained=True))
    assert any(a.tokens != b.tokens for a, b in zip(
        sorted(queued, key=lambda r: r.rid),
        sorted(greedy, key=lambda r: r.rid)))


def _classes(vocab, deadline_s=None):
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, vocab, size=8).astype(np.int32)
               for _ in range(3)]
    bulk = SLOClass("bulk", deadline_s=deadline_s)
    return [serving.Request(rid=0, prompt=prompts[0], max_new_tokens=20,
                            slo=bulk),
            serving.Request(rid=1, prompt=prompts[1], max_new_tokens=20,
                            slo=bulk),
            serving.Request(rid=2, prompt=prompts[2], max_new_tokens=4,
                            slo=SLOClass("gold", priority=2),
                            arrival_t=5.0)]


def test_a_preemption_drains_first(tiny_llama):
    """The victim leaves with every token it was given: `preempt`
    events and results are those of the engine drained every step, and
    the requeued request regenerates its stream."""
    model, params = tiny_llama
    vocab = model.config.vocab_size
    runs = {}
    for drained in (False, True):
        registry = MetricsRegistry()
        eng = _engine(model, params, registry, num_slots=2, preempt=True)
        runs[drained] = (drive(eng, _classes(vocab), drained=drained),
                         registry, eng.scheduler.preempted)
    (queued, registry, n), (drained, _, m) = runs[False], runs[True]
    assert n == m >= 1 and _drains(registry, "preempt") >= 1
    assert _summary(queued) == _summary(drained)
    for r, q in zip(sorted(queued, key=lambda r: r.rid), _classes(vocab)):
        assert r.tokens == _reference(model, params, q), r.rid


def test_a_deadline_that_expires_a_live_slot_drains_first(tiny_llama):
    """The expired request is returned with the tokens of every decode
    dispatched before its deadline."""
    model, params = tiny_llama
    vocab = model.config.vocab_size
    runs = {}
    for drained in (False, True):
        registry = MetricsRegistry()
        eng = _engine(model, params, registry, num_slots=2, deadline=True)
        runs[drained] = drive(eng, _classes(vocab, deadline_s=9.5)[:2],
                              drained=drained), registry
    (queued, registry), (drained, _) = runs[False], runs[True]
    assert _drains(registry, "deadline") == 1
    assert _summary(queued) == _summary(drained)
    assert {r.finished_reason for r in queued} == {"deadline_exceeded"}
    for r, q in zip(sorted(queued, key=lambda r: r.rid), _classes(vocab)):
        # admitted at step 0, one chunk, the first token at step 0's end,
        # a decode dispatched in each of steps 1-9, expired in step 10
        assert r.tokens == _reference(model, params, q)[:10], r.rid
    assert registry.counter_value("serve.overrun_rows") == 0


def test_fail_over_drains_first(tiny_llama):
    """The replica dies with a step queued: it is fetched first, so a
    request over its retry budget ends with every token it was given,
    and a requeued one regenerates its stream."""
    model, params = tiny_llama
    vocab = model.config.vocab_size
    runs = {}
    for drained in (False, True):
        registry = MetricsRegistry()
        eng = _engine(model, params, registry, num_slots=2, retry_budget=1)

        def kill(step, now, eng=eng):
            if step in (6, 12):
                eng.fail_over(now)
        runs[drained] = drive(eng, _classes(vocab)[:2], drained=drained,
                              at_step=kill), registry
    (queued, registry), (drained, _) = runs[False], runs[True]
    assert _drains(registry, "fail_over") == 2
    assert _summary(queued) == _summary(drained)
    assert {r.finished_reason for r in queued} == {"retry_exhausted"}
    assert all(len(r.tokens) >= 1 for r in queued)
    for r, q in zip(sorted(queued, key=lambda r: r.rid), _classes(vocab)):
        assert r.tokens == _reference(model, params, q)[:len(r.tokens)]


def test_adopt_prefilled_drains_first(tiny_llama):
    """A prefill-tier shipment is written from the host with nothing
    queued; the adopted stream and the one decoding beside it are the
    colocated engine's."""
    from hetu_tpu.serving.disagg import PrefillWorker
    model, params = tiny_llama
    vocab = model.config.vocab_size
    a, b = _classes(vocab)[:2]
    registry = MetricsRegistry()
    eng = _engine(model, params, registry, num_slots=2)
    tier = PrefillWorker(model, params, prefill_chunk=16, max_len=128)
    tier.submit(b)
    (req, _, t1, ks, vs), = tier.step()

    def adopt(step, now):
        if step == 4:
            assert eng._inflight is not None
            assert eng.adopt_prefilled(req, ks, vs, t1, now)
            assert eng._inflight is None
    out = drive(eng, [a], at_step=adopt)
    assert _drains(registry, "adopt") == 1
    assert registry.counter_value("serve.disagg_adoptions") == 1
    for r, q in zip(sorted(out, key=lambda r: r.rid), (a, b)):
        assert r.tokens == _reference(model, params, q), r.rid
