"""Serving-engine tests (tier-1, CPU, seeded): scheduler/pool invariants
under churn, paged-cache correctness, quantized-page parity, and the
continuous-batching engine's token-for-token equivalence with the
sequential `generate()` path — plus the 16-request staggered-arrival
acceptance run with SLO metrics in the RunLog."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import generate
from hetu_tpu import serving
from hetu_tpu.models.generation import prefill, decode_step
from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.obs.metrics import MetricsRegistry
from hetu_tpu.obs.runlog import RunLog
from hetu_tpu.serving.kv_pool import (PagePool, kv_bytes_per_token,
                                      quantize_heads, dequantize_heads)
from hetu_tpu.serving.request import Request
from hetu_tpu.serving.scheduler import Scheduler


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = LlamaConfig.tiny(remat=False, compute_dtype=jnp.float32,
                           use_flash_attention=False)
    model = LlamaLMHeadModel(cfg)
    return model, model.init(jax.random.key(0))


def _pool(num_pages=16, page_size=4, quant="none"):
    return PagePool(num_layers=2, num_pages=num_pages, page_size=page_size,
                    num_kv_heads=2, head_dim=16, quant=quant)


def _engine(model, params, registry=None, run_log=None, **cfg_kw):
    kw = dict(num_slots=3, page_size=8, max_len=64, prefill_chunk=8)
    kw.update(cfg_kw)
    return serving.ServingEngine(
        model, params, serving.ServeConfig(**kw),
        registry=registry or MetricsRegistry(), run_log=run_log)


def launches(reg):
    """{rows: launches of the chunk program that carried so many}
    (`serve.prefill_launches{rows}`)."""
    return {int(c["labels"]["rows"]): int(c["value"])
            for c in reg.snapshot()["counters"]
            if c["name"] == "serve.prefill_launches"}


# ---------------------------------------------------------------- pool
def test_pool_alloc_free_recycle():
    pool = _pool(num_pages=6)
    a = pool.alloc(3)
    b = pool.alloc(3)
    assert a is not None and b is not None
    assert not (set(a) & set(b)), "allocations alias"
    assert PagePool.NULL_PAGE not in a + b
    assert pool.alloc(1) is None, "overcommitted pool"
    pool.free(a)
    c = pool.alloc(2)
    assert set(c) <= set(a), "free list does not recycle"
    with pytest.raises(ValueError):
        pool.free(a[:1] if a[0] in pool._free else a)  # double free
    with pytest.raises(ValueError):
        pool.free([0])                                 # null page


def test_kv_bytes_analytic():
    # the acceptance ratio: blockwise-int8 pages vs the fp32 exact cache
    # at bench head_dim=128 is >= 3.5x; vs fp16 ~1.94x
    fp32 = kv_bytes_per_token(12, 12, 128, "fp32")
    int8 = kv_bytes_per_token(12, 12, 128, "int8")
    assert fp32 / int8 >= 3.5
    fp16 = kv_bytes_per_token(12, 12, 128, "fp16")
    assert 1.8 <= fp16 / int8 <= 2.0
    # nibble-packed int4 pages: >= 7x smaller than fp32 (the ISSUE
    # floor; 7.53x at head_dim 128 with the per-head f32 scale counted)
    int4 = kv_bytes_per_token(12, 12, 128, "int4")
    assert fp32 / int4 >= 7.0
    assert 1.8 <= int8 / int4 <= 2.0
    with pytest.raises(ValueError):
        kv_bytes_per_token(2, 2, 16, "fp8")


def test_quantize_heads_int4_roundtrip_error_bound():
    """bits=4 packs two codes per byte: payload is [..., head_dim//2]
    uint8, round-trip error within the 4-bit grid (absmax/14)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 5, 3, 16)) * 3.0, jnp.float32)
    q, s = quantize_heads(x, bits=4)
    assert q.shape == (2, 5, 3, 8) and q.dtype == jnp.uint8
    back = dequantize_heads(q, s, bits=4)
    bound = np.abs(np.asarray(x)).max(axis=-1, keepdims=True) / 14.0 + 1e-6
    assert (np.abs(np.asarray(back) - np.asarray(x)) <= bound).all()


def test_quantize_heads_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 5, 3, 16)) * 3.0, jnp.float32)
    q, s = quantize_heads(x)
    back = dequantize_heads(q, s)
    # blockwise absmax grid: error <= scale/2 = absmax/254 per element
    bound = np.abs(np.asarray(x)).max(axis=-1, keepdims=True) / 254.0 + 1e-6
    assert (np.abs(np.asarray(back) - np.asarray(x)) <= bound).all()


# ----------------------------------------------------------- scheduler
def test_scheduler_admit_evict_fuzz_invariants():
    """Randomized arrival/EOS churn — now with random engine kills
    (requeue_lost under a retry budget / retry_exhausted past it),
    deadline expiries, brownout sheds of queued requests, AND
    disaggregated shipment churn (apply/unapply, duplicate deliveries,
    out-of-order redeliveries, late dups after finish): the memory
    invariants (no page aliasing — a double-delivered shipment NEVER
    allocates, exact live+free partition, table mirrors, retry counts
    within budget, refcounts exact after a requeue) hold after every
    transition — AND so do the flight recorder's span-event invariants
    (a RequestTracer rides the same churn): every terminated request
    ends with exactly one terminal span, spans are ordered/
    non-overlapping, queued spans carry a reserve-on-admit stall
    reason — and every churned request yields a STITCHABLE FleetTrace
    with exact per-attempt tiling."""
    from hetu_tpu.serving.tracing import RequestTracer
    rng = np.random.default_rng(7)
    pool = _pool(num_pages=10, page_size=4)
    sched = Scheduler(num_slots=3, pool=pool, max_len=16,
                      retry_budget=2)
    tracer = RequestTracer()
    rid = 0
    finished: set = set()
    requeues = 0
    now = 0.0
    # disagg shipment books: channel-global seq, deliveries whose
    # adoption stalled (awaiting redelivery), live adopted (rid, seq)
    ship_seq = 0
    pending: list = []              # (req, seq) awaiting redelivery
    adopted_seq: dict = {}          # live rid -> its adopted seq
    adoptions = redeliveries = dup_refused = late_dups = 0

    def adopt(req, seq):
        """Deliver one shipment through the real dedupe gate; False
        leaves it in `pending` for an out-of-order redelivery."""
        nonlocal adoptions, dup_refused
        if not sched.apply_shipment(req.rid, seq):
            return False
        adm = sched.admit_direct(req, now)
        if adm is None:
            # no capacity: un-burn the seq so the SAME delivery can
            # retry later without counting as a dedupe
            sched.unapply_shipment(req.rid, seq)
            tracer.on_stall([req.rid], sched.last_stall or "none")
            pending.append((req, seq))
            return False
        slot_idx, st = adm
        st.pos = req.prompt_len          # shipped KV: no local prefill
        adoptions += 1
        adopted_seq[req.rid] = seq
        tracer.on_admit(req, slot_idx, now)
        tracer.on_first_token(req, slot_idx, now, chunk=0)
        # an immediate duplicate of the same seq must be refused — the
        # second delivery never touches the pool (no aliasing; the
        # invariant sweep below would catch it)
        assert not sched.apply_shipment(req.rid, seq)
        dup_refused += 1
        return True

    for _ in range(400):
        now += 0.01                      # strictly monotone fake clock
        op = rng.random()
        if op < 0.34:
            plen = int(rng.integers(1, 10))
            mnew = int(rng.integers(1, 16 - plen + 1))
            req = Request(rid=rid, prompt=np.ones(plen, np.int32),
                          max_new_tokens=mnew, arrival_t=now)
            sched.submit(req)
            tracer.on_submit(req)
            rid += 1
        elif op < 0.44:
            # a fresh KV shipment lands from the prefill tier: the
            # request bypasses the FIFO queue via admit_direct
            plen = int(rng.integers(1, 10))
            mnew = int(rng.integers(1, 16 - plen + 1))
            req = Request(rid=rid, prompt=np.ones(plen, np.int32),
                          max_new_tokens=mnew, arrival_t=now)
            tracer.on_submit(req)
            ship_seq += 1
            adopt(req, ship_seq)
            rid += 1
        elif op < 0.64:
            adm = sched.admit_next(now=now)
            if adm is not None:
                slot_idx, st = adm
                st.pos = st.request.prompt_len   # prefill done
                tracer.on_admit(st.request, slot_idx, now)
                tracer.on_first_token(st.request, slot_idx, now, chunk=1)
            elif sched.queue:
                assert sched.last_stall in ("no_slot", "no_pages")
                tracer.on_stall([r.rid for r in sched.queue],
                                sched.last_stall)
        elif op < 0.70:
            # out-of-order redelivery of a stalled shipment; sometimes
            # the sender timed out first and re-sent under a FRESH seq
            if pending:
                req, seq = pending.pop(int(rng.integers(len(pending))))
                if rng.random() < 0.3:
                    ship_seq += 1
                    seq = ship_seq
                redeliveries += 1
                adopt(req, seq)
        elif op < 0.80:
            # replica death on a random live slot: requeue under the
            # budget, terminate retry_exhausted past it
            live = sched.active_slots()
            if live:
                i = int(rng.choice(live))
                st = sched.slots[i]
                req = st.request
                if sched.retries.get(req.rid, 0) < 2:
                    sched.requeue_lost(i)
                    tracer.on_replica_lost(req, i, now)
                    requeues += 1
                else:
                    sched.release(i)
                    tracer.on_finish(req, i, "retry_exhausted", now,
                                     tokens=0,
                                     e2e_s=now - req.arrival_t,
                                     evicted=True)
                    sched.retries.pop(req.rid, None)
                    adopted_seq.pop(req.rid, None)
                    sched.ship_forget(req.rid)
                    finished.add(req.rid)
        elif op < 0.88:
            # deadline expiry / brownout shed of a random queued request
            if sched.queue:
                req = sched.queue[int(rng.integers(len(sched.queue)))]
                assert sched.drop_queued(req)
                sched.retries.pop(req.rid, None)
                if rng.random() < 0.5:
                    tracer.on_expire(req, now, e2e_s=now - req.arrival_t)
                else:
                    tracer.on_shed(req, now)
                finished.add(req.rid)
        else:
            live = sched.active_slots()
            if live:
                i = int(rng.choice(live))        # random EOS evict
                st = sched.slots[i]
                tracer.on_token(st.request, now)
                sched.release(i)
                sched.retries.pop(st.request.rid, None)
                tracer.on_finish(st.request, i, "eos", now,
                                 tokens=1, e2e_s=now - st.request.arrival_t)
                finished.add(st.request.rid)
                seq = adopted_seq.pop(st.request.rid, None)
                if seq is not None:
                    sched.ship_forget(st.request.rid)
                    # a LATE duplicate of a finished request's shipment
                    # still hits the dedupe gate (the seq set outlives
                    # the per-rid apply history)
                    assert not sched.apply_shipment(st.request.rid, seq)
                    late_dups += 1
        sched.check_invariants()
    assert requeues > 0, "fuzz never exercised requeue_lost"
    assert adoptions > 0, "fuzz never adopted a shipment"
    assert dup_refused > 0 and late_dups > 0
    assert redeliveries > 0, "fuzz never redelivered a stalled shipment"
    # drain: everything releasable, pool fully recovered
    now += 0.01
    for i in sched.active_slots():
        st = sched.slots[i]
        sched.release(i)
        sched.retries.pop(st.request.rid, None)
        adopted_seq.pop(st.request.rid, None)
        sched.ship_forget(st.request.rid)
        tracer.on_finish(st.request, i, "eos", now,
                         tokens=0, e2e_s=now - st.request.arrival_t)
        finished.add(st.request.rid)
    # stalled shipments redeliver cleanly into the drained fleet
    for req, seq in list(pending):
        now += 0.01
        assert sched.apply_shipment(req.rid, seq)
        adm = sched.admit_direct(req, now)
        assert adm is not None, "drained fleet must adopt the backlog"
        slot_idx, st = adm
        st.pos = req.prompt_len
        tracer.on_admit(req, slot_idx, now)
        tracer.on_first_token(req, slot_idx, now, chunk=0)
        sched.release(slot_idx)
        sched.ship_forget(req.rid)
        tracer.on_finish(req, slot_idx, "eos", now, tokens=0,
                         e2e_s=now - req.arrival_t)
        finished.add(req.rid)
        sched.check_invariants()
    sched.check_invariants()
    assert pool.free_count == pool.num_pages

    # span-event invariants over the whole churn
    assert set(tracer.traces) == finished, \
        "every terminated request must end in exactly one terminal span"
    for tr in tracer.traces.values():
        tr.validate()        # ordered, non-overlapping, queued reason,
        #                      exactly one terminal
        assert tr.reconcile(tr.terminal.attrs["e2e_s"]) <= 1e-9
    # still-queued requests hold open queued spans, not traces
    assert set(tracer.open_requests()) == {r.rid for r in sched.queue}

    # ...and every churned request STITCHES: dup/late-dup/unapply/
    # requeue traffic still assembles into a validated FleetTrace —
    # exactly one client terminal, no orphan hops, per-attempt tiling
    # exact (the fake clock has no step quantum to hide gaps behind)
    from hetu_tpu.obs.spans import FleetTrace
    fts = FleetTrace.stitch(traces=tracer.completed)
    assert set(fts) == finished
    for ft in fts.values():
        ft.validate(step_quantum=0.0)
    assert any(len(ft.primary.attempts()) > 1 for ft in fts.values()), \
        "fuzz never stitched a multi-attempt (requeued) trace"


def test_scheduler_rejects_impossible_requests():
    pool = _pool(num_pages=4, page_size=4)
    sched = Scheduler(num_slots=2, pool=pool, max_len=16)
    with pytest.raises(ValueError):   # beyond max_len
        sched.submit(Request(rid=0, prompt=np.ones(10, np.int32),
                             max_new_tokens=10))
    with pytest.raises(ValueError):   # can never fit the pool
        sched = Scheduler(num_slots=2, pool=_pool(num_pages=2, page_size=4),
                          max_len=16)
        sched.submit(Request(rid=1, prompt=np.ones(8, np.int32),
                             max_new_tokens=8))


def test_page_reservation_gates_admission():
    """Admission waits for the FULL reservation; released pages unblock
    the queue head (free-list recycling)."""
    pool = _pool(num_pages=4, page_size=4)
    sched = Scheduler(num_slots=2, pool=pool, max_len=16)
    sched.submit(Request(rid=0, prompt=np.ones(6, np.int32),
                         max_new_tokens=6))   # 3 pages
    sched.submit(Request(rid=1, prompt=np.ones(6, np.int32),
                         max_new_tokens=6))   # 3 pages
    s0 = sched.admit_next(0.0)
    assert s0 is not None
    assert sched.admit_next(0.0) is None, "admitted without pages"
    assert sched.queue_depth == 1
    sched.release(s0[0])
    assert sched.admit_next(0.0) is not None
    sched.check_invariants()


# -------------------------------------------------------------- engine
@pytest.mark.parametrize("others", [0, 1], ids=["alone", "shared"])
def test_chunked_prefill_interleaves_with_decode(tiny_llama, others):
    """Prefill/decode disaggregation contract: a step computes ONE
    chunk's prompt rows a prefilling slot, so a multi-chunk prompt
    prefilling alone advances one chunk per engine step, while
    already-running slots keep producing a token every step — a long
    admission never stalls the decode batch.  With another prompt in
    prefill beside it (`shared`) the step's rows, two chunks', go to the
    OLDER slot first, in one launch: it advances two chunks a step and
    the younger waits, then prefills alone."""
    model, params = tiny_llama
    eng = _engine(model, params, num_slots=3, prefill_chunk=8)
    short = Request(rid=0, prompt=np.arange(1, 5, dtype=np.int32),
                    max_new_tokens=12)
    long = Request(rid=1, prompt=np.arange(1, 25, dtype=np.int32),
                   max_new_tokens=4)    # 24 tokens = 3 chunks of 8
    eng.submit(short, now=0.0)
    eng.step(0.0)    # short: prefill completes, its first token fetched
    st0 = eng.scheduler.slots[0]
    assert not st0.prefilling and len(st0.generated) == 1
    eng.step(0.5)    # its first decode is dispatched, and stays queued
    assert len(st0.generated) == 1 and st0.inflight == 1
    eng.submit(long, now=1.0)
    if others:       # admitted behind it, in the same step
        eng.submit(Request(rid=2, prompt=np.arange(3, 22, dtype=np.int32),
                           max_new_tokens=2), now=1.0)
    #: (the long prompt's, the other's) chunks done after each step
    want = {0: [(1, 0), (2, 0), (3, 0)],
            1: [(2, 0), (3, 1), (3, 2), (3, 3)]}[others]
    for k, (mine, theirs) in enumerate(want, start=1):
        eng.step(float(k))
        st1 = eng.scheduler.slots[1]
        assert st1.chunks_done == mine and st1.prefilling == (mine < 3)
        if others:
            st2 = eng.scheduler.slots[2]
            assert st2.chunks_done == theirs
            assert st2.prefilling == (theirs < 3)
        # ...while the short request gained a token EVERY step (the one
        # dispatched the step before), with the next one queued
        assert len(st0.generated) == 1 + k and st0.inflight == 1
    assert launches(eng._registry) == (
        {8: 5, 16: 1} if others else {8: 4})
    # both finish cleanly and the long one's tokens match generate()
    results = []
    now = 5.0
    while eng.scheduler.active_slots():
        results.extend(eng.step(now))
        now += 1.0
    gold = generate(model, params, jnp.asarray(long.prompt[None]),
                    max_new_tokens=4)
    long_res = next(r for r in results if r.rid == 1)
    assert long_res.tokens == list(np.asarray(gold)[0, 24:])
    assert eng.pool.free_count == eng.pool.num_pages


def test_engine_eos_stops_and_recycles(tiny_llama):
    """A request whose first greedy token is its EOS finishes at TTFT,
    its pages recycle, and generate() agrees on the token."""
    model, params = tiny_llama
    prompt = np.array([1, 2, 3], np.int32)
    logits, _ = prefill(model, params, jnp.asarray(prompt[None]), max_len=8)
    eos = int(jnp.argmax(logits[0]))
    eng = _engine(model, params)
    res = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=10,
                           eos_token_id=eos)])
    assert res[0].finished_reason == "eos"
    assert res[0].tokens == [eos]
    assert eng.pool.free_count == eng.pool.num_pages


def test_quantized_cache_decode_parity(tiny_llama):
    """int8 paged decode stays within quantization tolerance of the fp
    path: same prefix, one decode step, logits close; and the engine's
    int8 run completes with the exact same first tokens (prefill is
    exact in both modes)."""
    model, params = tiny_llama
    prompt = jnp.asarray(np.random.default_rng(3).integers(
        0, 256, (1, 12)), jnp.int32)
    logits_fp, cache = prefill(model, params, prompt, max_len=16)
    ck, cv = cache
    qk, sk = quantize_heads(ck)
    qv, sv = quantize_heads(cv)
    cache_q = (dequantize_heads(qk, sk).astype(ck.dtype),
               dequantize_heads(qv, sv).astype(cv.dtype))
    tok = jnp.argmax(logits_fp, -1).astype(jnp.int32)
    out_fp, _ = decode_step(model, params, tok, cache, 12)
    out_q, _ = decode_step(model, params, tok, cache_q, 12)
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_fp),
                               atol=0.15, rtol=0.05)

    reqs = serving.synthetic_requests(4, vocab_size=256, prompt_lens=(3, 12),
                                      max_new=(2, 5), seed=4)
    eng_fp = _engine(model, params)
    eng_q = _engine(model, params, kv_quant="int8")
    res_fp = eng_fp.run([Request(**r.__dict__) for r in reqs])
    res_q = eng_q.run(reqs)
    assert len(res_q) == len(res_fp) == 4
    for a, b in zip(res_fp, res_q):
        assert a.tokens[0] == b.tokens[0], "exact prefill must agree"


def test_no_cross_sequence_leakage(tiny_llama):
    """A sequence decoded alongside a full batch of other sequences gets
    the same tokens as decoded alone — slots cannot read each other's
    pages (the device-side aliasing check)."""
    model, params = tiny_llama
    rng = np.random.default_rng(9)
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, size=5 + i).astype(
        np.int32), max_new_tokens=6) for i in range(3)]
    eng = _engine(model, params, num_slots=3)
    batch = eng.run(reqs)
    for i, req in enumerate(reqs):
        solo_eng = _engine(model, params, num_slots=3)
        solo = solo_eng.run([Request(rid=req.rid, prompt=req.prompt,
                                     max_new_tokens=req.max_new_tokens)])
        assert batch[i].tokens == solo[0].tokens


def test_reshard_hook_fires_on_load(tiny_llama):
    """The Hetis hook: queue-depth tier changes re-shard the serving
    params through the hot-switch machinery (and back), without
    perturbing the token stream."""
    from hetu_tpu.core.mesh import MeshConfig
    from hetu_tpu.parallel.strategy import ParallelStrategy
    model, params = tiny_llama
    mgr = serving.LoadAdaptiveMesh(
        lambda st: model,
        [(0, ParallelStrategy(mesh=MeshConfig(dp=1, tp=1))),
         (3, ParallelStrategy(mesh=MeshConfig(dp=1, tp=1)))],
        patience=1)
    reqs = serving.synthetic_requests(8, vocab_size=256, prompt_lens=(3, 6),
                                      max_new=(3, 6), seed=5)
    eng = serving.ServingEngine(
        model, params,
        serving.ServeConfig(num_slots=1, page_size=8, max_len=32,
                            prefill_chunk=8),
        registry=MetricsRegistry(), reshard=mgr)
    results = eng.run(reqs)
    assert len(results) == 8
    assert mgr.reshards >= 2, "never scaled up and back down"
    assert mgr.active_tier == 0, "drained queue should settle at tier 0"
    # token stream identical to a hook-less run
    plain = serving.ServingEngine(
        model, params,
        serving.ServeConfig(num_slots=1, page_size=8, max_len=32,
                            prefill_chunk=8),
        registry=MetricsRegistry())
    plain_res = plain.run(serving.synthetic_requests(
        8, vocab_size=256, prompt_lens=(3, 6), max_new=(3, 6), seed=5))
    assert [r.tokens for r in results] == [r.tokens for r in plain_res]


def test_traces_seeded_and_shaped():
    a = serving.poisson_arrivals(32, 10.0, seed=1)
    b = serving.poisson_arrivals(32, 10.0, seed=1)
    np.testing.assert_array_equal(a, b)
    assert (np.diff(a) >= 0).all() and a[0] == 0.0
    c = serving.bursty_arrivals(32, 10.0, burst=4, seed=1)
    assert (np.diff(c) >= 0).all() and len(c) == 32
    # bursts are tight: within-burst gaps are tiny vs between-burst gaps
    gaps = np.diff(c)
    assert np.median(gaps) < np.max(gaps) / 10
    with pytest.raises(ValueError):
        serving.poisson_arrivals(4, 0.0)
    with pytest.raises(ValueError):
        serving.synthetic_requests(3, vocab_size=16, arrivals=np.zeros(2))


def test_serve_config_validation(tiny_llama):
    model, params = tiny_llama
    with pytest.raises(ValueError):
        serving.ServeConfig(page_size=16, max_len=40)   # not a multiple
    with pytest.raises(ValueError):   # beyond the model context
        serving.ServingEngine(model, params, serving.ServeConfig(
            num_slots=1, page_size=16, max_len=512))
    with pytest.raises(ValueError):   # chunk padding would overrun scratch
        serving.ServeConfig(page_size=8, max_len=40, prefill_chunk=16)
    with pytest.raises(ValueError):   # unknown quant mode
        serving.ServeConfig(kv_quant="int3")
    cfg = serving.ServeConfig(num_slots=4, page_size=16, max_len=64)
    assert cfg.num_pages == 4 * 4    # full reservation default


def test_acceptance_16_requests_staggered(tiny_llama, tmp_path):
    """THE acceptance run: 16 seeded staggered arrivals through the
    engine — every request completes, SLO metrics land in the registry
    and as RunLog `serve` events, and tools_obs_report summarizes
    them."""
    model, params = tiny_llama
    log_path = str(tmp_path / "serve.jsonl")
    run_log = RunLog(log_path)
    registry = MetricsRegistry()
    arrivals = serving.poisson_arrivals(16, 30.0, seed=11)
    reqs = serving.synthetic_requests(
        16, vocab_size=256, prompt_lens=(3, 24), max_new=(2, 10),
        arrivals=arrivals, seed=11)
    eng = _engine(model, params, registry=registry, run_log=run_log,
                  num_slots=4, num_pages=20)   # pages under-provisioned:
    eng.warmup()                               # admission must queue
    results = eng.run(reqs)
    run_log.close()

    assert len(results) == 16
    assert sorted(r.rid for r in results) == list(range(16))
    for r in results:
        assert r.stats.ttft_s is not None and r.stats.ttft_s >= 0
        assert r.stats.e2e_s is not None and r.stats.e2e_s >= r.stats.ttft_s
        assert len(r.tokens) >= 1
    eng.scheduler.check_invariants()
    assert eng.pool.free_count == eng.pool.num_pages

    # registry SLO surface
    assert registry.counter_value("serve.requests_done") == 16
    assert registry.counter_value("serve.tokens_out") == \
        sum(len(r.tokens) for r in results)
    assert registry.histogram("serve.ttft_s").count == 16
    assert registry.histogram("serve.e2e_s").count == 16
    assert registry.histogram("serve.token_latency_s").count > 0

    # RunLog serve events + the report section
    records = RunLog.read(log_path)
    serves = [r for r in records if r["kind"] == "serve"]
    assert sum(r["event"] == "admit" for r in serves) == 16
    assert sum(r["event"] == "done" for r in serves) == 16
    assert serves[-1]["event"] == "report"
    assert serves[-1]["tokens_per_s"] > 0
    import tools_obs_report
    summary = tools_obs_report.summarize(records)
    assert summary["serving"]["requests_done"] == 16
    assert summary["serving"]["ttft_s"]["p95"] is not None


# ------------------------------------------- one builder of every program
@pytest.mark.parametrize("case", ["plain", "numerics", "spec",
                                  "spec-numerics", "experts-numerics"])
def test_a_program_is_its_body_under_one_builder(case, monkeypatch):
    """`ServingEngine._program` builds each program of the engine's table
    once: whatever it wraps a body in (resident int8 experts dequantized
    on entry, the numerics collector, both at once), the module a device
    trace finds the program by keeps the BODY's name (`jit_decode_fn`,
    `jit_chunk_fn`, `jit_write_fn`, `jit_verify_fn`: benchmarks/metrics
    read `"program": "decode_fn"`), the null-page arguments of the table
    lower and run, and the tokens are the unwrapped engine's.

    (Resident experts beside a model that carries `STATS` — the arity
    the wrappers of old got wrong — is reached by no configuration: the
    resident store reads `config.num_experts`, which only the Llama
    block has, and its `STATS` is empty; the families that count,
    Trinity, MiMo, Kimi and Ling, name their experts otherwise and keep
    them as they come.  The one builder passes `*args` through, so there
    is no arity left to hold.)"""
    if "numerics" in case:
        monkeypatch.setenv("HETU_TPU_NUMERICS", "1")
    moe = dict(num_experts=4, moe_top_k=2, moe_capacity_factor=4.0) \
        if "experts" in case else {}
    model = LlamaLMHeadModel(LlamaConfig.tiny(
        remat=False, compute_dtype=jnp.float32, use_flash_attention=False,
        **moe))
    params = model.init(jax.random.key(0))
    kw = dict(kv_quant="int8")
    if "spec" in case:
        kw.update(spec_decode="ngram", spec_k=2)
    if moe:
        kw.update(moe_dispatch="int8")
    eng = _engine(model, params, **kw)
    assert (eng._moe_spec is not None) == bool(moe)
    step = "verify" if "spec" in case else "decode"
    bodies = {step: f"{step}_fn", "prefill_chunk": "chunk_fn",
              "write_pages": "write_fn"}
    # (the chunk program a launch shape, one to four chunks, is the ONE
    # body under the one name: a trace's `chunk_fn` is all its launches)
    bodies.update({f"prefill_chunk_x{k}": "chunk_fn"
                   for k in eng._launch_multiples[1:]})
    assert len(bodies) == 6
    lowered = eng.lower_programs()
    assert sorted(lowered) == sorted(bodies)
    for name, body in bodies.items():
        assert f"module @jit_{body} " in lowered[name].as_text()[:200], name
    reqs = lambda: [Request(  # noqa: E731
        rid=i, prompt=np.arange(1, 6 + 3 * i, dtype=np.int32),
        max_new_tokens=4) for i in range(3)]
    got = [r.tokens for r in eng.warmup().run(reqs())]
    assert all(len(t) == 4 for t in got)
    if "numerics" in case:
        # the collector's stats ride out last (`_call` peels them): the
        # page write's hold the int8 pages' round-trip error
        assert eng._numerics
        tree, stats = lowered["write_pages"].out_info
        assert len(tree) == 4 and "kv_pages" in str(stats)
        assert len(lowered[step].out_info) == 2
        assert len(lowered["prefill_chunk"].out_info) == 3
        monkeypatch.setenv("HETU_TPU_NUMERICS", "0")
        plain = _engine(model, params, **kw)
        assert not plain._numerics
        assert got == [r.tokens for r in plain.run(reqs())]
