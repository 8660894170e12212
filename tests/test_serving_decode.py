"""Production decoding subsystem tests (tier-1, CPU, seeded):
in-graph sampling (determinism goldens across engine restarts/shapes),
radix prefix cache (COW refcount fuzz under scheduler churn, warm-vs-cold
token parity, prefill-FLOP elimination), speculative decoding (greedy
token-identity vs sequential generate(), seeded-sampling identity vs the
non-speculative path), SLO-class preemptive admission, and the analytic
acceptance gates recorded by bench.py detail.serving."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import generate
from hetu_tpu import serving
from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.obs.metrics import MetricsRegistry
from hetu_tpu.obs.runlog import RunLog
from hetu_tpu.serving.kv_pool import PagePool
from hetu_tpu.serving.prefix_cache import RadixPrefixCache
from hetu_tpu.serving.request import SamplingParams, SLOClass
from hetu_tpu.serving.scheduler import Scheduler
from hetu_tpu.serving.spec_decode import (NGramDrafter, accept_counts,
                                          expected_tokens_per_step,
                                          make_drafter, stochastic_verify)


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = LlamaConfig.tiny(remat=False, compute_dtype=jnp.float32,
                           use_flash_attention=False)
    model = LlamaLMHeadModel(cfg)
    return model, model.init(jax.random.key(0))


def _engine(model, params, **cfg_kw):
    kw = dict(num_slots=3, page_size=8, max_len=64, prefill_chunk=8)
    kw.update(cfg_kw)
    return serving.ServingEngine(
        model, params, serving.ServeConfig(**kw),
        registry=MetricsRegistry())


def _reqs(vocab, n=5, seed=3, max_new=8, sampling=None):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        sp = sampling(i) if sampling else serving.GREEDY
        out.append(serving.Request(
            rid=i,
            prompt=rng.integers(0, vocab,
                                size=int(rng.integers(4, 20))).astype(
                                    np.int32),
            max_new_tokens=max_new, sampling=sp))
    return out


# ------------------------------------------------------------- sampling
def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)
    assert SamplingParams().greedy
    assert not SamplingParams(temperature=0.5).greedy


def test_filtered_logits_topk_topp_semantics():
    """The in-graph filters agree with generate()'s sampler rules:
    top-k keeps exactly k survivors, nucleus keeps the smallest prefix
    whose preceding mass is < p, the argmax always survives, and
    disabled rows pass through scaled only."""
    from hetu_tpu.serving.sampling import filtered_logits
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.0, -1.0],
                          [0.0, 0.1, 0.2, 0.3, 0.4]], jnp.float32)
    temps = jnp.asarray([1.0, 1.0], jnp.float32)
    # top-k = 2: exactly two finite entries per row
    out = filtered_logits(logits, temps, jnp.asarray([2, 2]),
                          jnp.asarray([0.0, 0.0], jnp.float32))
    fin = np.asarray(out) > -1e29
    assert fin.sum(axis=1).tolist() == [2, 2]
    assert fin[0, 0] and fin[0, 1] and fin[1, 4] and fin[1, 3]
    # tiny top-p degenerates to greedy (argmax survives alone)
    out = filtered_logits(logits, temps, jnp.asarray([0, 0]),
                          jnp.asarray([1e-6, 1e-6], jnp.float32))
    fin = np.asarray(out) > -1e29
    assert fin.sum(axis=1).tolist() == [1, 1]
    assert fin[0, 0] and fin[1, 4]
    # disabled filters: pure temperature scaling
    out = filtered_logits(logits, jnp.asarray([2.0, 2.0], jnp.float32),
                          jnp.asarray([0, 0]),
                          jnp.asarray([0.0, 0.0], jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(logits) / 2.0,
                               rtol=1e-6)


def test_sampling_deterministic_across_engine_shapes(tiny_llama):
    """The determinism golden: same request seeds => same tokens across
    a fresh engine (restart) AND a different slot count / batch
    composition — the fold_in(key(seed), position) derivation is a pure
    function of the request."""
    model, params = tiny_llama
    vocab = model.config.vocab_size
    mk = lambda i: SamplingParams(temperature=0.9, top_k=20,  # noqa: E731
                                  top_p=0.95, seed=100 + i)
    r1 = _engine(model, params, num_slots=3, sampling=True).run(
        _reqs(vocab, sampling=mk))
    r2 = _engine(model, params, num_slots=2, sampling=True).run(
        _reqs(vocab, sampling=mk))
    for a, b in zip(r1, r2):
        assert a.tokens == b.tokens, a.rid
    # and the stream is actually sampling (greedy differs somewhere)
    g = _engine(model, params, num_slots=3).run(_reqs(vocab))
    assert any(a.tokens != b.tokens for a, b in zip(r1, g))


def test_greedy_rows_unchanged_by_sampling_program(tiny_llama):
    """Greedy requests decode bit-identically through the sampling
    program (temperature-0 rows take the plain argmax)."""
    model, params = tiny_llama
    vocab = model.config.vocab_size
    r1 = _engine(model, params, sampling=True).run(_reqs(vocab))
    r2 = _engine(model, params).run(_reqs(vocab))
    for a, b in zip(r1, r2):
        assert a.tokens == b.tokens


def test_sampling_request_on_greedy_engine_is_loud(tiny_llama):
    model, params = tiny_llama
    eng = _engine(model, params)
    req = serving.Request(rid=0, prompt=np.asarray([1, 2, 3]),
                          max_new_tokens=2,
                          sampling=SamplingParams(temperature=0.7))
    with pytest.raises(ValueError, match="HETU_TPU_SERVE_SAMPLE"):
        eng.submit(req)


# ----------------------------------------------------------- spec decode
def test_ngram_drafter_proposes_continuations():
    d = NGramDrafter(max_ngram=3)
    toks = [1, 2, 3, 9, 1, 2, 3]
    # trailing 3-gram (1,2,3) matched at position 0 -> proposes [9, 1]
    assert d.propose(toks, 2) == [9, 1]
    # no match anywhere: pads with the last token
    assert d.propose([5, 6, 7], 3) == [7, 7, 7]
    assert len(d.propose(list(range(50)), 4)) == 4
    with pytest.raises(ValueError):
        NGramDrafter(max_ngram=0)
    with pytest.raises(ValueError):
        make_drafter("tree")
    assert make_drafter("none") is None


def test_accept_counts_host_twin():
    targets = np.asarray([[5, 6, 7, 8],     # drafts [5, 6, 7]: all match
                          [5, 0, 7, 8],     # second draft wrong
                          [9, 6, 7, 8]])    # first draft wrong
    drafts = np.asarray([[5, 6, 7], [5, 6, 7], [5, 6, 7]])
    assert accept_counts(targets, drafts).tolist() == [4, 2, 1]
    assert expected_tokens_per_step(0.0, 4) == 1.0
    assert expected_tokens_per_step(1.0, 4) == 5.0
    assert abs(expected_tokens_per_step(0.7, 4) - 2.7731) < 1e-3


def test_spec_decode_greedy_token_identity(tiny_llama):
    """The acceptance golden: greedy speculative decoding emits exactly
    the sequential generate() token stream, request for request."""
    model, params = tiny_llama
    vocab = model.config.vocab_size
    reqs = _reqs(vocab, n=5, seed=11)
    eng = _engine(model, params, spec_decode="ngram", spec_k=3)
    res = eng.run(reqs)
    eng.scheduler.check_invariants()
    for r in reqs:
        out = generate(model, params, jnp.asarray(r.prompt)[None],
                       max_new_tokens=r.max_new_tokens)
        ref = [int(t) for t in np.asarray(out)[0][r.prompt_len:]]
        got = next(x for x in res if x.rid == r.rid).tokens
        assert got == ref, r.rid
    # the run actually speculated
    done = [r.stats for r in res]
    assert sum(s.spec_proposed for s in done) > 0


def test_spec_decode_matches_nonspec_sampling(tiny_llama):
    """Sampling + speculation: because the per-position PRNG keys are
    identical, the spec path's accepted/corrected tokens are
    token-IDENTICAL to the non-speculative sampling engine — the
    strongest form of the rejection-rule distribution claim."""
    model, params = tiny_llama
    vocab = model.config.vocab_size
    mk = lambda i: SamplingParams(temperature=0.8, top_k=30,  # noqa: E731
                                  seed=7 + i)
    spec = _engine(model, params, sampling=True, spec_decode="ngram",
                   spec_k=3).run(_reqs(vocab, n=4, sampling=mk))
    base = _engine(model, params, sampling=True).run(
        _reqs(vocab, n=4, sampling=mk))
    for a, b in zip(spec, base):
        assert a.tokens == b.tokens, a.rid


# ---------------------------------------- fused verify-and-sample path
# The tiny_llama fixture (head_dim 16, hidden 64) is gate-rejected by
# every decode kernel, so the fused-path goldens carry their own model:
# head_dim 128 routes paged_attn/paged_verify, hidden and vocab both
# lane-aligned route the fused sampling epilogue.
_FUSED_KERNELS = "paged_attn,paged_verify,sample"


@pytest.fixture(scope="module")
def hd128_llama():
    cfg = LlamaConfig(vocab_size=256, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=1, num_key_value_heads=1,
                      max_position_embeddings=128, remat=False,
                      compute_dtype=jnp.float32,
                      use_flash_attention=False)
    model = LlamaLMHeadModel(cfg)
    return model, model.init(jax.random.key(2))


def _kernel_took(eng, name: str) -> bool:
    """Every layer the engine's programs traced took the kernel `name`
    (the route is a layer's own, asked when its program is traced)."""
    took = eng.kernel_routes[name]
    return bool(took["pallas"]) and not took["xla"]


def test_spec_decode_fused_kernels_greedy_identity(hd128_llama,
                                                   monkeypatch):
    """The tentpole acceptance golden: greedy speculative decoding
    through the multi-query paged_verify kernel AND the fused sampling
    epilogue emits exactly the sequential generate() token stream."""
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", _FUSED_KERNELS)
    model, params = hd128_llama
    vocab = model.config.vocab_size
    reqs = _reqs(vocab, n=4, seed=13, max_new=6)
    eng = _engine(model, params, spec_decode="ngram", spec_k=3)
    assert eng.verify_fused_sample
    res = eng.run(reqs)
    assert _kernel_took(eng, "paged_verify")
    eng.scheduler.check_invariants()
    for r in reqs:
        out = generate(model, params, jnp.asarray(r.prompt)[None],
                       max_new_tokens=r.max_new_tokens)
        ref = [int(t) for t in np.asarray(out)[0][r.prompt_len:]]
        got = next(x for x in res if x.rid == r.rid).tokens
        assert got == ref, r.rid
    assert sum(r.stats.spec_proposed for r in res) > 0


def test_spec_decode_fused_kernels_int8_matches_gather(hd128_llama,
                                                       monkeypatch):
    """int8 KV through the fused verify kernel: spec decoding over
    quantized pages matches the non-speculative engine on the SAME
    quantized cache (both routed through the int8 paged kernels)."""
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", _FUSED_KERNELS)
    model, params = hd128_llama
    vocab = model.config.vocab_size
    spec = _engine(model, params, kv_quant="int8", spec_decode="ngram",
                   spec_k=3)
    assert spec.verify_fused_sample
    r1 = spec.run(_reqs(vocab, n=4, seed=13, max_new=6))
    base = _engine(model, params, kv_quant="int8")
    r2 = base.run(_reqs(vocab, n=4, seed=13, max_new=6))
    assert _kernel_took(spec, "paged_verify") \
        and _kernel_took(base, "paged_attn")
    for a, b in zip(r1, r2):
        assert a.tokens == b.tokens, a.rid


def test_spec_decode_fused_kernels_sampled_identity(hd128_llama,
                                                    monkeypatch):
    """Seeded sampling through the fused epilogue: the in-kernel
    Gumbel draw replays the non-speculative sampling engine token for
    token (the kernel shares the counter-based hash with the XLA
    path, so identity survives the routing change)."""
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", _FUSED_KERNELS)
    model, params = hd128_llama
    vocab = model.config.vocab_size
    mk = lambda i: SamplingParams(temperature=0.8, top_k=30,  # noqa: E731
                                  seed=17 + i)
    spec_eng = _engine(model, params, sampling=True, spec_decode="ngram",
                       spec_k=3)
    assert spec_eng.verify_fused_sample
    spec = spec_eng.run(_reqs(vocab, n=4, max_new=6, sampling=mk))
    base = _engine(model, params, sampling=True).run(
        _reqs(vocab, n=4, max_new=6, sampling=mk))
    for a, b in zip(spec, base):
        assert a.tokens == b.tokens, a.rid


# -------------------------------------- model drafter / stochastic rule
def _draft_llama(vocab):
    cfg = LlamaConfig.tiny(vocab_size=vocab, hidden_size=32,
                           intermediate_size=64, num_hidden_layers=1,
                           num_attention_heads=2, num_key_value_heads=1,
                           remat=False, compute_dtype=jnp.float32,
                           use_flash_attention=False)
    model = LlamaLMHeadModel(cfg)
    return model, model.init(jax.random.key(7))


def test_model_drafter_engine_greedy_identity_and_replay(tiny_llama):
    """HETU_TPU_SPEC_DECODE=model: a resident-quantized draft model
    proposes, the stochastic p/q rule verifies.  Greedy requests
    collapse the rule to accept-iff-argmax, so the stream is exactly
    generate()'s; sampled requests replay deterministically across a
    fresh engine (drafts AND accept draws are pure functions of the
    request's seed/position keys)."""
    model, params = tiny_llama
    vocab = model.config.vocab_size
    draft, dparams = _draft_llama(vocab)

    def eng():
        return serving.ServingEngine(
            model, params,
            serving.ServeConfig(num_slots=3, page_size=8, max_len=64,
                                prefill_chunk=8, sampling=True,
                                spec_decode="model", spec_k=2),
            draft_model=draft, draft_params=dparams,
            registry=MetricsRegistry())

    reqs = _reqs(vocab, n=3, seed=5, max_new=6)
    res = eng().run(reqs)
    for r in reqs:
        out = generate(model, params, jnp.asarray(r.prompt)[None],
                       max_new_tokens=r.max_new_tokens)
        ref = [int(t) for t in np.asarray(out)[0][r.prompt_len:]]
        got = next(x for x in res if x.rid == r.rid).tokens
        assert got == ref, r.rid
    assert sum(r.stats.spec_proposed for r in res) > 0

    mk = lambda i: SamplingParams(temperature=0.9, top_k=20,  # noqa: E731
                                  seed=40 + i)
    s1 = eng().run(_reqs(vocab, n=3, max_new=6, sampling=mk))
    s2 = eng().run(_reqs(vocab, n=3, max_new=6, sampling=mk))
    for a, b in zip(s1, s2):
        assert a.tokens == b.tokens, a.rid


def test_model_spec_mode_without_draft_model_is_loud(tiny_llama):
    model, params = tiny_llama
    with pytest.raises(ValueError, match="draft"):
        serving.ServingEngine(
            model, params,
            serving.ServeConfig(num_slots=2, page_size=8, max_len=64,
                                prefill_chunk=8, spec_decode="model",
                                spec_k=2),
            registry=MetricsRegistry())


def test_stochastic_verify_analytic_acceptance():
    """The p/q rejection rule is distribution-exact: over many slots
    sharing one (p, q) pair with independent hash draws, the measured
    acceptance rate converges to sum_v min(p(v), q(v)) and the marginal
    of the first emitted token converges to p — for a q deliberately
    DIFFERENT from p (the any-drafter guarantee).  Greedy rows collapse
    to accept-iff-argmax."""
    S, V, k = 4096, 32, 1
    rng = np.random.default_rng(0)
    t_logits = rng.normal(size=(1, k + 1, V)).astype(np.float32)
    logits = jnp.asarray(np.broadcast_to(t_logits, (S, k + 1, V)).copy())
    p = np.asarray(jax.nn.softmax(jnp.asarray(t_logits[0, 0])))
    q = np.exp(rng.normal(size=V)); q /= q.sum()
    q_probs = jnp.asarray(
        np.broadcast_to(q.astype(np.float32), (S, k, V)).copy())
    drafts = jnp.asarray(rng.choice(V, size=(S, k), p=q).astype(np.int32))
    seeds = jnp.arange(S, dtype=jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(k + 1, dtype=jnp.int32),
                                 (S, k + 1))
    ones = jnp.ones((S,), jnp.float32)
    zeros_i = jnp.zeros((S,), jnp.int32)
    zeros_f = jnp.zeros((S,), jnp.float32)
    out, n_emit = stochastic_verify(logits, q_probs, drafts, seeds,
                                    positions, ones, zeros_i, zeros_f)
    out, n_emit = np.asarray(out), np.asarray(n_emit)
    analytic = float(np.minimum(p, q).sum())
    measured = float((n_emit >= 2).mean())
    assert abs(measured - analytic) < 0.04, (measured, analytic)
    emp = np.bincount(out[:, 0], minlength=V) / S
    assert 0.5 * np.abs(emp - p).sum() < 0.08
    # greedy rows: the rule degenerates to argmax verification
    gout, gn = stochastic_verify(logits, q_probs, drafts, seeds,
                                 positions, zeros_f, zeros_i, zeros_f)
    gout, gn = np.asarray(gout), np.asarray(gn)
    am = t_logits[0].argmax(axis=-1)
    assert (gout[:, 0] == am[0]).all()
    match = np.asarray(drafts)[:, 0] == am[0]
    np.testing.assert_array_equal(gn, np.where(match, 2, 1))


# ---------------------------------------------------------------- int4 KV
def test_int4_kv_engine_decode(hd128_llama, monkeypatch):
    """int4 KV end to end: the engine decodes over nibble-packed pages
    by the composition over gathered pages and by the paged kernels,
    and each is a
    pure function of the request (restart/slot-shape invariant).  Token
    parity vs fp32 is deliberately NOT asserted — int4 is a lossy
    cache; the documented tolerance is pinned at the kernel-vs-dense
    and pool round-trip levels (test_pallas_kernels, test_ops)."""
    model, params = hd128_llama
    vocab = model.config.vocab_size
    mk = lambda: _reqs(vocab, n=4, seed=21, max_new=6)  # noqa: E731
    g1 = _engine(model, params, kv_quant="int4").run(mk())
    g2 = _engine(model, params, kv_quant="int4", num_slots=2).run(mk())
    for a, b in zip(g1, g2):
        assert a.tokens == b.tokens and len(a.tokens) == 6, a.rid

    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", _FUSED_KERNELS)
    spec = _engine(model, params, kv_quant="int4", spec_decode="ngram",
                   spec_k=3)
    # the int4 pool (packed head_dim 64) routes the int4 kernels
    assert spec.verify_fused_sample
    k1 = spec.run(mk())
    assert _kernel_took(spec, "paged_verify")
    spec.scheduler.check_invariants()
    k2 = _engine(model, params, kv_quant="int4", num_slots=2,
                 spec_decode="ngram", spec_k=3).run(mk())
    for a, b in zip(k1, k2):
        assert a.tokens == b.tokens and len(a.tokens) == 6, a.rid


def test_spec_lookahead_widens_reservation_validation():
    pool = PagePool(num_layers=1, num_pages=8, page_size=4,
                    num_kv_heads=1, head_dim=8)
    sched = Scheduler(num_slots=2, pool=pool, max_len=16, lookahead=4)
    # 10 prompt + 3 new = 13 fits max_len 16, but + lookahead 4 = 17
    with pytest.raises(ValueError, match="spec lookahead"):
        sched.submit(serving.Request(rid=0, prompt=np.arange(10),
                                     max_new_tokens=3))
    sched.submit(serving.Request(rid=1, prompt=np.arange(8),
                                 max_new_tokens=3))
    idx, st = sched.admit_next(0.0)
    # reservation covers total_len + lookahead = 15 tokens -> 4 pages
    assert len(st.pages) == 4
    sched.check_invariants()


# ----------------------------------------------------------- radix cache
def test_radix_cache_match_insert_evict_refcounts():
    pool = PagePool(num_layers=1, num_pages=12, page_size=4,
                    num_kv_heads=1, head_dim=8)
    cache = RadixPrefixCache(pool)
    prompt = np.arange(11)                       # pages [0:4) [4:8) +tail
    pages = pool.alloc(3)
    # cap: only full pages of prompt[:plen-1] = 10 -> 2 blocks
    assert cache.insert(prompt, pages) == 2
    assert pool.refcount[pages[0]] == 2 and pool.refcount[pages[2]] == 1
    shared, spages = cache.match(prompt)
    assert shared == 8 and spages == pages[:2]
    # a shorter prompt sharing one block
    shared, spages = cache.match(np.arange(6))
    assert shared == 4 and spages == pages[:1]
    # match never covers the whole prompt (>= 1 token must prefill)
    shared, _ = cache.match(np.arange(8))
    assert shared == 4
    # owner releases: cached pages stay resident, the tail page frees
    pool.free(pages)
    assert pool.free_count == 12 - 2
    # eviction releases the cache's refs leaf-first
    assert cache.evict(2) == 2
    assert pool.free_count == 12
    assert cache.num_pages == 0
    st = cache.stats()
    assert st["hits"] == 3 and st["evicted_pages"] == 2


def test_radix_cache_budget_and_dedup():
    pool = PagePool(num_layers=1, num_pages=8, page_size=4,
                    num_kv_heads=1, head_dim=8)
    cache = RadixPrefixCache(pool, max_pages=1)
    p1 = pool.alloc(2)
    assert cache.insert(np.arange(9), p1) == 1    # budget caps at 1
    assert cache.num_pages == 1
    # same block again: dedup, the duplicate page is NOT adopted
    p2 = pool.alloc(2)
    assert cache.insert(np.arange(9), p2) == 0
    assert pool.refcount[p2[0]] == 1


def test_admission_pins_matched_chain_before_eviction():
    """Regression (review finding): under page pressure, an admission
    whose matched shared chain is the cache's only evictable leaf must
    NOT evict-and-realloc those pages as its own 'fresh' suffix —
    pre-fix, `admit_next` matched un-pinned, the eviction freed the
    matched page, and the retried alloc handed it back as the suffix:
    pages like [1, 1, ...] (prefix and suffix aliased onto one
    physical page, silently wrong attention).  The match is now
    pinned (incref) before eviction runs, so the chain survives and
    the admission stalls honestly instead."""
    pool = PagePool(num_layers=1, num_pages=4, page_size=4,
                    num_kv_heads=1, head_dim=8)
    cache = RadixPrefixCache(pool)
    sched = Scheduler(num_slots=2, pool=pool, max_len=12,
                      prefix_cache=cache)
    # A fills + caches its full prefix page, then finishes
    sched.submit(serving.Request(rid=0, prompt=np.arange(5),
                                 max_new_tokens=3))
    idx, st = sched.admit_next(0.0)
    st.pos = 5
    cache.insert(st.request.prompt, st.pages, 0.0)
    sched.release(idx)
    # B occupies 2 pages and stays live -> free = 1
    sched.submit(serving.Request(rid=1, prompt=np.arange(4) + 50,
                                 max_new_tokens=4))
    b_idx, _ = sched.admit_next(0.5)
    assert pool.free_count == 1
    # C shares A's prefix page and needs 2 FRESH pages; only 1 is
    # free, and the only cache leaf is C's own matched chain
    sched.submit(serving.Request(rid=2, prompt=np.arange(5),
                                 max_new_tokens=7))
    adm = sched.admit_next(1.0)
    assert adm is None and sched.last_stall == "no_pages"
    # the matched chain was NOT cannibalized: still cached, still live
    assert cache.num_pages == 1
    assert cache.match(np.arange(5))[0] == 4
    sched.check_invariants()
    # pressure relieved -> C admits with distinct prefix/suffix pages
    sched.release(b_idx)
    adm = sched.admit_next(2.0)
    assert adm is not None
    _, st = adm
    assert st.shared_tokens == 4
    assert len(set(st.pages)) == len(st.pages), \
        f"prefix/suffix aliased: {st.pages}"
    sched.check_invariants()


def test_evict_counts_freed_pages_only_under_pressure():
    """require_free eviction (the scheduler's page-pressure path) only
    touches leaves the cache solely owns and counts pages actually
    freed; shared leaves keep their hit value."""
    pool = PagePool(num_layers=1, num_pages=4, page_size=4,
                    num_kv_heads=1, head_dim=8)
    cache = RadixPrefixCache(pool)
    shared = pool.alloc(1)       # 'live slot' holds this one too
    cache.insert(np.arange(5), shared)
    sole = pool.alloc(1)
    cache.insert(np.concatenate([np.arange(4) + 100, [1]]), sole)
    pool.free(sole)              # cache is now sole owner of `sole`
    assert pool.free_count == 2
    # pressure eviction frees exactly the solely-owned page and leaves
    # the shared leaf cached
    assert cache.evict(1, require_free=True) == 1
    assert pool.free_count == 3
    assert cache.num_pages == 1
    assert cache.match(np.arange(5))[0] == 4     # shared entry intact
    # budget eviction (insert path) still counts entries released
    assert cache.evict(1) == 1
    assert cache.num_pages == 0
    assert pool.free_count == 3                  # slot still holds it
    pool.free(shared)
    assert pool.free_count == 4


def test_preempted_spec_counters_carried_to_done(tiny_llama, tmp_path):
    """Review finding: draft counters accrued before a preemption must
    reach the final done event — the reported acceptance rate covers
    the whole run, not the last incarnation."""
    model, params = tiny_llama
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 250, size=8).astype(np.int32)
               for _ in range(3)]
    log = RunLog(str(tmp_path / "p.jsonl"))
    reg = MetricsRegistry()
    eng = serving.ServingEngine(
        model, params,
        serving.ServeConfig(num_slots=2, page_size=8, max_len=80,
                            prefill_chunk=8, preempt=True,
                            spec_decode="ngram", spec_k=3),
        registry=reg, run_log=log)
    reqs = [serving.Request(rid=i, prompt=prompts[i], max_new_tokens=24,
                            slo=SLOClass("bulk")) for i in range(2)]
    reqs.append(serving.Request(rid=2, prompt=prompts[2],
                                max_new_tokens=4,
                                slo=SLOClass("gold", priority=2),
                                arrival_t=0.001))
    res = eng.run(reqs)
    log.close()
    assert eng.scheduler.preempted >= 1
    dones = {r["req"]: r for r in RunLog.read(str(tmp_path / "p.jsonl"))
             if r.get("kind") == "serve" and r.get("event") == "done"}
    # sum of done-event draft counters == the registry's step-time total
    snap = {c["name"]: c["value"]
            for c in reg.snapshot()["counters"]}
    assert sum(d["spec_proposed"] for d in dones.values()) == \
        snap["serve.spec_proposed"]
    assert sum(d["spec_accepted"] for d in dones.values()) == \
        snap["serve.spec_accepted"]


def test_scheduler_cow_fuzz_with_prefix_cache():
    """The COW fuzz: 400 steps of random arrival/finish churn over a
    small pool WITH the radix cache attached and a handful of shared
    prompt families — refcounts exact, no unshared aliasing, pool
    partition exact after every transition (the extended
    check_invariants contract)."""
    rng = np.random.default_rng(7)
    pool = PagePool(num_layers=1, num_pages=24, page_size=4,
                    num_kv_heads=1, head_dim=8)
    cache = RadixPrefixCache(pool)
    sched = Scheduler(num_slots=3, pool=pool, max_len=32,
                      prefix_cache=cache)
    prefixes = [rng.integers(0, 50, size=8).astype(np.int32)
                for _ in range(3)]
    rid = 0
    for step in range(400):
        now = float(step)
        if rng.random() < 0.5 and len(sched.queue) < 4:
            pre = prefixes[int(rng.integers(len(prefixes)))]
            tail = rng.integers(0, 50,
                                size=int(rng.integers(1, 8))).astype(
                                    np.int32)
            sched.submit(serving.Request(
                rid=rid, prompt=np.concatenate([pre, tail]),
                max_new_tokens=int(rng.integers(1, 6))))
            rid += 1
        adm = sched.admit_next(now)
        if adm is not None:
            idx, st = adm
            # pretend prefill finished instantly: index the prompt
            st.pos = st.request.prompt_len
            cache.insert(st.request.prompt, st.pages, now)
        sched.check_invariants()
        live = sched.active_slots()
        if live and rng.random() < 0.4:
            victim = int(rng.choice(live))
            sched.release(victim)
        if rng.random() < 0.1:
            cache.evict(int(rng.integers(1, 4)))
        sched.check_invariants()
    # drain: everything back to free once slots + cache release
    for i in sched.active_slots():
        sched.release(i)
    cache.clear()
    sched.check_invariants()
    assert pool.free_count == pool.num_pages
    assert cache.stats()["hits"] > 0


def test_prefix_cache_warm_parity_and_flops_saved(tiny_llama):
    """Shared system prompt through the engine: warm admissions hit the
    cache, tokens are IDENTICAL to the uncached engine, and prefill
    work (chunks) drops to the unshared suffix — the >= 90% claim at
    scale is the same arithmetic bench.py records."""
    model, params = tiny_llama
    vocab = model.config.vocab_size
    rng = np.random.default_rng(0)
    sysp = rng.integers(0, vocab, size=24).astype(np.int32)
    reqs = []
    for i in range(6):
        tail = rng.integers(0, vocab, size=6).astype(np.int32)
        reqs.append(serving.Request(rid=i,
                                    prompt=np.concatenate([sysp, tail]),
                                    max_new_tokens=6))
    clone = lambda: [serving.Request(  # noqa: E731
        rid=r.rid, prompt=r.prompt,
        max_new_tokens=r.max_new_tokens) for r in reqs]
    warm_eng = _engine(model, params, num_slots=2, prefix_cache=True)
    warm = warm_eng.run(clone())
    warm_eng.scheduler.check_invariants()
    cold_eng = _engine(model, params, num_slots=2)
    cold = cold_eng.run(clone())
    for a, b in zip(warm, cold):
        assert a.tokens == b.tokens, a.rid
    st = warm_eng.prefix_cache.stats()
    assert st["hits"] >= 4 and st["shared_tokens"] >= 4 * 24
    snap_w = warm_eng._registry.snapshot()
    snap_c = cold_eng._registry.snapshot()
    chunks = lambda s: {r["name"]: r["value"]  # noqa: E731
                        for r in s["counters"]}["serve.prefill_chunks"]
    # 30-token prompts: 4 chunks cold; warm hits prefill 1 chunk each
    assert chunks(snap_w) <= chunks(snap_c) - 3 * 4 + 3


def test_bench_serving_acceptance_gates():
    """The hardware-free perf evidence bench.py detail.serving records:
    >= 2x roofline decode tokens/s from speculative decoding at
    acceptance 0.7, and >= 90% prefill FLOPs eliminated for a
    fully-shared system prompt — the prefix row's per-chunk FLOPs
    COUNTED from the lowered prefill HLO (flops_source)."""
    import bench
    rec = bench._hardware_free_serving(measure_hlo=True)
    spec = rec["spec_decode"]
    assert spec["acceptance"] == 0.7
    assert spec["speedup"] >= 2.0
    assert spec["spec_tokens_per_s"] >= 2.0 * spec["decode_tokens_per_s"]
    cache = rec["prefix_cache"]
    assert cache["prefill_flops_saved_frac"] >= 0.9
    assert cache["flops_source"] == "lowered_hlo"
    assert cache["flops_per_chunk_tiny_measured"] > 0
    assert cache["prefill_flops_cached"] <= 0.1 * cache["prefill_flops_full"]
    # int4 KV: >= 7x smaller cache than fp32 (the ISSUE floor)
    assert rec["kv_ratio_int4_vs_fp32"] >= 7.0
    assert rec["decode_tokens_per_s_int4_kv"] > \
        rec["decode_tokens_per_s_int8_kv"]
    # model drafter at its bench acceptance profile beats the n-gram
    # roofline even after paying the draft-model step tax
    spec_m = rec["spec_decode_model"]
    assert spec_m["draft_step_s"] > 0
    assert spec_m["spec_tokens_per_s"] > spec["spec_tokens_per_s"]


# ------------------------------------------------------------ preemption
def test_slo_class_priority_parse():
    c = SLOClass.parse("gold:0.2:0.05:2")
    assert (c.name, c.ttft_s, c.token_gap_s, c.priority) == \
        ("gold", 0.2, 0.05, 2)
    assert SLOClass.parse("bulk").priority == 0
    assert SLOClass.parse("fast:-:-:1").priority == 1
    with pytest.raises(ValueError):
        SLOClass.parse("a:b:c:d:e")


def test_preemption_evicts_lowest_class_and_requeues(tiny_llama, tmp_path):
    """Two bulk requests saturate both slots; a priority-2 gold arrival
    preempts one (pages released, request requeued, `preempted` stall
    span + serve event), finishes first, and the bulk victim still
    completes with its full token budget.  Spans stay tile-exact
    through the requeue (reconciliation == 0 under the virtual
    clock)."""
    model, params = tiny_llama
    rng = np.random.default_rng(1)
    gold = SLOClass("gold", priority=2)
    bulk = SLOClass("bulk")
    reqs = [serving.Request(rid=i,
                            prompt=rng.integers(0, 250, size=8).astype(
                                np.int32),
                            max_new_tokens=30, slo=bulk)
            for i in range(2)]
    reqs.append(serving.Request(rid=2,
                                prompt=rng.integers(0, 250,
                                                    size=8).astype(
                                                        np.int32),
                                max_new_tokens=4, slo=gold,
                                arrival_t=0.001))
    log = RunLog(str(tmp_path / "r.jsonl"))
    reg = MetricsRegistry()
    tracer = serving.RequestTracer(run_log=log, registry=reg)
    eng = serving.ServingEngine(
        model, params,
        serving.ServeConfig(num_slots=2, page_size=8, max_len=64,
                            prefill_chunk=8, preempt=True),
        registry=reg, run_log=log, tracer=tracer)
    res = eng.run(reqs)
    log.close()
    eng.scheduler.check_invariants()
    assert len(res) == 3 and eng.scheduler.preempted >= 1
    done_t = {r.rid: r.stats.done_t for r in res}
    assert done_t[2] < max(done_t[0], done_t[1])
    assert all(len(r.tokens) == reqs[r.rid].max_new_tokens for r in res)
    for t in tracer.traces.values():
        t.validate()
    records = RunLog.read(str(tmp_path / "r.jsonl"))
    rep = serving.serving_report(records)
    pre = rep["preemptions"]
    assert pre["victim_classes"] == {"bulk": pre["preemptions"]}
    assert pre["preemptor_classes"] == {"gold": pre["preemptions"]}
    assert rep["reconciliation"]["max_residual_s"] < 1e-9
    # the preempted request's final trace carries the sticky reason
    victims = [p["req"] for p in
               [r for r in records
                if r.get("kind") == "serve"
                and r.get("event") == "preempt"]]
    queued = [r for r in records if r.get("kind") == "span"
              and r.get("span") == "queued" and r["req"] in victims]
    assert any(q.get("reason") == "preempted" for q in queued)
    # equal priorities never preempt
    assert eng.scheduler.preempt_victim(0) is None


def test_preempted_tokens_match_unpreempted(tiny_llama):
    """Deterministic greedy decode means a preempted-and-requeued
    request regenerates exactly the tokens it would have produced
    uninterrupted."""
    model, params = tiny_llama
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 250, size=8).astype(np.int32)
               for _ in range(3)]
    bulk = [serving.Request(rid=i, prompt=prompts[i], max_new_tokens=24,
                            slo=SLOClass("bulk")) for i in range(2)]
    gold = serving.Request(rid=2, prompt=prompts[2], max_new_tokens=4,
                           slo=SLOClass("gold", priority=1),
                           arrival_t=0.001)
    pre = _engine(model, params, num_slots=2, preempt=True).run(
        bulk + [gold])
    base = _engine(model, params, num_slots=3).run(
        [serving.Request(rid=i, prompt=prompts[i],
                         max_new_tokens=r.max_new_tokens)
         for i, r in enumerate(bulk + [gold])])
    for a, b in zip(pre, base):
        assert a.tokens == b.tokens, a.rid


# ------------------------------------------------------- report sections
def test_slo_report_spec_and_cache_sections(tiny_llama, tmp_path):
    model, params = tiny_llama
    vocab = model.config.vocab_size
    log = RunLog(str(tmp_path / "s.jsonl"))
    reg = MetricsRegistry()
    eng = serving.ServingEngine(
        model, params,
        serving.ServeConfig(num_slots=2, page_size=8, max_len=64,
                            prefill_chunk=8, spec_decode="ngram",
                            spec_k=3, prefix_cache=True),
        registry=reg, run_log=log)
    reqs = serving.synthetic_requests(6, vocab_size=vocab,
                                      shared_prefix_len=16,
                                      prompt_lens=(4, 8), max_new=(4, 8),
                                      seed=2)
    eng.run(reqs)
    log.close()
    rep = serving.serving_report(RunLog.read(str(tmp_path / "s.jsonl")))
    spec = rep["spec_decode"]
    assert spec["drafts_proposed"] > 0
    assert 0.0 <= spec["acceptance_rate"] <= 1.0
    cache = rep["prefix_cache"]
    assert cache["hits"] >= 1
    assert 0.0 < cache["prefill_tokens_saved_frac"] < 1.0
    text = serving.render_text(rep)
    assert "spec decode:" in text and "prefix cache:" in text
