"""Trinity (`model_type: afmoe`) on the serving path, at a tiny size that
keeps every mechanism: layers that read a window of 12 positions beside
one that reads everything (one whole period of four), pages handed out
by kind of layer and released behind the window while a request lives,
RMSNorm over each head of q and k, rotation on window layers only, a
sigmoid gate on the attention's output, four norms a layer, two leading
dense layers, sigmoid-routed dropless experts held in part (4 of 16)
with a shared expert.  Seeded random float32 weights; the reference is
`benchmarks/families/afmoe.py`'s plain forward, which shares no code
with the program.

Tolerances.  Program and reference are both float32 here, so they differ
by the ORDER of float32 sums only: logits of O(1) agree to a few 1e-6;
LOGIT_ATOL = 2e-4 leaves two orders of room and is three orders under
what a wrong mask, window edge, rotation, expert or scale moves.
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

from benchmarks.families import afmoe as fam  # noqa: E402
from hetu_tpu.models import generation as gen  # noqa: E402
from hetu_tpu.models.cache_contract import (KVAttention,  # noqa: E402
                                            cache_contract)
from hetu_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from hetu_tpu.serving.engine import ServeConfig, ServingEngine  # noqa: E402
from hetu_tpu.serving.kv_pool import PagePool  # noqa: E402
from hetu_tpu.serving.request import Request, SLOClass  # noqa: E402
from hetu_tpu.serving.scheduler import Scheduler  # noqa: E402

LOGIT_ATOL = 2e-4
F32 = jnp.float32
WINDOW, PAGE, CHUNK = 12, 8, 16


def tiny_cfg():
    """The rehearsal's configuration without `router_tie_logit`: the
    reference's plain forward (the near-tie passes have tests of their
    own in benchmarks/tests/test_afmoe_family.py)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tiny-trinity.json")) as f:
        cfg = json.load(f)
    del cfg["router_tie_logit"]
    return cfg


def build(**over):
    cfg = dict(tiny_cfg(), **over)
    model = fam.build_model(cfg, cfg["serving"])
    return cfg, model, model.init(jax.random.key(7))


def ref_logits(params, cfg, ids):
    ids = jnp.asarray(ids, jnp.int32)
    return np.asarray(jax.jit(lambda p, i: fam.logits_at(
        p, i, jnp.arange(i.shape[0]), cfg))(params, ids))


def _engine(model, params, registry=None, **serve):
    return ServingEngine(model, params, ServeConfig(**{**dict(
        num_slots=3, page_size=PAGE, max_len=128, prefill_chunk=CHUNK),
        **serve}), registry=registry or MetricsRegistry())


# ------------------------------------------------------------------ (a)

@pytest.mark.parametrize("seq", [37, 64])
def test_whole_sequence_forward_is_the_reference(seq, rng):
    cfg, model, params = build()
    ids = rng.integers(0, cfg["vocab_size"], size=(2, seq)).astype(np.int32)
    got = np.asarray(jax.jit(model.forward)(params, jnp.asarray(ids)))
    for b in range(2):
        np.testing.assert_allclose(got[b], ref_logits(params, cfg, ids[b]),
                                   atol=LOGIT_ATOL, rtol=0)


def test_tiny_configuration_keeps_every_mechanism():
    cfg, model, _ = build()
    c = model.config
    assert c.layer_types.count("full_attention") == 1 \
        and c.layer_types[3] == "full_attention"
    assert c.num_dense_layers == 2 and c.num_hidden_layers == 6
    assert c.sliding_window == WINDOW < CHUNK
    assert (c.experts_held, c.router_experts, c.first_expert) == (4, 16, 4)
    contract = cache_contract(model)
    assert contract.kinds == (None, WINDOW)
    assert contract.layers_of(0) == (3,) \
        and contract.layers_of(1) == (0, 1, 2, 4, 5)
    assert model.STATS and c.mup_enabled and not c.tie_word_embeddings
    served = json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                         "trinity-mini-ep8.json")))
    full = fam.build_model(served, {"param_dtype": "bfloat16"})
    assert full.num_params() == served["parameters"] \
        == fam.counts(served)["total_params"]
    assert cache_contract(full).windows.count(2048) == 8
    # all 32 published layers: one chip's share is 4.27B parameters
    whole = dict(served, num_hidden_layers=32,
                 layer_types=(["sliding_attention"] * 3
                              + ["full_attention"]) * 8)
    assert fam.counts(whole)["total_params"] == 4_267_194_112


def test_the_model_is_not_the_model_without_its_own_mechanisms(rng):
    """Each (m) point of the configuration's `assumed` moves the logits:
    the reference would tell a program without it."""
    cfg, model, params = build()
    ids = rng.integers(0, cfg["vocab_size"], size=40).astype(np.int32)
    want = ref_logits(params, cfg, ids)
    for over in (dict(sliding_window=40), dict(mup_enabled=False),
                 dict(layer_types=["full_attention"] * 6),
                 dict(route_scale=1.0)):
        other = fam.logits_at(params, jnp.asarray(ids), jnp.arange(40),
                              dict(cfg, **over))
        assert np.abs(np.asarray(other) - want).max() > 100 * LOGIT_ATOL, over


# ------------------------------------------------------------------ (b)

def _prefill_then_decode(model, params, seq, plen):
    """The scheduler, the pool and the programs by hand, one request in
    slot 1 of 3: chunked prefill into the scratch, the page write, then
    teacher-forced decode steps over the gather route, pages released
    behind the window before each.  -> (logits [len(seq), vocab], the
    window kind's pages held per step)."""
    contract = cache_contract(model)
    pool = PagePool.for_contract(contract, num_pages=(32, 9), page_size=PAGE)
    sched = Scheduler(num_slots=3, pool=pool, max_len=128)
    sched.slots[0] = None
    req = Request(rid=0, prompt=seq[:plen], max_new_tokens=len(seq) - plen)
    sched.submit(req)
    # slot 0 stays empty: admit into slot 1
    sched.slots[0] = object()
    slot, st = sched.admit_next(0.0)
    sched.slots[0] = None
    assert slot == 1
    cache, logits = gen.init_cache(model, 1, 128), []
    chunk = jax.jit(gen.extend_cache, static_argnums=0)
    padded = -(-plen // CHUNK) * CHUNK
    ids = np.zeros(padded, np.int32)
    ids[:plen] = seq[:plen]
    stats = model.zero_stats()
    for s in range(0, padded, CHUNK):
        lg, cache, stats = chunk(model, params, jnp.asarray(
            ids[None, s: s + CHUNK]), cache, jnp.int32(s), stats)
        logits.append(np.asarray(lg[0]))
    logits = [np.concatenate(logits)[:plen]]
    tree = pool.write_pages(
        pool.arrays.tree(), jax.tree.map(jnp.asarray, sched.write_rows(slot)),
        *(c[:, 0] for c in cache))
    st.pos = plen
    step = jax.jit(gen.decode_step_slots, static_argnums=0)
    held = []
    for t in range(plen, len(seq)):
        sched.advance(slot)
        sched.check_invariants()
        held.append(len(st.pages_of(1)))
        table = jnp.asarray(sched.page_table)
        tokens, positions = np.zeros(3, np.int32), np.zeros(3, np.int32)
        tokens[slot], positions[slot] = seq[t], t
        lg, _, toks = step(model, params, jnp.asarray(tokens),
                           pool.gather(tree, table),
                           jnp.asarray(positions))
        tree = pool.write_token(tree, table, jnp.asarray(positions), *toks)
        logits.append(np.asarray(lg[slot])[None])
        st.pos = t + 1
    return np.concatenate(logits), held, st, sched


@pytest.mark.parametrize("plen", [5, 16, 23, 40, 17])
def test_chunked_prefill_page_write_and_paged_decode_are_the_reference(
        plen, rng):
    """Prompts straddle a page (8), a chunk (16) and the window (12): a
    chunk's queries see keys of the chunk before it through the window,
    and 30 decode steps run past the point where the first pages of the
    window layers are released."""
    cfg, model, params = build()
    seq = rng.integers(0, cfg["vocab_size"], size=plen + 30).astype(np.int32)
    got, held, st, sched = _prefill_then_decode(model, params, seq, plen)
    np.testing.assert_allclose(got, ref_logits(params, cfg, seq),
                               atol=LOGIT_ATOL, rtol=0)
    # a window layer holds at most the window and two pages, a layer that
    # reads everything its whole reservation
    cap = -(-WINDOW // PAGE) + 2
    assert max(held) <= cap and sched.pool.hold_pages(128, 1) <= cap
    assert st.first_page[1] == (plen + 29 - WINDOW + 1) // PAGE > 0
    assert len(st.pages) == -(-(plen + 30) // PAGE) and st.first_page[0] == 0
    # what lies behind the window is the null page in the table
    assert (sched.page_tables[1, 1, : st.first_page[1]] == 0).all()


def test_a_released_page_goes_to_another_request_and_changes_nothing(rng):
    """A's pages behind its window return to the free list while A still
    decodes; B, which waited for a page, is admitted onto one of them;
    A's tokens are those of A served alone."""
    cfg, model, params = build()
    vocab = cfg["vocab_size"]
    a = Request(rid=0, prompt=rng.integers(0, vocab, 20).astype(np.int32),
                max_new_tokens=44)
    b = Request(rid=1, prompt=rng.integers(0, vocab, 10).astype(np.int32),
                max_new_tokens=4)
    alone = _engine(model, params, num_pages=(32, 4)).run(
        [Request(rid=0, prompt=a.prompt, max_new_tokens=44)])[0].tokens
    reg = MetricsRegistry()
    eng = _engine(model, params, registry=reg, num_pages=(32, 4))
    eng.submit(a, now=0.0)
    eng.submit(b, now=0.0)
    done, a_pages, shared, now = {}, set(), set(), 0.0
    while len(done) < 2:
        for r in eng.step(now):
            done[r.rid] = r
        now += 1.0
        eng.scheduler.check_invariants()
        live = {st.request.rid: st for st in eng.scheduler.slots
                if st is not None}
        if 0 in live:
            a_pages |= set(live[0].pages_of(1))
            if 1 in live:       # both live: B on a page A has held
                shared |= set(live[1].pages_of(1)) & a_pages
                assert not set(live[1].pages_of(1)) & set(
                    live[0].pages_of(1))
    assert shared, "B never got a page A released while A lived"
    assert reg.counter_value("serve.admission_stalls",
                             reason="no_pages") > 0
    assert done[0].tokens == alone
    want = ref_logits(params, cfg, np.concatenate(
        [b.prompt, done[1].tokens[:-1]]))[b.prompt_len - 1:]
    assert (want.max(-1) - want[np.arange(4), done[1].tokens]
            <= LOGIT_ATOL).all()
    assert eng.pool.free_count == eng.pool.num_pages


@pytest.mark.parametrize("num_pages,short", [((10, 9), "full"),
                                             ((32, 4), "window")])
def test_admission_reserves_by_kind_and_takes_all_or_nothing(num_pages,
                                                             short):
    """Either kind's free list can be the one that decides.  A is seated
    with a reservation a kind (every page of the layers that read
    everything, the window and a page of the window layers'); B, which
    the `short` kind cannot hold beside A, waits with `no_pages` and
    takes no page of the OTHER kind meanwhile; once A has gone B takes
    its pages."""
    cfg, model, params = build()
    pool = PagePool.for_contract(cache_contract(model), num_pages=num_pages,
                                 page_size=PAGE, device_arrays=False)
    sched = Scheduler(num_slots=3, pool=pool, max_len=128)

    def req(rid):
        return Request(rid=rid, prompt=np.zeros(40, np.int32),
                       max_new_tokens=24)
    span = [sched._span(req(0), k)[1] for k in range(2)]
    assert span == [8, pool.hold_pages(64, 1)] and span[1] == 3
    k = ("full", "window").index(short)
    assert span[k] <= num_pages[k] < 2 * span[k] \
        and 2 * span[1 - k] <= num_pages[1 - k]
    sched.submit(req(0))
    sched.submit(req(1))
    slot, st = sched.admit_next(0.0)
    assert [len(st.pages_of(i)) for i in range(2)] == span
    free = [len(x._free) for x in pool.lists]
    assert free == [n - m for n, m in zip(num_pages, span)]
    assert sched.admit_next(0.0) is None and sched.last_stall == "no_pages"
    assert [len(x._free) for x in pool.lists] == free
    sched.check_invariants()
    sched.release(slot)
    _, st = sched.admit_next(0.0)
    assert st.request.rid == 1 and sched.last_stall is None
    assert [len(x._free) for x in pool.lists] == free
    sched.check_invariants()


def test_preempted_and_failed_over_requests_come_back_token_identical(rng):
    cfg, model, params = build()
    vocab = cfg["vocab_size"]

    def reqs():
        r = np.random.default_rng(5)
        return [Request(rid=i, prompt=r.integers(0, vocab, n)
                        .astype(np.int32), max_new_tokens=m,
                        slo=SLOClass(name="batch", priority=0))
                for i, (n, m) in enumerate([(30, 24), (21, 28), (40, 20)])]
    gold = {r.rid: r.tokens for r in _engine(model, params).run(reqs())}
    # preemption: a higher class arrives while three low ones hold the slots
    eng = _engine(model, params, preempt=True)
    for r in reqs():
        eng.submit(r, now=0.0)
    out, now = {}, 0.0
    for _ in range(12):
        out.update({r.rid: r for r in eng.step(now)})
        now += 1.0
    eng.submit(Request(rid=9, prompt=np.arange(1, 20, dtype=np.int32),
                       max_new_tokens=5,
                       slo=SLOClass(name="interactive", priority=2)),
               now=now)
    while eng.scheduler.active_slots() or eng.scheduler.queue:
        out.update({r.rid: r for r in eng.step(now)})
        now += 1.0
        eng.scheduler.check_invariants()
    assert eng.scheduler.preempted >= 1
    assert {k: out[k].tokens for k in gold} == gold
    # failover: the replica dies mid-decode, every live request re-prefills
    eng = _engine(model, params, retry_budget=1)
    for r in reqs():
        eng.submit(r, now=0.0)
    out, now = {}, 0.0
    for _ in range(12):
        out.update({r.rid: r for r in eng.step(now)})
        now += 1.0
    assert len(eng.fail_over(now)["requeued"]) == 3
    eng.scheduler.check_invariants()
    assert eng.pool.free_count == eng.pool.num_pages
    while eng.scheduler.active_slots() or eng.scheduler.queue:
        out.update({r.rid: r for r in eng.step(now)})
        now += 1.0
    assert {k: out[k].tokens for k in gold} == gold


# ------------------------------------------------------------------ (c)

def test_a_window_layers_chunk_reads_window_plus_chunk_positions():
    """The chunk program's attention of a window layer: the products'
    key dimension is window + chunk, not the scratch's max_len; a layer
    that reads everything keeps max_len."""
    attn = KVAttention()
    q = jax.ShapeDtypeStruct((1, CHUNK, 4, 16), F32)
    cache = jax.ShapeDtypeStruct((1, 128, 2, 16), F32)

    def key_dims(window):
        jaxpr = jax.make_jaxpr(lambda q, k, v, s: attn.attend_dense(
            None, q, (k, v), s, **window))(q, cache, cache, jnp.int32(0))
        return {d for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "dot_general"
                for v in (*e.invars, *e.outvars) for d in v.aval.shape}
    assert WINDOW + CHUNK in key_dims({"window": WINDOW})
    assert 128 not in key_dims({"window": WINDOW})
    assert 128 in key_dims({}) and WINDOW + CHUNK not in key_dims({})


def test_the_chunk_slice_stays_inside_the_scratch(rng):
    """Chunks at the scratch's start and end: the slice is shifted, the
    mask is by position, the result is the mask-only composition's."""
    attn = KVAttention()
    k, v = (jnp.asarray(rng.normal(size=(1, 128, 2, 16)), F32)
            for _ in range(2))
    q = jnp.asarray(rng.normal(size=(1, CHUNK, 4, 16)), F32)
    for start in (0, 16, 48, 112):
        got = attn.attend_dense(None, q, (k, v), jnp.int32(start),
                                window=WINDOW)
        want = gen._attend_cached_chunk(q, k, v, start, 16 ** -0.5,
                                        window=WINDOW)
        np.testing.assert_allclose(np.asarray(got).reshape(want.shape),
                                   np.asarray(want), atol=1e-6, rtol=0)


def test_large_scores_go_kv_head_by_kv_head(rng, monkeypatch):
    k, v = (jnp.asarray(rng.normal(size=(1, 64, 2, 16)), F32)
            for _ in range(2))
    q = jnp.asarray(rng.normal(size=(1, 8, 4, 16)), F32)
    want = gen._attend_cached_chunk(q, k, v, 40, 0.25, window=30)
    monkeypatch.setattr(gen, "_SCORES_AT_ONCE", 1)
    got = gen._attend_cached_chunk(q, k, v, 40, 0.25, window=30)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=0)


# ------------------------------------------------------------------ (d)

def test_the_eight_shares_sum_to_the_uncut_layer(rng):
    """One expert layer at router width 128, top-8: the shares
    first_expert 0, 16, .., 112 of 16 experts each, the shared expert
    counted once, add up to the layer with all 128 held; in the program
    and in the reference alike."""
    from hetu_tpu.nn.moe import SharedRoutedExperts
    h, inter, E = 32, 16, 128
    kw = dict(n_routed_experts=E, top_k=8, n_shared_experts=1,
              norm_topk_prob=True, routed_scaling_factor=2.826,
              bias_range=0.002)
    whole = SharedRoutedExperts(h, inter, experts_held=E, first_expert=0,
                                **kw)
    p = whole.init(jax.random.key(3))
    x = jnp.asarray(rng.normal(size=(1, 40, h)), F32)
    y_whole, _ = whole(p, x)
    cfg = dict(num_experts_per_tok=8, route_norm=True, route_scale=2.826)
    ref_whole = fam.experts(x[0], p, dict(cfg, first_expert=0))
    np.testing.assert_allclose(np.asarray(y_whole[0]), np.asarray(ref_whole),
                               atol=1e-5, rtol=0)
    shared = fam._swiglu(x[0], p["shared_gate_up"], p["shared_down"])
    total, ref_total = -7 * shared, -7 * shared
    for first in range(0, E, 16):
        part = SharedRoutedExperts(h, inter, experts_held=16,
                                   first_expert=first, **kw)
        pp = dict(p, w_gate_up=p["w_gate_up"][first: first + 16],
                  w_down=p["w_down"][first: first + 16])
        y, st = part(pp, x)
        total = total + y[0]
        ref_total = ref_total + fam.experts(x[0], pp,
                                            dict(cfg, first_expert=first))
        assert int(st[0]) == 40 * 8 and 0 <= int(st[1]) <= 40 * 8
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_whole[0]),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(ref_total), np.asarray(ref_whole),
                               atol=1e-5, rtol=0)


def test_moe_stats_live_beside_the_layer_and_kimi_re_exports_them():
    from hetu_tpu.models.kimi_k2 import model as kimi
    from hetu_tpu.nn import moe
    for name in ("MOE_STATS", "zero_moe_stats", "add_moe_stats"):
        assert getattr(kimi, name) is getattr(moe, name)
    _, model, _ = build()
    assert model.STATS is moe.MOE_STATS
    import hetu_tpu.models.trinity.model as tm
    assert "import" in open(tm.__file__).read() \
        and "from hetu_tpu.models.kimi_k2" not in open(tm.__file__).read()


# ------------------------------------------------------------------ (e)

@pytest.mark.parametrize("serve,names", [
    (dict(spec_decode="ngram"), "speculative decoding"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(kv_quant="int8"), "int8 / int4 pages"),
    (dict(), "reshard hook"),
])
def test_what_is_not_built_for_window_layers_is_refused_by_name(serve,
                                                                names):
    _, model, params = build()
    kw = {"reshard": object()} if names == "reshard hook" else {}
    with pytest.raises(NotImplementedError, match=names) as e:
        ServingEngine(model, params, ServeConfig(
            num_slots=2, page_size=8, max_len=64, prefill_chunk=16,
            **serve), registry=MetricsRegistry(), **kw)
    # ... and the message says what the model keeps, by kind of layer
    assert "1 layers keep every position" in str(e.value)
    assert f"5 layers keep the last {WINDOW} positions" in str(e.value)


def test_the_prefill_tier_refuses_window_layers():
    from hetu_tpu.serving.disagg import PrefillWorker
    _, model, params = build()
    with pytest.raises(NotImplementedError, match="disagg"):
        PrefillWorker(model, params, prefill_chunk=16, max_len=64)
    eng = _engine(model, params)
    with pytest.raises(NotImplementedError, match="adopt_prefilled"):
        eng.adopt_prefilled(Request(rid=0, prompt=np.arange(4), 
                                    max_new_tokens=2), None, None, 0, 0.0)


def test_a_verify_block_under_a_window_takes_the_composition(monkeypatch,
                                                            rng):
    """The kernels have no window over a block of queries: on a TPU the
    layer's own gate says so and the layer attends its gathered pages by
    the composition, which gives each query of the block what the
    single-token step gives it alone at its position."""
    from hetu_tpu.ops.pallas import record_routes
    attn = KVAttention()
    S, C, P, ps, mp = 2, 3, 9, 8, 4
    pools = tuple(jnp.asarray(rng.standard_normal((2 * P, ps, 2, 128)),
                              jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((S, C, 4, 128)), jnp.float32)
    table = jnp.asarray([[3, 5, 1, 0], [2, 4, 6, 8]], jnp.int32)
    positions = jnp.asarray([9, 20], jnp.int32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with record_routes() as routes:
        block = attn.attend_paged(None, q, pools, table, positions, P,
                                  window=8)
    monkeypatch.undo()
    took = routes["paged_verify"]
    assert took["xla"] == 1 and not took["pallas"]
    assert "the verify block is not built for them" in list(took["why"])[0]
    monkeypatch.setenv("HETU_TPU_PALLAS", "0")
    for i in range(C):
        one = attn.attend_paged(None, q[:, i: i + 1], pools, table,
                                positions + i, P, window=8)
        np.testing.assert_allclose(np.asarray(block[:, i]),
                                   np.asarray(one[:, 0]), atol=1e-6)


# ------------------------------------------------- gauges, routes, scopes

def test_gauges_routes_and_scopes_tell_the_kinds_of_layer_apart(monkeypatch):
    from hetu_tpu.obs import hlo_profile as hp
    _, model, params = build()
    reg = MetricsRegistry()
    eng = _engine(model, params, registry=reg)
    per_layer = 2 * 2 * 16 * 4      # K and V, 2 heads of 16, float32
    assert reg.gauge_value("serve.kv_bytes_per_token") == 6 * per_layer
    assert reg.gauge_value("serve.kv_bytes_per_token",
                           kind="full") == per_layer
    assert reg.gauge_value("serve.kv_bytes_per_token",
                           kind=f"window_{WINDOW}") == 5 * per_layer
    compiled = {k: v.compile() for k, v in eng.lower_programs().items()}
    groups = {g for g, _ in hp.scope_map(compiled["decode"]).values()}
    # (the one decode program; on a CPU its layers take the composition)
    assert {"layer/attn_window", "layer/attn_full", "layer/kv_write",
            "layer/router", "layer/experts", "layer/shared_expert",
            "layer/mlp", "lm_head"} <= groups
    assert {"layer/attn_window", "layer/attn_full"} <= {
        g for g, _ in hp.scope_map(compiled["prefill_chunk"]).values()}
    model.forward(params, jnp.zeros((1, 8), jnp.int32))


def test_counts_and_cost_functions_of_the_family():
    cfg = json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                      "trinity-mini-ep8.json")))
    n = fam.counts(cfg)
    assert n["total_params"] == cfg["parameters"]
    window = {"counters": {"serve.decode_context_tokens": 1000.0,
                           "serve.decode_window_context_tokens": 600.0,
                           "serve.decode_slot_steps": 10.0}}
    cost = fam.paged_attn_cost(cfg, window)
    tokens = 2 * 1000 + 8 * 600         # 2 full layers, 8 window layers
    assert cost["bytes"] == 2.0 * (2 * tokens * 4 * 128
                                   + 10 * 2 * 10 * 32 * 128)
    assert cost["ops"] == 4.0 * tokens * 32 * 128
    assert fam.paged_attn_cost(cfg, {"counters": {}}) is None
    g = fam.grouped_matmul_cost(cfg, {"counters": {
        "serve.moe_expert_hits": 14.0, "serve.moe_local_assignments": 32.0}})
    assert g["bytes"] == 2.0 * (14 * 3 * 2048 * 1024
                                + 32 * (2 * 2048 + 3 * 1024))
    assert fam.grouped_matmul_cost(cfg, {"counters": {}}) is None
