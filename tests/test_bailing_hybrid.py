"""Ling-3.0 (`bailing_hybrid`) on the serving path, at a tiny size that
keeps every mechanism: six linear-attention (KDA) layers whose state a
sequence lies beside the pages of the one latent-attention layer, a
leading dense layer, group-limited sigmoid routing with one group of
experts held and a shared expert.  Seeded random float32 weights; the
reference is `benchmarks/families/bailing_hybrid.py`'s plain forward (a
`lax.scan` over positions, no chunks, no state carried between calls),
which shares no code with the program.

Tolerances.  Program and reference are both float32 here, so they differ
by the ORDER of float32 sums only (the chunkwise form's products against
the recurrence, online softmax over key blocks, a grouped product over
sorted rows): logits of O(1) agree to a few 1e-6; LOGIT_ATOL = 2e-4
leaves two orders of room and is three orders under what a state carried
wrongly, a padding row taken in, a wrong expert or a wrong group moves
(O(0.1-1))."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.families import bailing_hybrid as fam  # noqa: E402
from hetu_tpu.models import generation as gen  # noqa: E402
from hetu_tpu.models.cache_contract import (CacheContract,  # noqa: E402
                                            cache_contract)
from hetu_tpu.nn import moe  # noqa: E402
from hetu_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from hetu_tpu.ops import delta_rule  # noqa: E402
from hetu_tpu.serving.engine import ServeConfig, ServingEngine  # noqa: E402
from hetu_tpu.serving.kv_pool import PagePool  # noqa: E402
from hetu_tpu.serving.request import Request, SLOClass  # noqa: E402

LOGIT_ATOL = 2e-4
F32 = jnp.float32


def tiny_cfg():
    """The rehearsal's configuration without `router_tie_logit`: the
    reference's plain forward (the near-tie passes have tests of their
    own in benchmarks/tests/test_ling_family.py)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tiny-ling.json")) as f:
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


def engine(model, params, **serve):
    reg = MetricsRegistry()
    cfg = dict(num_slots=4, page_size=8, max_len=128, prefill_chunk=16,
               num_pages=64)
    cfg.update(serve)
    return ServingEngine(model, params, ServeConfig(**cfg), registry=reg), reg


def served_against_reference(cfg, params, req, tokens):
    """Each served token's standing under the reference's largest logit,
    given the stream's own prefix."""
    toks = np.asarray(tokens)
    lg = ref_logits(params, cfg, np.concatenate([req.prompt, toks[:-1]]))[
        req.prompt_len - 1:]
    return lg.max(-1) - lg[np.arange(len(toks)), toks]


# ------------------------------------------------------ the delta rule
def _kda_inputs(rng, s, h=3, dk=16, dv=16, alike=0.0):
    q, k = (rng.standard_normal((s, h, dk)) for _ in range(2))
    for t in range(1, s):       # neighbouring keys alike (a convolution's)
        k[t] = alike * k[t - 1] + (1 - alike ** 2) ** 0.5 * k[t]
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((s, h, dv))
    # decays over the whole range the model allows, (-5, 0)
    g = -5.0 / (1.0 + np.exp(-3.0 * rng.standard_normal((s, h, dk))))
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((s, h))))
    S0 = rng.standard_normal((h, dk, dv))
    return tuple(jnp.asarray(a, F32) for a in (S0, q, k, v, g, beta))


@pytest.mark.parametrize("alike", [0.0, 0.99])
@pytest.mark.parametrize("positions", [16, 128, 37])
def test_chunkwise_scan_is_the_recurrence(positions, alike, rng):
    """The chunkwise form (the triangular system a block of 16, the walk
    over the blocks) against the recurrence position by position, at
    decays down to the lower bound: a block of 16 keeps float32 finite.
    With neighbouring keys ALIKE (0.99: what a short convolution over the
    projections makes of them) it stays exact; the inverse of a whole
    block of 64 by its power series, which stood here first, gave no
    finite value there (PERF.md s6, PR 41).  37 positions: the last block
    is padded with positions that leave the state alone."""
    args = _kda_inputs(rng, positions, alike=alike)
    with jax.default_matmul_precision("highest"):
        o1, S1 = delta_rule.recurrence(*args)
        o2, S2 = jax.jit(delta_rule.chunk_scan)(*args)
    assert o2.shape == o1.shape and np.isfinite(np.asarray(o2)).all()
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=2e-5)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S1), atol=5e-5)


def test_the_power_series_inverse_is_for_a_block_of_16_only(rng):
    S0, q, k, v, g, beta = _kda_inputs(rng, 64, h=1, dk=64, alike=0.99)
    A = jnp.tril(jnp.einsum("ihd,jhd->hij", k, k), -1) * 0.9
    eye = jnp.eye(64)
    n = delta_rule.BLOCK
    assert n == 16
    with jax.default_matmul_precision("highest"):
        small = delta_rule._unit_lower_inverse(A[:, :n, :n])
        ok = jnp.abs(small @ (eye[:n, :n] + A[:, :n, :n])
                     - eye[:n, :n]).max()
        whole = delta_rule._unit_lower_inverse(A)
        bad = jnp.abs(whole @ (eye + A) - eye).max()
    assert float(ok) < 1e-3
    assert not float(bad) < 1.0      # (nan or huge)


def test_decays_that_would_leave_float32_are_refused(rng):
    with pytest.raises(ValueError, match="float32's range"):
        delta_rule.chunk_scan(*_kda_inputs(rng, 64), g_floor=-6.0)


@pytest.mark.parametrize("valid", [0, 5, 16, 37, 64])
def test_a_chunk_whose_valid_rows_end_mid_block_leaves_the_state_there(
        valid, rng):
    """Rows past `valid` have beta = 0 and g = 0: the state after the
    chunk is the state after the last valid row, and the valid rows'
    outputs are the recurrence's."""
    S0, q, k, v, g, beta = _kda_inputs(rng, 64)
    real = jnp.arange(64) < valid
    gm = jnp.where(real[:, None, None], g, 0.0)
    bm = jnp.where(real[:, None], beta, 0.0)
    with jax.default_matmul_precision("highest"):
        o, S = delta_rule.chunk_scan(S0, q, k, v, gm, bm)
        o_want, S_want = delta_rule.recurrence(
            S0, *(a[:valid] for a in (q, k, v, g, beta)))
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_want), atol=5e-5)
    np.testing.assert_allclose(np.asarray(o[:valid]), np.asarray(o_want),
                               atol=2e-5)


def test_decode_step_is_the_recurrence_and_an_idle_row_keeps_its_state(rng):
    S0, q, k, v, g, beta = _kda_inputs(rng, 4)
    S, outs = S0, []
    for t in range(4):
        o, S = delta_rule.step(S, q[t], k[t], v[t], g[t], beta[t])
        outs.append(o)
    o_want, S_want = delta_rule.recurrence(S0, q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs)),
                               np.asarray(o_want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_want), atol=1e-5)
    # beta = 0 and g = 0: bit for bit the state that came in
    _, same = delta_rule.step(S0, q[0], k[0], v[0], jnp.zeros_like(g[0]),
                              jnp.zeros_like(beta[0]))
    assert (np.asarray(same) == np.asarray(S0)).all()


# ------------------------------------------------------- whole forward
@pytest.mark.parametrize("seq", [37, 64])
def test_whole_sequence_forward_is_the_reference(seq, rng):
    cfg, model, params = build()
    ids = rng.integers(0, cfg["vocab_size"], size=seq).astype(np.int32)
    got = np.asarray(model(params, jnp.asarray(ids[None]))[0])
    np.testing.assert_allclose(got, ref_logits(params, cfg, ids),
                               atol=LOGIT_ATOL, rtol=0)


def test_tiny_configuration_keeps_every_mechanism():
    cfg, model, _ = build()
    c = model.config
    kinds = [c.is_kda(l) for l in range(c.num_hidden_layers)]
    assert kinds == [True] * 5 + [False, True]          # 5 : 1, and one on
    assert c.first_k_dense_replace == 1 and c.num_hidden_layers == 7
    assert (c.num_experts, c.experts_held, c.first_expert) == (32, 8, 8)
    assert (c.n_group, c.topk_group) == (4, 2)           # one group held
    contract = cache_contract(model)
    assert contract.kinds == (None,) and contract.page_layers == 1
    assert contract.state_kinds == (c.state_shapes,)
    assert contract.layers_of(0) == (5,)
    assert contract.layers_of(1) == (0, 1, 2, 3, 4, 6)
    assert not contract.by_kind and contract.is_state(1)
    assert contract.values_per_token == c.latent_dim     # one layer's
    # the full configuration's arithmetic: 13.0 MB a slot
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ling-3.0-flash-ep8-depth7.json")) as f:
        full = json.load(f)
    big = cache_contract(fam.build_model(full, full["serving"]))
    assert big.state_bytes_per_slot(1) == 6 * (2_097_152 + 73_728)
    assert fam.counts(full)["total_params"] == 2_866_268_096


# ------------------------------------------------------ through the engine
@pytest.mark.parametrize("plens", [
    (5,),            # shorter than a chunk: 11 padding rows
    (23, 9),         # not multiples of 4, two requests interleaved
    (40, 17, 30),    # chunks of one prompt between another's decode steps
])
def test_chunked_prefill_then_decode_is_the_references_full_forward(
        plens, rng):
    """Prefill in chunks (the state handed from chunk to chunk in the
    slot's row, padding rows masked) then decode (the state advanced in
    place, other slots' rows untouched), through `ServingEngine`: every
    served token stands within LOGIT_ATOL of the reference's largest
    logit given the stream's own prefix."""
    cfg, model, params = build()
    eng, reg = engine(model, params)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg["vocab_size"], size=n)
                    .astype(np.int32), max_new_tokens=6, arrival_t=0.01 * i)
            for i, n in enumerate(plens)]
    results = {r.rid: r for r in eng.run(reqs)}
    for req in reqs:
        gap = served_against_reference(cfg, params, req,
                                       results[req.rid].tokens)
        assert (gap <= LOGIT_ATOL).all(), (req.rid, gap)
    eng.scheduler.check_invariants()
    assert eng.pool.free_count == eng.pool.num_pages
    assert reg.counter_value("serve.state_resets") == len(plens)
    assert reg.counter_value("serve.chunk_padded_rows") == sum(
        -n % 16 for n in plens)
    assert reg.counter_value("serve.kda_state_bytes") > 0


def test_a_reused_slot_starts_from_zero_state(rng):
    """One slot, three requests one after the other: the second and third
    find the first's state in the slot's row and must not see it."""
    cfg, model, params = build()
    eng, reg = engine(model, params, num_slots=1)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg["vocab_size"], size=n)
                    .astype(np.int32), max_new_tokens=5, arrival_t=0.0)
            for i, n in enumerate((21, 7, 33))]
    results = {r.rid: r for r in eng.run(reqs)}
    for req in reqs:
        gap = served_against_reference(cfg, params, req,
                                       results[req.rid].tokens)
        assert (gap <= LOGIT_ATOL).all(), (req.rid, gap)
    assert reg.counter_value("serve.state_resets") == 3
    # the slot's row holds the last request's state; the null slot's row
    # only ever saw the warm-up's writes
    assert float(jnp.abs(eng.pool.state[0][:, 0]).max()) > 0


def test_a_preempted_request_is_prefilled_again_from_position_zero(rng):
    """A higher class evicts the live request mid-decode; re-admitted, it
    is prefilled from position 0 (its slot's state reset by the first
    chunk) and its stream is the undisturbed one."""
    cfg, model, params = build()
    low, high = SLOClass("batch", priority=0), SLOClass("chat", priority=5)
    first = Request(rid=0, prompt=rng.integers(
        0, cfg["vocab_size"], size=27).astype(np.int32), max_new_tokens=8,
        arrival_t=0.0, slo=low)
    second = Request(rid=1, prompt=rng.integers(
        0, cfg["vocab_size"], size=19).astype(np.int32), max_new_tokens=4,
        arrival_t=0.0, slo=high)
    alone, _ = engine(model, params, num_slots=1)
    want = alone.run([first])[0].tokens
    eng, reg = engine(model, params, num_slots=1, preempt=True)
    eng.submit(first, now=0.0)
    now, results = 0.0, []
    while not any(st is not None and len(st.generated) >= 3
                  for st in eng.scheduler.slots):
        results += eng.step(now)
        now += 1.0
    eng.submit(second, now=now)
    while eng.scheduler.active_slots() or eng.scheduler.queue:
        results += eng.step(now)
        now += 1.0
    results = {r.rid: r for r in results}
    assert reg.counter_value("serve.preemptions") == 1
    assert results[0].tokens == want
    assert reg.counter_value("serve.state_resets") == 3
    for req in (first, second):
        gap = served_against_reference(cfg, params, req,
                                       results[req.rid].tokens)
        assert (gap <= LOGIT_ATOL).all(), (req.rid, gap)


def test_fail_over_prefills_the_lost_requests_again(rng):
    cfg, model, params = build()
    eng, reg = engine(model, params, num_slots=2, retry_budget=1)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg["vocab_size"], size=n)
                    .astype(np.int32), max_new_tokens=6, arrival_t=0.0)
            for i, n in enumerate((22, 13))]
    for r in reqs:
        eng.submit(r, now=0.0)
    now, results = 0.0, []
    for _ in range(4):
        results += eng.step(now)
        now += 1.0
    assert sorted(eng.fail_over(now)["requeued"]) == [0, 1]
    while eng.scheduler.active_slots() or eng.scheduler.queue:
        results += eng.step(now)
        now += 1.0
    results = {r.rid: r for r in results}
    for req in reqs:
        gap = served_against_reference(cfg, params, req,
                                       results[req.rid].tokens)
        assert (gap <= LOGIT_ATOL).all(), (req.rid, gap)
    assert reg.counter_value("serve.state_resets") == 4


# --------------------------------------------------------- the contract
def test_pool_holds_state_beside_pages_and_hands_out_pages_only():
    _, model, params = build()
    eng, reg = engine(model, params)
    c, pool = model.config, eng.pool
    assert [a.shape for a in pool.arrays.tree()] == [
        (1, 65, 8, c.latent_stored_dim)]
    assert [(a.shape, a.dtype.name) for a in pool.state] == [
        ((6, 5) + c.state_shapes[0][0], "float32"),
        ((6, 5) + c.state_shapes[1][0], "float32")]      # (tiny: float32)
    assert pool.null_slot == 4 and len(pool.tree()) == 3
    assert pool.num_pages == 64 and pool.free_count == 64
    pages = pool.alloc(3)
    assert len(pages) == 3 and pool.free_count == 61
    pool.free(pages)
    eng.scheduler.check_invariants()
    assert not eng.windowed and eng.stateful
    per_slot = cache_contract(model).state_bytes_per_slot(1)
    assert reg.gauge_value("serve.state_bytes_per_slot",
                           kind="state") == per_slot
    assert reg.gauge_value("serve.kv_bytes_per_token", kind="state") == 0
    assert reg.gauge_value("serve.kv_bytes_per_token", kind="full") \
        == 4 * c.latent_dim
    assert reg.gauge_value("serve.held_bytes", what="state") \
        == 5 * per_slot
    # a pool sized for other slots than the scheduler's is caught
    pool.state = tuple(a[:, :4] for a in pool.state)
    with pytest.raises(AssertionError, match="state array"):
        eng.scheduler.check_invariants()


def test_contract_kinds_pages_first_then_state():
    sh = (((2, 4, 4), "float32"),)
    c = CacheContract(4, ((2, 8), (2, 8)), windows=(None, 16, None, None),
                      state_shapes=(None, None, sh, sh))
    assert c.kinds == (None, 16) and c.state_kinds == (sh,)
    assert [c.kind_of(l) for l in range(4)] == [0, 1, 2, 2]
    assert c.by_kind and c.page_layers == 2
    assert (c.arrays_of(0), c.arrays_of(1), c.arrays_of(2)) == (
        slice(0, 2), slice(2, 4), slice(4, 5))
    assert c.state_bytes_per_slot(2) == 2 * 2 * 4 * 4 * 4
    with pytest.raises(ValueError, match="has to hold pages"):
        CacheContract(1, ((8,),), state_shapes=(sh,))
    with pytest.raises(ValueError, match="reads no window"):
        CacheContract(2, ((2, 8), (2, 8)), windows=(None, 4),
                      state_shapes=(None, sh))
    # a model without state layers: as it always was
    plain = CacheContract(3, ((2, 8), (2, 8)))
    assert plain.state_kinds == () and plain.page_layers == 3
    assert PagePool.for_contract(plain, num_pages=4, page_size=8,
                                 num_slots=2).state == ()


@pytest.mark.parametrize("serve,names", [
    (dict(spec_decode="ngram"), "speculative decoding"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(kv_quant="int8"), "int8 / int4 pages"),
    (dict(moe_dispatch="int8"), "resident quantized experts"),
    (dict(kv_repage=True), "kv_repage"),
])
def test_what_is_not_built_for_state_beside_pages_is_refused_by_name(
        serve, names):
    _, model, params = build()
    with pytest.raises(NotImplementedError, match=names) as e:
        ServingEngine(model, params, ServeConfig(
            num_slots=2, page_size=8, max_len=64, prefill_chunk=16,
            **serve), registry=MetricsRegistry())
    assert "keep a state a sequence" in str(e.value)


def test_the_reshard_hook_the_prefill_tier_and_generate_refuse_state():
    from hetu_tpu.serving.disagg import PrefillWorker
    _, model, params = build()
    with pytest.raises(NotImplementedError, match="reshard hook"):
        ServingEngine(model, params, ServeConfig(
            num_slots=2, page_size=8, max_len=64, prefill_chunk=16),
            registry=MetricsRegistry(), reshard=object())
    with pytest.raises(NotImplementedError, match="disagg"):
        PrefillWorker(model, params, prefill_chunk=16, max_len=64)
    eng, _ = engine(model, params)
    with pytest.raises(NotImplementedError, match="adopt_prefilled"):
        eng.adopt_prefilled(Request(rid=0, prompt=np.zeros(4, np.int32),
                                    max_new_tokens=1), None, None, 0, 0.0)
    with pytest.raises(NotImplementedError, match="state a sequence"):
        gen.generate(model, params, jnp.zeros((1, 4), jnp.int32),
                     max_new_tokens=2)


# ------------------------------------------------------------ the gate
def _gate_inputs(rng, T=64, h=32, E=32):
    return (jnp.asarray(rng.standard_normal((T, h)), F32),
            jnp.asarray(0.3 * rng.standard_normal((h, E)), F32),
            jnp.asarray(0.05 * rng.standard_normal((E,)), F32))


def test_one_group_is_the_gate_as_it_always_was(rng):
    x, w, b = _gate_inputs(rng)
    kw = dict(top_k=4, norm_topk_prob=True, routed_scaling_factor=2.5)
    i0, w0 = moe.noaux_tc_gate(x, w, b, **kw)
    i1, w1 = moe.noaux_tc_gate(x, w, b, n_group=1, topk_group=1, **kw)
    assert (np.asarray(i0) == np.asarray(i1)).all()
    assert (np.asarray(w0) == np.asarray(w1)).all()          # bit for bit
    # the layer passes no group argument at 1 and 1
    layer = moe.SharedRoutedExperts(
        32, 16, n_routed_experts=32, experts_held=8, first_expert=0,
        top_k=4, n_shared_experts=1, norm_topk_prob=True,
        routed_scaling_factor=2.5)
    assert layer.groups == {}


def test_grouped_gate_against_a_plain_one(rng):
    """n_group 4, topk_group 2 over 32 experts, by numpy: a group's score
    is the sum of its two largest s + b, the two best groups stay, the 4
    largest s + b inside them are chosen, weights s over their sum."""
    x, w, b = _gate_inputs(rng)
    idx, wt = moe.noaux_tc_gate(x, w, b, top_k=4, norm_topk_prob=True,
                                routed_scaling_factor=2.5, n_group=4,
                                topk_group=2)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                              @ np.asarray(w, np.float64))))
    v = s + np.asarray(b, np.float64)
    differs = 0
    for t in range(x.shape[0]):
        groups = v[t].reshape(4, 8)
        score = np.sort(groups, axis=-1)[:, -2:].sum(-1)
        keep = np.argsort(-score)[:2]
        inside = np.full(32, -np.inf)
        for g in keep:
            inside[g * 8:(g + 1) * 8] = v[t, g * 8:(g + 1) * 8]
        want = np.argsort(-inside)[:4]
        assert sorted(want) == sorted(np.asarray(idx[t])), t
        ws = s[t, np.asarray(idx[t])]
        np.testing.assert_allclose(np.asarray(wt[t]), 2.5 * ws / ws.sum(),
                                   rtol=1e-5)
        differs += sorted(want) != sorted(np.argsort(-v[t])[:4])
    assert differs > 5         # the groups DO change the choice
    # and the reference's own gate agrees
    cfg = dict(n_group=4, topk_group=2, num_experts_per_tok=4,
               norm_topk_prob=True, routed_scaling_factor=2.5)
    mp = {"w_gate": w, "e_score_correction_bias": b}
    with jax.default_matmul_precision("highest"):
        ridx, rw = fam.gate(x, mp, cfg)
    assert (np.sort(np.asarray(ridx), -1) == np.sort(np.asarray(idx), -1)
            ).all()


def _layer(first, held, key=3):
    layer = moe.SharedRoutedExperts(
        32, 16, n_routed_experts=64, experts_held=held, first_expert=first,
        top_k=8, n_shared_experts=1, norm_topk_prob=True,
        routed_scaling_factor=2.5, n_group=8, topk_group=4,
        param_dtype=F32, bias_range=0.2)
    return layer, layer.init(jax.random.key(key))


def _share(params, first, held):
    return dict(params, **{k: params[k][first:first + held]
                           for k in ("w_gate_up", "w_down")})


CFG_LAYER = {"num_experts_per_tok": 8, "norm_topk_prob": True,
             "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4}


def test_the_eight_shares_sum_to_the_uncut_layer(rng):
    """Guide s4's share test at the deployment's shape: eight shares of
    ONE routing group each (8 of 64 experts), the shared expert counted
    once, add up to what the uncut reference gives for the whole
    layer."""
    whole, params = _layer(0, 64)
    x = jnp.asarray(rng.standard_normal((2, 9, 32)), F32)
    xt = x.reshape(18, 32)
    with jax.default_matmul_precision("highest"):
        want = fam.experts(xt, params, CFG_LAYER)
        shared = fam._swiglu(xt, params["shared_gate_up"],
                             params["shared_down"])
    total, local = 0.0, 0
    for first in range(0, 64, 8):
        part, stats = _layer(first, 8)[0](_share(params, first, 8), x)
        total = total + part.reshape(18, 32) - shared
        local += int(stats[1])
        with jax.default_matmul_precision("highest"):
            alone = fam.experts(xt, _share(params, first, 8),
                                dict(CFG_LAYER, first_expert=first))
        np.testing.assert_allclose(np.asarray(part.reshape(18, 32)),
                                   np.asarray(alone), atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=5e-5, rtol=0)
    assert local == 18 * 8         # every pair fell on exactly one share
    got, stats = whole(params, x)
    np.testing.assert_allclose(np.asarray(got.reshape(18, 32)),
                               np.asarray(want), atol=5e-5, rtol=0)


# ------------------------------------------------- scopes and programs
def test_new_scopes_are_groups_and_old_programs_keep_theirs():
    from hetu_tpu.obs.hlo_profile import (PHASES, SCOPE_MAP_GROUPS,
                                          group_of)
    phases = (*PHASES, *SCOPE_MAP_GROUPS)
    for scope in ("kda", "kda_proj", "kda_conv", "kda_scan", "kda_step",
                  "kda_out"):
        assert scope in SCOPE_MAP_GROUPS
    assert group_of("jit(decode_fn)/layer/attn/kda/kda_step/mul",
                    phases) == "layer/kda_step"
    assert group_of("jit(decode_fn)/layer/attn/kda/dynamic_update_slice",
                    phases) == "layer/kda"
    assert group_of("jit(decode_fn)/layer/attn/mla_q/dot_general",
                    phases) == "layer/mla_q"
    assert group_of("jit(decode_fn)/layer/attn/dot_general",
                    phases) == "layer/attn"


def test_both_programs_carry_the_scopes_and_alias_the_state():
    """The decode and the chunk program run the KDA layers under their
    scopes, take the state arrays as donated arguments and hand them
    back: every state array of the arguments is aliased to an output."""
    _, model, params = build()
    eng, _ = engine(model, params)
    lowered = eng.lower_programs()
    for name, scopes in (("decode", ("kda_step", "kda_conv", "kda_proj",
                                     "kda_out", "mla_q")),
                         ("prefill_chunk", ("kda_scan", "kda_conv"))):
        text = lowered[name].as_text(debug_info=True)
        for scope in scopes:
            assert f"/{scope}/" in text or f"/{scope}\"" in text, (
                name, scope)
        compiled = lowered[name].compile()
        state = sum(a.size * a.dtype.itemsize for a in eng.pool.state)
        assert compiled.memory_analysis().alias_size_in_bytes >= state
    assert "write_pages" in lowered
    # the chunk program asks the scan's route once a KDA layer it traces
    # (the tiny configuration scans its layers: one trace for the run of
    # them; 16-wide heads on a CPU keep the composition), the decode
    # program asks for none
    rec = eng.kernel_routes["kda_scan"]
    assert rec["xla"] >= 1 and not rec["pallas"]
    assert list(rec["why"]) == ["not a TPU backend"]
