"""DeepSeek-V3.2 on the serving path, at a tiny size that keeps every
mechanism: Kimi-K2's block whose latent attention attends the positions a
lightning indexer SELECTS (DeepSeek Sparse Attention), a token's two cache
entries under one page table, group-limited routing over a share of the
experts.  Seeded random float32 weights; the reference is
`benchmarks/families/deepseek_v32.py`'s plain forward, which shares no
code with the program: not the scores, not the selection (a stable sort
there, a bisection or `lax.top_k` here), not the attention.

Tolerances: tests/test_kimi_k2.py's (program and reference are both
float32 here and differ by the order of float32 sums: LOGIT_ATOL = 2e-4
is two orders over that and three under what a wrong mask, rotation,
expert or SELECTION moves).  The tiny `index_topk` is 24 and every test
context is longer, so the selection decides what every checked row
attends.
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

from benchmarks import reference  # noqa: E402
from benchmarks.families import deepseek_v32 as fam  # noqa: E402
from hetu_tpu.models import generation as gen  # noqa: E402
from hetu_tpu.models.cache_contract import cache_contract  # noqa: E402
from hetu_tpu.models.kimi_k2 import KimiK2LMHeadModel  # noqa: E402
from hetu_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from hetu_tpu.ops import sparse_attention as dsa  # noqa: E402
from hetu_tpu.serving.engine import ServeConfig, ServingEngine  # noqa: E402
from hetu_tpu.serving.request import Request  # noqa: E402

from test_kimi_k2 import _programs as kimi_programs  # noqa: E402

LOGIT_ATOL = 2e-4
F32 = jnp.float32


def tiny_cfg():
    """The rehearsal's configuration, read as the plain forward (no
    near-tie pass: a test of logits compares ONE computation)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tiny-deepseek-v32.json")) as f:
        cfg = json.load(f)
    del cfg["router_tie_logit"]
    return cfg


_BUILT = {}


def build(**over):
    """(configuration, model, seeded parameters); made once a
    configuration: no test writes to any of them."""
    key = tuple(sorted(over.items()))
    if key not in _BUILT:
        cfg = dict(tiny_cfg(), **over)
        model = fam.build_model(cfg, cfg["serving"])
        _BUILT[key] = cfg, model, model.init(jax.random.key(7))
    return _BUILT[key]


_REF = {}
REF_LEN = 64


def ref_logits(params, cfg, ids, control=None):
    """The reference's logits at every position of `ids`; ONE program a
    (configuration, control) and length class: the ids are right-padded
    to a multiple of `REF_LEN` (causal, so the pad is inert)."""
    n = len(ids)
    padded = np.zeros(-(-n // REF_LEN) * REF_LEN, np.int32)
    padded[:n] = ids
    key = (json.dumps(cfg, sort_keys=True), len(padded), control)
    if key not in _REF:
        _REF[key] = jax.jit(lambda p, i: fam.logits_at(
            p, i, jnp.arange(i.shape[0]), cfg, control))
    return np.asarray(_REF[key](params, jnp.asarray(padded)))[:n]


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
    cfg, model, params = build()
    c = model.config
    assert (c.index_n_heads, c.index_head_dim, c.index_topk) == (4, 16, 24)
    assert (c.n_group, c.topk_group) == (4, 2)
    assert (c.n_routed_experts, c.experts_held, c.first_expert) == (16, 4, 4)
    runs = model.serving_layers(model.abstract_params())
    assert [b.moe for b, _, _ in runs] == [False, True, True]
    for _, lp, _ in model.serving_layers(params):
        ip = lp["attn"]["indexer"]
        assert {k: v.shape for k, v in ip.items() if k != "k_norm"} == {
            "wq_b": (48, 64), "wk": (64, 16), "w_heads": (64, 4)}
        assert {k: v.shape for k, v in ip["k_norm"].items()} == {
            "weight": (16,), "bias": (16,)}
    # a token stores the latent AND the indexer's key, and every layer
    # says how many of the positions it sees a query attends
    contract = model.cache_contract()
    assert contract.token_shapes == ((136,), (16,))
    assert contract.stored_shapes == ((256,), (16,))
    assert contract.selects == (24, 24, 24) and contract.kind == "latent"
    assert not contract.by_kind and contract.kinds == (None,)
    assert model.num_params() == fam.counts(cfg)["total_params"]


def _programs(model, params, prompt):
    """tests/test_kimi_k2.py's: the prompt prefilled in chunks into a
    dense scratch (here TWO arrays a layer), its pages written; (the
    chunk program's logits, the pool tree, the table, the stats)."""
    return kimi_programs(model, params, prompt, 0, max_len=128)


@pytest.mark.parametrize("plen", [23, 40, 81])
def test_chunked_prefill_page_write_and_paged_decode_are_the_reference(
        plen, rng):
    """Contexts straddle the tiny `index_topk` of 24, a page (8) and a
    chunk (16): the chunk program attends under the mask of the
    selection, the decode step gathers the selected entries."""
    cfg, model, params = build()
    n_decode = 6
    seq = rng.integers(0, cfg["vocab_size"],
                       size=plen + n_decode).astype(np.int32)
    want = ref_logits(params, cfg, seq)
    prefill_logits, tree, table, stats = _programs(model, params, seq[:plen])
    np.testing.assert_allclose(prefill_logits, want[:plen],
                               atol=LOGIT_ATOL, rtol=0)
    decode = jax.jit(gen.decode_step_paged, static_argnums=0)
    for i in range(n_decode):
        tokens = np.zeros(3, np.int32)
        positions = np.zeros(3, np.int32)
        tokens[1], positions[1] = seq[plen + i], plen + i
        lg, tree, stats = decode(model, params, jnp.asarray(tokens), tree,
                                 jnp.asarray(table), jnp.asarray(positions),
                                 stats)
        np.testing.assert_allclose(np.asarray(lg[1]), want[plen + i],
                                   atol=LOGIT_ATOL, rtol=0)


# ------------------------------------------------------------------ (b)

def _layer_inputs(rng, s=96):
    """One layer's attention module, its parameters, normed hidden
    states [1, s, hidden], the rotation tables and the positions."""
    cfg, model, params = build()
    block, lp, _ = model.serving_layers(params)[1]
    hn = jnp.asarray(rng.standard_normal((1, s, cfg["hidden_size"])), F32)
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    return cfg, block.attn, lp["attn"], hn, model.rope_tables(s), pos


def _reference_selection(cfg, ap, hn, control=None):
    with jax.default_matmul_precision("highest"):
        h, pos = hn[0], jnp.arange(hn.shape[1])
        cq = fam._rms_norm(h @ ap["wq_a"], ap["q_norm"]["weight"],
                           cfg["rms_norm_eps"])
        keys = fam.entries(h, pos, ap, cfg)[2]
        scores = fam.index_scores(cq, h, pos, keys, ap["indexer"], cfg,
                                  control)
        return np.asarray(scores), np.asarray(
            fam.selected(scores, pos, cfg, control))


def test_the_programs_selected_set_is_the_references(rng):
    """Position by position: the mask the chunk program attends under and
    the positions the decode step gathers are the reference's S_t for
    every query (float32 on both sides: no score stands within rounding
    of the 24th here; on the chip, in bfloat16, the measured swaps are
    PERF.md s6's)."""
    cfg, attn, ap, hn, rope, pos = _layer_inputs(rng)
    scores, want = _reference_selection(cfg, ap, hn)
    q, (_, keys) = attn.project(ap, hn, rope, pos)
    got_scores = np.asarray(dsa.index_scores(q[2], q[3], keys, pos))[0]
    seen = np.isfinite(scores)
    assert (np.isfinite(got_scores) == seen).all()
    np.testing.assert_allclose(got_scores[seen], scores[seen], atol=1e-5)
    keep = np.asarray(attn._keep(q, keys, pos))[0]
    assert (keep == want).all()
    assert (keep.sum(-1) == np.minimum(np.arange(96) + 1, 24)).all()
    idx, valid = dsa.select_indices(jnp.asarray(got_scores), 24)
    for t in (3, 23, 24, 60, 95):
        assert set(np.asarray(idx[t])[np.asarray(valid[t])]) \
            == set(np.flatnonzero(want[t]))


def test_the_references_quarters_are_the_whole_sequence(rng):
    """The reference takes a whole sequence's queries a quarter at a time,
    each over the positions up to its own end (33,792 positions at 128
    heads: five eighths of the work): the same numbers as every query
    over every position under the causal mask."""
    cfg, attn, ap, hn, rope, pos = _layer_inputs(rng, s=1024)
    with jax.default_matmul_precision("highest"):
        h, at = hn[0], jnp.arange(1024)
        ent = fam.entries(h, at, ap, cfg)
        whole = jax.jit(lambda h: fam.attend(h, at, *ent, ap, cfg,
                                             whole=True))(h)
        plain = jax.jit(lambda h: fam.attend(h, at, *ent, ap, cfg))(h)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(plain),
                               atol=1e-5, rtol=0)


def test_bfloat16_scores_select_another_set_where_float32_selects_the_same(
        rng):
    """The scores are float32 whatever the operands: rounded to bfloat16
    before the ReLU and the sum over heads, the selection differs from
    the reference's in some rows; in float32 in none."""
    cfg, attn, ap, hn, rope, pos = _layer_inputs(rng, s=512)
    cfg = dict(cfg, index_topk=128)
    _, want = _reference_selection(cfg, ap, hn)
    q, (_, keys) = attn.project(ap, hn, rope, pos)

    def rows_off(scores):
        keep = np.asarray(dsa.select_mask(scores, 128))[0]
        return int((keep != want).any(-1).sum())
    assert rows_off(dsa.index_scores(q[2], q[3], keys, pos)) == 0
    # the control, this test's own copy of the score: each head's
    # q . key rounded to bfloat16 before the ReLU and the sum over heads
    s = jnp.einsum("bqhd,bkd->bqhk", q[2], keys,
                   preferred_element_type=jnp.float32)
    s = jnp.einsum("bqhk,bqh->bqk", jax.nn.relu(
        s.astype(jnp.bfloat16).astype(jnp.float32)), q[3])
    seen = jnp.arange(512)[None, None, :] <= pos[:, :, None]
    assert rows_off(jnp.where(seen, s, -jnp.inf)) > 10


def test_equal_scores_go_to_the_lower_position():
    """The tie rule, in the three places that select: the mask by
    bisection, the indices by `lax.top_k`, the reference's stable sort."""
    s = np.full((4, 40), -np.inf, np.float32)
    s[0, :30] = 1.0                          # all equal: the first 8
    s[1, :30] = np.arange(30) % 3            # 10 twos, then ones
    s[2, :5] = [0.0, -0.0, 0.0, 2.0, -0.0]   # -0.0 IS 0.0; fewer than k
    s[3, :30] = np.r_[np.zeros(10), -np.ones(10), np.zeros(10)]
    want = np.zeros((4, 40), bool)
    want[0, :8] = True
    want[1, [2, 5, 8, 11, 14, 17, 20, 23]] = True
    want[2, :5] = True
    want[3, :8] = True
    scores = jnp.asarray(s)
    assert (np.asarray(dsa.select_mask(scores, 8)) == want).all()
    idx, valid = dsa.select_indices(scores, 8)
    for r in range(4):
        assert sorted(np.asarray(idx[r])[np.asarray(valid[r])]) \
            == list(np.flatnonzero(want[r]))
    cfg = dict(tiny_cfg(), index_topk=8)
    pos = jnp.full((4,), 39)
    assert (np.asarray(fam.selected(scores, pos, cfg)) == want).all()
    # a row of 12 twos and a k of 8 mid-run of them: the lowest 8
    many = jnp.asarray(np.r_[np.ones(5), 2 * np.ones(12), np.ones(23)]
                       [None].astype(np.float32))
    assert list(np.flatnonzero(np.asarray(dsa.select_mask(many, 8))[0])) \
        == list(range(5, 13))


def test_a_selection_of_everything_is_kimis_attention(rng):
    """`index_topk` >= the context: every query attends all it sees, and
    the logits are `KimiK2LMHeadModel`'s over the same weights, the
    indexer's ignored: whole sequences, and chunks + paged decode."""
    cfg, model, params = build(index_topk=128)
    kimi = KimiK2LMHeadModel(model.config)

    def without_indexer(tree):
        return {k: without_indexer(v) for k, v in tree.items()
                if k != "indexer"} if isinstance(tree, dict) else tree
    kparams = without_indexer(params)
    assert jax.tree.structure(kparams) == jax.tree.structure(
        kimi.abstract_params())
    seq = rng.integers(0, cfg["vocab_size"], size=46).astype(np.int32)
    want = np.asarray(jax.jit(kimi.forward)(kparams, jnp.asarray(seq[None])))
    got = np.asarray(jax.jit(model.forward)(params, jnp.asarray(seq[None])))
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)
    prefill_logits, tree, table, stats = _programs(model, params, seq[:40])
    np.testing.assert_allclose(prefill_logits, want[0, :40],
                               atol=LOGIT_ATOL, rtol=0)
    decode = jax.jit(gen.decode_step_paged, static_argnums=0)
    for i in range(40, 46):
        tokens = np.zeros(3, np.int32)
        positions = np.zeros(3, np.int32)
        tokens[1], positions[1] = seq[i], i
        lg, tree, stats = decode(model, params, jnp.asarray(tokens), tree,
                                 jnp.asarray(table), jnp.asarray(positions),
                                 stats)
        np.testing.assert_allclose(np.asarray(lg[1]), want[0, i],
                                   atol=LOGIT_ATOL, rtol=0)
    # and with the tiny selection of 24 they are NOT Kimi's
    _, sparse, sparams = build()
    off = np.asarray(jax.jit(sparse.forward)(sparams, jnp.asarray(seq[None])))
    assert np.abs(off[0, 30:] - want[0, 30:]).max() > 100 * LOGIT_ATOL


def test_the_shares_of_the_chips_sum_to_the_uncut_layer(rng):
    """Guide s4's share test at the tiny router's 16 outputs in 4 groups
    of which 2 are kept: four shares of 4 experts each, the shared expert
    counted once, add up to what the uncut reference gives for the whole
    layer; each share alone is the reference GIVEN that share."""
    from hetu_tpu.nn.moe import SharedRoutedExperts
    cfg = dict(tiny_cfg(), hidden_size=32, first_expert=0)

    def layer(first, held):
        return SharedRoutedExperts(
            32, cfg["moe_intermediate_size"], n_routed_experts=16,
            experts_held=held, first_expert=first,
            top_k=cfg["num_experts_per_tok"], n_shared_experts=1,
            norm_topk_prob=True,
            routed_scaling_factor=cfg["routed_scaling_factor"],
            n_group=cfg["n_group"], topk_group=cfg["topk_group"])
    whole = layer(0, 16)
    params = whole.init(jax.random.key(11))

    def share(first, held):
        return dict(params, **{k: params[k][first:first + held]
                               for k in ("w_gate_up", "w_down")})
    x = jnp.asarray(rng.standard_normal((2, 9, 32)), F32)
    flat = x.reshape(18, 32)
    with jax.default_matmul_precision("highest"):
        want = fam.experts(flat, params, cfg)
        shared = fam._swiglu(flat, params["shared_gate_up"],
                             params["shared_down"])
    total = 0.0
    for first in (0, 4, 8, 12):
        part, _ = layer(first, 4)(share(first, 4), x)
        total = total + part.reshape(18, 32) - shared   # its routed part
        with jax.default_matmul_precision("highest"):
            alone = fam.experts(flat, share(first, 4),
                                dict(cfg, first_expert=first))
        np.testing.assert_allclose(np.asarray(part.reshape(18, 32)),
                                   np.asarray(alone), atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=5e-5, rtol=0)
    got_whole, stats = whole(params, x)
    np.testing.assert_allclose(np.asarray(got_whole.reshape(18, 32)),
                               np.asarray(want), atol=5e-5, rtol=0)
    assert list(np.asarray(stats)[:2]) == [72, 72]


def test_cache_bytes_pool_and_scratch_come_from_the_contract():
    """One place says what a token stores: at the published widths the
    latent's 576 values + the index key's 128 = 1,408 B a layer in
    bfloat16 (the 640 lanes the latent is stored in are not a token's
    bytes); the pool holds the two arrays under the same page ids and the
    scratch two arrays a layer."""
    from hetu_tpu.models.deepseek_v32 import (DeepseekV32Config,
                                              DeepseekV32LMHeadModel)
    from hetu_tpu.serving.costs import CostModel
    from hetu_tpu.serving.kv_pool import PagePool, contract_bytes_per_token
    full = DeepseekV32LMHeadModel(DeepseekV32Config(
        num_hidden_layers=5, first_k_dense_replace=1, experts_held=8))
    contract = cache_contract(full)
    assert contract.token_shapes == ((576,), (128,))
    assert contract.stored_shapes == ((640,), (128,))
    assert contract.selects == (2048,) * 5
    assert contract_bytes_per_token(contract, "bf16") == 5 * 1408
    cm = CostModel.from_model(full, num_params=3.2e9, page_size=256,
                              kv_mode="bf16")
    assert cm.kv_bytes_per_token == 5 * 1408
    pool = PagePool.for_contract(contract, num_pages=4, page_size=256,
                                 device_arrays=False)
    assert pool.token_shapes == ((640,), (128,)) and not pool.windowed
    with pytest.raises(ValueError, match="exact"):
        contract_bytes_per_token(contract, "int8")
    # the tiny engine's: two arrays a layer, one table
    cfg, model, params = build()
    engine = ServingEngine(model, params, ServeConfig(
        num_slots=2, page_size=8, max_len=64, prefill_chunk=16,
        num_pages=16))
    assert [a.shape for a in engine.pool.arrays.tree()] == [
        (3, 17, 8, 256), (3, 17, 8, 16)]
    assert [a.shape for a in jax.eval_shape(engine._fresh_scratch)] == [
        (3, 1, 64, 256), (3, 1, 64, 16)]
    assert engine.scheduler.page_table.shape == (2, 8)
    reg = engine._registry.snapshot()
    held = {g["labels"]["what"]: g["value"] for g in reg["gauges"]
            if g["name"] == "serve.held_bytes"}
    assert held["pool"] == 3 * 17 * 8 * (256 + 16) * 4
    assert held["scratch"] == 3 * 64 * (256 + 16) * 4
    engine.close()


def test_a_contract_says_what_it_selects_and_refuses_what_it_cannot():
    from hetu_tpu.models.cache_contract import CacheContract, kv_contract
    assert kv_contract(3, 2, 16).selects == (None,) * 3
    with pytest.raises(ValueError, match="3 layers"):
        CacheContract(3, ((8,),), kind="latent", selects=(4, 4))
    with pytest.raises(ValueError, match="count of positions"):
        CacheContract(2, ((8,),), kind="latent", selects=(0, 4))
    with pytest.raises(ValueError, match="NUMBER of arrays"):
        CacheContract(2, ((8,), (4,)), kind="latent",
                      layer_token_shapes=(((8,), (4,)), ((8,),)))


# ------------------------------------------------------------------ (c)

@pytest.mark.parametrize("control", fam.CONTROLS)
def test_a_wrong_selection_is_not_the_reference(control, rng):
    """The ReLU dropped, half the selection, the last 24 positions for
    the selected ones, flat head weights: each moves the logits by O(1)."""
    cfg, model, params = build()
    ids = rng.integers(0, cfg["vocab_size"], size=64).astype(np.int32)
    got = np.asarray(jax.jit(model.forward)(params, jnp.asarray(ids[None])))
    bad = ref_logits(params, cfg, ids, control)
    assert np.abs(got[0] - bad).max() > 100 * LOGIT_ATOL
    if control == "recent_2048":
        # a context of 24 or fewer: the last 24 ARE the selection
        assert np.abs(got[0, :24] - bad[:24]).max() <= LOGIT_ATOL


@pytest.fixture(scope="module")
def served():
    """Streams the tiny engine served: prompts past the tiny selection,
    inside a chunk, at its edge and over several, more requests than
    slots."""
    cfg, model, params = build()
    reg = MetricsRegistry()
    engine = ServingEngine(model, params, ServeConfig(
        num_slots=3, page_size=8, max_len=128, prefill_chunk=16,
        num_pages=48), registry=reg)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg["vocab_size"], size=n)
                    .astype(np.int32), max_new_tokens=24, arrival_t=0.0)
            for i, n in enumerate((26, 32, 81, 50))]
    results = {r.rid: r for r in engine.run(reqs)}
    texts = {name: low.compile().as_text()
             for name, low in engine.lower_programs().items()}
    engine.close()
    return cfg, params, reqs, results, reg, texts


_CHECKS = {}


def _check(served, control=None, cfg=None):
    own, params, reqs, results = served[:4]

    def forward(p, ids, rows, c):
        return fam.logits_at(p, ids, rows, c, control)
    forward = _CHECKS.setdefault(control, forward)
    return [reference.check_stream(forward, params, cfg or own, r.prompt,
                                   results[r.rid].tokens, 128)
            for r in reqs]


def test_served_streams_are_correct_by_the_comparison(served):
    streams = _check(served)
    assert all(s["ok"] and s["max_gap"] == 0.0 for s in streams), streams
    # and under the near-tie passes of the rehearsal's configuration
    tied = _check(served, cfg=dict(served[0], router_tie_logit=0.02))
    assert all(s["ok"] for s in tied), tied


@pytest.mark.parametrize("control", fam.CONTROLS)
def test_the_controls_show_in_the_comparison(served, control):
    """Against the reference with ONE thing of the selection done wrongly
    served streams lose tokens to other candidates and show a gap, where
    against the reference as it stands none does.  The controlled
    reference runs the SAME near-tie passes as the check of a sound
    program does: what they forgive a control is forgiven."""
    bad = _check(served, control,
                 cfg=dict(served[0], router_tie_logit=0.02))
    assert any(s["argmax_equal"] < s["tokens"] and s["max_gap"] > 0
               for s in bad), bad


def test_scopes_and_counters_of_the_programs(served):
    """The five scopes stand in both programs inside `attn`, beside
    MLA's, and hold none of its projections and no expert; the two
    counters are what the schedule says."""
    from hetu_tpu.obs import hlo_profile as hp
    cfg, _, reqs, results, reg, texts = served
    scopes = {"dsa_index_q", "dsa_index_k", "dsa_score", "dsa_select",
              "dsa_attend"}
    assert scopes <= set(hp.SCOPE_MAP_GROUPS)
    for name in ("decode", "prefill_chunk"):
        groups = {g for g, _ in hp.scope_map(texts[name]).values()}
        assert {f"layer/{s}" for s in scopes} | {
            "layer/mla_q", "layer/mla_kv", "layer/kv_write",
            "layer/mla_out", "layer/router", "layer/experts",
            "layer/shared_expert", "layer/attn", "layer/mlp", "embed",
            "lm_head"} <= groups, (name, groups)
        for line in texts[name].splitlines():
            if "dsa_" in line and 'op_name="' in line:
                path = line.split('op_name="')[1].split('"')[0].split("/")
                assert "attn" in path and "mlp" not in path, path
                assert not {"mla_q", "mla_kv", "mla_out", "experts",
                            "router"} & set(path), path
    # every request decodes 23 tokens after its first, at contexts of
    # prompt + 1 .. prompt + 23, all past the selection of 24: 3 layers
    # x 24 a slot step; the chunk rows select min(position + 1, 24)
    steps = sum(len(results[r.rid].tokens) - 1 for r in reqs)
    assert reg.counter_value("serve.decode_slot_steps") == steps == 4 * 23
    assert reg.counter_value("serve.decode_selected_tokens") \
        == 3 * 24 * steps
    assert reg.counter_value("serve.decode_context_tokens") == sum(
        r.prompt_len + i for r in reqs for i in range(1, 24))
    assert reg.counter_value("serve.decode_selectable_tokens") \
        == 3 * reg.counter_value("serve.decode_context_tokens")
    want = 3 * sum(min(t + 1, 24) for r in reqs
                   for t in range(-(-r.prompt_len // 16) * 16))
    assert reg.counter_value("serve.prefill_selected_keys") == want
    pairs = reg.counter_value("serve.prefill_attended_keys", kind="full")
    assert want < 3 * pairs
    # the cost functions read them
    window = {"counters": {
        k: reg.counter_value(k) for k in (
            "serve.decode_slot_steps", "serve.decode_selected_tokens",
            "serve.decode_selectable_tokens", "serve.prefill_tokens",
            "serve.prefill_chunks")}}
    c = window["counters"]
    c["serve.prefill_attended_keys"] = pairs
    cost = fam.sparse_latent_attn_cost(cfg, window, elem_bytes=4.0)
    assert cost["ops"] == 2.0 * (
        4 * 16 * c["serve.decode_selectable_tokens"]
        + 4 * (136 + 128) * c["serve.decode_selected_tokens"])
    cost = fam.indexer_score_cost(cfg, window, elem_bytes=4.0)
    assert cost["ops"] == 3 * 2.0 * 4 * 16 * c["serve.prefill_attended_keys"]
    assert fam.indexer_score_cost(cfg, {"counters": {}}) is None
    assert fam.sparse_latent_attn_cost(cfg, {"counters": {}}) is None
