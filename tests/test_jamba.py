"""Jamba (`model_type: jamba`, AI21-Jamba2-3B) on the serving path, at a
tiny size that keeps every mechanism: one whole period of 14 layers, 13
Mamba-1 layers with normed dt / B / C whose state the pool holds by slot
(runs of 7 and 6 around the attention layer), ONE attention layer of ONE
K/V head read by 6 query heads (a group that is no power of two, as the
published 20), no positional encoding.  Seeded random float32 weights;
the reference is `benchmarks/families/jamba.py`'s plain forward (a
`lax.scan` over positions, one softmax over an explicit mask, no chunks,
no cache), which shares no code with the program.

Tolerances.  Program and reference are both float32 here, so they differ
by the ORDER of float32 sums only (a blocked walk against a scan, one
row's product against a chunk's): logits of O(1) agree to a few 1e-6;
LOGIT_ATOL = 2e-4 leaves two orders of room and is two orders under what
leaving the inner norms out moves (0.03 and more).  A state kept in
bfloat16 moves these logits by less than that room: at 192 channels the
state's share of a Mamba layer's output is a thousandth of what it is at
5,120, so that control is held on the scan's own output, where the state
is all there is (SCAN_RTOL)."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.families import jamba as fam  # noqa: E402
from hetu_tpu.models import generation as gen  # noqa: E402
from hetu_tpu.models.cache_contract import cache_contract  # noqa: E402
from hetu_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from hetu_tpu.serving.engine import ServeConfig, ServingEngine  # noqa: E402
from hetu_tpu.serving.kv_pool import (PagePool,  # noqa: E402
                                      contract_bytes_per_token)
from hetu_tpu.serving.request import Request, SamplingParams  # noqa: E402

LOGIT_ATOL = 2e-4
#: the scan's output against the reference's, relative to its largest
SCAN_RTOL = 1e-5
F32 = jnp.float32


def config(name="tiny-jamba"):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


def build(**over):
    cfg = dict(config(), **over)
    model = fam.build_model(cfg, cfg["serving"])
    return cfg, model, model.init(jax.random.key(7))


_REF = {}


def ref_logits(params, cfg, ids, control=None):
    """The reference's logits at every position of `ids`, which are
    padded to a multiple of 64 positions (the model is causal: what
    follows a position does not reach it) so that one compiled forward
    serves every stream of a length class, in every test of this file
    (all of them build the one tiny configuration)."""
    n = len(ids)
    pad = -(-n // 64) * 64
    if (control, pad) not in _REF:
        _REF[control, pad] = jax.jit(lambda p, i: fam.logits_at(
            p, i, jnp.arange(pad), cfg, control))
    padded = np.zeros(pad, np.int32)
    padded[:n] = ids
    return np.asarray(_REF[control, pad](params, jnp.asarray(padded)))[:n]


def engine(model, params, **serve):
    reg = MetricsRegistry()
    cfg = dict(num_slots=3, page_size=8, max_len=128, prefill_chunk=16,
               num_pages=48)
    cfg.update(serve)
    return ServingEngine(model, params, ServeConfig(**cfg), registry=reg), reg


def requests(rng, cfg, plens, new=6, **kw):
    return [Request(rid=i, prompt=rng.integers(0, cfg["vocab_size"], size=n)
                    .astype(np.int32), max_new_tokens=new,
                    arrival_t=0.01 * i, **kw) for i, n in enumerate(plens)]


def gaps(params, cfg, req, tokens):
    """How far under the reference's maximum each served token's
    reference logit lies, given the stream's own prefix."""
    toks = np.asarray(tokens)
    lg = ref_logits(params, cfg, np.concatenate(
        [req.prompt, toks[:-1]]))[req.prompt_len - 1:]
    return lg.max(-1) - lg[np.arange(len(toks)), toks]


def chunked(model, params, ids, C=16, max_len=64):
    """`ids` [s] through the chunk program C rows at a time (the last
    chunk padded, its padding masked by `valid`), state row 1 of 2: the
    logits of every launch [C, vocab], and the cache."""
    contract = cache_contract(model)
    K = len(contract.kinds)
    state = tuple(
        jnp.zeros((len(contract.layers_of(K + i)), 2) + tuple(shape),
                  jnp.dtype(dt))
        for i, shapes in enumerate(contract.state_kinds)
        for shape, dt in shapes)
    cache = tuple(gen.init_cache(model, 1, max_len)) + state
    step = jax.jit(lambda p, t, c, s, v: gen.extend_cache(
        model, p, t, c, s, state_row=1, valid=v))
    out = []
    for s in range(0, len(ids), C):
        seg = np.zeros(C, np.int32)
        n = min(C, len(ids) - s)
        seg[:n] = ids[s: s + n]
        lg, cache = step(params, jnp.asarray(seg[None]), cache, jnp.int32(s),
                         jnp.int32(n))
        out.append(np.asarray(lg[0]))
    return out, cache


# ------------------------------------------------------------ the model
@pytest.mark.parametrize("seq", [37, 64])
def test_whole_sequence_forward_is_the_reference(seq, rng):
    cfg, model, params = build()
    ids = rng.integers(0, cfg["vocab_size"], size=seq)
    got = np.asarray(model(params, jnp.asarray(ids[None], jnp.int32)))[0]
    np.testing.assert_allclose(got, ref_logits(params, cfg, ids),
                               atol=LOGIT_ATOL)


def test_the_references_rows_are_its_full_forward(rng):
    """`logits_at` multiplies the head at the rows asked for alone; rows
    out of order and repeated, as the check's padding repeats the last."""
    cfg, _, params = build()
    ids = jnp.asarray(rng.integers(0, cfg["vocab_size"], size=48), jnp.int32)
    rows = jnp.asarray([3, 17, 30, 47, 47])
    full = jax.jit(lambda p, i: fam.hidden_states(p, i, cfg)
                   @ p["model"]["embed"]["weight"].T)(params, ids)
    got = jax.jit(lambda p, i, r: fam.logits_at(p, i, r, cfg))(
        params, ids, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full)[rows],
                               atol=2e-5)


def test_tiny_configuration_keeps_every_mechanism():
    cfg, model, _ = build()
    c = model.config
    mixers = ["ssm"] * 7 + ["full"] + ["ssm"] * 6
    assert list(c.mixers) == mixers == [fam.mixer_of(l, cfg)
                                        for l in range(14)]
    assert c.runs == (("ssm", 0, 7), ("full", 7, 1), ("ssm", 8, 6))
    assert (c.num_key_value_heads, c.num_attention_heads) == (1, 6)
    # three runs, three layer bodies: two scanned, one called
    runs = model.serving_layers(model.abstract_params())
    assert [n for _, _, n in runs] == [7, None, 6]
    assert all(b.attn.inner_norms for b, _, n in runs if n)
    assert fam.counts(cfg)["total_params"] == model.num_params()
    contract = cache_contract(model)
    assert contract.kinds == (None,) and len(contract.state_kinds) == 1
    assert contract.layers_of(0) == (7,)
    assert contract.layers_of(1) == tuple(range(7)) + tuple(range(8, 14))
    assert [contract.place_of(l) for l in (0, 6, 7, 8, 13)] == [0, 6, 0, 7,
                                                               12]
    assert not contract.by_kind and not contract.borrows


def test_published_widths_the_contract_and_the_pool_by_its_bytes():
    """The published model: 28 layers, 26 hold a state and 2 hold pages
    of ONE K/V head at the model's own bytes."""
    cfg = config("jamba2-3b")
    model = fam.build_model(cfg, cfg["serving"])
    c, contract = model.config, cache_contract(model)
    assert model.num_params() == cfg["parameters"] == 3_029_337_472 \
        == fam.counts(cfg)["total_params"]
    assert c.runs == (("ssm", 0, 7), ("full", 7, 1), ("ssm", 8, 13),
                      ("full", 21, 1), ("ssm", 22, 6))
    assert [n for _, _, n in model.serving_layers(
        model.abstract_params())] == [7, None, 13, None, 6]
    assert contract.layers_of(0) == (7, 21) and contract.page_layers == 2
    assert len(contract.layers_of(1)) == 26
    assert contract.token_shapes == ((1, 128), (1, 128)) \
        == contract.stored_shapes
    # a token: K and V of ONE head of 128 in 2 layers, no second head
    assert contract_bytes_per_token(contract, "bf16") == 2 * 2 * 128 * 2 \
        == 1024
    # a sequence: 26 x (16 x 5120 float32 + 3 x 5120 bfloat16)
    assert contract.state_bytes_per_slot(1) == 26 * (327_680 + 30_720) \
        == fam.ssm_state_bytes_per_slot(cfg) == 9_318_400
    sv = cfg["serving"]
    pool = PagePool.for_contract(
        contract, num_pages=sv["num_pages"], page_size=sv["page_size"],
        num_slots=sv["num_slots"], device_arrays=False)
    assert pool.num_layers == 2
    assert pool.num_pages >= 128 * 4608 // sv["page_size"]


def test_the_pool_holds_state_by_slot_beside_one_head_pages():
    _, model, params = build()
    eng, _ = engine(model, params)
    c = model.config
    assert [a.shape for a in eng.pool.tree()] == [(1, 49, 8, 1, 16)] * 2 + [
        (13, 4, c.mamba_d_state, c.d_inner), (13, 4, 3, c.d_inner)]
    assert eng.scheduler.page_table.ndim == 2          # one kind of pages


# ------------------------------------------------------ through the engine
@pytest.mark.parametrize("plens", [
    (5,),            # ends inside the first chunk: 11 padding rows; two
                     # slots idle beside the live one
    (16, 32),        # end at a chunk's edge
    (40, 17, 30),    # three slots at depths of their own, one prompt's
])                   # chunks (that end no prompt) between decode steps
def test_chunked_prefill_then_paged_decode_is_the_references_forward(
        plens, rng):
    cfg, model, params = build()
    eng, reg = engine(model, params)
    reqs = requests(rng, cfg, plens)
    results = {r.rid: r for r in eng.run(reqs)}
    for req in reqs:
        gap = gaps(params, cfg, req, results[req.rid].tokens)
        assert (gap <= LOGIT_ATOL).all(), (req.rid, gap)
    eng.scheduler.check_invariants()
    assert eng.pool.free_count == eng.pool.num_pages
    assert reg.counter_value("serve.state_resets") == len(plens)
    assert reg.counter_value("serve.prefill_tail_rows") == len(plens)
    # 13 layers' state read and written for every row that decodes
    assert reg.counter_value("serve.ssm_state_bytes") == 2 * (
        13 * (8 + 3) * 192 * 4) * reg.counter_value("serve.decode_slot_steps")
    assert not reg.counter_value("serve.kda_state_bytes")
    # one layer's causal pairs, every chunk launch
    assert reg.counter_value("serve.prefill_attended_keys", kind="full") > 0


def test_a_reused_slot_starts_from_zero_state(rng):
    """Two slots, five requests, one prefilling at a time: every slot is
    reused after its state was left by another sequence, and every
    stream is the reference's."""
    cfg, model, params = build()
    eng, reg = engine(model, params, num_slots=2, max_prefilling=1)
    reqs = requests(rng, cfg, (21, 37, 9, 33, 50), new=14)
    for r in reqs:
        r.arrival_t = 0.0
    results = {r.rid: r for r in eng.run(reqs)}
    for req in reqs:
        assert (gaps(params, cfg, req, results[req.rid].tokens)
                <= LOGIT_ATOL).all(), req.rid
    assert reg.counter_value("serve.state_resets") == 5
    assert reg.counter_value("serve.admission_stalls",
                             reason="prefill_scratch") > 0


def test_the_chunk_program_is_the_reference_at_every_row(rng):
    """The chunk program's own logits, a prompt of three chunks and a
    half (6 padding rows, none taken into the state), against the
    reference."""
    cfg, model, params = build()
    ids = rng.integers(0, cfg["vocab_size"], size=58)
    got = np.concatenate(chunked(model, params, ids)[0])[:58]
    np.testing.assert_allclose(got, ref_logits(params, cfg, ids),
                               atol=LOGIT_ATOL)
    # the inner norms left out of the reference: far outside the tolerance
    off = np.abs(got - ref_logits(params, cfg, ids, "no_inner_norms")).max()
    assert off > 100 * LOGIT_ATOL, off


def test_a_state_kept_in_bfloat16_comes_out_not_correct(rng):
    """On one Mamba layer's scan output with the skip term taken out
    (D = 0): the program's chunks (state handed from chunk to chunk, the
    last one padded) stand within SCAN_RTOL of the reference's scan,
    relative to the largest output, and a hundred times that or more
    from the same scan with its state rounded to bfloat16 after every
    position.  (The cell's `correct` does not see the state's precision:
    PERF.md s7.)"""
    cfg, model, params = build()
    block, lp = next(iter(model.model.layers_0.layers(
        params["model"]["layers_0"])))
    ap = dict(lp["attn"], D=jnp.zeros_like(lp["attn"]["D"]))
    hn = jnp.asarray(rng.standard_normal((58, cfg["hidden_size"])), F32)
    state, got = block.attn.zero_state(1, F32), []
    for s in range(0, 58, 16):
        seg = jnp.zeros((1, 16, hn.shape[1]), F32).at[0, : min(16, 58 - s)] \
            .set(hn[s: s + 16])
        _, state, y = block.attn.state_chunk(
            ap, seg, state, jnp.asarray([s]),
            jnp.asarray([min(16, 58 - s)]))
        got.append(np.asarray(y[0]))
    got = np.concatenate(got)[:58]
    want = np.asarray(fam._mamba(hn, ap, cfg)[1])
    ctrl = np.asarray(fam._mamba(hn, ap, cfg, bf16_state=True)[1])
    top = np.abs(want).max()
    assert np.abs(got - want).max() <= SCAN_RTOL * top
    assert np.abs(got - ctrl).max() >= 100 * SCAN_RTOL * top


# ------------------------------------------------------ one K/V head
def _one_head_layer(rng, dtype, g=20, hd=128, ps=8, pages=6):
    from hetu_tpu.models.jamba import JambaAttention, JambaConfig
    c = JambaConfig(hidden_size=g * hd, num_attention_heads=g,
                    num_key_value_heads=1, num_hidden_layers=14,
                    vocab_size=64, intermediate_size=64, param_dtype=dtype,
                    compute_dtype=dtype)
    attn = JambaAttention(c)
    pools = tuple(jnp.asarray(rng.standard_normal(
        (2 * (pages + 1), ps, 1, hd)), dtype) for _ in range(2))
    return attn, attn.init(jax.random.key(3)), pools


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_paged_kernel_takes_one_kv_head(dtype, rng, monkeypatch):
    """The decode step's route on the chip (run here by the interpreter):
    20 query heads over pages of ONE K/V head, which the kernel reads as
    [page_size, 128] tiles, against the composition over gathered pages;
    a slot at a position inside a page (20), on a page's edge (the last
    row of a page: 15; the first of the next: 16) and a slot of one
    token (0); the second layer's pages (`base`)."""
    from hetu_tpu.ops.pallas import paged_attention as pa
    dtype = jnp.dtype(dtype)
    attn, ap, pools = _one_head_layer(rng, dtype)
    table = jnp.asarray([[1, 2, 3], [4, 5, 0], [6, 0, 0], [3, 2, 1]],
                        jnp.int32)
    positions = jnp.asarray([20, 15, 0, 16], jnp.int32)
    q = attn.project(ap, jnp.asarray(rng.standard_normal(
        (4, 1, 20 * 128)), dtype), None, None)[0]
    assert q.shape == (4, 1, 20, 128)
    assert pa.compatible((4, 20, 128), pools[0].shape, table.shape, (4,),
                         pool_dtype=dtype)
    plain = attn._attend_gathered(ap, q, pools, table, positions, 7, None)
    routed = attn._attend_paged_kernel(ap, q, pools, table, positions, 7)
    assert routed.shape == plain.shape == (4, 1, 20 * 128)
    np.testing.assert_allclose(
        np.asarray(routed, np.float32), np.asarray(plain, np.float32),
        atol=2e-5 if dtype == jnp.float32 else 2e-2)
    # and the route itself, asked as on a TPU: the kernel, not the gather
    monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", "paged_attn")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attn._paged_kernel_takes(ap, q, pools, table, None)


def test_the_chunk_kernel_takes_one_kv_head(rng):
    """The chunk program's attention on the chip (interpreted here): a
    chunk of 16 queries, the group of 20 heads as one tall operand over
    ONE K/V head's cache, nothing relaid, against the composition."""
    from hetu_tpu.ops.pallas import chunk_attention as ca
    q = jnp.asarray(rng.standard_normal((1, 16, 20, 128)), F32)
    k, v = (jnp.asarray(rng.standard_normal((1, 64, 1, 128)), F32)
            for _ in range(2))
    for start in (0, 16, 48):
        got = ca.chunk_attention(q, k, v, jnp.int32(start))
        want = gen._attend_cached_chunk(q, k, v, start, 128 ** -0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)


# ------------------------------------------------------ 128 slots
def test_the_engine_at_128_slots(rng):
    """The cell's slots on the tiny model: 160 requests over 128 slots
    (the scheduler admits 128 at once, 8 prefill at a time, 32 wait for
    a slot that another sequence left its state in), in-graph sampling
    on with greedy and sampling rows side by side in the 128-row
    sampler.  Every request ends at its length, every greedy stream is
    the reference's, every admission reset its slot's state."""
    cfg, model, params = build()
    eng, reg = engine(model, params, num_slots=128, max_len=64,
                      num_pages=128 * 8, max_prefilling=8, sampling=True)
    plens = rng.integers(3, 40, size=160)
    reqs = requests(rng, cfg, plens, new=5)
    for r in reqs:
        r.arrival_t = 0.0
        if r.rid % 4 == 3:
            r.sampling = SamplingParams(temperature=0.8, top_k=20,
                                        seed=r.rid)
    results = {r.rid: r for r in eng.run(reqs)}
    assert sorted(results) == list(range(160))
    assert all(len(r.tokens) == 5 and r.finished_reason == "length"
               for r in results.values())
    for req in reqs[:6] + reqs[-6:]:
        if req.rid % 4 != 3:
            assert (gaps(params, cfg, req, results[req.rid].tokens)
                    <= LOGIT_ATOL).all(), req.rid
    assert reg.counter_value("serve.state_resets") == 160
    assert reg.counter_value("serve.admission_stalls",
                             reason="prefill_scratch") > 0
    # the decode batch filled: some pass decoded a hundred rows or more
    assert reg.counter_value("serve.decode_slot_steps") \
        >= 160 * 4
    eng.scheduler.check_invariants()
    assert eng.pool.free_count == eng.pool.num_pages


# ------------------------------------------------------------ refusals
@pytest.mark.parametrize("serve,names", [
    (dict(spec_decode="ngram"), "speculative decoding"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(kv_quant="int8"), "int8 / int4 pages"),
    (dict(kv_repage=True), "kv_repage"),
])
def test_what_a_state_layer_cannot_do_is_refused_by_name(serve, names):
    _, model, params = build()
    with pytest.raises(NotImplementedError, match=names) as e:
        ServingEngine(model, params, ServeConfig(
            num_slots=2, page_size=8, max_len=64, prefill_chunk=16,
            **serve), registry=MetricsRegistry())
    assert "layers 0, 1, 2, 3, 4, 5, 6, 8, 9, 10, 11, 12, 13 keep a state " \
        "a sequence" in str(e.value)


def test_what_the_configuration_does_not_build_is_refused():
    from hetu_tpu.models.jamba import JambaConfig
    with pytest.raises(NotImplementedError, match="experts"):
        JambaConfig(num_experts=16, num_experts_per_tok=2)
    with pytest.raises(NotImplementedError, match="published model"):
        JambaConfig(mamba_proj_bias=True)
    with pytest.raises(ValueError, match="no Mamba layer or no attention"):
        JambaConfig(num_hidden_layers=4)
    _, model, params = build()
    with pytest.raises(NotImplementedError, match="keep a state"):
        gen.generate(model, params, jnp.zeros((1, 4), jnp.int32),
                     max_new_tokens=2)


# ------------------------------------------------------------ the scopes
def test_the_programs_carry_the_mixers_scopes():
    """`ssm_norm` is a group of its own inside `attn` > `ssm` (the
    metrics `jamba.*_ssm_norm_dev_ms` read it), beside the scopes the
    shared mixer has always had; the attention layer runs under
    `attn_full`.  (A Phi-4-flash program, whose mixer has no inner norms,
    compiles to the parent's instructions: PERF.md s4.)"""
    from hetu_tpu.obs.hlo_profile import SCOPE_MAP_GROUPS, scope_map
    assert "ssm_norm" in SCOPE_MAP_GROUPS
    _, model, params = build()
    eng, _ = engine(model, params)
    groups = {name: {g for g, _ in scope_map(low.compile()).values()}
              for name, low in eng.lower_programs().items()}
    for name, step in (("decode", "ssm_step"), ("prefill_chunk",
                                                "ssm_scan")):
        for scope in ("ssm", "ssm_proj", "ssm_conv", "ssm_norm", step,
                      "ssm_out", "attn_full", "kv_write", "mlp"):
            assert f"layer/{scope}" in groups[name], (name, scope)
