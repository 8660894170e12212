"""LFM2-MoE (`model_type: lfm2_moe`, LFM2-8B-A1B) on the serving path, at
a tiny size that keeps every mechanism: 7 layers (c c A c c c A), five
gated short convolutions whose two-position tail the pool holds by slot,
two attention layers whose four K/V heads are stored two a row, both
leading dense layers, eight experts all held.  Seeded random float32
weights; the reference is `benchmarks/families/lfm2_moe.py`'s plain
forward (three shifted products, one softmax over an explicit mask, no
chunks, no cache, no heads in pairs), which shares no code with the
program.

Tolerances.  Program and reference are both float32 here, so they differ
by the ORDER of float32 sums only: logits of O(1) agree to a few 1e-6;
LOGIT_ATOL = 2e-4 leaves two orders of room and is an order or more under
what each of the reference's four controls moves."""
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
from benchmarks.families import lfm2_moe as fam  # noqa: E402
from hetu_tpu.models import generation as gen  # noqa: E402
from hetu_tpu.models.cache_contract import cache_contract  # noqa: E402
from hetu_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from hetu_tpu.serving.engine import ServeConfig, ServingEngine  # noqa: E402
from hetu_tpu.serving.kv_pool import (PagePool,  # noqa: E402
                                      contract_bytes_per_token)
from hetu_tpu.serving.request import Request  # noqa: E402

LOGIT_ATOL = 2e-4
F32 = jnp.float32


def config(name="tiny-lfm2"):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


def build(head_dim=None, **over):
    """The tiny configuration without `router_tie_logit` (the tests that
    compare logits read the plain forward); `head_dim` 64 widens it to
    the published head (8 heads of 64: hidden 512)."""
    cfg = dict(config(), **over)
    cfg.pop("router_tie_logit")
    if head_dim:
        cfg.update(head_dim=head_dim,
                   hidden_size=head_dim * cfg["num_attention_heads"])
    model = fam.build_model(cfg, cfg["serving"])
    return cfg, model, model.init(jax.random.key(7))


_REF = {}


def ref_logits(params, cfg, ids, control=None, plen=None):
    """The reference's logits at every position of `ids`, padded to a
    multiple of 64 positions (the model is causal) so that one compiled
    forward serves every stream of a length class.  `plen`: where the
    prompt ends, for the control that needs it (`logits_at` reads it
    from its first row)."""
    n = len(ids)
    pad = -(-n // 64) * 64
    key = (control, pad, cfg["hidden_size"], plen is not None)
    if key not in _REF:
        def run(p, i, first):
            rows = jnp.arange(pad) if plen is None else jnp.concatenate(
                [first[None], jnp.arange(1, pad)])
            return fam.logits_at(p, i, rows, cfg, control)
        _REF[key] = jax.jit(run)
    padded = np.zeros(pad, np.int32)
    padded[:n] = ids
    return np.asarray(_REF[key](params, jnp.asarray(padded),
                                jnp.int32((plen or 1) - 1)))[:n]


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


def test_the_reference_gathers_an_experts_rows_or_takes_them_all(rng):
    """`experts` computes an expert for the rows that chose it (`cap`
    rows at most) and falls back to every row where some expert has more:
    both are the same sum."""
    cfg, _, params = build()
    mp = params["model"]["layer_3"]["mlp"]
    x = jnp.asarray(rng.standard_normal((128, cfg["hidden_size"])), F32)
    with jax.default_matmul_precision("highest"):
        spread = fam.experts(x, mp, cfg)
        # every row the same token: one expert set has all 128 rows,
        # over the cap of 64
        same = fam.experts(jnp.broadcast_to(x[:1], x.shape), mp, cfg)
        idx, w = fam.gate(x, mp, cfg)
        want = sum(
            jnp.sum(jnp.where(idx == e, w, 0.0), -1)[:, None]
            * fam._swiglu(x, mp["w_gate_up"][e], mp["w_down"][e])
            for e in range(cfg["num_experts"]))
    np.testing.assert_allclose(np.asarray(spread), np.asarray(want),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(same),
                               np.broadcast_to(np.asarray(same[:1]),
                                               same.shape), atol=1e-6)
    np.testing.assert_allclose(np.asarray(same[0]), np.asarray(want[0]),
                               atol=1e-6)


def test_tiny_configuration_keeps_every_mechanism():
    cfg, model, _ = build()
    c = model.config
    assert list(c.layer_types) == ["conv", "conv", "full_attention", "conv",
                                   "conv", "conv", "full_attention"]
    assert [b.moe for b in model.model.blocks] == [False] * 2 + [True] * 5
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            c.kv_fold, c.kv_row) == (8, 4, 8, 2, (2, 16))
    runs = model.serving_layers(model.abstract_params())
    assert [n for _, _, n in runs] == [None] * 7
    assert fam.counts(cfg)["total_params"] == model.num_params()
    contract = cache_contract(model)
    assert contract.kinds == (None,) and len(contract.state_kinds) == 1
    assert contract.layers_of(0) == (2, 6)
    assert contract.layers_of(1) == (0, 1, 3, 4, 5)
    assert contract.state_kinds[0] == (((2 * 64,), "float32"),)
    assert not contract.by_kind and not contract.borrows
    assert fam.pass_codes(config()).shape == (5 * 3 + 1, 5)


def test_published_widths_the_contract_and_the_pool_by_its_bytes():
    """The cell's cut: layers 0-11 as published, 9 hold a tail and 3 hold
    pages of four lane rows a token in K and in V: the model's own 2,048
    B a token a layer, no padded lane."""
    cfg = config("lfm2-8b-a1b-depth12")
    model = fam.build_model(cfg, cfg["serving"])
    c, contract = model.config, cache_contract(model)
    assert model.num_params() == cfg["parameters"] == 3_928_728_256 \
        == fam.counts(cfg)["total_params"]
    assert [b.mixer for b in model.model.blocks] == cfg["layer_types"] \
        == list(c.layer_types)
    assert (c.head_dim, c.kv_fold) == (64, 2)
    assert contract.layers_of(0) == (2, 6, 10) and contract.page_layers == 3
    assert contract.token_shapes == ((4, 128), (4, 128)) \
        == contract.stored_shapes
    assert contract_bytes_per_token(contract, "bf16") == 3 * 2048
    # a sequence: 9 x 2 positions x 2,048 bfloat16
    assert contract.state_bytes_per_slot(1) == 9 * 8192 \
        == fam.conv_state_bytes_per_slot(cfg) == 73_728
    # every published key at its published value but the two reduced
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    assert cfg["source"] == row["source_url"]
    assert sorted(cfg["reduced"]) == ["layer_types", "num_hidden_layers"]
    for key, value in row["config"].items():
        if key == "layer_types":
            assert cfg[key] == value[:12]
        elif key != "num_hidden_layers":
            assert cfg[key] == value, key
    sv = cfg["serving"]
    pool = PagePool.for_contract(
        contract, num_pages=sv["num_pages"], page_size=sv["page_size"],
        num_slots=sv["num_slots"], device_arrays=False)
    assert pool.num_layers == 3
    assert pool.num_pages >= 128 * 4608 // sv["page_size"]


def test_the_pool_holds_the_tails_by_slot_beside_paired_head_pages():
    _, model, params = build()
    eng, _ = engine(model, params)
    assert [a.shape for a in eng.pool.tree()] == [(2, 49, 8, 2, 16)] * 2 + [
        (5, 4, 128)]
    assert eng.scheduler.page_table.ndim == 2          # one kind of pages


# ------------------------------------------------------ through the engine
@pytest.mark.parametrize("plens,chunk", [
    ((1,), 16),            # a prompt shorter than the tail: one position
    ((5,), 16),            # ends inside the first chunk: 11 padding rows
    ((16, 32), 16),        # end at a chunk's edge
    ((40, 17, 30), 16),    # three slots at depths of their own
    ((40, 17, 30), 8),     # the same prompts in chunks of 8 ...
    ((40, 17, 30), 32),    # ... and of 32 (17 and 30 in one launch)
])
def test_chunked_prefill_then_paged_decode_is_the_references_forward(
        plens, chunk, rng):
    cfg, model, params = build()
    eng, reg = engine(model, params, prefill_chunk=chunk)
    reqs = requests(rng, cfg, plens)
    results = {r.rid: r for r in eng.run(reqs)}
    for req in reqs:
        gap = gaps(params, cfg, req, results[req.rid].tokens)
        assert (gap <= LOGIT_ATOL).all(), (req.rid, gap)
    eng.scheduler.check_invariants()
    assert eng.pool.free_count == eng.pool.num_pages
    assert reg.counter_value("serve.state_resets") == len(plens)
    # 5 layers' tails (2 positions x 64 float32) read and written for
    # every row that decodes, under the family's own counter
    assert reg.counter_value("serve.conv_state_bytes") == 2 * (
        5 * 2 * 64 * 4) * reg.counter_value("serve.decode_slot_steps")
    assert not reg.counter_value("serve.ssm_state_bytes")
    assert reg.counter_value("serve.moe_layer_steps") > 0
    assert reg.counter_value("serve.moe_assignments") \
        == reg.counter_value("serve.moe_local_assignments")


def test_a_slot_reused_after_a_longer_request_starts_from_a_zero_tail(rng):
    """Two slots, five requests, one prefilling at a time: every slot is
    reused after its tail was left by another (longer) sequence, and
    every stream is the reference's, the one-token prompt's too."""
    cfg, model, params = build()
    eng, reg = engine(model, params, num_slots=2, max_prefilling=1)
    reqs = requests(rng, cfg, (50, 37, 1, 9, 2), new=14)
    for r in reqs:
        r.arrival_t = 0.0
    results = {r.rid: r for r in eng.run(reqs)}
    for req in reqs:
        assert (gaps(params, cfg, req, results[req.rid].tokens)
                <= LOGIT_ATOL).all(), req.rid
    assert reg.counter_value("serve.state_resets") == 5


def test_preemption_and_readmission_leave_the_right_tail(rng):
    """A higher class evicts the live request mid-decode; re-admitted, it
    is prefilled from position 0 (its slot's tail reset by the first
    chunk, after another sequence left its own there) and its stream is
    the undisturbed one."""
    from hetu_tpu.serving.request import SLOClass
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
        assert (gaps(params, cfg, req, results[req.rid].tokens)
                <= LOGIT_ATOL).all(), req.rid


def test_the_chunk_program_is_the_reference_at_every_row(rng):
    """The chunk program's own logits, a prompt of three chunks and a
    half (6 padding rows, none taken into the tail), against the
    reference."""
    cfg, model, params = build()
    ids = rng.integers(0, cfg["vocab_size"], size=58)
    got = np.concatenate(chunked(model, params, ids)[0])[:58]
    np.testing.assert_allclose(got, ref_logits(params, cfg, ids),
                               atol=LOGIT_ATOL)


@pytest.mark.parametrize("control", fam.CONTROLS)
def test_a_control_comes_out_not_correct(control, rng):
    """Each of the reference's five faults (the tail in the precision
    below, a dropped tap, a tail from a padding row, the bias in the
    weights, a wrong fourth expert) moves the logits the served stream
    is held to by ten times the tolerance or more, at the positions it
    can reach, and fails `reference.check_stream`, the comparison that
    decides `correct`, on a stream the plain reference passes.  (The
    bias at five times the configuration's std for its control: at toy
    widths the served tokens win by more than the configuration's bias
    moves a logit.)"""
    cfg, model, params = build(**(dict(correction_bias_std=0.5)
                                  if "bias" in control else {}))
    eng, _ = engine(model, params)
    (req,) = requests(rng, cfg, (21,), new=24)
    (res,) = eng.run([req])
    ids = np.concatenate([req.prompt, np.asarray(res.tokens)[:-1]])
    plain = ref_logits(params, cfg, ids)
    wrong = ref_logits(params, cfg, ids, control, plen=21)
    moved = np.abs(wrong - plain).max(-1)
    if control == "pad_tail":
        # the two positions after the prompt, and what attends them
        # (row 0 is the prompt's last position here: `ref_logits`)
        assert moved[1:21].max() == 0.0 and moved[21:23].min() \
            > 10 * LOGIT_ATOL
    else:
        assert moved[20:].min() > 10 * LOGIT_ATOL

    def check(ctrl):
        return reference.check_stream(
            lambda p, i, r, c: fam.logits_at(p, i, r, c, ctrl), params, cfg,
            req.prompt, res.tokens, 64)
    assert check(None)["ok"]
    assert check(None)["max_gap"] <= LOGIT_ATOL
    assert check(control)["max_gap"] > 10 * LOGIT_ATOL


def test_the_near_tie_passes_forgive_an_exchange_and_nothing_else(rng):
    """Under `router_tie_logit` a row keeps the plain forward's argmax
    and every value stands at or above the plain pass's; a margin of 0
    is the plain forward."""
    cfg, _, params = build()
    ids = jnp.asarray(rng.integers(0, cfg["vocab_size"], size=64), jnp.int32)
    rows = jnp.arange(40, 64)
    plain = np.asarray(jax.jit(lambda p, i: fam.logits_at(
        p, i, rows, cfg))(params, ids))
    tied = np.asarray(jax.jit(lambda p, i: fam.logits_at(
        p, i, rows, dict(cfg, router_tie_logit=0.5)))(params, ids))
    assert (tied >= plain).all() and (tied > plain).any()
    assert (tied.argmax(-1) == plain.argmax(-1)).all()
    np.testing.assert_allclose(tied.max(-1), plain.max(-1))
    none = np.asarray(jax.jit(lambda p, i: fam.logits_at(
        p, i, rows, dict(cfg, router_tie_logit=1e-9)))(params, ids))
    np.testing.assert_array_equal(none, plain)


def test_no_standing_cells_pool_writes_a_page_at_a_time():
    """`PagePool.short_rows` (the one branch this family added to the
    shared pool) is true of this cell's pool and of no other serving
    cell's in BENCHMARK.json: their page-write programs are the parent's."""
    import importlib
    from benchmarks import traffic
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    serving = {w["config"] for w in bench["workloads"]
               if "serve" in w["name"]}
    assert len(serving) >= 11
    took = []
    for name in sorted(serving):
        cfg = traffic.load_json("configs", name)
        family = importlib.import_module(
            "benchmarks.families." + cfg.get("family", "llama"))
        sv = cfg["serving"]
        model = family.build_model(cfg, sv)
        pool = PagePool.for_contract(
            cache_contract(model), num_pages=sv["num_pages"],
            page_size=sv["page_size"], num_slots=sv["num_slots"],
            quant=sv.get("kv_quant", "none"),
            device_arrays=False)
        if pool.short_rows:
            took.append(name)
    assert took == ["lfm2-8b-a1b-depth12"]


# ------------------------------------------------------ heads in pairs
def _plain_attention(attn, ap, hn, cfg):
    """64-wide attention computed plainly from the layer's own weights:
    the reference's projection and softmax over an explicit mask."""
    pos = jnp.arange(hn.shape[0])
    with jax.default_matmul_precision("highest"):
        q, k, v = fam.attn_project(hn, pos, ap, cfg)
        return fam.attend(q, pos, k, v, ap)


def test_heads_laid_in_pairs_are_64_wide_attention_computed_plainly(rng):
    """At the published head: 8 query heads of 64 over 4 K/V heads, a
    token's K stored as two lane rows of 128.  `project` lays each query
    in its K/V head's half beside zeros; the whole-prompt hook, the
    chunk's dense-cache hook and `output` over them equal attention of 64
    computed from the same weights without any pairing."""
    cfg, model, params = build(head_dim=64)
    block = model.model.blocks[2]
    ap = params["model"]["layer_2"]["attn"]
    hn = jnp.asarray(rng.standard_normal((1, 48, cfg["hidden_size"])), F32)
    pos = jnp.arange(48, dtype=jnp.int32)[None]
    q, (k, v) = block.attn.project(ap, hn, model.rope_tables(48), pos)
    assert q.shape == (1, 48, 8, 128) and k.shape == v.shape == (1, 48, 2,
                                                                  128)
    # a query's other half is zeros; head j lies in half (j // 2) % 2
    halves = np.asarray(q).reshape(48, 8, 2, 64)
    for j in range(8):
        assert np.abs(halves[:, j, 1 - (j // 2) % 2]).max() == 0.0
        assert np.abs(halves[:, j, (j // 2) % 2]).min() > 0.0
    want = np.asarray(_plain_attention(block.attn, ap, hn[0], cfg))
    whole = block.attn.output(ap, block.attn.attend_prompt(ap, q, (k, v)))
    np.testing.assert_allclose(np.asarray(whole[0]), want, atol=2e-5)
    # the chunk program's hook: the last 16 queries over a dense cache
    caches = tuple(jnp.pad(a, ((0, 0), (0, 16), (0, 0), (0, 0)))
                   for a in (k, v))
    part = block.attn.output(ap, block.attn.attend_dense(
        ap, q[:, 32:], caches, jnp.int32(32)))
    np.testing.assert_allclose(np.asarray(part[0]), want[32:], atol=2e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_paged_kernel_takes_heads_laid_in_pairs(dtype, rng, monkeypatch):
    """The decode step's route on the chip (run here by the interpreter):
    8 query heads of 128 lanes (a head's 64 beside zeros) over pages of
    two lane rows a token, against the composition over gathered pages,
    at the family's scale 64^-1/2."""
    from hetu_tpu.ops.pallas import paged_attention as pa
    dtype = jnp.dtype(dtype)
    cfg, model, params = build(
        head_dim=64, serving=dict(config()["serving"], param_dtype=dtype.name))
    attn = model.model.blocks[2].attn
    ap = params["model"]["layer_2"]["attn"]
    assert attn.softmax_scale(128) == 64 ** -0.5
    pools = tuple(jnp.asarray(rng.standard_normal((2 * 7, 8, 2, 128)), dtype)
                  for _ in range(2))
    table = jnp.asarray([[1, 2, 3], [4, 5, 0], [6, 0, 0], [3, 2, 1]],
                        jnp.int32)
    positions = jnp.asarray([20, 15, 0, 16], jnp.int32)
    hn = jnp.asarray(rng.standard_normal((4, 1, cfg["hidden_size"])), dtype)
    q = attn.project(ap, hn, model.rope_tables(32), positions[:, None])[0]
    assert q.shape == (4, 1, 8, 128)
    assert pa.compatible((4, 8, 128), pools[0].shape, table.shape, (4,),
                         pool_dtype=dtype)
    plain = attn._attend_gathered(ap, q, pools, table, positions, 7, None)
    routed = attn._attend_paged_kernel(ap, q, pools, table, positions, 7)
    assert routed.shape == plain.shape == (4, 1, 8 * 128)
    np.testing.assert_allclose(
        np.asarray(routed, np.float32), np.asarray(plain, np.float32),
        atol=2e-5 if dtype == jnp.float32 else 2e-2)
    monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", "paged_attn")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attn._paged_kernel_takes(ap, q, pools, table, None)


def test_pages_of_two_rows_are_written_a_page_at_a_time(rng):
    """A one-kind pool of 2 to 7 rows a token writes a prompt's pages one
    after the other in place (the whole-pool scatter copies the pool on
    the chip: serving/kv_pool.py): the same pages as the scatter's."""
    L, ps, rows = 2, 8, 2
    pool = PagePool(num_layers=L, num_pages=6, page_size=ps,
                    num_kv_heads=rows, head_dim=16, dtype=F32,
                    device_arrays=False)
    tree = tuple(jnp.asarray(rng.standard_normal((L, 7, ps, rows, 16)), F32)
                 for _ in range(2))
    ks, vs = (jnp.asarray(rng.standard_normal((L, 3 * ps, rows, 16)), F32)
              for _ in range(2))
    pages = jnp.asarray([4, 2, 0], jnp.int32)
    got = jax.jit(pool.write_pages)(tree, pages, ks, vs)
    for new, old, x in zip(got, tree, (ks, vs)):
        want = np.asarray(old).copy()
        want[:, [4, 2, 0]] = np.asarray(x).reshape(L, 3, ps, rows, 16)
        np.testing.assert_array_equal(np.asarray(new), want)
    text = jax.jit(pool.write_pages).lower(tree, pages, ks, vs).as_text()
    assert "scatter" not in text and "dynamic_update_slice" in text


# ------------------------------------------------------------ the gate
def test_the_gates_epsilon_is_an_argument_with_the_standing_default(rng):
    """`norm_eps` 1e-6 is the published lfm2_moe rule; without it every
    standing family's gate is, bit for bit, what it was (1e-20)."""
    from hetu_tpu.nn.moe import SharedRoutedExperts, noaux_tc_gate
    x = jnp.asarray(rng.standard_normal((32, 64)), F32)
    w = jnp.asarray(rng.standard_normal((64, 8)) * 0.3, F32)
    b = jnp.asarray(rng.standard_normal(8) * 0.1, F32)
    s = jax.nn.sigmoid(jnp.einsum("th,he->te", x, w,
                                  precision=jax.lax.Precision.HIGHEST))
    _, top = jax.lax.top_k(s + b, 2)
    chosen = jnp.take_along_axis(s, top, -1)
    kw = dict(top_k=2, norm_topk_prob=True, routed_scaling_factor=1.0)
    idx, got = noaux_tc_gate(x, w, b, norm_eps=1e-6, **kw)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(top))
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(chosen / (chosen.sum(-1, keepdims=True) + 1e-6)))
    _, standing = noaux_tc_gate(x, w, b, **kw)
    np.testing.assert_array_equal(
        np.asarray(standing),
        np.asarray(chosen / (chosen.sum(-1, keepdims=True) + 1e-20)))
    assert (np.asarray(standing) != np.asarray(got)).any()
    # a layer told nothing hands the gate no epsilon: the program of
    # every standing family lowers to the text it lowered to
    make = lambda **kw: SharedRoutedExperts(  # noqa: E731
        64, 32, n_routed_experts=8, experts_held=8, first_expert=0, top_k=2,
        n_shared_experts=0, norm_topk_prob=True, routed_scaling_factor=1.0,
        **kw)
    assert make().groups == {} and make(norm_eps=1e-6).groups == {
        "norm_eps": 1e-6}
    cfg, model, params = build()
    layer = model.model.blocks[3].mlp
    assert layer.groups == {"norm_eps": 1e-6}
    u = jnp.asarray(rng.standard_normal((16, cfg["hidden_size"])), F32)
    mp = params["model"]["layer_3"]["mlp"]
    idx, wts = layer.route(mp, u)
    with jax.default_matmul_precision("highest"):
        ridx, rw = fam.gate(u, mp, cfg)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(wts), np.asarray(rw), atol=1e-6)
    with pytest.raises(ValueError, match="an epsilon of its own"):
        make(scoring="softmax", norm_eps=1e-6)


def test_the_bias_is_seeded_around_an_offset_only_a_fault_can_see(rng):
    """`expert_bias` is seeded around `correction_bias_mean` (-0.8 in the
    cell's file and the toy's): the choice is of s + b, so an offset
    common to every expert leaves the experts and their weights what
    they are at offset 0, and a bias added into the WEIGHTS is moved by
    it (the control `bias_in_weights`)."""
    from hetu_tpu.nn.moe import SharedRoutedExperts
    cfg, model, params = build()
    assert cfg["correction_bias_mean"] == -0.8
    mp = dict(params["model"]["layer_3"]["mlp"])
    b = np.asarray(mp["e_score_correction_bias"])
    assert b.mean() == pytest.approx(-0.8, abs=3 * 0.1 / 8 ** 0.5)
    u = jnp.asarray(rng.standard_normal((64, cfg["hidden_size"])), F32)
    layer = model.model.blocks[3].mlp
    idx, w = layer.route(mp, u)
    idx0, w0 = layer.route(
        dict(mp, e_score_correction_bias=mp["e_score_correction_bias"]
             + 0.8), u)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx0))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w0))
    with jax.default_matmul_precision("highest"):
        _, sound = fam.gate(u, mp, cfg)
        _, wrong = fam.gate(u, mp, cfg, control="bias_in_weights")
    assert np.abs(np.asarray(wrong) - np.asarray(sound)).max() > 0.5
    # a layer told no offset seeds what it always seeded
    spec = SharedRoutedExperts(
        64, 32, n_routed_experts=8, experts_held=8, first_expert=0, top_k=2,
        n_shared_experts=0, norm_topk_prob=True, routed_scaling_factor=1.0,
        bias_range=0.1).param_specs()["e_score_correction_bias"]
    seeded = np.asarray(spec.init(jax.random.key(3), spec.shape, spec.dtype))
    np.testing.assert_array_equal(
        seeded, 0.1 * np.asarray(jax.random.normal(jax.random.key(3), (8,))))


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
    assert "layers 0, 1, 3, 4, 5 keep a state a sequence" in str(e.value)


def test_what_the_configuration_does_not_build_is_refused():
    from hetu_tpu.models.lfm2_moe import Lfm2MoeConfig
    with pytest.raises(NotImplementedError, match="published model"):
        Lfm2MoeConfig(conv_bias=True)
    with pytest.raises(NotImplementedError, match="published model"):
        Lfm2MoeConfig(use_expert_bias=False)
    with pytest.raises(ValueError, match="has to hold pages"):
        Lfm2MoeConfig(num_hidden_layers=2, layer_types=("conv", "conv"))
    with pytest.raises(ValueError, match="published order is of 24"):
        Lfm2MoeConfig(num_hidden_layers=12)
    with pytest.raises(ValueError, match="11 layer_types for 12"):
        Lfm2MoeConfig(num_hidden_layers=12, layer_types=("conv",) * 11)
    with pytest.raises(ValueError, match="a stored row"):
        Lfm2MoeConfig(num_key_value_heads=1, num_attention_heads=32)
    assert Lfm2MoeConfig().layer_types.count("full_attention") == 6
    _, model, params = build()
    with pytest.raises(NotImplementedError, match="keep a state"):
        gen.generate(model, params, jnp.zeros((1, 4), jnp.int32),
                     max_new_tokens=2)


# ------------------------------------------------------------ the scopes
def test_the_programs_carry_the_operators_scopes():
    """`short_conv` and its two parts are groups of their own inside
    `attn` (the metrics `lfm2.*_conv_dev_ms` and `lfm2.short_conv_roofline`
    read them); the attention layers run under `attn_full`, the experts
    under `router` and `experts`."""
    from hetu_tpu.obs.hlo_profile import SCOPE_MAP_GROUPS, scope_map
    assert {"short_conv", "short_conv_proj", "short_conv_mix"} \
        <= set(SCOPE_MAP_GROUPS)
    _, model, params = build()
    eng, _ = engine(model, params)
    groups = {name: {g for g, _ in scope_map(low.compile()).values()}
              for name, low in eng.lower_programs().items()}
    for name in ("decode", "prefill_chunk"):
        for scope in ("short_conv", "short_conv_proj", "short_conv_mix",
                      "attn_full", "kv_write", "mlp", "router", "experts"):
            assert f"layer/{scope}" in groups[name], (name, scope)
