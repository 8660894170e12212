"""The chunk program's head runs for the ONE row a finished prompt
samples from, and for no row of a chunk that does not end its prompt
(`extend_cache(read_row=)`, `ServingEngine`'s `chunk_fn`), in every
family the engine serves: the six tiny configurations of the rehearsals,
seeded random float32 weights.

Tolerances.  One row's product against the same row of a chunk's differs
by the order of float32 sums only: logits of O(1) agree to a few 1e-6,
ROW_ATOL = 2e-5.  Against the families' plain references (no chunks, no
cache, no code shared with the program) LOGIT_ATOL = 2e-4, as in each
family's own tests."""
import dataclasses
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from conftest import generate  # noqa: E402
from hetu_tpu.models import generation as gen  # noqa: E402
from hetu_tpu.models.cache_contract import cache_contract  # noqa: E402
from hetu_tpu.obs import hlo_profile as hp  # noqa: E402
from hetu_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from hetu_tpu.serving.engine import ServingEngine  # noqa: E402
from hetu_tpu.serving.request import Request, SamplingParams  # noqa: E402
from hetu_tpu.serving.sampling import sample_tokens  # noqa: E402
from test_serving import launches  # noqa: E402

ROW_ATOL = 2e-5
LOGIT_ATOL = 2e-4
CHUNK = 16

#: family module -> its tiny configuration
FAMILIES = {"llama": "tiny", "kimi_k2": "tiny-kimi-k2",
            "afmoe": "tiny-trinity", "mimo_v2": "tiny-mimo",
            "bailing_hybrid": "tiny-ling", "phi4flash": "tiny-phi4flash"}


def build(family, config=None):
    fam = importlib.import_module(f"benchmarks.families.{family}")
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           (config or FAMILIES[family]) + ".json")) as f:
        cfg = json.load(f)
    cfg.pop("router_tie_logit", None)   # the reference's plain forward
    if family == "mimo_v2":
        for a, b in (("head_dim", "swa_head_dim"),
                     ("v_head_dim", "swa_v_head_dim"),
                     ("sliding_window", "sliding_window_size")):
            cfg[b] = cfg[a]
    assert cfg["serving"]["prefill_chunk"] == CHUNK
    model = fam.build_model(cfg, cfg["serving"])
    return fam, cfg, model, model.init(jax.random.key(7))


def ref_logits(fam, params, cfg, ids):
    ids = jnp.asarray(ids, jnp.int32)
    return np.asarray(jax.jit(lambda p, i: fam.logits_at(
        p, i, jnp.arange(i.shape[0]), cfg))(params, ids))


def engine(fam, cfg, model, params, **serve):
    reg = MetricsRegistry()
    conf = dataclasses.replace(fam.serve_config(cfg), **serve)
    return ServingEngine(model, params, conf, registry=reg), reg


def fresh_cache(model, rows=2, max_len=64):
    """A dense cache of one row and, behind it, the state arrays of
    `rows` rows of a model with state layers, as the engine hands them
    to the chunk program."""
    contract = cache_contract(model)
    K = len(contract.kinds)
    state = tuple(
        jnp.zeros((len(contract.layers_of(K + i)), rows) + tuple(shape),
                  jnp.dtype(dt))
        for i, shapes in enumerate(contract.state_kinds)
        for shape, dt in shapes)
    return tuple(gen.init_cache(model, 1, max_len)) + state


# ------------------------------------------------------------------ (a)
@pytest.mark.parametrize("family", FAMILIES)
def test_read_row_is_that_row_of_the_whole_call(family, rng):
    """`extend_cache(.., read_row=r)` returns [b, 1, vocab]: row r of
    what the same call returns without `read_row`, zeros for r = -1; the
    cache and the stats vector are those of the whole call either way."""
    _, cfg, model, params = build(family)
    stats = model.zero_stats() if model.STATS else None

    def step(read_row):
        kw = {} if read_row is None else {"read_row": read_row}
        return jax.jit(lambda p, t, c, s, v, *st: gen.extend_cache(
            model, p, t, c, s, *st, state_row=1, valid=v, **kw))

    whole = step(None)
    cache = fresh_cache(model)
    ids = rng.integers(0, cfg["vocab_size"], size=(1, 2 * CHUNK)) \
        .astype(np.int32)
    # (the second chunk holds 9 rows of its prompt: the row read is the
    # prompt's last, the rows behind it are padding)
    for s, valid, r in ((0, CHUNK, -1), (CHUNK, 9, 8)):
        args = (params, jnp.asarray(ids[:, s: s + CHUNK]), cache,
                jnp.int32(s), jnp.int32(valid),
                *(() if stats is None else (stats,)))
        full, one = whole(*args), step(jnp.int32(r))(*args)
        assert full[0].shape == (1, CHUNK, cfg["vocab_size"])
        assert one[0].shape == (1, 1, cfg["vocab_size"])
        if r < 0:
            assert not np.asarray(one[0]).any()
        else:
            np.testing.assert_allclose(np.asarray(one[0][:, 0]),
                                       np.asarray(full[0][:, r]),
                                       atol=ROW_ATOL, rtol=0)
        # (Phi-4-flash writes layer `read_rows_from`'s entries without
        # its attention: another order of the same float32 sums)
        for a, b in zip(one[1], full[1]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=0,
                atol=ROW_ATOL if family == "phi4flash" else 0)
        if stats is not None:
            np.testing.assert_array_equal(np.asarray(one[2]),
                                          np.asarray(full[2]))
            stats = full[2]
        cache = full[1]


def test_a_call_without_read_row_keeps_every_row(rng):
    """`verify_step_slots` and serving/disagg.py call without
    `read_row`: [b, C, vocab], no conditional in the program."""
    _, cfg, model, params = build("llama")
    lowered = jax.jit(lambda p, t, c: gen.extend_cache(
        model, p, t, c, jnp.int32(0))).lower(
            params, jnp.zeros((1, CHUNK), jnp.int32), fresh_cache(model))
    assert lowered.out_info[0].shape == (1, CHUNK, cfg["vocab_size"])
    text = lowered.as_text()
    assert "stablehlo.case" not in text and "stablehlo.if" not in text


# ------------------------------------------------------------------ (b)
def _requests(rng, cfg, plens, sampled):
    return [Request(
        rid=i, prompt=rng.integers(0, cfg["vocab_size"], size=n)
        .astype(np.int32), max_new_tokens=5, arrival_t=0.01 * i,
        sampling=SamplingParams(temperature=0.9, top_k=20, seed=100 + i)
        if sampled else SamplingParams()) for i, n in enumerate(plens)]


def _holds_the_launch_counters(eng, reg, chunks, tokens):
    """`serve.prefill_chunks` counts LAUNCHES, each of a shape the engine
    warms up; together they carried `chunks` chunks of rows, `tokens` of
    them the prompts'.  A window scratch that slides keeps one chunk a
    launch; elsewhere slots that prefill in one step share its rows
    oldest first, so some launch carried more."""
    by_rows = launches(reg)
    assert sum(by_rows.values()) == reg.counter_value("serve.prefill_chunks")
    assert sum(r * n for r, n in by_rows.items()) == chunks * CHUNK
    assert reg.counter_value("serve.prefill_tokens") == tokens
    shapes = eng.kernel_routes["prefill_launch_rows"]
    assert set(by_rows) <= set(shapes["rows"])
    if eng._slide:
        assert shapes["rows"] == [CHUNK] and "slides" in shapes["why"]
    else:
        assert shapes["rows"] == [k * CHUNK for k in (1, 2, 3, 4)]
        assert max(by_rows) > CHUNK


def _holds_against_reference(fam, cfg, params, req, tokens):
    """Every served token against the plain reference's logits of the
    stream's own prefix: within LOGIT_ATOL of the largest (greedy), or
    what the seeded sampler draws from that row at that position."""
    toks = np.asarray(tokens)
    lg = ref_logits(fam, params, cfg, np.concatenate(
        [req.prompt, toks[:-1]]))[req.prompt_len - 1:]
    if req.sampling.temperature == 0:
        gap = lg.max(-1) - lg[np.arange(len(toks)), toks]
        assert (gap <= LOGIT_ATOL).all(), (req.rid, gap)
        return
    sp, n = req.sampling, len(toks)
    drawn = sample_tokens(
        jnp.asarray(lg), jnp.full(n, sp.seed & 0xFFFFFFFF, jnp.uint32),
        req.prompt_len + jnp.arange(n, dtype=jnp.int32),
        jnp.full(n, sp.temperature, jnp.float32),
        jnp.full(n, sp.top_k, jnp.int32), jnp.full(n, sp.top_p, jnp.float32))
    assert list(np.asarray(drawn)) == list(toks), req.rid


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("family", FAMILIES)
def test_served_tokens_are_the_references(family, sampled, rng):
    """An engine run, greedy and with the seeded sampler (whose first
    token the host draws from the chunk program's `logits[0, 0]`):
    prompts shorter than a chunk, ending inside their third chunk and
    ending at a chunk's edge.  The head ran once a prompt."""
    fam, cfg, model, params = build(family)
    eng, reg = engine(fam, cfg, model, params, sampling=True)
    plens = (7, 2 * CHUNK, 2 * CHUNK + 5)
    reqs = _requests(rng, cfg, plens, sampled)
    results = {r.rid: r for r in eng.run(reqs)}
    for req in reqs:
        _holds_against_reference(fam, cfg, params, req,
                                 results[req.rid].tokens)
    assert reg.counter_value("serve.prefill_tail_rows") == len(plens)
    _holds_the_launch_counters(eng, reg, 1 + 2 + 3, sum(plens))


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_llama_tokens_are_generates_and_a_prefix_hit_leaves_a_row(
        sampled, rng):
    """The llama engine against `generate` (greedy), and with the prefix
    cache on: a prompt that is whole cached pages of an earlier one
    still prefills its last page (`match` stops at plen - 1), so its
    last chunk has a row to read and the tokens are those of an engine
    that caches nothing."""
    fam, cfg, model, params = build("llama")
    reqs = _requests(rng, cfg, (2 * CHUNK, 2 * CHUNK + 5, 7), sampled)
    # the whole of request 0's prompt, and its first three pages
    for rid, n in ((3, 2 * CHUNK), (4, 24)):
        reqs.append(dataclasses.replace(
            reqs[0], rid=rid, prompt=reqs[0].prompt[:n], arrival_t=1.0 + rid,
            sampling=dataclasses.replace(reqs[0].sampling, seed=100 + rid)))
    plain, _ = engine(fam, cfg, model, params, sampling=True)
    cached, reg = engine(fam, cfg, model, params, sampling=True,
                         prefix_cache=True)
    want = {r.rid: r.tokens for r in plain.run(
        [dataclasses.replace(r) for r in reqs])}
    got = {r.rid: r.tokens for r in cached.run(reqs)}
    assert got == want
    assert reg.counter_value("serve.prefix_hits") == 2
    # the hits prefilled one chunk each from the shared boundary on
    shared = reg.counter_value("serve.prefix_shared_tokens")
    _holds_the_launch_counters(cached, reg, 2 + 3 + 1 + 1 + 1,
                               sum(r.prompt_len for r in reqs) - shared)
    assert reg.counter_value("serve.prefill_tail_rows") == len(reqs)
    for req in reqs:
        _holds_against_reference(fam, cfg, params, req, got[req.rid])
        if not sampled:
            gold = generate(model, params, jnp.asarray(req.prompt[None]),
                            max_new_tokens=req.max_new_tokens)
            assert got[req.rid] == list(
                np.asarray(gold)[0, req.prompt_len:])


# ------------------------------------------------------------------ (d)
def _eqns(jaxpr, stack=(), in_cond=False):
    """Every equation of a jaxpr and of the jaxprs inside it, with its
    whole scope path and whether a `cond` encloses it."""
    for eqn in jaxpr.eqns:
        here = stack + tuple(
            t for t in str(eqn.source_info.name_stack).split("/") if t)
        yield eqn, here, in_cond
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(
                        sub, here, in_cond or eqn.primitive.name == "cond")


@pytest.mark.parametrize("family", FAMILIES)
def test_the_chunk_program_holds_the_head_inside_one_conditional(family):
    """The `prefill_chunk` program: ONE conditional; the head's product
    inside it and nowhere outside, under the scope `lm_head` at the
    program's top level (`obs.scope_map`'s group `lm_head`, not
    `layer/...`), for one row; the logits handed back are
    [1, 1, vocab]."""
    fam, cfg, model, params = build(family)
    eng, _ = engine(fam, cfg, model, params)
    vocab = cfg["vocab_size"]
    lowered = eng.lower_programs()["prefill_chunk"]
    assert lowered.out_info[0].shape == (1, 1, vocab)
    text = lowered.as_text()
    assert text.count("stablehlo.case") + text.count("stablehlo.if") == 1
    traced = eng._jits["prefill_chunk"].trace(*eng._dummy_args("prefill_chunk"))
    heads = [(eqn, path, in_cond)
             for eqn, path, in_cond in _eqns(traced.jaxpr.jaxpr)
             if eqn.primitive.name == "dot_general" and "lm_head" in path]
    assert heads
    phases = (*hp.PHASES, *hp.SCOPE_MAP_GROUPS)
    for eqn, path, in_cond in heads:
        assert in_cond, path
        assert eqn.outvars[0].aval.shape == (1, 1, vocab), path
        assert hp.group_of("/".join(("jit(chunk_fn)",) + path
                                    + ("dot_general",)), phases) == "lm_head"
    # and no product as wide as the vocabulary for every row of a chunk
    assert not [path for eqn, path, _ in _eqns(traced.jaxpr.jaxpr)
                if eqn.primitive.name == "dot_general"
                and eqn.outvars[0].aval.shape[-2:] == (CHUNK, vocab)
                and "lm_head" in path]
