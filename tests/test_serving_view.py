"""The layer walk of the serving programs moves no bytes a layer does not
read (tier-1, CPU).

Two things, both between the programs of models/generation.py and what
they are handed:

* the model's serving view (`serving_params`, the optional hook): the
  fused qkv and gate/up weights as plain matrices a product takes where
  they lie in the layers' stack.  It is a permutation of the training
  tensors' columns, `project` and the MLP give over it what `forward`'s
  einsums give, an engine serves the same tokens with and without it,
  and takes it only where the parameters are whole on one device;
* the dense prefill scratch as a carry of the walk, donated by the
  chunk program: every admission advances zeros of its own in place.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import generate
from hetu_tpu import ops, serving
from hetu_tpu.models.generation import (extend_cache, init_cache,
                                        prefill, verify_step_slots)
from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.models.llama.model import LlamaAttention, LlamaMLP
from hetu_tpu.obs.metrics import MetricsRegistry
from hetu_tpu.parallel.strategy import ParallelStrategy
from hetu_tpu.serving.engine import serving_view
from hetu_tpu.serving.request import Request
from test_serving import _engine
from test_serving_families import _HooksOnlyFamily

#: (q heads a kv head, kv heads): MHA, GQA, and one kv head for all
HEADS = [(g, n_kv) for g in (1, 2, 4) for n_kv in (1, 8)]


class TrainingLayoutLlama(LlamaLMHeadModel):
    """A llama that brings no serving view: served with its parameters
    as they come, by the same programs."""
    serving_params = None


def _config(g=2, n_kv=2, hd=16, **kw):
    base = dict(vocab_size=256, hidden_size=g * n_kv * hd,
                intermediate_size=96, num_hidden_layers=2,
                num_attention_heads=g * n_kv, num_key_value_heads=n_kv,
                max_position_embeddings=128, use_flash_attention=False,
                compute_dtype=jnp.float32, param_dtype=jnp.float32,
                remat=False)
    base.update(kw)
    return LlamaConfig(**base)


def _model(cls=LlamaLMHeadModel, seed=1, **kw):
    model = cls(_config(**kw))
    return model, model.init(jax.random.key(seed))


def _requests(vocab, lens=((5, 6), (19, 4), (23, 6), (40, 5), (9, 3)),
              sampling=None, gap=0.02):
    rng = np.random.default_rng(7)
    return [Request(rid=i, prompt=rng.integers(0, vocab, size=n)
                    .astype(np.int32), max_new_tokens=m, arrival_t=i * gap,
                    **({"sampling": sampling(i)} if sampling else {}))
            for i, (n, m) in enumerate(lens)]


def _tokens(engine, reqs):
    return {r.rid: list(r.tokens) for r in engine.run(reqs)}


# ------------------------------------------------------------ the view
@pytest.mark.parametrize("g,n_kv", HEADS)
def test_the_view_is_a_permutation_of_the_training_columns(g, n_kv):
    """Every column of the regrouped `wqkv` [L, h, q | k | v heads] and
    of `w_gate_up` [L, h, gate | up] IS a column of the training tensor:
    q head j (with kv head j // g) from [.., j // g, j % g, :], k and v
    head c from [.., c, g, :] and [.., c, g + 1, :]."""
    L, h, hd, inter = 3, 24, 8, 40
    rng = np.random.default_rng(g * 10 + n_kv)
    wqkv = rng.standard_normal((L, h, n_kv, g + 2, hd)).astype(np.float32)
    w_gu = rng.standard_normal((L, h, 2, inter)).astype(np.float32)
    attn = LlamaAttention(_config(g, n_kv, hd), ParallelStrategy())
    view = np.asarray(attn.serving_view(jnp.asarray(wqkv)))
    n_q = g * n_kv
    assert view.shape == (L, h, (n_q + 2 * n_kv) * hd)
    for j in range(n_q):
        np.testing.assert_array_equal(view[..., j * hd:(j + 1) * hd],
                                      wqkv[:, :, j // g, j % g, :])
    for c in range(n_kv):
        k0, v0 = (n_q + c) * hd, (n_q + n_kv + c) * hd
        np.testing.assert_array_equal(view[..., k0:k0 + hd],
                                      wqkv[:, :, c, g, :])
        np.testing.assert_array_equal(view[..., v0:v0 + hd],
                                      wqkv[:, :, c, g + 1, :])
    gu = np.asarray(LlamaMLP.serving_view(jnp.asarray(w_gu)))
    assert gu.shape == (L, h, 2 * inter)
    np.testing.assert_array_equal(gu[..., :inter], w_gu[:, :, 0, :])
    np.testing.assert_array_equal(gu[..., inter:], w_gu[:, :, 1, :])
    # one layer's own arrays (use_scan=False) are relaid the same way
    np.testing.assert_array_equal(
        np.asarray(attn.serving_view(jnp.asarray(wqkv[1]))), view[1])


@pytest.mark.parametrize("g,n_kv", HEADS)
def test_products_over_the_view_equal_forwards_einsums(g, n_kv):
    """`project` and the MLP's serving form over the view give, bit for
    bit, the q, k, v and the MLP output of `forward`'s einsums on the
    training tree: the same numbers multiplied in the same order along
    the hidden dim, cut out of the product's output, not the weight."""
    model, params = _model(g=g, n_kv=n_kv)
    view = model.serving_params(params)
    block = model.model.layers.block
    lp, lv = (jax.tree.map(lambda a: a[1], t["model"]["layers"]["layers"])
              for t in (params, view))
    assert lv["attn"]["wqkv"].ndim == 2 and lv["mlp"]["w_gate_up"].ndim == 2
    b, s, hd = 2, 5, model.config.head_dim
    x = jax.random.normal(jax.random.key(2),
                          (b, s, model.config.hidden_size), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32) + 3, (b, s))
    rope = model.rope_tables(64)

    # forward's own lines (models/llama/model.py LlamaAttention.forward)
    qkv = jnp.einsum("bsh,hkgd->bskgd", x, lp["attn"]["wqkv"])
    q = qkv[..., :g, :].reshape(b, s, g * n_kv, hd)
    q, k = ops.apply_rotary_qk(q, qkv[..., g, :], *rope, pos)
    v = qkv[..., g + 1, :]
    for p in (lp, lv):
        q2, (k2, v2) = block.attn.project(p["attn"], x, rope, pos)
        for got, want in ((q2, q), (k2, k), (v2, v)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    want = np.asarray(block.mlp(lp["mlp"], x))
    for p in (lp, lv):
        y, stats = block.mlp_stats(p["mlp"], x)
        assert stats is None
        np.testing.assert_array_equal(np.asarray(y), want)


def test_chunked_prefill_over_the_view_equals_prefill_whole():
    """Consecutive chunks through `extend_cache` over the view are the
    incremental form of `prefill` over the training tree: the same
    next-token logits and the same cache."""
    model, params = _model()
    view = model.serving_params(params)
    ids = jax.random.randint(jax.random.key(4), (1, 24), 0, 256)
    logits, cache = prefill(model, params, ids, 32)
    got = init_cache(model, 1, 32)
    for s in range(0, 24, 8):
        lg, got = extend_cache(model, view, ids[:, s:s + 8], got, s)
    np.testing.assert_allclose(np.asarray(lg[:, -1]), np.asarray(logits),
                               atol=2e-5)
    for a, b in zip(got, cache):
        np.testing.assert_allclose(np.asarray(a[:, :, :24]),
                                   np.asarray(b[:, :, :24]), atol=2e-5)
    # the verify step's reference over a dense cache too
    lv, _, kv = verify_step_slots(model, view, ids[:, 16:], got,
                                  jnp.array([16]))
    lt, _, kt = verify_step_slots(model, params, ids[:, 16:], got,
                                  jnp.array([16]))
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(lt))
    for a, b in zip(kv, kt):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("stacked", [True, False])
@pytest.mark.parametrize("mode", ["greedy", "sampled"])
def test_engine_tokens_equal_with_and_without_the_view(mode, stacked):
    """An engine over the model's view and one over a llama that brings
    none (the parameters as they come) emit the same tokens, greedy and
    with the seeded sampler, stacked layers (scanned) and layers with
    arrays of their own (called); greedy also `generate()`'s."""
    sample = (lambda i: serving.SamplingParams(
        temperature=0.9, top_k=20, seed=100 + i)) if mode == "sampled" \
        else None
    kw = dict(sampling=True) if mode == "sampled" else {}
    model, params = _model(use_scan=stacked)
    plain, _ = _model(TrainingLayoutLlama, use_scan=stacked)
    eng = _engine(model, params, **kw)
    base = _engine(plain, params, **kw)
    assert eng.relaid_weight_bytes > 0 and base.relaid_weight_bytes == 0
    assert base.params is params
    got = _tokens(eng, _requests(256, sampling=sample))
    assert got == _tokens(base, _requests(256, sampling=sample))
    if mode == "greedy":
        for req in _requests(256):
            gold = generate(model, params, jnp.asarray(req.prompt[None]),
                            max_new_tokens=req.max_new_tokens)
            assert got[req.rid] == list(
                np.asarray(gold)[0, req.prompt_len:])


@pytest.mark.parametrize("stacked", [True, False])
def test_the_engine_holds_every_weight_once(stacked):
    """The engine's tree is the view: every leaf but the relaid fused
    weights IS the caller's array, `relaid_weight_bytes` is the
    arithmetic of those, and `kernel_routes` and the registry say it."""
    model, params = _model(use_scan=stacked)
    reg = MetricsRegistry()
    eng = _engine(model, params, registry=reg)
    own = dict(jax.tree.leaves_with_path(params))
    theirs = dict(jax.tree.leaves_with_path(eng.params))
    assert theirs.keys() == own.keys()
    relaid = {jax.tree_util.keystr(p) for p in own
              if theirs[p] is not own[p]}
    layers = ["['layers']"] if stacked else ["['layer_0']", "['layer_1']"]
    assert relaid == {f"['model']['layers']{name}{leaf}" for name in layers
                      for leaf in ("['attn']['wqkv']",
                                   "['mlp']['w_gate_up']")}
    c = model.config
    n_q, n_kv = c.num_attention_heads, c.num_key_value_heads
    want = c.num_hidden_layers * c.hidden_size * 4 * (
        (n_q + 2 * n_kv) * c.head_dim + 2 * c.intermediate_size)
    assert eng.relaid_weight_bytes == want
    assert eng.kernel_routes["relaid_weight_bytes"] == want
    assert "serving_params" in eng.kernel_routes["relaid_weight_why"]
    gauges = {r["name"]: r["value"] for r in reg.snapshot()["gauges"]}
    assert gauges["serve.relaid_weight_bytes"] == want
    # the same numbers, by the InternLM2-1.8B cells' shapes in bf16
    assert 24 * 2048 * (16384 + 4096) * 2 == 2_013_265_920


@pytest.mark.parametrize("how", ["reshard", "tp", "no_hook", "hooks_family"])
def test_parameters_not_whole_on_one_device_are_served_as_they_come(how):
    """With a `reshard` hook (which re-shards the engine's parameters by
    the training specs) or a model built for tp > 1 (whose layers
    constrain by them), as for a family without the hook, the engine
    serves the tree as it came, and `kernel_routes` says why."""
    from hetu_tpu.core.mesh import MeshConfig
    reshard, cls, strategy = None, LlamaLMHeadModel, None
    if how == "reshard":
        reshard = serving.LoadAdaptiveMesh(
            lambda st: LlamaLMHeadModel(_config()),
            [(0, ParallelStrategy(mesh=MeshConfig(dp=1, tp=1)))])
        want = "reshard hook"
    elif how == "tp":
        strategy = ParallelStrategy(mesh=MeshConfig(dp=1, tp=2))
        want = "tp = 2"
    else:
        cls, want = TrainingLayoutLlama, "no serving_params hook"
    if how == "hooks_family":
        model = _HooksOnlyFamily(16)
        params = model.init(jax.random.key(3))
    else:
        model = cls(_config(), strategy)
        params = LlamaLMHeadModel(_config()).init(jax.random.key(1))
    reg = MetricsRegistry()
    eng = serving.ServingEngine(
        model, params, serving.ServeConfig(num_slots=2, page_size=8,
                                           max_len=32, prefill_chunk=8),
        registry=reg, reshard=reshard)
    assert eng.params is params
    assert eng.relaid_weight_bytes == 0
    assert eng.kernel_routes["relaid_weight_bytes"] == 0
    why = eng.kernel_routes["relaid_weight_why"]
    assert why.startswith("served as they came") and want in why
    gauges = {r["name"]: r["value"] for r in reg.snapshot()["gauges"]}
    assert gauges["serve.relaid_weight_bytes"] == 0
    assert serving_view(model, params, reshard) == (params, 0, why)


def test_the_view_of_abstract_parameters_is_abstract():
    """A compile for a described chip builds the engine over shapes:
    the view is then shapes too, and counts the same bytes."""
    model, params = _model()
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          params)
    view, n, _ = serving_view(model, shapes)
    real, m, _ = serving_view(model, params)
    assert n == m > 0
    assert jax.tree.map(lambda a: (a.shape, a.dtype), view) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), real)


# --------------------------------------------------------- the scratch
def test_every_admission_advances_zeros_of_its_own():
    """The chunk program donates its scratch and advances it in place.
    Two admissions in a row: the second is handed zeros, whatever the
    first wrote; two prefills at once: each chunk call changes its own
    buffer and no other."""
    model, params = _model()
    eng = _engine(model, params)
    ids = jnp.asarray(np.arange(8, dtype=np.int32)[None] + 1)
    a, b = eng._fresh_scratch(), eng._fresh_scratch()
    assert all(not np.asarray(x).any() for x in a + b)
    _, _, a2 = eng._jits["prefill_chunk"](eng.params, ids, a, jnp.int32(0), jnp.int32(0))
    assert all(np.asarray(x[:, :, :8]).any() for x in a2)
    assert all(not np.asarray(x[:, :, 8:]).any() for x in a2)
    # the other prefill's buffer and the next admission's are untouched
    assert all(not np.asarray(x).any() for x in b)
    assert all(not np.asarray(x).any() for x in eng._fresh_scratch())
    _, _, b2 = eng._jits["prefill_chunk"](eng.params, ids + 9, b, jnp.int32(0),
                               jnp.int32(0))
    _, _, a3 = eng._jits["prefill_chunk"](eng.params, ids + 20, a2, jnp.int32(8),
                               jnp.int32(0))
    for x, y in zip(a3, b2):
        assert not np.array_equal(np.asarray(x[:, :, :8]),
                                  np.asarray(y[:, :, :8]))
    # a's first chunk is still where it was written, beside its second
    want = extend_cache(model, eng.params, ids, init_cache(model, 1, 64), 0)[1]
    for x, y in zip(a3, want):
        np.testing.assert_allclose(np.asarray(x[:, :, :8]),
                                   np.asarray(y[:, :, :8]), atol=1e-6)
    assert all(np.asarray(x[:, :, 8:16]).any() for x in a3)


def test_prefills_side_by_side_equal_prefills_alone():
    """Several slots prefilling long prompts at once, admissions back to
    back through the same slots: every stream is the one its request
    gets alone on a fresh engine."""
    model, params = _model()
    lens = ((40, 4), (37, 5), (44, 3), (33, 4), (41, 4), (12, 5))
    together = _tokens(_engine(model, params, num_slots=2),
                       _requests(256, lens, gap=0.0))
    for req in _requests(256, lens):
        alone = _tokens(_engine(model, params, num_slots=2),
                        [Request(rid=req.rid, prompt=req.prompt,
                                 max_new_tokens=req.max_new_tokens)])
        assert together[req.rid] == alone[req.rid], req.rid


def test_warmup_then_the_first_request_gives_generates_tokens():
    """`warmup()` runs the donating chunk program on a scratch of its
    own: the first real request still starts from zeros."""
    model, params = _model()
    eng = _engine(model, params).warmup()
    reqs = _requests(256)
    got = _tokens(eng, reqs)
    for req in reqs:
        gold = generate(model, params, jnp.asarray(req.prompt[None]),
                        max_new_tokens=req.max_new_tokens)
        assert got[req.rid] == list(np.asarray(gold)[0, req.prompt_len:])


def test_a_radix_primed_prompt_gives_the_tokens_it_gave():
    """A prefix-cache hit primes the scratch from the shared pages (the
    priming program reads the pool, the chunk program then owns what it
    made): same tokens as without the cache, and as without the view."""
    model, params = _model()
    plain, _ = _model(TrainingLayoutLlama)
    rng = np.random.default_rng(0)
    sysp = rng.integers(0, 256, size=24).astype(np.int32)
    prompts = [np.concatenate([sysp, rng.integers(0, 256, size=6)
                               .astype(np.int32)]) for _ in range(5)]

    def reqs():
        return [Request(rid=i, prompt=p, max_new_tokens=5)
                for i, p in enumerate(prompts)]
    warm = _engine(model, params, num_slots=2, prefix_cache=True)
    got = _tokens(warm, reqs())
    assert warm.prefix_cache.stats()["hits"] >= 3
    assert got == _tokens(_engine(model, params, num_slots=2), reqs())
    assert got == _tokens(_engine(plain, params, num_slots=2,
                                  prefix_cache=True), reqs())


def test_speculative_verify_gives_the_tokens_it_gave():
    """The verify step (`verify_step_paged`, the block's layers
    attending the slot's gathered pages by the composition here) accepts
    to the same greedy tokens as plain decoding, over the view and over
    the training layout."""
    model, params = _model()
    plain, _ = _model(TrainingLayoutLlama)
    gold = _tokens(_engine(model, params), _requests(256))
    for m in (model, plain):
        assert _tokens(_engine(m, params, spec_decode="ngram", spec_k=3),
                       _requests(256)) == gold


def test_the_prefill_tier_ships_what_it_shipped():
    """`serving/disagg.py`'s prefill tier takes the view at its build
    and donates its scratch like the engine: first token and shipped
    K/V equal a tier's that keeps the training layout."""
    from hetu_tpu.serving.disagg import PrefillWorker
    model, params = _model()
    plain, _ = _model(TrainingLayoutLlama)
    out = []
    for m in (model, plain):
        worker = PrefillWorker(m, params, prefill_chunk=8, max_len=64)
        for req in _requests(256):
            worker.submit(req)
        done = []
        while not worker.idle:
            done += worker.step()
        out.append({req.rid: (t1, ks, vs) for req, _, t1, ks, vs in done})
    assert out[0].keys() == out[1].keys() and len(out[0]) == 5
    for rid, (t1, ks, vs) in out[0].items():
        t1b, ksb, vsb = out[1][rid]
        assert t1 == t1b
        np.testing.assert_array_equal(ks, ksb)
        np.testing.assert_array_equal(vs, vsb)


def test_the_train_step_does_not_know_the_serving_view(monkeypatch):
    """`forward` is untouched: the tiny llama's train step (loss and
    gradients) lowers to the same text with every line this view added
    to the model taken away."""
    model, params = _model()
    ids = jnp.zeros((2, 16), jnp.int32)

    def lowered():
        return jax.jit(jax.value_and_grad(
            lambda p: model(p, ids, labels=ids))).lower(params).as_text()
    base = lowered()

    def gone(*a, **k):
        raise AssertionError("the train step called a serving hook")
    for cls, names in ((LlamaAttention, ("project", "serving_view")),
                       (LlamaMLP, ("serve", "serving_view")),
                       (LlamaLMHeadModel, ("serving_params",
                                           "serving_layers"))):
        for name in names:
            monkeypatch.setattr(cls, name, gone)
    assert lowered() == base
