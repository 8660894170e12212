"""Xing4.0 on the serving path, at a tiny size that keeps every mechanism:
Kimi-K2's block inside a residual STREAM of four hidden vectors a token,
mixed by manifold-constrained hyper-connections around both sublayers of
every layer (`generation._layer`'s residual hooks).  Seeded random float32
weights; the reference is `benchmarks/families/xing4.py`'s plain forward,
which shares no code with the program and not the stream's either.

Tolerances: tests/test_kimi_k2.py's (program and reference are both
float32 here and differ by the order of float32 sums: LOGIT_ATOL = 2e-4
is two orders over that and three under what a wrong mask, rotation,
expert, coefficient or stream moves).
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
from benchmarks.families import xing4 as fam  # noqa: E402
from hetu_tpu.models import generation as gen  # noqa: E402
from hetu_tpu.models.kimi_k2 import KimiK2LMHeadModel  # noqa: E402
from hetu_tpu.nn import hyper_connections as hc  # noqa: E402
from hetu_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from hetu_tpu.serving.engine import ServeConfig, ServingEngine  # noqa: E402
from hetu_tpu.serving.request import Request  # noqa: E402

from test_kimi_k2 import _programs  # noqa: E402

LOGIT_ATOL = 2e-4
F32 = jnp.float32


def tiny_cfg():
    """The rehearsal's configuration, read as the plain forward (no
    near-tie pass: a test of logits compares ONE computation)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tiny-xing4.json")) as f:
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
    assert (c.hc_mult, c.hc_sinkhorn_iters, c.hc_eps) == (4, 20, 1e-6)
    assert (c.num_hidden_layers, c.first_k_dense_replace) == (3, 1)
    assert (c.n_routed_experts, c.experts_held, c.num_experts_per_tok) \
        == (8, 8, 2)
    assert c.latent_dim == 136 and c.latent_stored_dim == 256
    runs = model.serving_layers(model.abstract_params())
    assert [b.moe for b, _, _ in runs] == [False, True, True]
    assert all(count is None and hasattr(b, "residual_pre")
               for b, _, count in runs)
    for _, lp, _ in model.serving_layers(params):
        for side in ("hc_attn", "hc_mlp"):
            assert {k: v.shape for k, v in lp[side].items()} == {
                "phi": (4 * 64, 24), "b": (24,), "alpha": (3,)}
            assert all(v.dtype == F32 for v in lp[side].values())
    # what a token stores is Kimi's: the stream is nothing in the cache
    assert model.cache_contract().stored_shapes == ((256,),)
    assert model.num_params() == fam.counts(cfg)["total_params"]
    # the input-dependent part of a logit stands near 0.5: a mixing that
    # did not vary with the token would hide a dropped `x^ phi`
    X = jax.random.normal(jax.random.key(3), (512, 4, 64), F32)
    hp = params["model"]["moe_layers"]["layer_0"]["hc_attn"]
    x = X.reshape(512, -1)
    x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))
    u = hp["alpha"][0] * (x @ hp["phi"])[:, :4]
    assert 0.35 < float(jnp.std(u)) < 0.65


# ------------------------------------------------------------------ (b)

@pytest.mark.parametrize("plen", [5, 16, 40])
def test_chunked_prefill_page_write_and_paged_decode_are_the_reference(
        plen, rng):
    """Prompt lengths inside a chunk (16), at its edge and over two: the
    stream is the carry of the chunk and of the decode program alike, and
    the latents are read back from their pages."""
    cfg, model, params = build()
    n_decode = 5
    seq = rng.integers(0, cfg["vocab_size"],
                       size=plen + n_decode).astype(np.int32)
    want = ref_logits(params, cfg, seq)
    prefill_logits, tree, table, stats = _programs(
        model, params, seq[:plen], n_decode)
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


# ------------------------------------------------------------------ (c)

def _connection(n=4, hidden=64, **kw):
    return hc.HyperConnection(hidden, n, sinkhorn_iters=20, eps=1e-6,
                              rms_eps=1e-6, initializer_range=0.08, **kw)


def test_h_res_is_doubly_stochastic_and_finite_at_the_clamps():
    conn = _connection()
    params = conn.init(jax.random.key(0))
    X = jax.random.normal(jax.random.key(1), (96, 4, 64), F32)
    h_pre, h_post, h_res = jax.jit(conn.coefficients)(params, X)
    assert h_pre.shape == (4, 96) and h_res.shape == (4, 4, 96)
    assert float(jnp.abs(h_res.sum(0) - 1).max()) < 1e-4      # columns
    assert float(jnp.abs(h_res.sum(1) - 1).max()) < 1e-4      # rows
    assert float(h_pre.min()) > 0 and float(h_pre.max()) < 1
    assert float(h_post.min()) > 0 and float(h_post.max()) < 2
    # it leans to the identity, and it varies with the token
    assert float(jnp.mean(h_res[jnp.arange(4), jnp.arange(4)])) > 0.4
    assert float(jnp.std(h_res[0, 0])) > 0.01
    # every logit at either clamp, in every pattern of the two
    bits = (jnp.arange(2 ** 16)[None, :] >> jnp.arange(16)[:, None]) & 1
    logits = jnp.where(bits == 1, 1e9, -1e9).reshape(4, 4, -1).astype(F32)
    m = jax.jit(lambda x: hc.sinkhorn(x, 20, 1e-6))(logits)
    assert bool(jnp.isfinite(m).all()) and float(m.min()) >= 0
    assert float(m.max()) <= 1.0 + 1e-6
    # the reference's own coefficients are the same numbers
    cfg = dict(hc_mult=4, rms_norm_eps=1e-6, hc_sinkhorn_iters=20,
               hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
    with jax.default_matmul_precision("highest"):
        r_pre, r_post, r_res = fam.coefficients(X, params, cfg)
    np.testing.assert_allclose(h_pre.T, r_pre, atol=2e-6)
    np.testing.assert_allclose(h_post.T, r_post, atol=4e-6)
    np.testing.assert_allclose(jnp.moveaxis(h_res, -1, 0), r_res, atol=2e-6)


def test_one_stream_with_unit_coefficients_is_kimis_block(rng, monkeypatch):
    """n = 1 with H_pre = H_post = 1 and H_res = 1 handed to the pre- and
    post-mix: the model's logits are `KimiK2LMHeadModel`'s on the same
    weights, to float rounding, whole sequences and through the chunk and
    decode programs alike."""
    cfg, model, params = build(hc_mult=1)

    def ones(self, p, X, dtype=F32):
        r = X.shape[0]
        return (jnp.ones((1, r), dtype), jnp.ones((1, r), dtype),
                jnp.ones((1, 1, r), dtype))
    monkeypatch.setattr(hc.HyperConnection, "coefficients", ones)
    kimi = KimiK2LMHeadModel(model.config)
    drop = lambda t: {k: drop(v) for k, v in t.items()  # noqa: E731
                      if not k.startswith("hc_")} if isinstance(t, dict) else t
    kparams = drop(params)
    assert jax.tree.structure(kparams) == jax.tree.structure(
        kimi.abstract_params())
    ids = rng.integers(0, cfg["vocab_size"], size=(1, 45)).astype(np.int32)
    want = np.asarray(jax.jit(kimi.forward)(kparams, jnp.asarray(ids)))[0]
    got = np.asarray(jax.jit(model.forward)(params, jnp.asarray(ids)))[0]
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    for m, p in ((model, params), (kimi, kparams)):
        lg, tree, table, stats = _programs(m, p, ids[0, :40], 5)
        np.testing.assert_allclose(lg, want[:40], atol=2e-5, rtol=0)
        out = jax.jit(gen.decode_step_paged, static_argnums=0)(
            m, p, jnp.asarray([0, ids[0, 40], 0]), tree, jnp.asarray(table),
            jnp.asarray([0, 40, 0], jnp.int32), stats)
        np.testing.assert_allclose(np.asarray(out[0][1]), want[40],
                                   atol=2e-5, rtol=0)


def test_coefficients_in_bfloat16_fail_where_float32_passes(rng,
                                                            monkeypatch):
    """The stream is the model's dtype, the coefficients are float32:
    computed in bfloat16 (8 bits of mantissa on sums of 256 products,
    then 40 normalisations on top of one another) the logits leave the
    reference by more than LOGIT_ATOL, which the float32 coefficients
    keep with two orders to spare."""
    cfg, model, params = build()
    ids = rng.integers(0, cfg["vocab_size"], size=(1, 64)).astype(np.int32)
    want = ref_logits(params, cfg, ids[0])
    got = np.asarray(jax.jit(model.forward)(params, jnp.asarray(ids)))[0]
    assert np.abs(got - want).max() < LOGIT_ATOL / 10
    f32 = hc.HyperConnection.coefficients
    monkeypatch.setattr(
        hc.HyperConnection, "coefficients", lambda self, p, X, dtype=F32: tuple(
            c.astype(F32) for c in f32(self, p, X, jnp.bfloat16)))
    coarse = np.asarray(jax.jit(lambda p, i: model.forward(p, i))(
        params, jnp.asarray(ids)))[0]
    assert np.abs(coarse - want).max() > 5 * LOGIT_ATOL


def test_the_references_exchanges_at_the_edge_of_the_chosen_experts():
    """`families/xing4.gate(code=)`: one exchange of `EXCHANGES` at the
    edge of a token's chosen k where the margin in the router's logit is
    under `router_tie_logit`, none where it is not; the passes are every
    way to give one or two expert layers one exchange each."""
    cfg = dict(num_experts_per_tok=2, norm_topk_prob=True,
               routed_scaling_factor=2.0, router_tie_logit=0.25)
    # token 0: scores ranked e0 > e1 | e2 > e3, a tenth of a logit apart;
    # token 1: the same order, a whole logit apart
    logits = jnp.asarray([[0.3, 0.2, 0.1, 0.0, -3.0],
                          [3.0, 2.0, 1.0, 0.0, -3.0]], F32)
    mp = {"w_gate": jnp.eye(5, dtype=F32),
          "e_score_correction_bias": jnp.zeros(5, F32)}
    chosen = lambda code: [sorted(r) for r in np.asarray(  # noqa: E731
        fam.gate(logits, mp, cfg, code)[0]).tolist()]
    assert chosen(None) == [[0, 1], [0, 1]]
    idx, w, moved = fam.gate(logits, mp, cfg, jnp.int32(0))
    assert not bool(moved.any()) and chosen(jnp.int32(0)) == chosen(None)
    for code, want in ((1, [0, 2]), (2, [1, 2]), (3, [0, 3])):
        idx, w, moved = fam.gate(logits, mp, cfg, jnp.int32(code))
        assert np.asarray(moved).tolist() == [True, False], code
        assert chosen(jnp.int32(code)) == [want, [0, 1]], code
        # the weights are the chosen experts' scores over their sum, x 2
        s = np.asarray(jax.nn.sigmoid(logits))[0][want]
        np.testing.assert_allclose(sorted(np.asarray(w[0])),
                                   sorted(2 * s / s.sum()), rtol=1e-6)
    codes = fam.pass_codes(dict(num_hidden_layers=5, first_k_dense_replace=1))
    assert codes.shape == (4 * 3 + 6 * 9, 4) and codes.max() == 3
    assert set(((codes > 0).sum(1)).tolist()) == {1, 2}
    assert len({tuple(c) for c in codes.tolist()}) == len(codes)


@pytest.mark.parametrize("control", fam.CONTROLS)
def test_a_wrong_stream_is_not_the_reference(control, rng):
    """One Sinkhorn iteration in place of 20, and the input-dependent
    part of the coefficients dropped: each moves the logits by O(0.1)."""
    cfg, model, params = build()
    ids = rng.integers(0, cfg["vocab_size"], size=64).astype(np.int32)
    got = np.asarray(jax.jit(model.forward)(params, jnp.asarray(ids[None])))
    bad = ref_logits(params, cfg, ids, control)
    assert np.abs(got[0] - bad).max() > 100 * LOGIT_ATOL


# ------------------------------------------------------------------ (d)

@pytest.fixture(scope="module")
def served():
    """Streams the tiny engine served: prompts inside a chunk, at its
    edge and over several, more requests than slots."""
    cfg, model, params = build()
    reg = MetricsRegistry()
    engine = ServingEngine(model, params, ServeConfig(
        num_slots=3, page_size=8, max_len=128, prefill_chunk=16,
        num_pages=48), registry=reg)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg["vocab_size"], size=n)
                    .astype(np.int32), max_new_tokens=24, arrival_t=0.0)
            for i, n in enumerate((9, 32, 81, 50))]
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
    """As far as the tiny size shows it (256 candidates at a width of 64
    over 3 layers leave the winner far ahead): against the reference with
    ONE thing of the stream done wrongly, served streams lose tokens to
    other candidates and show a gap, where against the reference as it
    stands none does.  At the cell's size on the chip (131,072 candidates,
    5 layers of 3,584) both controls come out NOT correct (PERF.md s6).
    The controlled reference runs the SAME near-tie passes as the check
    of a sound program does: what they forgive a control is forgiven."""
    bad = _check(served, control,
                 cfg=dict(served[0], router_tie_logit=0.02))
    assert any(s["argmax_equal"] < s["tokens"] and s["max_gap"] > 0
               for s in bad), bad


def test_scopes_and_counters_of_the_programs(served):
    from hetu_tpu.obs import hlo_profile as hp
    cfg, _, reqs, results, reg, texts = served
    assert {"mhc_pre", "mhc_sinkhorn", "mhc_post"} <= set(
        hp.SCOPE_MAP_GROUPS)
    for name in ("decode", "prefill_chunk"):
        placed = hp.scope_map(texts[name])
        groups = {g for g, _ in placed.values()}
        assert {"layer/mhc_pre", "layer/mhc_sinkhorn", "layer/mhc_post",
                "layer/mla_q", "layer/mla_kv", "layer/kv_write",
                "layer/mla_out", "layer/router", "layer/experts",
                "layer/shared_expert", "layer/attn", "layer/mlp", "embed",
                "lm_head"} <= groups, name
        # SIBLINGS of `attn` and `mlp`: no operation's own path holds one
        # of them inside the other
        for line in texts[name].splitlines():
            if "mhc_" in line and 'op_name="' in line:
                path = line.split('op_name="')[1].split('"')[0]
                assert "attn" not in path.split("/") \
                    and "mlp" not in path.split("/"), path
    # the mixes run for every computed row, so the engine counts none of
    # their own: the cost function reads the chunk programs' rows, and
    # their least bytes are 3 layers x 2 sublayers x (4 + 1 + 8 + 1) x 64
    # values a row (+ phi, b and alpha a sublayer a launch, float32)
    prompt = sum(r.prompt_len for r in reqs)
    assert reg.counter_value("serve.prefill_tokens") == prompt
    launches = reg.counter_value("serve.prefill_chunks")
    cost = fam.mhc_chunk_cost(cfg, {"counters": {
        "serve.prefill_tokens": prompt, "serve.prefill_chunks": launches}},
        elem_bytes=4.0)
    assert cost["bytes"] == 3 * 2 * (prompt * 14 * 64 * 4 + launches * 4.0 * (
        4 * 64 * 24 + 24 + 3))
