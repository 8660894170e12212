"""MiMo-V2 (`model_type: mimo_v2_flash`) on the serving path, at a tiny
size that keeps every mechanism: keys wider than values (24 / 16; 192 /
128 under the kernels), 1 KV head where a layer reads everything and 2
under the window (groups of 4 and 2; 16 and 8 under the kernels), a
window of 12 that is smaller than a page pair (16) and than the chunk
(16), a learned sink a head in the window layers' softmax, a rotation of
the first third of a head with a base a kind of layer, a value scale, a
leading dense layer, sigmoid-routed dropless experts held in part (4 of
16) with NO shared expert.  Seeded random float32 weights; the reference
is `benchmarks/families/mimo_v2.py`'s plain forward, which shares no
code with the program.

Tolerances.  Program and reference are both float32 here, so they differ
by the ORDER of float32 sums only: logits of O(1) agree to a few 1e-6;
LOGIT_ATOL = 2e-4 leaves two orders of room and is three orders under
what a wrong mask, window edge, rotation, sink, expert or scale moves.
The kernels in interpret mode against the XLA composition: float32
operands, sums in another order: 2e-5 of outputs of O(1).
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

from benchmarks.families import mimo_v2 as fam  # noqa: E402
from hetu_tpu.models import generation as gen  # noqa: E402
from hetu_tpu.models.cache_contract import cache_contract  # noqa: E402
from hetu_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from hetu_tpu.serving.engine import ServeConfig, ServingEngine  # noqa: E402
from hetu_tpu.serving.kv_pool import (PagePool,  # noqa: E402
                                      contract_bytes_per_token)
from hetu_tpu.serving.request import Request  # noqa: E402
from hetu_tpu.serving.scheduler import Scheduler  # noqa: E402

LOGIT_ATOL = 2e-4
KERNEL_ATOL = 2e-5
F32 = jnp.float32
WINDOW, PAGE, CHUNK = 12, 8, 16


def tiny_cfg():
    """The rehearsal's configuration without `router_tie_logit`: the
    reference's plain forward (the near-tie passes have tests of their
    own in benchmarks/tests/test_mimo_family.py)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tiny-mimo.json")) as f:
        cfg = json.load(f)
    del cfg["router_tie_logit"]
    return cfg


def build(**over):
    cfg = dict(tiny_cfg(), **over)
    for a, b in (("head_dim", "swa_head_dim"),
                 ("v_head_dim", "swa_v_head_dim"),
                 ("sliding_window", "sliding_window_size")):
        cfg[b] = cfg[a]
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
    cfg, model, params = build()
    c = model.config
    assert c.hybrid_layer_pattern == (0, 1, 1, 0, 1) \
        and c.moe_layer_freq == (0, 1, 1, 1, 1)
    assert c.head_dim == 24 and c.v_head_dim == 16 and c.rotary_dim == 8
    assert (c.num_key_value_heads, c.swa_num_key_value_heads) == (1, 2)
    assert c.router_experts == 16 and c.experts_held == 4 \
        and c.first_expert == 4
    # a window smaller than a page pair and than the chunk
    assert WINDOW == c.sliding_window < 2 * PAGE == CHUNK
    layers = params["model"]
    # the sink stands on the window layers alone, and is not zero
    assert [("sink" in layers[f"layer_{i}"]["attn"]) for i in range(5)] \
        == [False, True, True, False, True]
    assert float(jnp.abs(layers["layer_1"]["attn"]["sink"]).min()) > 0
    # no shared expert: no such weights
    assert sorted(layers["layer_1"]["mlp"]) == [
        "e_score_correction_bias", "w_down", "w_gate", "w_gate_up"]
    assert "w_gate" not in layers["layer_0"]["mlp"]
    assert model.num_params() == fam.counts(cfg)["total_params"]
    # a kind of layer is a window AND what a token stores there
    contract = cache_contract(model)
    assert contract.kinds == (None, WINDOW)
    assert contract.layers_of(0) == (0, 3) \
        and contract.layers_of(1) == (1, 2, 4)
    assert contract.token_shapes_of(0) == ((1, 24), (1, 16))
    assert contract.token_shapes_of(1) == ((2, 24), (2, 16))
    assert contract.stored_shapes_of(1) == ((2, 128), (2, 16))
    assert [contract.kind_of(i) for i in range(5)] == [0, 1, 1, 0, 1]
    # the planner's bytes a token: summed over the kinds, each its own
    assert contract_bytes_per_token(contract, "fp32") \
        == 4 * (2 * 1 * 40 + 3 * 2 * 40)


def test_the_full_configuration_is_the_published_one_cut_as_it_says():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mimo-v2-flash-ep16-depth7.json")) as f:
        served = json.load(f)
    model = fam.build_model(served, {"param_dtype": "bfloat16"})
    assert model.num_params() == served["parameters"] \
        == fam.counts(served)["total_params"]
    contract = cache_contract(model)
    assert contract.kinds == (None, 128)
    assert contract.layers_of(0) == (0, 5)
    # 2,560 B a token on a full layer, 5,120 B on a window layer
    assert [2 * sum(int(np.prod(s)) for s in contract.token_shapes_of(k))
            for k in (0, 1)] == [2560, 5120]
    assert contract.stored_shapes_of(0) == ((4, 256), (4, 128))
    assert contract_bytes_per_token(contract, "bf16") \
        == 2 * 2560 + 5 * 5120
    # all 48 published layers of this chip's share
    whole = dict(served, num_hidden_layers=48,
                 hybrid_layer_pattern=[0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7
                 + [0],
                 moe_layer_freq=[0] + [1] * 47)
    c = fam.counts(whole)
    assert c["total_params"] > 47 * 16 * 3 * 4096 * 2048


def test_the_model_is_not_the_model_without_its_own_mechanisms(rng):
    """Each mechanism moves the logits by far more than the tolerance:
    the reference would tell a program without it.  The sink (not zero)
    and the value scale among them."""
    cfg, model, params = build()
    ids = rng.integers(0, cfg["vocab_size"], size=40).astype(np.int32)
    want = ref_logits(params, cfg, ids)
    for over, times in ((dict(add_swa_attention_sink_bias=False), 100),
                        (dict(attention_value_scale=1.0), 100),
                        (dict(sliding_window=40), 100),
                        # (at this width the scores are small and the
                        # rotation moves them little: 0.012, and 0.0008 for the
                        # base over a window of 12 positions)
                        (dict(partial_rotary_factor=0.5), 20),
                        (dict(swa_rope_theta=5000000), 2)):
        other = fam.logits_at(params, jnp.asarray(ids), jnp.arange(40),
                              dict(cfg, **over))
        assert np.abs(np.asarray(other) - want).max() \
            > times * LOGIT_ATOL, over
    # ... and the PROGRAM without the sink or the value scale is told by
    # the reference with them
    no_sink = jax.tree_util.tree_map_with_path(
        lambda path, a: a - 60.0 if path[-1].key == "sink" else a, params)
    moved = np.asarray(jax.jit(model.forward)(
        no_sink, jnp.asarray(ids[None])))[0]
    assert np.abs(moved - want).max() > 100 * LOGIT_ATOL
    _, unscaled, _ = build(attention_value_scale=1.0)
    moved = np.asarray(jax.jit(unscaled.forward)(
        params, jnp.asarray(ids[None])))[0]
    assert np.abs(moved - want).max() > 100 * LOGIT_ATOL


# ------------------------------------------------------------------ (b)

def _prefill_then_decode(model, params, seq, plen, slide=True):
    """The scheduler, the pool and the programs by hand, one request in
    slot 1 of 3: chunked prefill into the scratch (a window layer's of
    window + chunk positions where `slide`, of max_len else), the page
    write, then teacher-forced decode steps over the gather route, pages
    released behind the window before each.  -> (logits [len(seq),
    vocab], the window kind's pages held per step, slot, scheduler)."""
    contract = cache_contract(model)
    pool = PagePool.for_contract(contract, num_pages=(32, 9), page_size=PAGE)
    sched = Scheduler(num_slots=3, pool=pool, max_len=128)
    req = Request(rid=0, prompt=seq[:plen], max_new_tokens=len(seq) - plen)
    sched.submit(req)
    sched.slots[0] = object()          # slot 0 stays empty: admit into 1
    slot, st = sched.admit_next(0.0)
    sched.slots[0] = None
    assert slot == 1
    cache = gen.init_cache(model, 1, 128,
                           **(dict(chunk=CHUNK, page=PAGE) if slide else {}))
    assert [c.shape[2] for c in cache] == (
        [128, 128, 16 + CHUNK, 16 + CHUNK] if slide else [128] * 4)
    chunk = jax.jit(lambda p, ids, cache, s, stats: gen.extend_cache(
        model, p, ids, cache, s, stats, slide=slide, max_len=128))
    padded = -(-plen // CHUNK) * CHUNK
    ids = np.zeros(padded, np.int32)
    ids[:plen] = seq[:plen]
    stats, logits = model.zero_stats(), []
    for s in range(0, padded, CHUNK):
        lg, cache, stats = chunk(params, jnp.asarray(ids[None, s: s + CHUNK]),
                                 cache, jnp.int32(s), stats)
        logits.append(np.asarray(lg[0]))
    logits = [np.concatenate(logits)[:plen]]
    bases = [0, max(0, s - 16)] if slide else None
    tree = pool.write_pages(
        pool.arrays.tree(),
        jax.tree.map(jnp.asarray, sched.write_rows(slot, bases=bases)),
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
                           pool.gather(tree, table), jnp.asarray(positions))
        tree = pool.write_token(tree, table, jnp.asarray(positions), *toks)
        logits.append(np.asarray(lg[slot])[None])
        st.pos = t + 1
    return np.concatenate(logits), held, st, sched


@pytest.mark.parametrize("plen", [5, 16, 23, 40, 17, 64])
def test_chunked_prefill_page_write_and_paged_decode_are_the_reference(
        plen, rng):
    """Prefill, then decode THROUGH THE PAGES, against the reference's
    logits.  Prompts straddle a page (8), a chunk (16) and the window
    (12 < 16): a chunk's queries see keys of the chunk before it through
    the window, the window layers' scratch slides, a prompt's end writes
    its last window's pages and no more, and 30 decode steps run past
    the point where the first pages of the window layers are released."""
    cfg, model, params = build()
    seq = rng.integers(0, cfg["vocab_size"], size=plen + 30).astype(np.int32)
    got, held, st, sched = _prefill_then_decode(model, params, seq, plen)
    np.testing.assert_allclose(got, ref_logits(params, cfg, seq),
                               atol=LOGIT_ATOL, rtol=0)
    # a window layer's slot never holds more than ceil((w - 1) / page) + 1
    # pages, whatever the prompt's length
    cap = -(-(WINDOW - 1) // PAGE) + 1
    assert cap == 3 == sched.pool.hold_pages(128, 1) and max(held) <= cap
    assert st.first_page[1] == (plen + 29 - WINDOW + 1) // PAGE > 0
    assert len(st.pages) == -(-(plen + 30) // PAGE) and st.first_page[0] == 0
    assert (sched.page_tables[1, 1, : st.first_page[1]] == 0).all()


def test_a_sliding_scratch_gives_the_logits_of_a_max_len_scratch(rng):
    """The window layers' scratch of window + chunk positions against
    one of `max_len`: the same logits, prefill and decode (what the page
    write took out of either is what the decode steps read)."""
    cfg, model, params = build()
    seq = rng.integers(0, cfg["vocab_size"], size=75).astype(np.int32)
    slid, *_ = _prefill_then_decode(model, params, seq, 55, slide=True)
    kept, *_ = _prefill_then_decode(model, params, seq, 55, slide=False)
    np.testing.assert_allclose(slid, kept, atol=2e-6, rtol=0)


# ------------------------------------------------- the kernels' new shapes

def _dense_case(rng, C, M, nq, n_kv):
    d_k, d_v = 256, 128
    q = jnp.asarray(rng.standard_normal((1, C, nq, d_k)), F32) * 0.3
    q = q.at[..., 192:].set(0.0)
    k = jnp.asarray(rng.standard_normal((1, M, n_kv, d_k)), F32) * 0.3
    v = jnp.asarray(rng.standard_normal((1, M, n_kv, d_v)), F32)
    sink = jnp.asarray(rng.standard_normal((nq,)), F32)
    return q, k, v, sink


@pytest.mark.parametrize("with_sink", [False, True])
@pytest.mark.parametrize("n_kv,window,start,first", [
    (4, None, 0, 0), (4, None, 272, 0), (8, 128, 0, 0), (8, 128, 400, 128)])
def test_chunk_kernel_takes_wide_keys_groups_and_a_sink(
        n_kv, window, start, first, with_sink, rng):
    """ops/pallas/chunk_attention in interpret mode against the XLA
    composition `_attend_cached_chunk`: d_k 256 (192 used) != d_v 128,
    groups of 16 and 8, with and without a sink, a window smaller than
    the chunk, a cache that begins at `first`."""
    from hetu_tpu.ops.pallas import chunk_attention as ca
    C, M = 144, 384
    q, k, v, sink = _dense_case(rng, C, M, 64, n_kv)
    extra = {"sink": sink} if with_sink else {}
    assert ca.compatible(q.shape, k.shape, (), window=window, dtype=F32,
                         v_shape=v.shape, sink=with_sink)
    want = gen._attend_cached_chunk(q, k, v, start, 192 ** -0.5,
                                    window=window, first=first, **extra)
    got = ca.chunk_attention(q, k, v, jnp.int32(start),
                             softmax_scale=192 ** -0.5, window=window,
                             first=first, **extra)
    assert got.shape == (1, C, 64, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=KERNEL_ATOL, rtol=0)
    if with_sink:
        # the sink takes probability and adds no value
        bare = gen._attend_cached_chunk(q, k, v, start, 192 ** -0.5,
                                        window=window, first=first)
        assert np.abs(np.asarray(bare) - np.asarray(want)).max() \
            > 100 * KERNEL_ATOL


@pytest.mark.parametrize("with_sink", [False, True])
@pytest.mark.parametrize("n_kv,window", [(4, None), (8, 128)])
def test_paged_kernel_takes_wide_keys_groups_and_a_sink(
        n_kv, window, with_sink, rng):
    """ops/pallas/paged_attention in interpret mode against the
    composition over the gathered pages: K pages of 256 lanes beside V
    pages of 128, groups of 16 and 8, with and without a sink, a window
    of two pages of 64."""
    from hetu_tpu.ops.pallas import paged_attention as pa
    S, ps, mp, P, nq = 3, 64, 6, 20, 64
    kp = jnp.asarray(rng.standard_normal((P, ps, n_kv, 256)), F32) * 0.3
    vp = jnp.asarray(rng.standard_normal((P, ps, n_kv, 128)), F32)
    q = jnp.asarray(rng.standard_normal((S, nq, 256)), F32) * 0.3
    q = q.at[..., 192:].set(0.0)
    sink = jnp.asarray(rng.standard_normal((nq,)), F32)
    table = jnp.asarray(rng.permutation(np.arange(1, P))[: S * mp]
                        .reshape(S, mp), jnp.int32)
    positions = jnp.asarray([5, 200, 383], jnp.int32)
    extra = {"sink": sink} if with_sink else {}
    assert pa.compatible(q.shape, kp.shape, table.shape, (S,), window=window,
                         v_shape=vp.shape, sink=with_sink)
    got = pa.paged_attention(q, kp, vp, table, positions,
                             softmax_scale=192 ** -0.5, window=window,
                             **extra)
    assert got.shape == (S, nq, 128)
    dense_k = kp[table].reshape(S, mp * ps, n_kv, 256)
    dense_v = vp[table].reshape(S, mp * ps, n_kv, 128)
    want = gen._attend_cached_chunk(q[:, None], dense_k, dense_v, positions,
                                    192 ** -0.5, window=window, **extra)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=KERNEL_ATOL, rtol=0)


def test_kernel_gates_say_what_they_take():
    from hetu_tpu.ops.pallas import chunk_attention as ca
    from hetu_tpu.ops.pallas import paged_attention as pa
    pool, table = (9, 64, 4, 256), (2, 4)
    assert pa.compatible((2, 64, 256), pool, table, (2,),
                         v_shape=(9, 64, 4, 128), sink=True)
    # a head dim off the lanes, a V pool of other pages, a sink over
    # quantized pages: refused
    assert not pa.compatible((2, 64, 192), (9, 64, 4, 192), table, (2,))
    assert not pa.compatible((2, 64, 256), pool, table, (2,),
                             v_shape=(9, 64, 4, 64))
    assert not pa.compatible((2, 64, 256), pool, table, (2,),
                             v_shape=(8, 64, 4, 128))
    with pytest.raises(ValueError, match="exact pages"):
        pa.check_shapes((2, 64, 128), (9, 64, 4, 128), table, (2,),
                        quant="int8", sink=True)
    q, k = (1, 512, 64, 256), (1, 640, 8, 256)
    assert ca.compatible(q, k, (), window=128, v_shape=(1, 640, 8, 128),
                         sink=True)
    assert not ca.compatible(q, k, (), v_shape=(1, 640, 8, 96))
    assert not ca.compatible((1, 512, 64, 192), (1, 640, 8, 192), ())
    # the route's gate is PR 35's: MiMo's window layer (84 MB of scores)
    # passes it, InternLM2's chunk does not
    ca.check_route(q, k, (), window=128, v_shape=(1, 640, 8, 128), sink=True)
    with pytest.raises(ValueError, match="under the 64 MB"):
        ca.check_route((1, 128, 16, 128), (1, 2048, 8, 128), ())


# ------------------------------------------------- the share ties to the model

def test_the_sixteen_shares_add_up_to_the_uncut_layer(rng):
    """The expert layer of every share (`first_expert` 0, 4, 8, 12 of 16
    here; 0, 16, .., 240 of 256 in the deployment), program and
    reference, add up to the uncut reference's layer: the router's
    weights are the same, each share holds its slice of the experts, and
    there is no shared expert that every share would add again."""
    from hetu_tpu.nn.moe import SharedRoutedExperts
    hidden, inter, E, held, k = 64, 32, 16, 4, 4
    whole = SharedRoutedExperts(
        hidden, inter, n_routed_experts=E, experts_held=E, first_expert=0,
        top_k=k, n_shared_experts=0, norm_topk_prob=True,
        routed_scaling_factor=1.0)
    wp = whole.init(jax.random.key(3))
    assert "shared_gate_up" not in wp
    x = jnp.asarray(rng.standard_normal((1, 24, hidden)), F32)
    cfg = dict(num_experts_per_tok=k, norm_topk_prob=True,
               routed_scaling_factor=None, first_expert=0)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(fam.experts(x[0], wp, cfg))
        total_prog, total_ref = 0.0, 0.0
        for first in range(0, E, held):
            share = SharedRoutedExperts(
                hidden, inter, n_routed_experts=E, experts_held=held,
                first_expert=first, top_k=k, n_shared_experts=0,
                norm_topk_prob=True, routed_scaling_factor=1.0)
            sp = dict(wp, w_gate_up=wp["w_gate_up"][first: first + held],
                      w_down=wp["w_down"][first: first + held])
            y, _ = share(sp, x)
            total_prog = total_prog + np.asarray(y[0])
            total_ref = total_ref + np.asarray(fam.experts(
                x[0], sp, dict(cfg, first_expert=first)))
    np.testing.assert_allclose(total_ref, uncut, atol=2e-6, rtol=0)
    np.testing.assert_allclose(total_prog, uncut, atol=2e-6, rtol=0)
    # and some token of a share has no held expert: it gets exactly 0
    y, st = share(sp, x)
    assert int(st[1]) < int(st[0])


def test_no_shared_expert_is_neither_built_nor_traced():
    from hetu_tpu.nn.moe import SharedRoutedExperts
    kw = dict(n_routed_experts=8, experts_held=4, first_expert=0, top_k=2,
              norm_topk_prob=True, routed_scaling_factor=1.0)
    x = jnp.zeros((1, 8, 32), F32)
    texts = {}
    for n in (0, 1):
        layer = SharedRoutedExperts(32, 16, n_shared_experts=n, **kw)
        p = layer.init(jax.random.key(0))
        assert ("shared_down" in p) == bool(n)
        texts[n] = jax.jit(layer.forward).lower(p, x).compile().as_text()
    # (the scope in an operation's name: "jit(forward)/shared_expert/..")
    assert "/shared_expert/" in texts[1] \
        and "/shared_expert/" not in texts[0]
    assert texts[0].count(" dot(") < texts[1].count(" dot(")


# ------------------------------------------------- gauges, counters, routes

def test_gauges_and_counters_read_the_kinds_own_shapes(rng):
    _, model, params = build()
    reg = MetricsRegistry()
    eng = _engine(model, params, registry=reg, num_pages=(32, 9))
    full, win = 4 * 1 * 40, 4 * 2 * 40      # float32: K 24 + V 16 a head
    assert reg.gauge_value("serve.kv_bytes_per_token") == 2 * full + 3 * win
    assert reg.gauge_value("serve.kv_bytes_per_token", kind="full") \
        == 2 * full
    assert reg.gauge_value("serve.kv_bytes_per_token",
                           kind=f"window_{WINDOW}") == 3 * win
    # the scratch: a full layer keeps max_len, a window layer window (up
    # to whole pages) + chunk positions, of the STORED shapes
    assert reg.gauge_value("serve.prefill_scratch_bytes", kind="full") \
        == 2 * 128 * 4 * (128 + 16)
    assert reg.gauge_value("serve.prefill_scratch_bytes",
                           kind=f"window_{WINDOW}") \
        == 3 * (16 + CHUNK) * 4 * 2 * (128 + 16)
    req = Request(rid=0, prompt=rng.integers(0, 256, 40).astype(np.int32),
                  max_new_tokens=3)
    eng.run([req])
    # chunks at 0, 16, 32 of 16 rows each (the last one's padding counts)
    want_full = sum(16 * s + 16 * 17 // 2 for s in (0, 16, 32))
    want_win = sum(min(s + i + 1, WINDOW) for s in (0, 16, 32)
                   for i in range(16))
    assert reg.counter_value("serve.prefill_attended_keys",
                             kind="full") == want_full
    assert reg.counter_value("serve.prefill_attended_keys",
                             kind=f"window_{WINDOW}") == want_win
    routes = eng.kernel_routes
    assert "chunk_attn" in routes and "paged_attn" in routes
    # (a CPU: the composition in both programs; the lines say the
    # widths, the group and whether a sink entered all the same)
    assert sorted(routes["chunk_attn_shapes"]["why"]) == [
        "keys 24 (held in 128) against values 16, groups of 2, a sink a head",
        "keys 24 (held in 128) against values 16, groups of 4, no sink"]
