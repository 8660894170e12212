"""The chunk program's blockwise attention kernel
(hetu_tpu/ops/pallas/chunk_attention.py) behind the one hook
`cache_contract.KVAttention.attend_dense`.

All CPU, the kernel in interpret mode: values against the composition
`models/generation._attend_cached_chunk` (which stays the route of every
backend but a TPU and of every shape the gate refuses), the gate's
refusals with their reasons, the route record, and two tiny models
served with the kernel forced on and off.
"""
from __future__ import annotations

import functools
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(__file__))

from hetu_tpu.models.cache_contract import KVAttention  # noqa: E402
from hetu_tpu.models.generation import _attend_cached_chunk  # noqa: E402
from hetu_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from hetu_tpu.ops.pallas import chunk_attention as ca  # noqa: E402
from hetu_tpu.ops.pallas import record_routes  # noqa: E402
from hetu_tpu.serving.engine import ServeConfig, ServingEngine  # noqa: E402
from hetu_tpu.serving.request import Request  # noqa: E402
from test_serving import launches  # noqa: E402

HD, KB = 128, 128
F32, BF16 = jnp.float32, jnp.bfloat16

#: name -> (C, group, n_kv, M, start, window, first, dtype, row tile)
#: with key blocks of KB = 128 positions.  `first` > 0: the cache handed
#: in is a slice that begins at that position (a window layer's slab).
CASES = {
    # no window: the prompt's first chunk, one in the middle whose live
    # keys end inside a block, one whose live keys end on a block's edge,
    # and the last chunk of the cache (a padded tail is rows like any)
    "full_start0_g1": (32, 1, 2, 512, 0, None, 0, F32, 1024),
    "full_mid_inside_block_g2": (32, 2, 2, 512, 200, None, 0, F32, 1024),
    "full_mid_block_edge_g8": (32, 8, 1, 512, 224, None, 0, F32, 1024),
    "full_last_chunk_g2": (32, 2, 2, 512, 480, None, 0, F32, 1024),
    "full_bf16_g8": (32, 8, 1, 512, 300, None, 0, BF16, 1024),
    # rows of several query heads in one tile, tiles of part of a chunk
    "full_two_heads_a_tile_g8": (32, 8, 2, 384, 130, None, 0, F32, 64),
    "full_tile_within_chunk_g2": (64, 2, 1, 384, 100, None, 0, F32, 32),
    # a window: the chunk wholly inside it, one straddling its edge, one
    # far behind which it reaches, and the slab attend_dense slices
    "window_chunk_inside_g2": (32, 2, 2, 512, 64, 192, 0, F32, 1024),
    "window_straddles_edge_g1": (32, 1, 2, 512, 176, 192, 0, F32, 1024),
    "window_far_g8": (32, 8, 1, 512, 400, 192, 0, F32, 1024),
    "window_slab_g2": (32, 2, 2, 256, 400, 224, 176, F32, 1024),
    "window_slab_bf16_g8": (32, 8, 1, 256, 300, 224, 76, BF16, 1024),
    "window_of_one_g2": (32, 2, 1, 256, 100, 1, 0, F32, 1024),
}


#: the band form (a window far narrower than the slice: a row tile is
#: the group's heads at ONE block of 128 positions and walks its own
#: band of key blocks): name -> (C, start, sink).  Window 128 over the
#: slab of window + C positions `attend_dense` cuts, n_kv 2, groups of
#: 4, keys of 256 lanes beside values of 128; the starts clip the band
#: at the cache's head (0, 5), leave it unaligned to the key blocks (5,
#: 127: three live blocks a tile) and whole (128, 1000: two).
BAND_WINDOW, BAND_HD_K = 128, 256
BAND_CASES = {
    f"band_c{C}_start{start}{'_sink' if sink else ''}": (C, start, sink)
    for C in (256, 384) for start in (0, 5, 127, 128, 1000)
    for sink in (False, True)}


def _case(name):
    """-> (C, g, n_kv, M, start, window, first, dtype, row tile, key
    width, sink, times the band has to fit) of a case of either table."""
    if name in CASES:
        return CASES[name] + (HD, False, 2)
    C, start, sink = BAND_CASES[name]
    M = BAND_WINDOW + C
    return (C, 4, 2, M, start, BAND_WINDOW, max(0, start + C - M),
            BF16 if sink and C == 384 else F32, 512, BAND_HD_K, sink, 1)


@functools.lru_cache(maxsize=None)
def _jitted(window, *patched):
    """One jit a (window, patched constants): the constants are read
    while tracing, so a trace is good for the values it was made under."""
    return jax.jit(lambda q, k, v, s, f, sink: ca.chunk_attention(
        q, k, v, s, window=window, first=f, sink=sink))


@pytest.mark.parametrize("case", list(CASES) + list(BAND_CASES))
def test_chunk_kernel_is_the_composition(case, monkeypatch):
    """ONE body: the kernel's output is `_attend_cached_chunk`'s (to
    float32 reassociation; to bfloat16 rounding of the probabilities for
    bfloat16 caches), and every key block the chunk cannot see (past
    the block of position start + C - 1; before the block of the
    window's first position) is never read: filled with NaN, it changes
    nothing.  In the band form a TILE reads its own band alone: with
    every block outside the last tile's band NaN, that tile's rows are
    still the composition's."""
    (C, g, n_kv, M, start, window, first, dtype, tile, hd_k, has_sink,
     pays) = _case(case)
    monkeypatch.setattr(ca, "_KEY_BLOCK", KB)
    monkeypatch.setattr(ca, "_ROW_TILE", tile)
    monkeypatch.setattr(ca, "_BAND_PAYS", pays)
    ks = jax.random.split(jax.random.key(hash(case) % 1000), 4)
    q = jax.random.normal(ks[0], (1, C, g * n_kv, hd_k), F32).astype(dtype)
    k = jax.random.normal(ks[1], (1, M, n_kv, hd_k), F32).astype(dtype)
    v = jax.random.normal(ks[2], (1, M, n_kv, HD), F32).astype(dtype)
    sink = jax.random.normal(ks[3], (g * n_kv,), F32) if has_sink else None
    plan = ca.check_shapes(q.shape, k.shape, (), window=window, dtype=dtype,
                           v_shape=v.shape, sink=has_sink)
    band = case in BAND_CASES
    assert (plan.pb, plan.tr, plan.kb, plan.steps) == (
        (128, g * 128, KB, 3) if band else (0, plan.tr, KB, M // KB))
    want = np.asarray(_attend_cached_chunk(
        q, k, v, start, hd_k ** -0.5, window=window, first=first,
        **({"sink": sink} if has_sink else {})), np.float32)
    fn = _jitted(window, tile, pays)
    pos = np.arange(M) // KB
    atol = 2e-5 if dtype == F32 else 2e-2

    def got(lo_pos, hi_pos):
        """the kernel's, with every key block outside those of cache
        positions lo_pos .. hi_pos (relative to `first`) NaN"""
        lo, hi = max(0, lo_pos) // KB, hi_pos // KB
        dead = jnp.asarray((pos < lo) | (pos > hi))[None, :, None, None]
        assert dead.any() or M // KB == hi - lo + 1
        out = fn(q, jnp.where(dead, jnp.nan, k), jnp.where(dead, jnp.nan, v),
                 jnp.int32(start), jnp.int32(first), sink)
        assert out.shape == (1, C, g * n_kv, HD) and out.dtype == q.dtype
        return np.asarray(out, np.float32)

    q0 = start - first
    np.testing.assert_allclose(
        got(0 if window is None else q0 - window + 1, q0 + C - 1), want,
        atol=atol, rtol=0)
    if band:
        t0 = C - plan.pb        # the last tile's first position
        np.testing.assert_allclose(
            got(q0 + t0 - window + 1, q0 + C - 1)[:, t0:], want[:, t0:],
            atol=atol, rtol=0)


#: the benchmark's cells: (q, k, start shapes), window, (row tile, key
#: block), and where a cell's keys are wider than its values, V's shape;
#: the LAST entry of a band-form plan is (pb, steps)
CELL_SHAPES = {
    "trinity_full": (((1, 512, 32, 128), (1, 8192, 4, 128), (1,)), None,
                     (1024, 1024)),
    "trinity_window_slab": (((1, 512, 32, 128), (1, 2560, 4, 128), (1,)),
                            2048, (1024, 640)),
    "internlm2": (((1, 128, 16, 128), (1, 2048, 8, 128), (1,)), None,
                  (256, 1024)),
    # a cache shorter than a lane tile is one key block
    "cache_of_64": (((1, 16, 4, 128), (1, 64, 2, 128), ()), None, (32, 64)),
    # the rule of the band form, from window, C, M and the group alone:
    # MiMo's window layers (128 positions a tile see 2 x 128 of the 1,152
    # keys) take it; a window as wide as the chunk or wider (Phi, Trinity
    # above) and every call without a window keep the present tiling
    "mimo_window_slab_band": (((1, 1024, 64, 256), (1, 1152, 8, 256), (1,)),
                              128, (1024, 128), (1, 1152, 8, 128), (128, 3)),
    "mimo_full": (((1, 1024, 64, 256), (1, 16384, 4, 256), (1,)), None,
                  (1024, 1024), (1, 16384, 4, 128)),
    "phi_window_slab": (((1, 512, 40, 128), (1, 1024, 10, 128), (1,)), 512,
                        (1024, 1024)),
    "internlm2_512_rows": (((1, 512, 16, 128), (1, 2048, 8, 128), (1,)),
                           None, (1024, 1024)),
    "jamba_one_kv_head": (((1, 512, 20, 128), (1, 4608, 1, 128), (1,)), None,
                          (1024, 768)),
}

#: what the gate refuses, and a word of its reason
REFUSED = {
    "rows_at_depths_of_their_own": (((4, 16, 4, 128), (4, 256, 2, 128),
                                     (4,)), "depths of their own"),
    "one_row_per_row_start_of_two": (((1, 16, 4, 128), (1, 256, 2, 128),
                                      (2,)), "depths of their own"),
    "single_query": (((1, 1, 4, 128), (1, 256, 2, 128), ()), "C = 1"),
    "head_dim_64": (((1, 16, 4, 64), (1, 256, 2, 64), ()), "lane-aligned"),
    "cache_of_1000": (((1, 16, 4, 128), (1, 1000, 2, 128), ()),
                      "multiple of 128"),
    "cache_of_one_block_of_60": (((1, 16, 4, 128), (1, 60, 2, 128), ()),
                                 "multiple of 128"),
    "chunk_of_12": (((1, 12, 4, 128), (1, 256, 2, 128), ()), "sublanes"),
    "heads_3_over_2": (((1, 16, 3, 128), (1, 256, 2, 128), ()),
                       "divide by kv heads"),
}


@pytest.mark.parametrize("case", list(CELL_SHAPES) + list(REFUSED))
def test_gate_drift_chunk_attention(case):
    """`compatible` is `check_shapes` is the kernel's own entry
    validation: the cells' shapes pass with tiles from the shapes alone;
    per-row starts, a single query and misaligned head dims, cache
    lengths and chunks are refused with the reason the route record
    then carries, and the kernel itself raises the same (`check_route`,
    the gate the hook hands to `resolve_route`, raises it too)."""
    if case in CELL_SHAPES:
        shapes, window, tiles, *more = CELL_SHAPES[case]
        kw = dict(window=window, dtype=BF16,
                  v_shape=more[0] if more else None)
        pb, steps = more[1] if more[1:] else (0, shapes[1][1] // tiles[1])
        assert ca.compatible(*shapes, **kw)
        plan = ca.check_shapes(*shapes, **kw)
        assert (plan.tr, plan.kb, plan.pb, plan.steps) == (*tiles, pb, steps)
        assert ("pb 128, kb 128, steps 3" in plan.why) == bool(pb)
        assert ("no window" in plan.why) == (window is None)
        # the ROUTE's gate also asks whether the kernel pays: Trinity's
        # 168 / 537 MB of float32 scores do (MiMo's 302 MB, and exactly
        # 64 MB in the 512-row launches), InternLM2's 16.8 MB do not
        if case in ("internlm2", "cache_of_64"):
            with pytest.raises(ValueError, match="MB of float32 scores"):
                ca.check_route(*shapes, **kw)
        else:
            assert ca.check_route(*shapes, **kw) == plan
        return
    shapes, word = REFUSED[case]
    assert not ca.compatible(*shapes, dtype=F32)
    for check in (ca.check_shapes, ca.check_route):
        with pytest.raises(ValueError, match=word):
            check(*shapes, dtype=F32)
    q, k = (jnp.zeros(s, F32) for s in shapes[:2])
    with pytest.raises(ValueError, match=word):
        ca.chunk_attention(q, k, k, jnp.zeros(shapes[2], jnp.int32))


def _force(monkeypatch, on: bool):
    monkeypatch.setenv("HETU_TPU_PALLAS", "1" if on else "0")
    monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", "chunk_attn")


@pytest.mark.parametrize("call", ["chunk", "chunk_window", "chunk_band",
                                  "decode_rows", "verify_rows", "not_a_tpu"])
def test_attend_dense_routes_by_what_it_observes(call, monkeypatch):
    """The hook asks the one routing rule for ONE row's chunk and for
    nothing else: a single query and rows at depths of their own keep
    the composition whatever the flags say, and so does every backend
    but a TPU; the record says which and why, and of a layer the kernel
    takes also which tiling (`chunk_attn_band`: the band form with its
    pb, kb and steps, or why not).  Routed or not, the values are the
    composition's."""
    b, C, window, start = {
        "chunk": (1, 16, None, jnp.asarray([40], jnp.int32)),
        "chunk_window": (1, 16, 112, jnp.asarray([130], jnp.int32)),
        # groups of 8 at 640 rows under a window of 128: blocks of 128
        # positions see 3 x 128 of the 768 keys `attend_dense` slices
        "chunk_band": (1, 640, 128, jnp.asarray([300], jnp.int32)),
        "decode_rows": (3, 1, None, jnp.asarray([5, 40, 17], jnp.int32)),
        "verify_rows": (3, 4, None, jnp.asarray([5, 40, 17], jnp.int32)),
        "not_a_tpu": (1, 16, None, jnp.asarray([40], jnp.int32)),
    }[call]
    if call == "not_a_tpu":
        monkeypatch.delenv("HETU_TPU_PALLAS", raising=False)
    else:
        _force(monkeypatch, True)
    nq, n_kv, M = (8, 1, 1024) if call == "chunk_band" else (4, 2, 256)
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (b, C, nq, HD), F32)
    k = jax.random.normal(ks[1], (b, M, n_kv, HD), F32)
    v = jax.random.normal(ks[2], (b, M, n_kv, HD), F32)
    kw = {} if window is None else {"window": window}
    with record_routes() as routes:
        got = KVAttention().attend_dense(None, q, (k, v), start, **kw)
    rec = routes["chunk_attn"]
    kernel = call.startswith("chunk")
    assert (rec["pallas"], rec["xla"]) == ((1, 0) if kernel else (0, 1))
    why, = rec["why"]
    assert {"chunk": "forced on", "chunk_window": "forced on",
            "chunk_band": "forced on",
            "decode_rows": "single query", "verify_rows": "single query",
            "not_a_tpu": "not a TPU backend"}[call] in why
    # the tiling is a question of the kernel's: no line where it is not taken
    assert ("chunk_attn_band" in routes) == kernel
    if kernel:
        band = routes["chunk_attn_band"]
        assert (band["pallas"], band["xla"]) == (
            (1, 0) if call == "chunk_band" else (0, 1))
        why, = band["why"]
        assert {"chunk": "no window",
                "chunk_window": "no block of positions (C 16, groups of 2)",
                "chunk_band": "3 key blocks of 128 against the chunk's 768, "
                              "pb 128, kb 128, steps 3"}[call] in why
    want = _attend_cached_chunk(q, k, v, start, HD ** -0.5, window=window)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want).reshape(b, C, nq * HD),
                               atol=2e-5, rtol=0)


def _model(family):
    """(model, params, layers the chunk program traces): scanned layers
    are traced once a run; Trinity's have arrays of their own, each."""
    if family == "trinity":
        from test_trinity import build
        _, model, params = build(head_dim=HD, sliding_window=112)
        return model, params, 6
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
    model = LlamaLMHeadModel(LlamaConfig.tiny(
        remat=False, compute_dtype=F32, use_flash_attention=False,
        hidden_size=256, num_attention_heads=2, num_key_value_heads=1,
        max_position_embeddings=256))
    return model, model.init(jax.random.key(1)), 1


@pytest.mark.parametrize("family", ["llama", "trinity"])
def test_serving_with_the_chunk_kernel_serves_the_same_tokens(
        family, monkeypatch):
    """A tiny llama (scanned layers, g = 2) and the tiny Trinity (window
    layers of 112 beside one full layer, each layer with arrays of its
    own) at head_dim 128: the engine serves the same tokens with the
    chunk kernel forced on (interpret mode) and off, prompts that span
    several chunks and reach past the window; `kernel_routes` counts the
    chunk programs' traced layers on the kernel (a program a launch
    shape) and nothing else."""
    model, params, traced_layers = _model(family)
    vocab = model.config.vocab_size
    rng = np.random.default_rng(2)
    lens = [(5, 4), (40, 5), (150, 6), (23, 3)]

    def serve(on):
        _force(monkeypatch, on)
        reg = MetricsRegistry()
        eng = ServingEngine(model, params, ServeConfig(
            num_slots=3, page_size=8, max_len=256, prefill_chunk=16,
            num_pages=(96, 64) if family == "trinity" else 96),
            registry=reg)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=m, arrival_t=0.0)
                for i, (p, m) in enumerate(prompts)]
        out = {r.rid: list(r.tokens) for r in eng.run(reqs)}
        # a chunk program a launch shape issued: Trinity's window scratch
        # slides, so its launches are one chunk (ONE program); the
        # llama's three slots prefill in launches of one to three chunks
        shapes.append(len(launches(reg)))
        return out, eng.kernel_routes["chunk_attn"]

    prompts = [(rng.integers(0, vocab, size=n).astype(np.int32), m)
               for n, m in lens]
    shapes = []
    off, routes_off = serve(False)
    on, routes_on = serve(True)
    assert sorted(on) == list(range(len(lens)))
    assert on == off
    assert shapes[0] == shapes[1] and (shapes[0] == 1) == (
        family == "trinity")
    traced_layers *= shapes[0]
    assert routes_on["pallas"] == traced_layers and not routes_off["pallas"]
    # (the decode program asks nothing of this route: its layers attend
    # the pages where they lie, `attend_paged`)
    assert not routes_on["xla"] and all("forced on" in w
                                        for w in routes_on["why"])
    assert routes_off["xla"] >= traced_layers
