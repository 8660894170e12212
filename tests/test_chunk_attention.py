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


@pytest.mark.parametrize("case", CASES)
def test_chunk_kernel_is_the_composition(case, monkeypatch):
    """ONE body: the kernel's output is `_attend_cached_chunk`'s (to
    float32 reassociation; to bfloat16 rounding of the probabilities for
    bfloat16 caches), and every key block the chunk cannot see (past
    the block of position start + C - 1; before the block of the
    window's first position) is never read: filled with NaN, it changes
    nothing."""
    C, g, n_kv, M, start, window, first, dtype, tile = CASES[case]
    monkeypatch.setattr(ca, "_KEY_BLOCK", KB)
    monkeypatch.setattr(ca, "_ROW_TILE", tile)
    ks = jax.random.split(jax.random.key(hash(case) % 1000), 3)
    q = jax.random.normal(ks[0], (1, C, g * n_kv, HD), F32).astype(dtype)
    k = jax.random.normal(ks[1], (1, M, n_kv, HD), F32).astype(dtype)
    v = jax.random.normal(ks[2], (1, M, n_kv, HD), F32).astype(dtype)
    want = _attend_cached_chunk(q, k, v, start, HD ** -0.5, window=window,
                                first=first)
    lo = 0 if window is None else max(0, start - window + 1 - first) // KB
    hi = (start + C - 1 - first) // KB
    pos = np.arange(M) // KB
    dead = jnp.asarray((pos < lo) | (pos > hi))[None, :, None, None]
    assert dead.any() or M // KB == hi - lo + 1
    got = jax.jit(lambda q, k, v, s, f: ca.chunk_attention(
        q, k, v, s, window=window, first=f))(
            q, jnp.where(dead, jnp.nan, k), jnp.where(dead, jnp.nan, v),
            jnp.int32(start), jnp.int32(first))
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-5 if dtype == F32 else 2e-2, rtol=0)


#: the benchmark's cells: (q, k, start shapes), window, (row tile, key block)
CELL_SHAPES = {
    "trinity_full": (((1, 512, 32, 128), (1, 8192, 4, 128), (1,)), None,
                     (1024, 1024)),
    "trinity_window_slab": (((1, 512, 32, 128), (1, 2560, 4, 128), (1,)),
                            2048, (1024, 640)),
    "internlm2": (((1, 128, 16, 128), (1, 2048, 8, 128), (1,)), None,
                  (256, 1024)),
    # a cache shorter than a lane tile is one key block
    "cache_of_64": (((1, 16, 4, 128), (1, 64, 2, 128), ()), None, (32, 64)),
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
        shapes, window, tiles = CELL_SHAPES[case]
        assert ca.compatible(*shapes, window=window, dtype=BF16)
        assert ca.check_shapes(*shapes, window=window,
                               dtype=BF16)[-2:] == tiles
        # the ROUTE's gate also asks whether the kernel pays: Trinity's
        # 168 / 537 MB of float32 scores do, InternLM2's 16.8 MB do not
        if case.startswith("trinity"):
            assert ca.check_route(*shapes, window=window,
                                  dtype=BF16)[-2:] == tiles
        else:
            with pytest.raises(ValueError, match="MB of float32 scores"):
                ca.check_route(*shapes, window=window, dtype=BF16)
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


@pytest.mark.parametrize("call", ["chunk", "chunk_window", "decode_rows",
                                  "verify_rows", "not_a_tpu"])
def test_attend_dense_routes_by_what_it_observes(call, monkeypatch):
    """The hook asks the one routing rule for ONE row's chunk and for
    nothing else: a single query and rows at depths of their own keep
    the composition whatever the flags say, and so does every backend
    but a TPU; the record says which and why.  Routed or not, the values
    are the composition's."""
    b, C, window, start = {
        "chunk": (1, 16, None, jnp.asarray([40], jnp.int32)),
        "chunk_window": (1, 16, 112, jnp.asarray([130], jnp.int32)),
        "decode_rows": (3, 1, None, jnp.asarray([5, 40, 17], jnp.int32)),
        "verify_rows": (3, 4, None, jnp.asarray([5, 40, 17], jnp.int32)),
        "not_a_tpu": (1, 16, None, jnp.asarray([40], jnp.int32)),
    }[call]
    if call == "not_a_tpu":
        monkeypatch.delenv("HETU_TPU_PALLAS", raising=False)
    else:
        _force(monkeypatch, True)
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (b, C, 4, HD), F32)
    k = jax.random.normal(ks[1], (b, 256, 2, HD), F32)
    v = jax.random.normal(ks[2], (b, 256, 2, HD), F32)
    kw = {} if window is None else {"window": window}
    with record_routes() as routes:
        got = KVAttention().attend_dense(None, q, (k, v), start, **kw)
    rec = routes["chunk_attn"]
    kernel = call in ("chunk", "chunk_window")
    assert (rec["pallas"], rec["xla"]) == ((1, 0) if kernel else (0, 1))
    why, = rec["why"]
    assert {"chunk": "forced on", "chunk_window": "forced on",
            "decode_rows": "single query", "verify_rows": "single query",
            "not_a_tpu": "not a TPU backend"}[call] in why
    want = _attend_cached_chunk(q, k, v, start, HD ** -0.5, window=window)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want).reshape(b, C, 4 * HD),
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
