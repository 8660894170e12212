"""The chunk program's blockwise attention kernel over a LATENT cache
(hetu_tpu/ops/pallas/latent_chunk_attention.py) behind the one hook
`kimi_k2.MLAttention.attend_dense` (Ling's `GatedMLAttention` inherits
it).

All CPU, the kernel in interpret mode: values against the composition
`MLAttention._attend_composed` (which stays the route of every backend
but a TPU and of every shape the gate refuses), the gate's refusals
with their reasons, the route record, and a tiny Kimi and a tiny Ling
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

from hetu_tpu.models.kimi_k2 import KimiK2Config  # noqa: E402
from hetu_tpu.models.kimi_k2.model import MLAttention  # noqa: E402
from hetu_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from hetu_tpu.ops.pallas import latent_chunk_attention as lca  # noqa: E402
from hetu_tpu.ops.pallas import record_routes  # noqa: E402
from hetu_tpu.parallel.strategy import ParallelStrategy  # noqa: E402
from hetu_tpu.serving.engine import ServeConfig, ServingEngine  # noqa: E402
from hetu_tpu.serving.request import Request  # noqa: E402
from test_serving import launches  # noqa: E402

KB = 128
DN, DR, DV = 128, 64, 128
F32, BF16 = jnp.float32, jnp.bfloat16


def _attention(nh, rank, dtype=F32):
    """An MLA layer of `nh` heads at the published head dims (keys of
    128 + 64, values of 128) over latents of `rank` + 64 values, stored
    in rank + 128 lanes."""
    cfg = KimiK2Config(vocab_size=64, hidden_size=64, num_hidden_layers=2,
                       num_attention_heads=nh, kv_lora_rank=rank,
                       q_lora_rank=32, param_dtype=dtype,
                       compute_dtype=dtype)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.latent_stored_dim) == (DN, DR, DV, rank + 128)
    return MLAttention(cfg, ParallelStrategy()), cfg


def _inputs(seed, nh, C, M, rank, dtype, q_gain=1.0):
    ks = jax.random.split(jax.random.key(seed), 4)
    w = (jax.random.normal(ks[0], (rank, nh, DN + DV), F32)
         * rank ** -0.5).astype(dtype)
    q_nope = (q_gain * jax.random.normal(ks[1], (1, C, nh, DN), F32)
              ).astype(dtype)
    q_rope = (q_gain * jax.random.normal(ks[2], (1, C, nh, DR), F32)
              ).astype(dtype)
    lat = jax.random.normal(ks[3], (1, M, rank + 128), F32).astype(dtype)
    # the lanes behind [c_kv | k_rope] are zeros, as `project` writes them
    return w, q_nope, q_rope, lat.at[..., rank + DR:].set(0)


#: name -> (nh, C, M, rank, start, dtype, row tile, q gain) with key
#: blocks of KB = 128 positions.  "ling": few heads, a long chunk;
#: "kimi": more heads, a short chunk
CASES = {
    # the prompt's first chunk, one in the middle whose live keys end
    # inside a block, one whose live keys end on a block's edge, and the
    # last chunk of the cache
    "ling_start0": (2, 64, 512, 256, 0, F32, 2048, 1.0),
    "ling_mid_inside_block": (2, 64, 512, 256, 200, F32, 2048, 1.0),
    "ling_mid_block_edge": (2, 64, 512, 256, 192, F32, 2048, 1.0),
    "ling_last_block": (2, 64, 512, 256, 448, F32, 2048, 1.0),
    "ling_bf16": (2, 64, 512, 256, 300, BF16, 2048, 1.0),
    "kimi_start0": (4, 16, 384, 128, 0, F32, 2048, 1.0),
    "kimi_mid": (4, 16, 384, 128, 150, F32, 2048, 1.0),
    "kimi_last_block": (4, 16, 384, 128, 368, F32, 2048, 1.0),
    "kimi_bf16": (4, 16, 384, 128, 100, BF16, 2048, 1.0),
    # a prefix far shorter than the cache: six of eight blocks are dead
    "ling_short_prefix_of_1k": (2, 32, 1024, 256, 130, F32, 2048, 1.0),
    # a chunk longer than a key block: a row's later blocks are wholly
    # masked (its statistics pass through them), and tiles of part of a
    # chunk stop at their own last row
    "ling_chunk_of_two_blocks": (2, 256, 512, 256, 0, F32, 2048, 1.0),
    "ling_tiles_within_chunk": (2, 256, 512, 256, 100, F32, 64, 1.0),
    # scores of +-1e3: float32 statistics (bfloat16's exp would not do)
    "kimi_large_scores_bf16": (4, 16, 384, 128, 200, BF16, 2048, 30.0),
}


@pytest.mark.parametrize("case", CASES)
def test_latent_kernel_is_the_composition(case, monkeypatch):
    """ONE body: the kernel's output is `_attend_composed`'s (to float32
    reassociation; to bfloat16 rounding of k_nope | v and of the
    probabilities for bfloat16 caches), and every key block the chunk
    cannot see (past the block of position start + C - 1) is never
    read: filled with NaN, it changes nothing."""
    nh, C, M, rank, start, dtype, tile, gain = CASES[case]
    monkeypatch.setattr(lca, "_KEY_BLOCK", KB)
    monkeypatch.setattr(lca, "_ROW_TILE", tile)
    att, cfg = _attention(nh, rank, dtype)
    w, q_nope, q_rope, lat = _inputs(sum(map(ord, case)), nh, C, M, rank,
                                     dtype, gain)
    at = jnp.asarray([start], jnp.int32)
    want = att._attend_composed({"wkv_b": w}, (q_nope, q_rope), (lat,), at,
                                block=KB)
    dead = jnp.asarray(np.arange(M) // KB > (start + C - 1) // KB)[
        None, :, None]
    assert dead.any() or start + C == M
    got = jax.jit(lambda *a: lca.latent_chunk_attention(
        *a, softmax_scale=cfg.softmax_scale))(
            q_nope, q_rope, jnp.where(dead, jnp.nan, lat), w, at)
    assert got.shape == (1, C, nh * DV) and got.dtype == dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 if dtype == F32 else 2e-2)


def test_padding_rows_move_no_row_of_the_prompt(monkeypatch):
    """A prompt's last chunk: `valid` rows are its own, the tail is
    padding whose queries and cache entries may hold anything finite.
    The prompt's rows see no padding position (each lies behind them),
    so they come out the same whatever the padding holds."""
    monkeypatch.setattr(lca, "_KEY_BLOCK", KB)
    nh, C, M, rank, start, valid = 2, 64, 512, 256, 140, 23
    att, cfg = _attention(nh, rank)
    w, q_nope, q_rope, lat = _inputs(3, nh, C, M, rank, F32)
    _, q_nope2, q_rope2, lat2 = _inputs(4, nh, C, M, rank, F32)
    pad_q = (jnp.arange(C) >= valid)[None, :, None, None]
    pad_k = (jnp.arange(M) >= start + valid)[None, :, None]
    at = jnp.asarray([start], jnp.int32)
    run = jax.jit(lambda *a: lca.latent_chunk_attention(
        *a, softmax_scale=cfg.softmax_scale))
    one = run(q_nope, q_rope, lat, w, at)
    two = run(jnp.where(pad_q, q_nope2, q_nope),
              jnp.where(pad_q, q_rope2, q_rope),
              jnp.where(pad_k, 7.0 * lat2, lat), w, at)
    np.testing.assert_array_equal(np.asarray(one[:, :valid]),
                                  np.asarray(two[:, :valid]))
    assert np.isfinite(np.asarray(two)).all()
    want = att._attend_composed({"wkv_b": w}, (q_nope, q_rope), (lat,), at,
                                block=KB)
    np.testing.assert_allclose(np.asarray(one), np.asarray(want), atol=2e-5,
                               rtol=0)


def _shapes(b, C, nh, M, rank=512, stored=640, dn=DN, dr=DR, dv=DV,
            start=(1,)):
    return ((b, C, nh, dn), (b, C, nh, dr), (b, M, stored),
            (rank, nh, dn + dv), start)


#: the benchmark's cells: shapes -> (row tile, key block)
CELL_SHAPES = {
    "ling": (_shapes(1, 2048, 32, 32768), (2048, 1024)),
    "kimi": (_shapes(1, 512, 64, 4096), (512, 1024)),
    # a chunk longer than the row tile splits into its largest divisor
    "chunk_of_5120": (_shapes(1, 5120, 32, 32768), (1280, 1024)),
    # a cache shorter than a lane tile is one key block
    "cache_of_64": (_shapes(1, 16, 4, 64, start=()), (16, 64)),
}

#: what the gate refuses, and a word of its reason
REFUSED = {
    "rows_at_depths_of_their_own": (_shapes(2, 16, 4, 256, start=(2,)),
                                    "depths of their own"),
    "one_row_start_of_two": (_shapes(1, 16, 4, 256, start=(2,)),
                             "depths of their own"),
    "single_query": (_shapes(1, 1, 4, 256), "C = 1"),
    "rank_of_96": (_shapes(1, 16, 4, 256, rank=96, stored=256),
                   "lane-aligned"),
    "heads_of_16": (_shapes(1, 16, 4, 256, dn=16, dr=8, dv=16, rank=128,
                            stored=256), "lane-aligned"),
    "latent_stored_unpadded": (_shapes(1, 16, 4, 256, stored=576),
                               "behind its rank"),
    "cache_of_1000": (_shapes(1, 16, 4, 1000), "multiple of 128"),
    "chunk_of_12": (_shapes(1, 12, 4, 256), "sublanes"),
    "heads_mismatch": (((1, 16, 4, DN), (1, 16, 2, DR), (1, 256, 640),
                        (512, 4, DN + DV), ()), "do not match"),
}


@pytest.mark.parametrize("case", list(CELL_SHAPES) + list(REFUSED))
def test_gate_drift_latent_chunk_attention(case):
    """`compatible` is `check_shapes` is the kernel's own entry
    validation: the cells' shapes pass with tiles from the shapes alone;
    per-row starts, a single query and misaligned widths, cache lengths
    and chunks are refused with the reason the route record then
    carries, and the kernel itself raises the same (`check_route`, the
    gate the hook hands to `resolve_route`, raises it too)."""
    if case in CELL_SHAPES:
        shapes, tiles = CELL_SHAPES[case]
        assert lca.compatible(*shapes, dtype=BF16)
        assert lca.check_shapes(*shapes, dtype=BF16)[-2:] == tiles
        # the ROUTE's gate also asks whether the kernel pays: the cells'
        # 512 MB and 8 GB of float32 scores do, a cache of 64 does not
        if case == "cache_of_64":
            with pytest.raises(ValueError, match="MB of float32 scores"):
                lca.check_route(*shapes, dtype=BF16)
        else:
            assert lca.check_route(*shapes, dtype=BF16)[-2:] == tiles
        return
    shapes, word = REFUSED[case]
    assert not lca.compatible(*shapes, dtype=F32)
    for check in (lca.check_shapes, lca.check_route):
        with pytest.raises(ValueError, match=word):
            check(*shapes, dtype=F32)
    q_nope, q_rope, lat, w = (jnp.zeros(s, F32) for s in shapes[:4])
    with pytest.raises(ValueError, match=word):
        lca.latent_chunk_attention(q_nope, q_rope, lat, w,
                                   jnp.zeros(shapes[4], jnp.int32),
                                   softmax_scale=1.0)


def _force(monkeypatch, on: bool):
    monkeypatch.setenv("HETU_TPU_PALLAS", "1" if on else "0")
    monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", "latent_chunk_attn")


@pytest.mark.parametrize("call", ["chunk", "decode_rows", "verify_rows",
                                  "not_a_tpu", "tpu_refused_shape"])
def test_attend_dense_routes_by_what_it_observes(call, monkeypatch):
    """The hook asks the one routing rule for ONE row's chunk and for
    nothing else: a single query (C = 1) and rows at depths of their own
    (b = 2) keep the composition whatever the flags say, and so does
    every backend but a TPU; on a TPU a shape the gate refuses keeps it
    with the gate's reason.  The record carries the kernel's name with a
    reason on the routed and on the refused side.  Routed or not, the
    values are the composition's."""
    b, C, start = {
        "chunk": (1, 16, jnp.asarray([40], jnp.int32)),
        "decode_rows": (3, 1, jnp.asarray([5, 40, 17], jnp.int32)),
        "verify_rows": (2, 4, jnp.asarray([5, 40], jnp.int32)),
        "not_a_tpu": (1, 16, jnp.asarray([40], jnp.int32)),
        "tpu_refused_shape": (1, 16, jnp.asarray([40], jnp.int32)),
    }[call]
    if call == "not_a_tpu":
        monkeypatch.delenv("HETU_TPU_PALLAS", raising=False)
    elif call == "tpu_refused_shape":
        monkeypatch.delenv("HETU_TPU_PALLAS", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    else:
        _force(monkeypatch, True)
    nh, M, rank = 4, 256, 128
    att, cfg = _attention(nh, rank)
    w, q_nope, q_rope, lat = (
        jnp.broadcast_to(a, (b,) + a.shape[1:]) if i else a
        for i, a in enumerate(_inputs(5, nh, C, M, rank, F32)))
    params, q = {"wkv_b": w}, (q_nope, q_rope)
    with record_routes() as routes:
        if call == "tpu_refused_shape":
            # (traced only: nothing lowers for the pretended backend)
            jax.eval_shape(att.attend_dense, params, q, (lat,), start)
        else:
            got = att.attend_dense(params, q, (lat,), start)
    rec = routes["latent_chunk_attn"]
    assert (rec["pallas"], rec["xla"]) == ((1, 0) if call == "chunk"
                                           else (0, 1))
    why, = rec["why"]
    assert {"chunk": "forced on", "decode_rows": "single query",
            "verify_rows": "single query",
            "not_a_tpu": "not a TPU backend",
            "tpu_refused_shape": "shape gate: 4 heads x 16 queries x 256 "
                                 "positions are 0 MB of float32 scores",
            }[call] in why
    if call == "tpu_refused_shape":
        return
    want = att._attend_composed(params, q, (lat,), start)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=0)


def _model(family):
    """(model, params, MLA layers the chunk program traces): the tiny
    configurations with the MLA layers at the published head dims, which
    the kernel's lanes need (the rehearsal's own are 16 + 8 wide and
    keep the composition)."""
    if family == "ling":
        from test_bailing_hybrid import build
        traced = 1
    else:
        from test_kimi_k2 import build
        traced = 3
    _, model, params = build(qk_nope_head_dim=DN, qk_rope_head_dim=DR,
                             v_head_dim=DV)
    assert model.config.latent_stored_dim == 256
    return model, params, traced


@pytest.mark.parametrize("family", ["kimi", "ling"])
def test_serving_with_the_latent_kernel_serves_the_same_tokens(
        family, monkeypatch):
    """A tiny Kimi (three MLA layers) and a tiny Ling (KDA state beside
    one gated MLA layer's pages): the engine serves the same tokens with
    the kernel forced on (interpret mode) and off, prompts that span
    several chunks and key blocks; `kernel_routes` counts the chunk
    programs' traced MLA layers on the kernel, a program a launch shape."""
    monkeypatch.setattr(lca, "_KEY_BLOCK", KB)
    model, params, traced_layers = _model(family)
    vocab = model.config.vocab_size
    rng = np.random.default_rng(2)
    lens = [(5, 4), (40, 5), (150, 6), (23, 3)]
    prompts = [(rng.integers(0, vocab, size=n).astype(np.int32), m)
               for n, m in lens]

    def serve(on):
        _force(monkeypatch, on)
        reg = MetricsRegistry()
        eng = ServingEngine(model, params, ServeConfig(
            num_slots=3, page_size=8, max_len=256, prefill_chunk=16,
            num_pages=96), registry=reg)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=m, arrival_t=0.0)
                for i, (p, m) in enumerate(prompts)]
        out = {r.rid: list(r.tokens) for r in eng.run(reqs)}
        # (a chunk program a launch shape issued: three slots prefill at
        # once, so launches of one to three chunks)
        shapes = len(launches(reg))
        assert shapes > 1
        return out, eng.kernel_routes["latent_chunk_attn"], shapes

    off, routes_off, shapes_off = serve(False)
    on, routes_on, shapes = serve(True)
    assert sorted(on) == list(range(len(lens)))
    assert on == off and shapes == shapes_off
    assert routes_on["pallas"] == shapes * traced_layers
    assert not routes_on["xla"]
    assert list(routes_on["why"]) == ["forced on by HETU_TPU_PALLAS=1"]
    assert routes_off["xla"] == shapes * traced_layers
    assert not routes_off["pallas"]
