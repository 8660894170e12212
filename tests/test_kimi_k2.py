"""Kimi-K2 (the DeepSeek-V3 block) on the serving path, at a tiny size
that keeps every mechanism: latent attention with a paged latent cache,
a leading dense layer, sigmoid-routed dropless experts held in part with
a shared expert, YaRN.  Seeded random float32 weights; the reference is
`benchmarks/families/kimi_k2.py`'s plain forward, which shares no code
with the program.

Tolerances.  Program and reference are both float32 here, so they differ
by the ORDER of float32 sums only (online softmax over key blocks against
one softmax, a grouped product over sorted rows against a loop over
experts, the absorbed form's reassociated products): logits of O(1)
agree to a few 1e-6; LOGIT_ATOL = 2e-4 leaves two orders of room and is
three orders under what a wrong mask, rotation, expert or scale moves
(O(0.1-1)).
"""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.families import kimi_k2 as fam  # noqa: E402
from hetu_tpu.models import generation as gen  # noqa: E402
from hetu_tpu.models.cache_contract import cache_contract  # noqa: E402
from hetu_tpu.models.kimi_k2 import (KimiK2Config,  # noqa: E402
                                     KimiK2LMHeadModel)
from hetu_tpu.nn import moe  # noqa: E402
from hetu_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from hetu_tpu.serving.engine import ServeConfig, ServingEngine  # noqa: E402
from hetu_tpu.serving.request import Request  # noqa: E402

LOGIT_ATOL = 2e-4
F32 = jnp.float32


def tiny_cfg():
    """The rehearsal's configuration without `router_tie_logit`: the
    reference's plain forward (the near-tie passes have tests of their
    own below)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tiny-kimi-k2.json")) as f:
        cfg = json.load(f)
    del cfg["router_tie_logit"]
    return cfg


def build(**over):
    cfg = dict(tiny_cfg(), **over)
    model = fam.build_model(cfg, cfg["serving"])
    return cfg, model, model.init(jax.random.key(7))


def ref_logits(params, cfg, ids):
    ids = jnp.asarray(ids, jnp.int32)
    return np.asarray(jax.jit(lambda p, i: fam.logits_at(
        p, i, jnp.arange(i.shape[0]), cfg))(params, ids))


# ------------------------------------------------------------------ (a)

@pytest.mark.parametrize("seq", [37, 64])    # one key block; odd, so many
def test_whole_sequence_forward_is_the_reference(seq, rng):
    cfg, model, params = build()
    ids = rng.integers(0, cfg["vocab_size"], size=(2, seq)).astype(np.int32)
    got = np.asarray(jax.jit(model.forward)(params, jnp.asarray(ids)))
    for b in range(2):
        np.testing.assert_allclose(got[b], ref_logits(params, cfg, ids[b]),
                                   atol=LOGIT_ATOL, rtol=0)


def test_tiny_configuration_keeps_every_mechanism():
    cfg, model, _ = build()
    c = model.config
    assert c.first_k_dense_replace == 1 and c.num_moe_layers == 2
    assert (c.n_routed_experts, c.experts_held, c.first_expert) == (16, 4, 4)
    assert c.rope_scaling["factor"] == 4 and c.n_shared_experts == 1
    assert c.latent_dim == 136 and c.latent_stored_dim == 256


# ------------------------------------------------------------------ (b)

def _programs(model, params, prompt, n_decode, *, page=8, chunk=16,
              max_len=64, slots=3, slot=1):
    """Prefill `prompt` in chunks into a dense scratch, write its pages,
    then `n_decode` paged decode steps feeding the reference's own next
    tokens: the logits of every position the programs produce."""
    contract = model.cache_contract()
    L, mp = contract.num_layers, max_len // page
    # (one array of the scratch and of the pool a shape a token stores)
    cache = tuple(jnp.zeros((L, 1, max_len) + stored, F32)
                  for stored in contract.stored_shapes)
    stats = model.zero_stats()
    plen = len(prompt)
    padded = -(-plen // chunk) * chunk
    ids = np.zeros(padded, np.int32)
    ids[:plen] = prompt
    rows = []
    for s in range(0, padded, chunk):
        lg, cache, stats = jax.jit(gen.extend_cache, static_argnums=0)(
            model, params, jnp.asarray(ids[None, s:s + chunk]), cache,
            jnp.int32(s), stats)
        rows.append(np.asarray(lg[0]))
    prefill_logits = np.concatenate(rows)[:plen]
    # pages 3.. of a pool of 1 + slots * mp pages belong to `slot`
    from hetu_tpu.serving.kv_pool import PagePool
    pool = PagePool.for_contract(
        dataclasses.replace(contract, dtype=F32),
        num_pages=slots * mp, page_size=page)
    pages = np.arange(mp, dtype=np.int32) * slots + slot + 1
    tree = pool.write_pages(pool.arrays.tree(), jnp.asarray(pages),
                            *(c[:, 0] for c in cache))
    table = np.zeros((slots, mp), np.int32)
    table[slot] = pages
    return prefill_logits, tree, table, stats


@pytest.mark.parametrize("plen", [5, 16, 23, 40, 17])
def test_chunked_prefill_page_write_and_paged_decode_are_the_reference(
        plen, rng):
    """Prompt lengths straddle a page (8) and a chunk (16)."""
    cfg, model, params = build()
    n_decode = 6
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
    stats = np.asarray(stats)
    stats = dict(zip((name for name, _ in model.STATS), stats))
    steps = stats["serve.moe_layer_steps"]
    assert steps == 2 * (-(-plen // 16) + n_decode)
    assert 0 < stats["serve.moe_local_assignments"] \
        < stats["serve.moe_assignments"]
    assert stats["serve.moe_expert_hits"] <= 4 * steps
    assert stats["serve.moe_extra_row_blocks"] >= 0
    # (a program's stats count the blocks the grouped product walked)
    assert stats["serve.moe_row_blocks"] \
        >= max(stats["serve.moe_extra_row_blocks"], 1)


# (the served streams against the reference, over the XLA attention and
# the kernel: two cases of tests/test_serving.py::
# test_a_family_is_served_by_its_hooks, the one body every family goes
# through)


# ------------------------------------------------------------------ (c)

def test_absorbed_decode_is_expanded_attention(rng):
    """One query over a cache of 29 latents: the absorbed form over the
    pool (what decode runs) against the expanded form over the dense
    cache (what prefill runs).  Same mathematics, reassociated."""
    _, model, params = build()
    attn = model.model.moe_layers.block.attn
    ap = params["model"]["moe_layers"]["layer_0"]["attn"]
    c = model.config
    M, n = 32, 29
    hn = jnp.asarray(rng.standard_normal((1, M, c.hidden_size)), F32)
    rope = model.rope_tables(M)
    pos = jnp.arange(M, dtype=jnp.int32)[None]
    q, (lat,) = attn.project(ap, hn, rope, pos)
    one = jax.tree.map(lambda a: a[:, n - 1:n], q)
    expanded = attn.attend_dense(ap, one, (lat,), jnp.asarray([n - 1]))
    pool = jnp.concatenate([jnp.zeros((1, 8, lat.shape[-1]), F32),
                            lat[0].reshape(M // 8, 8, -1)])
    absorbed = attn.attend_paged(ap, one, (pool,),
                                 jnp.arange(M // 8)[None],
                                 jnp.asarray([n - 1]), 1)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=2e-5, rtol=0)


# ------------------------------------------------------------------ (d)

def test_latent_kernel_in_interpret_mode_is_jnp(rng):
    """Ragged lengths, one slot on the null page (position 0, a table of
    zeros), a slot whose length ends mid-page and one that fills its
    table.  float32, so only the order of the online softmax's sums
    differs: 1e-5."""
    from hetu_tpu.ops.pallas import paged_latent_attention as pla
    S, nq, dk, dv, ps, mp = 4, 4, 256, 128, 8, 5
    P = 1 + S * mp
    pool = jnp.asarray(rng.standard_normal((P, ps, dk)), F32)
    q = jnp.asarray(rng.standard_normal((S, nq, dk)), F32)
    table = np.arange(1, P).reshape(S, mp).astype(np.int32)
    positions = np.asarray([0, 11, 39, 20], np.int32)
    table[0] = 0                                        # the null page
    table[1, 2:] = 0
    table[3, 3:] = 0
    args = (q, pool, jnp.asarray(table), jnp.asarray(positions))
    kw = dict(value_dim=dv, softmax_scale=0.11)
    got = pla.paged_latent_attention(*args, **kw)
    want = pla.paged_latent_attention_xla(*args, **kw)
    assert got.shape == (S, nq, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=0)
    # by hand for one slot: softmax(q.c * scale) c[:, :dv]
    c = np.asarray(pool)[table[1, :2]].reshape(-1, dk)[:12]
    s = np.asarray(q)[1] @ c.T * 0.11
    p = np.exp(s - s.max(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(got)[1],
                               (p / p.sum(-1, keepdims=True)) @ c[:, :dv],
                               atol=1e-5, rtol=0)


#: positions of the walk's slots at pages of 8 and a table of 5: None = an
#: idle slot (position 0, a null table row); mid-page, a page's last
#: position, the first of a page, the edges of blocks of 2, 3 and 4 pages,
#: the table's end
WALK_POSITIONS = {
    "ragged": [None, 11, 15, 16, 23, 31, 39, 3, None],
    "all_idle": [None] * 4,
}
#: pages a block: one, two, the whole table, one that does not divide it
WALK_BLOCKS = (1, 2, 5, 3)


def _poisoned_walk(rng, slots, dtype, *, nq=4, dk=256, ps=8, mp=5):
    """-> (q, clean pool, clean table, poisoned pool, poisoned table,
    positions).  Poisoned: every table entry past a slot's live pages
    names a page of NaN (a valid id), every position past a slot's last,
    in its last page and in the null page, holds NaN: a walk that reads
    one of them into the result gives NaN."""
    S = len(slots)
    P = 2 + S * mp                       # null page, pages, the NaN page
    pool = rng.standard_normal((P, ps, dk)).astype(np.float32)
    table = np.arange(1, P - 1).reshape(S, mp).astype(np.int32)
    bad_pool, bad_table = pool.copy(), table.copy()
    bad_pool[P - 1] = np.nan
    bad_pool[0, 1:] = np.nan
    positions = np.zeros(S, np.int32)
    for s, pos in enumerate(slots):
        if pos is None:
            table[s], bad_table[s] = 0, 0
            continue
        positions[s] = pos
        table[s, pos // ps + 1:] = 0
        bad_table[s, pos // ps + 1:] = P - 1
        bad_pool[table[s, pos // ps], pos % ps + 1:] = np.nan
    q = jnp.asarray(rng.standard_normal((S, nq, dk)), dtype)
    cast = lambda a: jnp.asarray(a).astype(dtype)
    return (q, cast(pool), jnp.asarray(table), cast(bad_pool),
            jnp.asarray(bad_table), jnp.asarray(positions))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("ppb", WALK_BLOCKS)
@pytest.mark.parametrize("slots", WALK_POSITIONS)
def test_latent_walk_reads_a_slots_live_pages_and_nothing_else(
        slots, ppb, dtype, rng, monkeypatch):
    """The walk at the latent shape, a block of `ppb` pages: slot 0 idle,
    the last slot idle, every slot idle; lengths that end mid-page, at a
    page's last position, at a block's edge and at the table's end.  The
    kernel sees the POISONED pool and table, the composition the clean
    ones: float32 differs by the order of the softmax's sums (1e-5), a
    bfloat16 pool by the products' and the output's 2**-8 (2e-2, the K/V
    walk's)."""
    from hetu_tpu.ops.pallas import paged_latent_attention as pla
    ps, mp, dv = 8, 5, 128
    monkeypatch.setattr(pla, "_BLOCK_TOKENS", ppb * ps)
    q, pool, table, bad_pool, bad_table, positions = _poisoned_walk(
        rng, WALK_POSITIONS[slots], jnp.dtype(dtype), ps=ps, mp=mp)
    assert pla.pages_per_block(q.shape[1], ps, q.shape[2],
                               pool.dtype.itemsize, mp) == ppb
    kw = dict(value_dim=dv, softmax_scale=0.11)
    got = pla.paged_latent_attention(q, bad_pool, bad_table, positions, **kw)
    want = pla.paged_latent_attention_xla(
        q.astype(F32), pool.astype(F32), table, positions, **kw)
    assert got.dtype == q.dtype and got.shape == (len(positions), 4, dv)
    np.testing.assert_allclose(
        np.asarray(got.astype(F32)), np.asarray(want),
        atol=1e-5 if dtype == "float32" else 2e-2, rtol=0)


#: the three cells' decode launches: (query rows, table width)
LATENT_CELLS = {"longcat": (64, 10), "kimi": (64, 16), "ling": (32, 128)}


@pytest.mark.parametrize("cell", LATENT_CELLS)
def test_latent_block_rule_fits_vmem_and_the_table(cell):
    """The block the rule returns for a cell's shape (pages of 256
    tokens of 640 bfloat16 lanes) holds its two buffers and a block's
    float32 scores within `_VMEM_BUDGET`, is never wider than the table
    and never under a page; a table narrower than the block bounds it."""
    from hetu_tpu.ops.pallas import paged_latent_attention as pla
    nq, mp = LATENT_CELLS[cell]
    ppb = pla.pages_per_block(nq, 256, 640, 2, mp)
    assert 1 <= ppb <= mp
    assert ppb * 256 * pla._token_vmem_bytes(nq, 640, 2) <= pla._VMEM_BUDGET
    assert pla.pages_per_block(nq, 256, 640, 2, 1) == 1
    # float32 pages under a table of any width: the budget still holds
    wide = pla.pages_per_block(nq, 256, 640, 4, 1 << 20)
    assert 1 <= wide <= ppb and wide * 256 * pla._token_vmem_bytes(
        nq, 640, 4) <= pla._VMEM_BUDGET


def test_latent_route_reason_carries_the_block(monkeypatch):
    """`kernel_routes["paged_latent"]`: the dispatcher's reason, then the
    block the wrapper's rule chose, once a traced layer; a call past the
    dispatcher records nothing."""
    from hetu_tpu.ops import pallas as plz
    from hetu_tpu.ops.pallas import paged_latent_attention as pla
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    shapes = ((2, 4, 256), (7, 8, 256), (2, 3), (2,))
    q, pool = jnp.zeros(shapes[0], F32), jnp.zeros(shapes[1], F32)
    table, pos = jnp.zeros(shapes[2], jnp.int32), jnp.zeros(2, jnp.int32)
    with plz.record_routes() as routes:
        for _ in range(2):
            assert plz.resolve_route("paged_latent", pla.check_shapes,
                                     *shapes, value_dim=128)
            pla.paged_latent_attention(q, pool, table, pos, value_dim=128)
        pla.paged_latent_attention(q, pool, table, pos, value_dim=128)
    assert routes["paged_latent"] == {"pallas": 2, "xla": 0, "why": {
        "forced on by HETU_TPU_PALLAS=1, pages_per_block=3": 2}}


def test_latent_kernel_gate_names_what_it_refuses():
    from hetu_tpu.ops.pallas import paged_latent_attention as pla
    ok = ((4, 64, 640), (9, 64, 640), (4, 3), (4,))
    assert pla.compatible(*ok, value_dim=512)
    for bad, kw in (((4, 64, 576), (9, 64, 576), (4, 3), (4,)),
                    dict(value_dim=512)), (ok, dict(value_dim=500)), \
            (((4, 64, 640), (9, 12, 640), (4, 3), (4,)),
             dict(value_dim=512)):
        assert not pla.compatible(*bad, **kw)
        with pytest.raises(ValueError):
            pla.check_shapes(*bad, **kw)


# ------------------------------------------------------- (e), (f), (g)

def _layer(first, held, key=3, **kw):
    layer = moe.SharedRoutedExperts(
        32, 16, n_routed_experts=16, experts_held=held, first_expert=first,
        top_k=4, n_shared_experts=1, norm_topk_prob=True,
        routed_scaling_factor=2.827, param_dtype=F32, bias_range=0.2, **kw)
    return layer, layer.init(jax.random.key(key))


def _share(params, first, held):
    cut = {k: params[k][first:first + held]
           for k in ("w_gate_up", "w_down")}
    return dict(params, **cut)


CFG_LAYER = {"num_experts_per_tok": 4, "norm_topk_prob": True,
             "routed_scaling_factor": 2.827}


def test_the_four_shares_sum_to_the_uncut_layer(rng):
    """Guide s4's share test: shares of 4 experts each, the shared expert
    counted once, add up to what the uncut reference gives for the whole
    layer."""
    whole, params = _layer(0, 16)
    x = jnp.asarray(rng.standard_normal((2, 9, 32)), F32)
    with jax.default_matmul_precision("highest"):
        want = fam.experts(x.reshape(18, 32), params, CFG_LAYER)
        shared = fam._swiglu(x.reshape(18, 32), params["shared_gate_up"],
                             params["shared_down"])
    total = 0.0
    for first in (0, 4, 8, 12):
        part, _ = _layer(first, 4)[0](_share(params, first, 4), x)
        total = total + part.reshape(18, 32) - shared   # its routed part
        # and each share alone is the reference GIVEN that share
        with jax.default_matmul_precision("highest"):
            alone = fam.experts(x.reshape(18, 32), _share(params, first, 4),
                                dict(CFG_LAYER, first_expert=first))
        np.testing.assert_allclose(np.asarray(part.reshape(18, 32)),
                                   np.asarray(alone), atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=5e-5, rtol=0)
    got_whole, stats = whole(params, x)
    np.testing.assert_allclose(np.asarray(got_whole.reshape(18, 32)),
                               np.asarray(want), atol=5e-5, rtol=0)
    assert list(np.asarray(stats)[:2]) == [72, 72]


def test_dropless_under_imbalance_and_shared_alone_elsewhere(rng):
    """A bias sends EVERY token to experts 0-3.  The share that holds
    them computes all 4 x T pairs, none lost (a capacity factor of 1.25
    over 16 experts would keep 2 of the 24 tokens an expert); the share
    that holds 8-11 has no pair and yields the shared expert alone."""
    _, params = _layer(0, 16)
    params["e_score_correction_bias"] = jnp.where(
        jnp.arange(16) < 4, 100.0, 0.0)
    x = jnp.asarray(rng.standard_normal((1, 24, 32)), F32)
    xt = x.reshape(24, 32)
    with jax.default_matmul_precision("highest"):
        want = fam.experts(xt, params, CFG_LAYER)
        shared = fam._swiglu(xt, params["shared_gate_up"],
                             params["shared_down"])
    here, stats = _layer(0, 4)[0](_share(params, 0, 4), x)
    np.testing.assert_allclose(np.asarray(here[0]), np.asarray(want),
                               atol=5e-5, rtol=0)
    # 96 pairs of 96 here: two blocks of 64 rows (1.5 x 1/4 of 96)
    assert list(np.asarray(stats)) == [96, 96, 4, 1, 2, 24]
    gone, stats = _layer(8, 4)[0](_share(params, 8, 4), x)
    np.testing.assert_allclose(np.asarray(gone[0]), np.asarray(shared),
                               atol=1e-6, rtol=0)
    assert list(np.asarray(stats)) == [96, 0, 0, 0, 0, 0]


def test_one_row_block_and_several_agree_with_the_reference(rng):
    """With 1/4 of the pairs expected here the grouped product walks the
    pairs on held experts in blocks of 1.5 x 1/4 of the rows (128 of
    256): one block where they fit, two where the bias above sends
    every pair here, and the blocks beyond the first are counted."""
    layer, params = _layer(0, 4)
    x = jnp.asarray(rng.standard_normal((1, 64, 32)), F32)
    for extra, bias in ((0, params["e_score_correction_bias"]),
                        (1, jnp.where(jnp.arange(16) < 4, 100.0, 0.0))):
        p = dict(params, e_score_correction_bias=bias)
        with jax.default_matmul_precision("highest"):
            want = fam.experts(x[0], p, CFG_LAYER)
        got, stats = layer(p, x)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                   atol=5e-5, rtol=0)
        assert int(stats[3]) == extra
    assert int(stats[1]) == 256 == 2 * 128


def _walk_beside_an_expert_loop(rng, idx, held, share, atol=5e-5):
    """`dropless_local_experts` for the routing `idx` [T, k] over `held`
    experts of 16 -> 8 -> 16 in float32, held to a plain loop over the
    experts: (counts, extra, blocks)."""
    T, k = idx.shape
    h, inter = 16, 8
    idx = jnp.asarray(idx, jnp.int32)
    w = jnp.asarray(rng.uniform(0.5, 1.0, size=(T, k)), F32)
    x = jnp.asarray(rng.standard_normal((T, h)), F32)
    wgu = jnp.asarray(rng.standard_normal((held, h, 2 * inter)) * 0.3, F32)
    wd = jnp.asarray(rng.standard_normal((held, inter, h)) * 0.3, F32)
    with jax.default_matmul_precision("highest"):
        y, counts, extra, blocks = jax.jit(
            lambda *a: moe.dropless_local_experts(
                *a, first_expert=0, share=share))(x, idx, w, wgu, wd)
        want = sum(jnp.sum(jnp.where(idx == e, w, 0.0), -1)[:, None]
                   * fam._swiglu(x, wgu[e], wd[e]) for e in range(held))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=atol,
                               rtol=0)
    return list(np.asarray(counts)), int(extra), int(blocks)


def _blocks_walked(loads, rows):
    """The walk of `dropless_local_experts` over experts with `loads`
    pairs in blocks of `rows`: a block ends where the last whole expert
    inside it ends, or, where none ends inside, after `rows` rows."""
    ends = np.cumsum(loads)
    lo = blocks = 0
    while lo < ends[-1]:
        whole = max([e for e in ends if e <= lo + rows], default=0)
        lo = whole if whole > lo else min(lo + rows, ends[-1])
        blocks += 1
    return blocks


@pytest.mark.parametrize("loads", [(70, 0, 20), (0, 0, 0), (1, 64, 35)])
def test_row_blocks_cut_an_experts_pairs_at_any_row(loads, rng):
    """100 pairs in blocks of 64 rows (the last block padded): an
    expert's pairs may straddle a block's edge, an expert may have none,
    and the pairs here may be none at all; every pair on a held expert
    is computed once."""
    T, k = 50, 2
    idx = np.full(T * k, 7, np.int32)                   # 7: held elsewhere
    idx[rng.permutation(T * k)[: sum(loads)]] = np.repeat(
        np.arange(3), loads)
    counts, extra, blocks = _walk_beside_an_expert_loop(
        rng, idx.reshape(T, k), 3, 0.1, atol=2e-5)
    assert tuple(counts) == loads
    assert extra == max(-(-sum(loads) // 64) - 1, 0)
    # (70, 0, 20): 64 of expert 0, then its last 6 and expert 2;
    # (1, 64, 35): a block an expert, each whole
    assert blocks == _blocks_walked(loads, 64) \
        == {90: 2, 0: 0, 100: 3}[sum(loads)]


def _round_robin(T, k, experts):
    """Token t's k experts are k t .. k t + k - 1 (mod `experts`): every
    expert gets T k / experts pairs exactly."""
    return (k * np.arange(T)[:, None] + np.arange(k)) % experts


def _skewed(T, k, rng):
    """Every token's first expert is 3 (T pairs on one expert: more than
    a block's rows); its others are drawn from all 512."""
    rest = np.stack([rng.permutation(np.r_[0:3, 4:512])[:k - 1]
                     for _ in range(T)])
    return np.concatenate([np.full((T, 1), 3), rest], axis=1)


# a cell's block regime: (T, k, held, share, its routing)
ROW_BLOCK_REGIMES = {
    # LFM2's decode pass: 512 pairs, every expert held, 16 rows each
    "all held, 16 rows an expert": (
        128, 4, 32, 1.0, lambda rng: _round_robin(128, 4, 32)),
    # LFM2's chunk: every expert's rows just under a block of 192-256
    "all held, 190 rows an expert": (
        380, 4, 8, 1.0, lambda rng: _round_robin(380, 4, 8)),
    # Ling: an eighth of the router's width, one expert over a block
    "64 of 512 held, one expert over a block": (
        320, 8, 64, 64 / 512, lambda rng: _skewed(320, 8, rng)),
    # Kimi: 1/32 of the pairs expected here, one block of 64 rows
    "12 of 384 held": (
        64, 8, 12, 12 / 384,
        lambda rng: np.stack([rng.permutation(384)[:8]
                              for _ in range(64)])),
    "no pair here": (
        48, 4, 4, 4 / 16, lambda rng: 4 + _round_robin(48, 4, 12)),
}


@pytest.mark.parametrize("regime", list(ROW_BLOCK_REGIMES))
def test_row_blocks_of_a_cells_regime_agree_with_an_expert_loop(regime,
                                                                rng):
    """`dropless_local_experts` at the block regimes the cells have,
    against a plain loop over the held experts: every pair on a held
    expert is computed once however the blocks cut them, `counts` are
    the loads, and the blocks walked are the pairs here over a block's
    rows."""
    T, k, held, share, routing = ROW_BLOCK_REGIMES[regime]
    idx = routing(rng)
    counts, extra, blocks = _walk_beside_an_expert_loop(rng, idx, held,
                                                        share)
    loads = np.bincount(idx.ravel(), minlength=512)[:held]
    assert counts == list(loads)
    rows, expected = moe.row_block(T * k, share, held)
    assert rows <= min(moe.RIDGE_ROWS, expected)
    assert blocks == _blocks_walked(loads, rows)
    assert extra == max(-(-loads.sum() // expected) - 1, 0)
    if regime.endswith("over a block"):
        assert loads.max() > rows


@pytest.mark.parametrize("cell, T, k, held, outputs, rows, expected", [
    # at or under the ridge: the block of PR 27, row for row
    ("kimi-k2.6 chunk", 512, 8, 12, 384, 192, 192),
    ("kimi-k2.6 decode", 64, 8, 12, 384, 64, 64),
    ("longcat-flash chunk", 512, 12, 16, 768, 192, 192),
    ("longcat-flash decode", 96, 12, 16, 768, 64, 64),
    ("xing4.0 decode", 16, 4, 64, 64, 64, 64),
    ("ling-3.0 decode", 32, 8, 64, 512, 64, 64),
    # over it: a tile for one expert's expected rows and a quarter more
    ("lfm2 decode", 128, 4, 32, 32, 128, 512),          # 16 an expert
    ("lfm2 chunk", 1536, 4, 32, 32, 256, 6144),         # 192 an expert
    ("xing4.0 chunk", 1024, 4, 64, 64, 128, 4096),      # 64 an expert
    ("ling-3.0 chunk", 2048, 8, 64, 512, 128, 3072),
    ("mimo-v2 chunk", 1024, 8, 16, 256, 128, 768),
    ("trinity-mini chunk", 512, 8, 16, 128, 128, 768),
    ("deepseek-v3.2 chunk", 1024, 8, 8, 256, 128, 384),
    ("an expert over the ridge", 4096, 2, 8, 8, 256, 8192),
])
def test_the_row_block_of_a_cell(cell, T, k, held, outputs, rows, expected):
    """The block is one and a half times the expected pairs, rounded up
    to 64 (the rule of PR 27), where that is a tile the chip can pay
    for; over the ridge it is a tile of 128 or 256 rows."""
    assert moe.row_block(T * k, held / outputs, held) == (rows, expected)
    assert (rows == expected) == (expected <= moe.RIDGE_ROWS)


@pytest.mark.parametrize("T, k, share, expected, rows, over", [
    (64, 4, 0.25, 128, 128, 0),     # a block under the ridge: as it was
    (64, 4, 0.25, 128, 128, 1),
    (256, 4, 0.25, 384, 128, 0),    # over it: tiles of 128
    (256, 4, 0.25, 384, 128, 1),
    (96, 4, 1.0, 384, 128, 0),      # every pair here: they always fit
])
def test_extra_row_blocks_count_in_the_expected_rows_whatever_the_block(
        T, k, share, expected, rows, over, rng):
    """`extra` is counted in blocks of 1.5 x the expected rows, as it was
    before the walk's block had a cap: 0 where the pairs here fill them,
    1 where there is one pair more; the walk takes its own blocks, which
    the last result counts."""
    held = 4
    assert moe.row_block(T * k, share, held) == (rows, expected)
    idx = np.full(T * k, 9, np.int32)                   # 9: held elsewhere
    loads = np.bincount(rng.integers(0, held, expected + over),
                        minlength=held)
    idx[rng.permutation(T * k)[: expected + over]] = np.repeat(
        np.arange(held), loads)
    counts, extra, blocks = _walk_beside_an_expert_loop(
        rng, idx.reshape(T, k), held, share)
    assert counts == list(loads)
    assert extra == over
    assert blocks == _blocks_walked(loads, rows)


def test_the_tools_sweep_walks_the_rules_block_and_forced_ones():
    """`tools_bench_kernels.py --grouped-product` (the sweep the comment
    above `moe.RIDGE_ROWS` cites) times the walk with the rule's block
    and with others forced, and leaves the rule as it found it."""
    from tools_bench_kernels import grouped_product_sweep
    rule = moe.row_block
    recs = list(grouped_product_sweep(
        blocks=(64, 128), reps=1, draws=2,
        shapes=(("all held", 64, 4, 8, 8, 32, 16),
                ("cut", 256, 4, 32, 8, 32, 16))))
    assert moe.row_block is rule
    assert [(r["shape"], r["rows"], r["taken"]) for r in recs] == [
        ("all held", 64, False), ("all held", 128, False),
        ("all held", 256, True), ("cut", 64, False), ("cut", 128, True),
        ("cut", 384, False)]
    by = {(r["shape"], r["rows"]): r["blocks"] for r in recs}
    assert by["all held", 256] == 1 and by["cut", 384] == 1
    assert by["all held", 64] >= 4 and by["cut", 64] > by["cut", 128] >= 2


def test_gate_chooses_by_s_plus_b_and_weights_by_s():
    """Two experts a token of four.  Scores s = sigmoid([2, 1, 0, -1]) =
    [.881, .731, .5, .269]; a bias of +1 on expert 3 changes the choice
    from {0, 1} to {3, 0}; the weights are s (without the bias) at the
    chosen, over their sum, times 2.827."""
    x = jnp.ones((1, 1), F32)
    w_gate = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    s = 1 / (1 + np.exp(-np.asarray([2.0, 1.0, 0.0, -1.0])))
    kw = dict(top_k=2, norm_topk_prob=True, routed_scaling_factor=2.827)
    idx, w = moe.noaux_tc_gate(x, w_gate, jnp.zeros(4), **kw)
    assert list(np.asarray(idx[0])) == [0, 1]
    np.testing.assert_allclose(np.asarray(w[0]),
                               2.827 * s[:2] / s[:2].sum(), rtol=1e-6)
    idx, w = moe.noaux_tc_gate(x, w_gate, jnp.asarray([0, 0, 0, 1.0]), **kw)
    assert list(np.asarray(idx[0])) == [3, 0]
    np.testing.assert_allclose(
        np.asarray(w[0]), 2.827 * s[[3, 0]] / (s[3] + s[0]), rtol=1e-6)
    _, w = moe.noaux_tc_gate(x, w_gate, jnp.zeros(4), top_k=2,
                             norm_topk_prob=False, routed_scaling_factor=1.0)
    np.testing.assert_allclose(np.asarray(w[0]), s[:2], rtol=1e-6)
    # the reference's gate is the same function of the same numbers
    ids, wr = fam.gate(x, {"w_gate": w_gate, "e_score_correction_bias":
                           jnp.asarray([0, 0, 0, 1.0])},
                       {"num_experts_per_tok": 2, "norm_topk_prob": True,
                        "routed_scaling_factor": 2.827})
    assert list(np.asarray(ids[0])) == [3, 0]
    np.testing.assert_allclose(np.asarray(wr), np.asarray(w * 0 + wr))


# ------------------------------------------- the reference's near ties

TIE_GATE = {"num_experts_per_tok": 2, "norm_topk_prob": True,
            "routed_scaling_factor": 2.827, "first_expert": 1}


def _tie_params(logits):
    """A router whose logits ARE `logits` (x = 1) over 5 experts, of which
    experts 1 and 2 are held (the weights' leading size)."""
    return {"w_gate": jnp.asarray([logits], F32),
            "e_score_correction_bias": jnp.zeros(len(logits)),
            "w_gate_up": jnp.zeros((2, 1, 2))}


@pytest.mark.parametrize("logits,margin,plain,tilted,why", [
    # held expert 1 is chosen, 0.03 above the best not chosen (expert 3)
    ([2.0, 1.0, -3.0, 0.97, -1.0], 0.03, [0, 1], [0, 3], "leaves"),
    # held expert 2 is not chosen, 0.04 under the worst chosen (expert 3)
    ([2.0, -3.0, 0.96, 1.0, -1.0], 0.04, [0, 3], [0, 2], "enters"),
    # the nearest held expert is 0.5 from the edge: over the limit
    ([2.0, -3.0, 0.5, 1.0, -1.0], 0.5, [0, 3], [0, 3], "stays"),
])
def test_reference_gate_moves_the_nearest_held_expert_across_the_edge(
        logits, margin, plain, tilted, why):
    """`router_tie_logit` 0.1: the held expert nearest the edge of the
    chosen set changes sides if the router logit that would move it is
    under 0.1 away (the sigmoid's slope turns the distance in s + b into
    one in the logit: first order, hence 10%), one expert a token; the
    weights are the published rule's on the new choice; not tilted, or
    over the limit, the choice is the plain gate's."""
    x = jnp.ones((1, 1), F32)
    cfg = dict(TIE_GATE, router_tie_logit=0.1)
    mp = _tie_params(logits)
    idx0, w0 = fam.gate(x, mp, cfg)
    assert sorted(np.asarray(idx0[0])) == plain
    idx, w, moved, m = fam.gate(x, mp, cfg, jnp.bool_(False))
    assert sorted(np.asarray(idx[0])) == plain and not bool(moved[0])
    np.testing.assert_allclose(np.asarray(w), np.asarray(w0))
    assert float(m[0]) == pytest.approx(margin, rel=0.1)
    idx, w, moved, _ = fam.gate(x, mp, cfg, jnp.bool_(True))
    assert sorted(np.asarray(idx[0])) == tilted, why
    assert bool(moved[0]) == (tilted != plain)
    s = 1 / (1 + np.exp(-np.asarray(logits)))
    chosen = np.asarray(idx[0])
    np.testing.assert_allclose(np.asarray(w[0]),
                               2.827 * s[chosen] / s[chosen].sum(), rtol=1e-6)


def test_reference_holds_a_token_to_the_choice_a_near_tie_allows(rng):
    """The comparison (`benchmarks/reference.check_stream`) reads a
    served token's logit against the row's largest.  A token that is
    the argmax under the OTHER choice of a near tie stands at the row's
    largest logit under `router_tie_logit`; rows whose token has no near
    tie are the plain forward's to the bit; no row's largest logit
    changes; and a limit of 0 (or no key) is the plain forward."""
    cfg, _, params = build()
    ids = jnp.asarray(rng.integers(0, cfg["vocab_size"], size=96), jnp.int32)
    rows = jnp.arange(96)
    plain = ref_logits(params, cfg, ids)
    assert np.array_equal(
        plain, ref_logits(params, dict(cfg, router_tie_logit=0.0), ids))
    tie = dict(cfg, router_tie_logit=0.02)
    lg, moved, margins = map(np.asarray, jax.jit(
        lambda p, i: fam.logits_by_pass(p, i, rows, tie))(params, ids))
    got = ref_logits(params, tie, ids)
    assert lg.shape[0] == 2 + 2 and np.array_equal(lg[0], plain)
    assert not moved[0].any()
    near = margins < 0.02                        # [layers, rows]
    assert 0 < near.sum() < near.size            # some, not all
    for layer in range(2):     # a layer's pass moves its near ties alone
        assert np.array_equal(moved[1 + layer], near[layer])
    touched = moved.any(0)
    assert np.array_equal(got[~touched], plain[~touched])
    np.testing.assert_allclose(got.max(-1), plain.max(-1), atol=1e-5)
    assert (got >= plain).all()
    # the token the other choice would serve: its standing is the top
    row = int(np.argmax(moved[1]))
    other = int(lg[1, row].argmax())
    assert got[row, other] == pytest.approx(plain[row].max(), abs=1e-5)
    assert got[row, other] >= plain[row, other]
    # and the row's argmax stays the plain forward's own
    assert np.array_equal(got.argmax(-1), plain.argmax(-1))


def test_reference_rows_alone_are_the_whole_sequence_with_one_token_changed(
        rng):
    """`rows_tilted` runs the expert layers again for the rows alone over
    what the plain pass holds of every other token.  Nothing tilted, it
    gives the plain pass's rows; one row with layer 0's near tie decided
    the other way is the WHOLE forward with that one token's choice
    changed there (a loop over the layers with a one-token `tilt`)."""
    cfg, _, params = build()
    cfg = dict(cfg, router_tie_logit=1e9)        # every margin is near
    ids = jnp.asarray(rng.integers(0, cfg["vocab_size"], size=48), jnp.int32)
    rows = jnp.asarray([5, 17, 40, 40])          # the pad repeats a row
    with jax.default_matmul_precision("highest"):
        entering = []
        plain = fam.hidden_states(params, ids, cfg, entering)
        x, moved, _ = fam.rows_tilted(params, cfg, entering, rows,
                                      jnp.zeros(2, bool))
        assert not np.asarray(moved).any()
        np.testing.assert_allclose(np.asarray(x), np.asarray(plain[rows]),
                                   atol=2e-5, rtol=0)
        one = jnp.asarray([17])
        x, moved, _ = fam.rows_tilted(params, cfg, entering, one,
                                      jnp.asarray([True, False]))
        assert bool(moved[0])
        # the whole sequence, token 17's choice changed in expert layer 0
        m, eps = params["model"], cfg["rms_norm_eps"]
        w = fam._block(entering[0] * 0 + m["embed"]["weight"][ids],
                       m["dense_layers"]["layer_0"], cfg, False)
        for i, tilt in enumerate([jnp.arange(48) == 17, None]):
            lp = m["moe_layers"][f"layer_{i}"]
            w = w + fam._mla(fam._rms_norm(
                w, lp["input_norm"]["weight"], eps), lp["attn"], cfg)
            h = fam._rms_norm(w, lp["post_norm"]["weight"], eps)
            w = w + (fam.experts(h, lp["mlp"], cfg) if tilt is None
                     else fam.experts(h, lp["mlp"], cfg, tilt)[0])
        want = fam._rms_norm(w, m["final_norm"]["weight"], eps)[17]
        np.testing.assert_allclose(np.asarray(x[0]), np.asarray(want),
                                   atol=2e-5, rtol=0)
        assert float(jnp.abs(want - plain[17]).max()) > 1e-3


# ------------------------------------------------------------------ (h)

KIMI_YARN = dict(factor=64.0, original_max_position_embeddings=4096,
                 beta_fast=32.0, beta_slow=1.0)


def test_yarn_frequencies_and_scale_against_the_closed_form():
    """Kimi-K2's rotary: 32 pairs of a 64-wide rope head, theta 50000.
    The correction dimensions of 32 and 1 rotations over 4,096 positions
    are d ln(4096 / (2 pi r)) / (2 ln theta) = 8.93 and 19.17: pairs 0-8
    keep theta^(-2i/64), pairs 20-31 are divided by 64, pairs between
    blend linearly by (i - 8) / 12."""
    from hetu_tpu import ops
    inv = np.asarray(ops.yarn_inv_freq(64, 50000.0, **KIMI_YARN))
    base = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:9], base[:9], rtol=1e-6)
    np.testing.assert_allclose(inv[20:], base[20:] / 64, rtol=1e-6)
    i = np.arange(9, 20)
    ramp = (i - 8) / 12
    np.testing.assert_allclose(inv[9:20],
                               base[9:20] * (1 - ramp + ramp / 64), rtol=1e-5)
    np.testing.assert_allclose(
        inv, np.asarray(fam.yarn_inv_freq(64, 50000.0, dict(
            KIMI_YARN, mscale=1, mscale_all_dim=1))), rtol=1e-6)
    m = 0.1 * np.log(64) + 1
    assert ops.yarn_mscale(64, 1.0) == pytest.approx(m) == pytest.approx(
        1.4159, abs=1e-4)
    c = KimiK2Config(rope_scaling=dict(KIMI_YARN, type="yarn", mscale=1.0,
                                       mscale_all_dim=1.0))
    assert c.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    assert fam.softmax_scale({
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rope_scaling": dict(KIMI_YARN, mscale_all_dim=1.0)}) \
        == pytest.approx(c.softmax_scale)


def test_yarn_is_not_the_identity_at_short_positions():
    """Unlike dynamic NTK scaling, YaRN rotates pair 31 at position 100
    by 100 theta^(-62/64) / 64, not by 100 theta^(-62/64): the table is
    built, not assumed away below 4,096; and to `max_len` rows."""
    from hetu_tpu import ops
    cos, sin = ops.build_yarn_rope_cache(256, 64, 50000.0, mscale=1.0,
                                         mscale_all_dim=1.0, **KIMI_YARN)
    cos0, _ = ops.build_rope_cache(256, 64, 50000.0)
    assert cos.shape == (256, 32)
    # the unscaled pairs: the two tables raise theta to the power two
    # ways in float32 (angles up to 255 rad: 1e-4)
    np.testing.assert_allclose(np.asarray(cos[:, :9]),
                               np.asarray(cos0[:, :9]), atol=1e-4)
    want = np.cos(100 * 50000.0 ** (-62 / 64) / 64)
    assert float(cos[100, 31]) == pytest.approx(want, abs=1e-6)
    assert abs(float(cos[100, 20]) - float(cos0[100, 20])) > 1e-3
    # m(mscale) / m(mscale_all_dim): 1 when equal, m when the latter is 0
    cos_m, _ = ops.build_yarn_rope_cache(4, 64, 50000.0, mscale=1.0,
                                         mscale_all_dim=0.0, **KIMI_YARN)
    assert float(cos_m[0, 0]) == pytest.approx(0.1 * np.log(64) + 1)


# ------------------------------------------------------------------ (i)

def _llama_engine(**serve):
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
    model = LlamaLMHeadModel(LlamaConfig.tiny(param_dtype=F32,
                                              compute_dtype=F32))
    params = model.init(jax.random.key(0))
    return ServingEngine(model, params, ServeConfig(
        num_slots=4, page_size=8, max_len=64, prefill_chunk=16,
        num_pages=16, **serve), registry=MetricsRegistry())


def _gpt_engine():
    from hetu_tpu.models.gpt.model import GPTConfig, GPTLMHeadModel
    model = GPTLMHeadModel(GPTConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=128,
        param_dtype=F32, compute_dtype=F32))
    params = model.init(jax.random.key(0))
    return ServingEngine(model, params, ServeConfig(
        num_slots=4, page_size=8, max_len=64, prefill_chunk=16,
        num_pages=16), registry=MetricsRegistry())


@pytest.mark.parametrize("make,n_kv,hd", [(_llama_engine, 2, 16),
                                          (_gpt_engine, 4, 16)])
def test_kv_engines_pools_and_programs_are_as_they_were(make, n_kv, hd):
    """The K/V families: two pool arrays of [L, pages + 1, page, n_kv,
    head_dim], the same scratch, programs of the same arguments."""
    eng = make()
    tree = eng.pool.arrays.tree()
    assert [a.shape for a in tree] == [(2, 17, 8, n_kv, hd)] * 2
    assert [a.shape for a in eng._fresh_scratch()] == [
        (2, 1, 64, n_kv, hd)] * 2
    assert eng.cache.kind == "kv" and eng.model.STATS == ()
    assert eng._stats_acc is None
    assert len(eng._dummy_args("decode")) == 6      # + the decode before
    assert len(eng._dummy_args("prefill_chunk")) == 5   # + the token's row
    assert len(eng._dummy_args("write_pages")) == 4
    low = eng.lower_programs()
    # (the chunk program at each launch shape: one to four chunks of 16)
    assert sorted(low) == ["decode", "prefill_chunk", "prefill_chunk_x2",
                           "prefill_chunk_x3", "prefill_chunk_x4",
                           "write_pages"]
    assert [low[n].args_info[0][1].shape for n in sorted(low)[1:5]] == [
        (1, 16 * k) for k in (1, 2, 3, 4)]
    # 2 layers x (K + V) x n_kv x hd float32 values a token
    assert eng._registry.snapshot()["gauges"] and any(
        g["name"] == "serve.kv_bytes_per_token"
        and g["value"] == 2 * 2 * n_kv * hd * 4
        for g in eng._registry.snapshot()["gauges"])


def test_int8_pages_of_a_kv_engine_are_as_they_were():
    eng = _llama_engine(kv_quant="int8")
    assert [a.shape for a in eng.pool.arrays.tree()] == [
        (2, 17, 8, 2, 16)] * 2 + [(2, 17, 8, 2)] * 2


def test_cache_bytes_come_from_the_contract():
    """One place says what a token stores: 1,152 B a layer for Kimi-K2's
    latent in bf16 (576 values; the 640 lanes it is stored in are not a
    token's bytes), 4,096 B a layer for InternLM2's 8 x 128 K and V."""
    from hetu_tpu.models.cache_contract import kv_contract
    from hetu_tpu.serving.costs import CostModel
    from hetu_tpu.serving.kv_pool import (contract_bytes_per_token,
                                          kv_bytes_per_token)
    kimi = KimiK2LMHeadModel(KimiK2Config(num_hidden_layers=6,
                                          experts_held=12))
    contract = cache_contract(kimi)
    assert contract.token_shapes == ((576,),)
    assert contract.stored_shapes == ((640,),)
    assert contract_bytes_per_token(contract, "bf16") == 6 * 1152
    cm = CostModel.from_model(kimi, num_params=4.17e9, page_size=64,
                              kv_mode="bf16")
    assert cm.kv_bytes_per_token == 6 * 1152
    intern = kv_contract(24, 8, 128)
    assert contract_bytes_per_token(intern, "bf16") == 24 * 4096 \
        == kv_bytes_per_token(24, 8, 128, "bf16")
    assert contract_bytes_per_token(intern, "int8") \
        == kv_bytes_per_token(24, 8, 128, "int8")


@pytest.mark.parametrize("serve,names", [
    (dict(spec_decode="ngram"), "speculative decoding"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(kv_quant="int8"), "int8 / int4 pages"),
    (dict(moe_dispatch="int8"), "resident quantized experts"),
])
def test_what_is_not_built_for_a_latent_cache_is_refused_by_name(serve,
                                                                 names):
    _, model, params = build()
    with pytest.raises(NotImplementedError, match=names):
        ServingEngine(model, params, ServeConfig(
            num_slots=2, page_size=8, max_len=64, prefill_chunk=16,
            **serve), registry=MetricsRegistry())


def test_disaggregated_prefill_refuses_a_latent_cache():
    from hetu_tpu.serving.disagg import PrefillWorker
    _, model, params = build()
    with pytest.raises(NotImplementedError, match="disagg"):
        PrefillWorker(model, params, prefill_chunk=16, max_len=64)


# -------------------------------------------------- scopes and counts

def test_new_scope_names_leave_the_old_programs_groups_alone():
    """`SCOPE_MAP_GROUPS` grew; a program without the new scopes gets
    the groups it got (llama's decode, chunk and write programs, and one
    train step's paths), and Kimi-K2's decode program gets the new ones."""
    from hetu_tpu.obs import hlo_profile as hp
    from hetu_tpu.utils.profiling import PHASES
    old = (*PHASES, "kv_write", "loss")
    new = (*PHASES, *hp.SCOPE_MAP_GROUPS)
    seen = set()
    for low in _llama_engine().lower_programs().values():
        for line in low.compile().as_text().splitlines():
            m = hp.OP_NAME_PAT.search(line)
            if m:
                seen.add(m.group(1))
    seen |= {"jit(train_step)/transpose(jvp(layer))/while/body/attn/"
             "pallas_flash_attention/pallas_call",
             "jit(train_step)/jvp(loss)/reduce_sum", "optimizer/mul"}
    assert len(seen) > 50
    assert all(hp.group_of(p, old) == hp.group_of(p, new) for p in seen)
    _, model, params = build()
    eng = ServingEngine(model, params, ServeConfig(
        num_slots=2, page_size=8, max_len=64, prefill_chunk=16),
        registry=MetricsRegistry())
    groups = {g for g, _ in hp.scope_map(
        eng.lower_programs()["decode"].compile()).values()}
    assert {"layer/mla_q", "layer/mla_kv", "layer/kv_write",
            "layer/mla_out", "layer/router", "layer/experts",
            "layer/shared_expert", "layer/attn", "layer/mlp",
            "embed", "lm_head"} <= groups


def test_a_grouped_product_keeps_the_scope_of_its_rows():
    """Lines as the v5e compiler writes them (compiled for a described
    chip, PR 27): `lax.ragged_dot` becomes custom calls whose `op_name`
    is "ragged-dot-none" with no path.  `scope_map` gives each the
    group of its first scoped operand; the metadata call, whose operand
    has no scope, stays unscoped, and so does every other pathless
    instruction."""
    from hetu_tpu.obs import hlo_profile as hp
    text = """
  %ragged-dot-metadata.1 = (s32[5]{0}, s32[1]{0}) custom-call(%get-tuple-element.548), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %get-tuple-element.541 = s32[1]{0} get-tuple-element(%ragged-dot-metadata.1), index=1
  %fusion.13 = bf16[128,1024]{1,0} fusion(%p.1), kind=kLoop, metadata={op_name="jit(decode_fn)/layer/mlp/experts/cond/branch_1_fun/gather"}
  %ragged-dot-none.3 = bf16[128,1024]{1,0} custom-call(%get-tuple-element.541, /*index=5*/%fusion.13, %get-tuple-element.547), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %slice_multiply_fusion.1 = bf16[128,512]{1,0} fusion(%ragged-dot-none.3), kind=kLoop, metadata={op_name="jit(decode_fn)/layer/mlp/experts/cond/branch_1_fun/mul"}
  %ragged-dot-none.2 = bf16[128,1024]{1,0} custom-call(%get-tuple-element.541, /*index=5*/%slice_multiply_fusion.1, %custom-call.29), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %copy.9 = bf16[4,8]{1,0} copy(%fusion.13), metadata={op_name="copy-none"}
"""
    groups = {k: g for k, (g, _) in hp.scope_map(text).items()}
    assert groups["ragged-dot-none.3"] == groups["ragged-dot-none.2"] \
        == groups["fusion.13"] == "layer/experts"
    assert groups["ragged-dot-metadata.1"] == groups["copy.9"] \
        == groups["get-tuple-element.541"] == hp.UNSCOPED


def test_counts_and_cost_functions_of_the_family():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kimi-k2.6-ep32-depth6.json")) as f:
        cfg = json.load(f)
    n = fam.counts(cfg)
    assert n["total_params"] == cfg["parameters"] == 4173177728
    assert n["total_params"] == fam.build_model(
        cfg, cfg["serving"]).num_params()
    # MLA 101.1M, shared 44.0M, router 2.75M, 8 x 12 / 384 of an expert
    per_moe = 101_122_048 + 44_040_192 + 2_752_512 + 0.25 * 44_040_192
    assert n["matmul_params"] == pytest.approx(
        5 * per_moe + 101_122_048 + 3 * 7168 * 18432 + 7168 * 20480)
    window = {"counters": {"serve.decode_context_tokens": 1000.0,
                           "serve.decode_slot_steps": 10.0}}
    cost = fam.paged_latent_attn_cost(cfg, window)
    assert cost == {"ops": 6 * 2 * 64 * (576 + 512) * 1000.0,
                    "bytes": 6 * 2 * (576 * 1000.0
                                      + 10 * 64 * (576 + 512))}
    assert fam.paged_latent_attn_cost(cfg, {"counters": {}}) is None
    moe_cost = fam.grouped_matmul_cost(cfg, {"counters": {
        "serve.moe_expert_hits": 9.0, "serve.moe_local_assignments": 16.0}})
    assert moe_cost["bytes"] == 2 * (9 * 44_040_192
                                     + 16 * (2 * 7168 + 3 * 2048))
    assert moe_cost["ops"] == 2 * 16 * 44_040_192
    assert fam.grouped_matmul_cost(cfg, {"counters": {}}) is None
