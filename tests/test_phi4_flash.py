"""Phi-4-mini-flash (`phi4flash`, the SambaY decoder-hybrid-decoder) on the
serving path, at a tiny size that keeps every mechanism: (Mamba-1, window
attention) pairs, the memory layer, the ONE full-attention layer, and
(gated memory unit, cross attention) pairs that keep no cache of their
own; Mamba state by slot beside window and full pages; differential
attention on K/V pairs stored several to a row; a prefill that stops at
the full layer for every row but the last.  Seeded random float32
weights; the reference is `benchmarks/families/phi4flash.py`'s plain
forward (a `lax.scan` over positions, two softmaxes over explicit masks,
no chunks, no cache), which shares no code with the program.

Tolerances.  Program and reference are both float32 here, so they differ
by the ORDER of float32 sums only (a blocked walk against a scan, online
softmax against a plain one, one row's product against a chunk's): logits
of O(1) agree to a few 1e-6; LOGIT_ATOL = 2e-4 leaves two orders of room
and is one to three orders under what three of the four controls move
(lambda left at lambda_init 3e-3, the memory taken after the gate 1e-2,
the cross layers masked to the window 0.1).  The fourth, a state kept in
bfloat16, moves these logits by 3e-7: at 192 channels the state's share
of a Mamba layer's output is a thousandth of what it is at 5,120 (B and
C are sums over d_inner of weights at std 0.02), so that control is held
on the scan's own output, where the state is all there is."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.families import phi4flash as fam  # noqa: E402
from hetu_tpu.models import generation as gen  # noqa: E402
from hetu_tpu.models.cache_contract import (NO_CACHE,  # noqa: E402
                                            CacheContract, cache_contract)
from hetu_tpu.obs.metrics import MetricsRegistry  # noqa: E402
from hetu_tpu.ops import selective_scan  # noqa: E402
from hetu_tpu.serving.engine import ServeConfig, ServingEngine  # noqa: E402
from hetu_tpu.serving.kv_pool import (PagePool,  # noqa: E402
                                      contract_bytes_per_token)
from hetu_tpu.serving.request import Request  # noqa: E402

LOGIT_ATOL = 2e-4
F32 = jnp.float32
CONTROLS = ("fixed_lambda", "memory_after_gate", "cross_window")


def config(name="tiny-phi4flash"):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


def build(**over):
    cfg = dict(config(), **over)
    model = fam.build_model(cfg, cfg["serving"])
    return cfg, model, model.init(jax.random.key(7))


def ref_logits(params, cfg, ids, control=None):
    ids = jnp.asarray(ids, jnp.int32)
    return np.asarray(jax.jit(lambda p, i: fam.logits_at(
        p, i, jnp.arange(i.shape[0]), cfg, control))(params, ids))


def engine(model, params, **serve):
    reg = MetricsRegistry()
    cfg = dict(num_slots=3, page_size=8, max_len=128, prefill_chunk=16,
               num_pages=(48, 15))
    cfg.update(serve)
    return ServingEngine(model, params, ServeConfig(**cfg), registry=reg), reg


def requests(rng, cfg, plens, new=6):
    return [Request(rid=i, prompt=rng.integers(0, cfg["vocab_size"], size=n)
                    .astype(np.int32), max_new_tokens=new,
                    arrival_t=0.01 * i) for i, n in enumerate(plens)]


def fresh_cache(model, rows=2, max_len=64):
    """A dense cache of one row and, behind it, state arrays of `rows`
    rows, as the engine hands them to the chunk program."""
    contract = cache_contract(model)
    K = len(contract.kinds)
    state = tuple(
        jnp.zeros((len(contract.layers_of(K + i)), rows) + tuple(shape),
                  jnp.dtype(dt))
        for i, shapes in enumerate(contract.state_kinds)
        for shape, dt in shapes)
    return tuple(gen.init_cache(model, 1, max_len)) + state


def chunked(model, params, ids, C=16, max_len=64, **kw):
    """`ids` [s] through the chunk program C rows at a time (the last
    chunk padded, its padding masked by `valid`), state row 1: (the
    logits the launches returned, one entry a launch, the cache)."""
    cache = fresh_cache(model, max_len=max_len)
    step = jax.jit(lambda p, t, c, s, v, r: gen.extend_cache(
        model, p, t, c, s, state_row=1, valid=v,
        **({"read_row": r} if kw.get("stop") else {})))
    out = []
    for s in range(0, len(ids), C):
        seg = np.zeros(C, np.int32)
        n = min(C, len(ids) - s)
        seg[:n] = ids[s: s + n]
        last = s + C >= len(ids)
        lg, cache = step(params, jnp.asarray(seg[None]), cache, jnp.int32(s),
                         jnp.int32(n), jnp.int32(n - 1 if last else -1))
        out.append(np.asarray(lg[0]))
    return out, cache


# ------------------------------------------------------ the selective scan
def _scan_inputs(rng, b, s, D=24, N=8):
    u = rng.standard_normal((b, s, D))
    delta = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (b, s, D)))
    A = -np.broadcast_to(np.arange(1, N + 1)[:, None], (N, D))
    B, C = (rng.standard_normal((b, s, N)) for _ in range(2))
    h = rng.standard_normal((b, N, D))
    return tuple(jnp.asarray(a, F32) for a in (
        h, u, delta, A, B, C, rng.standard_normal(D)))


@pytest.mark.parametrize("positions", [16, 37, 128])
def test_blocked_scan_is_the_recurrence(positions, rng):
    """The walk by blocks of 16 positions (1 where the length has no
    larger common divisor) against the definition position by position:
    the same operations in the same order, equal to rounding."""
    args = _scan_inputs(rng, 2, positions)
    y1, h1 = selective_scan.recurrence(*args)
    y2, h2 = jax.jit(selective_scan.chunk_scan)(*args)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h1), atol=1e-5)


@pytest.mark.parametrize("valid", [0, 5, 16, 37])
def test_padding_rows_leave_the_state_where_the_valid_rows_end(valid, rng):
    h, u, delta, A, B, C, D = _scan_inputs(rng, 1, 48)
    y, h2 = jax.jit(selective_scan.chunk_scan)(
        h, u, delta, A, B, C, D, valid=jnp.asarray([valid]))
    y1, h1 = selective_scan.recurrence(
        h, u[:, :valid], delta[:, :valid], A, B[:, :valid], C[:, :valid], D)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h1), atol=1e-5)
    np.testing.assert_allclose(np.asarray(y[:, :valid]), np.asarray(y1),
                               atol=1e-5)
    assert np.isfinite(np.asarray(y)).all()


def test_decode_step_is_the_recurrence_and_an_idle_slot_keeps_its_state(rng):
    h, u, delta, A, B, C, D = _scan_inputs(rng, 3, 1)
    live = jnp.asarray([True, False, True])
    y, h2 = selective_scan.step(h, u[:, 0], delta[:, 0], A, B[:, 0],
                                C[:, 0], D, live=live)
    y1, h1 = selective_scan.recurrence(h, u, delta, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y)[[0, 2]],
                               np.asarray(y1)[[0, 2], 0], atol=1e-6)
    np.testing.assert_allclose(np.asarray(h2)[[0, 2]],
                               np.asarray(h1)[[0, 2]], atol=1e-6)
    np.testing.assert_array_equal(np.asarray(h2)[1], np.asarray(h)[1])


# ------------------------------------------------- differential attention
@pytest.mark.parametrize("kind", ["window", "full", "cross"])
def test_differential_attention_through_the_kv_route_is_two_softmaxes(
        kind, rng):
    """A layer's attention as the program makes it (zero-padded query
    heads over the pairs' rows, through `KVAttention`'s composition:
    whole prompts and a chunk over a dense cache of stored rows) against
    the reference's two softmaxes over explicit masks."""
    from hetu_tpu.models.phi4_flash import DiffAttention
    cfg, model, params = build()
    c = model.config
    layer = {"window": 1, "full": c.shared_kv_layer,
             "cross": c.shared_kv_layer + 2}[kind]
    window = c.sliding_window if kind == "window" else None
    attn = DiffAttention(c, window, cross=kind == "cross")
    ap = attn.init(jax.random.key(3))
    ap["lambda_init"] = jnp.asarray(c.lambda_init(layer), F32)
    s = 40
    hn = jnp.asarray(rng.standard_normal((1, s, c.hidden_size)), F32)
    other = DiffAttention(c, None)
    kv_params = other.init(jax.random.key(4))
    pos = jnp.arange(s)[None]
    q, entries = attn.project(ap, hn, None, pos)
    if kind == "cross":
        assert entries == ()
        entries = other.project(kv_params, hn, None, pos)[1]
        k, v = fam._keys_values(hn[0], kv_params, cfg)
    else:
        k, v = fam._keys_values(hn[0], ap, cfg)
    assert entries[0].shape == (1, s) + c.kv_row
    want = np.asarray(fam._diff_attend(hn[0], jnp.arange(s), k, v, ap, cfg,
                                       layer, window=window))
    got = attn.output(ap, attn.attend_prompt(ap, q, entries, window=window))
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-5)
    # a chunk of 8 queries at position 32 over the dense cache
    got = attn.output(ap, attn.attend_dense(
        ap, q[:, 32:], entries, jnp.asarray([32]), window=window))
    np.testing.assert_allclose(np.asarray(got[0]), want[32:], atol=2e-5)


def test_the_paged_kernel_reads_stored_rows_where_they_lie(rng, monkeypatch):
    """The decode step's route on the chip: queries laid at their pair's
    place in a stored row's width, the pair's values cut out of what the
    paged kernel returns (run here by the interpreter), against the
    composition over gathered pages."""
    from hetu_tpu.models.phi4_flash import DiffAttention
    assert build()[1].config.kv_fold == 3
    # (the kernel takes lane rows: one attention layer at the published
    # widths, 5 pairs to a stored row of 640)
    cfg = config("phi-4-mini-flash-reasoning")
    c = fam.build_model(cfg, {"param_dtype": "float32"}).config
    assert c.kv_fold == 5 and c.kv_row == (2, 640)
    attn = DiffAttention(c, None)
    ap = attn.init(jax.random.key(3))
    S, ps, pages = 3, 8, 6
    pools = tuple(jnp.asarray(rng.standard_normal(
        (2 * (pages + 1), ps) + c.kv_row), F32) for _ in range(2))
    table = jnp.asarray([[1, 2, 3], [4, 0, 0], [5, 6, 0]], jnp.int32)
    positions = jnp.asarray([20, 3, 12], jnp.int32)
    q = attn.project(ap, jnp.asarray(rng.standard_normal(
        (S, 1, c.hidden_size)), F32), None, None)[0]
    base = pages + 1            # the second layer's pages
    plain = attn.attend_paged(ap, q, pools, table, positions, base)
    monkeypatch.setattr(DiffAttention, "_paged_kernel_takes",
                        lambda self, *a: True)
    routed = attn.attend_paged(ap, q, pools, table, positions, base)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(plain),
                               atol=2e-5)


# ------------------------------------------------------------ the model
@pytest.mark.parametrize("seq", [37, 64])
def test_whole_sequence_forward_is_the_reference(seq, rng):
    cfg, model, params = build()
    ids = rng.integers(0, cfg["vocab_size"], size=seq)
    got = np.asarray(model(params, jnp.asarray(ids[None], jnp.int32)))[0]
    np.testing.assert_allclose(got, ref_logits(params, cfg, ids),
                               atol=LOGIT_ATOL)


def test_the_references_rows_only_evaluation_is_its_full_forward(rng):
    """`logits_at` runs the full layer's attention and the cross-decoder
    at the rows asked for alone; `hidden_states` runs every row through
    every layer.  Rows out of order and repeated, as the check's padding
    repeats the last."""
    cfg, model, params = build()
    ids = jnp.asarray(rng.integers(0, cfg["vocab_size"], size=48), jnp.int32)
    rows = jnp.asarray([3, 17, 30, 47, 47])
    full = fam.hidden_states(params, ids, cfg) \
        @ params["model"]["embed"]["weight"].T
    got = fam.logits_at(params, ids, rows, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full)[rows],
                               atol=2e-5)


def test_tiny_configuration_keeps_every_mechanism():
    cfg, model, _ = build()
    c = model.config
    L = c.num_hidden_layers
    mixers = [c.mixer_of(l) for l in range(L)]
    assert mixers == ["ssm", "window"] * 3 + ["ssm", "full"] \
        + ["gmu", "cross"] * 2
    assert mixers == [fam.mixer_of(l, cfg) for l in range(L)]
    assert model.read_rows_from == c.shared_kv_layer == 7
    # four runs, six layer bodies: two scanned periods, two called layers
    runs = model.serving_layers(model.abstract_params())
    assert [(len(b) if isinstance(b, tuple) else 1, n)
            for b, _, n in runs] == [(2, 3), (1, None), (1, None), (2, 2)]
    assert fam.counts(cfg)["total_params"] == model.num_params()


def test_published_widths_the_contract_and_the_pool_by_its_bytes():
    """The published model: 18 layers store (9 hold pages, 9 a state), 14
    keep nothing; layer 17's one page set is read by eight layers."""
    cfg = config("phi-4-mini-flash-reasoning")
    model = fam.build_model(cfg, cfg["serving"])
    contract = cache_contract(model)
    assert model.num_params() == cfg["parameters"] == 3_852_562_960 \
        == fam.counts(cfg)["total_params"]
    assert fam.counts(cfg)["prefill_matmul_params"] == 1_870_888_960
    assert contract.kinds == (None, 512) and len(contract.state_kinds) == 1
    assert [len(contract.layers_of(k)) for k in range(3)] == [1, 8, 9]
    assert contract.layers_of(0) == (17,)
    assert contract.readers_of(17) == tuple(range(19, 32, 2))
    assert [contract.reads[l] for l in range(18, 32, 2)] == [NO_CACHE] * 7
    assert all(contract.kind_of(l) == contract.kind_of(17) == 0
               and contract.place_of(l) == 0 for l in range(19, 32, 2))
    assert all(contract.kind_of(l) is None for l in range(18, 32, 2))
    assert contract.page_layers == 9 and contract.borrows
    # a token: K and V rows of 1,280 values in 9 layers; 2 rows of 640
    assert model.config.kv_row == (2, 640) and model.config.kv_fold == 5
    assert contract_bytes_per_token(contract, "bf16") == 9 * 5120 == 46080
    # a sequence: 9 x (16 x 5120 float32 + 3 x 5120 bfloat16)
    assert contract.state_bytes_per_slot(2) == 9 * (327_680 + 30_720) \
        == fam.ssm_state_bytes_per_slot(cfg) == 3_225_600
    sv = cfg["serving"]
    pool = PagePool.for_contract(
        contract, num_pages=tuple(sv["num_pages"]), page_size=sv["page_size"],
        num_slots=sv["num_slots"], device_arrays=False)
    # (among the nine layers that hold pages, layer 17 is the last)
    assert pool.num_layers == 9 and pool.layers == ((8,), tuple(range(8)))
    assert pool.hold_pages(sv["max_len"], 1) * sv["page_size"] == 640
    assert pool.pages_by_kind[0] >= 32 * 24576 // sv["page_size"]
    assert pool.pages_by_kind[1] >= 32 * pool.hold_pages(sv["max_len"], 1)


def test_the_pool_holds_nothing_for_the_layers_that_keep_no_cache():
    _, model, params = build()
    eng, reg = engine(model, params)
    c, contract = model.config, eng.cache
    shapes = [a.shape for a in eng.pool.tree()]
    row = c.kv_row
    assert shapes == [(1, 49, 8) + row] * 2 + [(3, 16, 8) + row] * 2 + [
        (4, 4, c.mamba_d_state, c.d_inner), (4, 4, 3, c.d_inner)]
    nbytes = sum(a.size * a.dtype.itemsize for a in eng.pool.tree())
    per_token = 2 * row[0] * row[1] * 4
    assert nbytes == (1 * 49 + 3 * 16) * 8 * per_token \
        + 4 * 4 * (c.mamba_d_state + 3) * c.d_inner * 4
    assert eng.scheduler.page_table.shape[0] == 2      # one table a kind
    assert contract.values_per_token == 4 * 2 * row[0] * row[1]


# ------------------------------------------------------ through the engine
@pytest.mark.parametrize("plens", [
    (5,),            # ends inside the first chunk: 11 padding rows
    (16, 32),        # end at a chunk's edge
    (40, 17, 30),    # past the window of 24, three slots at depths of
])                   # their own, one prompt's chunks between decode steps
def test_chunked_prefill_then_paged_decode_is_the_references_forward(
        plens, rng):
    cfg, model, params = build()
    eng, reg = engine(model, params)
    reqs = requests(rng, cfg, plens)
    results = {r.rid: r for r in eng.run(reqs)}
    for req in reqs:
        toks = np.asarray(results[req.rid].tokens)
        lg = ref_logits(params, cfg, np.concatenate(
            [req.prompt, toks[:-1]]))[req.prompt_len - 1:]
        gap = lg.max(-1) - lg[np.arange(len(toks)), toks]
        assert (gap <= LOGIT_ATOL).all(), (req.rid, gap)
    eng.scheduler.check_invariants()
    assert eng.pool.free_count == eng.pool.num_pages
    assert reg.counter_value("serve.state_resets") == len(plens)
    assert reg.counter_value("serve.prefill_tail_rows") == len(plens)
    assert reg.counter_value("serve.ssm_state_bytes") > 0
    assert not reg.counter_value("serve.kda_state_bytes")
    # two cross layers read the full layer's pages at every decode row
    assert reg.counter_value("serve.shared_kv_positions") == \
        2 * reg.counter_value("serve.decode_context_tokens")


def test_the_chunk_program_is_the_reference_at_every_row(rng):
    """The chunk program's own logits, a prompt of three chunks and a
    half (22 padding rows... none taken into the state), every row
    through every layer, against the reference."""
    cfg, model, params = build()
    ids = rng.integers(0, cfg["vocab_size"], size=58)
    lg, _ = chunked(model, params, ids)
    got = np.concatenate(lg)[:58]
    np.testing.assert_allclose(got, ref_logits(params, cfg, ids),
                               atol=LOGIT_ATOL)


@pytest.mark.parametrize("control", CONTROLS)
def test_a_control_comes_out_not_correct(control, rng):
    """Each thing the comparison has to see, done wrongly in the
    reference: the program's logits then stand far outside the tolerance
    they meet against the reference as it is."""
    cfg, model, params = build()
    ids = rng.integers(0, cfg["vocab_size"], size=58)
    got = np.concatenate(chunked(model, params, ids)[0])[:58]
    assert np.abs(got - ref_logits(params, cfg, ids)).max() <= LOGIT_ATOL
    off = np.abs(got - ref_logits(params, cfg, ids, control)).max()
    assert off > 5 * LOGIT_ATOL, (control, off)


def test_a_state_kept_in_bfloat16_comes_out_not_correct(rng):
    """The fourth control, on one Mamba layer's scan output with the
    skip term taken out (D = 0) and B, C at the published widths' scale
    (W_x x 16): the program's chunks (state handed from chunk to chunk,
    the last one padded) stand within 1e-5 of the reference's scan,
    relative to the largest output, and 1e-3 or more from the same scan
    with its state rounded to bfloat16 after every position."""
    cfg, model, params = build()
    block, lp = next(iter(model.model.memory.layers(
        params["model"]["memory"])))
    ap = dict(lp["attn"], D=jnp.zeros_like(lp["attn"]["D"]),
              w_x=16.0 * lp["attn"]["w_x"])
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
    assert np.abs(got - want).max() <= 1e-5 * top
    assert np.abs(got - ctrl).max() >= 1e-3 * top


@pytest.mark.parametrize("plen", [7, 16, 45])
def test_the_early_stop_leaves_what_the_full_walk_leaves(plen, rng):
    """The chunk program that stops at the full layer's page write for
    every row but the one read, against the same program run through all
    layers for every row: identical cache arrays and state (the rows that
    stop do not reach them), and the read row's logits equal to float32
    rounding (one row's products against a chunk's)."""
    cfg, model, params = build()
    ids = rng.integers(0, cfg["vocab_size"], size=plen)
    full, cache_full = chunked(model, params, ids)
    stop, cache_stop = chunked(model, params, ids, stop=True)
    for a, b in zip(cache_full, cache_stop):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert all(lg.shape == (1, cfg["vocab_size"]) for lg in stop)
    # a chunk that does not end its prompt ran no tail: zeros
    assert all(not lg.any() for lg in stop[:-1])
    np.testing.assert_allclose(stop[-1][0], full[-1][(plen - 1) % 16],
                               atol=2e-5)


def test_a_reused_slot_and_a_capped_prefill(rng):
    """One slot may prefill at a time (`max_prefilling`): requests wait
    for the scratch, slots are reused, and every stream is the
    reference's."""
    cfg, model, params = build()
    eng, reg = engine(model, params, num_slots=2, max_prefilling=1)
    reqs = requests(rng, cfg, (21, 37, 9, 33), new=14)
    for r in reqs:
        r.arrival_t = 0.0
    results = {r.rid: r for r in eng.run(reqs)}
    for req in reqs:
        toks = np.asarray(results[req.rid].tokens)
        lg = ref_logits(params, cfg, np.concatenate(
            [req.prompt, toks[:-1]]))[req.prompt_len - 1:]
        assert (lg.max(-1) - lg[np.arange(len(toks)), toks]
                <= LOGIT_ATOL).all(), req.rid
    assert reg.counter_value("serve.state_resets") == 4
    # 37 + 14 positions under a window of 24: pages fall behind it
    assert reg.counter_value("serve.window_pages_released") > 0
    assert reg.counter_value("serve.admission_stalls",
                             reason="prefill_scratch") > 0


# ------------------------------------------------------------ refusals
@pytest.mark.parametrize("serve,names", [
    (dict(spec_decode="ngram"), "speculative decoding"),
    (dict(prefix_cache=True), "prefix cache"),
    (dict(kv_quant="int8"), "int8 / int4 pages"),
    (dict(kv_repage=True), "kv_repage"),
])
def test_what_a_reading_layer_cannot_do_is_refused_by_name(serve, names):
    _, model, params = build()
    with pytest.raises(NotImplementedError, match=names) as e:
        ServingEngine(model, params, ServeConfig(
            num_slots=2, page_size=8, max_len=64, prefill_chunk=16,
            **serve), registry=MetricsRegistry())
    # the kinds of layer that stop it, by name
    assert "layers 0, 2, 4, 6 keep a state a sequence" in str(e.value)
    assert "layers 9, 11 read layer 7's entries" in str(e.value)
    assert "layers 8, 10 keep and read no cache" in str(e.value)


def test_the_reshard_hook_the_prefill_tier_and_the_dense_programs_refuse():
    from hetu_tpu.serving.disagg import PrefillWorker
    _, model, params = build()
    with pytest.raises(NotImplementedError, match="reshard hook"):
        ServingEngine(model, params, ServeConfig(
            num_slots=2, page_size=8, max_len=64, prefill_chunk=16),
            registry=MetricsRegistry(), reshard=object())
    with pytest.raises(NotImplementedError, match="disagg"):
        PrefillWorker(model, params, prefill_chunk=16, max_len=64)
    eng, _ = engine(model, params)
    with pytest.raises(NotImplementedError, match="read layer 7's entries"):
        eng.adopt_prefilled(Request(rid=0, prompt=np.zeros(4, np.int32),
                                    max_new_tokens=1), None, None, 0, 0.0)
    with pytest.raises(NotImplementedError, match="read layer 7's entries"):
        gen.generate(model, params, jnp.zeros((1, 4), jnp.int32),
                     max_new_tokens=2)


# ------------------------------------------------------------ the contract
KV = ((2, 8), (2, 8))


def test_the_contract_counts_the_layers_that_store():
    c = CacheContract(4, KV, windows=(None, 16, None, None),
                      reads=(None, None, 0, NO_CACHE))
    assert c.kinds == (None, 16) and not c.state_kinds
    assert [c.kind_of(l) for l in range(4)] == [0, 1, 0, None]
    assert c.layers_of(0) == (0,) and c.place_of(2) == 0
    assert c.page_layers == 2 and c.values_per_token == 2 * 32
    assert c.layer_token_shapes[2] == () == c.layer_token_shapes[3]
    assert c.readers_of(0) == (2,) and c.borrows
    assert not CacheContract(2, KV).borrows


@pytest.mark.parametrize("kw,message", [
    (dict(reads=(NO_CACHE, NO_CACHE)), "some storing layer has to hold"),
    (dict(state_shapes=((((4,), "float32"),), None), reads=(None, 0)),
     "EARLIER layer that holds pages"),
    (dict(layer_token_shapes=(KV, KV[:1])), "same NUMBER of arrays"),
    (dict(reads=(None, 1)), "EARLIER layer that holds pages"),
    (dict(windows=(None, 8), reads=(None, 0)), "no window of its own"),
])
def test_the_contract_refuses_by_message(kw, message):
    with pytest.raises(ValueError, match=message):
        CacheContract(2, KV, **kw)


def test_a_storing_layers_arrays_are_counted_over_storing_layers_only():
    """Layers that store nothing a token (a state layer, a reading layer,
    one that keeps no cache) do not enter "the same NUMBER of arrays"."""
    c = CacheContract(
        4, KV, state_shapes=((((4,), "float32"),), None, None, None),
        reads=(None, None, 1, NO_CACHE))
    assert c.page_layers == 1 and c.kinds == (None,)


# ------------------------------------------------------------ the programs
def test_new_scopes_are_groups_and_old_programs_keep_theirs():
    from hetu_tpu.obs.hlo_profile import (PHASES, SCOPE_MAP_GROUPS,
                                          group_of)
    phases = (*PHASES, *SCOPE_MAP_GROUPS)
    assert group_of("jit(decode_fn)/layer/attn/ssm/ssm_step/mul",
                    phases) == "layer/ssm_step"
    assert group_of("jit(decode_fn)/layer/attn/attn_cross/diff_out/mul",
                    phases) == "layer/attn_cross"
    assert group_of("jit(chunk_fn)/layer/tail/attn/gmu/dot_general",
                    phases) == "layer/gmu"
    assert group_of("jit(chunk_fn)/layer/tail/dynamic_slice",
                    phases) == "layer/tail"
    assert group_of("jit(decode_fn)/layer/attn/kda/kda_step/mul",
                    phases) == "layer/kda_step"
    assert group_of("jit(decode_fn)/layer/attn/attn_full/dot_general",
                    phases) == "layer/attn_full"


def _programs(**over):
    _, model, params = build(**over)
    eng, _ = engine(model, params)
    return eng, eng.lower_programs()


def test_both_programs_carry_the_scopes_and_alias_the_state():
    eng, lowered = _programs()
    for name, scopes in (
            ("decode", ("ssm_step", "ssm_conv", "ssm_proj", "ssm_out", "gmu",
                        "attn_cross", "attn_window", "attn_full",
                        "diff_out")),
            ("prefill_chunk", ("ssm_scan", "ssm_conv", "tail", "gmu",
                               "attn_cross", "diff_out", "kv_write"))):
        text = lowered[name].as_text(debug_info=True)
        for scope in scopes:
            assert f"/{scope}/" in text or f"/{scope}\"" in text, (
                name, scope)
        compiled = lowered[name].compile()
        state = sum(a.size * a.dtype.itemsize for a in eng.pool.state)
        assert compiled.memory_analysis().alias_size_in_bytes >= state
    # the chunk program holds the tail inside ONE conditional
    assert lowered["prefill_chunk"].as_text().count("stablehlo.case") \
        + lowered["prefill_chunk"].as_text().count("stablehlo.if") == 1


def test_the_programs_do_not_grow_with_the_depth():
    """One body a distinct layer of a period: 12 layers and 28 compile to
    programs of the same size (the scans' trip counts differ)."""
    small, big = (_programs(num_hidden_layers=n)[1] for n in (12, 28))
    for name in ("decode", "prefill_chunk"):
        a, b = (len(p[name].as_text().splitlines()) for p in (small, big))
        assert abs(a - b) <= 0.02 * a, (name, a, b)
