"""KV-cache generation tests: greedy decode must match the naive
full-recompute argmax loop exactly."""
import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.models.generation import generate, prefill, decode_step


def _model():
    cfg = LlamaConfig.tiny(remat=False, compute_dtype=jnp.float32,
                           use_flash_attention=False)
    m = LlamaLMHeadModel(cfg)
    return m, m.init(jax.random.key(0))


def test_greedy_matches_full_recompute():
    model, params = _model()
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, 256, (2, 8)), jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=6)
    assert out.shape == (2, 14)

    # naive loop: full forward each step, take argmax
    seq = prompt
    for _ in range(6):
        logits = model(params, seq)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_prefill_logits_match_forward():
    model, params = _model()
    prompt = jnp.asarray(np.random.default_rng(1).integers(0, 256, (2, 12)),
                         jnp.int32)
    logits, cache = prefill(model, params, prompt, max_len=16)
    full = model(params, prompt)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, -1, :]),
                               rtol=1e-4, atol=1e-5)


def test_sampled_generation_runs_and_eos_stops():
    model, params = _model()
    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=8, temperature=0.8,
                   top_k=20, rng=jax.random.key(5))
    assert out.shape == (1, 12)
    # eos propagation: once produced (forced here by eos_id == every token)
    logits, cache = prefill(model, params, prompt, max_len=12)
    tok = int(jnp.argmax(logits[0]))
    out2 = generate(model, params, prompt, max_new_tokens=8, eos_id=tok)
    tail = np.asarray(out2)[0, 4:]
    first = np.flatnonzero(tail == tok)
    if len(first):
        assert (tail[first[0]:] == tok).all()


def test_gqa_generation():
    cfg = LlamaConfig.tiny(remat=False, compute_dtype=jnp.float32,
                           num_key_value_heads=2, use_flash_attention=False)
    model = LlamaLMHeadModel(cfg)
    params = model.init(jax.random.key(2))
    prompt = jnp.asarray([[5, 6, 7]], jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=5)
    seq = prompt
    for _ in range(5):
        nxt = jnp.argmax(model(params, seq)[:, -1, :], -1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt[:, None]], 1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_top_p_sampling_restricts_support():
    """Nucleus sampling: with a peaked distribution and small top_p, only
    the head of the distribution is ever sampled."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models.generation import generate

    cfg = LlamaConfig.tiny(remat=False, vocab_size=64,
                           max_position_embeddings=64)
    model = LlamaLMHeadModel(cfg)
    params = model.init(jax.random.key(0))
    ids = jnp.ones((2, 4), jnp.int32)
    out = generate(model, params, ids, max_new_tokens=6, temperature=1.0,
                   top_p=0.9, rng=jax.random.key(1))
    assert out.shape == (2, 10)
    # same seed + same settings -> deterministic
    out2 = generate(model, params, ids, max_new_tokens=6, temperature=1.0,
                    top_p=0.9, rng=jax.random.key(1))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    # tiny top_p degenerates to greedy (only the argmax survives)
    outg = generate(model, params, ids, max_new_tokens=6, temperature=0.0)
    outp = generate(model, params, ids, max_new_tokens=6, temperature=1.0,
                    top_p=1e-6, rng=jax.random.key(2))
    np.testing.assert_array_equal(np.asarray(outg), np.asarray(outp))


def _attend_cached_repeat(q, ck, cv, pos, scale):
    """The PRE-refactor GQA attention (materializes group-repeated K/V
    with jnp.repeat every step) — kept here as the bit-exactness
    reference for the grouped-einsum replacement."""
    b, M, n_kv, hd = ck.shape
    nq = q.shape[2]
    group = nq // n_kv
    if group > 1:
        ck = jnp.repeat(ck, group, axis=2)
        cv = jnp.repeat(cv, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   ck.astype(jnp.float32)) * scale
    mask = jnp.arange(M)[None, None, None, :] <= pos
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, cv.astype(jnp.float32))
    return out.astype(q.dtype)


def _assert_within_ulps(new, old, ulps, scale):
    """|new - old| <= `ulps` float32 ulps AT `scale`, the magnitude of
    the largest term of the sums compared: that sets the rounding of a
    float32 sum, so an element that cancels to near zero is held to it,
    not to its own magnitude."""
    np.testing.assert_allclose(
        np.asarray(new), np.asarray(old), rtol=0,
        atol=ulps * np.spacing(np.float32(scale)))


def test_gqa_attend_bit_exact_vs_repeat_path():
    """Regression vs the old repeat-then-attend GQA path: the same
    per-head dot, the same mapping q head j -> kv head j // group, so
    both contractions equal the repeat path's up to float32 summation
    order.  CHANGES.md PR 7 recorded "q·k scores bit-identical, p·v
    within f32 ulp" and this test asserted bit equality of the scores;
    on the XLA:CPU of this installation the two score einsums reduce
    over head_dim in different orders (398 of 576 scores differ, by one
    ulp of the largest score; each as near a float64 reference as the
    other: 1.3e-6 and 1.0e-6), so the scores are held to what the
    outputs are: 2 float32 ulps at the scale of the sums' largest term —
    the largest score for q·k, the largest cached value for p·v, whose
    weights are <= 1 (measured: 1 ulp each).  End-to-end greedy decode
    stays token-identical (below, and the goldens elsewhere in this file
    pin that against full recompute and HF)."""
    from hetu_tpu.models.generation import _attend_cached
    rng = np.random.default_rng(0)
    b, M, n_kv, group, hd = 3, 24, 2, 4, 16
    nq = n_kv * group
    q = jnp.asarray(rng.normal(size=(b, 1, nq, hd)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(b, M, n_kv, hd)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(b, M, n_kv, hd)), jnp.float32)
    # scores: grouped einsum == repeated einsum
    ckr = jnp.repeat(ck, group, axis=2)
    s_old = jnp.einsum("bqhd,bkhd->bhqk", q, ckr)
    s_new = jnp.einsum("bqhgd,bkhd->bhgqk",
                       q.reshape(b, 1, n_kv, group, hd), ck)
    _assert_within_ulps(np.asarray(s_new).reshape(b, nq, 1, M), s_old, 2,
                        scale=np.abs(s_old).max())
    # full attend: the reassociated p·v sum on top
    v_max = np.abs(cv).max()
    for pos in (0, 5, M - 1):
        _assert_within_ulps(
            _attend_cached(q, ck, cv, pos, hd ** -0.5),
            _attend_cached_repeat(q, ck, cv, pos, hd ** -0.5), 2, v_max)
    # MHA (group == 1): same code path shape, same tolerance contract
    q1 = jnp.asarray(rng.normal(size=(b, 1, n_kv, hd)), jnp.float32)
    _assert_within_ulps(
        _attend_cached(q1, ck, cv, 7, hd ** -0.5),
        _attend_cached_repeat(q1, ck, cv, 7, hd ** -0.5), 2, v_max)
    # and the decode-level contract: token-identical greedy continuations
    # through the real model (GQA config) vs full recompute
    cfg = LlamaConfig.tiny(remat=False, compute_dtype=jnp.float32,
                           num_key_value_heads=2, use_flash_attention=False)
    model = LlamaLMHeadModel(cfg)
    params = model.init(jax.random.key(7))
    prompt = jnp.asarray([[11, 12, 13, 14]], jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=6)
    seq = prompt
    for _ in range(6):
        nxt = jnp.argmax(model(params, seq)[:, -1, :], -1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt[:, None]], 1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_eos_pad_early_exit():
    """With eos_token_id + pad_token_id set, finished sequences emit pad
    (not the eos forever), and the legacy eos_id behavior is unchanged
    when pad is unset."""
    model, params = _model()
    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    from hetu_tpu.models.generation import prefill
    logits, _ = prefill(model, params, prompt, max_len=12)
    eos = int(jnp.argmax(logits[0]))   # the first greedy token IS eos
    out = generate(model, params, prompt, max_new_tokens=8,
                   eos_token_id=eos, pad_token_id=0)
    tail = np.asarray(out)[0, 4:]
    assert tail[0] == eos
    np.testing.assert_array_equal(tail[1:], np.zeros(7, np.int32))
    # legacy alias: eos_id with no pad keeps emitting eos
    out_legacy = generate(model, params, prompt, max_new_tokens=8,
                          eos_id=eos)
    np.testing.assert_array_equal(np.asarray(out_legacy)[0, 4:],
                                  np.full(8, eos, np.int32))
    # a batch where only ONE row finishes: the other row keeps decoding
    # exactly as the eos-free run does
    prompt2 = jnp.asarray([[1, 2, 3, 4], [9, 8, 7, 6]], jnp.int32)
    out2 = generate(model, params, prompt2, max_new_tokens=6,
                    eos_token_id=eos, pad_token_id=0)
    free = generate(model, params, prompt2, max_new_tokens=6)
    row1_free = np.asarray(free)[1]
    row1_eos = np.asarray(out2)[1]
    cut = np.flatnonzero(row1_eos[4:] == eos)
    upto = 4 + (cut[0] + 1 if len(cut) else 6)
    np.testing.assert_array_equal(row1_eos[:upto], row1_free[:upto])


def test_decode_step_slots_per_slot_positions():
    """Two sequences at DIFFERENT depths decoded in one slot batch match
    their individual decode_step results (the serving engine's core
    contract), and the returned per-layer token K/V equal what was
    written into the cache."""
    from hetu_tpu.models.generation import (decode_step_slots, prefill,
                                            init_cache)
    model, params = _model()
    rng = np.random.default_rng(4)
    M = 16
    pa = jnp.asarray(rng.integers(0, 256, (1, 5)), jnp.int32)
    pb = jnp.asarray(rng.integers(0, 256, (1, 9)), jnp.int32)
    la, ca = prefill(model, params, pa, max_len=M)
    lb, cb = prefill(model, params, pb, max_len=M)
    ta = jnp.argmax(la, -1).astype(jnp.int32)
    tb = jnp.argmax(lb, -1).astype(jnp.int32)
    # solo decodes
    oa, na = decode_step(model, params, ta, ca, 5)
    ob, nb = decode_step(model, params, tb, cb, 9)
    # batched slot decode at per-slot positions
    cab = tuple(jnp.concatenate([x, y], axis=1) for x, y in zip(ca, cb))
    toks = jnp.concatenate([ta, tb])
    positions = jnp.asarray([5, 9], jnp.int32)
    out, new_cache, (kt, vt) = decode_step_slots(model, params, toks, cab,
                                                 positions)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(oa[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(ob[0]),
                               rtol=1e-5, atol=1e-5)
    # token K/V mirror the cache writes
    np.testing.assert_array_equal(np.asarray(new_cache[0][:, 0, 5]),
                                  np.asarray(kt[:, 0]))
    np.testing.assert_array_equal(np.asarray(new_cache[1][:, 1, 9]),
                                  np.asarray(vt[:, 1]))


def test_extend_cache_chunked_matches_prefill():
    """Chunked prefill (extend_cache over consecutive chunks) reproduces
    the one-shot prefill: same last-token logits, same cached K/V."""
    from hetu_tpu.models.generation import extend_cache, init_cache
    model, params = _model()
    rng = np.random.default_rng(6)
    plen, C, M = 12, 4, 16
    prompt = jnp.asarray(rng.integers(0, 256, (1, plen)), jnp.int32)
    gold_logits, gold_cache = prefill(model, params, prompt, max_len=M)
    cache = init_cache(model, 1, M)
    logits = None
    for s in range(0, plen, C):
        logits, cache = extend_cache(model, params, prompt[:, s: s + C],
                                     cache, s)
    np.testing.assert_allclose(np.asarray(logits[:, -1]),
                               np.asarray(gold_logits),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(cache[0][:, :, :plen]),
                               np.asarray(gold_cache[0][:, :, :plen]),
                               rtol=2e-4, atol=2e-5)
    # GQA config through the chunked path too
    cfg = LlamaConfig.tiny(remat=False, compute_dtype=jnp.float32,
                           num_key_value_heads=2, use_flash_attention=False)
    m2 = LlamaLMHeadModel(cfg)
    p2 = m2.init(jax.random.key(3))
    g2, _ = prefill(m2, p2, prompt, max_len=M)
    c2 = init_cache(m2, 1, M)
    for s in range(0, plen, C):
        l2, c2 = extend_cache(m2, p2, prompt[:, s: s + C], c2, s)
    np.testing.assert_allclose(np.asarray(l2[:, -1]), np.asarray(g2),
                               rtol=2e-4, atol=2e-5)


def test_gpt_generate_matches_hf_greedy():
    """GPT family through the KV-cache decode loop: greedy continuations
    match HF transformers token-for-token under converted weights."""
    import pytest
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    import jax
    from hetu_tpu.models.generation import generate
    from hetu_tpu.models.gpt import GPTConfig, GPTLMHeadModel
    from hetu_tpu.models.gpt.convert import convert_hf_gpt2

    hf_cfg = transformers.GPT2Config(
        vocab_size=256, n_embd=64, n_layer=2, n_head=4, n_positions=256,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
    torch.manual_seed(3)
    hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
    cfg = GPTConfig.tiny(remat=False, compute_dtype=jnp.float32)
    model = GPTLMHeadModel(cfg)
    params = convert_hf_gpt2(hf.state_dict(), cfg)
    ids = np.random.default_rng(3).integers(0, 256, size=(2, 8))
    with torch.no_grad():
        # explicit mask: otherwise HF infers one from pad_token_id and a
        # random 0 in the prompt would mask a real token
        hf_out = hf.generate(torch.tensor(ids), max_new_tokens=6,
                             do_sample=False, pad_token_id=0,
                             attention_mask=torch.ones_like(
                                 torch.tensor(ids)))
    ours = generate(model, params, jnp.asarray(ids, jnp.int32),
                    max_new_tokens=6, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(ours), hf_out.numpy())
