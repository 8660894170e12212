"""Fused-kernel layer tests (hetu_tpu/ops/pallas, docs/kernels.md).

All CPU: every kernel runs in interpret mode (`_interpret()`), so
forward AND gradient parity against the XLA ops is provable without a
TPU.  Tolerances: float32 forward parity within 1e-5 (the kernels
compute in f32, same as the fallbacks), gradients within 1e-4
(reassociated reductions), quantize BIT-identical (same scale / same
round-half-to-even as comm/compress).

Also pins the layer's contracts:
  * gate/kernel drift — each dispatcher gate (`compatible`) must agree
    with whether the kernel actually accepts the shape;
  * HETU_TPU_PALLAS=off HLO byte-identity — the fallback path IS the
    seed path, for the llama/gpt train step and the serving decode;
  * the shared int4 nibble packer — both wire formats pinned so
    ops/quantization and comm/compress can never silently diverge;
  * obs attribution — pallas scopes form their own layer_table rows.
"""
from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from hetu_tpu import ops  # noqa: E402
from hetu_tpu.ops import norms  # noqa: E402
from hetu_tpu.ops.pallas import (KERNEL_NAMES, fused_norm,  # noqa: E402
                                 kernel_enabled, paged_attention, quant,
                                 record_routes, resolve_route, rotary,
                                 swiglu)

FWD_TOL = 1e-5
GRAD_TOL = 1e-4


def _rand(shape, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape, dtype=np.float32))


# ---------------------------------------------------------------------------
# forward + gradient parity (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["rms", "ln", "ln_nobias"])
def test_fused_residual_norm_parity(kind):
    x, h = _rand((2, 16, 256), 0), _rand((2, 16, 256), 1)
    w = _rand((256,), 2)
    b = None if kind != "ln" else _rand((256,), 3)

    if kind == "rms":
        fused = lambda x, h, w, b: fused_norm.fused_residual_rmsnorm(x, h, w)
        ref = lambda x, h, w, b: (norms.rms_norm(x + h, w), x + h)
    else:
        fused = lambda x, h, w, b: fused_norm.fused_residual_layernorm(
            x, h, w, b)
        ref = lambda x, h, w, b: (norms.layer_norm(x + h, w, b), x + h)

    y, s = fused(x, h, w, b)
    yr, sr = ref(x, h, w, b)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=FWD_TOL)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=FWD_TOL)

    # gradient parity through the custom vjp: cotangents flow into BOTH
    # outputs (the pre-norm block consumes y and the residual stream s)
    def scalar(fn):
        def g(*args):
            y, s = fn(*args)
            return (y * 1.3).sum() + (s * 0.7).sum()
        return g

    argnums = (0, 1, 2) if b is None else (0, 1, 2, 3)
    gf = jax.grad(scalar(fused), argnums=argnums)(x, h, w, b)
    gr = jax.grad(scalar(ref), argnums=argnums)(x, h, w, b)
    for a, r in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=GRAD_TOL)


def test_fused_swiglu_parity():
    g, u = _rand((4, 8, 128), 0), _rand((4, 8, 128), 1)
    y = swiglu.fused_swiglu(g, u)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ops.silu(g) * u), atol=FWD_TOL)
    ga = jax.grad(lambda a, b: (swiglu.fused_swiglu(a, b) ** 2).sum(),
                  argnums=(0, 1))(g, u)
    gb = jax.grad(lambda a, b: ((ops.silu(a) * b) ** 2).sum(),
                  argnums=(0, 1))(g, u)
    for a, r in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=GRAD_TOL)


def test_fused_rotary_parity():
    b, s, nq, nk, hd = 2, 8, 4, 2, 128
    q, k = _rand((b, s, nq, hd), 0), _rand((b, s, nk, hd), 1)
    cos, sin = ops.build_rope_cache(s, hd)
    cos_t = jnp.broadcast_to(cos[:s][None], (b, s, hd // 2))
    sin_t = jnp.broadcast_to(sin[:s][None], (b, s, hd // 2))
    qr, kr = rotary.fused_rotary_qk(q, k, cos_t, sin_t)
    np.testing.assert_allclose(np.asarray(qr),
                               np.asarray(ops.apply_rotary(q, cos, sin)),
                               atol=FWD_TOL)
    np.testing.assert_allclose(np.asarray(kr),
                               np.asarray(ops.apply_rotary(k, cos, sin)),
                               atol=FWD_TOL)
    ga = jax.grad(
        lambda a, b_: sum((t ** 2).sum() for t in
                          rotary.fused_rotary_qk(a, b_, cos_t, sin_t)),
        argnums=(0, 1))(q, k)
    gb = jax.grad(
        lambda a, b_: (ops.apply_rotary(a, cos, sin) ** 2).sum()
        + (ops.apply_rotary(b_, cos, sin) ** 2).sum(),
        argnums=(0, 1))(q, k)
    for a, r in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=GRAD_TOL)


@pytest.mark.parametrize("seq", [300, 1100, 40])
def test_fused_rotary_overhanging_row_block(seq):
    """At 32 heads x 128 the row budget is 32 rows: seq 300 and 1100 have
    no multiple-of-8 divisor under it, so the last block overhangs the
    sequence (40 blocks evenly by 8).  Rows rotate on their own, so the
    overhang must change nothing — values and gradients as XLA's."""
    assert (rotary._fit_seq(seq, 32 * 128) % 8 == 0
            and rotary.compatible((1, seq, 32, 128), (1, seq, 8, 128)))
    q, k = _rand((1, seq, 32, 128), 0), _rand((1, seq, 8, 128), 1)
    cos, sin = ops.build_rope_cache(seq, 128)

    def loss(use_pallas):
        def f(q, k):
            qr, kr = ops.apply_rotary_qk(q, k, cos, sin,
                                         use_pallas=use_pallas)
            return (qr ** 2).sum() + (kr * k).sum(), (qr, kr)
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(q, k)
    ((_, fused), gf), ((_, ref), gr) = loss(True), loss(False)
    for a, b in zip(fused + gf, ref + gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=GRAD_TOL)


def test_dispatcher_rotary_position_ids(monkeypatch):
    """ops.apply_rotary_qk with explicit per-row position_ids matches
    the two seed apply_rotary calls when force-routed to the kernel."""
    b, s, hd = 2, 8, 128
    q, k = _rand((b, s, 4, hd), 0), _rand((b, s, 2, hd), 1)
    cos, sin = ops.build_rope_cache(32, hd)
    pos = jnp.asarray([[3, 5, 7, 9, 11, 13, 15, 17],
                       [0, 1, 2, 3, 4, 5, 6, 7]], jnp.int32)
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    qr, kr = ops.apply_rotary_qk(q, k, cos, sin, pos)
    monkeypatch.setenv("HETU_TPU_PALLAS", "0")
    q0, k0 = ops.apply_rotary_qk(q, k, cos, sin, pos)
    np.testing.assert_allclose(np.asarray(qr), np.asarray(q0), atol=FWD_TOL)
    np.testing.assert_allclose(np.asarray(kr), np.asarray(k0), atol=FWD_TOL)


@pytest.mark.parametrize("bits", [8, 4])
def test_fused_quantize_bit_identical(bits, monkeypatch):
    """The Pallas quantize is BIT-identical to the jnp chain (same
    absmax scale, same round-half-to-even, same 1e-12 floor), so every
    comm/compress consumer inherits it transparently."""
    from hetu_tpu.comm import compress
    x = _rand((4, 512), 0) * 3.0
    q, s = quant.quantize_blockwise_pallas(x, 256, bits=bits)
    monkeypatch.setenv("HETU_TPU_PALLAS", "0")
    qr, sr = compress.quantize_blockwise(x, 256, bits=bits)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-7)
    y = quant.dequantize_blockwise_pallas(q, s)
    yr = compress.dequantize_blockwise(qr, sr)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-6)


def test_quantize_dispatcher_routes(monkeypatch):
    """comm/compress.quantize_blockwise routes through the kernel under
    the flag and stays bit-identical; stochastic rounding keeps the XLA
    path (it needs a threaded rng)."""
    from hetu_tpu.comm import compress
    x = _rand((2, 1024), 1)
    monkeypatch.setenv("HETU_TPU_PALLAS", "0")
    q0, s0 = compress.quantize_blockwise(x, 512)
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    q1, s1 = compress.quantize_blockwise(x, 512)
    np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), rtol=1e-7)
    # stochastic mode must not hit the kernel (and must still work)
    qs, ss = compress.quantize_blockwise(
        x, 512, stochastic=True, rng=jax.random.key(0))
    assert qs.shape == q0.shape


def test_quantize_dispatcher_forced_loud(monkeypatch):
    """Forced mode never silently falls back (the flash contract): a
    gate-rejected shape under HETU_TPU_PALLAS=1 raises instead of
    running the jnp chain, for quantize AND dequantize."""
    from hetu_tpu.comm import compress
    x = _rand((2, 96), 1)          # block 96: not lane-aligned (% 128)
    monkeypatch.setenv("HETU_TPU_PALLAS", "0")
    q, s = compress.quantize_blockwise(x, 96)
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    with pytest.raises(ValueError, match="lane-aligned"):
        compress.quantize_blockwise(x, 96)
    with pytest.raises(ValueError, match="lane-aligned"):
        compress.dequantize_blockwise(q, s)
    # auto mode on CPU: silent exact fallback, as before
    monkeypatch.setenv("HETU_TPU_PALLAS", "auto")
    np.testing.assert_array_equal(
        np.asarray(compress.dequantize_blockwise(q, s)),
        np.asarray((q.astype(jnp.float32) * s[:, None]).reshape(-1)))


def _dense_paged_reference(q, kp, vp, table, positions, window=None):
    S, nq, hd = q.shape
    _, ps, n_kv, _ = kp.shape
    mp = table.shape[1]
    group = nq // n_kv
    outs = []
    for si in range(S):
        ks = jnp.concatenate([kp[table[si, p]] for p in range(mp)], axis=0)
        vs = jnp.concatenate([vp[table[si, p]] for p in range(mp)], axis=0)
        kg = jnp.repeat(ks, group, axis=1)
        vg = jnp.repeat(vs, group, axis=1)
        M = mp * ps
        s = jnp.einsum("qd,kqd->qk", q[si],
                       kg.reshape(M, nq, hd)) * hd ** -0.5
        mask = jnp.arange(M) <= positions[si]
        if window is not None:
            mask = mask & (jnp.arange(M) > positions[si] - window)
        s = jnp.where(mask[None, :], s, -1e30)
        p_ = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("qk,kqd->qd", p_, vg.reshape(M, nq, hd)))
    return jnp.stack(outs)


def test_paged_attention_parity():
    """Kernel vs the dense gather+mask reference: GQA grouping, per-slot
    depths, null-page (id 0) masking for short/inactive slots."""
    rng = np.random.default_rng(3)
    S, P, ps, n_kv, nq, hd = 3, 9, 8, 2, 4, 128
    kp = jnp.asarray(rng.standard_normal((P, ps, n_kv, hd),
                                         dtype=np.float32))
    vp = jnp.asarray(rng.standard_normal((P, ps, n_kv, hd),
                                         dtype=np.float32))
    q = jnp.asarray(rng.standard_normal((S, nq, hd), dtype=np.float32))
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 0]],
                        jnp.int32)
    positions = jnp.asarray([20, 9, 17], jnp.int32)
    out = paged_attention.paged_attention(q, kp, vp, table, positions)
    ref = _dense_paged_reference(q, kp, vp, table, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=FWD_TOL)


def test_paged_attention_int8_parity():
    """int8 pages (PR 15): the kernel's in-VMEM dequantize matches the
    gather path's dequantize-then-attend over the SAME quantized pool —
    the parity half of closing the exact-fp-pages-only gap."""
    from hetu_tpu.serving.kv_pool import quantize_heads, dequantize_heads
    rng = np.random.default_rng(5)
    S, P, ps, n_kv, nq, hd = 3, 9, 8, 2, 4, 128
    kp32 = jnp.asarray(rng.standard_normal((P, ps, n_kv, hd),
                                           dtype=np.float32))
    vp32 = jnp.asarray(rng.standard_normal((P, ps, n_kv, hd),
                                           dtype=np.float32))
    q = jnp.asarray(rng.standard_normal((S, nq, hd), dtype=np.float32))
    kq, ks = quantize_heads(kp32)
    vq, vs = quantize_heads(vp32)
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 0]],
                        jnp.int32)
    positions = jnp.asarray([20, 9, 17], jnp.int32)
    out = paged_attention.paged_attention(q, kq, vq, table, positions,
                                          k_scale=ks, v_scale=vs)
    ref = _dense_paged_reference(q, dequantize_heads(kq, ks),
                                 dequantize_heads(vq, vs), table,
                                 positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=FWD_TOL)
    # scales must come as a pair, with the pinned layout
    with pytest.raises(ValueError, match="both k_scale and v_scale"):
        paged_attention.paged_attention(q, kq, vq, table, positions,
                                        k_scale=ks)
    with pytest.raises(ValueError, match="scales"):
        paged_attention.paged_attention(q, kq, vq, table, positions,
                                        k_scale=ks.T, v_scale=vs.T)
    # the gate accepts exactly the supported page modes
    assert paged_attention.compatible(q.shape, kq.shape, table.shape,
                                      positions.shape, quant="int8")
    assert not paged_attention.compatible(q.shape, kq.shape, table.shape,
                                          positions.shape, quant="int4")


def test_paged_attention_int4_parity():
    """int4 pages: the kernel's in-VMEM nibble unpack + dequantize
    matches the gather path's dequantize-then-attend over the SAME
    packed pool (pool head dim hd//2, one f32 scale per head-vector)."""
    from hetu_tpu.serving.kv_pool import quantize_heads, dequantize_heads
    rng = np.random.default_rng(7)
    S, P, ps, n_kv, nq, hd = 3, 9, 8, 2, 4, 128
    kp32 = jnp.asarray(rng.standard_normal((P, ps, n_kv, hd),
                                           dtype=np.float32))
    vp32 = jnp.asarray(rng.standard_normal((P, ps, n_kv, hd),
                                           dtype=np.float32))
    q = jnp.asarray(rng.standard_normal((S, nq, hd), dtype=np.float32))
    kq, ks = quantize_heads(kp32, bits=4)
    vq, vs = quantize_heads(vp32, bits=4)
    assert kq.shape == (P, ps, n_kv, hd // 2) and kq.dtype == jnp.uint8
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 0]],
                        jnp.int32)
    positions = jnp.asarray([20, 9, 17], jnp.int32)
    out = paged_attention.paged_attention(q, kq, vq, table, positions,
                                          k_scale=ks, v_scale=vs,
                                          quant="int4")
    ref = _dense_paged_reference(q, dequantize_heads(kq, ks, bits=4),
                                 dequantize_heads(vq, vs, bits=4), table,
                                 positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=FWD_TOL)
    # the packed pool is an int4 pool, NOT an int8 one
    assert paged_attention.compatible(q.shape, kq.shape, table.shape,
                                      positions.shape, quant="int4")
    assert not paged_attention.compatible(q.shape, kq.shape, table.shape,
                                          positions.shape, quant="int8")


def _dense_verify_reference(q, kp, vp, table, positions):
    """[S, C, nq, hd] multi-query verify reference: query j of slot s
    sits at global position positions[s] + j and attends causally."""
    S, C, nq, hd = q.shape
    return jnp.stack([
        jnp.stack([_dense_paged_reference(
            q[si:si + 1, j], kp, vp, table[si:si + 1],
            positions[si:si + 1] + j)[0] for j in range(C)])
        for si in range(S)])


@pytest.mark.parametrize("quant", ["none", "int8", "int4"])
def test_paged_verify_parity(quant):
    """Multi-query verify kernel vs the per-position dense reference:
    the k+1 query positions share one pass over the pages with
    per-position causal masking — all three page modes."""
    from hetu_tpu.serving.kv_pool import quantize_heads, dequantize_heads
    rng = np.random.default_rng(9)
    S, C, P, ps, n_kv, nq, hd = 3, 3, 9, 8, 2, 4, 128
    kp32 = jnp.asarray(rng.standard_normal((P, ps, n_kv, hd),
                                           dtype=np.float32))
    vp32 = jnp.asarray(rng.standard_normal((P, ps, n_kv, hd),
                                           dtype=np.float32))
    q = jnp.asarray(rng.standard_normal((S, C, nq, hd), dtype=np.float32))
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 0]],
                        jnp.int32)
    positions = jnp.asarray([18, 7, 15], jnp.int32)
    if quant == "none":
        out = paged_attention.paged_verify(q, kp32, vp32, table, positions)
        ref = _dense_verify_reference(q, kp32, vp32, table, positions)
    else:
        bits = 8 if quant == "int8" else 4
        kq, ks = quantize_heads(kp32, bits=bits)
        vq, vs = quantize_heads(vp32, bits=bits)
        out = paged_attention.paged_verify(q, kq, vq, table, positions,
                                           k_scale=ks, v_scale=vs,
                                           quant=quant)
        ref = _dense_verify_reference(
            q, dequantize_heads(kq, ks, bits=bits),
            dequantize_heads(vq, vs, bits=bits), table, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=FWD_TOL)


# the walk (PR 28): a slot's live pages in blocks, fetched by the kernel's
# own copies.  Each case is (q heads, kv heads, page size, table width,
# tokens a block holds (None: the module's own), tokens each slot holds
# (1 = an idle slot: position 0 over a null table row), pool dtype,
# page ids permuted, tolerance).
_WALK_CASES = {
    # 21 = two pages and five tokens of a third: the context ends mid-page
    "ends_mid_page": (4, 2, 8, 8, 24, [21, 5, 38], "float32", False),
    # a block is 3 pages x 8 tokens: one, two and three whole blocks
    "ends_on_a_block_boundary": (4, 2, 8, 9, 24, [24, 48, 72], "float32",
                                 False),
    "one_token_over_a_null_row": (4, 2, 8, 8, 24, [1, 30, 1], "float32",
                                  False),
    "slot_at_max_pages_full": (4, 2, 8, 6, 24, [48, 48, 17], "float32",
                               False),
    # 7 page slots in blocks of 3: the last block is one page wide
    "table_not_a_multiple_of_the_block": (4, 2, 8, 7, 24, [56, 50, 9],
                                          "float32", False),
    "permuted_page_ids": (4, 2, 8, 8, 24, [21, 64, 38], "float32", True),
    "group_2_16q_8kv": (16, 8, 8, 8, 24, [21, 1, 60], "float32", True),
    "group_4_32q_8kv": (32, 8, 8, 8, 24, [21, 1, 60], "float32", True),
    "one_page_a_block": (4, 2, 8, 8, 8, [21, 1, 64], "float32", True),
    # the module's own block (256 tokens = 16 pages of 16): one block,
    # two, and two with a single live page in the second
    "the_modules_own_block": (4, 2, 16, 40, None, [300, 1, 257, 512],
                              "float32", True),
    # bfloat16 pages against the float32 reference: the products round
    # q, k, p and v to bfloat16 (8 bits of mantissa: 2**-8 relative)
    "bfloat16_pools": (16, 8, 16, 8, 48, [21, 100, 1], "bfloat16", True),
}


def _walk_inputs(case, seed=11):
    nq, n_kv, ps, mp, block_tokens, lengths, dtype, permute = \
        _WALK_CASES[case]
    rng = np.random.default_rng(seed)
    S, hd = len(lengths), 128
    P = S * mp + 1
    ids = np.arange(1, P)
    if permute:
        ids = rng.permutation(ids)
    table = ids.reshape(S, mp).astype(np.int32)
    for s, n in enumerate(lengths):
        if n == 1:
            table[s] = 0        # an idle slot: position 0, the null row
    dt = jnp.dtype(dtype)
    kp = jnp.asarray(rng.standard_normal((P, ps, n_kv, hd),
                                         dtype=np.float32)).astype(dt)
    vp = jnp.asarray(rng.standard_normal((P, ps, n_kv, hd),
                                         dtype=np.float32)).astype(dt)
    q = jnp.asarray(rng.standard_normal((S, nq, hd),
                                        dtype=np.float32)).astype(dt)
    positions = jnp.asarray(np.asarray(lengths) - 1, jnp.int32)
    return q, kp, vp, jnp.asarray(table), positions, block_tokens


@pytest.mark.parametrize("case", _WALK_CASES)
def test_paged_attention_walk_parity(case, monkeypatch):
    """The block walk against the dense gather+mask reference, a case
    per edge of the walk."""
    q, kp, vp, table, positions, block_tokens = _walk_inputs(case)
    if block_tokens is not None:
        monkeypatch.setattr(paged_attention, "_BLOCK_TOKENS", block_tokens)
    ps, mp = kp.shape[1], table.shape[1]
    ppb = paged_attention.pages_per_block(
        q.shape[1], ps, kp.shape[2], q.shape[2], kp.dtype.itemsize, mp)
    assert ppb == min((block_tokens or 256) // ps, mp)
    out = paged_attention.paged_attention(q, kp, vp, table, positions)
    f32 = jnp.float32
    ref = _dense_paged_reference(q.astype(f32), kp.astype(f32),
                                 vp.astype(f32), table, positions)
    assert out.dtype == q.dtype
    # float32 pools: float32 products.  bfloat16 pools: the output is
    # bfloat16 (2**-8 relative at |out| <= ~1.5) over bfloat16 products
    tol = FWD_TOL if kp.dtype == f32 else 2e-2
    np.testing.assert_allclose(np.asarray(out.astype(f32)),
                               np.asarray(ref), atol=tol)


@pytest.mark.parametrize("kernel", ["decode", "verify_c3"])
def test_paged_walk_reads_nothing_past_a_slots_length(kernel, monkeypatch):
    """Every table entry names a page of its own; every page past a
    slot's length, and the tail of its last live page, is NaN (the null
    page kept): the result is what it was, and finite."""
    monkeypatch.setattr(paged_attention, "_BLOCK_TOKENS", 24)
    rng = np.random.default_rng(13)
    C = 3 if kernel == "verify_c3" else 1
    S, ps, mp, n_kv, nq, hd = 4, 8, 8, 2, 4, 128
    P = S * mp + 1
    table = rng.permutation(np.arange(1, P)).reshape(S, mp).astype(np.int32)
    lengths = np.asarray([21, C, 48, 58])       # up to the LAST query position
    kp = rng.standard_normal((P, ps, n_kv, hd), dtype=np.float32)
    vp = rng.standard_normal((P, ps, n_kv, hd), dtype=np.float32)
    kn, vn = kp.copy(), vp.copy()
    for s, n in enumerate(lengths):
        for slot in range(mp):
            live = min(max(n - slot * ps, 0), ps)
            kn[table[s, slot], live:] = np.nan
            vn[table[s, slot], live:] = np.nan
    assert np.isfinite(kn[0]).all() and np.isnan(kn).sum() > kn.size // 3
    positions = jnp.asarray(lengths - C, jnp.int32)
    table = jnp.asarray(table)
    if C == 1:
        q = jnp.asarray(rng.standard_normal((S, nq, hd), dtype=np.float32))
        fn, ref = paged_attention.paged_attention, _dense_paged_reference
    else:
        q = jnp.asarray(rng.standard_normal((S, C, nq, hd),
                                            dtype=np.float32))
        fn, ref = paged_attention.paged_verify, _dense_verify_reference
    clean = fn(q, jnp.asarray(kp), jnp.asarray(vp), table, positions)
    poisoned = fn(q, jnp.asarray(kn), jnp.asarray(vn), table, positions)
    assert np.isfinite(np.asarray(poisoned)).all()
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(clean))
    np.testing.assert_allclose(
        np.asarray(clean),
        np.asarray(ref(q, jnp.asarray(kp), jnp.asarray(vp), table,
                       positions)), atol=FWD_TOL)


# a WINDOW (PR 34): the walk begins at the block that holds the window's
# first position.  (q heads, kv heads, page size, table width, tokens a
# block holds, tokens each slot holds, window, pool dtype).  The cases of
# `_WALK_CASES` above are `window=None`, unchanged.
_WINDOW_CASES = {
    # g = 1, 4, 8; a block is 3 pages of 8
    "g1_window_inside_one_block": (4, 4, 8, 8, 24, [21, 5, 38], 6,
                                   "float32"),
    "g4_window_starts_mid_page": (32, 8, 8, 8, 24, [21, 1, 60], 19,
                                  "float32"),
    "g8_window_starts_mid_page": (32, 4, 8, 8, 24, [21, 1, 60], 19,
                                  "float32"),
    # position 47, window 24: first position 24 = the first row of block 1
    "g8_window_starts_on_a_block": (32, 4, 8, 9, 24, [48, 72, 30], 24,
                                    "float32"),
    # first and last block differ, with whole blocks between them
    "g8_window_over_several_blocks": (32, 4, 8, 16, 24, [128, 100, 77], 70,
                                      "float32"),
    "g8_window_of_one_position": (32, 4, 8, 8, 24, [21, 1, 60], 1,
                                  "float32"),
    "g8_window_wider_than_any_context": (32, 4, 8, 8, 24, [21, 1, 60], 500,
                                         "float32"),
    "g8_one_page_a_block": (32, 4, 8, 8, 8, [21, 1, 64], 13, "float32"),
    # the cell's own: 16-token pages, the module's block, bfloat16
    "g8_the_cells_shape_bfloat16": (32, 4, 16, 40, None,
                                    [300, 1, 257, 640], 200, "bfloat16"),
}


def _window_inputs(case):
    nq, n_kv, ps, mp, block_tokens, lengths, window, dtype = \
        _WINDOW_CASES[case]
    _WALK_CASES["_window"] = (nq, n_kv, ps, mp, block_tokens, lengths,
                              dtype, True)
    try:
        return _walk_inputs("_window", seed=17) + (window,)
    finally:
        del _WALK_CASES["_window"]


@pytest.mark.parametrize("case", _WINDOW_CASES)
def test_paged_attention_window_parity(case, monkeypatch):
    """The walk under a window against the dense gather+mask
    composition; what lies wholly before the window is the null page in
    the table, as the scheduler leaves it."""
    q, kp, vp, table, positions, block_tokens, window = _window_inputs(case)
    if block_tokens is not None:
        monkeypatch.setattr(paged_attention, "_BLOCK_TOKENS", block_tokens)
    ps = kp.shape[1]
    released = np.asarray(table).copy()
    for s, pos in enumerate(np.asarray(positions)):
        released[s, : max(pos - window + 1, 0) // ps] = 0
    out = paged_attention.paged_attention(q, kp, vp, jnp.asarray(released),
                                          positions, window=window)
    f32 = jnp.float32
    ref = _dense_paged_reference(q.astype(f32), kp.astype(f32),
                                 vp.astype(f32), table, positions, window)
    assert out.dtype == q.dtype
    tol = FWD_TOL if kp.dtype == f32 else 2e-2
    np.testing.assert_allclose(np.asarray(out.astype(f32)),
                               np.asarray(ref), atol=tol)


def test_paged_walk_under_a_window_reads_nothing_before_it(monkeypatch):
    """Every page wholly before a slot's window, the part of the
    window's first page that precedes it, and everything past the slot's
    length are NaN: the result is what it was, and finite."""
    monkeypatch.setattr(paged_attention, "_BLOCK_TOKENS", 24)
    rng = np.random.default_rng(19)
    S, ps, mp, n_kv, nq, hd, window = 4, 8, 12, 4, 32, 128, 29
    P = S * mp + 1
    table = rng.permutation(np.arange(1, P)).reshape(S, mp).astype(np.int32)
    lengths = np.asarray([21, 1, 90, 58])
    kp = rng.standard_normal((P, ps, n_kv, hd), dtype=np.float32)
    vp = rng.standard_normal((P, ps, n_kv, hd), dtype=np.float32)
    kn, vn = kp.copy(), vp.copy()
    for s, n in enumerate(lengths):
        first = max(n - window, 0)              # the window's first position
        for slot in range(mp):
            for x in (kn, vn):
                page = x[table[s, slot]]
                at = slot * ps + np.arange(ps)
                page[(at < first) | (at >= n)] = np.nan
    assert np.isnan(kn).sum() > kn.size // 2
    positions, table = jnp.asarray(lengths - 1, jnp.int32), jnp.asarray(table)
    q = jnp.asarray(rng.standard_normal((S, nq, hd), dtype=np.float32))
    clean = paged_attention.paged_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), table, positions, window=window)
    poisoned = paged_attention.paged_attention(
        q, jnp.asarray(kn), jnp.asarray(vn), table, positions, window=window)
    assert np.isfinite(np.asarray(poisoned)).all()
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(clean))
    np.testing.assert_allclose(
        np.asarray(clean), np.asarray(_dense_paged_reference(
            q, jnp.asarray(kp), jnp.asarray(vp), table, positions, window)),
        atol=FWD_TOL)


@pytest.mark.parametrize("window,quant", [(8, "none"), (1, "none"),
                                          (0, "none"), (8, "int8")])
def test_gate_drift_paged_window(window, quant):
    """`compatible` under a window says what the kernel does: a window of
    at least one position over exact pages."""
    qs, pool_s, ts, pos_s = (3, 4, 128), (9, 8, 2, 128), (3, 4), (3,)
    q = jnp.zeros(qs, jnp.float32)
    kp = jnp.zeros(pool_s, jnp.int8 if quant == "int8" else jnp.float32)
    scales = ({"k_scale": jnp.ones(pool_s[:3]),
               "v_scale": jnp.ones(pool_s[:3])} if quant == "int8" else {})
    takes = paged_attention.compatible(qs, pool_s, ts, pos_s, quant=quant,
                                       window=window)
    assert takes == (window >= 1 and quant == "none")
    assert takes == _accepts(
        lambda *a: paged_attention.paged_attention(*a, window=window,
                                                   **scales),
        q, kp, kp, jnp.zeros(ts, jnp.int32), jnp.zeros(pos_s, jnp.int32))


# ---------------------------------------------------------------------------
# gate/kernel drift: the gate's verdict must MATCH what the kernel
# actually accepts (satellite 2 — extended to every kernel's gate)
# ---------------------------------------------------------------------------

def _accepts(fn, *args):
    """Does the kernel accept these shapes?  eval_shape traces the
    pallas_call without running it; the entry validation's ValueError is
    the (only) rejection signal."""
    try:
        jax.eval_shape(fn, *args)
        return True
    except ValueError:
        return False


_NORM_SHAPES = [(16, 256), (8, 128), (16, 200), (12, 256), (3, 128),
                (2, 8, 128)]


@pytest.mark.parametrize("shape", _NORM_SHAPES)
def test_gate_drift_norm(shape):
    x = jnp.zeros(shape, jnp.float32)
    w = jnp.zeros((shape[-1],), jnp.float32)
    gate = fused_norm.compatible(x.shape, x.shape, w.shape)
    assert gate == _accepts(
        lambda x, h, w: fused_norm.fused_residual_rmsnorm(x, h, w), x, x, w)
    assert gate == _accepts(
        lambda x, h, w: fused_norm.fused_residual_layernorm(x, h, w, None),
        x, x, w)


@pytest.mark.parametrize("shape", _NORM_SHAPES)
def test_gate_drift_swiglu(shape):
    g = jnp.zeros(shape, jnp.float32)
    assert swiglu.compatible(g.shape, g.shape) == _accepts(
        swiglu.fused_swiglu, g, g)


@pytest.mark.parametrize("qk", [
    ((2, 8, 4, 128), (2, 8, 2, 128)),
    ((2, 8, 4, 64), (2, 8, 2, 64)),      # hd not lane-aligned
    ((2, 8, 4, 128), (2, 4, 2, 128)),    # seq mismatch
    ((1, 3, 2, 256), (1, 3, 2, 256)),
])
def test_gate_drift_rotary(qk):
    qs, ks = qk
    q, k = jnp.zeros(qs, jnp.float32), jnp.zeros(ks, jnp.float32)
    d2 = qs[-1] // 2
    cos = jnp.zeros((qs[0], qs[1], d2), jnp.float32)
    assert rotary.compatible(qs, ks) == _accepts(
        rotary.fused_rotary_qk, q, k, cos, cos)


@pytest.mark.parametrize("n,bs,bits", [
    (1024, 256, 8), (1024, 256, 4), (1024, 100, 8), (1000, 256, 8),
    (1024, 256, 3),
])
def test_gate_drift_quant(n, bs, bits):
    x = jnp.zeros((n,), jnp.float32)
    assert quant.compatible(n, bs, bits) == _accepts(
        lambda x: quant.quantize_blockwise_pallas(x, bs, bits=bits), x)


@pytest.mark.parametrize("shapes", [
    ((3, 4, 128), (9, 8, 2, 128), (3, 4), (3,)),
    ((3, 4, 64), (9, 8, 2, 64), (3, 4), (3,)),     # hd unaligned
    ((3, 3, 128), (9, 8, 2, 128), (3, 4), (3,)),   # heads not divisible
    ((3, 4, 128), (9, 8, 2, 128), (2, 4), (3,)),   # table/slot mismatch
])
def test_gate_drift_paged(shapes):
    qs, pool_s, ts, pos_s = shapes
    q = jnp.zeros(qs, jnp.float32)
    kp = jnp.zeros(pool_s, jnp.float32)
    table = jnp.zeros(ts, jnp.int32)
    pos = jnp.zeros(pos_s, jnp.int32)
    assert paged_attention.compatible(qs, pool_s, ts, pos_s) == _accepts(
        paged_attention.paged_attention, q, kp, kp, table, pos)


@pytest.mark.parametrize("shapes,dtype,takes", [
    (((3, 4, 128), (9, 8, 2, 128), (3, 4), (3,)), "bfloat16", True),
    # one bfloat16 KV head: a token's row is under a 32-bit word, and the
    # kernel reads the pages as [page_size, hd] (refused until PR 47)
    (((3, 4, 128), (9, 8, 1, 128), (3, 4), (3,)), "bfloat16", True),
    (((3, 4, 128), (9, 8, 1, 128), (3, 4), (3,)), "float32", True),
    # one page alone over the kernel's VMEM limit (33 MB of buffers)
    (((3, 32, 128), (9, 512, 32, 128), (3, 4), (3,)), "float32", False),
    (((3, 32, 128), (9, 128, 32, 128), (3, 4), (3,)), "bfloat16", True),
])
def test_gate_drift_paged_pool_dtype(shapes, dtype, takes):
    """The gate told the pool's dtype (as the engine tells it) agrees
    with the kernel on the limits that depend on the element size."""
    qs, pool_s, ts, pos_s = shapes
    q = jnp.zeros(qs, dtype)
    kp = jnp.zeros(pool_s, dtype)
    table = jnp.zeros(ts, jnp.int32)
    pos = jnp.zeros(pos_s, jnp.int32)
    for gate, fn, q_ in (
            (paged_attention.compatible, paged_attention.paged_attention, q),
            (paged_attention.verify_compatible,
             paged_attention.paged_verify, q[:, None])):
        assert gate(q_.shape, pool_s, ts, pos_s, pool_dtype=dtype) == takes
        assert _accepts(fn, q_, kp, kp, table, pos) == takes


@pytest.mark.parametrize("shapes", [
    ((3, 3, 4, 128), (9, 8, 2, 128), (3, 4), (3,)),
    ((3, 3, 4, 64), (9, 8, 2, 64), (3, 4), (3,)),    # hd unaligned
    ((3, 3, 3, 128), (9, 8, 2, 128), (3, 4), (3,)),  # heads not divisible
    ((2, 3, 4, 128), (9, 8, 2, 128), (3, 4), (3,)),  # table/slot mismatch
    ((3, 4, 128), (9, 8, 2, 128), (3, 4), (3,)),     # missing C dim
])
def test_gate_drift_paged_verify(shapes):
    qs, pool_s, ts, pos_s = shapes
    q = jnp.zeros(qs, jnp.float32)
    kp = jnp.zeros(pool_s, jnp.float32)
    table = jnp.zeros(ts, jnp.int32)
    pos = jnp.zeros(pos_s, jnp.int32)
    assert paged_attention.verify_compatible(qs, pool_s, ts, pos_s) == \
        _accepts(paged_attention.paged_verify, q, kp, kp, table, pos)


@pytest.mark.parametrize("hw", [
    ((8, 128), (128, 256)),
    ((8, 100), (100, 256)),     # hidden unaligned
    ((8, 128), (128, 200)),     # vocab unaligned
    ((8, 128), (64, 256)),      # hidden dim mismatch
])
def test_gate_drift_sample(hw):
    from hetu_tpu.ops.pallas import sample as psample
    hs, ws = hw
    h = jnp.zeros(hs, jnp.float32)
    w = jnp.zeros(ws, jnp.float32)
    R = hs[0]
    words = jnp.zeros((R, 2), jnp.uint32)
    t = jnp.ones((R,), jnp.float32)
    k = jnp.zeros((R,), jnp.int32)
    p = jnp.zeros((R,), jnp.float32)
    assert psample.compatible(hs, ws) == _accepts(
        psample.fused_sample, h, w, words, t, k, p)


@pytest.mark.parametrize("sq,sk,d", [
    (256, 256, 128), (256, 256, 64), (100, 256, 128), (8, 8, 128),
])
def test_gate_drift_flash(sq, sk, d):
    """ops.attention.flash_attention routes on the kernel module's own
    `compatible` — pin that the verdict matches the public entry's
    acceptance under the default block geometry."""
    from hetu_tpu.ops.pallas import flash_attention as fa
    q = jnp.zeros((1, sq, 2, d), jnp.float32)
    k = jnp.zeros((1, sk, 2, d), jnp.float32)
    gate = fa.compatible(q.shape, k.shape)
    assert gate == _accepts(
        lambda q, k: fa.flash_attention(q, k, k, causal=False), q, k)


# ---------------------------------------------------------------------------
# routing surface
# ---------------------------------------------------------------------------

def test_kernel_routing_flags(monkeypatch):
    monkeypatch.delenv("HETU_TPU_PALLAS", raising=False)
    monkeypatch.delenv("HETU_TPU_PALLAS_KERNELS", raising=False)
    for name in KERNEL_NAMES:
        assert kernel_enabled(name) is None          # auto
        # auto on CPU resolves to the fallback, whatever the gate says
        assert resolve_route(name, lambda: None) is False
    monkeypatch.setenv("HETU_TPU_PALLAS", "0")
    assert all(kernel_enabled(n) is False for n in KERNEL_NAMES)
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    assert all(kernel_enabled(n) is True for n in KERNEL_NAMES)
    # per-kernel bisect: only the named kernels participate
    monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", "flash,quant")
    assert kernel_enabled("flash") is True
    assert kernel_enabled("norm") is False
    monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", "none")
    assert all(kernel_enabled(n) is False for n in KERNEL_NAMES)
    monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", "nope")
    with pytest.raises(ValueError):
        kernel_enabled("flash")
    monkeypatch.delenv("HETU_TPU_PALLAS_KERNELS")
    with pytest.raises(ValueError):
        kernel_enabled("not_a_kernel")


def _tiny_llama(hd128=False, **kw):
    from hetu_tpu.models.llama import LlamaConfig
    from hetu_tpu.models.llama.model import LlamaLMHeadModel
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2,
                max_position_embeddings=256, use_flash_attention=False,
                compute_dtype=jnp.float32, param_dtype=jnp.float32,
                remat=False, use_scan=True)
    if hd128:
        base.update(hidden_size=256, num_attention_heads=2,
                    num_key_value_heads=2)
    base.update(kw)
    cfg = LlamaConfig(**base)
    model = LlamaLMHeadModel(cfg)
    return model, model.init(jax.random.key(0))


def _tiny_gpt():
    from hetu_tpu.models.gpt.model import GPTConfig, GPTLMHeadModel
    cfg = GPTConfig.tiny(compute_dtype=jnp.float32,
                         param_dtype=jnp.float32, remat=False,
                         use_flash_attention=False)
    model = GPTLMHeadModel(cfg)
    return model, model.init(jax.random.key(0))


def test_model_forced_pallas_parity():
    """Whole-model parity: llama train loss + grads with every kernel
    force-routed (interpret mode) match the XLA path."""
    import os
    model, params = _tiny_llama(hd128=True)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 255, (2, 16)),
                      jnp.int32)

    def loss(p):
        return model(p, ids, labels=ids)

    os.environ["HETU_TPU_PALLAS"] = "0"
    try:
        l0, g0 = jax.value_and_grad(loss)(params)
        os.environ["HETU_TPU_PALLAS"] = "1"
        l1, g1 = jax.value_and_grad(loss)(params)
    finally:
        del os.environ["HETU_TPU_PALLAS"]
    assert abs(float(l0) - float(l1)) < 1e-4
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


# ---------------------------------------------------------------------------
# under a multi-device mesh: once per shard, on the declared layouts
# ---------------------------------------------------------------------------
# The TPU lowering refuses a Mosaic call in a program GSPMD partitions, so
# under a mesh the dispatchers wrap the kernel in a shard_map over the
# layouts their caller declares (ops/pallas.per_shard).  Interpret mode
# would let GSPMD partition the kernel body instead, so these force the
# kernels on and check both that the per-shard region is there and that it
# computes what the XLA composition computes on one device — gradients
# included (the gain's cotangent is summed over the mesh by the
# shard_map's transpose).

def _dp2_tp2(devices):
    from hetu_tpu.core.mesh import MeshConfig
    from hetu_tpu.parallel import ParallelStrategy
    st = ParallelStrategy(mesh=MeshConfig(dp=2, tp=2),
                          sequence_parallel=True, zero=True)
    return st, st.build_mesh(devices[:4])


def _per_shard_case(name, st):
    """-> (fn(use_pallas, layouts_on) -> loss over *args, args)"""
    b, s, hd = 2, 128, 128
    if name == "swiglu":
        args = (_rand((b, s, 512), 0), _rand((b, s, 512), 1))

        def fn(on, lay):
            return lambda g, u: ops.swiglu(
                g, u, use_pallas=on, layout=st.act_inner() if lay else None)
    elif name == "norm":
        args = (_rand((b, s, 256), 0), _rand((b, s, 256), 1),
                _rand((256,), 2))

        def fn(on, lay):
            return lambda x, h, w: ops.residual_rms_norm(
                x, h, w, use_pallas=on,
                layout=st.act_hidden() if lay else None)
    elif name == "rotary":
        args = (_rand((b, s, 4, hd), 0), _rand((b, s, 2, hd), 1))
        cos, sin = ops.build_rope_cache(s, hd)

        def fn(on, lay):
            return lambda q, k: ops.apply_rotary_qk(
                q, k, cos, sin, use_pallas=on,
                layout=st.act_attn() if lay else None)
    else:
        assert name == "flash", name
        args = (_rand((b, s, 4, hd), 0), _rand((b, s, 2, hd), 1),
                _rand((b, s, 2, hd), 2))
        seg = jnp.asarray(np.repeat([[0] * 64 + [1] * 64], b, 0), jnp.int32)

        def fn(on, lay):
            return lambda q, k, v: ops.flash_attention(
                q, k, v, segment_ids=seg, use_pallas=on,
                layout=st.act_attn() if lay else None)

    def loss(on, lay):
        def f(*a):
            outs = jax.tree.leaves(fn(on, lay)(*a))
            return sum((o * jnp.cos(o)).sum() for o in outs)
        return jax.value_and_grad(f, argnums=tuple(range(len(args))))
    return loss, args


@pytest.mark.parametrize("name", ["swiglu", "norm", "rotary", "flash"])
def test_kernel_runs_per_shard_under_a_mesh(name, devices):
    from hetu_tpu.core.mesh import use_mesh
    st, mesh = _dp2_tp2(devices)
    loss, args = _per_shard_case(name, st)
    want, want_g = loss(False, False)(*args)          # XLA, one device
    with use_mesh(mesh):
        sharded = jax.jit(loss(True, True))
        assert "manual_computation" in sharded.lower(*args).as_text()
        got, got_g = sharded(*args)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=GRAD_TOL)


def test_flash_refuses_a_layout_that_shards_the_sequence(devices):
    from hetu_tpu.core.mesh import MeshConfig, use_mesh
    from hetu_tpu.parallel import ParallelStrategy
    st = ParallelStrategy(mesh=MeshConfig(cp=2))
    q = _rand((2, 128, 2, 128))
    with use_mesh(st.build_mesh(devices[:2])):
        with pytest.raises(ValueError, match="shards the sequence"):
            ops.flash_attention(q, q, q, use_pallas=True,
                                layout=st.act_attn())


def test_trainer_runs_every_kernel_per_shard(devices, monkeypatch):
    """The whole step under dp2 x tp2 + SP + ZeRO with every kernel forced
    on — flash, norm, swiglu, rotary per shard of the activations —
    tracks the one-device step, and the Trainer says which kernels the
    plan runs."""
    from hetu_tpu.engine.trainer import Trainer
    from hetu_tpu.engine.trainer_config import TrainingConfig
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
    from hetu_tpu.parallel import ParallelStrategy
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    cfg = LlamaConfig.tiny(hidden_size=256, num_attention_heads=2,
                           num_key_value_heads=2, intermediate_size=256,
                           compute_dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(0, 256, (2, 128), dtype=np.int32)
    batch = {"input_ids": ids, "labels": ids}
    losses = {}
    for name, st in (("single", ParallelStrategy()),
                     ("sharded", _dp2_tp2(devices)[0])):
        tc = TrainingConfig(global_batch_size=2,
                            micro_batch_size=2 // max(st.dp, 1), seq_len=128,
                            lr=1e-2, warmup_steps=1, total_steps=4,
                            log_every=10 ** 9)
        trainer = Trainer(LlamaLMHeadModel(cfg, st), tc, st).build()
        losses[name] = [float(trainer.train_step(batch)["loss"])
                        for _ in range(3)]
        routes = trainer.kernel_routes
        assert sorted(routes) == ["flash", "norm", "rotary", "swiglu"]
        assert all(r["pallas"] and not r["xla"] for r in routes.values())
        assert ("manual_computation" in trainer.lowered_step(batch)) == (
            name == "sharded")
        trainer.close()
    np.testing.assert_allclose(losses["sharded"], losses["single"],
                               rtol=1e-4)


def test_routes_are_recorded_with_their_reasons(monkeypatch, devices):
    from hetu_tpu.core.mesh import use_mesh
    monkeypatch.delenv("HETU_TPU_PALLAS", raising=False)
    st, mesh = _dp2_tp2(devices)
    g = jnp.zeros((2, 128, 512), jnp.float32)

    def trace(*a, **kw):      # routing happens while tracing; run nothing
        jax.eval_shape(lambda: ops.swiglu(*a, **kw))
    with record_routes() as log:
        trace(g, g)                                       # CPU: XLA
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        trace(g, g)                                       # gate passes
        trace(g[..., :100], g[..., :100])                 # gate refuses
        with use_mesh(mesh):
            trace(g, g)                                   # no layout
            # dp=2 cannot split a batch of 1
            trace(g[:1], g[:1], layout=st.act_inner())
    rec = log["swiglu"]
    assert (rec["pallas"], rec["xla"]) == (1, 4)
    for reason in ("multi-device mesh and the caller declares no layout",
                   "not a TPU backend",
                   "shape gate: a dim of 1 does not split 2 ways",
                   "shape gate: inner dim 100", "shape gate passes"):
        assert sum(n for w, n in rec["why"].items()
                   if w.startswith(reason)) == 1, (reason, rec["why"])
    assert sum(rec["why"].values()) == 5


def test_fused_sample_token_identity(monkeypatch):
    """The fused sampling epilogue picks the IDENTICAL tokens as the XLA
    path (both consume the same hash-Gumbel words and the same exact
    logit values via the bisection over the monotone uint32 image) —
    greedy rows, top-k, top-p and plain-temperature rows alike."""
    from hetu_tpu.ops.pallas import sample as psample
    from hetu_tpu.serving import sampling
    rng = np.random.default_rng(11)
    R, H, V = 10, 128, 256
    hidden = jnp.asarray(rng.standard_normal((R, H)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((H, V)) * 0.2, jnp.float32)
    seeds = jnp.arange(R, dtype=jnp.uint32) + 3
    positions = jnp.arange(R, dtype=jnp.int32) + 5
    temps = jnp.asarray([0.0, 1.0, 0.8, 0.0, 1.2, 1.0, 0.5, 1.0, 1.0,
                         0.9], jnp.float32)
    top_ks = jnp.asarray([0, 0, 20, 0, 5, 0, 0, 50, 0, 3], jnp.int32)
    top_ps = jnp.asarray([0.0, 0.9, 0.0, 0.0, 0.95, 0.5, 0.0, 0.0, 0.8,
                          1.0], jnp.float32)
    monkeypatch.setenv("HETU_TPU_PALLAS", "0")
    ref = sampling.sample_hidden(hidden, w, seeds, positions, temps,
                                 top_ks, top_ps)
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    assert psample.compatible(hidden.shape, w.shape)
    out = sampling.sample_hidden(hidden, w, seeds, positions, temps,
                                 top_ks, top_ps)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # greedy rows are exactly the logits argmax
    logits = np.asarray(hidden @ w)
    np.testing.assert_array_equal(np.asarray(out)[[0, 3]],
                                  logits.argmax(-1)[[0, 3]])


# ---------------------------------------------------------------------------
# HETU_TPU_PALLAS=off byte-identity (satellite 3): the fallback path
# must be the seed path — off vs unset lowers to the SAME HLO
# ---------------------------------------------------------------------------

def _lowered_train(model, params, monkeypatch, flag):
    if flag is None:
        monkeypatch.delenv("HETU_TPU_PALLAS", raising=False)
    else:
        monkeypatch.setenv("HETU_TPU_PALLAS", flag)
    ids = jnp.zeros((2, 16), jnp.int32)
    return jax.jit(
        lambda p: model(p, ids, labels=ids)).lower(params).as_text()


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_flag_off_train_step_hlo_identical(family, monkeypatch):
    model, params = (_tiny_llama() if family == "llama" else _tiny_gpt())
    base = _lowered_train(model, params, monkeypatch, None)
    off = _lowered_train(model, params, monkeypatch, "0")
    assert off == base


def test_flag_off_serving_decode_hlo_identical(monkeypatch):
    """The serving decode program (every layer's attention the
    composition over gathered pages) is byte-identical with the flag off
    vs unset — and the engine's routes say so, a traced layer."""
    from hetu_tpu.serving import ServeConfig, ServingEngine
    model, params = _tiny_llama()
    texts = {}
    for flag in (None, "0"):
        if flag is None:
            monkeypatch.delenv("HETU_TPU_PALLAS", raising=False)
        else:
            monkeypatch.setenv("HETU_TPU_PALLAS", flag)
        eng = ServingEngine(model, params,
                            ServeConfig(num_slots=2, page_size=8,
                                        max_len=32, prefill_chunk=8))
        texts[flag] = eng.lower_programs()["decode"].as_text()
        took = eng.kernel_routes["paged_attn"]
        assert took["xla"] and not took["pallas"]
        eng.close()
    assert texts["0"] == texts[None]


def test_serving_paged_decode_token_identical(monkeypatch):
    """The decode program whose layers take the Pallas kernel (interpret
    mode) emits the SAME tokens as the one whose layers take the
    composition over gathered pages, over a multi-request trace — the
    PR 7 follow-up contract."""
    import copy
    from hetu_tpu.serving import Request, ServeConfig, ServingEngine
    model, params = _tiny_llama(hd128=True)
    sc = dict(num_slots=8, page_size=8, max_len=64, prefill_chunk=8)
    reqs = [Request(rid=i,
                    prompt=list(np.random.default_rng(i).integers(
                        1, 250, size=9 + i)),
                    max_new_tokens=5, arrival_t=0.0) for i in range(4)]
    monkeypatch.setenv("HETU_TPU_PALLAS", "0")
    eng0 = ServingEngine(model, params, ServeConfig(**sc))
    def kernel(eng):
        """Whether the traced layers took the kernel (all or none)."""
        took = eng.kernel_routes["paged_attn"]
        assert bool(took["pallas"]) != bool(took["xla"])
        return bool(took["pallas"])
    r0 = eng0.run([copy.deepcopy(r) for r in reqs])
    assert not kernel(eng0)
    eng0.close()
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    eng1 = ServingEngine(model, params, ServeConfig(**sc))
    r1 = eng1.run([copy.deepcopy(r) for r in reqs])
    assert kernel(eng1)
    eng1.close()
    assert [r.tokens for r in r0] == [r.tokens for r in r1]
    # int8 page mode routes too (PR 15: in-kernel dequantize closed the
    # exact-fp-pages-only gap) and matches the int8 composition
    # token-for-token — the one program quantizes through the same
    # blockwise primitives, so pool contents are bit-identical
    eng2 = ServingEngine(model, params,
                         ServeConfig(kv_quant="int8", **sc))
    r2 = eng2.run([copy.deepcopy(r) for r in reqs])
    assert kernel(eng2)
    eng2.close()
    monkeypatch.setenv("HETU_TPU_PALLAS", "0")
    eng3 = ServingEngine(model, params,
                         ServeConfig(kv_quant="int8", **sc))
    r3 = eng3.run([copy.deepcopy(r) for r in reqs])
    assert not kernel(eng3)
    eng3.close()
    assert [r.tokens for r in r2] == [r.tokens for r in r3]


def _paged_step_case(family, quant):
    """A 3-layer model with 128-wide heads, a pool of 6 pages of 8 tokens
    (+ the null page) filled with random content, and four slots: one
    inactive (zeroed table row), one whose verify block crosses a page."""
    from hetu_tpu.serving.kv_pool import PagePool
    if family == "llama":
        model, params = _tiny_llama(hd128=True, num_hidden_layers=3)
        n_kv = model.config.num_key_value_heads
    else:
        from hetu_tpu.models.gpt.model import GPTConfig, GPTLMHeadModel
        model = GPTLMHeadModel(GPTConfig.tiny(
            hidden_size=256, num_attention_heads=2, num_hidden_layers=3,
            compute_dtype=jnp.float32, param_dtype=jnp.float32,
            remat=False, use_flash_attention=False))
        params = model.init(jax.random.key(0))
        n_kv = model.config.num_attention_heads
    pool = PagePool(num_layers=3, num_pages=6, page_size=8,
                    num_kv_heads=n_kv, head_dim=model.config.head_dim,
                    dtype=jnp.float32, quant=quant)
    rng = np.random.default_rng(5)
    a = pool.arrays
    if quant == "none":
        tree = tuple(_rand(a.k.shape, seed) for seed in (1, 2))
    else:
        lo, hi, dt = ((-127, 128, np.int8) if quant == "int8"
                      else (0, 256, np.uint8))
        tree = tuple(jnp.asarray(rng.integers(lo, hi, a.k.shape).astype(dt))
                     for _ in range(2)) + tuple(
            jnp.asarray(rng.uniform(0.005, 0.02, a.k_scale.shape)
                        .astype(np.float32)) for _ in range(2))
    table = jnp.asarray([[3, 5, 0, 0], [1, 0, 0, 0], [0, 0, 0, 0],
                         [2, 4, 6, 0]], jnp.int32)
    return model, params, pool, tree, table


@pytest.mark.parametrize("attention", ["kernel", "composition"])
@pytest.mark.parametrize("step", ["decode", "verify"])
@pytest.mark.parametrize("quant", ["none", "int8", "int4"])
@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_paged_step_writes_the_pool_in_place(family, quant, step, attention,
                                             monkeypatch):
    """One `decode_step_paged` / `verify_step_paged` (the pool a loop
    carry, layer l's pages at flat ids l * P + p), its layers attending
    by the Pallas kernel or by the composition over the slot's gathered
    pages (`KVAttention.attend_paged`'s two routes: exact, int8 and int4
    pages, one query a slot and a verify block), against the reference
    over dense views (`decode_step_slots` / `verify_step_slots` over
    `pool.gather`, then `pool.write_token` / `write_tokens`):

      * every pool entry but the written (layer, page, offset) ones is
        untouched, BIT FOR BIT, payload and scale planes alike — a wrong
        l * P lands a layer's token in another layer's page;
      * layer 0's written payload is bit-identical to the gather path's
        (nothing upstream of it but the embedding); deeper layers sit
        downstream of an attention the two paths compute differently
        (the kernel's online softmax; a quantized token that attends to
        its own dequantized K/V, where the gather path sees it exact),
        so they agree to rounding — 1e-5 exact, two quantization steps
        int8 / int4;
      * the inactive slot's writes land in EVERY layer's own null page
        (for the verify block also through the out-of-reach redirect);
      * logits of the active slots match."""
    from hetu_tpu.models import generation as G
    from hetu_tpu.serving.kv_pool import dequantize_heads
    from hetu_tpu.ops.pallas import record_routes
    monkeypatch.setenv("HETU_TPU_PALLAS",
                       "1" if attention == "kernel" else "0")
    monkeypatch.setenv("HETU_TPU_PALLAS_KERNELS", "paged_attn,paged_verify")
    model, params, pool, tree, table = _paged_step_case(family, quant)
    L, ps, mp = 3, 8, table.shape[1]
    ck, cv = pool.gather(tree, table)
    if step == "decode":
        positions = jnp.asarray([11, 7, 0, 16], jnp.int32)
        tokens = jnp.asarray([5, 9, 0, 77], jnp.int32)
        pos_grid = np.asarray(positions)[:, None]
        ref_logits, _, (kt, vt) = G.decode_step_slots(
            model, params, tokens, (ck, cv), positions)
        ref = pool.write_token(tree, table, positions, kt, vt)
        with record_routes() as took:
            logits, new = G.decode_step_paged(
                model, params, tokens, tree, table, positions)
    else:
        # slot 0's block crosses into its second page; the inactive
        # slot's runs past the table's reach (positions 30, 31 | 32)
        positions = jnp.asarray([6, 2, 30, 18], jnp.int32)
        tokens = jnp.asarray(
            np.random.default_rng(9).integers(1, 250, (4, 3)), jnp.int32)
        pos_grid = np.asarray(positions)[:, None] + np.arange(3)[None, :]
        ref_logits, _, (kt, vt) = G.verify_step_slots(
            model, params, tokens, (ck, cv), positions)
        ref = pool.write_tokens(tree, table, jnp.asarray(pos_grid), kt, vt)
        with record_routes() as took:
            logits, new = G.verify_step_paged(
                model, params, tokens, tree, table, positions)
    took = took["paged_attn" if step == "decode" else "paged_verify"]
    assert took["pallas" if attention == "kernel" else "xla"] \
        and not took["xla" if attention == "kernel" else "pallas"]
    assert len(new) == len(tree)
    new, ref, old = ([np.asarray(x) for x in t] for t in (new, ref, tree))

    # where a step writes: [P + 1, ps], the same in every layer
    pidx = pos_grid // ps
    page = np.where(pidx < mp,
                    np.asarray(table)[np.arange(4)[:, None],
                                      np.clip(pidx, 0, mp - 1)], 0)
    written = np.zeros(old[0].shape[1:3], bool)
    written[page, pos_grid % ps] = True
    live, null = written.copy(), written.copy()
    live[0], null[1:] = False, False
    assert null.any() and live.sum() == 3 * pos_grid.shape[1]

    for n, o in zip(new, old):
        assert n.shape == o.shape and n.dtype == o.dtype
        np.testing.assert_array_equal(n[:, ~written], o[:, ~written])
        for l in range(L):    # each layer's OWN null page took the write
            assert (n[l][null] != o[l][null]).any(), l
    for a in (0, 1):
        np.testing.assert_array_equal(new[a][0][live], ref[a][0][live])
        if quant == "none":
            got, want, tol = new[a], ref[a], 1e-5
        else:
            bits = 4 if quant == "int4" else 8
            got, want = (np.asarray(dequantize_heads(
                jnp.asarray(t[a]), jnp.asarray(t[a + 2]), bits))
                for t in (new, ref))
            tol = 2.0 * ref[a + 2][:, live].max()
        np.testing.assert_allclose(got[:, live], want[:, live],
                                   rtol=0, atol=tol)
    active = np.asarray([0, 1, 3])
    np.testing.assert_allclose(
        np.asarray(logits)[active], np.asarray(ref_logits)[active], rtol=0,
        atol={"none": 1e-5, "int8": 2e-2, "int4": 0.5}[quant])


# ---------------------------------------------------------------------------
# shared int4 nibble packer (satellite 1)
# ---------------------------------------------------------------------------

def test_int4_packing_formats_pinned():
    """Both wire formats roundtrip through the ONE shared packer and
    their byte layouts are pinned (golden bytes), so neither path can
    silently diverge."""
    from hetu_tpu.comm.compress import pack_int4, unpack_int4
    from hetu_tpu.ops.quantization import pack_nibbles, unpack_nibbles
    vals = jnp.asarray([[-8, -7, -1, 0, 1, 6, 7, 3]], jnp.int8)
    # comm wire format: offset-binary, even index in the HIGH nibble
    wire = pack_int4(vals)
    np.testing.assert_array_equal(
        np.asarray(wire), np.asarray([[0x01, 0x78, 0x9E, 0xFB]], np.uint8))
    np.testing.assert_array_equal(np.asarray(unpack_int4(wire)),
                                  np.asarray(vals))
    # storage format (ops/quantization): even index in the LOW nibble
    u = (vals.astype(jnp.int32) + 8).astype(jnp.uint8)
    stored = pack_nibbles(u, even_high=False)
    np.testing.assert_array_equal(
        np.asarray(stored), np.asarray([[0x10, 0x87, 0xE9, 0xBF]],
                                       np.uint8))
    np.testing.assert_array_equal(np.asarray(unpack_nibbles(
        stored, even_high=False)), np.asarray(u))
    # the two layouts are nibble-swaps of each other — one packer
    swapped = ((stored >> 4) & 0xF) | ((stored & 0xF) << 4)
    np.testing.assert_array_equal(
        np.asarray(pack_nibbles(u, even_high=True)), np.asarray(swapped))
    with pytest.raises(ValueError):
        pack_nibbles(jnp.zeros((1, 3), jnp.uint8), even_high=True)


def test_int4_quantize_roundtrip_both_paths():
    """End-to-end: ops.quantize_int4 and comm's pack_int4(quantize
    bits=4) both reconstruct within the int4 grid error."""
    from hetu_tpu.comm.compress import (dequantize_blockwise, pack_int4,
                                        quantize_blockwise, unpack_int4)
    x = _rand((4, 64), 5)
    packed, scale = ops.quantize_int4(x, block_size=64)
    y = ops.dequantize_int4(packed, scale, x.shape)
    assert float(jnp.abs(y - x).max()) <= float(scale.max()) * 0.5 + 1e-6
    q, s = quantize_blockwise(x, 64, bits=4)
    y2 = dequantize_blockwise(unpack_int4(pack_int4(q)).astype(jnp.int8), s)
    np.testing.assert_allclose(np.asarray(y2).reshape(x.shape),
                               np.asarray(dequantize_blockwise(q, s)
                                          ).reshape(x.shape), rtol=1e-6)


# ---------------------------------------------------------------------------
# observability: attribution + analytic byte model (acceptance gates)
# ---------------------------------------------------------------------------

def test_hlo_profile_attributes_kernel_groups(monkeypatch):
    """Pallas custom-calls land in their own named kernel rows inside
    layer_table, and kernel_table aggregates them across layers."""
    from hetu_tpu.obs.hlo_profile import kernel_table, layer_table
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    model, params = _tiny_llama(hd128=True, use_scan=False)
    ids = jnp.zeros((2, 16), jnp.int32)
    comp = jax.jit(
        lambda p: model(p, ids, labels=ids)).lower(params).compile()
    lt = layer_table(comp)
    assert "layer_0/mlp/pallas_swiglu" in lt
    assert "layer_0/mlp/pallas_residual_rmsnorm" in lt
    assert "layer_0/attn/pallas_rotary" in lt
    kt = kernel_table(comp)
    for kern in ("pallas_swiglu", "pallas_residual_rmsnorm",
                 "pallas_rotary"):
        assert kt[kern]["instructions"] > 0
        assert len(kt[kern]["groups"]) == 2          # both layers
    # flag off -> no kernel rows at all
    monkeypatch.setenv("HETU_TPU_PALLAS", "0")
    comp0 = jax.jit(
        lambda p: model(p, ids, labels=ids)).lower(params).compile()
    assert kernel_table(comp0) == {}


def test_kernel_traffic_acceptance():
    """The analytic byte model's headline gates: residual+RMSNorm shows
    the >= 3x read/write cut of fusing the XLA chain (bf16 activations,
    the bench config's dtype), and every kernel's record carries both
    byte counts."""
    from hetu_tpu.obs.mfu import kernel_roofline
    from hetu_tpu.ops.pallas.traffic import (kernel_traffic_report,
                                             norm_traffic)
    rec = norm_traffic(16384, 1536, elem_bytes=2.0)
    assert rec["reduction"] >= 3.0
    rep = kernel_traffic_report(batch=8, seq=2048, hidden=1536,
                                intermediate=4096, num_layers=12,
                                q_heads=12, kv_heads=12, head_dim=128)
    assert set(rep) == {"norm", "swiglu", "rotary", "flash", "quant",
                        "paged_attn", "paged_attn_int8",
                        "paged_attn_int4", "paged_verify", "sample"}
    for r in rep.values():
        assert r["fused_bytes"] > 0
        assert r["unfused_bytes"] > r["fused_bytes"]
    # the int8-page kernel reads ~1/elem_bytes the cache payload of the
    # fp kernel AND skips the dequantized dense round trip; int4 halves
    # the payload again
    assert rep["paged_attn_int8"]["fused_bytes"] < \
        rep["paged_attn"]["fused_bytes"]
    assert rep["paged_attn_int8"]["reduction"] >= 3.0
    assert rep["paged_attn_int4"]["fused_bytes"] < \
        rep["paged_attn_int8"]["fused_bytes"]
    roof = kernel_roofline(rep)
    assert roof["norm"]["speedup"] >= 3.0
    assert all(v["fused_s"] > 0 for v in roof.values())


def test_bench_detail_kernels_record():
    """bench.py's detail.kernels producer (the tools_bench_kernels
    section): every kernel row, norm >= 3x, and the fused verify chain
    acceptance gate (>= 2x fewer HBM bytes than gather at k=4)."""
    import bench
    rec = bench._hardware_free_kernels(batch=2, seq=512)
    assert set(rec) == {"norm", "swiglu", "rotary", "flash", "quant",
                        "paged_attn", "paged_attn_int8",
                        "paged_attn_int4", "paged_verify", "sample",
                        "fused_verify_chain"}
    assert rec["norm"]["reduction"] >= 3.0
    assert rec["paged_attn"]["reduction"] >= 3.0
    assert rec["paged_attn_int8"]["reduction"] >= 3.0
    assert rec["fused_verify_chain"]["reduction"] >= 2.0
    from tools_bench_kernels import kernel_section
    assert kernel_section(2, 512) == rec


def test_cost_model_pallas_candidate():
    """The searcher sees the fusion win: a pallas candidate is strictly
    faster, and kernel_fusion_factors carries per-kernel reductions."""
    from hetu_tpu.search.cost_model import CostModel, StrategyCandidate
    from hetu_tpu.search.profiler import HardwareProfile
    cm = CostModel(hw=HardwareProfile.preset("v5e"), num_layers=12,
                   hidden=1536, intermediate=4096, vocab=32000,
                   num_params=500_000_000, global_batch=8, seq_len=2048)
    plain = StrategyCandidate()
    fused = StrategyCandidate(pallas=True)
    assert cm.step_time(fused) < cm.step_time(plain)
    assert fused.describe().endswith("pk")
    ff = cm.kernel_fusion_factors()
    assert ff["norm"]["reduction"] >= 3.0
    assert all(v["unfused_bytes"] > v["fused_bytes"] for v in ff.values())
