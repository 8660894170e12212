"""The chunkwise delta rule as one Pallas kernel (`ops/pallas/kda_scan.py`),
in interpret mode on the CPU: against the definition (`delta_rule.
recurrence`) and against the XLA composition it takes the place of on a
TPU (`delta_rule.chunk_scan`'s other route), at the tolerances the
composition itself is held to (o 2e-5, S 5e-5 absolute)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from hetu_tpu.ops import delta_rule  # noqa: E402
from hetu_tpu.ops.pallas import kda_scan as ks  # noqa: E402
from hetu_tpu.ops.pallas import record_routes  # noqa: E402

F32 = jnp.float32
H, D = 2, 128
O_ATOL, S_ATOL = 2e-5, 5e-5


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def inputs(rng, s, h=H, d=D, alike=0.0, zero_state=False, g=None):
    """One sequence [s, h, d]: unit keys (neighbours alike as a short
    convolution makes them), decays over the whole range the model
    allows, down to the lower bound."""
    q, k = (rng.standard_normal((s, h, d)) for _ in range(2))
    for t in range(1, s):
        k[t] = alike * k[t - 1] + (1 - alike ** 2) ** 0.5 * k[t]
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((s, h, d))
    if g is None:
        g = -5.0 / (1.0 + np.exp(-3.0 * rng.standard_normal((s, h, d))))
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((s, h))))
    S0 = (0.0 if zero_state else 1.0) * rng.standard_normal((h, d, d))
    return tuple(jnp.asarray(a, F32) for a in (S0, q, k, v, g, beta))


def kernel(S0, q, k, v, g, beta, valid=None, **kw):
    """One sequence through the kernel: rows padded to a whole tile and
    left out by `valid`, the operands flattened to [1, C, h * d] as the
    dispatcher hands them over."""
    s, h, d = q.shape
    C = -(-s // ks.TILE) * ks.TILE
    flat = lambda x: jnp.pad(  # noqa: E731
        x, ((0, C - s),) + ((0, 0),) * (x.ndim - 1)).reshape(1, C, -1)
    o, S = ks.kda_scan(S0[None], flat(q), flat(k), flat(v), flat(g),
                       flat(beta), jnp.asarray([s if valid is None
                                                else valid], jnp.int32),
                       g_floor=-5.0, **kw)
    return o.reshape(C, h, d), S[0]


def close(o, S, o_want, S_want):
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_want),
                               atol=O_ATOL)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_want),
                               atol=S_ATOL)


@pytest.mark.parametrize("zero_state", [True, False],
                         ids=["from_zero", "from_a_state"])
@pytest.mark.parametrize("alike", [0.0, 0.99])
@pytest.mark.parametrize("positions", [16, 64, 128, 2 * ks.TILE + 16])
def test_kernel_is_the_recurrence_and_the_composition(positions, alike,
                                                      zero_state, rng):
    """Decays down to the lower bound, neighbouring keys alike at 0.99
    (where the power series of a whole 64-row block gave no finite
    value: PERF.md s6, PR 41), from zero and from a state; positions
    short of a tile are the tile's padding."""
    args = inputs(rng, positions, alike=alike, zero_state=zero_state)
    o, S = kernel(*args)
    assert not np.asarray(o[positions:]).any()
    close(o[:positions], S, *delta_rule.recurrence(*args))
    close(o[:positions], S, *delta_rule.chunk_scan(*args))


def test_decays_at_the_lower_bound_stay_finite(rng):
    """Every channel AT the bound for whole tiles: exp(G) underflows to
    0 inside a tile and nothing overflows.  The last row of a 16-row
    block then multiplies q exp(-80) by k exp(80), and float32's
    subnormals cost that row's own term some digits in the kernel as in
    the composition (a sigmoid never reaches the bound): the kernel is
    held to the composition on every row, and both to the recurrence on
    the others."""
    s = 2 * ks.TILE
    args = inputs(rng, s, g=np.full((s, H, D), -5.0))
    o, S = kernel(*args)
    close(o, S, *delta_rule.chunk_scan(*args))
    o_want, S_want = delta_rule.recurrence(*args)
    rows = np.arange(s) % delta_rule.BLOCK != delta_rule.BLOCK - 1
    close(o[rows], S, o_want[rows], S_want)


@pytest.mark.parametrize("valid", [0, 5, ks.TILE, ks.TILE + 37,
                                   3 * ks.TILE])
@pytest.mark.parametrize("rows", [ks.TILE, 3 * ks.TILE],
                         ids=["a_tile_a_step", "tiles_in_a_step"])
def test_state_stops_at_the_last_valid_row(valid, rows, rng):
    """The kernel masks the rows past `valid` itself: the state after
    the chunk is the state after the last valid row, the valid rows' o
    the recurrence's, the rest zeros; grid steps wholly past `valid`
    pass the state through."""
    s = 3 * ks.TILE
    S0, *cols = inputs(rng, s, alike=0.9)
    o, S = kernel(S0, *cols, valid=valid, rows=rows)
    o_want, S_want = delta_rule.recurrence(S0, *(a[:valid] for a in cols))
    close(o[:valid], S, o_want, S_want)
    assert not np.asarray(o[valid:]).any()
    if valid == 0:
        assert (np.asarray(S) == np.asarray(S0)).all()


def test_heads_read_their_own_columns(rng):
    """Two heads with different decays, states and write strengths in
    one launch give what each gives alone."""
    s = ks.TILE
    g = np.stack([np.full((s, D), -0.01), np.full((s, D), -4.0)], axis=1)
    S0, q, k, v, g, beta = inputs(rng, s, g=g)
    o, S = kernel(S0, q, k, v, g, beta)
    for h in range(H):
        o1, S1 = kernel(S0[h: h + 1], *(a[:, h: h + 1]
                                        for a in (q, k, v, g, beta)))
        np.testing.assert_array_equal(np.asarray(o[:, h]),
                                      np.asarray(o1[:, 0]))
        np.testing.assert_array_equal(np.asarray(S[h]), np.asarray(S1[0]))
    assert np.abs(np.asarray(S[0] - S[1])).max() > 0.1


def test_a_batch_of_sequences_each_with_its_own_valid(rng):
    s = 2 * ks.TILE
    a, b = inputs(rng, s), inputs(rng, s, alike=0.9)
    flat = lambda x, y: jnp.stack([x, y]).reshape(2, s, -1)  # noqa: E731
    o, S = ks.kda_scan(jnp.stack([a[0], b[0]]),
                       *(flat(x, y) for x, y in zip(a[1:], b[1:])),
                       jnp.asarray([s, 21], jnp.int32), g_floor=-5.0)
    close(o[0].reshape(s, H, D), S[0], *delta_rule.recurrence(*a))
    o_b, S_b = delta_rule.recurrence(b[0], *(x[:21] for x in b[1:]))
    close(o[1].reshape(s, H, D)[:21], S[1], o_b, S_b)


@pytest.mark.parametrize("alike", [0.0, 0.99])
def test_the_scan_makes_the_unit_norm_of_q_and_k_itself(alike, rng):
    """With `qk_scale` q and k come as the activation left them (any
    length); kernel and composition give what the recurrence gives on
    `unit_length(q) * qk_scale` and `unit_length(k)`, padding rows of any
    content included."""
    s, scale = 2 * ks.TILE, D ** -0.5
    S0, q, k, v, g, beta = inputs(rng, s, alike=alike)
    raw_q, raw_k = (x * jnp.asarray(rng.uniform(0.2, 5.0, (s, H, 1)), F32)
                    for x in (q / scale, k))
    want = delta_rule.recurrence(S0, *(x[:s - 21] for x in (
        delta_rule.unit_length(raw_q) * scale, delta_rule.unit_length(raw_k),
        v, g, beta)))
    o, S = kernel(S0, raw_q, raw_k, v, g, beta, valid=s - 21, qk_scale=scale)
    close(o[:s - 21], S, *want)
    o, S = delta_rule.chunk_scan(S0, raw_q, raw_k, v, g, beta,
                                 valid=s - 21, qk_scale=scale)
    close(o[:s - 21], S, *want)


REFUSED = {
    "state_not_square": (dict(s=(1, 2, 128, 256), v=(1, 128, 512)),
                         "square and a multiple of 128"),
    "state_not_128_lanes": (dict(s=(1, 2, 64, 64), q=(1, 128, 128),
                                 v=(1, 128, 128)),
                            "square and a multiple of 128"),
    "rows_not_whole_tiles": (dict(q=(1, 72, 256), v=(1, 72, 256),
                                  beta=(1, 72, 2)),
                             "not a multiple of the kernel's tile"),
    "decays_outside_float32": (dict(g_floor=-6.0), "float32's range"),
    "state_not_float32": (dict(state_dtype=jnp.bfloat16), "keeps it float32"),
    "columns_of_another_state": (dict(q=(1, 128, 384)), "do not match"),
}


@pytest.mark.parametrize("case", REFUSED)
def test_the_gate_refuses_with_its_reason(case):
    over, why = REFUSED[case]
    shapes = dict(s=(1, 2, 128, 128), q=(1, 128, 256), v=(1, 128, 256),
                  beta=(1, 128, 2))
    kw = dict(g_floor=-5.0)
    assert ks.compatible(*shapes.values(), **kw)
    for key, val in over.items():
        (shapes if key in shapes else kw)[key] = val
    with pytest.raises(ValueError, match=why):
        ks.check_shapes(*shapes.values(), **kw)
    assert not ks.compatible(*shapes.values(), **kw)


def test_the_kernel_entry_raises_what_the_gate_raises(rng):
    S0, *cols = inputs(rng, ks.TILE)
    with pytest.raises(ValueError, match="float32's range"):
        ks.kda_scan(S0[None], *(x.reshape(1, ks.TILE, -1) for x in cols),
                    jnp.asarray([ks.TILE], jnp.int32), g_floor=-6.0)


# ------------------------------------------------------------- the route
def test_on_the_cpu_the_one_entry_takes_the_composition(rng):
    args = inputs(rng, ks.TILE)
    with record_routes() as routes:
        delta_rule.chunk_scan(*args)
    assert routes == {"kda_scan": {"pallas": 0, "xla": 1,
                                   "why": {"not a TPU backend": 1}}}


@pytest.mark.parametrize("qk_scale", [None, 0.5])
@pytest.mark.parametrize("valid", [None, 37])
def test_forced_onto_the_kernel_the_entry_gives_the_same(valid, qk_scale,
                                                         rng, monkeypatch):
    """`chunk_scan` with the kernel forced (interpret mode) against its
    own XLA route: one sequence and a batch, with and without `valid`."""
    s = ks.TILE
    one = inputs(rng, s, alike=0.9)
    two = tuple(jnp.stack([x, y]) for x, y in zip(one, inputs(rng, s)))
    kw = dict(valid=valid, qk_scale=qk_scale)
    want = [delta_rule.chunk_scan(*one, **kw),
            delta_rule.chunk_scan(*two, **kw)]
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    with record_routes() as routes:
        got = [delta_rule.chunk_scan(*one, **kw),
               delta_rule.chunk_scan(*two, **kw)]
    assert routes["kda_scan"]["pallas"] == 2 and not routes["kda_scan"]["xla"]
    for (o, S), (o_want, S_want) in zip(got, want):
        assert o.shape == o_want.shape and S.shape == S_want.shape
        close(o, S, o_want, S_want)
    if valid is not None:
        assert not np.asarray(got[1][0][:, valid:]).any()
        assert not np.asarray(want[1][0][:, valid:]).any()


def test_a_shape_the_gate_refuses_keeps_the_composition(rng, monkeypatch):
    """16-wide heads (the tiny configurations): the gate's reason is the
    route's, and the composition's own range rule still raises."""
    import hetu_tpu.ops.pallas as pk
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = inputs(rng, 64, h=3, d=16)
    with record_routes() as routes:
        jax.eval_shape(delta_rule.chunk_scan, *args)
        with pytest.raises(ValueError, match="float32's range"):
            jax.eval_shape(lambda *a: delta_rule.chunk_scan(
                *a, g_floor=-6.0), *args)
    rec = routes["kda_scan"]
    assert rec["xla"] == 2 and not rec["pallas"]
    assert all(w.startswith("shape gate: ") for w in rec["why"]), rec
    assert "kda_scan" in pk.KERNEL_NAMES
