"""The selective scan of a Mamba-1 layer as one Pallas kernel
(`ops/pallas/selective_scan.py`), in interpret mode on the CPU: against
the definition (`selective_scan.recurrence`) for y and the state at 1e-5
absolute, and against the XLA composition it takes the place of on a TPU
(`selective_scan.chunk_scan`'s other route).  A state kept in bfloat16
passes the cells' own check on the chip (PERF.md s7): THIS file is what
holds the kernel's arithmetic to float32.

Interpret mode traces the whole kernel a shape (~2 s), so the cases share
a few shapes and `valid` is an argument, not a shape."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from hetu_tpu.ops import selective_scan as ss  # noqa: E402
from hetu_tpu.ops.pallas import record_routes  # noqa: E402
from hetu_tpu.ops.pallas import selective_scan as pk  # noqa: E402

F32, BF16 = jnp.float32, jnp.bfloat16
ATOL = 1e-5
#: d_inner: two channel blocks of 128 lanes (`KW`), so that a block's
#: columns laid out by its first channel block are read by the second;
#: the lengths the cases share: one position block, and two of the
#: module's own size (a cell's chunk)
D = 256
KW = dict(dblk=128)
ONE, TWO = 128, 2 * pk.ROWS


@pytest.fixture
def rng():
    return np.random.default_rng(52)


def inputs(rng, s, b=1, N=16, d=D, zero_state=False, dtype=F32):
    """As a Mamba-1 layer makes them (`nn/mamba.py`: the configs'
    `mamba_init`): A's lanes -1 .. -N a channel, Delta log-uniform in
    [0.001, 0.1], u', B and C in the model's dtype, a state of the size
    a few hundred positions leave."""
    h = (0.0 if zero_state else 1.0) * rng.standard_normal((b, N, d))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (b, s, d)))
    A = -np.broadcast_to(np.arange(1.0, N + 1)[:, None], (N, d))
    u, B, C = (jnp.asarray(rng.standard_normal(shape), dtype)
               for shape in ((b, s, d), (b, s, N), (b, s, N)))
    return (jnp.asarray(h, F32), u, jnp.asarray(dt, F32),
            jnp.asarray(A, F32), B, C,
            jnp.asarray(rng.standard_normal((d,)), F32))


_kernel = jax.jit(pk.selective_scan, static_argnames=("rows", "dblk"))


def kernel(args, valid=None, **kw):
    b, s = args[1].shape[:2]
    valid = jnp.broadcast_to(
        jnp.asarray(s if valid is None else valid, jnp.int32), (b,))
    return _kernel(*args, valid, **{**KW, **kw})


def first(args, i, n):
    """Sequence i's first n positions, as `recurrence` takes them."""
    h, u, dt, A, B, C, Dk = args
    return (h[i: i + 1], u[i: i + 1, :n], dt[i: i + 1, :n], A,
            B[i: i + 1, :n], C[i: i + 1, :n], Dk)


def close(y, h, y_want, h_want):
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_want), atol=ATOL)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_want), atol=ATOL)


# --------------------------------------------------- against the definition
@pytest.mark.parametrize("zero_state", [True, False],
                         ids=["from_zero", "from_a_state"])
@pytest.mark.parametrize("s", [ONE, TWO], ids=["one_block", "two_blocks"])
def test_kernel_is_the_recurrence_and_the_composition(s, zero_state, rng):
    args = inputs(rng, s, zero_state=zero_state)
    y, h = kernel(args)
    close(y, h, *ss.recurrence(*args))
    close(y, h, *ss.chunk_scan(*args))


def test_lanes_1_to_16_and_steps_0_001_to_0_1_over_512_positions(rng):
    """The configurations' `mamba_init` at its edges over a cell's whole
    chunk: half the channels step by 0.001 or 0.1 at every position, so
    a position's decays run from exp(-1.6) to exp(-0.001) and a state
    holds up to a thousand positions of history."""
    h, u, dt, A, B, C, Dk = inputs(rng, TWO)
    edge = jnp.where(jnp.arange(D) % 2 == 0, 0.001, 0.1)
    args = (h, u, jnp.where(jnp.arange(D) < D // 2, edge, dt), A, B, C, Dk)
    y, h1 = kernel(args)
    assert y.dtype == F32 and h1.dtype == F32
    close(y, h1, *ss.recurrence(*args))


@pytest.mark.parametrize("valid", [0, 5, pk.ROWS, pk.ROWS + 37, TWO])
def test_state_stops_at_the_last_valid_row(valid, rng):
    """`valid` in the middle of a block, at a block's edge, nothing and
    everything: the state is the recurrence's over the valid rows, their
    y the recurrence's, every other y finite; a block wholly past
    `valid` passes the state through and writes zeros."""
    args = inputs(rng, TWO)
    y, h = kernel(args, valid=valid)
    assert np.isfinite(np.asarray(y)).all()
    y_want, h_want = ss.recurrence(*first(args, 0, valid))
    close(y[:, :valid], h, y_want, h_want)
    assert not np.asarray(y[:, -(-valid // pk.ROWS) * pk.ROWS:]).any()
    # the composition masks the same rows the same way
    _, h_xla = ss.chunk_scan(*args, valid=jnp.asarray([valid], jnp.int32))
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_xla), atol=ATOL)


def test_a_chunk_in_two_launches_is_the_chunk_in_one(rng):
    """The engine's own use: a prompt's chunks one launch after the
    other, the state handed from one to the next; the second launch's
    last 28 rows are padding."""
    args = inputs(rng, 256)
    h, u, dt, A, B, C, Dk = args
    y_a, h_a = kernel((h, u[:, :128], dt[:, :128], A, B[:, :128],
                       C[:, :128], Dk))
    y_b, h_b = kernel((h_a, u[:, 128:], dt[:, 128:], A, B[:, 128:],
                       C[:, 128:], Dk), valid=100)
    y_want, h_want = ss.recurrence(*first(args, 0, 228))
    close(jnp.concatenate([y_a, y_b[:, :100]], axis=1), h_b, y_want, h_want)


# ------------------------------------------------------- the gate, the route
GOOD = dict(h=(1, 16, 256), u=(1, 128, 256), b=(1, 128, 16))
REFUSED = {
    "rows_not_whole_blocks": (dict(u=(1, 100, 256), b=(1, 100, 16)),
                              "not a multiple of the kernel's 128"),
    "channels_not_128_lanes": (dict(h=(1, 16, 192), u=(1, 128, 192)),
                               "not a multiple of 128 lanes"),
    "state_lanes_not_8": (dict(h=(1, 12, 256), b=(1, 128, 12)),
                          "not a multiple of 8 sublanes"),
    "state_not_float32": (dict(state_dtype=BF16), "keeps it float32"),
    "operands_of_another_state": (dict(u=(1, 128, 512)), "do not match"),
    "not_three_dims": (dict(h=(16, 256)), "expected h"),
}


@pytest.mark.parametrize("case", REFUSED)
def test_the_gate_and_the_entry_refuse_with_the_same_words(case):
    """The drift rule of tests/test_pallas_kernels.py: what `check_shapes`
    (the route's gate) refuses, the kernel's entry refuses, in the same
    words, before anything is traced."""
    over, why = REFUSED[case]
    shapes = {**GOOD, **{k: v for k, v in over.items() if k in GOOD}}
    kw = {k: v for k, v in over.items() if k not in GOOD}
    assert pk.check_shapes(*GOOD.values()) == (1, 128, 16, 256)
    with pytest.raises(ValueError, match=why) as gate:
        pk.check_shapes(*shapes.values(), **kw)
    dt = kw.get("state_dtype", F32)
    zeros = lambda shape, t=F32: jnp.zeros(shape, t)  # noqa: E731
    N = shapes["h"][-2] if len(shapes["h"]) == 3 else 16
    with pytest.raises(ValueError, match=why) as entry:
        pk.selective_scan(
            zeros(shapes["h"], dt), zeros(shapes["u"]), zeros(shapes["u"]),
            zeros((N, shapes["u"][-1])), zeros(shapes["b"]),
            zeros(shapes["b"]), zeros(shapes["u"][-1:]),
            jnp.zeros((1,), jnp.int32))
    assert str(gate.value) == str(entry.value)


def test_on_the_cpu_the_one_entry_takes_the_composition(rng):
    with record_routes() as routes:
        ss.chunk_scan(*inputs(rng, 128))
    assert routes == {"selective_scan": {
        "pallas": 0, "xla": 1, "why": {"not a TPU backend": 1}}}


def test_forced_onto_the_kernel_the_entry_gives_the_same(rng, monkeypatch):
    """`chunk_scan` with the kernel forced (interpret mode), at the block
    sizes the entry itself chooses: a batch of two sequences of eight
    state lanes (one vreg a lane group), u', B and C in bfloat16 as the
    projections leave them, each sequence with its own `valid`, against
    the XLA route and the definition; the route is on the record."""
    args = inputs(rng, 128, b=2, N=8, dtype=BF16)
    valid = jnp.asarray([128, 37], jnp.int32)
    y_want, h_want = ss.chunk_scan(*args, valid=valid)
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    with record_routes() as routes:
        y, h = ss.chunk_scan(*args, valid=valid)
    assert routes == {"selective_scan": {
        "pallas": 1, "xla": 0, "why": {"forced on by HETU_TPU_PALLAS=1": 1}}}
    assert np.isfinite(np.asarray(y)).all()
    for i, n in enumerate((128, 37)):
        close(y[i, :n], h[i], y_want[i, :n], h_want[i])
        y_def, h_def = ss.recurrence(*first(args, i, n))
        close(y[i: i + 1, :n], h[i: i + 1], y_def, h_def)


def test_a_shape_the_gate_refuses_keeps_the_composition(rng, monkeypatch):
    """A chunk that is no whole number of the kernel's blocks (the tiny
    configurations' 16 rows): on a TPU the gate's reason is the route's."""
    import hetu_tpu.ops.pallas as pallas
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with record_routes() as routes:
        jax.eval_shape(ss.chunk_scan, *inputs(rng, 100))
        jax.eval_shape(ss.chunk_scan, *inputs(rng, 128))
    rec = routes["selective_scan"]
    assert rec["xla"] == 1 and rec["pallas"] == 1
    assert sorted(rec["why"]) == [
        "shape gate passes",
        "shape gate: 100 rows are not a multiple of the kernel's 128 "
        "positions"], rec
    assert "selective_scan" in pallas.KERNEL_NAMES


def test_grad_through_the_mixer_is_the_compositions(rng, monkeypatch):
    """`MambaMixer.forward` reaches the same entry: with the kernel forced
    its gradient is the composition's (the kernel's `custom_vjp` runs the
    composition backward)."""
    from hetu_tpu.nn.mamba import MambaMixer
    mixer = MambaMixer(32, 128, 8, 4, 4, param_dtype=F32, compute_dtype=F32)
    params = jax.jit(mixer.init)(jax.random.PRNGKey(0))
    hn = jnp.asarray(rng.standard_normal((1, 128, 32)), F32)

    def loss(params, hn):
        out, y = mixer.forward(params, hn)
        return jnp.sum(jnp.square(out)) + jnp.sum(jnp.square(y))
    want = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, hn)
    monkeypatch.setenv("HETU_TPU_PALLAS", "1")
    with record_routes() as routes:
        # another function to jit: the first one's trace took the
        # composition
        got = jax.jit(jax.grad(lambda *a: loss(*a), argnums=(0, 1)))(
            params, hn)
    assert routes["selective_scan"]["pallas"] == 1
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)
