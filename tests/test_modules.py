"""Module system + layers + optimizer unit tests (golden vs numpy/jax)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import nn, optim


def test_sequential_with_paramless_children():
    model = nn.Sequential([nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2)])
    params = model.init(jax.random.key(0))
    assert "1" not in params
    y = model(params, jnp.ones((3, 4)))
    assert y.shape == (3, 2)


def test_linear_matches_numpy():
    m = nn.Linear(4, 3)
    p = m.init(jax.random.key(1))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 4)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(m(p, x)),
        np.asarray(x) @ np.asarray(p["weight"]) + np.asarray(p["bias"]),
        rtol=1e-5)


def test_rmsnorm_golden():
    m = nn.RMSNorm(8)
    p = m.init(jax.random.key(0))
    x = np.random.default_rng(1).normal(size=(2, 8)).astype(np.float32)
    y = m(p, jnp.asarray(x))
    ref = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-5)


def test_adamw_converges_and_zero_shardings():
    m = nn.Linear(8, 8, bias=False)
    p = m.init(jax.random.key(0))
    opt = optim.AdamW(lr=1e-2)
    s = opt.init(p)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(16, 8)), jnp.float32)
    y = x @ jnp.ones((8, 8)) * 0.1

    @jax.jit
    def step(p, s):
        loss, g = jax.value_and_grad(lambda p: ht.ops.mse_loss(m(p, x), y))(p)
        p, s = opt.update(g, s, p)
        return p, s, loss

    losses = [None, None]
    for i in range(50):
        p, s, loss = step(p, s)
        losses[min(i, 1)] = float(loss)
    assert losses[1] < losses[0] * 0.1

    # ZeRO: replicated params must still get dp-sharded states.
    mesh = ht.create_mesh(dp=4)
    from hetu_tpu.optim.optimizer import zero_shardings
    z = zero_shardings(m.shardings(mesh), m.abstract_params(), mesh, "dp")
    assert z["weight"].spec == jax.sharding.PartitionSpec("dp", None)


@pytest.mark.parametrize("shape,dtype", [
    ((8, 128), jnp.float32), ((256,), jnp.bfloat16), ((5,), jnp.float32),
], ids=["matrix_f32", "vector_bf16", "ragged_f32"])
def test_adamw_update_is_the_reference_update(shape, dtype):
    """Two steps of `AdamW.update` (the bias corrections move) against
    the update written out in numpy float64: decoupled weight decay, f32
    moments whatever the leaf's dtype, the new leaf rounded once."""
    rng = np.random.default_rng(3)
    p = jnp.asarray(rng.normal(size=shape), dtype)
    g = jnp.asarray(rng.normal(size=shape) * 0.1, dtype)
    lr, b1, b2, eps, wd = 1e-2, 0.9, 0.95, 1e-8, 0.01
    opt = optim.AdamW(lr=lr, b1=b1, b2=b2, eps=eps, weight_decay=wd)
    state = opt.init({"w": p})
    ref_p, ref_g = np.asarray(p, np.float64), np.asarray(g, np.float64)
    m = v = np.zeros(shape)
    params = {"w": p}
    for step in (1, 2):
        params, state = opt.update({"w": g}, state, params)
        m = b1 * m + (1 - b1) * ref_g
        v = b2 * v + (1 - b2) * ref_g ** 2
        ref_p = ref_p - lr * (
            (m / (1 - b1 ** step)) / (np.sqrt(v / (1 - b2 ** step)) + eps)
            + wd * ref_p)
        assert params["w"].dtype == dtype
        assert state["m"]["w"].dtype == state["v"]["w"].dtype == jnp.float32
        tol = 1e-5 if dtype == jnp.float32 else 1e-2   # one bf16 rounding
        np.testing.assert_allclose(np.asarray(params["w"], np.float64),
                                   ref_p, rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(state["m"]["w"]), m,
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(np.asarray(state["v"]["w"]), v,
                                   rtol=1e-5, atol=1e-9)
        # the bf16 leaf is carried rounded, as the step carries it
        ref_p = np.asarray(params["w"], np.float64)
    assert int(state["step"]) == 2


def test_grad_scaler_dynamics():
    from hetu_tpu.optim import GradScaler
    gs = GradScaler(init_scale=4.0, growth_interval=2)
    st = gs.init()
    grads = {"w": jnp.ones(3)}
    g2, finite = gs.unscale_and_check(grads, st)
    assert bool(finite)
    np.testing.assert_allclose(np.asarray(g2["w"]), 0.25)
    st = gs.update(st, finite)
    st = gs.update(st, jnp.asarray(True))
    assert float(st["scale"]) == 8.0  # grew after interval
    st = gs.update(st, jnp.asarray(False))
    assert float(st["scale"]) == 4.0  # backoff


def test_conv_pool_forward():
    m = nn.Sequential([nn.Conv2d(3, 8, 3), nn.ReLU(), nn.MaxPool2d(2)])
    p = m.init(jax.random.key(0))
    y = m(p, jnp.ones((2, 8, 8, 3)))
    assert y.shape == (2, 4, 4, 8)


def test_dropout_deterministic_and_random():
    d = nn.Dropout(0.5)
    x = jnp.ones((4, 4))
    assert (d({}, x) == x).all()
    y = d({}, x, rng=jax.random.key(0), deterministic=False)
    vals = np.unique(np.asarray(y))
    assert set(vals.tolist()) <= {0.0, 2.0}


def test_cifar_style_cnn_smoke():
    """BASELINE config 1 mirror (reference tests/test_cifar10.py): MLP/CNN
    graph-executor smoke — trains to high accuracy on separable data."""
    import os
    import runpy
    import sys
    old = sys.argv
    sys.argv = ["cifar10.py", "--steps", "30", "--batch", "64"]
    try:
        import io, contextlib
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            runpy.run_path(
                os.path.join(os.path.dirname(__file__), "..", "examples",
                             "cifar10.py"), run_name="__main__")
        out = buf.getvalue()
    finally:
        sys.argv = old
    last = [l for l in out.strip().splitlines() if l.startswith("step")][-1]
    acc = float(last.split("acc")[1])
    assert acc > 0.85, out


def test_batchnorm_functional_state():
    """BatchNorm with explicit running stats (reference:
    nn/modules/batchnorm.py; functional state threads through jit)."""
    import jax
    from hetu_tpu.nn import BatchNorm
    bn = BatchNorm(4, momentum=0.5)
    params = bn.init(jax.random.key(0))
    state = bn.init_state()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(3.0, 2.0, (8, 5, 5, 4)), jnp.float32)
    y, state2 = jax.jit(lambda p, x, s: bn(p, x, s, training=True))(
        params, x, state)
    # normalized over (N, H, W): per-channel ~N(0,1)
    np.testing.assert_allclose(np.asarray(jnp.mean(y, axis=(0, 1, 2))),
                               np.zeros(4), atol=1e-4)
    np.testing.assert_allclose(np.asarray(jnp.var(y, axis=(0, 1, 2))),
                               np.ones(4), atol=1e-3)
    # running stats moved toward the batch stats
    assert float(jnp.max(jnp.abs(state2["mean"]))) > 1.0
    # eval mode uses the running stats and returns them unchanged
    y2, state3 = bn(params, x, state2, training=False)
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), state2, state3))


def test_instance_norm_and_padding():
    import jax
    from hetu_tpu.nn import ConstantPad2d, InstanceNorm, ZeroPad2d
    inorm = InstanceNorm(3)
    params = inorm.init(jax.random.key(1))
    x = jnp.asarray(np.random.default_rng(1).normal(2, 3, (2, 6, 6, 3)),
                    jnp.float32)
    y = inorm(params, x)
    # per-sample, per-channel spatial stats ~N(0,1)
    np.testing.assert_allclose(np.asarray(jnp.mean(y, axis=(1, 2))),
                               np.zeros((2, 3)), atol=1e-4)
    pad = ZeroPad2d(1)
    assert pad({}, x).shape == (2, 8, 8, 3)
    cp = ConstantPad2d((1, 2, 0, 3), value=7.0)
    out = cp({}, x)
    assert out.shape == (2, 9, 9, 3)
    assert float(out[0, -1, 0, 0]) == 7.0


def test_constant_pad_negative_crops():
    from hetu_tpu.nn import ConstantPad2d
    x = jnp.arange(2 * 4 * 4 * 1, dtype=jnp.float32).reshape(2, 4, 4, 1)
    out = ConstantPad2d((-1, 1, -2, 0), value=5.0)({}, x)
    assert out.shape == (2, 2, 4, 1)       # H: 4-2; W: 4-1+1
    assert float(out[0, 0, -1, 0]) == 5.0  # right pad value
    np.testing.assert_array_equal(np.asarray(out[0, :, :-1, 0]),
                                  np.asarray(x[0, 2:, 1:, 0]))
