"""Hetero-TP pipeline: unequal effective TP degree per stage in ONE program
(reference: distributed_states.h:158-321 unions over unequal device groups +
define_and_run_graph.cc:159 DeducePipeline)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.core.mesh import MeshConfig
from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.parallel import ParallelStrategy


def _cfg(**kw):
    return LlamaConfig.tiny(remat=False, compute_dtype=jnp.float32,
                            use_flash_attention=False, use_scan=True, **kw)


def _golden(cfg, ids):
    model = LlamaLMHeadModel(cfg, ParallelStrategy())
    p = model.init(jax.random.key(1))
    return model, p, model(p, ids)


def _ids(b=4, s=64, vocab=256, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, (b, s)),
                       jnp.int32)


@pytest.mark.parametrize("tp_eff", [(2, 1), (1, 2), (2, 2), (1, 1)])
def test_hetero_tp_pipeline_matches_single_device(tp_eff):
    cfg = _cfg()
    ids = _ids()
    _, _, golden = _golden(cfg, ids)

    st = ParallelStrategy(mesh=MeshConfig(pp=2, tp=2), pp_tp_eff=tp_eff)
    mesh = st.build_mesh(devices=jax.devices()[:4])
    model = LlamaLMHeadModel(cfg, st)
    with ht.use_mesh(mesh):
        params = model.init(jax.random.key(1), mesh=mesh)
        out = jax.jit(lambda p, x: model(p, x, n_micro=2))(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden),
                               rtol=2e-3, atol=2e-3)


def test_hetero_tp_pipeline_gradients():
    cfg = _cfg()
    ids = _ids(seed=3)
    gmodel, gp, _ = _golden(cfg, ids)

    def gloss(p):
        return gmodel(p, ids, labels=ids)
    g_ref = jax.grad(gloss)(gp)

    st = ParallelStrategy(mesh=MeshConfig(pp=2, tp=2), pp_tp_eff=(2, 1))
    mesh = st.build_mesh(devices=jax.devices()[:4])
    model = LlamaLMHeadModel(cfg, st)
    with ht.use_mesh(mesh):
        params = model.init(jax.random.key(1), mesh=mesh)
        g = jax.jit(jax.grad(
            lambda p: model(p, ids, labels=ids, n_micro=2)))(params)
    flat_ref = jax.tree.leaves_with_path(g_ref)
    flat = dict(jax.tree.leaves_with_path(g))
    assert len(flat) == len(flat_ref)
    for path, a in flat_ref:
        b = flat[path]
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=3e-3, atol=3e-3,
                                   err_msg=str(path))


def test_hetero_tp_with_uneven_stage_layers():
    # Malleus composition: unequal layers AND unequal tp per stage
    cfg = _cfg(num_hidden_layers=3, pipeline_stage_layers=(2, 1))
    ids = _ids(seed=4)
    _, _, golden = _golden(cfg, ids)

    st = ParallelStrategy(mesh=MeshConfig(pp=2, tp=2), pp_tp_eff=(2, 1))
    mesh = st.build_mesh(devices=jax.devices()[:4])
    model = LlamaLMHeadModel(cfg, st)
    with ht.use_mesh(mesh):
        params = model.init(jax.random.key(1), mesh=mesh)
        out = jax.jit(lambda p, x: model(p, x, n_micro=2))(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden),
                               rtol=2e-3, atol=2e-3)


def test_bad_tp_eff_rejected():
    from hetu_tpu.parallel.hetero_pp import staged_stack_forward_hetero_tp
    with pytest.raises(ValueError):
        staged_stack_forward_hetero_tp(
            lambda e, m: None, {}, {}, jnp.zeros((2, 8, 4)),
            num_layers=2, pp=2, tp=2, tp_eff=(3, 1), mesh=None)

def test_full_train_step_driver_envelope():
    """The EXACT envelope the driver's dryrun topology 8 compiles: 8 devices,
    dp as an auto axis, ZeRO-1 optimizer shardings, remat=True, donated
    AdamW update. Guards the XLA:CPU AllReducePromotion crash (16-bit
    all-reduce with a partial-manual sdy constraint in its reducer) that
    r3 shipped because the unit tests only covered 4-dev fwd/grad."""
    from hetu_tpu import optim
    from hetu_tpu.optim.optimizer import state_shardings

    st = ParallelStrategy(mesh=MeshConfig(dp=2, pp=2, tp=2), zero=True,
                          pp_tp_eff=(2, 1))
    cfg = LlamaConfig.tiny(remat=True)
    mesh = st.build_mesh(devices=jax.devices()[:8])
    model = LlamaLMHeadModel(cfg, st)
    opt = optim.AdamW(lr=1e-3)
    with ht.use_mesh(mesh):
        params = model.init(jax.random.key(0), mesh=mesh)
        pshard, sshard = state_shardings(model, mesh, zero=True)
        opt_state = jax.jit(opt.init, out_shardings=sshard)(params)
        ids = jnp.zeros((8, 64), jnp.int32)
        ids = jax.device_put(ids, st.act_tokens().named_sharding(mesh))

        def step(params, opt_state, ids):
            loss, grads = jax.value_and_grad(
                lambda p: model(p, ids, labels=ids, n_micro=2))(params)
            grads, _ = optim.clip_by_global_norm(grads, 1.0)
            params, opt_state = opt.update(grads, opt_state, params)
            return params, opt_state, loss

        step_fn = jax.jit(step, out_shardings=(pshard, sshard, None),
                          donate_argnums=(0, 1))
        params, opt_state, loss = step_fn(params, opt_state, ids)
        assert bool(jnp.isfinite(loss))


def test_1f1b_pp_tp_eff_envelope():
    """pp_tp_eff under 1f1b runs (test_pipeline_1f1b.test_1f1b_hetero_tp
    is the parity test) but keeps the hetero envelope: SP/cp/MoE/dropout
    compositions must refuse loudly."""
    cfg = _cfg(num_experts=2)
    st = ParallelStrategy(mesh=MeshConfig(pp=2, tp=2), pp_tp_eff=(2, 1))
    model = LlamaLMHeadModel(cfg, st)
    ids = _ids()
    with pytest.raises(NotImplementedError, match="pp_tp_eff"):
        model.pipeline_train_grads({}, ids, ids, n_micro=2)


def test_gpt_hetero_tp_pipeline_matches_single_device():
    """GPT family through the hetero-TP pipeline (gpt_block_maker):
    logits parity with the single-device model."""
    from hetu_tpu.models.gpt import GPTConfig, GPTLMHeadModel
    cfg = GPTConfig.tiny(remat=False, compute_dtype=jnp.float32,
                         use_flash_attention=False, use_scan=True)
    ids = _ids(vocab=cfg.vocab_size)
    gmodel = GPTLMHeadModel(cfg, ParallelStrategy())
    gp = gmodel.init(jax.random.key(1))
    golden = gmodel(gp, ids)

    st = ParallelStrategy(mesh=MeshConfig(pp=2, tp=2), pp_tp_eff=(2, 1))
    mesh = st.build_mesh(devices=jax.devices()[:4])
    model = GPTLMHeadModel(cfg, st)
    with ht.use_mesh(mesh):
        params = model.init(jax.random.key(1), mesh=mesh)
        out = jax.jit(lambda p, x: model(p, x, n_micro=2))(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("tp_eff", [(2, 1), (2, 2)])
def test_hetero_tp_with_sequence_parallel(tp_eff):
    """SP + hetero-TP: between-block activations seq-sharded over the
    full tp axis (manual all-gather/reduce-scatter in the block makers) —
    logits parity with the single-device model."""
    cfg = _cfg()
    ids = _ids()
    _, _, golden = _golden(cfg, ids)

    st = ParallelStrategy(mesh=MeshConfig(pp=2, tp=2), pp_tp_eff=tp_eff,
                          sequence_parallel=True)
    mesh = st.build_mesh(devices=jax.devices()[:4])
    model = LlamaLMHeadModel(cfg, st)
    with ht.use_mesh(mesh):
        params = model.init(jax.random.key(1), mesh=mesh)
        out = jax.jit(lambda p, x: model(p, x, n_micro=2))(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden),
                               rtol=2e-3, atol=2e-3)


def test_gpt_hetero_tp_with_sequence_parallel():
    from hetu_tpu.models.gpt import GPTConfig, GPTLMHeadModel
    cfg = GPTConfig.tiny(remat=False, compute_dtype=jnp.float32,
                         use_flash_attention=False, use_scan=True)
    ids = _ids(vocab=cfg.vocab_size)
    gmodel = GPTLMHeadModel(cfg, ParallelStrategy())
    gp = gmodel.init(jax.random.key(1))
    golden = gmodel(gp, ids)

    st = ParallelStrategy(mesh=MeshConfig(pp=2, tp=2), pp_tp_eff=(2, 1),
                          sequence_parallel=True)
    mesh = st.build_mesh(devices=jax.devices()[:4])
    model = GPTLMHeadModel(cfg, st)
    with ht.use_mesh(mesh):
        params = model.init(jax.random.key(1), mesh=mesh)
        out = jax.jit(lambda p, x: model(p, x, n_micro=2))(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_sp_hetero_full_train_step_driver_envelope():
    """The dp+ZeRO+remat+donated-AdamW envelope WITH SP hetero (bf16):
    guards the 16-bit all-gather-transpose reduce-scatter crash the
    _gather_seq widening works around (test_xla_canaries pins it)."""
    from hetu_tpu import optim
    from hetu_tpu.optim.optimizer import state_shardings

    st = ParallelStrategy(mesh=MeshConfig(dp=2, pp=2, tp=2), zero=True,
                          pp_tp_eff=(2, 1), sequence_parallel=True)
    cfg = LlamaConfig.tiny(remat=True)
    mesh = st.build_mesh(devices=jax.devices()[:8])
    model = LlamaLMHeadModel(cfg, st)
    opt = optim.AdamW(lr=1e-3)
    with ht.use_mesh(mesh):
        params = model.init(jax.random.key(0), mesh=mesh)
        pshard, sshard = state_shardings(model, mesh, zero=True)
        opt_state = jax.jit(opt.init, out_shardings=sshard)(params)
        ids = jax.device_put(jnp.zeros((8, 64), jnp.int32),
                             st.act_tokens().named_sharding(mesh))

        def step(params, opt_state, ids):
            loss, grads = jax.value_and_grad(
                lambda p: model(p, ids, labels=ids, n_micro=2))(params)
            grads, _ = optim.clip_by_global_norm(grads, 1.0)
            params, opt_state = opt.update(grads, opt_state, params)
            return params, opt_state, loss

        step_fn = jax.jit(step, out_shardings=(pshard, sshard, None),
                          donate_argnums=(0, 1))
        params, opt_state, loss = step_fn(params, opt_state, ids)
        assert bool(jnp.isfinite(loss))


def test_hetero_tp_hidden_dropout():
    """hidden_dropout inside the hetero-TP pipeline: active masks change
    the output vs the deterministic run, training stays finite, and
    passing the SAME rng twice reproduces the masks exactly."""
    cfg = _cfg(hidden_dropout=0.3)
    ids = _ids()
    st = ParallelStrategy(mesh=MeshConfig(pp=2, tp=2), pp_tp_eff=(2, 1))
    mesh = st.build_mesh(devices=jax.devices()[:4])
    model = LlamaLMHeadModel(cfg, st)
    with ht.use_mesh(mesh):
        params = model.init(jax.random.key(1), mesh=mesh)
        det = jax.jit(lambda p: model(p, ids, labels=ids, n_micro=2))(params)
        k = jax.random.key(9)
        f = jax.jit(lambda p, r: model(p, ids, labels=ids, n_micro=2,
                                       rng=r, deterministic=False))
        drop1 = f(params, k)
        drop2 = f(params, k)
        other = f(params, jax.random.key(10))
    assert np.isfinite(float(drop1))
    assert abs(float(drop1) - float(det)) > 1e-4       # masks applied
    assert float(drop1) == float(drop2)                # deterministic replay
    assert abs(float(drop1) - float(other)) > 1e-6     # key-dependent
