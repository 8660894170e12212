"""Each family's plain float32 forward against the program's model of that
family (`models/llama`, `models/gpt`) at a tiny size on the CPU, and the
comparisons of `reference.py` written against it."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks import reference  # noqa: E402
from benchmarks.families import gpt2, llama  # noqa: E402


def load(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
        return json.load(f)


def llama_model(cfg, **kw):
    """The program's model in float32 throughout (the families build it
    with the program's bfloat16 compute type)."""
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
    return LlamaLMHeadModel(LlamaConfig(
        **{k: cfg[k] for k in llama.PUBLISHED}, **kw,
        compute_dtype=jnp.float32, param_dtype=jnp.float32))


def gpt2_model(cfg):
    from hetu_tpu.models.gpt.model import GPTConfig, GPTLMHeadModel
    return GPTLMHeadModel(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_hidden_layers=cfg["n_layer"], num_attention_heads=cfg["n_head"],
        max_position_embeddings=cfg["n_positions"],
        layer_norm_eps=cfg["layer_norm_epsilon"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        compute_dtype=jnp.float32, param_dtype=jnp.float32))


@pytest.fixture(scope="module", params=["llama", "gpt2"])
def tiny(request):
    """(configuration, the program's model, its parameters, the family's
    forward) of each family."""
    if request.param == "llama":
        cfg = load("tiny.json")
        model, forward = llama_model(cfg), llama.logits_at
    else:
        cfg = load("tiny-gpt2.json")
        model, forward = gpt2_model(cfg), gpt2.logits_at
    params = jax.jit(model.init)(jax.random.key(3))
    if request.param == "gpt2":
        # zero-initialised biases would hide a bias the forward forgets
        keys = iter(jax.random.split(jax.random.key(4), 64))
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: x + 0.1 * jax.random.normal(next(keys), x.shape)
            if "bias" in jax.tree_util.keystr(path)
            or "bqkv" in jax.tree_util.keystr(path)
            or "b_up" in jax.tree_util.keystr(path) else x, params)
    return cfg, model, params, forward


def test_reference_logits_equal_the_models_in_float32(tiny):
    cfg, model, params, forward = tiny
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], 48,
                                            dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        sys_logits = model(params, jnp.asarray(ids[None]))[0]
    ref = forward(params, jnp.asarray(ids), jnp.arange(48), cfg)
    np.testing.assert_allclose(np.asarray(sys_logits), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_tied_head_reads_the_embedding():
    """A llama configuration with `tie_word_embeddings`: the forward may
    not assume `params["lm_head"]`."""
    cfg = dict(load("tiny.json"), tie_word_embeddings=True)
    model = llama_model(dict(cfg))
    params = jax.jit(model.init)(jax.random.key(5))
    assert "lm_head" not in params
    ids = np.random.default_rng(6).integers(0, cfg["vocab_size"], 24,
                                            dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        sys_logits = model(params, jnp.asarray(ids[None]))[0]
    ref = llama.logits_at(params, jnp.asarray(ids), jnp.arange(24), cfg)
    np.testing.assert_allclose(np.asarray(sys_logits), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert llama.counts(cfg)["total_params"] == sum(
        x.size for x in jax.tree.leaves(params))


@pytest.mark.parametrize("family,name", [(llama, "tiny.json"),
                                         (gpt2, "tiny-gpt2.json")])
def test_counts_are_the_models_parameters(family, name):
    cfg = load(name)
    model = family.build_model(cfg, {"param_dtype": "float32"})
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    counts = family.counts(cfg)
    assert counts["total_params"] == sum(
        x.size for x in jax.tree.leaves(shapes))
    assert 0 < counts["matmul_params"] < counts["total_params"] + \
        cfg["vocab_size"] * shapes["model"][
            "embed" if family is llama else "wte"]["weight"].shape[1]


def test_reference_is_causal_and_position_dependent(tiny):
    cfg, _, params, forward = tiny
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], 32,
                                            dtype=np.int32)
    base = np.asarray(forward(params, jnp.asarray(ids), jnp.arange(32), cfg))
    changed = ids.copy()
    changed[20] = (changed[20] + 1) % cfg["vocab_size"]
    after = np.asarray(forward(params, jnp.asarray(changed), jnp.arange(32),
                               cfg))
    np.testing.assert_array_equal(base[:20], after[:20])   # causal
    assert np.abs(base[20:] - after[20:]).max() > 1e-4
    rolled = np.asarray(forward(
        params, jnp.asarray(np.roll(ids, 1)), jnp.arange(32), cfg))
    assert np.abs(rolled[1:] - base[:-1]).max() > 1e-4   # rotary / learned


def test_check_training_accepts_the_model_and_refuses_a_wrong_one(tiny):
    cfg, model, params, forward = tiny
    ids = np.random.default_rng(2).integers(0, cfg["vocab_size"], 32,
                                            dtype=np.int32)
    logits = model(params, jnp.asarray(ids[None]))[0]
    good = reference.check_training(forward, params, cfg, ids, logits)
    assert good["ok"] and good["loss_rel_err"] < 1e-4
    # logits of the sequence read backwards: a structural error
    bad = reference.check_training(forward, params, cfg, ids, logits[::-1])
    # (a tied head's logits share a part that does not depend on position)
    assert not bad["ok"]
    assert bad["logit_rms_rel_err"] > 4 * reference.LOGIT_RMS_RTOL
    # a lower precision than stated, even at the output alone: 8-bit
    # floats with 2 bits of mantissa
    coarse = logits.astype(jnp.float8_e5m2).astype(jnp.float32)
    assert not reference.check_training(forward, params, cfg, ids,
                                        coarse)["ok"]


def test_reference_gradient_is_the_models_with_masked_labels(tiny):
    """The loss and gradient norm of the model on sequences whose labels
    are masked past n tokens are the reference's on the first n tokens."""
    cfg, model, params, forward = tiny
    ids = np.random.default_rng(4).integers(0, cfg["vocab_size"], (2, 48),
                                            dtype=np.int32)
    labels = ids.copy()
    labels[:, 20:] = -100

    def loss(p):
        return model(p, jnp.asarray(ids), labels=jnp.asarray(labels))
    val, g = jax.value_and_grad(loss)(params)
    norm = float(jnp.sqrt(sum(jnp.sum(jnp.square(x))
                              for x in jax.tree.leaves(g))))
    ref = reference.loss_and_grad_norm(forward, params, cfg, ids[:, :20])
    assert ref["loss"] == pytest.approx(float(val), rel=1e-5)
    assert ref["grad_norm"] == pytest.approx(norm, rel=1e-4)


@pytest.mark.parametrize("fault,key", [
    ({}, None),
    ({"grad_norm": 2.0 * 2 ** 0.5}, "grad_norm_rel_err"),   # no dp average
    ({"loss": 5.5 * 1.01}, "step_loss_rel_err"),
    ({"v_sum_after": 0.95 * 3.0 + 0.05 * 0.8}, "adam_v_rel_err"),  # a leaf
])                                            # with 20% of g^2 left out
def test_check_train_step_refuses_a_wrong_step(fault, key):
    ref = {"loss": 5.5, "grad_norm": 2.0}
    system = {"loss": 5.5002, "grad_norm": 2.001, "v_sum_before": 3.0,
              "v_sum_after": 0.95 * 3.0 + 0.05 * 1.0}      # clipped to 1.0
    out = reference.check_train_step(ref, dict(system, **fault), clip=1.0,
                                     b2=0.95)
    assert out["ok"] is (key is None)
    if key:
        assert out[key] > 0.005
    none = reference.check_train_step(
        ref, dict(system, v_sum_before=None, v_sum_after=None), 1.0, 0.95)
    assert none["ok"] and none["adam_v_rel_err"] is None


def test_check_stream_accepts_greedy_and_refuses_a_wrong_token(tiny):
    cfg, _, params, forward = tiny
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg["vocab_size"], 9, dtype=np.int32)
    stream, tokens = list(prompt), []
    for _ in range(6):                              # greedy by the reference
        lg = forward(params, jnp.asarray(np.asarray(stream)),
                     jnp.asarray([len(stream) - 1]), cfg)
        tokens.append(int(np.asarray(lg)[0].argmax()))
        stream.append(tokens[-1])
    good = reference.check_stream(forward, params, cfg, prompt, tokens,
                                  pad_to=128)
    assert good["ok"] and good["argmax_equal"] == 6 and good["max_gap"] == 0
    wrong = list(tokens)
    wrong[3] = (wrong[3] + 1) % cfg["vocab_size"]
    bad = reference.check_stream(forward, params, cfg, prompt, wrong,
                                 pad_to=128)
    assert not bad["ok"] and bad["worst_gap"] > bad["tol_there"]


def test_logit_gap_tolerance_is_sixteen_bf16_ulps():
    assert reference.logit_gap_tolerance(5.0) == 16 * 2.0 ** -5
    assert reference.logit_gap_tolerance(3.0) == 16 * 2.0 ** -6
    assert reference.logit_gap_tolerance(0.9) == 16 * 2.0 ** -8
