"""The GPT-2 family: what `hetu_tpu/models/gpt` implements and OpenAI's
GPT-2 `config.json` describes (its own key names: `n_embd`, `n_layer`,
`n_head`, `n_positions`, `layer_norm_epsilon`).

The second family, and the worked example of adding an architecture by
files (PERF.md s3): the same four things as `families/llama.py`, and no
line of the harness knows which of the two it runs.  In no cell of
`BENCHMARK.json` yet: `benchmarks/tests/rehearsal.json` runs it at a tiny
size on the CPU.

The block: learned position embeddings, pre-LayerNorm (with bias),
multi-head attention with biased fused q|k|v, GELU (tanh form,
`gelu_new`) MLP of width 4 x hidden with biases, final LayerNorm, and the
head TIED to the token embedding.  The forward is straightforward float32
`jax.numpy` under `default_matmul_precision("highest")`; it reads the
program's parameter tree and nothing else of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.families import llama
from benchmarks.families.llama import serve_config  # noqa: F401 (the
#                          engine's six keys are the same for this family)

F32 = jnp.float32


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def build_model(config: dict, how: dict, strategy=None):
    """The program's model; `how` as in `families/llama.py`."""
    from hetu_tpu.models.gpt.model import GPTConfig, GPTLMHeadModel
    gcfg = GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_hidden_layers=config["n_layer"],
        num_attention_heads=config["n_head"],
        max_position_embeddings=config["n_positions"],
        layer_norm_eps=config["layer_norm_epsilon"],
        tie_word_embeddings=config["tie_word_embeddings"],
        param_dtype=jnp.dtype(how.get("param_dtype", "bfloat16")),
        remat_policy=how.get("remat_policy", "nothing"))
    return GPTLMHeadModel(gcfg, strategy)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * p["weight"].astype(F32)
            + p["bias"].astype(F32))


def _block(x, lp, cfg):
    """One decoder layer on one sequence x [s, hidden] (float32)."""
    eps, s = cfg["layer_norm_epsilon"], x.shape[0]
    wqkv = lp["attn"]["wqkv"].astype(F32)           # [h, heads, 3, hd]
    hd = wqkv.shape[-1]
    h = _layer_norm(x, lp["ln1"], eps)
    qkv = jnp.einsum("sh,hngd->sngd", h, wqkv) + lp["attn"]["bqkv"].astype(F32)
    q, k, v = qkv[:, :, 0, :], qkv[:, :, 1, :], qkv[:, :, 2, :]
    scores = jnp.einsum("qnd,knd->nqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("nqk,knd->qnd", probs, v).reshape(s, -1)
    o = lp["attn"]["o_proj"]
    x = x + attn @ o["weight"].astype(F32) + o["bias"].astype(F32)
    h = _layer_norm(x, lp["ln2"], eps)
    m = lp["mlp"]
    up = h @ m["w_up"].astype(F32) + m["b_up"].astype(F32)
    return (x + jax.nn.gelu(up, approximate=True)
            @ m["down"]["weight"].astype(F32) + m["down"]["bias"].astype(F32))


def hidden_states(params, ids, cfg):
    """Final-norm hidden states [s, hidden] of one sequence `ids` [s]."""
    with jax.default_matmul_precision("highest"):
        m = params["model"]
        x = (m["wte"]["weight"][ids].astype(F32)
             + m["wpe"][: ids.shape[0]].astype(F32))

        @jax.checkpoint
        def body(x, lp):
            return _block(x, lp, cfg), None
        x, _ = jax.lax.scan(body, x, m["blocks"])
        return _layer_norm(x, m["final_ln"], cfg["layer_norm_epsilon"])


def logits_at(params, ids, rows, cfg):
    """Reference logits [len(rows), vocab] at the positions `rows`."""
    with jax.default_matmul_precision("highest"):
        hid = hidden_states(params, ids, cfg)[rows]
        head = (params["model"]["wte"]["weight"].T
                if cfg["tie_word_embeddings"] else params["lm_head"])
        return hid @ head.astype(F32)


# ---------------------------------------------------------------------------
# the counts
# ---------------------------------------------------------------------------

def counts(cfg: dict) -> dict:
    """As `families/llama.counts`: the q|k|v, output, up and down
    projections and the (tied) head multiply; embeddings, biases and norms
    do not."""
    h, L, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    matmul = L * 12 * h * h + h * v
    untied = 0 if cfg["tie_word_embeddings"] else v * h
    return {"matmul_params": matmul, "attn_width": L * h,
            "total_params": (L * (12 * h * h + 13 * h) + v * h + untied
                             + cfg["n_positions"] * h + 2 * h)}


def paged_attn_cost(cfg: dict, window: dict):
    """`families/llama.paged_attn_cost` for multi-head attention: every
    head has its own cached K and V."""
    return llama.paged_attn_cost({
        "hidden_size": cfg["n_embd"], "num_attention_heads": cfg["n_head"],
        "num_key_value_heads": cfg["n_head"],
        "num_hidden_layers": cfg["n_layer"]}, window)
