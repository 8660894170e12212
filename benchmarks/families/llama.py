"""The Llama-dense family: what `hetu_tpu/models/llama` implements and the
Mistral and InternLM2 configuration files describe.

A family module is everything the harness knows about one model
architecture; a configuration file names it (`"family": "llama"`) and
`run.py` takes from it, and from nowhere else:

* `build_model` / `serve_config`: the program's model (and the engine's
  configuration) from the file's published keys;
* `logits_at`: the plain float32 forward of one sequence, which the
  comparisons of `reference.py` are written against;
* `counts`: the weights and widths `peaks.train_flops_per_token` needs;
* the kernels' cost functions, which a `roofline_pct` metric names in its
  `cost` key: `fn(cfg, counts) -> {"ops", "bytes"}`, `counts` being what
  the traced window held (`run.py`'s `window_counts`).

The block: pre-norm RMSNorm, grouped-query attention with rotary
embeddings (half-split rotation, as Hugging Face's Llama, Mistral and
InternLM2 code), SwiGLU MLP, final RMSNorm, head tied or not.  The forward
is straightforward float32 `jax.numpy` under
`default_matmul_precision("highest")`: no kernel, no cache, no batching
tricks.  (Under `jax.grad` a layer is recomputed in the backward pass, so
that one layer's intermediates are held and not all of them.)  It reads
the program's parameter tree (the weights are the system's own, made from
the seed) and nothing else of the program.

InternLM2 publishes one fused `wqkv`; the program keeps a fused
`[hidden, kv_heads, group + 2, head_dim]` weight as well; both are the
same equations as separate q, k and v projections, which is how they are
applied here.

The byte counts of the cost functions follow
`hetu_tpu/ops/pallas/traffic.py`'s *fused* path (one read of each input,
one write of each output); its `paged_attn_traffic` prices the whole page
table, `paged_attn_cost` the tokens a step's slots really hold, which is
what the kernel has to read.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: the configuration file's keys that `LlamaConfig` takes as they are
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "max_position_embeddings",
             "rms_norm_eps", "rope_theta", "tie_word_embeddings")


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def build_model(config: dict, how: dict, strategy=None):
    """The program's model.  `how` is how the job is run, not what the
    model is: the traffic file of a training cell, `config["serving"]` of
    a serving one (`param_dtype`, `remat_policy`)."""
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
    hd = config.get("head_dim")
    if hd and hd * config["num_attention_heads"] != config["hidden_size"]:
        raise ValueError("models/llama derives head_dim from hidden / heads")
    lcfg = LlamaConfig(
        param_dtype=jnp.dtype(how.get("param_dtype", "bfloat16")),
        remat_policy=how.get("remat_policy", "nothing"),
        **{k: config[k] for k in PUBLISHED})
    return LlamaLMHeadModel(lcfg, strategy)


def serve_config(config: dict):
    from hetu_tpu.serving.engine import ServeConfig
    sv = config["serving"]
    return ServeConfig(**{k: sv[k] for k in (
        "num_slots", "page_size", "max_len", "prefill_chunk", "num_pages",
        "kv_quant")})


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _rms_norm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(F32)


def _rope(x, theta):
    """x [s, heads, hd]; positions 0..s-1; half-split rotation."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.outer(jnp.arange(s, dtype=F32), inv)        # [s, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(x, lp, cfg):
    """One decoder layer on one sequence x [s, hidden] (float32)."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    group = nq // nkv
    s = x.shape[0]
    wqkv = lp["attn"]["wqkv"].astype(F32)       # [h, nkv, group + 2, hd]
    hd = wqkv.shape[-1]
    h = _rms_norm(x, lp["input_norm"]["weight"], eps)
    qkv = jnp.einsum("sh,hkgd->skgd", h, wqkv)
    q = qkv[:, :, :group, :].reshape(s, nq, hd)   # q head = kv * group + g
    k, v = qkv[:, :, group, :], qkv[:, :, group + 1, :]
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, group, axis=1)              # each q head's kv head
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qnd,knd->nqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("nqk,knd->qnd", probs, v).reshape(s, nq * hd)
    x = x + attn @ lp["attn"]["o_proj"]["weight"].astype(F32)
    h = _rms_norm(x, lp["post_norm"]["weight"], eps)
    gu = jnp.einsum("sh,hci->sci", h, lp["mlp"]["w_gate_up"].astype(F32))
    act = jax.nn.silu(gu[:, 0, :]) * gu[:, 1, :]
    return x + act @ lp["mlp"]["down_proj"]["weight"].astype(F32)


def hidden_states(params, ids, cfg):
    """Final-norm hidden states [s, hidden] of one sequence `ids` [s].
    Layers are walked with `lax.scan` over the stacked weights so that
    only one layer is ever held in float32."""
    with jax.default_matmul_precision("highest"):
        m = params["model"]
        x = m["embed"]["weight"][ids].astype(F32)

        @jax.checkpoint
        def body(x, lp):
            return _block(x, lp, cfg), None
        x, _ = jax.lax.scan(body, x, m["layers"]["layers"])
        return _rms_norm(x, m["final_norm"]["weight"], cfg["rms_norm_eps"])


def logits_at(params, ids, rows, cfg):
    """Reference logits [len(rows), vocab] at the positions `rows`."""
    with jax.default_matmul_precision("highest"):
        hid = hidden_states(params, ids, cfg)[rows]
        head = (params["model"]["embed"]["weight"].T
                if cfg["tie_word_embeddings"] else params["lm_head"])
        return hid @ head.astype(F32)


# ---------------------------------------------------------------------------
# the counts
# ---------------------------------------------------------------------------

def _widths(cfg: dict):
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return (hd, cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["num_hidden_layers"])


def counts(cfg: dict) -> dict:
    """`matmul_params`: weights that take part in a matrix multiplication
    per token: the attention projections, the SwiGLU MLP and the head
    (tied or not, it is a matmul).  The embedding is a lookup and the
    norm gains are elementwise.  `attn_width`: the query width summed
    over the layers, which the attention matmuls' operations scale with."""
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd, nq, nkv, L = _widths(cfg)
    per_layer = h * (nq * hd + 2 * nkv * hd) + nq * hd * h + 3 * h * i
    matmul = L * per_layer + h * v
    embed = 0 if cfg["tie_word_embeddings"] else v * h
    return {"matmul_params": matmul, "attn_width": L * nq * hd,
            "total_params": matmul + embed + (2 * L + 1) * h}


def flash_attn_cost(cfg: dict, window: dict, elem_bytes: float = 2.0) -> dict:
    """Required operations and bytes of causal flash attention, forward
    and backward, on ONE chip for the window's `steps` train steps of
    `batch` x `seq` tokens (`shards` chips share the batch x heads
    evenly).  Forward: 2 matmuls over the causal half.  Backward: 4 (dV,
    dP, dQ, dK); the kernel's recomputed QK^T is not required work.
    Bytes: forward reads q,k,v and writes o and the f32 row statistics;
    backward reads q,k,v,o,do and the statistics and writes dq,dk,dv."""
    hd, nq, nkv, L = _widths(cfg)
    batch, seq = window["batch"] * window["steps"], window["seq"]
    matmul = 2.0 * batch * nq * seq * seq * hd / 2.0      # one, causal
    ops = L * 6.0 * matmul
    q_io = elem_bytes * batch * seq * nq * hd
    kv_io = elem_bytes * batch * seq * nkv * hd
    lse = 4.0 * batch * nq * seq
    fwd = q_io + 2 * kv_io + q_io + lse
    bwd = (3 * q_io + 2 * kv_io + lse) + (q_io + 2 * kv_io)
    shards = window["shards"]
    return {"ops": ops / shards, "bytes": L * (fwd + bwd) / shards}


def paged_attn_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations and bytes of paged decode attention over all
    layers, for the single-token queries of the window's decode steps
    (`serve.decode_slot_steps`, the program's counter) whose contexts
    hold `serve.decode_context_tokens` cached positions in total: every
    cached K and V vector is read once, q is read and o written.  None
    where the program counted no decode step."""
    hd, nq, nkv, L = _widths(cfg)
    context_tokens = window["counters"].get("serve.decode_context_tokens")
    queries = window["counters"].get("serve.decode_slot_steps")
    if not context_tokens or not queries:
        return None
    ops = L * 2.0 * 2.0 * context_tokens * nq * hd
    bytes_ = L * elem_bytes * (2.0 * context_tokens * nkv * hd
                               + 2.0 * queries * nq * hd)
    return {"ops": ops, "bytes": bytes_}
