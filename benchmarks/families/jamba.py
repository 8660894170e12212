"""The Jamba family (`model_type: jamba`): what `hetu_tpu/models/jamba`
implements and
https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json
describes, under its published key names.

The plain reference (`logits_at`) is float32 `jax.numpy` under
`default_matmul_precision("highest")`, reading the program's parameter
tree and nothing else of the program: no kernel, no cache, no chunks, no
state carried between calls, no pages.  Layer l of L, pre-norm RMSNorm
(a learned gain, `rms_norm_eps`), x the normed hidden state of one
token, every layer followed by the bias-free SwiGLU
`W_down [silu(x W_gate) * (x W_up)]`; no positional encoding; the head is
the embedding, transposed:

* l % attn_layer_period != attn_layer_offset: Mamba-1.  [u, z] = x W_in;
  u' = silu(conv_K(u) + b_c), causal and depthwise (zeros before the
  sequence); [dt_r, B_t, C_t] = u' W_x; dt_r, B_t and C_t each through
  an RMSNorm over its own width (a learned gain, eps `rms_norm_eps`);
  Delta_t = softplus(dt_r W_dt + b_dt); A = -exp(A_log); a `lax.scan`
  over the positions of
      h_t = exp(Delta_t A) * h_{t-1} + (Delta_t u'_t) B_t^T
      y_t = h_t C_t + D u'_t
  from h = 0; out = W_out [y_t * silu(z_t)].
* l % attn_layer_period == attn_layer_offset (layers 7 and 21 of 28):
  q = x W_q (`num_attention_heads` heads), k = x W_k, v = x W_v
  (`num_key_value_heads` heads: ONE), no bias, no rotation; causal
  softmax(q k^T / sqrt(head_dim)) v over an explicit mask; W_o.

Departures, each where it is made: (1) the Mamba state is laid [d_state,
d_inner] as the program's parameters are (`A_log` [16, 5120]); the
equations are elementwise and do not see the order.  (2) The three
inner norms' gains are read from the program's ONE vector `inner_norm`
(dt_r's 160, then B's 16, then C's 16).  (3) attention runs in blocks of
`Q_BLOCK` query rows so that the longest checked stream fits; every
block sees every key under its explicit mask.  (4) `logits_at`
multiplies the head for the rows asked for only; every row goes through
every layer (a later row's state and keys need it).

The family also brings its cost functions under the names the standing
rule files ask of a cell's family (`ssm_chunk_cost`, `ssm_state_cost`,
`paged_attn_cost`, `chunk_attn_cost`) and reads them
through the harness's rule `scope_roofline_pct` (benchmarks/trace.py): the two scan
computations are XLA compositions, whose device events carry no name of
their own.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.families.kimi_k2 import _rms_norm, _swiglu
from benchmarks.families.phi4flash import serve_config  # noqa: F401
# at import, not in `build_model`: a program without the family (the
# parent of PR 47) then fails in `run.load_cell`, at once, with exit 2
from hetu_tpu.models.jamba import JambaConfig, JambaLMHeadModel

F32 = jnp.float32
Q_BLOCK = 128

#: the configuration file's keys that `JambaConfig` takes as they are
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "attn_layer_period", "attn_layer_offset",
             "expert_layer_period", "expert_layer_offset", "num_experts",
             "num_experts_per_tok", "mamba_d_state", "mamba_d_conv",
             "mamba_expand", "mamba_dt_rank", "mamba_conv_bias",
             "mamba_proj_bias", "hidden_act", "rms_norm_eps",
             "sliding_window", "max_position_embeddings",
             "tie_word_embeddings")


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def build_model(config: dict, how: dict, strategy=None):
    """The program's model: the published keys as they are."""
    if config["model_type"] != "jamba":
        raise ValueError("models/jamba implements model_type='jamba', the "
                         f"file says {config['model_type']!r}")
    dtype = jnp.dtype(how.get("param_dtype", "bfloat16"))
    cfg = JambaConfig(
        param_dtype=dtype, compute_dtype=dtype,
        initializer_range=config["assumed"]["initializer_range"],
        **{k: config[k] for k in PUBLISHED})
    return JambaLMHeadModel(cfg, strategy)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _sizes(cfg):
    return (cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"],
            cfg["mamba_d_conv"], cfg["mamba_dt_rank"])


def mixer_of(layer: int, cfg) -> str:
    return "full" if layer % cfg["attn_layer_period"] \
        == cfg["attn_layer_offset"] else "ssm"


def _layers(params, cfg):
    """(layer, mixer, the layer's parameters) in layer order, out of the
    program's tree: runs of like neighbours under `layers_<first>`, a
    Mamba run's parameters stacked, an attention layer's its own."""
    m = params["model"]
    out, first = [], 0
    for l in range(cfg["num_hidden_layers"]):
        mixer = mixer_of(l, cfg)
        if l and mixer != mixer_of(l - 1, cfg):
            first = l
        run = m[f"layers_{first}"]
        out.append((l, mixer, run if mixer == "full" else jax.tree.map(
            lambda a, i=l - first: a[i], run)))
    return out


def _mamba(h, ap, cfg, bf16_state=False, inner_norms=True):
    """The Mamba-1 mixer of one sequence h [s, hidden] (normed) from zero
    state -> (out [s, hidden], y [s, d_inner] before the gate).
    Controls: `bf16_state`, the carried state rounded to bfloat16 after
    every position (`reduce_precision`: the compiler drops an `astype`
    round trip); `inner_norms` False, the three norms left out."""
    di, N, K, R = _sizes(cfg)
    s, eps = h.shape[0], cfg["rms_norm_eps"]
    uz = h @ ap["w_in"].astype(F32)
    u, z = uz[:, :di], uz[:, di:]
    xx = jnp.concatenate([jnp.zeros((K - 1, di), F32), u])
    w = ap["conv_w"].astype(F32)
    u1 = jax.nn.silu(sum(w[i] * xx[i: i + s] for i in range(K))
                     + ap["conv_b"].astype(F32))
    x = u1 @ ap["w_x"].astype(F32)
    dt_r, B, C = x[:, :R], x[:, R: R + N], x[:, R + N:]
    if inner_norms:
        g = ap["inner_norm"]
        dt_r, B, C = (_rms_norm(dt_r, g[:R], eps),
                      _rms_norm(B, g[R: R + N], eps),
                      _rms_norm(C, g[R + N:], eps))
    delta = jax.nn.softplus(dt_r @ ap["w_dt"].astype(F32)
                            + ap["dt_bias"].astype(F32))
    A = -jnp.exp(ap["A_log"].astype(F32))                    # [N, di]

    def one(hs, x):
        u_t, d_t, B_t, C_t = x
        hs = jnp.exp(d_t[None, :] * A) * hs \
            + (d_t * u_t)[None, :] * B_t[:, None]
        if bf16_state:
            hs = jax.lax.reduce_precision(hs, exponent_bits=8,
                                          mantissa_bits=7)
        return hs, jnp.sum(hs * C_t[:, None], axis=0)
    _, y = jax.lax.scan(one, jnp.zeros((N, di), F32), (u1, delta, B, C))
    y = y + ap["D"].astype(F32) * u1
    return (y * jax.nn.silu(z)) @ ap["w_out"].astype(F32), y


def _attend(h, ap, cfg):
    """Causal attention of one sequence h [s, hidden] (normed) over its
    own keys and values, in blocks of `Q_BLOCK` query rows under an
    explicit mask."""
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nq
    s = h.shape[0]
    x = h @ ap["w_qkv"].astype(F32)
    q = x[:, :nq * hd].reshape(s, nq, hd)
    k = jnp.repeat(x[:, nq * hd: (nq + nkv) * hd].reshape(s, nkv, hd),
                   nq // nkv, axis=1)
    v = jnp.repeat(x[:, (nq + nkv) * hd:].reshape(s, nkv, hd),
                   nq // nkv, axis=1)
    qb = math.gcd(s, Q_BLOCK)

    def rows(blk):
        q_blk, at = blk
        seen = jnp.arange(s)[None, :] <= at[:, None]
        sc = jnp.einsum("qhd,khd->hqk", q_blk, k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)
    a = jax.lax.map(rows, (q.reshape(s // qb, qb, nq, hd),
                           jnp.arange(s).reshape(s // qb, qb)))
    return a.reshape(s, nq * hd) @ ap["w_o"].astype(F32)


def hidden_states(params, ids, cfg, control=None):
    """The full forward: final-norm hidden states [s, hidden] of one
    sequence `ids` [s], every row through every layer."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = params["model"]["embed"]["weight"][ids].astype(F32)
        for _, mixer, lp in _layers(params, cfg):
            h = _rms_norm(x, lp["input_norm"]["weight"], eps)
            x = x + (_attend(h, lp["attn"], cfg) if mixer == "full"
                     else _mamba(h, lp["attn"], cfg,
                                 bf16_state=control == "bf16_state",
                                 inner_norms=control != "no_inner_norms")[0])
            x = x + _swiglu(_rms_norm(x, lp["post_norm"]["weight"], eps),
                            lp["mlp"]["w_gate_up"], lp["mlp"]["w_down"])
        return _rms_norm(x, params["model"]["final_norm"]["weight"], eps)


def logits_at(params, ids, rows, cfg, control=None):
    """Reference logits [len(rows), vocab] at the positions `rows`.
    `control` (tier-1 tests and the chip's control run only): one of
    "bf16_state", "no_inner_norms": the forward with that ONE thing done
    wrongly, which the comparison has to tell from the program."""
    x = hidden_states(params, ids, cfg, control)[rows]
    with jax.default_matmul_precision("highest"):
        return x @ params["model"]["embed"]["weight"].astype(F32).T


# ---------------------------------------------------------------------------
# the counts
# ---------------------------------------------------------------------------

def _mixer_counts(cfg):
    kinds = [mixer_of(l, cfg) for l in range(cfg["num_hidden_layers"])]
    return {k: kinds.count(k) for k in ("ssm", "full")}


def counts(cfg: dict) -> dict:
    """`matmul_params`: the weights one token multiplies (every layer;
    the tied head once).  `total_params`: everything held, as
    `model.num_params` counts it (3,029,337,472 at the published
    sizes)."""
    h, v, I = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = h // nq
    di, N, K, R = _sizes(cfg)
    n = _mixer_counts(cfg)
    attn_w = h * (nq + 2 * nkv) * hd + nq * hd * h
    mamba_mm = h * 2 * di + di * (R + 2 * N) + R * di + di * h
    mamba_small = K * di + di + di + N * di + di + R + 2 * N
    L = cfg["num_hidden_layers"]
    mlp = 3 * h * I
    return {
        "matmul_params": n["ssm"] * mamba_mm + n["full"] * attn_w + L * mlp
        + h * v,
        "attn_width": n["full"] * nq * hd,
        "total_params": (n["ssm"] * (mamba_mm + mamba_small)
                         + n["full"] * attn_w + L * (mlp + 2 * h)
                         + h * v + h)}


def ssm_state_bytes_per_slot(cfg: dict, elem_bytes: float = 2.0) -> float:
    di, N, K, _ = _sizes(cfg)
    return _mixer_counts(cfg)["ssm"] * (4.0 * N * di
                                        + elem_bytes * (K - 1) * di)


def ssm_chunk_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations and bytes of the selective scan
    (ops/selective_scan.chunk_scan) over the prompt tokens the window's
    chunk programs prefilled, all Mamba layers: families/phi4flash's
    count at this family's sizes.  A position a channel and state lane:
    the decay's exponent and exponential, the state's multiply-add, the
    input's product and the output's multiply-add (7 operations; none is
    a matrix product, and they are laid against the ONE peak `peaks.py`
    has, the MXU's, so the share reads low by the ratio of the two units:
    PERF.md s7).  Bytes: u', B, C in the model's dtype and Delta in
    float32 read, y written in float32, a position; the state read and
    written once a chunk launch.  None where no chunk ran."""
    tokens = window["counters"].get("serve.prefill_tokens")
    launches = window["counters"].get("serve.prefill_chunks")
    if not tokens or not launches:
        return None
    di, N, _, _ = _sizes(cfg)
    n = _mixer_counts(cfg)["ssm"]
    return {"ops": n * 7.0 * N * di * tokens,
            "bytes": n * (tokens * (elem_bytes * (di + 2 * N) + 8.0 * di)
                          + launches * 2 * 4.0 * N * di)}


def ssm_state_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required bytes and operations of the Mamba STATE of the window's
    decode steps: the state of each row that decodes read once and
    written once (the program's own count, `serve.ssm_state_bytes`) and
    the step's 7 operations a channel and state lane.  The layers'
    weights are NOT in it (families/phi4flash says why): the share is
    laid against the scopes that hold the state alone (`ssm`,
    `ssm_conv`, `ssm_step`).  None where the program counted no decode
    step."""
    state = window["counters"].get("serve.ssm_state_bytes")
    rows = window["counters"].get("serve.decode_slot_steps")
    if not state or not rows:
        return None
    di, N, _, _ = _sizes(cfg)
    return {"ops": _mixer_counts(cfg)["ssm"] * rows * 7.0 * N * di,
            "bytes": state}


def paged_attn_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations and bytes of paged decode attention over the
    attention layers, for the single-token queries of the window's decode
    steps: what the MODEL needs, whatever implements it.  Each attention
    layer reads every cached K and V row of the steps' contexts once
    (`serve.decode_context_tokens`, one layer's count): ONE head of
    `head_dim` values each a position; the operations are q . k and
    p . v a query head.  None where the program counted no decode step."""
    c = window["counters"]
    tokens, queries = (c.get("serve.decode_context_tokens"),
                       c.get("serve.decode_slot_steps"))
    if not tokens or not queries:
        return None
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nq
    layers = _mixer_counts(cfg)["full"]
    return {"ops": layers * 4.0 * tokens * nq * hd,
            "bytes": elem_bytes * layers * (2.0 * tokens * nkv * hd
                                            + queries * nq * 2 * hd)}


def chunk_attn_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations of the chunk program's attention over the
    attention layers: every (query, key) pair the causal mask lets
    through (`serve.prefill_attended_keys`, counted per chunk launch and
    ONE layer) is a q . k and a p . v of `head_dim` for each of the query
    heads.  The bytes are the chunk's own q and o once a layer and the K
    and V of the positions its queries see, ONE head each.  None where
    the program counted no chunk."""
    c = window["counters"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nq
    layers = _mixer_counts(cfg)["full"]
    pairs = c.get("serve.prefill_attended_keys")
    rows, launches = c.get("serve.prefill_tokens"), c.get(
        "serve.prefill_chunks")
    if not pairs or not rows or not launches:
        return None
    # a launch's queries see, together, the keys its LAST query sees:
    # pairs / rows is the mean over queries, at least half of that
    seen = pairs / rows * launches
    return {"ops": layers * 4.0 * nq * hd * pairs,
            "bytes": elem_bytes * layers * (rows * 2 * nq * hd
                                            + seen * 2 * nkv * hd)}
