"""The Phi-4-mini-flash family (`model_type: phi4flash`, the SambaY
decoder-hybrid-decoder of arXiv:2507.06607): what
`hetu_tpu/models/phi4_flash` implements and
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json
describes, under its published key names (Mamba-1's four sizes, which
config does not give, under the `mamba_*` names of the configuration
file's `assumed`).

The plain reference (`logits_at`) is float32 `jax.numpy` under
`default_matmul_precision("highest")`, reading the program's parameter
tree and nothing else of the program: no kernel, no cache, no chunks, no
state carried between calls, no lane rows, no zero-padded queries.  Layer
l of L, pre-norm LayerNorm (with bias, `layer_norm_eps`), x the normed
hidden state of one token, every layer followed by the bias-free SwiGLU
`W_down [silu(x W_gate) * (x W_up)]`; no positional encoding; the head is
the embedding, transposed:

* l even, l <= L/2: Mamba-1.  [u, z] = x W_in; u' = silu(conv_K(u) +
  b_c), causal and depthwise (zeros before the sequence); [dt_r, B_t,
  C_t] = u' W_x; Delta_t = softplus(dt_r W_dt + b_dt); A = -exp(A_log);
  a `lax.scan` over the positions of
      h_t = exp(Delta_t A) * h_{t-1} + (Delta_t u'_t) B_t^T
      y_t = h_t C_t + D u'_t
  from h = 0; out = W_out [y_t * silu(z_t)].  Layer L/2's y is the
  MEMORY m, before the gate.
* l odd, l < L/2: differential attention over the last `sliding_window`
  positions; l = L/2 + 1: over every position.  The 40 query heads of 64
  are 20 pairs (heads 2i, 2i + 1 of W_q's columns), the 20 K/V heads 10
  pairs, query pair i reading K/V pair i // 2: a_j = softmax(q_j k_j^T /
  8 + mask) [v_1 | v_2], two softmaxes over explicit masks; o = (1 -
  lambda_init) * RMSNorm_128(a_1 - lambda a_2) (a learned gain, eps
  `layer_norm_eps`), lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda_init, lambda_init = 0.8 - 0.6 exp(-0.3 l) RECOMPUTED HERE from
  the layer's index (the program keeps it as a buffer); W_o with bias.
* l even, l > L/2 + 1: gated memory unit, out = W_2 [m_t * silu(x W_1)].
* l odd, l > L/2 + 1: differential CROSS attention: W_q and W_o of its
  own, layer L/2 + 1's keys and values, causal, no window.

Departures, each where it is made: (1) the Mamba state is laid [d_state,
d_inner] as the program's parameters are (`A_log` [16, 5120]); the
equations are elementwise and do not see the order.  (2) `logits_at`
evaluates layer L/2 + 1's attention and MLP and the whole cross-decoder
ONLY AT THE ROWS ASKED FOR: those layers are functions of the row's own
hidden state, the memory at the row and layer L/2 + 1's keys and values,
which are made for every position; `hidden_states` (every row through
every layer) is the full forward, and a tier-1 test holds the two to each
other.  (3) attention runs in blocks of `Q_BLOCK` query rows so that
24,576 positions fit; every block sees every key under its explicit mask.

The family also brings its cost functions (`ssm_chunk_cost`,
`ssm_state_cost`, `paged_attn_cost`, `chunk_attn_cost`) and reads them
through the harness's rule `scope_roofline_pct` (benchmarks/trace.py): the two scan
computations are XLA compositions, whose device events carry no name of
their own.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.families.kimi_k2 import _swiglu
# at import, not in `build_model`: a program without the family (the
# parent of PR 43) then fails in `run.load_cell`, at once, with exit 2
from hetu_tpu.models.phi4_flash import (Phi4FlashConfig,
                                        Phi4FlashLMHeadModel)

F32 = jnp.float32
Q_BLOCK = 128

#: the configuration file's keys that `Phi4FlashConfig` takes as they are
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "sliding_window", "mb_per_layer",
             "layer_norm_eps", "max_position_embeddings",
             "tie_word_embeddings", "mlp_bias", "lm_head_bias")
#: the keys of `assumed` that are sizes the model is built from
ASSUMED_SIZES = ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                 "mamba_dt_rank")


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def build_model(config: dict, how: dict, strategy=None):
    """The program's model: the published keys as they are, Mamba's
    sizes from the file's `assumed`."""
    for key, want in (("hidden_act", "silu"), ("model_type", "phi4flash"),
                      ("embd_pdrop", 0), ("resid_pdrop", 0)):
        if config[key] != want:
            raise ValueError(f"models/phi4_flash implements {key}={want!r}, "
                             f"the file says {config[key]!r}")
    dtype = jnp.dtype(how.get("param_dtype", "bfloat16"))
    cfg = Phi4FlashConfig(
        param_dtype=dtype, compute_dtype=dtype,
        **{k: config[k] for k in PUBLISHED},
        **{k: config["assumed"][k] for k in ASSUMED_SIZES})
    return Phi4FlashLMHeadModel(cfg, strategy)


def serve_config(config: dict):
    """families/llama's, and how many slots may prefill at once (each
    holds a scratch of max_len positions of the full layer)."""
    from hetu_tpu.serving.engine import ServeConfig
    sv = config["serving"]
    return ServeConfig(**{k: sv[k] for k in (
        "num_slots", "page_size", "max_len", "prefill_chunk", "num_pages",
        "kv_quant", "max_prefilling")})


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _sizes(cfg):
    a = cfg["assumed"]
    return (a["mamba_expand"] * cfg["hidden_size"], a["mamba_d_state"],
            a["mamba_d_conv"], a["mamba_dt_rank"])


def mixer_of(layer: int, cfg) -> str:
    half = cfg["num_hidden_layers"] // 2
    if layer <= half:
        return "ssm" if layer % cfg["mb_per_layer"] == 0 else "window"
    if layer == half + 1:
        return "full"
    return "gmu" if layer % cfg["mb_per_layer"] == 0 else "cross"


def _layers(params, cfg):
    """One layer's parameters and mixer, in layer order, out of the
    program's tree (two stacks of pairs and two layers of their own)."""
    m = params["model"]
    half, mb = cfg["num_hidden_layers"] // 2, cfg["mb_per_layer"]

    def of(stack, name, i):
        return jax.tree.map(lambda a: a[i], stack[name])
    out = []
    for l in range(cfg["num_hidden_layers"]):
        mixer = mixer_of(l, cfg)
        if l < half:
            lp = of(m["self_decoder"], mixer, l // mb)
        elif l == half:
            lp = m["memory"]
        elif l == half + 1:
            lp = m["shared_kv"]
        else:
            lp = of(m["cross_decoder"], mixer, (l - half - 2) // mb)
        out.append((l, mixer, lp))
    return out


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["weight"].astype(F32) \
        + p["bias"].astype(F32)


def _mamba(h, ap, cfg, bf16_state=False):
    """The Mamba-1 mixer of one sequence h [s, hidden] (normed) from zero
    state: (out [s, hidden], y [s, d_inner] before the gate, and after
    it).
    `bf16_state` (a control): the carried state rounded to bfloat16 after
    every position (`reduce_precision`: the compiler drops an `astype`
    round trip)."""
    di, N, K, R = _sizes(cfg)
    s = h.shape[0]
    uz = h @ ap["w_in"].astype(F32)
    u, z = uz[:, :di], uz[:, di:]
    xx = jnp.concatenate([jnp.zeros((K - 1, di), F32), u])
    w = ap["conv_w"].astype(F32)
    u1 = jax.nn.silu(sum(w[i] * xx[i: i + s] for i in range(K))
                     + ap["conv_b"].astype(F32))
    x = u1 @ ap["w_x"].astype(F32)
    delta = jax.nn.softplus(x[:, :R] @ ap["w_dt"].astype(F32)
                            + ap["dt_bias"].astype(F32))
    B, C = x[:, R: R + N], x[:, R + N:]
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
    gated = y * jax.nn.silu(z)
    return gated @ ap["w_out"].astype(F32), y, gated


def _keys_values(h, ap, cfg):
    """(k, v) [s, K/V pairs, 2, head_dim] of the tokens h [s, hidden]
    (normed): W_qkv's columns are q, then k, then v, a pair's two heads
    neighbours."""
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nq
    s = h.shape[0]
    x = h @ ap["w_qkv"].astype(F32)[:, nq * hd:] \
        + ap["b_qkv"].astype(F32)[nq * hd:]
    return (x[:, :nkv * hd].reshape(s, nkv // 2, 2, hd),
            x[:, nkv * hd:].reshape(s, nkv // 2, 2, hd))


def _lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _diff_attend(h, pos, k, v, ap, cfg, layer, window=None,
                 fixed_lambda=False):
    """Differential attention of the queries h [n, hidden] (normed) at
    positions `pos` [n] over the keys and values of positions 0..s-1, in
    blocks of `Q_BLOCK` query rows; `fixed_lambda`: a control that leaves
    lambda at lambda_init."""
    nq = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // nq
    n, s, pairs = h.shape[0], k.shape[0], nq // 2
    group = pairs // k.shape[1]
    q = (h @ ap["w_qkv"].astype(F32)[:, :nq * hd]
         + ap["b_qkv"].astype(F32)[:nq * hd]).reshape(n, pairs, 2, hd)
    kq = jnp.repeat(k, group, axis=1)                 # [s, pairs, 2, hd]
    vq = jnp.repeat(v, group, axis=1).reshape(s, pairs, 2 * hd)
    li = _lambda_init(layer)
    lam = li if fixed_lambda else (
        jnp.exp(jnp.sum(ap["lambda_q1"] * ap["lambda_k1"]))
        - jnp.exp(jnp.sum(ap["lambda_q2"] * ap["lambda_k2"])) + li)
    qb = math.gcd(n, Q_BLOCK)

    def rows(blk):
        q_blk, at = blk
        seen = jnp.arange(s)[None, :] <= at[:, None]
        if window is not None:
            seen = seen & (jnp.arange(s)[None, :] > at[:, None] - window)

        def one(j):
            sc = jnp.einsum("qpd,kpd->pqk", q_blk[:, :, j], kq[:, :, j]) \
                * hd ** -0.5
            p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("pqk,kpd->qpd", p, vq)
        return one(0) - lam * one(1)
    d = jax.lax.map(rows, (q.reshape(n // qb, qb, pairs, 2, hd),
                           pos.reshape(n // qb, qb))).reshape(n, pairs,
                                                              2 * hd)
    d = d * jax.lax.rsqrt(jnp.mean(jnp.square(d), -1, keepdims=True)
                          + cfg["layer_norm_eps"]) \
        * ap["subln"].astype(F32) * (1.0 - li)
    return d.reshape(n, -1) @ ap["w_o"].astype(F32) + ap["b_o"].astype(F32)


def _gmu(h, m, ap):
    return (m * jax.nn.silu(h @ ap["w_1"].astype(F32))) \
        @ ap["w_2"].astype(F32)


def _mlp(x, lp, eps):
    return x + _swiglu(_layer_norm(x, lp["post_norm"], eps),
                       lp["mlp"]["w_gate_up"], lp["mlp"]["w_down"])


def _self_decoder(params, ids, cfg, control=None):
    """Layers 0 .. L/2 for every position: (x [s, hidden] entering layer
    L/2 + 1, the memory [s, d_inner])."""
    eps = cfg["layer_norm_eps"]
    half = cfg["num_hidden_layers"] // 2
    x = params["model"]["embed"]["weight"][ids].astype(F32)
    pos = jnp.arange(ids.shape[0])
    memory = None
    for l, mixer, lp in _layers(params, cfg)[: half + 1]:
        h = _layer_norm(x, lp["input_norm"], eps)
        if mixer == "ssm":
            out, y, gated = _mamba(h, lp["attn"], cfg,
                                   control == "bf16_state")
            if l == half:
                # the memory is the scan's output BEFORE the gate
                memory = gated if control == "memory_after_gate" else y
        else:
            out = _diff_attend(h, pos, *_keys_values(h, lp["attn"], cfg),
                               lp["attn"], cfg, l,
                               window=cfg["sliding_window"],
                               fixed_lambda=control == "fixed_lambda")
        x = _mlp(x + out, lp, eps)
    return x, memory


def _rows_onward(params, x, memory, rows, cfg, control=None):
    """Layer L/2 + 1 and the cross-decoder for the tokens at `rows`
    [n] alone, given x [s, hidden] entering layer L/2 + 1 and the memory
    [s, d_inner] of every position -> final-norm hidden [n, hidden]."""
    eps = cfg["layer_norm_eps"]
    half = cfg["num_hidden_layers"] // 2
    layers = _layers(params, cfg)
    _, _, shared = layers[half + 1]
    k, v = _keys_values(_layer_norm(x, shared["input_norm"], eps),
                        shared["attn"], cfg)
    x, m = x[rows], memory[rows]
    for l, mixer, lp in layers[half + 1:]:
        h = _layer_norm(x, lp["input_norm"], eps)
        if mixer == "gmu":
            out = _gmu(h, m, lp["attn"])
        else:
            out = _diff_attend(
                h, rows, k, v, lp["attn"], cfg, l,
                window=cfg["sliding_window"]
                if control == "cross_window" and mixer == "cross" else None,
                fixed_lambda=control == "fixed_lambda")
        x = _mlp(x + out, lp, eps)
    return _layer_norm(x, params["model"]["final_norm"], eps)


def hidden_states(params, ids, cfg):
    """The full forward: final-norm hidden states [s, hidden] of one
    sequence `ids` [s], every row through every layer."""
    with jax.default_matmul_precision("highest"):
        x, memory = _self_decoder(params, ids, cfg)
        return _rows_onward(params, x, memory, jnp.arange(ids.shape[0]),
                            cfg)


def logits_at(params, ids, rows, cfg, control=None):
    """Reference logits [len(rows), vocab] at the positions `rows`
    (module docstring, departure 2).  `control` (tier-1 tests and the
    chip's control run only): one of "bf16_state", "fixed_lambda",
    "memory_after_gate", "cross_window": the forward with that ONE thing
    done wrongly, which the comparison has to tell from the program."""
    with jax.default_matmul_precision("highest"):
        x, memory = _self_decoder(params, ids, cfg, control)
        return _rows_onward(params, x, memory, rows, cfg, control) \
            @ params["model"]["embed"]["weight"].astype(F32).T


# ---------------------------------------------------------------------------
# the counts
# ---------------------------------------------------------------------------

def _mixer_counts(cfg):
    L = cfg["num_hidden_layers"]
    kinds = [mixer_of(l, cfg) for l in range(L)]
    return {k: kinds.count(k) for k in ("ssm", "window", "full", "gmu",
                                        "cross")}


def _mamba_params(cfg) -> int:
    h = cfg["hidden_size"]
    di, N, K, R = _sizes(cfg)
    return (h * 2 * di + K * di + di + di * (R + 2 * N) + R * di + di
            + N * di + di + di * h)


def counts(cfg: dict) -> dict:
    """`matmul_params`: the weights ONE decoded token multiplies (every
    layer; the tied head once).  `prefill_matmul_params`: those a prompt
    token multiplies, which stops after layer L/2 + 1's K/V projection
    (layers 0 .. L/2 whole, W_k and W_v of layer L/2 + 1).
    `total_params`: everything held, as `model.num_params` counts it."""
    h, v, I = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = h // nq
    di = _sizes(cfg)[0]
    n = _mixer_counts(cfg)
    attn_w = h * (nq + 2 * nkv) * hd + nq * hd * h
    attn_small = (nq + 2 * nkv) * hd + h + 4 * hd + 1 + 2 * hd
    cross_w = 2 * h * nq * hd
    cross_small = nq * hd + h + 4 * hd + 1 + 2 * hd
    gmu_w = 2 * h * di
    mamba_mm = h * 2 * di + di * (_sizes(cfg)[3] + 2 * _sizes(cfg)[1]) \
        + _sizes(cfg)[3] * di + di * h
    L = cfg["num_hidden_layers"]
    mlp = 3 * h * I
    matmul = (n["ssm"] * mamba_mm + (n["window"] + n["full"]) * attn_w
              + n["cross"] * cross_w + n["gmu"] * gmu_w + L * mlp + h * v)
    prefill = (n["ssm"] * mamba_mm + n["window"] * attn_w
               + h * 2 * nkv * hd + (L // 2 + 1) * mlp)
    return {
        "matmul_params": matmul,
        "prefill_matmul_params": prefill,
        "attn_width": (n["window"] + n["full"] + n["cross"]) * nq * hd,
        "total_params": (
            n["ssm"] * _mamba_params(cfg)
            + (n["window"] + n["full"]) * (attn_w + attn_small)
            + n["cross"] * (cross_w + cross_small) + n["gmu"] * gmu_w
            + L * (mlp + 4 * h) + h * v + 2 * h)}


def ssm_state_bytes_per_slot(cfg: dict, elem_bytes: float = 2.0) -> float:
    di, N, K, _ = _sizes(cfg)
    return _mixer_counts(cfg)["ssm"] * (4.0 * N * di
                                        + elem_bytes * (K - 1) * di)


def ssm_chunk_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations and bytes of the selective scan
    (ops/selective_scan.chunk_scan) over the prompt tokens the window's
    chunk programs prefilled, all Mamba layers.  A position a channel and
    state lane: the decay's exponent and exponential, the state's
    multiply-add, the input's product and the output's multiply-add (7
    operations; none is a matrix product, and they are laid against the
    ONE peak `peaks.py` has, the MXU's, so the share reads low by the
    ratio of the two units: PERF.md s7).  Bytes: u', B, C in the model's
    dtype and Delta in float32 read, y written in float32, a position;
    the state read and written once a chunk launch.  None where no chunk
    ran."""
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
    the step's 7 operations a channel and state lane.  The layers' weights
    are NOT in it: the products that read them are not all under the
    Mamba scopes of a trace (the largest, W_in, is fused with what comes
    before it and carries no scope: PERF.md s5), so the share is laid
    against the scopes that hold the state alone (`ssm`, `ssm_conv`,
    `ssm_step`).  None where the program counted no decode step."""
    state = window["counters"].get("serve.ssm_state_bytes")
    rows = window["counters"].get("serve.decode_slot_steps")
    if not state or not rows:
        return None
    di, N, _, _ = _sizes(cfg)
    return {"ops": _mixer_counts(cfg)["ssm"] * rows * 7.0 * N * di,
            "bytes": state}


def paged_attn_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations and bytes of paged decode attention over all
    attention layers, for the single-token queries of the window's decode
    steps: what the MODEL needs, whatever implements it.  The full layer
    and each of the cross layers read every cached K and V row of the
    steps' contexts once (the full layer's own read,
    `serve.decode_context_tokens`, and the readers' of its page set,
    `serve.shared_kv_positions`); a window layer those of the last
    `sliding_window` positions (`serve.decode_window_context_tokens`).
    A position's K (or V) is `num_key_value_heads` x 64 values; the
    operations are the model's 64-wide q . k and 128-wide p . [v_1 | v_2]
    a query head, not the lane rows' zero-padded products.  None where
    the program counted no decode step."""
    c = window["counters"]
    full, queries = (c.get("serve.decode_context_tokens"),
                     c.get("serve.decode_slot_steps"))
    if not full or not queries:
        return None
    n = _mixer_counts(cfg)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nq
    tokens = (full + c.get("serve.shared_kv_positions", 0.0)
              + n["window"] * c.get("serve.decode_window_context_tokens",
                                    full))
    layers = n["window"] + n["full"] + n["cross"]
    return {"ops": 2.0 * tokens * nq * (hd + 2 * hd),
            "bytes": elem_bytes * (2.0 * tokens * nkv * hd
                                   + layers * queries * nq * 3 * hd)}


def chunk_attn_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations of the chunk program's attention over the
    layers that run it for every row (the window layers; the full layer
    attends for the read row alone, in the tail): every (query, key) pair
    a window layer's mask lets through
    (`serve.prefill_attended_keys{kind=window_<w>}`, counted per chunk
    launch and ONE layer) is a 64-wide q . k and a 128-wide p . v for each
    of the query heads.  The bytes are the chunk's own q, K, V and o once
    a layer.  None where the program counted no chunk."""
    c = window["counters"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nq
    n = _mixer_counts(cfg)
    w = cfg["sliding_window"]
    pairs = n["window"] * c.get(
        f"serve.prefill_attended_keys{{kind=window_{w}}}", 0.0)
    rows = c.get("serve.prefill_tokens")
    if not pairs or not rows:
        return None
    return {"ops": 2.0 * nq * 3 * hd * pairs,
            "bytes": elem_bytes * rows * n["window"] * (
                nq * 3 * hd + 2 * nkv * hd)}
