"""The mimo_v2 family (Xiaomi MiMo-V2-Flash): what
`hetu_tpu/models/mimo_v2` implements and
https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json
describes, under its published key names.

The plain reference (`logits_at`) is float32 `jax.numpy` under
`default_matmul_precision("highest")`, reading the program's parameter
tree and nothing else of the program: no kernel, no cache, no pages, no
batching, no padded key.  h = hidden_size, eps = layernorm_epsilon, no
bias anywhere.

* RMSNorm: y = w * x / sqrt(mean(x^2) + eps).
* x_0 = E[ids]; final RMSNorm; an untied head.
* Layer i, two pre-norms: h = x + Attn_i(N1(x)); y = h + FFN_i(N2(h)).
  (Departure from the published description: none is known; that the
  norms are two pre-norms is the family's convention, `assumed`.)
* Attn_i, kind = `hybrid_layer_pattern[i]` (0 reads everything, 1 the
  window): q = x W_q as `num_attention_heads` heads of `head_dim` (192);
  k = x W_k as n_kv heads of 192; v = `attention_value_scale` * (x W_v)
  as n_kv heads of `v_head_dim` (128); n_kv = `num_key_value_heads`
  (kind 0) or `swa_num_key_value_heads` (kind 1).  The first r =
  int(192 * `partial_rotary_factor`) = 64 values of every q and k head
  are rotated (half-split over those r; base `rope_theta` for kind 0,
  `swa_rope_theta` for kind 1), the others carry no position.  s_tj =
  q_t . k_j / sqrt(192) for j <= t and, kind 1, j > t -
  `sliding_window`.  Kind 0: p = softmax_j(s).  Kind 1 with
  `add_swa_attention_sink_bias` (and kind 0 with `add_full_...`), b_n a
  learned scalar a query head: p_tj = exp(s_tj) / (exp(b_n) + sum_j'
  exp(s_tj')).  o_t = sum_j p_tj v_j (query head n reads KV head n //
  (heads / n_kv)); Attn = concat_n(o) W_o.  The program's ONE projection
  matrix `wqkv` holds, column block after column block: every q head's r
  rotated values, every q head's 192 - r plain values, k's rotated, k's
  plain, v (a fixed arrangement of columns: with random weights nothing
  else); `_project` reads it so.  Queries are taken in blocks of
  `Q_BLOCK` rows, a window layer's block against the window + block
  positions that end with it.
* FFN_i: a SwiGLU of `intermediate_size` where `moe_layer_freq[i]` is 0;
  else s = sigmoid(x W_r) in float32 over the router's whole width;
  chosen = top `num_experts_per_tok` of s + bias (`n_group` =
  `topk_group` = 1); w = s at the chosen over (their sum + 1e-20)
  (`norm_topk_prob`), times `routed_scaling_factor` (null = 1); y = sum
  over the chosen AND held experts of w_e SwiGLU_e(x), width
  `moe_intermediate_size`.  NO shared expert: a token none of whose
  experts is held gets 0.  The weights hold experts `first_expert` .. +
  `n_routed_experts` - 1 of the router's range (`n_routed_experts` of
  the file is how many are HELD, `router_experts` the router's width).
  Tokens go through every matrix in blocks of `T_BLOCK` rows and a
  layer's weights are upcast where they are used, so that 16,384
  positions fit beside the program's parameters.
* The published model's 3 multi-token-prediction layers are in no key
  of its config: left out here and in the program.

Where a choice of experts is a near tie (`router_tie_logit`, a key of
the configuration; absent, `logits_at` is the plain forward and nothing
else), `logits_at` takes the form the Kimi and the afmoe families' have
and no other: the forward once as it stands, and for the tokens at
`rows` alone (every other token as the plain pass has it: its keys and
values are kept, layer by layer) once per expert layer with that layer's
near ties decided the other way (the held expert nearest the edge of the
chosen set, if its margin in the router's logit is under
`router_tie_logit`, leaves or enters it: one expert a token a layer),
and once with every layer's.  A row whose own token was so changed in a
pass gets, value by value, its best standing under the row's largest
logit in any of its passes (each other pass's logits shifted so that
its largest stands one float32 step under the plain pass's largest,
which stays the row's argmax).  It reads the prefix `check_stream`
hands it and no served token beyond it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# at import, not in `build_model`: a program without the family (the
# parent of PR 36) then fails in `run.load_cell`, at once, with exit 2
from hetu_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2LMHeadModel

F32 = jnp.float32
Q_BLOCK = 128
T_BLOCK = 2048

#: the configuration file's keys that `MiMoV2Config` takes as they are
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "moe_intermediate_size", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads",
             "swa_num_key_value_heads", "head_dim", "v_head_dim",
             "sliding_window", "hybrid_layer_pattern", "moe_layer_freq",
             "partial_rotary_factor", "rope_theta", "swa_rope_theta",
             "attention_value_scale", "add_swa_attention_sink_bias",
             "add_full_attention_sink_bias", "num_experts_per_tok",
             "norm_topk_prob", "routed_scaling_factor",
             "max_position_embeddings", "layernorm_epsilon",
             "tie_word_embeddings")


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def build_model(config: dict, how: dict, strategy=None):
    """The program's model.  `n_routed_experts` of the file is how many
    experts are HELD here (`reduced`); the router keeps the published
    width, `router_experts`."""
    for key, want in (("scoring_func", "sigmoid"), ("n_group", 1),
                      ("topk_group", 1), ("topk_method", "noaux_tc"),
                      ("hidden_act", "silu"), ("n_shared_experts", None),
                      ("attention_bias", False),
                      ("swa_num_attention_heads",
                       config["num_attention_heads"]),
                      ("swa_head_dim", config["head_dim"]),
                      ("swa_v_head_dim", config["v_head_dim"]),
                      ("sliding_window_size", config["sliding_window"])):
        if config[key] != want:
            raise ValueError(f"models/mimo_v2 implements {key}={want!r}, "
                             f"the file says {config[key]!r}")
    dtype = jnp.dtype(how.get("param_dtype", "bfloat16"))
    return MiMoV2LMHeadModel(MiMoV2Config(
        router_experts=config.get("router_experts",
                                  config["n_routed_experts"]),
        experts_held=config["n_routed_experts"],
        first_expert=config.get("first_expert", 0),
        param_dtype=dtype, compute_dtype=dtype,
        router_bias_range=config.get("router_bias_std", 0.002),
        sink_range=config.get("sink_std", 8.0),
        initializer_range=config.get("initializer_range", 0.02),
        **{k: config[k] for k in PUBLISHED}), strategy)


def serve_config(config: dict):
    from hetu_tpu.serving.engine import ServeConfig
    sv = config["serving"]
    return ServeConfig(**{k: sv[k] for k in (
        "num_slots", "page_size", "max_len", "prefill_chunk", "num_pages",
        "kv_quant") if k in sv})


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _by_blocks(fn, *xs):
    """fn over the rows of the arrays xs [n, ...], `T_BLOCK` rows (or a
    divisor of n) at a time."""
    n = xs[0].shape[0]
    tb = math.gcd(n, T_BLOCK)
    if tb == n:
        return fn(*xs)
    out = jax.lax.map(lambda b: fn(*b), tuple(
        x.reshape((n // tb, tb) + x.shape[1:]) for x in xs))
    return jax.tree.map(lambda a: a.reshape((n,) + a.shape[2:]), out)


def _rms_norm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(F32)


def _rotate(x, pos, theta):
    """x [n, heads, r] at positions `pos` [n]: half-split rotation over
    all r values."""
    r = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = jnp.outer(pos.astype(F32), inv)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _kind(cfg, i):
    """(window or None, KV heads, rotation base, whether a sink) of
    layer i."""
    if cfg["hybrid_layer_pattern"][i]:
        return (cfg["sliding_window"], cfg["swa_num_key_value_heads"],
                cfg["swa_rope_theta"], cfg["add_swa_attention_sink_bias"])
    return (None, cfg["num_key_value_heads"], cfg["rope_theta"],
            cfg["add_full_attention_sink_bias"])


def _project(h, pos, ap, cfg, i):
    """(q [n, heads, 192], k [n, n_kv, 192], v [n, n_kv, 128]) of the
    tokens h [n, hidden] (normed) at positions `pos` [n]."""
    nq, hd, hv = (cfg["num_attention_heads"], cfg["head_dim"],
                  cfg["v_head_dim"])
    _, nkv, theta, _ = _kind(cfg, i)
    r = int(hd * cfg["partial_rotary_factor"])
    w = ap["wqkv"].astype(F32)

    def rows(hb, pb):
        x, n, at, parts = hb @ w, hb.shape[0], 0, []
        for heads, width in ((nq, r), (nq, hd - r), (nkv, r),
                             (nkv, hd - r), (nkv, hv)):
            parts.append(x[:, at: at + heads * width]
                         .reshape(n, heads, width))
            at += heads * width
        q_r, q_p, k_r, k_p, v = parts
        return (jnp.concatenate([_rotate(q_r, pb, theta), q_p], -1),
                jnp.concatenate([_rotate(k_r, pb, theta), k_p], -1),
                cfg["attention_value_scale"] * v)
    return _by_blocks(rows, h, pos)


def _attend(q, pos, k, v, window, sink, whole: bool):
    """The queries q [n, heads, 192] at positions `pos` [n] over the keys
    [s, n_kv, 192] and values [s, n_kv, 128] of positions 0..s-1, in
    blocks of `Q_BLOCK` query rows.  `whole`: the queries are the
    sequence itself (pos = 0..s-1), and a window layer's block then
    reads the window + block positions that end with it and no others.
    `sink` [heads] or None.  -> [n, heads * 128]."""
    n, nq, hd = q.shape
    s, nkv, hv = v.shape
    qb = math.gcd(n, Q_BLOCK)
    span = min(s, window + qb) if whole and window is not None else s
    q = q.reshape(n // qb, qb, nkv, nq // nkv, hd)
    pos = pos.reshape(n // qb, qb)

    def rows(i):
        first = jnp.clip((i + 1) * qb - span, 0, s - span) if span < s else 0
        kb = jax.lax.dynamic_slice_in_dim(k, first, span)
        vb = jax.lax.dynamic_slice_in_dim(v, first, span)
        sc = jnp.einsum("qngd,knd->ngqk", q[i], kb) / math.sqrt(hd)
        qpos = pos[i][:, None]
        kpos = first + jnp.arange(span)[None, :]
        seen = kpos <= qpos
        if window is not None:
            seen = seen & (kpos > qpos - window)
        sc = jnp.where(seen, sc, -jnp.inf)
        if sink is None:
            p = jax.nn.softmax(sc, axis=-1)
        else:
            b = sink.astype(F32).reshape(nkv, nq // nkv)[:, :, None, None]
            m = jnp.maximum(sc.max(-1, keepdims=True), b)
            e = jnp.exp(sc - m)
            p = e / (jnp.exp(b - m) + e.sum(-1, keepdims=True))
        return jnp.einsum("ngqk,knd->qngd", p, vb)
    return jax.lax.map(rows, jnp.arange(n // qb)).reshape(n, nq * hv)


def _swiglu(x, w_gate_up, w_down):
    """w_gate_up [hidden, 2 I]: the gate's columns, then up's."""
    wgu, wd = w_gate_up.astype(F32), w_down.astype(F32)

    def rows(xb):
        gu = xb @ wgu
        i = gu.shape[-1] // 2
        return (jax.nn.silu(gu[:, :i]) * gu[:, i:]) @ wd
    return _by_blocks(rows, x)


def _tilt_nearest_held(v, scores, cfg, held, on):
    """The choice values `v` = s + b [s, E] with, where `on` and the
    margin allows, the held expert nearest the edge of the chosen set
    pushed across it; (v', which tokens were changed [s], that expert's
    margin [s]).  The margin is the distance in `v` between the expert
    and the edge (the best value not chosen if it is chosen, the worst
    chosen if it is not), over the sigmoid's slope there: the change of
    its router logit that would move it across."""
    k, first = cfg["num_experts_per_tok"], cfg.get("first_expert", 0)
    top, _ = jax.lax.top_k(v, k + 1)
    worst_in, best_out = top[:, k - 1: k], top[:, k: k + 1]
    vh = v[:, first: first + held]
    sh = scores[:, first: first + held]
    chosen = vh >= worst_in
    margin = jnp.abs(vh - jnp.where(chosen, best_out, worst_in)) / (
        sh * (1.0 - sh) + 1e-30)
    j = jnp.argmin(margin, axis=-1)
    m = jnp.take_along_axis(margin, j[:, None], axis=-1)[:, 0]
    move = on & (m < cfg["router_tie_logit"])
    push = jnp.where(jnp.take_along_axis(chosen, j[:, None], -1)[:, 0],
                     -4.0, 4.0)                  # |v| < 2: out, or in
    v = v + (jax.nn.one_hot(first + j, v.shape[-1], dtype=F32)
             * (move * push)[:, None])
    return v, move, m


def gate(x, mp, cfg, tilt=None):
    """(expert ids [s, k], weights [s, k]) of the published gate, over
    the router's whole width; with `tilt` (a traced bool; module
    docstring) also which tokens' near tie was decided the other way [s]
    and the margins [s]."""
    scores = jax.nn.sigmoid(x @ mp["w_gate"].astype(F32))
    v = scores + mp["e_score_correction_bias"].astype(F32)
    if tilt is not None:
        v, moved, margin = _tilt_nearest_held(
            v, scores, cfg, mp["w_gate_up"].shape[0], tilt)
    _, idx = jax.lax.top_k(v, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * (cfg.get("routed_scaling_factor") or 1.0)
    return (idx, w) if tilt is None else (idx, w, moved, margin)


def experts(x, mp, cfg, tilt=None):
    """The expert layer on x [s, hidden]: a loop over the experts held
    (`first_expert` .. + held - 1), each applied to every token (in
    blocks of rows) and weighted by the gate's weight for it there (0
    where it was not chosen).  No shared expert."""
    idx, w, *tilted = gate(x, mp, cfg, tilt)
    first = cfg.get("first_expert", 0)

    def one(acc, xs):
        w_gate_up, w_down, e = xs
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * _swiglu(x, w_gate_up, w_down), None
    held = mp["w_gate_up"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (mp["w_gate_up"], mp["w_down"], jnp.arange(held)))
    return y if tilt is None else (y, *tilted)


def _layer(x, pos, lp, cfg, i, keys_values=None, tilt=None, keep=None):
    """Layer i on the tokens x [n, hidden] at positions `pos` [n].
    `keys_values` None: the tokens are the whole sequence and attend
    themselves (`keep`, a list, is given the layer's (k, v)); else
    (k, v) of the whole sequence from a plain pass, in which these
    tokens' own entries are replaced."""
    eps = cfg["layernorm_epsilon"]
    window, _, _, has_sink = _kind(cfg, i)
    ap = lp["attn"]
    q, k, v = _project(_rms_norm(x, lp["input_norm"]["weight"], eps),
                       pos, ap, cfg, i)
    if keys_values is not None:
        k = keys_values[0].at[pos].set(k)
        v = keys_values[1].at[pos].set(v)
    elif keep is not None:
        keep.append((k, v))
    o = _attend(q, pos, k, v, window, ap["sink"] if has_sink else None,
                whole=keys_values is None)
    wo = ap["wo"].astype(F32)
    x = x + _by_blocks(lambda ob: ob @ wo, o)
    h = _rms_norm(x, lp["post_norm"]["weight"], eps)
    ffn, tilted = lp["mlp"], ()
    if not cfg["moe_layer_freq"][i]:
        y = _swiglu(h, ffn["w_gate_up"], ffn["w_down"])
    elif tilt is None:
        y = experts(h, ffn, cfg)
    else:
        y, *tilted = experts(h, ffn, cfg, tilt)
    return (x + y, *tilted)


def _moe_layers(cfg):
    return [i for i in range(cfg["num_hidden_layers"])
            if cfg["moe_layer_freq"][i]]


def hidden_states(params, ids, cfg, entering=None, keep=None):
    """Final-norm hidden states [s, hidden] of one sequence `ids` [s]:
    the layers one after the other.  `entering` (a list) is given the
    hidden states that enter the first expert layer, `keep` (a list)
    the keys and values of every layer from there on."""
    with jax.default_matmul_precision("highest"):
        m = params["model"]
        pos = jnp.arange(ids.shape[0])
        x = m["embed"]["weight"][ids].astype(F32)
        first_moe = _moe_layers(cfg)[0]
        for i in range(cfg["num_hidden_layers"]):
            if i == first_moe and entering is not None:
                entering.append(x)
            (x,) = _layer(x, pos, m[f"layer_{i}"], cfg, i,
                          keep=keep if i >= first_moe else None)
        return _rms_norm(x, m["final_norm"]["weight"],
                         cfg["layernorm_epsilon"])


def rows_tilted(params, cfg, x, keys_values, rows, tilts):
    """The layers from the first expert layer on again for the tokens at
    `rows` alone (x: their hidden states entering it), with the near
    ties of the layers `tilts` (a bool each) decided the other way;
    every other token is as the plain pass has it (`keys_values`, of
    `hidden_states`), and the rows attend those.  (final-norm hidden
    states [len(rows), hidden], which rows were changed in any layer,
    each layer's margins [layers, len(rows)])."""
    m, n0 = params["model"], _moe_layers(cfg)[0]
    moved, margins = jnp.zeros(rows.shape, bool), []
    for j, kv in enumerate(keys_values):
        x, mv, mg = _layer(x, rows, m[f"layer_{n0 + j}"], cfg, n0 + j,
                           keys_values=kv, tilt=tilts[j])
        moved, margins = moved | mv, margins + [mg]
    return (_rms_norm(x, m["final_norm"]["weight"],
                      cfg["layernorm_epsilon"]),
            moved, jnp.stack(margins))


def logits_by_pass(params, ids, rows, cfg):
    """(logits [passes, len(rows), vocab], which rows a pass changed
    [passes, len(rows)], the plain pass's margins [layers, len(rows)]):
    the plain pass of the whole sequence, then for the rows alone one
    pass per expert layer with its near ties decided the other way, and
    one with every layer's."""
    if any(not cfg["moe_layer_freq"][i]
           for i in range(_moe_layers(cfg)[0], cfg["num_hidden_layers"])):
        raise ValueError("the near-tie passes take every layer after the "
                         "first expert layer to be an expert layer")
    with jax.default_matmul_precision("highest"):
        entering, keep = [], []
        head = params["lm_head"].astype(F32)
        plain = hidden_states(params, ids, cfg, entering, keep)[rows] @ head
        n = len(keep)

        def one(tilts):
            x, moved, margins = rows_tilted(params, cfg, entering[0][rows],
                                            keep, rows, tilts)
            return x @ head, moved, margins
        lg, moved, margins = jax.lax.map(one, jnp.concatenate(
            [jnp.eye(n, dtype=bool), jnp.ones((1, n), bool)]))
        # a layer's margins are the plain pass's up to the first tilted
        return (jnp.concatenate([plain[None], lg]),
                jnp.concatenate([jnp.zeros((1,) + rows.shape, bool), moved]),
                jnp.stack([margins[i, i] for i in range(n)]))


def logits_at(params, ids, rows, cfg):
    """Reference logits [len(rows), vocab] at the positions `rows`; under
    `router_tie_logit`, a row's standing under the best of the choices
    its near ties allow (module docstring)."""
    if not cfg.get("router_tie_logit"):
        with jax.default_matmul_precision("highest"):
            return hidden_states(params, ids, cfg)[rows] \
                @ params["lm_head"].astype(F32)
    lg, moved, _ = logits_by_pass(params, ids, rows, cfg)
    plain = lg[0]
    # one float32 step under the plain pass's largest, so that a row's
    # argmax stays the plain forward's own
    under = jnp.nextafter(plain.max(-1, keepdims=True), -jnp.inf)
    standing = lg - lg.max(-1, keepdims=True) + under
    return jnp.where(moved[..., None], standing, plain[None]).max(0)


# ---------------------------------------------------------------------------
# the counts
# ---------------------------------------------------------------------------

def _layers_by_kind(cfg):
    """(window layers, layers that read everything)."""
    n_win = sum(cfg["hybrid_layer_pattern"][: cfg["num_hidden_layers"]])
    return n_win, cfg["num_hidden_layers"] - n_win


def _attn_params(cfg, i):
    h, nq, hd, hv = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["head_dim"], cfg["v_head_dim"])
    _, nkv, _, sink = _kind(cfg, i)
    return h * ((nq + nkv) * hd + nkv * hv) + nq * hv * h, nq * sink


def counts(cfg: dict) -> dict:
    """`matmul_params`: the weights ONE token multiplies HERE: attention
    (q, k, v, out) and the router in every expert layer, and of the
    routed experts the share of a token's `num_experts_per_tok` that
    falls on the experts held; the dense layers; the sliced head.
    `total_params`: everything held, as `model.num_params` counts it (the
    router's weights and bias at their published width, the sinks)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    held = cfg["n_routed_experts"]
    router = cfg.get("router_experts", held)
    expert = 3 * h * cfg["moe_intermediate_size"]
    matmul = total = 0
    for i in range(cfg["num_hidden_layers"]):
        attn, sinks = _attn_params(cfg, i)
        if cfg["moe_layer_freq"][i]:
            matmul += attn + h * router + (
                cfg["num_experts_per_tok"] * held / router * expert)
            total += attn + sinks + 2 * h + h * router + router \
                + held * expert
        else:
            dense = 3 * h * cfg["intermediate_size"]
            matmul += attn + dense
            total += attn + sinks + 2 * h + dense
    return {"matmul_params": matmul + h * v,
            "attn_width": cfg["num_hidden_layers"]
            * cfg["num_attention_heads"] * cfg["head_dim"],
            "total_params": total + 2 * h * v + h}


def _kv_values(cfg, kind: int) -> int:
    """Values a token stores in one layer of a kind, as the MODEL needs
    them: K of `head_dim` and V of `v_head_dim` over the kind's KV
    heads."""
    nkv = (cfg["swa_num_key_value_heads"] if kind
           else cfg["num_key_value_heads"])
    return nkv * (cfg["head_dim"] + cfg["v_head_dim"])


def paged_attn_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations and bytes of paged decode attention over all
    layers, for the single-token queries of the window's decode steps
    (`serve.decode_slot_steps`): what the MODEL needs, whatever a kernel
    fetched or a pool pads.  A full layer reads every cached K (192) and
    V (128) vector of the steps' contexts once over its 4 KV heads
    (`serve.decode_context_tokens`), a window layer those of the last
    `sliding_window` positions over its 8
    (`serve.decode_window_context_tokens`: per slot min(context,
    window)); q is read and o written.  None where the program counted
    no decode step."""
    nq, hd, hv = (cfg["num_attention_heads"], cfg["head_dim"],
                  cfg["v_head_dim"])
    n_win, n_full = _layers_by_kind(cfg)
    c = window["counters"]
    full, queries = (c.get("serve.decode_context_tokens"),
                     c.get("serve.decode_slot_steps"))
    win = c.get("serve.decode_window_context_tokens", full)
    if not full or not queries:
        return None
    return {"ops": 2.0 * (n_full * full + n_win * win) * nq * (hd + hv),
            "bytes": elem_bytes * (
                n_full * full * _kv_values(cfg, 0)
                + n_win * win * _kv_values(cfg, 1)
                + cfg["num_hidden_layers"] * queries * nq * (hd + hv))}


def chunk_attn_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations of the chunk program's attention over all
    layers: every (query, key) pair a layer's mask lets through
    (`serve.prefill_attended_keys{kind}`, counted per chunk launch and
    ONE layer of the kind) is a 192-wide q . k and a 128-wide p . v for
    each of the query heads, times the kind's layers.  The bytes are the
    chunk's own q, K, V and o once a layer (the cached keys a chunk
    re-reads are few beside the arithmetic: the MXU's peak bounds).
    None where the program counted no chunk."""
    nq, hd, hv = (cfg["num_attention_heads"], cfg["head_dim"],
                  cfg["v_head_dim"])
    n_win, n_full = _layers_by_kind(cfg)
    c = window["counters"]
    w = cfg["sliding_window"]
    full = c.get("serve.prefill_attended_keys{kind=full}", 0.0)
    win = c.get(f"serve.prefill_attended_keys{{kind=window_{w}}}", 0.0)
    pairs = n_full * full + n_win * win
    rows = c.get("serve.prefill_tokens")
    if not pairs or not rows:
        return None
    return {"ops": 2.0 * nq * (hd + hv) * pairs,
            "bytes": elem_bytes * rows * (
                cfg["num_hidden_layers"] * nq * (hd + hv)
                + n_full * _kv_values(cfg, 0) + n_win * _kv_values(cfg, 1))}


def grouped_matmul_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations and bytes of the routed experts' grouped
    matrix products (gate|up, then down) of the window's decode and
    chunk programs: each held expert that has a token in an execution
    (`serve.moe_expert_hits`) has its weights read once there; every
    pair on a held expert (`serve.moe_local_assignments`) multiplies one
    expert's weights, reads its input row and writes its output row.
    None where the program counted no expert layer."""
    hits = window["counters"].get("serve.moe_expert_hits")
    pairs = window["counters"].get("serve.moe_local_assignments")
    if not hits or not pairs:
        return None
    h, i = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {"ops": 2.0 * pairs * 3 * h * i,
            "bytes": elem_bytes * (hits * 3 * h * i
                                   + pairs * (2 * h + 3 * i))}
