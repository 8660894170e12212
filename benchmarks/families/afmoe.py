"""The afmoe family (Arcee Trinity): what `hetu_tpu/models/trinity`
implements and
https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json
describes, under its published key names.

The plain reference (`logits_at`) is float32 `jax.numpy` under
`default_matmul_precision("highest")`, reading the program's parameter
tree and nothing else of the program: no kernel, no cache, no pages, no
batching.  h = hidden_size, eps = rms_norm_eps, no bias anywhere.

* RMSNorm: y = w * x / sqrt(mean(x^2) + eps).
* Embedding: x_0 = E[ids] * sqrt(h) (`mup_enabled`); final RMSNorm;
  an untied head.
* Layer i, four norms: x = x + N2(Attn(N1(x))); x = x + N4(FFN(N3(x))).
  FFN is a dense SwiGLU for i < `num_dense_layers`, the expert layer
  after.
* Attention: [q | k | v | g] = x W (q: heads x 128, k, v: kv heads x
  128, g: heads x 128); q and k RMS-normalised over each head with a
  learned gain; on a `sliding_attention` layer q and k are rotated over
  the whole head (half-split, theta `rope_theta`) and key j is seen by
  query t iff t - `sliding_window` < j <= t; a `full_attention` layer is
  NOT rotated and sees j <= t; softmax of q . k / sqrt(128), q head n
  reading kv head n // group; y = (o * sigmoid(g)) W_o.  Computed in
  blocks of `Q_BLOCK` query rows, a window layer's block against the
  window + block positions that end with it and no others, so that
  8,192 positions fit beside the weights.
* Experts (`score_func` sigmoid, one group): s = sigmoid(x W_r) over the
  router's whole width; chosen = top `num_experts_per_tok` of s + b;
  weights = s at the chosen over (their sum + 1e-20) (`route_norm`),
  times `route_scale`; y = sum_i w_i E_i(x) + E_shared(x), every E a
  SwiGLU.  The configuration gives the share: the weights hold experts
  `first_expert` .. + `num_experts` - 1 of the router's range, a loop
  walks them, and experts not held add nothing: that partial result
  goes on.

Where a choice of experts is a near tie (`router_tie_logit`, a key of
the configuration; absent, `logits_at` is the plain forward and nothing
else): the top-k is a step function of the router's logits, and the
program's bfloat16 hidden states differ from this forward's float32
ones, so where a HELD expert stands closer to the edge of the chosen
set than that noise reaches, the program may rightly have chosen the
other way; with ~1 of a token's 8 experts held here and a norm on the
layer's output, its hidden state then differs by about half of one
layer's contribution.  Both choices are computations of the published
layer at the stated precision.  With the reference's routing FORCED on
the program, no logit of 8,192 rows differs by more than 0.2 of the
comparison's limit; with its own, 0.3% of the rows lie over it at 10
layers, every one of them with a margin under 0.018 in some layer
(PERF.md s6: my chip run, PR 34; at all 32 layers 1 stream of 64 is
not ok even under the passes below, at margin 0.1 and 0.2 alike: the
cell keeps 10).  `logits_at` then takes the form the
Kimi family's has and no other: the forward once as it stands, and for
the tokens at `rows` alone (every other token as the plain pass has it:
its keys and values are kept, layer by layer) once per expert layer
with that layer's near ties decided the other way (the held expert
nearest the edge, if its margin in the router's logit is under
`router_tie_logit`, leaves or enters the chosen set: one expert a token
a layer), and once with every layer's.  A row whose own token was so
changed in a pass gets, value by value, its best standing under the
row's largest logit in any of its passes (each other pass's logits are
shifted so that its largest stands one float32 step under the plain
pass's largest, which stays the row's argmax).  `logits_at` reads the
prefix `check_stream` hands it and no served token beyond it, and never
chooses a routing by which one a served token likes best: the standing
is taken of every value alike.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# at import, not in `build_model`: a program without the family (the
# parent of PR 34) then fails in `run.load_cell`, at once, with exit 2
from hetu_tpu.models.trinity import TrinityConfig, TrinityLMHeadModel

F32 = jnp.float32
Q_BLOCK = 256

#: the configuration file's keys that `TrinityConfig` takes as they are
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "moe_intermediate_size", "num_hidden_layers",
             "num_dense_layers", "num_attention_heads",
             "num_key_value_heads", "head_dim", "layer_types",
             "global_attn_every_n_layers", "sliding_window",
             "num_shared_experts", "num_experts_per_tok", "route_norm",
             "route_scale", "mup_enabled", "max_position_embeddings",
             "rms_norm_eps", "rope_theta", "tie_word_embeddings")


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def build_model(config: dict, how: dict, strategy=None):
    """The program's model.  `num_experts` of the file is how many
    experts are HELD here (`reduced`); the router keeps the published
    width, `router_experts`."""
    for key, want in (("score_func", "sigmoid"), ("n_group", 1),
                      ("topk_group", 1), ("num_expert_groups", 1),
                      ("num_limited_groups", 1), ("hidden_act", "silu"),
                      ("rope_scaling", None)):
        if config[key] != want:
            raise ValueError(f"models/trinity implements {key}={want!r}, "
                             f"the file says {config[key]!r}")
    dtype = jnp.dtype(how.get("param_dtype", "bfloat16"))
    return TrinityLMHeadModel(TrinityConfig(
        router_experts=config.get("router_experts", config["num_experts"]),
        experts_held=config["num_experts"],
        first_expert=config.get("first_expert", 0),
        param_dtype=dtype, compute_dtype=dtype,
        expert_bias_range=config.get("expert_bias_std", 0.002),
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

def _rms_norm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(F32)


def _rope(x, pos, theta):
    """x [n, heads, hd] at positions `pos` [n]; half-split rotation."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.outer(pos.astype(F32), inv)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _project(h, pos, ap, cfg, window):
    """(q [n, heads, hd], k, v [n, kv heads, hd], gate [n, heads * hd])
    of the tokens h [n, hidden] (normed) at positions `pos` [n]: head
    norms, and the rotation on a window layer only."""
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    n, eps = h.shape[0], cfg["rms_norm_eps"]
    x = h @ ap["wqkvg"].astype(F32)
    q = x[:, :nq * hd].reshape(n, nq, hd)
    k = x[:, nq * hd: (nq + nkv) * hd].reshape(n, nkv, hd)
    v = x[:, (nq + nkv) * hd: (nq + 2 * nkv) * hd].reshape(n, nkv, hd)
    q = _rms_norm(q, ap["q_norm"], eps)
    k = _rms_norm(k, ap["k_norm"], eps)
    if window is not None:
        q, k = (_rope(t, pos, cfg["rope_theta"]) for t in (q, k))
    return q, k, v, x[:, (nq + 2 * nkv) * hd:]


def _attend(q, pos, k, v, window, whole: bool):
    """The queries q [n, heads, hd] at positions `pos` [n] over the keys
    and values of positions 0..s-1, in blocks of `Q_BLOCK` query rows.
    `whole`: the queries are the sequence itself (pos = 0..s-1), and a
    window layer's block then reads the window + block positions that
    end with it and no others.  -> [n, heads * hd]."""
    n, nq, hd = q.shape
    s, nkv = k.shape[:2]
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
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("ngqk,knd->qngd", p, vb)
    return jax.lax.map(rows, jnp.arange(n // qb)).reshape(n, nq * hd)


def _window(cfg, i):
    return (cfg["sliding_window"]
            if cfg["layer_types"][i] == "sliding_attention" else None)


def _swiglu(x, w_gate_up, w_down):
    """w_gate_up [hidden, 2 I]: the gate's columns, then up's."""
    gu = x @ w_gate_up.astype(F32)
    i = gu.shape[-1] // 2
    return (jax.nn.silu(gu[:, :i]) * gu[:, i:]) @ w_down.astype(F32)


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
    if cfg["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["route_scale"]
    return (idx, w) if tilt is None else (idx, w, moved, margin)


def experts(x, mp, cfg, tilt=None):
    """The expert layer on x [s, hidden]: a loop over the experts held
    (`first_expert` .. + held - 1), each applied to every token and
    weighted by the gate's weight for it there (0 where it was not
    chosen), plus the shared expert."""
    idx, w, *tilted = gate(x, mp, cfg, tilt)
    first = cfg.get("first_expert", 0)

    def one(acc, xs):
        w_gate_up, w_down, e = xs
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * _swiglu(x, w_gate_up, w_down), None
    held = mp["w_gate_up"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (mp["w_gate_up"], mp["w_down"], jnp.arange(held)))
    y = y + _swiglu(x, mp["shared_gate_up"], mp["shared_down"])
    return y if tilt is None else (y, *tilted)


def _layer(x, pos, lp, cfg, i, keys_values=None, tilt=None, keep=None):
    """Layer i on the tokens x [n, hidden] at positions `pos` [n].
    `keys_values` None: the tokens are the whole sequence and attend
    themselves (`keep`, a list, is given the layer's (k, v)); else
    (k, v) of the whole sequence from a plain pass, in which these
    tokens' own entries are replaced."""
    eps, window = cfg["rms_norm_eps"], _window(cfg, i)
    ap = lp["attn"]
    q, k, v, g = _project(_rms_norm(x, lp["input_norm"]["weight"], eps),
                          pos, ap, cfg, window)
    if keys_values is not None:
        k = keys_values[0].at[pos].set(k)
        v = keys_values[1].at[pos].set(v)
    elif keep is not None:
        keep.append((k, v))
    o = _attend(q, pos, k, v, window, whole=keys_values is None)
    a = (o * jax.nn.sigmoid(g)) @ ap["wo"].astype(F32)
    x = x + _rms_norm(a, ap["out_norm"]["weight"], eps)
    h = _rms_norm(x, lp["post_norm"]["weight"], eps)
    ffn, tilted = lp["mlp"]["ffn"], ()
    if i < cfg["num_dense_layers"]:
        y = _swiglu(h, ffn["w_gate_up"], ffn["w_down"])
    elif tilt is None:
        y = experts(h, ffn, cfg)
    else:
        y, *tilted = experts(h, ffn, cfg, tilt)
    return (x + _rms_norm(y, lp["mlp"]["out_norm"]["weight"], eps), *tilted)


def hidden_states(params, ids, cfg, entering=None, keep=None):
    """Final-norm hidden states [s, hidden] of one sequence `ids` [s]:
    the layers one after the other, one at a time held in float32.
    `entering` (a list) is given the hidden states that enter the first
    expert layer, `keep` (a list) every expert layer's keys and
    values."""
    with jax.default_matmul_precision("highest"):
        m = params["model"]
        pos = jnp.arange(ids.shape[0])
        x = m["embed"]["weight"][ids].astype(F32)
        if cfg["mup_enabled"]:
            x = x * math.sqrt(cfg["hidden_size"])
        for i in range(cfg["num_hidden_layers"]):
            moe = i >= cfg["num_dense_layers"]
            if i == cfg["num_dense_layers"] and entering is not None:
                entering.append(x)
            (x,) = _layer(x, pos, m[f"layer_{i}"], cfg, i,
                          keep=keep if moe else None)
        return _rms_norm(x, m["final_norm"]["weight"], cfg["rms_norm_eps"])


def rows_tilted(params, cfg, x, keys_values, rows, tilts):
    """The expert layers again for the tokens at `rows` alone (x: their
    hidden states entering the first expert layer), with the near ties
    of the layers `tilts` (a bool each) decided the other way; every
    other token is as the plain pass has it (`keys_values`, of
    `hidden_states`), and the rows attend those.  (final-norm hidden
    states [len(rows), hidden], which rows were changed in any layer,
    each layer's margins [layers, len(rows)])."""
    m, n0 = params["model"], cfg["num_dense_layers"]
    moved, margins = jnp.zeros(rows.shape, bool), []
    for j, kv in enumerate(keys_values):
        x, mv, mg = _layer(x, rows, m[f"layer_{n0 + j}"], cfg, n0 + j,
                           keys_values=kv, tilt=tilts[j])
        moved, margins = moved | mv, margins + [mg]
    return (_rms_norm(x, m["final_norm"]["weight"], cfg["rms_norm_eps"]),
            moved, jnp.stack(margins))


def logits_by_pass(params, ids, rows, cfg):
    """(logits [passes, len(rows), vocab], which rows a pass changed
    [passes, len(rows)], the plain pass's margins [layers, len(rows)]):
    the plain pass of the whole sequence, then for the rows alone one
    pass per expert layer with its near ties decided the other way, and
    one with every layer's."""
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

def _widths(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def _kinds(cfg):
    """(window layers, full layers)."""
    n_win = sum(t == "sliding_attention" for t in cfg["layer_types"])
    return n_win, cfg["num_hidden_layers"] - n_win


def counts(cfg: dict) -> dict:
    """`matmul_params`: the weights ONE token multiplies HERE: attention
    (q, k, v, gate, out), the shared expert and the router in every
    expert layer, and of the routed experts the share of a token's
    `num_experts_per_tok` that falls on the experts held; the dense
    layers; the sliced head.  `total_params`: everything held, as
    `model.num_params` counts it (the router's weights and bias at their
    published width)."""
    h, nq, nkv, hd = _widths(cfg)
    v = cfg["vocab_size"]
    n_dense = cfg["num_dense_layers"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    held = cfg["num_experts"]
    router = cfg.get("router_experts", held)
    expert = 3 * h * cfg["moe_intermediate_size"]
    attn = h * (2 * nq + 2 * nkv) * hd + nq * hd * h
    norms = 4 * h + 2 * hd
    moe_matmul = (attn + cfg["num_shared_experts"] * expert + h * router
                  + cfg["num_experts_per_tok"] * held / router * expert)
    dense = attn + 3 * h * cfg["intermediate_size"]
    return {
        "matmul_params": n_moe * moe_matmul + n_dense * dense + h * v,
        "attn_width": cfg["num_hidden_layers"] * nq * hd,
        "total_params": (
            n_moe * (attn + norms + cfg["num_shared_experts"] * expert
                     + h * router + router + held * expert)
            + n_dense * (dense + norms) + 2 * h * v + h)}


def paged_attn_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations and bytes of paged decode attention over all
    layers, for the single-token queries of the window's decode steps
    (`serve.decode_slot_steps`): what the MODEL needs, whatever a kernel
    fetched.  A full layer reads every cached K and V vector of the
    steps' contexts once (`serve.decode_context_tokens`), a window layer
    those of the last `sliding_window` positions
    (`serve.decode_window_context_tokens`: per slot min(context,
    window)); q is read and o written.  None where the program counted
    no decode step."""
    _, nq, nkv, hd = _widths(cfg)
    n_win, n_full = _kinds(cfg)
    c = window["counters"]
    full, queries = (c.get("serve.decode_context_tokens"),
                     c.get("serve.decode_slot_steps"))
    win = c.get("serve.decode_window_context_tokens", full)
    if not full or not queries:
        return None
    tokens = n_full * full + n_win * win
    return {"ops": 2.0 * 2.0 * tokens * nq * hd,
            "bytes": elem_bytes * (
                2.0 * tokens * nkv * hd
                + cfg["num_hidden_layers"] * 2.0 * queries * nq * hd)}


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
