"""The Ling-3.0 family (`model_type: bailing_hybrid`): what
`hetu_tpu/models/bailing_hybrid` implements and
https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json
describes, under its published key names.

The plain reference (`logits_at`) is float32 `jax.numpy` under
`default_matmul_precision("highest")`, reading the program's parameter
tree and nothing else of the program: no kernel, no cache, no chunks, no
state carried between calls, no absorption.  One layer, pre-norm, x the
normed hidden state of one token:

* KDA layer ((l + 1) % `layer_group_size` != 0): [q' | k' | v'] = x W_qkv
  (32 heads of 128 each; `num_kv_heads_for_linear_attn` 0 read as "as
  many as query heads"); each channel through a causal depthwise
  convolution over the last `short_conv_kernel_size` positions (zeros
  before the sequence), then SiLU (`linear_silu`); q and k of unit length
  a head (`use_qk_norm`), q times 128^-1/2; g = `kda_lower_bound` *
  sigmoid(exp(A_log_h) (x W_g)_h + dt_bias) a head and key channel
  (`kda_safe_gate`; W_g full rank, `no_kda_lora`); beta = sigmoid(x
  W_beta) a head.  A `lax.scan` over the positions of
      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t
  from S = 0; out = W_o [RMSNorm_head(o_t) * sigmoid(x W_z)].
* MLA layer, EXPANDED: q = x W_q a head directly (`q_lora_rank` null);
  [c_kv | k_rope] = x W_kva, c_kv = RMSNorm(c_kv); k_rope rotated once
  for all heads (theta 6e6, no scaling); [k_nope | v] = c_kv W_kvb a
  head; causal softmax of [q_nope | RoPE(q_rope)] . [k_nope | k_rope] *
  192^-1/2, times v, each head times sigmoid((x W_a)_h)
  (`gated_attention_proj_granularity_type` head_wise), through W_o; in
  blocks of `Q_BLOCK` query rows so that 32,768 positions fit.
* Experts (`score_function` sigmoid, `topk_method` noaux_tc, `n_group` 8,
  `topk_group` 4): s = sigmoid(x W_r); the experts lie in `n_group`
  groups of neighbours; a group's score is the sum of its two largest
  s + b; the `topk_group` best groups stay; the `num_experts_per_tok`
  largest s + b inside them are chosen; weights s (without b) at those,
  over their sum, times `routed_scaling_factor`; plus the shared expert.
  The configuration gives the share: the weights hold experts
  `first_expert` .. + held - 1 of the router's range, a loop walks them,
  and experts not held add nothing: that partial result goes on.

Where a choice is a near tie (`router_tie_logit`, as families/kimi_k2
says it): here the choice has TWO edges, the last group kept and the last
expert chosen inside the kept groups.  A pass decides, a token a layer,
the ONE nearest of them the other way: a group that holds held experts
and stands nearer than the margin to the edge of the kept groups enters
or leaves them (margin: the distance of its score to the edge over the
steeper of its two leading experts' sigmoid slopes), or the held expert
nearest the edge of the chosen set does (Kimi's margin), whichever is
nearer.  `logits_at` runs the forward once as it stands and, for the
tokens at `rows` alone (a run of consecutive positions, the last one
repeated as padding, as `reference.check_stream` hands them; every other
token as the plain pass has it), once for every set of ONE OR TWO of the
expert layers (6 + 15 = 21 passes at six expert layers), the near ties
of the set's layers decided the other way.  Why two: a chip that holds
64 of 512 experts has a held expert or its group within the margin of an
edge in 1.6-2.1 of the six layers for the mean checked token, and the
program's bfloat16 turns a held expert's choice at 0.6-1.2% of the tokens
a layer.  Over 12 streams of three seeds (3,912 tokens; my chip run,
PR 41, PERF.md s6) the plain pass left 3 tokens over the comparison's
limit (0.27, 0.33, 0.44 of 0.25), each brought under it by the pass of
ONE layer: 3 of the ~235 tokens turned somewhere.  By those rates a run's
1,300 checked tokens hold two that are turned in TWO layers at once (15
pairs x 1e-4) and 0.03 turned in three, so a run in twenty or so would
meet a token that only a pair's pass excuses, and one in a thousand a
token that needs three: sets of two are run, larger ones are not (with
all 63 sets the 12 streams' largest gaps read 0 to 0.06 lower, and the
largest of all is 0.150 either way).  A pass
walks the rows alone: a KDA layer starts from the plain pass's state
before the first row (kept by the plain scan) and its convolution from
the plain pass's three positions before it; the MLA layer's keys and
values are the plain pass's with the rows' own replaced.

Departures from the published code: rotation is written half-split where
the published code interleaves (`rope_interleave`: a fixed permutation of
weight columns, nothing with random weights); the multi-token-prediction
layer (`num_nextn_predict_layers` 1) is not built.

The two KDA computations are XLA compositions, whose device events carry
no name of their own for `roofline_pct` to match, so their share is the
cost function's least time over the device time of their SCOPES: the
harness's rule `scope_roofline_pct` (benchmarks/trace.py; written here in
PR 41, moved there in PR 57).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.families.kimi_k2 import (_rms_norm, _swiglu,  # noqa: F401
                                         grouped_matmul_cost)
from benchmarks.families.kimi_k2 import \
    latent_chunk_attn_cost as kimi_k2_latent_chunk_attn_cost
from benchmarks.families.llama import serve_config  # noqa: F401
# at import, not in `build_model`: a program without the family (the
# parent of PR 41) then fails in `run.load_cell`, at once, with exit 2
from hetu_tpu.models.bailing_hybrid import (BailingHybridConfig,
                                            BailingHybridLMHeadModel)
from hetu_tpu.ops.delta_rule import BLOCK as KDA_BLOCK

F32 = jnp.float32
Q_BLOCK = 128
#: the most expert layers whose near ties ONE pass of `logits_at` decides
#: the other way (module docstring)
NEAR_TIE_LAYERS = 2

#: the configuration file's keys that `BailingHybridConfig` takes as they
#: are
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "moe_intermediate_size", "moe_shared_expert_intermediate_size",
             "num_hidden_layers", "first_k_dense_replace",
             "layer_group_size", "num_attention_heads", "head_dim",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "short_conv_kernel_size", "kda_lower_bound",
             "num_shared_experts", "num_experts_per_tok", "n_group",
             "topk_group", "norm_topk_prob", "routed_scaling_factor",
             "max_position_embeddings", "rms_norm_eps", "rope_theta",
             "tie_word_embeddings")


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def build_model(config: dict, how: dict, strategy=None):
    """The program's model.  `num_experts` of the file is how many
    experts are HELD here (`reduced`); the router keeps the published
    width, `router_experts`."""
    L = config["num_hidden_layers"]
    for key, want in (("score_function", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("hidden_act", "silu"),
                      ("q_lora_rank", None), ("rope_scaling", None),
                      ("use_qk_norm", True), ("linear_silu", True),
                      ("kda_safe_gate", True), ("no_kda_lora", True),
                      ("num_kv_heads_for_linear_attn", 0),
                      ("use_bias", False), ("use_qkv_bias", False),
                      ("gated_attention_proj_granularity_type", "head_wise"),
                      ("expert_swiglu_limit_list", [0] * L),
                      ("share_expert_swiglu_limit_list", [0] * L)):
        if config[key] != want:
            raise ValueError(f"models/bailing_hybrid implements "
                             f"{key}={want!r}, the file says "
                             f"{config[key]!r}")
    dtype = jnp.dtype(how.get("param_dtype", "bfloat16"))
    cfg = BailingHybridConfig(
        num_experts=config.get("router_experts", config["num_experts"]),
        experts_held=config["num_experts"],
        first_expert=config.get("first_expert", 0),
        param_dtype=dtype, compute_dtype=dtype,
        correction_bias_range=config.get("correction_bias_std", 0.02),
        **{k: config[k] for k in PUBLISHED})
    return BailingHybridLMHeadModel(cfg, strategy)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _is_kda(layer: int, cfg) -> bool:
    return (layer + 1) % cfg["layer_group_size"] != 0


def _rope(x, pos, cfg):
    """x [s, heads, d] at positions `pos` [s]; half-split rotation."""
    d = x.shape[-1]
    inv = cfg["rope_theta"] ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.outer(pos.astype(F32), inv)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- KDA ---------------------------------------------------------------------

def _kda_inputs(h, before, ap, cfg):
    """(q, k, v [n, nh, hd], g [n, nh, hd], beta [n, nh], z [n, nh, hd])
    of the positions h [n, hidden] (normed), whose convolution also sees
    the K - 1 positions `before` [K - 1, hidden] (normed; zeros before a
    sequence's first position give zero inputs: no bias)."""
    nh, hd = cfg["num_attention_heads"], cfg["head_dim"]
    K, n = cfg["short_conv_kernel_size"], h.shape[0]
    x = jnp.concatenate([before, h]) @ ap["w_qkv"].astype(F32)
    w = ap["conv_w"].astype(F32)
    y = sum(w[i] * x[i: i + n] for i in range(K))
    y = jax.nn.silu(y).reshape(n, 3, nh, hd)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q, k, v = unit(y[:, 0]) * hd ** -0.5, unit(y[:, 1]), y[:, 2]
    raw = (h @ ap["w_g"].astype(F32)).reshape(n, nh, hd)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(ap["A_log"].astype(F32))[:, None] * raw
        + ap["dt_bias"].astype(F32).reshape(nh, hd))
    beta = jax.nn.sigmoid(h @ ap["w_beta"].astype(F32))
    z = (h @ ap["w_z"].astype(F32)).reshape(n, nh, hd)
    return q, k, v, g, beta, z


def _kda_walk(S, q, k, v, g, beta, keep_at=None, skip=None):
    """The recurrence, position by position, from state S [nh, hd, hd].
    -> (o [n, nh, hd], the state after position `keep_at` (zeros where
    that is -1; None: not asked)).  `skip` [n]: positions that leave the
    state alone and read it (a padding row that repeats the one before
    it)."""
    n = q.shape[0]
    skip = jnp.zeros((n,), bool) if skip is None else skip

    def one(carry, x):
        S, kept = carry
        q, k, v, g, beta, t, skip = x
        Sd = S * jnp.exp(g)[..., None]
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", Sd, k))
        new = Sd + k[..., None] * u[:, None, :]
        new = jnp.where(skip, S, new)
        if kept is not None:
            kept = jnp.where(t == keep_at, new, kept)
        return (new, kept), jnp.einsum("hkv,hk->hv", new, q)
    kept = None if keep_at is None else jnp.zeros_like(S)
    (_, kept), o = jax.lax.scan(
        one, (S, kept), (q, k, v, g, beta, jnp.arange(n), skip))
    return o, kept


def _kda_out(o, z, ap, cfg):
    o = _rms_norm(o, ap["o_norm"]["weight"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(z)
    return o.reshape(o.shape[0], -1) @ ap["wo"].astype(F32)


def _kda(h, ap, cfg, keep_at=None):
    """The KDA mixer of one sequence h [s, hidden] (normed) from zero
    state: (out [s, hidden], the state after position `keep_at`)."""
    nh, hd = cfg["num_attention_heads"], cfg["head_dim"]
    K = cfg["short_conv_kernel_size"]
    q, k, v, g, beta, z = _kda_inputs(
        h, jnp.zeros((K - 1, h.shape[1]), F32), ap, cfg)
    o, kept = _kda_walk(jnp.zeros((nh, hd, hd), F32), q, k, v, g, beta,
                        keep_at)
    return _kda_out(o, z, ap, cfg), kept


def _kda_rows(before, x_rows, rows, S, ap, cfg):
    """The KDA mixer for the tokens at `rows` alone (consecutive
    positions, the last repeated), their normed hidden states `x_rows`,
    the K - 1 positions before rows[0] as `before` [K - 1, hidden]
    (normed; zeros before the sequence), from the state S before
    rows[0]."""
    n = rows.shape[0]
    # the rows by position: a repeated row is its position once more
    first = jnp.concatenate([jnp.ones((1,), bool), rows[1:] != rows[:-1]])
    # position p of the run is row (p - rows[0]); the repeats fall on the
    # last position and are computed from the same inputs
    run = jnp.zeros((n, x_rows.shape[1]), F32).at[rows - rows[0]].set(x_rows)
    q, k, v, g, beta, z = _kda_inputs(run, before, ap, cfg)
    take = rows - rows[0]
    q, k, v, g, beta, z = (a[take] for a in (q, k, v, g, beta, z))
    o, _ = _kda_walk(S, q, k, v, g, beta, skip=~first)
    return _kda_out(o, z, ap, cfg)


# -- MLA ---------------------------------------------------------------------

def _keys_values(h, ap, cfg, pos=None):
    """Expanded keys [s, heads, nope + rope] and values [s, heads, v] of
    the tokens h [s, hidden] (normed) at positions `pos` (None: one
    sequence, positions 0..s-1)."""
    r, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    s, nh = h.shape[0], cfg["num_attention_heads"]
    ckv = h @ ap["wkv_a"].astype(F32)
    c = _rms_norm(ckv[:, :r], ap["kv_norm"]["weight"], cfg["rms_norm_eps"])
    kv = jnp.einsum("sr,rnd->snd", c, ap["wkv_b"].astype(F32))
    k_rope = _rope(ckv[:, None, r:],
                   jnp.arange(s) if pos is None else pos, cfg)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (s, nh, k_rope.shape[-1]))],
                        axis=-1)
    return k, kv[..., dn:]


def _attend(h, pos, k, v, ap, cfg):
    """The queries of h [q, hidden] (normed) at positions `pos` [q] over
    the keys and values of positions 0..s-1, causal, gated a head,
    through W_o; in blocks of `Q_BLOCK` query rows."""
    dn, nh = cfg["qk_nope_head_dim"], cfg["num_attention_heads"]
    n, s = h.shape[0], k.shape[0]
    q = (h @ ap["wq"].astype(F32)).reshape(n, nh, -1)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, cfg)], axis=-1)
    scale = q.shape[-1] ** -0.5
    qb = math.gcd(n, Q_BLOCK)

    def rows(q_blk_and_pos):
        q_blk, at = q_blk_and_pos
        sc = jnp.einsum("qnd,knd->nqk", q_blk, k) * scale
        seen = jnp.arange(s)[None, :] <= at[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("nqk,knd->qnd", p, v)
    out = jax.lax.map(rows, (q.reshape(n // qb, qb, nh, -1),
                             pos.reshape(n // qb, qb)))
    gate = jax.nn.sigmoid(h @ ap["w_gate"].astype(F32))        # [n, nh]
    return (out.reshape(n, nh, -1) * gate[..., None]).reshape(n, -1) \
        @ ap["wo"].astype(F32)


# -- experts -----------------------------------------------------------------

def _choose(v, cfg, lift=None, push=None):
    """The published choice over v = s + b [s, E]: (expert ids [s, k],
    the group scores [s, G], which groups were kept [s, G], v with the
    experts outside them at -inf).  A near tie decided the other way:
    `lift` [s, G] is added to the group scores, `push` [s, E] to the
    experts' values INSIDE the kept groups (not to the group scores they
    enter: a pushed expert must not turn its group)."""
    G, kg = cfg["n_group"], cfg["topk_group"]
    s, E = v.shape
    by_group = v.reshape(s, G, E // G)
    score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    _, keep = jax.lax.top_k(score if lift is None else score + lift, kg)
    kept = jnp.any(keep[:, :, None] == jnp.arange(G), axis=1)
    inside = jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(s, E)
    _, idx = jax.lax.top_k(inside if push is None else inside + push,
                           cfg["num_experts_per_tok"])
    return idx, score, kept, inside


def _tilt_nearest_edge(v, scores, cfg, held, on):
    """(push [s, E], lift [s, G], which tokens were changed [s], the
    margin [s]): the nearest of the two edges of a token's choice that a
    HELD expert stands at, decided the other way where `on` and the
    margin allows (module docstring); `_choose` takes the two."""
    G, kg, k = cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"]
    first = cfg.get("first_expert", 0)
    s, E = v.shape
    size = E // G
    _, score, kept, inside = _choose(v, cfg)
    slope = scores * (1.0 - scores) + 1e-30
    # the groups' edge: the worst kept score, the best score left out
    top_g, _ = jax.lax.top_k(score, kg + 1) if kg < G else (
        jnp.concatenate([jax.lax.top_k(score, kg)[0],
                         jnp.full((s, 1), -jnp.inf)], -1), None)
    g_edge = jnp.where(kept, top_g[:, kg: kg + 1], top_g[:, kg - 1: kg])
    lead, lead_at = jax.lax.top_k(v.reshape(s, G, size), 2)
    lead_slope = jnp.max(jnp.take_along_axis(
        slope.reshape(s, G, size), lead_at, axis=-1), axis=-1)
    holds = jnp.zeros((G,), bool).at[
        jnp.arange(first, first + held) // size].set(True)
    g_margin = jnp.where(holds[None] & (G > kg),
                         jnp.abs(score - g_edge) / lead_slope, jnp.inf)
    # the experts' edge, inside the kept groups
    top, _ = jax.lax.top_k(inside, k + 1)
    worst_in, best_out = top[:, k - 1: k], top[:, k: k + 1]
    vh = inside[:, first: first + held]
    chosen = vh >= worst_in
    e_margin = jnp.where(
        jnp.isfinite(vh),
        jnp.abs(vh - jnp.where(chosen, best_out, worst_in))
        / slope[:, first: first + held], jnp.inf)
    gj, ej = jnp.argmin(g_margin, -1), jnp.argmin(e_margin, -1)
    gm = jnp.take_along_axis(g_margin, gj[:, None], -1)[:, 0]
    em = jnp.take_along_axis(e_margin, ej[:, None], -1)[:, 0]
    m = jnp.minimum(gm, em)
    move = on & (m < cfg["router_tie_logit"])
    by_group = move & (gm <= em)
    by_expert = move & ~by_group
    g_in = jnp.take_along_axis(kept, gj[:, None], -1)[:, 0]
    lift = jax.nn.one_hot(gj, G, dtype=F32) * (
        by_group * jnp.where(g_in, -100.0, 100.0))[:, None]
    e_in = jnp.take_along_axis(chosen, ej[:, None], -1)[:, 0]
    push = jax.nn.one_hot(first + ej, E, dtype=F32) * (
        by_expert * jnp.where(e_in, -4.0, 4.0))[:, None]   # |v| < 2
    return push, lift, move, m


def gate(x, mp, cfg, tilt=None):
    """(expert ids [s, k], weights [s, k]) of the published gate; with
    `tilt` (a traced bool; module docstring) also which tokens' near tie
    was decided the other way [s] and the margins [s]."""
    scores = jax.nn.sigmoid(x @ mp["w_gate"].astype(F32))
    v = scores + mp["e_score_correction_bias"].astype(F32)
    push = lift = None
    if tilt is not None:
        push, lift, moved, margin = _tilt_nearest_edge(
            v, scores, cfg, mp["w_gate_up"].shape[0], tilt)
    idx = _choose(v, cfg, lift, push)[0]
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    return (idx, w) if tilt is None else (idx, w, moved, margin)


def experts(x, mp, cfg, tilt=None):
    """The expert layer on x [s, hidden]: a loop over the experts held,
    each applied to every token and weighted by the gate's weight for it
    there (0 where it was not chosen), plus the shared expert."""
    idx, w, *tilted = gate(x, mp, cfg, tilt)
    first = cfg.get("first_expert", 0)
    held = mp["w_gate_up"].shape[0]

    def one(acc, xs):
        w_gate_up, w_down, e = xs
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * _swiglu(x, w_gate_up, w_down), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (mp["w_gate_up"], mp["w_down"], jnp.arange(held)))
    y = y + _swiglu(x, mp["shared_gate_up"], mp["shared_down"])
    return y if tilt is None else (y, *tilted)


# -- the forward --------------------------------------------------------------

def _layers(params, cfg):
    group = params["model"]["layers"]
    return [(group[f"layer_{i}"], _is_kda(i, cfg),
             i >= cfg["first_k_dense_replace"]) for i in range(len(group))]


def hidden_states(params, ids, cfg, entering=None, states=None, keep_at=-1):
    """Final-norm hidden states [s, hidden] of one sequence `ids` [s].
    `entering` (a list) is given the hidden states that enter EVERY
    layer, `states` (a list) each KDA layer's state after position
    `keep_at` and each MLA layer's keys and values."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = params["model"]["embed"]["weight"][ids].astype(F32)
        for lp, kda, moe in _layers(params, cfg):
            if entering is not None:
                entering.append(x)
            h = _rms_norm(x, lp["input_norm"]["weight"], eps)
            if kda:
                out, kept = _kda(h, lp["attn"], cfg,
                                 None if states is None else keep_at)
                if states is not None:
                    states.append(kept)
            else:
                kv = _keys_values(h, lp["attn"], cfg)
                if states is not None:
                    states.append(kv)
                out = _attend(h, jnp.arange(h.shape[0]), *kv, lp["attn"],
                              cfg)
            x = x + out
            h = _rms_norm(x, lp["post_norm"]["weight"], eps)
            x = x + (experts(h, lp["mlp"], cfg) if moe else _swiglu(
                h, lp["mlp"]["w_gate_up"], lp["mlp"]["w_down"]))
        return _rms_norm(x, params["model"]["final_norm"]["weight"], eps)


def rows_tilted(params, cfg, entering, states, rows, tilts):
    """Every layer again for the tokens at `rows` alone, with the near
    ties of the expert layers `tilts` (a bool each) decided the other
    way; every other token is as the plain pass has it.  (final-norm
    hidden states [len(rows), hidden], which rows were changed in any
    layer, each expert layer's margins [expert layers, len(rows)])."""
    eps, K = cfg["rms_norm_eps"], cfg["short_conv_kernel_size"]
    x = entering[0][rows]
    moved, margins = jnp.zeros(rows.shape, bool), []
    states = iter(states)
    tilts = iter(tilts)
    at = rows[0] - (K - 1) + jnp.arange(K - 1)
    for i, (lp, kda, moe) in enumerate(_layers(params, cfg)):
        w = lp["input_norm"]["weight"]
        h = _rms_norm(x, w, eps)
        if kda:
            before = jnp.where(
                (at >= 0)[:, None],
                _rms_norm(entering[i][jnp.maximum(at, 0)], w, eps), 0.0)
            out = _kda_rows(before, h, rows, next(states), lp["attn"], cfg)
        else:
            k_all, v_all = next(states)
            k, v = _keys_values(h, lp["attn"], cfg, rows)
            out = _attend(h, rows, k_all.at[rows].set(k),
                          v_all.at[rows].set(v), lp["attn"], cfg)
        x = x + out
        h = _rms_norm(x, lp["post_norm"]["weight"], eps)
        if moe:
            y, mv, mg = experts(h, lp["mlp"], cfg, next(tilts))
            x, moved, margins = x + y, moved | mv, margins + [mg]
        else:
            x = x + _swiglu(h, lp["mlp"]["w_gate_up"], lp["mlp"]["w_down"])
    return (_rms_norm(x, params["model"]["final_norm"]["weight"], eps),
            moved, jnp.stack(margins))


def pass_masks(cfg):
    """The passes of `logits_by_pass` as bit masks, in its order: 0 (the
    plain pass), then every non-empty set of at most `NEAR_TIE_LAYERS`
    expert layers (mask m tilts expert layer i where bit i of m is
    set)."""
    n = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return [0] + [m for m in range(1, 2 ** n)
                  if bin(m).count("1") <= NEAR_TIE_LAYERS]


def logits_by_pass(params, ids, rows, cfg):
    """(logits [passes, len(rows), vocab], which rows a pass changed
    [passes, len(rows)], the plain pass's margins [expert layers,
    len(rows)]): the plain pass of the whole sequence, then for the rows
    alone one pass for every set of `pass_masks`, the set's near ties
    decided the other way."""
    n = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    masks = pass_masks(cfg)[1:]
    with jax.default_matmul_precision("highest"):
        entering, states = [], []
        head = params["lm_head"].astype(F32)
        plain = hidden_states(params, ids, cfg, entering, states,
                              rows[0] - 1)[rows] @ head

        def one(tilts):
            x, moved, margins = rows_tilted(params, cfg, entering, states,
                                            rows, tilts)
            return x @ head, moved, margins
        sets = (jnp.asarray(masks)[:, None] >> jnp.arange(n)) & 1
        lg, moved, margins = jax.lax.map(one, sets.astype(bool))
        # a layer's margins are the plain pass's in the pass of it alone
        return (jnp.concatenate([plain[None], lg]),
                jnp.concatenate([jnp.zeros((1,) + rows.shape, bool), moved]),
                jnp.stack([margins[masks.index(2 ** i), i]
                           for i in range(n)]))


def logits_at(params, ids, rows, cfg):
    """Reference logits [len(rows), vocab] at the positions `rows`; under
    `router_tie_logit`, a row's standing under the best of the choices
    its near ties allow (families/kimi_k2.logits_at says how)."""
    if not cfg.get("router_tie_logit"):
        with jax.default_matmul_precision("highest"):
            return hidden_states(params, ids, cfg)[rows] \
                @ params["lm_head"].astype(F32)
    lg, moved, _ = logits_by_pass(params, ids, rows, cfg)
    plain = lg[0]
    under = jnp.nextafter(plain.max(-1, keepdims=True), -jnp.inf)
    standing = lg - lg.max(-1, keepdims=True) + under
    return jnp.where(moved[..., None], standing, plain[None]).max(0)


# ---------------------------------------------------------------------------
# the counts
# ---------------------------------------------------------------------------

def _kda_params(cfg):
    h = cfg["hidden_size"]
    w = cfg["num_attention_heads"] * cfg["head_dim"]
    return (h * 3 * w + cfg["short_conv_kernel_size"] * 3 * w + 2 * h * w
            + h * cfg["num_attention_heads"] + w * h)


def _mla_params(cfg):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    return (h * nh * (dn + dr) + h * (r + dr) + r * nh * (dn + dv)
            + h * nh + nh * dv * h)


def _kinds(cfg):
    L = cfg["num_hidden_layers"]
    n_kda = sum(_is_kda(l, cfg) for l in range(L))
    return n_kda, L - n_kda


def counts(cfg: dict) -> dict:
    """`matmul_params`: the weights ONE token multiplies HERE (the mixers,
    the shared expert and the router in every expert layer, of the routed
    experts the share of a token's `num_experts_per_tok` that falls on
    the experts held; the dense layers; the sliced head).
    `total_params`: everything held, as `model.num_params` counts it."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    nh, hd = cfg["num_attention_heads"], cfg["head_dim"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    n_kda, n_mla = _kinds(cfg)
    held = cfg["num_experts"]
    router = cfg.get("router_experts", held)
    expert = 3 * h * cfg["moe_intermediate_size"]
    shared = 3 * h * cfg["moe_shared_expert_intermediate_size"]
    mixers = n_kda * _kda_params(cfg) + n_mla * _mla_params(cfg)
    return {
        "matmul_params": (
            mixers + n_dense * 3 * h * cfg["intermediate_size"] + h * v
            + n_moe * (shared + h * router + cfg["num_experts_per_tok"]
                       * held / router * expert)),
        "attn_width": n_mla * nh * (cfg["qk_nope_head_dim"]
                                    + cfg["qk_rope_head_dim"]),
        "total_params": (
            mixers + n_kda * (nh + nh * hd + hd) + n_mla * cfg["kv_lora_rank"]
            + cfg["num_hidden_layers"] * 2 * h
            + n_dense * 3 * h * cfg["intermediate_size"]
            + n_moe * (shared + h * router + router + held * expert)
            + 2 * h * v + h)}


def paged_latent_attn_cost(cfg: dict, window: dict,
                           elem_bytes: float = 2.0):
    """families/kimi_k2.paged_latent_attn_cost over this family's latent
    layers alone (one of every `layer_group_size`): every cached latent
    read once a layer, the absorbed query read and the latent output
    written a head."""
    context_tokens = window["counters"].get("serve.decode_context_tokens")
    queries = window["counters"].get("serve.decode_slot_steps")
    if not context_tokens or not queries:
        return None
    L, nh = _kinds(cfg)[1], cfg["num_attention_heads"]
    r = cfg["kv_lora_rank"]
    latent = r + cfg["qk_rope_head_dim"]
    return {"ops": L * 2.0 * nh * (latent + r) * context_tokens,
            "bytes": L * elem_bytes * (latent * context_tokens
                                       + queries * nh * (latent + r))}


def latent_chunk_attn_cost(cfg: dict, window: dict,
                           elem_bytes: float = 2.0):
    """families/kimi_k2.latent_chunk_attn_cost over this family's latent
    layers alone (one of every `layer_group_size`).
    `serve.prefill_attended_keys` is a launch's pairs in ONE layer of
    the one kind of layer that attends (the KDA layers keep state, no
    keys)."""
    return kimi_k2_latent_chunk_attn_cost(
        dict(cfg, num_hidden_layers=_kinds(cfg)[1]), window, elem_bytes)


def kda_state_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required bytes and operations of the KDA layers of the window's
    decode steps: the state of each row that decodes read once and
    written once (the program's own count, `serve.kda_state_bytes`:
    2 x rows x layers x (the float32 state + the convolution's tail)),
    and the KDA weights once a step.  None where the program counted no
    decode step."""
    state = window["counters"].get("serve.kda_state_bytes")
    steps = window["counters"].get("serve.decode_steps")
    rows = window["counters"].get("serve.decode_slot_steps")
    if not state or not steps:
        return None
    n_kda = _kinds(cfg)[0]
    hd, nh = cfg["head_dim"], cfg["num_attention_heads"]
    return {"ops": n_kda * (rows or 0) * (2.0 * _kda_params(cfg)
                                          + 8.0 * nh * hd * hd),
            "bytes": state + steps * n_kda * elem_bytes * _kda_params(cfg)}


def kda_chunk_cost(cfg: dict, window: dict):
    """Required operations and bytes of the chunkwise delta rule
    (ops/delta_rule.chunk_scan) over the prompt tokens the window's chunk
    programs prefilled, all KDA layers.  A block of L positions (the
    program's own `delta_rule.BLOCK`) a head:
    A and B (2 x 2 L^2 dk), the triangular system for dk + dv right-hand
    sides (L^2 (dk + dv)), and the walk's products (3 x 2 L dk dv + 2 L^2
    dv).  Bytes: q, k, v, g, o in float32 and beta a position, and the
    state read and written once a chunk launch.  None where no chunk
    ran."""
    tokens = window["counters"].get("serve.prefill_tokens")
    launches = window["counters"].get("serve.prefill_chunks")
    if not tokens or not launches:
        return None
    n_kda = _kinds(cfg)[0]
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    L = KDA_BLOCK
    per_block = 4.0 * L * L * d + 2.0 * L * L * d + 6.0 * L * d * d \
        + 2.0 * L * L * d
    return {"ops": n_kda * nh * per_block * tokens / L,
            "bytes": n_kda * nh * 4.0 * (tokens * (5 * d + 1)
                                         + launches * 2 * d * d)}
