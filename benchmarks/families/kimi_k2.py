"""The Kimi-K2 family (the block DeepSeek-V3 published): what
`hetu_tpu/models/kimi_k2` implements and
https://huggingface.co/moonshotai/Kimi-K2.6/blob/main/config.json
describes, under its published key names.

The plain reference (`logits_at`) is float32 `jax.numpy` under
`default_matmul_precision("highest")`, reading the program's parameter
tree and nothing else of the program: no kernel, no cache, no batching,
no absorption.  One layer, pre-norm:

* MLA, EXPANDED: c_q = RMSNorm(x W_qa); [q_nope | q_rope] = c_q W_qb per
  head; [c_kv | k_rope] = x W_kva, c_kv = RMSNorm(c_kv); k_rope rotated
  once for all heads; [k_nope | v] = c_kv W_kvb per head; causal softmax
  of [q_nope | RoPE(q_rope)] . [k_nope | k_rope] * s, times v, through
  W_o.  Attention is computed in blocks of `Q_BLOCK` query rows so that
  4,096 positions x 64 heads fit beside the weights.
* YaRN: inverse frequencies blended per pair between theta^(-2i/d) and
  the same over `factor`, by the linear ramp between the correction
  dimensions of `beta_fast` and `beta_slow` rotations over
  `original_max_position_embeddings`; the table's magnitude is
  m(mscale) / m(mscale_all_dim); s = qk_head_dim^-0.5 m(mscale_all_dim)^2
  with m(x) = 0.1 x ln(factor) + 1.
* Experts (`scoring_func` sigmoid, `topk_method` noaux_tc, one group):
  s = sigmoid(x W_g); the `num_experts_per_tok` experts are the top of
  s + b; their weights s (without b) at those, over their sum
  (`norm_topk_prob`), times `routed_scaling_factor`; y = sum_i w_i E_i(x)
  + E_shared(x).  The configuration gives the share: the weights hold
  experts `first_expert` .. + `n_routed_experts` - 1 of the router's
  range (its width is the router weight's own), a loop walks them, and
  experts not held add nothing: that partial result goes on.

Where a choice of experts is a near tie (`router_tie_logit`, a key of
the configuration; absent, `logits_at` is the plain forward and nothing
else): the top-k is a step function of the router's logits, and the
program's bfloat16 hidden states differ from this forward's float32
ones by ~0.4%, so where a HELD expert stands closer to the edge of the
chosen set than that noise reaches, the program may rightly have chosen
the other way, and its hidden state then differs by one expert's
weighted output (~9% at six layers).  Both choices are computations of
the published layer at the stated precision.  `logits_at` then runs the
forward once as it stands, and for the tokens at `rows` alone (every
other token as the plain pass has it) once per expert layer with that
layer's near ties decided the other way (the held expert nearest the
edge, if its margin in the router's logit is under `router_tie_logit`,
leaves or enters the chosen set: one expert a token a layer), and once
with every layer's.  A row whose own token was so changed in a pass
gets, value by value, its best standing under the row's largest logit
in any of its passes (each other pass's logits are shifted so that its
largest stands one float32 step under the plain pass's largest, which
stays the row's argmax): a served token is held to the comparison's
limit as it stands, under the plain choice or under one that a near tie
allows.  A row with no near tie is the plain forward's own.

Departures from the published code: rotation is written half-split
where the published code de-interleaves q_rope and k_rope first (a fixed
permutation of weight columns, nothing with random weights); no vision
tower (the catalog's `config` holds the language model only).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.families.llama import serve_config  # noqa: F401 (the
#                          engine's six keys are the same for this family)
# at import, not in `build_model`: a program without the family (the
# parent of PR 27) then fails in `run.load_cell`, at once, with exit 2
from hetu_tpu.models.kimi_k2 import KimiK2Config, KimiK2LMHeadModel

F32 = jnp.float32
Q_BLOCK = 256

#: the configuration file's keys that `KimiK2Config` takes as they are
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "moe_intermediate_size", "num_hidden_layers",
             "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "n_shared_experts", "num_experts_per_tok",
             "norm_topk_prob", "routed_scaling_factor",
             "max_position_embeddings", "rms_norm_eps", "rope_theta",
             "rope_scaling", "tie_word_embeddings")


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def build_model(config: dict, how: dict, strategy=None):
    """The program's model.  `n_routed_experts` of the file is how many
    experts are HELD here (`reduced`); the router keeps the published
    width, `router_experts`."""
    for key, want in (("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("n_group", 1),
                      ("topk_group", 1), ("moe_layer_freq", 1),
                      ("hidden_act", "silu"), ("attention_bias", False)):
        if config[key] != want:
            raise ValueError(f"models/kimi_k2 implements {key}={want!r}, "
                             f"the file says {config[key]!r}")
    kcfg = KimiK2Config(
        n_routed_experts=config.get("router_experts",
                                    config["n_routed_experts"]),
        experts_held=config["n_routed_experts"],
        first_expert=config.get("first_expert", 0),
        param_dtype=jnp.dtype(how.get("param_dtype", "bfloat16")),
        compute_dtype=jnp.dtype(how.get("param_dtype", "bfloat16")),
        correction_bias_range=config.get("correction_bias_std", 0.02),
        **{k: config[k] for k in PUBLISHED})
    return KimiK2LMHeadModel(kcfg, strategy)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _rms_norm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(F32)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(d, theta, rs):
    """[d / 2] inverse frequencies, closed form (module docstring)."""
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    if not rs:
        return inv

    def dim_of(rotations):
        return d * math.log(rs["original_max_position_embeddings"]
                            / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low)
                    / (high - low if high > low else 0.001), 0.0, 1.0)
    return inv / rs["factor"] * ramp + inv * (1.0 - ramp)


def softmax_scale(cfg):
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        s *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def _rope(x, pos, cfg):
    """x [s, heads, d] at positions `pos` [s]; half-split rotation, YaRN."""
    d = x.shape[-1]
    rs = cfg.get("rope_scaling")
    ang = jnp.outer(pos.astype(F32), yarn_inv_freq(d, cfg["rope_theta"], rs))
    m = (yarn_mscale(rs["factor"], rs.get("mscale", 1.0))
         / yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0.0))
         if rs else 1.0)
    cos, sin = (jnp.cos(ang) * m)[:, None, :], (jnp.sin(ang) * m)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _keys_values(h, ap, cfg):
    """Expanded keys [s, heads, nope + rope] and values [s, heads, v] of
    one sequence h [s, hidden] (normed), positions 0..s-1."""
    r, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    s, nh = h.shape[0], cfg["num_attention_heads"]
    ckv = h @ ap["wkv_a"].astype(F32)
    c = _rms_norm(ckv[:, :r], ap["kv_norm"]["weight"], cfg["rms_norm_eps"])
    kv = jnp.einsum("sr,rnd->snd", c, ap["wkv_b"].astype(F32))
    k_rope = _rope(ckv[:, None, r:], jnp.arange(s), cfg)   # one for all heads
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (s, nh, k_rope.shape[-1]))],
                        axis=-1)
    return k, kv[..., dn:]


def _attend(h, pos, k, v, ap, cfg):
    """The queries of h [q, hidden] (normed) at positions `pos` [q] over
    the keys and values of positions 0..s-1, causal, through W_o; in
    blocks of `Q_BLOCK` query rows so that 4,096 positions x 64 heads
    fit beside the weights."""
    dn, nh = cfg["qk_nope_head_dim"], cfg["num_attention_heads"]
    n, s = h.shape[0], k.shape[0]
    cq = _rms_norm(h @ ap["wq_a"].astype(F32), ap["q_norm"]["weight"],
                   cfg["rms_norm_eps"])
    q = (cq @ ap["wq_b"].astype(F32)).reshape(n, nh, -1)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, cfg)], axis=-1)
    scale = softmax_scale(cfg)
    qb = math.gcd(n, Q_BLOCK)

    def rows(q_blk_and_pos):
        q_blk, at = q_blk_and_pos
        sc = jnp.einsum("qnd,knd->nqk", q_blk, k) * scale
        seen = jnp.arange(s)[None, :] <= at[:, None]            # [qb, s]
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("nqk,knd->qnd", p, v)
    out = jax.lax.map(rows, (q.reshape(n // qb, qb, nh, -1),
                             pos.reshape(n // qb, qb)))
    return out.reshape(n, -1) @ ap["wo"].astype(F32)


def _mla(h, ap, cfg):
    """Expanded latent attention of one sequence h [s, hidden] (normed)."""
    return _attend(h, jnp.arange(h.shape[0]), *_keys_values(h, ap, cfg),
                   ap, cfg)


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
    """(expert ids [s, k], weights [s, k]) of the published gate; with
    `tilt` (a traced bool; module docstring) also which tokens' near tie
    was decided the other way [s] and the margins [s]."""
    scores = jax.nn.sigmoid(x @ mp["w_gate"].astype(F32))
    v = scores + mp["e_score_correction_bias"].astype(F32)
    if tilt is not None:
        v, moved, margin = _tilt_nearest_held(
            v, scores, cfg, mp["w_gate_up"].shape[0], tilt)
    _, idx = jax.lax.top_k(v, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    return (idx, w) if tilt is None else (idx, w, moved, margin)


def experts(x, mp, cfg, tilt=None):
    """The expert layer on x [s, hidden]: a loop over the experts held
    (`first_expert` .. + held - 1), each applied to every token and
    weighted by the gate's weight for it there (0 where it was not
    chosen), plus the shared expert."""
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


def _block(x, lp, cfg, moe: bool):
    eps = cfg["rms_norm_eps"]
    x = x + _mla(_rms_norm(x, lp["input_norm"]["weight"], eps),
                 lp["attn"], cfg)
    h = _rms_norm(x, lp["post_norm"]["weight"], eps)
    if moe:
        return x + experts(h, lp["mlp"], cfg)
    return x + _swiglu(h, lp["mlp"]["w_gate_up"], lp["mlp"]["w_down"])


def hidden_states(params, ids, cfg, entering=None):
    """Final-norm hidden states [s, hidden] of one sequence `ids` [s]:
    the layers one after the other (`layer_<i>` of each group), one at a
    time held in float32.  `entering` (a list) is given the hidden
    states that enter each expert layer."""
    with jax.default_matmul_precision("highest"):
        m = params["model"]
        x = m["embed"]["weight"][ids].astype(F32)
        for group, moe in ((m["dense_layers"], False),
                           (m["moe_layers"], True)):
            for i in range(len(group)):
                if moe and entering is not None:
                    entering.append(x)
                x = _block(x, group[f"layer_{i}"], cfg, moe)
        return _rms_norm(x, m["final_norm"]["weight"], cfg["rms_norm_eps"])


def rows_tilted(params, cfg, entering, rows, tilts):
    """The expert layers again for the tokens at `rows` alone, with the
    near ties of the layers `tilts` (a bool each) decided the other way;
    every other token is as the plain pass has it (`entering`, of
    `hidden_states`), and the rows attend those.  (final-norm hidden
    states [len(rows), hidden], which rows were changed in any layer,
    each layer's margins [layers, len(rows)])."""
    eps, moe = cfg["rms_norm_eps"], params["model"]["moe_layers"]
    x = entering[0][rows]
    moved, margins = jnp.zeros(rows.shape, bool), []
    for i in range(len(moe)):
        lp = moe[f"layer_{i}"]
        h = _rms_norm(entering[i].at[rows].set(x),
                      lp["input_norm"]["weight"], eps)
        x = x + _attend(h[rows], rows, *_keys_values(h, lp["attn"], cfg),
                        lp["attn"], cfg)
        y, mv, mg = experts(_rms_norm(x, lp["post_norm"]["weight"], eps),
                            lp["mlp"], cfg, tilts[i])
        x, moved, margins = x + y, moved | mv, margins + [mg]
    return (_rms_norm(x, params["model"]["final_norm"]["weight"], eps),
            moved, jnp.stack(margins))


def logits_by_pass(params, ids, rows, cfg):
    """(logits [passes, len(rows), vocab], which rows a pass changed
    [passes, len(rows)], the plain pass's margins [layers, len(rows)]):
    the plain pass of the whole sequence, then for the rows alone one
    pass per expert layer with its near ties decided the other way, and
    one with every layer's."""
    with jax.default_matmul_precision("highest"):
        entering = []
        head = params["lm_head"].astype(F32)
        plain = hidden_states(params, ids, cfg, entering)[rows] @ head
        n = len(entering)

        def one(tilts):
            x, moved, margins = rows_tilted(params, cfg, entering, rows,
                                            tilts)
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

def _mla_params(cfg):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    qr, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (h * qr + qr * nh * (dn + dr) + h * (r + dr)
            + r * nh * (dn + dv) + nh * dv * h)


def counts(cfg: dict) -> dict:
    """`matmul_params`: the weights ONE token multiplies HERE: latent
    attention, the shared expert and the router in every expert layer,
    and of the routed experts the share of a token's
    `num_experts_per_tok` that falls on the experts held
    (x held / router width of one expert each); the dense layers; the
    sliced head.  `total_params`: everything held, as `model.num_params`
    counts it (the router's weights and bias at their published width)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    held = cfg["n_routed_experts"]
    router = cfg.get("router_experts", held)
    expert = 3 * h * cfg["moe_intermediate_size"]
    mla, norms = _mla_params(cfg), (
        2 * h + cfg["q_lora_rank"] + cfg["kv_lora_rank"])
    moe_matmul = (mla + cfg["n_shared_experts"] * expert + h * router
                  + cfg["num_experts_per_tok"] * held / router * expert)
    dense = mla + 3 * h * cfg["intermediate_size"]
    return {
        "matmul_params": n_moe * moe_matmul + n_dense * dense + h * v,
        "attn_width": cfg["num_hidden_layers"] * cfg["num_attention_heads"]
        * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]),
        "total_params": (
            n_moe * (mla + norms + cfg["n_shared_experts"] * expert
                     + h * router + router + held * expert)
            + n_dense * (dense + norms) + 2 * h * v + h)}


def paged_latent_attn_cost(cfg: dict, window: dict,
                           elem_bytes: float = 2.0):
    """Required operations and bytes of the paged latent attention of
    the window's decode steps, all layers: every cached latent
    (`kv_lora_rank` + `qk_rope_head_dim` values; the lanes it is padded
    to are not required work) is read ONCE, the absorbed query is read
    and the latent output written per query head; each cached position
    is a key of `latent` and a value of `kv_lora_rank` values for every
    head.  None where the program counted no decode step."""
    context_tokens = window["counters"].get("serve.decode_context_tokens")
    queries = window["counters"].get("serve.decode_slot_steps")
    if not context_tokens or not queries:
        return None
    L, nh = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    r = cfg["kv_lora_rank"]
    latent = r + cfg["qk_rope_head_dim"]
    return {"ops": L * 2.0 * nh * (latent + r) * context_tokens,
            "bytes": L * elem_bytes * (latent * context_tokens
                                       + queries * nh * (latent + r))}


def latent_chunk_attn_cost(cfg: dict, window: dict,
                           elem_bytes: float = 2.0):
    """families/longcat_flash.latent_chunk_attn_cost over this family's
    `num_hidden_layers` cache layers, every one a latent layer (that
    function counts two cache layers a layer of its `num_layers`, and
    every term by the layer; families/xing4 reads it the same way).
    Imported here: that module imports this one."""
    from benchmarks.families import longcat_flash
    cost = longcat_flash.latent_chunk_attn_cost(
        dict(cfg, num_layers=1), window, elem_bytes)
    return cost and {k: v * cfg["num_hidden_layers"] / 2
                     for k, v in cost.items()}


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
