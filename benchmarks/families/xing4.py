"""The Xing4.0 family: what `hetu_tpu/models/xing4` implements and
https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json
describes, under its published key names: Kimi-K2's block (latent
attention, leading dense layers, sigmoid-routed experts beside a shared
one, YaRN) inside a residual STREAM of `hc_mult` hidden vectors a token,
mixed by manifold-constrained hyper-connections (Hyper-Connections,
arXiv:2409.19606, constrained as in mHC, arXiv:2512.24880).

The plain reference (`logits_at`) is float32 `jax.numpy` under
`default_matmul_precision("highest")`, reading the program's parameter
tree and nothing else of the program: no kernel, no cache, no batching, no
absorption, and NOT `hetu_tpu/nn/hyper_connections.py`: the stream is
written out here from the equations.  n = `hc_mult`, C = `hidden_size`;
the carry of a token is X in R^{n x C}.  For each of a layer's two
sublayers F (F_attn(h) = MLA(RMSNorm_in(h)); F_mlp(h) = SwiGLU or
experts(RMSNorm_post(h)): families/kimi_k2's), with that sublayer's own
`phi` [n C, n^2 + 2 n], `b` [n^2 + 2 n], `alpha` [3]:

    x^ = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)           (over n C)
    [u_pre | u_post | u_res] = x^ phi                            (n, n, n^2)
    H_pre  = sigmoid(alpha_pre u_pre + b_pre)
    H_post = 2 sigmoid(alpha_post u_post + b_post)
    M      = exp(clamp(alpha_res mat(u_res) + b_res, clamp_min, clamp_max))
    `hc_sinkhorn_iters` times:  M <- M / (rowsum(M) + hc_eps);
                                M <- M / (colsum(M) + hc_eps)
    H_res  = M
    h  = sum_i H_pre[i] X[i];   y = F(h)
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

Entry: the embedding replicated into the n streams; exit: the streams
summed, the final RMSNorm, the head.  The stream's part runs in blocks of
`ROW_BLOCK` rows (a token's mixing reads no other token) and attention in
groups of `HEAD_GROUP` heads by `Q_BLOCK` query rows, over keys made from
the sequence's LATENTS, so that the 33,152 positions of the cell's longest
stream fit beside the program's weights AND the engine's pool, which the
harness still holds when it checks (4.4 GiB were free there: my chip run,
PR 55): one float32 stream is 1.9 GB and is updated where it lies, the
MLP side runs by the same row blocks (the dense layer's gate and up
products of a whole sequence are 2.3 GB), and the head is multiplied a
slice of the vocabulary at a time.

Where a choice of experts is a near tie (`router_tie_logit`, a key of the
configuration; absent or 0, `logits_at` is the plain forward and nothing
else): the top 4 of 64 is a step function of the router's logits, the
program's bfloat16 hidden states differ from this forward's float32 ones,
and EVERY expert is held here, so every near tie at the edge of a token's
chosen four reaches the logits: an expert that changes places carries
about a quarter of the layer's routed output (weights renormalised over
four, times 2).  Both choices are computations of the published layer at
the stated precision (families/kimi_k2.logits_at says it of its own).
`logits_at` runs the forward once as it stands, and for the tokens at
`rows` alone (every other token as the plain pass has it: its latents
are kept) once for every way to give ONE or TWO expert layers one
exchange each at the edge of a token's chosen experts (`EXCHANGES`: the
last chosen for the best not chosen, the second-last chosen for the best
not chosen, the last chosen for the second-best not chosen; 4 x 3 + 6 x 9
= 66 passes at four expert layers), an exchange being made only where the
margin between the two experts in the router's logit is under
`router_tie_logit`.  A row whose own token was so changed in a pass
gets, value by value, its best standing under the row's largest logit in
any of its passes (each other pass's logits are shifted so that its
largest stands one float32 step under the plain pass's largest, which
stays the row's argmax): a served token is held to the comparison's limit
as it stands, under the plain choice or under one that near ties allow.
Readings (my chip runs, PR 55; the program's chunked bfloat16 prefill of
ONE sequence of 32,768 random ids against this forward, seed 5500000017;
the configuration's `assumed.router_tie_logit` has them in full): the
plain pass leaves 522-613 rows over the limit, one or more in 98-100% of
answers of 256 tokens; Kimi's own five passes (the nearest held expert of
ONE layer, or of all: families/kimi_k2.experts(.., tilt), imported for the
reading) leave 6-23; these 66 leave 15 / 3 / 1 / none at margins of 0.02 /
0.03 / 0.04 / 0.05 and none beyond, and the configuration keeps 0.07.
The passes forgive a reference that is WRONG as readily as one that is
right wherever the wrong thing shows as exchanged experts and little
else: `control` therefore runs through every pass, and the model's
initial H_pre reads ONE stream a sublayer so that what H_res does to the
streams reaches the logits (`assumed.mhc_init`: with H_pre near the
streams' mean, one Sinkhorn iteration for 20 left NO row of 32,768 over
the limit under these passes).

Departures from the published code: rotation is written half-split (a
fixed permutation of weight columns, nothing with random weights); the
multi-token-prediction layer (`num_nextn_predict_layers` 1) is not built:
the main model's logits do not depend on it.
"""
from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families import kimi_k2, longcat_flash
from benchmarks.families.kimi_k2 import (_mla_params, _rms_norm, _rope,
                                         _swiglu, softmax_scale)
from benchmarks.families.llama import serve_config  # noqa: F401
# at import, not in `build_model`: a program without the family (the
# parent of PR 55) then fails in `run.load_cell`, at once, with exit 2
from hetu_tpu.models.xing4 import Xing4Config, Xing4LMHeadModel

F32 = jnp.float32
ROW_BLOCK = 512
Q_BLOCK = 256
HEAD_GROUP = 4
#: what ONE pass of `logits_at` may exchange at the edge of a token's k
#: chosen experts in ONE expert layer, as (the rank that leaves, the rank
#: that enters) counted from the edge (rank k - 1 is the last chosen, k
#: the best not chosen): nothing; the nearest tie; the second-last chosen
#: for the best not chosen; the last chosen for the second-best not
#: chosen (module docstring)
EXCHANGES = ((-1, 0), (-1, 0), (-2, 0), (-1, 1))
#: the most expert layers ONE pass makes an exchange in (module docstring)
NEAR_TIE_LAYERS = 2
#: what `logits_at(control=)` may do wrongly, one thing each (the tests'
#: and the chip's controls)
CONTROLS = ("one_sinkhorn", "alpha_zero")

#: the configuration file's keys that `Xing4Config` takes as they are
PUBLISHED = kimi_k2.PUBLISHED + (
    "n_routed_experts", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
    "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def build_model(config: dict, how: dict, strategy=None):
    """The program's model: every routed expert of a layer is held
    (`ep_size` 1, as published)."""
    for key, want in (("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("n_group", 1),
                      ("topk_group", 1), ("moe_layer_freq", 1),
                      ("hidden_act", "silu"), ("attention_bias", False),
                      ("ep_size", 1), ("num_nextn_predict_layers", 0)):
        if config[key] != want:
            raise ValueError(f"models/xing4 implements {key}={want!r}, "
                             f"the file says {config[key]!r}")
    dtype = jnp.dtype(how.get("param_dtype", "bfloat16"))
    return Xing4LMHeadModel(Xing4Config(
        param_dtype=dtype, compute_dtype=dtype,
        initializer_range=config.get("initializer_range", 0.02),
        correction_bias_range=config.get("correction_bias_std", 0.002),
        **{k: config[k] for k in PUBLISHED}), strategy)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _by_row_blocks(fn, *xs):
    """fn over blocks of `ROW_BLOCK` rows of every array of `xs`."""
    s = xs[0].shape[0]
    rb = math.gcd(s, ROW_BLOCK)
    out = jax.lax.map(lambda blk: fn(*blk), tuple(
        x.reshape((s // rb, rb) + x.shape[1:]) for x in xs))
    return jax.tree.map(lambda o: o.reshape((s,) + o.shape[2:]), out)


def coefficients(X, hp, cfg, control=None):
    """X [r, n, C] -> (H_pre [r, n], H_post [r, n], H_res [r, n, n]) of
    the module docstring."""
    n = cfg["hc_mult"]
    x = X.reshape(X.shape[0], -1)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"])
    u = x @ hp["phi"].astype(F32)
    alpha, b = hp["alpha"].astype(F32), hp["b"].astype(F32)
    if control == "alpha_zero":         # the input-dependent part dropped
        alpha = jnp.zeros_like(alpha)
    h_pre = jax.nn.sigmoid(alpha[0] * u[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * u[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(alpha[2] * u[:, 2 * n:] + b[2 * n:],
                         cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"])).reshape(-1, n, n)
    iters = 1 if control == "one_sinkhorn" else cfg["hc_sinkhorn_iters"]
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + cfg["hc_eps"])
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + cfg["hc_eps"])
    return h_pre, h_post, m


def _pre(X, hp, cfg, control=None):
    """X [s, n, C] -> (h [s, C], (H_post, H_res)), by row blocks."""
    def rows(Xb):
        h_pre, h_post, h_res = coefficients(Xb, hp, cfg, control)
        return jnp.einsum("rn,rnc->rc", h_pre, Xb), h_post, h_res
    h, h_post, h_res = _by_row_blocks(rows, X)
    return h, (h_post, h_res)


def _post(X, y, mix):
    """X' of the module docstring, written over X block by block (one
    float32 stream of the cell's longest sequence is 1.9 GB)."""
    h_post, h_res = mix
    s = X.shape[0]
    rb = math.gcd(s, ROW_BLOCK)

    def one(i, X):
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, i * rb, rb)  # noqa: E731
        new = (jnp.einsum("rij,rjc->ric", take(h_res), take(X))
               + take(h_post)[:, :, None] * take(y)[:, None, :])
        return jax.lax.dynamic_update_slice_in_dim(X, new, i * rb, 0)
    return jax.lax.fori_loop(0, s // rb, one, X)


def latents(h, pos, ap, cfg):
    """(normed c_kv [q, r], rotated k_rope [q, dr]) of the rows h [q,
    hidden] (normed) at positions `pos`: what a token's cache entry
    holds."""
    r = cfg["kv_lora_rank"]
    ckv = h @ ap["wkv_a"].astype(F32)
    return (_rms_norm(ckv[:, :r], ap["kv_norm"]["weight"],
                      cfg["rms_norm_eps"]),
            _rope(ckv[:, None, r:], pos, cfg)[:, 0])


def attend(h, pos, c, k_rope, ap, cfg):
    """Expanded latent attention of the queries h [q, hidden] (normed) at
    positions `pos` over the keys and values made from the latents of
    positions 0..s-1 (c [s, r], k_rope [s, dr]), causal, through W_o;
    `HEAD_GROUP` heads at a time, `Q_BLOCK` query rows at a time."""
    dn, nh = cfg["qk_nope_head_dim"], cfg["num_attention_heads"]
    n, s, g = h.shape[0], c.shape[0], math.gcd(nh, HEAD_GROUP)
    cq = _rms_norm(h @ ap["wq_a"].astype(F32), ap["q_norm"]["weight"],
                   cfg["rms_norm_eps"])
    scale = softmax_scale(cfg)
    qb = math.gcd(n, Q_BLOCK)
    wq_b = ap["wq_b"].reshape(cq.shape[-1], nh // g, g, -1)
    wkv_b = ap["wkv_b"].reshape(c.shape[-1], nh // g, g, -1)

    def heads(ws):
        wq, wkv = ws
        q = jnp.einsum("qr,rgd->qgd", cq, wq.astype(F32))
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, cfg)], -1)
        kv = jnp.einsum("sr,rgd->sgd", c, wkv.astype(F32))
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(
                k_rope[:, None], (s, g, k_rope.shape[-1]))], axis=-1)

        def rows(q_blk_and_pos):
            q_blk, at = q_blk_and_pos
            sc = jnp.einsum("qgd,kgd->gqk", q_blk, k) * scale
            seen = jnp.arange(s)[None, :] <= at[:, None]        # [qb, s]
            p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kgd->qgd", p, kv[..., dn:])
        return jax.lax.map(rows, (q.reshape(n // qb, qb, g, -1),
                                  pos.reshape(n // qb, qb))).reshape(n, g, -1)
    out = jax.lax.map(heads, (jnp.moveaxis(wq_b, 1, 0),
                              jnp.moveaxis(wkv_b, 1, 0)))   # [nh/g, n, g, dv]
    return jnp.moveaxis(out, 0, 1).reshape(n, -1) @ ap["wo"].astype(F32)


def gate(x, mp, cfg, code=None):
    """(expert ids [s, k], weights [s, k]) of the published gate
    (families/kimi_k2.gate: sigmoid scores s, the top k of s + b, their
    weights s over their sum, times the factor).  With `code` (a traced
    index into `EXCHANGES`) also which tokens were changed [s]: the
    chosen expert at rank `leaves` gives its place to the one at rank
    `enters` where the router-logit margin between the two (their
    distance in s + b over the sigmoid's mean slope at the two) is under
    `router_tie_logit`."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ mp["w_gate"].astype(F32))
    v = scores + mp["e_score_correction_bias"].astype(F32)
    top_v, top = jax.lax.top_k(v, k + 2)
    idx = top[:, :k]
    if code is not None:
        leaves, enters = (jnp.asarray([e[i] for e in EXCHANGES])[code] + k
                          for i in (0, 1))
        pick = lambda a, r: jnp.take(a, r, axis=1)  # noqa: E731
        a, b = pick(top, leaves), pick(top, enters)
        slope = sum(s * (1.0 - s) for s in (
            jnp.take_along_axis(scores, e[:, None], -1)[:, 0]
            for e in (a, b))) / 2
        margin = (pick(top_v, leaves) - pick(top_v, enters)) / (slope
                                                                + 1e-30)
        moved = (code > 0) & (margin < cfg["router_tie_logit"])
        idx = jnp.where(moved[:, None] & (jnp.arange(k)[None] == leaves),
                        b[:, None], idx)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    return (idx, w) if code is None else (idx, w, moved)


def experts(x, mp, cfg, code=None):
    """The expert layer on x [s, hidden] (families/kimi_k2.experts with
    every expert held): a loop over the experts, each applied to every
    token and weighted by the gate's weight for it there (0 where it was
    not chosen), plus the shared expert."""
    idx, w, *moved = gate(x, mp, cfg, code)

    def one(acc, xs):
        w_gate_up, w_down, e = xs
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * _swiglu(x, w_gate_up, w_down), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        mp["w_gate_up"], mp["w_down"], jnp.arange(mp["w_gate_up"].shape[0])))
    y = y + _swiglu(x, mp["shared_gate_up"], mp["shared_down"])
    return (y, *moved) if moved else y


def _attn_side(X, rows, c, k_rope, lp, cfg, control=None):
    """The attention sublayer for the queries X [q, n, C] at positions
    `rows`, over keys from (c, k_rope) with the queries' own latents put
    at their positions; -> X'."""
    h, mix = _pre(X, lp["hc_attn"], cfg, control)
    hn = _rms_norm(h, lp["input_norm"]["weight"], cfg["rms_norm_eps"])
    c_q, kr_q = latents(hn, rows, lp["attn"], cfg)
    c, k_rope = (c_q, kr_q) if c is None else (
        c.at[rows].set(c_q), k_rope.at[rows].set(kr_q))
    return _post(X, attend(hn, rows, c, k_rope, lp["attn"], cfg),
                 mix), (c, k_rope)


def _mlp(h, lp, cfg, moe: bool, code=None):
    """The MLP side's F on rows h [r, hidden]: the post-attention norm,
    then the experts (with `code`, also which rows were changed) or the
    dense SwiGLU."""
    h = _rms_norm(h, lp["post_norm"]["weight"], cfg["rms_norm_eps"])
    if moe:
        return experts(h, lp["mlp"], cfg, code)
    return _swiglu(h, lp["mlp"]["w_gate_up"], lp["mlp"]["w_down"])


def _layers(params):
    m = params["model"]
    return [(group[f"layer_{i}"], moe)
            for group, moe in ((m["dense_layers"], False),
                               (m["moe_layers"], True))
            for i in range(len(group))]


def _exit(X, params, cfg):
    """The streams summed, then the final norm."""
    return _rms_norm(jnp.sum(X, axis=-2),
                     params["model"]["final_norm"]["weight"],
                     cfg["rms_norm_eps"])


def hidden_states(params, ids, cfg, control=None, keep=None):
    """Final-norm hidden states [s, hidden] of one sequence `ids` [s]:
    the layers one after the other.  `keep` (a dict with `rows`) is
    given what the near-tie passes start from: the stream of the tokens
    at `rows` as it enters the first expert layer (`X`) and every expert
    layer's latents of the whole sequence (`latents`)."""
    with jax.default_matmul_precision("highest"):
        s = ids.shape[0]
        pos = jnp.arange(s)
        x = params["model"]["embed"]["weight"][ids].astype(F32)
        X = jnp.broadcast_to(x[:, None], (s, cfg["hc_mult"], x.shape[-1]))
        for lp, moe in _layers(params):
            if moe and keep is not None and "X" not in keep:
                keep["X"], keep["latents"] = X[keep["rows"]], []
            X, lat = _attn_side(X, pos, None, None, lp, cfg, control)
            if moe and keep is not None:
                keep["latents"].append(lat)
            h, mix = _pre(X, lp["hc_mlp"], cfg, control)
            X = _post(X, _by_row_blocks(
                lambda hb, lp=lp, moe=moe: _mlp(hb, lp, cfg, moe), h), mix)
        return _exit(X, params, cfg)


def rows_passed(params, cfg, kept, codes, control=None):
    """The expert layers again for the tokens at `kept["rows"]` alone,
    with the exchange `codes[i]` (`EXCHANGES`) made at the edge of each
    token's chosen experts in expert layer i where its margin allows;
    every other token is as the plain pass has it (its latents, `kept`),
    and the rows attend those.  (final-norm hidden states [rows,
    hidden], which rows were changed in any layer [rows])."""
    rows, X = kept["rows"], kept["X"]
    moved = jnp.zeros(rows.shape, bool)
    moe = [lp for lp, is_moe in _layers(params) if is_moe]
    for i, lp in enumerate(moe):
        X, _ = _attn_side(X, rows, *kept["latents"][i], lp, cfg, control)
        h, mix = _pre(X, lp["hc_mlp"], cfg, control)
        y, mv = _mlp(h, lp, cfg, True, codes[i])
        X, moved = _post(X, y, mix), moved | mv
    return _exit(X, params, cfg), moved


def pass_codes(cfg):
    """The passes of `logits_at` beyond the plain one, [passes, expert
    layers] of indices into `EXCHANGES`: every way to give one or two
    expert layers (`NEAR_TIE_LAYERS`) one exchange each (66 passes at
    four expert layers)."""
    n = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    codes = np.asarray(list(itertools.product(range(len(EXCHANGES)),
                                              repeat=n)), np.int32)
    layers = (codes > 0).sum(1)
    return codes[(layers > 0) & (layers <= NEAR_TIE_LAYERS)]


def _head(x, w):
    """x [r, hidden] @ w [hidden, vocab] in float32, a slice of the
    vocabulary at a time (the whole head in float32 is 1.9 GB)."""
    V = w.shape[1]
    vb = V // max(1, V // 16384)

    def one(i, out):
        wb = jax.lax.dynamic_slice_in_dim(w, i * vb, vb, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, x @ wb.astype(F32), i * vb, axis=1)
    return jax.lax.fori_loop(0, V // vb, one,
                             jnp.zeros((x.shape[0], V), F32))


def logits_at(params, ids, rows, cfg, control=None):
    """Reference logits [len(rows), vocab] of one sequence `ids` [s] at
    the positions `rows`; under `router_tie_logit`, a row's standing
    under the best of the choices its near ties allow (module
    docstring).  `control` (tests and the chip's control runs only; one
    of `CONTROLS`): the same forward and the same passes with that ONE
    thing done wrongly in every one of them, which the comparison has
    to tell from the program."""
    with jax.default_matmul_precision("highest"):
        kept = {"rows": rows} if cfg.get("router_tie_logit") else None
        plain = _head(hidden_states(params, ids, cfg, control, kept)[rows],
                      params["lm_head"])
        if kept is None:
            return plain
        # one float32 step under the plain pass's largest, so that a
        # row's argmax stays the plain forward's own
        under = jnp.nextafter(plain.max(-1, keepdims=True), -jnp.inf)

        def one(best, codes):
            x, moved = rows_passed(params, cfg, kept, codes, control)
            lg = _head(x, params["lm_head"])
            standing = lg - lg.max(-1, keepdims=True) + under
            return jnp.maximum(best, jnp.where(moved[:, None], standing,
                                               plain)), None
        return jax.lax.scan(one, plain, jnp.asarray(pass_codes(cfg)))[0]


# ---------------------------------------------------------------------------
# the counts
# ---------------------------------------------------------------------------

def _mhc_params(cfg):
    """One sublayer's `phi`, `b` and `alpha`."""
    n = cfg["hc_mult"]
    k = n * n + 2 * n
    return n * cfg["hidden_size"] * k + k + 3


def counts(cfg: dict) -> dict:
    """families/kimi_k2.counts with every expert held and the stream's
    product with `phi` twice a layer.  `total_params` as
    `model.num_params` counts it."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    E = cfg["n_routed_experts"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    mla, norms = _mla_params(cfg), (
        2 * h + cfg["q_lora_rank"] + cfg["kv_lora_rank"])
    n = cfg["hc_mult"]
    phi = 2 * n * h * (n * n + 2 * n)
    moe_matmul = (mla + phi + cfg["n_shared_experts"] * expert + h * E
                  + cfg["num_experts_per_tok"] * expert)
    dense = mla + phi + 3 * h * cfg["intermediate_size"]
    return {
        "matmul_params": n_moe * moe_matmul + n_dense * dense + h * v,
        "attn_width": cfg["num_hidden_layers"] * cfg["num_attention_heads"]
        * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]),
        "total_params": (
            n_moe * (mla + norms + 2 * _mhc_params(cfg)
                     + cfg["n_shared_experts"] * expert + h * E + E
                     + E * expert)
            + n_dense * (mla + norms + 2 * _mhc_params(cfg)
                         + 3 * h * cfg["intermediate_size"])
            + 2 * h * v + h)}


def paged_latent_attn_cost(cfg: dict, window: dict,
                           elem_bytes: float = 2.0):
    """families/kimi_k2.paged_latent_attn_cost: every cached latent (576
    values: the lanes it is padded to are not required work) read ONCE a
    layer, the absorbed query read and the latent output written a head.
    None where the program counted no decode step."""
    return kimi_k2.paged_latent_attn_cost(cfg, window, elem_bytes)


def latent_chunk_attn_cost(cfg: dict, window: dict,
                           elem_bytes: float = 2.0):
    """families/longcat_flash.latent_chunk_attn_cost over this family's
    `num_hidden_layers` cache layers (that function counts two cache
    layers a layer of its `num_layers`, and every term by the layer)."""
    cost = longcat_flash.latent_chunk_attn_cost(
        dict(cfg, num_layers=1), window, elem_bytes)
    return cost and {k: v * cfg["num_hidden_layers"] / 2
                     for k, v in cost.items()}


grouped_matmul_cost = kimi_k2.grouped_matmul_cost


def mhc_chunk_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations and bytes of the stream's mixes in the chunk
    programs of the window, every layer's two sublayers, for the rows the
    launches computed (`serve.prefill_tokens`: padding rows excluded).
    Bytes: the stream's least passes (a sublayer reads n vectors and
    writes h, reads n and y and writes n: (n + 1 + 2 n + 1) C values a
    row) + `phi`, `b` and `alpha` read once a sublayer a launch
    (float32).  Operations a row a sublayer: the product with `phi`
    2 n C (n^2 + 2 n), the norm 3 n C, the pre-mix 2 n C, the post-mix
    2 n (n + 1) C, and of the Sinkhorn iterations 4 n^2 each (two sums,
    two divisions over n x n) + the 2 n + n^2 activations; ALL laid
    against the one peak `peaks.py` has, the MXU's, though only the first
    runs there: the share's bound is the bytes'.  No entry reads it yet
    (PERF.md s7: the fused kernel's PR lists it).  None where the program
    counted no chunk row."""
    c = window["counters"]
    rows, launches = c.get("serve.prefill_tokens"), c.get(
        "serve.prefill_chunks")
    if not rows or not launches:
        return None
    n, C = cfg["hc_mult"], cfg["hidden_size"]
    sublayers = 2 * cfg["num_hidden_layers"]
    a_row = (2.0 * n * C * (n * n + 2 * n) + 3 * n * C + 2 * n * C
             + 2 * n * (n + 1) * C
             + cfg["hc_sinkhorn_iters"] * 4 * n * n + 2 * n + n * n)
    return {"ops": sublayers * rows * a_row,
            "bytes": sublayers * (
                rows * (3 * n + 2) * C * elem_bytes
                + 4.0 * launches * _mhc_params(cfg))}
