"""The LFM2-MoE family (`model_type: lfm2_moe`): what
`hetu_tpu/models/lfm2_moe` implements and
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json
describes, under its published key names.

The plain reference (`logits_at`) is float32 `jax.numpy` under
`default_matmul_precision("highest")`, reading the program's parameter
tree and nothing else of the program: no kernel, no cache, no chunks, no
state carried between calls, no pages, no heads laid in pairs.  x in
R^hidden, RMSNorm with a learned gain and `norm_eps`, no bias anywhere:

    h = x + Op_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h));
    logits = RMSNorm(x_L) E^T            (E the embedding: the head is tied)

* `layer_types[l] == "conv"`: [B | C | z] = u W_in (in that order); a =
  B * z; c_t = sum_j w_j a_{t-(K-1)+j} over K = `conv_L_cache` taps, the
  last on the current position, a = 0 before the sequence, computed as K
  shifted products; o = (C * c) W_out.
* `"full_attention"`: q = RMSNorm_hd(u W_q), k = RMSNorm_hd(u W_k), v =
  u W_v over heads of hd = hidden / `num_attention_heads`; q and k rotated
  after the norms (half-split over the whole head, `rope_theta`); causal
  softmax(q k^T hd^-1/2) v over an explicit mask, query head j reading
  K/V head j // group; W_o.  In blocks of `Q_BLOCK` query rows.
* l < `num_dense_layers`: W_2 (silu(W_1 u) * W_3 u).  After: s =
  sigmoid(u W_r); the `num_experts_per_tok` experts of a token are the
  largest of s + `expert_bias`; their weights are s there WITHOUT the
  bias over (their sum + 1e-6) (`norm_topk_prob`) times
  `routed_scaling_factor`; sum_i w_i E_i(u), each E a SwiGLU.  A loop
  over the experts, each applied to the rows that chose it (gathered,
  `cap` rows at most; to every row, weighted 0 where it was not chosen,
  if some expert has more).

Where a choice of experts is a near tie (`router_tie_logit`, a key of
the configuration; absent or 0, `logits_at` is the plain forward and
nothing else): families/xing4 says why every near tie reaches the logits
where EVERY expert is held, and why both choices are computations of the
published layer at the stated precision; the passes here are its own.
`logits_at` runs the forward once as it stands and, for the tokens at
`rows` alone (every other token as the plain pass has it: a convolution
layer's inputs a and an attention layer's keys and values are kept, and
the rows' own replaced), once for each exchange of
families/xing4.EXCHANGES in ONE expert layer and once with the nearest
tie of every layer decided the other way (`pass_codes`: 31 passes at ten
expert layers; a pass begins at the first layer it changes), an exchange
being made only where the margin between the two experts in the router's
logit is under `router_tie_logit`.  A row whose own token, or one of the
`conv_L_cache` - 1 tokens before it (whose inputs its convolutions
read), was so changed in a pass gets, value by value, its best standing
under the row's largest logit in any of its passes (each other pass's
logits are shifted so that its largest stands one float32 step under the
plain pass's largest, which stays the row's argmax).  `control` runs
through every pass: what the passes forgive a wrong reference is
forgiven.

A list that short covers ten all-held layers because of how the
configuration SEEDS the experts (its `assumed.expert_initializer_range`
says why, with the readings): the share of bfloat16's exchanges is not
the router's to lower (the choice is the same at any scale of its
weights), so the routed experts are seeded a little smaller than the
other matrices, until what two or three stacked exchanges move stays
under the comparison's limit.

Controls (`logits_at(control=)`, tests and the chip's control runs only;
`CONTROLS`): "fp8_tail", the convolution's inputs of the EARLIER
positions (what a sequence's state holds) rounded to e4m3, the nearest
precision below the configuration's bfloat16; "dropped_tap", the oldest
tap left out; "pad_tail", the two positions after the prompt read, for
the positions before them, the inputs of the stream's LAST rows (padding:
a tail taken where the chunk ends, not where its valid rows end);
"bias_in_weights", the chosen experts weighted by s + b (the
configuration seeds b around an offset that the choice cannot see and
these weights do); "wrong_fourth", the last of a token's chosen experts
replaced by the best not chosen, in every layer: the smallest fault a
choice can have.

The family also brings its cost functions under the names the standing
rule files ask of a cell's family (`paged_attn_cost`, `chunk_attn_cost`,
`grouped_matmul_cost`) and one of its own (`short_conv_cost`): each
counts the MODEL's bytes and products, heads of 64 (the zeros laid
beside a query in a lane row are no work).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families import kimi_k2, xing4
from benchmarks.families.afmoe import _rope
from benchmarks.families.kimi_k2 import _rms_norm, _swiglu
from benchmarks.families.phi4flash import serve_config  # noqa: F401
# at import, not in `build_model`: a program without the family (the
# parent of PR 62) then fails in `run.load_cell`, at once, with exit 2
from hetu_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeLMHeadModel

F32 = jnp.float32
Q_BLOCK = 256
#: what ONE pass may exchange at the edge of a token's k chosen experts
#: in ONE expert layer (families/xing4 says what each is)
EXCHANGES = xing4.EXCHANGES
CONTROLS = ("fp8_tail", "dropped_tap", "pad_tail", "bias_in_weights",
            "wrong_fourth")

#: the configuration file's keys that `Lfm2MoeConfig` takes as they are
PUBLISHED = ("vocab_size", "hidden_size", "intermediate_size",
             "moe_intermediate_size", "num_hidden_layers",
             "num_dense_layers", "layer_types", "num_attention_heads",
             "num_key_value_heads", "conv_L_cache", "conv_bias",
             "num_experts", "num_experts_per_tok", "norm_topk_prob",
             "routed_scaling_factor", "use_expert_bias", "norm_eps",
             "rope_theta", "max_position_embeddings")


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def build_model(config: dict, how: dict, strategy=None):
    """The program's model: the published keys as they are, what the
    file assumes beside them."""
    if config["model_type"] != "lfm2_moe":
        raise ValueError("models/lfm2_moe implements model_type='lfm2_moe', "
                         f"the file says {config['model_type']!r}")
    dtype = jnp.dtype(how.get("param_dtype", "bfloat16"))
    return Lfm2MoeLMHeadModel(Lfm2MoeConfig(
        param_dtype=dtype, compute_dtype=dtype,
        tie_word_embeddings=config["tie_word_embeddings"],
        initializer_range=config["initializer_range"],
        expert_bias_std=config["correction_bias_std"],
        expert_bias_mean=config["correction_bias_mean"],
        expert_initializer_range=config["expert_initializer_range"],
        conv_tap_std=config["conv_tap_std"],
        route_norm_eps=config["route_norm_eps"],
        **{k: config[k] for k in PUBLISHED}), strategy)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _widths(cfg):
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return cfg["hidden_size"], nq, nkv, cfg["hidden_size"] // nq


def _expert_layers(cfg):
    return range(cfg["num_dense_layers"], cfg["num_hidden_layers"])


def conv_inputs(u, ap, cfg):
    """(a = B * z, C) [n, hidden] of the rows u [n, hidden] (normed)."""
    h = cfg["hidden_size"]
    x = u @ ap["w_in"].astype(F32)
    return x[:, :h] * x[:, 2 * h:], x[:, h: 2 * h]


def conv_taps(a, at, ap, cfg, control=None, plen=None):
    """c_t at the positions `at` [n] of a sequence whose inputs are a
    [s, hidden]: K shifted products, zeros before the sequence."""
    K = cfg["conv_L_cache"]
    w = ap["conv_w"].astype(F32)
    earlier = a
    if control == "fp8_tail":
        earlier = jax.lax.reduce_precision(a, exponent_bits=4,
                                           mantissa_bits=3)

    def padded(x):
        return jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), F32), x])
    own = padded(a)

    def taps(src):
        """The current position's input as it is, the earlier ones' from
        `src`."""
        old = own if src is a else padded(src)
        return sum(w[j] * (own if j == K - 1 else old)[at + j]
                   for j in range(K)
                   if not (control == "dropped_tap" and j == 0))
    c = taps(earlier)
    if control == "pad_tail":
        # positions plen .. plen + K - 2 read, for what lies before
        # plen, the stream's last K - 1 rows
        before = jnp.arange(a.shape[0])
        wrong = jnp.where(
            ((before >= plen - (K - 1)) & (before < plen))[:, None],
            jnp.roll(a, plen, axis=0), a)
        late = (at >= plen) & (at < plen + K - 1)
        c = jnp.where(late[:, None], taps(wrong), c)
    return c


def attn_project(u, pos, ap, cfg):
    """(q [n, nq, hd], k, v [n, nkv, hd]) of the rows u [n, hidden]
    (normed) at positions `pos`: head norms, then the rotation."""
    _, nq, nkv, hd = _widths(cfg)
    n, eps = u.shape[0], cfg["norm_eps"]
    x = u @ ap["w_qkv"].astype(F32)
    q = _rms_norm(x[:, :nq * hd].reshape(n, nq, hd), ap["q_norm"], eps)
    k = _rms_norm(x[:, nq * hd: (nq + nkv) * hd].reshape(n, nkv, hd),
                  ap["k_norm"], eps)
    v = x[:, (nq + nkv) * hd:].reshape(n, nkv, hd)
    return (_rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"]),
            v)


def attend(q, pos, k, v, ap):
    """The queries q [n, nq, hd] at positions `pos` over the keys and
    values of positions 0..s-1, causal under an explicit mask, in blocks
    of `Q_BLOCK` query rows, through W_o."""
    n, nq, hd = q.shape
    s, nkv = k.shape[:2]
    qb = math.gcd(n, Q_BLOCK)

    def rows(blk):
        q_blk, at = blk
        sc = jnp.einsum("qngd,knd->ngqk",
                        q_blk.reshape(qb, nkv, nq // nkv, hd), k) * hd ** -0.5
        seen = jnp.arange(s)[None, :] <= at[:, None]
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("ngqk,knd->qngd", p, v)
    o = jax.lax.map(rows, (q.reshape(n // qb, qb, nq, hd),
                           pos.reshape(n // qb, qb)))
    return o.reshape(n, nq * hd) @ ap["w_o"].astype(F32)


def gate(x, mp, cfg, code=None, control=None):
    """(expert ids [s, k], weights [s, k]) of the published gate.  With
    `code` (an index into `EXCHANGES` a token, [s]) also which tokens were
    changed [s]: the chosen expert at rank `leaves` gives its place to
    the one at rank `enters` where the router-logit margin between the
    two (their distance in s + b over the sigmoid's mean slope at the
    two) is under `router_tie_logit`."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ mp["w_gate"].astype(F32))
    bias = mp["e_score_correction_bias"].astype(F32)
    v = scores + bias
    top_v, top = jax.lax.top_k(v, k + 2)
    idx = top[:, :k]
    if control == "wrong_fourth":
        idx = idx.at[:, k - 1].set(top[:, k])
    if code is not None:
        leaves, enters = (jnp.asarray([e[i] for e in EXCHANGES])[code] + k
                          for i in (0, 1))
        pick = lambda a, r: jnp.take_along_axis(  # noqa: E731
            a, r[:, None], axis=1)[:, 0]
        a, b = pick(top, leaves), pick(top, enters)
        slope = sum(s * (1.0 - s) for s in (
            jnp.take_along_axis(scores, e[:, None], -1)[:, 0]
            for e in (a, b))) / 2
        margin = (pick(top_v, leaves) - pick(top_v, enters)) / (slope
                                                                + 1e-30)
        moved = (code > 0) & (margin < cfg["router_tie_logit"])
        idx = jnp.where(
            moved[:, None] & (jnp.arange(k)[None] == leaves[:, None]),
            b[:, None], idx)
    w = jnp.take_along_axis(
        scores + bias if control == "bias_in_weights" else scores, idx,
        axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * cfg["routed_scaling_factor"]
    return (idx, w) if code is None else (idx, w, moved)


def experts(x, mp, cfg, code=None, control=None):
    """The expert layer on x [s, hidden] (module docstring)."""
    idx, w, *moved = gate(x, mp, cfg, code, control)
    s, E = x.shape[0], mp["w_gate_up"].shape[0]
    xs = (mp["w_gate_up"], mp["w_down"], jnp.arange(E))
    # twice an expert's mean share of the rows, in whole 64s
    cap = min(s, -(-2 * s * idx.shape[1] // E // 64) * 64)

    def weight_of(e):
        return jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)

    def every_row(_):
        def one(acc, xs):
            w_gate_up, w_down, e = xs
            return acc + weight_of(e)[:, None] * _swiglu(x, w_gate_up,
                                                         w_down), None
        return jax.lax.scan(one, jnp.zeros_like(x), xs)[0]

    def chosen_rows(_):
        def one(acc, xs):
            w_gate_up, w_down, e = xs
            (at,) = jnp.nonzero(jnp.any(idx == e, axis=-1), size=cap,
                                fill_value=s)
            y = _swiglu(x.at[at].get(mode="fill", fill_value=0.0),
                        w_gate_up, w_down)
            y = y * weight_of(e).at[at].get(mode="fill",
                                            fill_value=0.0)[:, None]
            return acc.at[at].add(y, mode="drop"), None
        return jax.lax.scan(one, jnp.zeros_like(x), xs)[0]
    load = jnp.max(jnp.bincount(idx.reshape(-1), length=E))
    y = jax.lax.cond(load <= cap, chosen_rows, every_row, None)
    return (y, *moved) if moved else y


def _layers(params, cfg):
    return [params["model"][f"layer_{i}"]
            for i in range(cfg["num_hidden_layers"])]


def _operator(u, pos, lp, cfg, l, seq, control, plen):
    """Layer l's operator for the rows u [n, hidden] (normed) at
    positions `pos`.  `seq` None: the rows are the whole sequence; else
    what the plain pass kept of the whole sequence in this layer (a, or
    (k, v)), in which the rows' own are replaced.  -> (out, what the
    layer keeps of the sequence)."""
    ap = lp["attn"]
    if cfg["layer_types"][l] == "conv":
        a, C = conv_inputs(u, ap, cfg)
        a = a if seq is None else seq.at[pos].set(a)
        c = conv_taps(a, pos, ap, cfg, control, plen)
        return (C * c) @ ap["w_out"].astype(F32), a
    q, k, v = attn_project(u, pos, ap, cfg)
    if seq is not None:
        k, v = seq[0].at[pos].set(k), seq[1].at[pos].set(v)
    return attend(q, pos, k, v, ap), (k, v)


def _layer(x, pos, lp, cfg, l, seq=None, code=None, control=None,
           plen=None):
    """Layer l on the rows x [n, hidden] at positions `pos` -> (x', what
    the layer keeps of the sequence, which rows an exchange changed)."""
    eps = cfg["norm_eps"]
    out, kept = _operator(_rms_norm(x, lp["input_norm"]["weight"], eps),
                          pos, lp, cfg, l, seq, control, plen)
    x = x + out
    u = _rms_norm(x, lp["post_norm"]["weight"], eps)
    moved = jnp.zeros(pos.shape, bool)
    if l < cfg["num_dense_layers"]:
        y = _swiglu(u, lp["mlp"]["w_gate_up"], lp["mlp"]["w_down"])
    elif code is None:
        y = experts(u, lp["mlp"], cfg, control=control)
    else:
        y, moved = experts(u, lp["mlp"], cfg, code, control)
    return x + y, kept, moved


def hidden_states(params, ids, cfg, control=None, keep=None, plen=None):
    """Final-norm hidden states [s, hidden] of one sequence `ids` [s]:
    the layers one after the other.  `keep` (a dict with `rows`) is given
    what the near-tie passes start from: the tokens at `rows` as they
    enter each expert layer (`X` [expert layers, rows, hidden]) and what
    every expert layer keeps of the whole sequence (`seq`)."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(ids.shape[0])
        x = params["model"]["embed"]["weight"][ids].astype(F32)
        if keep is not None:
            keep["X"], keep["seq"] = [], []
        for l, lp in enumerate(_layers(params, cfg)):
            if keep is not None and l in _expert_layers(cfg):
                keep["X"].append(x[keep["rows"]])
            x, kept, _ = _layer(x, pos, lp, cfg, l, control=control,
                                plen=plen)
            if keep is not None and l in _expert_layers(cfg):
                keep["seq"].append(kept)
        if keep is not None:
            keep["X"] = jnp.stack(keep["X"])
        return _rms_norm(x, params["model"]["final_norm"]["weight"],
                         cfg["norm_eps"])


def rows_passed(params, cfg, kept, codes, control=None, plen=None):
    """The expert layers again for the tokens at `kept["rows"]` alone,
    with the exchange `codes[i]` (`EXCHANGES`) made at the edge of each
    token's chosen experts in expert layer i where its margin allows,
    from the first layer with an exchange on (the layers before it are
    the plain pass's: `kept["X"]`); every other token is as the plain
    pass has it (`kept["seq"]`).  (final-norm hidden states [rows,
    hidden], which rows were changed in any layer [rows])."""
    rows = kept["rows"]
    n = codes.shape[0]
    first = jnp.min(jnp.where(codes > 0, jnp.arange(n), n))
    X, moved = kept["X"][0], jnp.zeros(rows.shape, bool)
    layers = _layers(params, cfg)
    for i, l in enumerate(_expert_layers(cfg)):
        X = jnp.where(i == first, kept["X"][i], X)

        def run(X, lp=layers[l], l=l, i=i):
            X, _, mv = _layer(X, rows, lp, cfg, l, kept["seq"][i],
                              jnp.broadcast_to(codes[i], rows.shape),
                              control, plen)
            return X, mv
        X, mv = jax.lax.cond(i >= first, run,
                             lambda X: (X, jnp.zeros(rows.shape, bool)), X)
        moved = moved | mv
    return _rms_norm(X, params["model"]["final_norm"]["weight"],
                     cfg["norm_eps"]), moved


def pass_codes(cfg):
    """The passes of `logits_at` beyond the plain one, [passes, expert
    layers] of indices into `EXCHANGES`: each exchange in ONE expert
    layer, and the nearest tie of every layer at once (31 passes at ten
    expert layers)."""
    n = len(_expert_layers(cfg))
    one = [[e if j == i else 0 for j in range(n)]
           for i in range(n) for e in range(1, len(EXCHANGES))]
    return np.asarray(one + [[1] * n], np.int32)


def logits_at(params, ids, rows, cfg, control=None):
    """Reference logits [len(rows), vocab] of one sequence `ids` [s] at
    the positions `rows` (the first of them the prompt's last position);
    under `router_tie_logit`, a row's standing under the best of the
    choices its near ties allow (module docstring).  `control`: one of
    `CONTROLS`, the same forward and the same passes with that ONE thing
    done wrongly in every one of them."""
    with jax.default_matmul_precision("highest"):
        head = params["model"]["embed"]["weight"].astype(F32).T
        plen = rows[0] + 1
        kept = {"rows": rows} if cfg.get("router_tie_logit") else None
        plain = hidden_states(params, ids, cfg, control, kept,
                              plen)[rows] @ head
        if kept is None:
            return plain
        # one float32 step under the plain pass's largest, so that a
        # row's argmax stays the plain forward's own
        under = jnp.nextafter(plain.max(-1, keepdims=True), -jnp.inf)

        def one(best, codes):
            x, moved = rows_passed(params, cfg, kept, codes, control, plen)
            lg = x @ head
            standing = lg - lg.max(-1, keepdims=True) + under
            # a convolution layer reads the K - 1 positions before a
            # row's own: a neighbour's exchange is the row's as well
            near = moved
            for j in range(1, cfg["conv_L_cache"]):
                near = near | (jnp.roll(moved, j) & (jnp.roll(rows, j)
                                                     == rows - j))
            return jnp.maximum(best, jnp.where(near[:, None], standing,
                                               plain)), None
        return jax.lax.scan(one, plain, jnp.asarray(pass_codes(cfg)))[0]


# ---------------------------------------------------------------------------
# the counts
# ---------------------------------------------------------------------------

def _kinds(cfg):
    """(convolution layers, attention layers)."""
    n_conv = sum(t == "conv" for t in cfg["layer_types"])
    return n_conv, cfg["num_hidden_layers"] - n_conv


def _operator_params(cfg):
    """(a convolution operator's matrix weights, an attention
    operator's)."""
    h, nq, nkv, hd = _widths(cfg)
    return 3 * h * h + h * h, h * (nq + 2 * nkv) * hd + nq * hd * h


def counts(cfg: dict) -> dict:
    """`matmul_params`: the weights ONE token multiplies: its layer's
    operator, the dense FFN or the router and `num_experts_per_tok`
    experts, the tied head once.  `total_params`: everything held, as
    `model.num_params` counts it (3,928,728,256 at the cell's depth)."""
    h, nq, _, hd = _widths(cfg)
    n_conv, n_attn = _kinds(cfg)
    conv, attn = _operator_params(cfg)
    n_dense = cfg["num_dense_layers"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    dense = 3 * h * cfg["intermediate_size"]
    operators = n_conv * conv + n_attn * attn
    return {
        "matmul_params": operators + n_dense * dense
        + n_moe * (h * E + k * expert) + h * cfg["vocab_size"],
        "attn_width": n_attn * nq * hd,
        "total_params": (
            operators + n_conv * cfg["conv_L_cache"] * h + n_attn * 2 * hd
            + cfg["num_hidden_layers"] * 2 * h + n_dense * dense
            + n_moe * (h * E + E + E * expert)
            + h * cfg["vocab_size"] + h)}


def conv_state_bytes_per_slot(cfg: dict, elem_bytes: float = 2.0) -> float:
    return _kinds(cfg)[0] * elem_bytes * (
        cfg["conv_L_cache"] - 1) * cfg["hidden_size"]


def paged_attn_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations and bytes of paged decode attention over the
    attention layers, for the single-token queries of the window's decode
    steps: what the MODEL needs, whatever implements it.  Each attention
    layer reads every cached K and V of the steps' contexts once
    (`serve.decode_context_tokens`, one layer's count), `nkv` heads of
    `hd` = 64 values each; q is read and o written, `nq` heads of 64; the
    operations are q . k and p . v of 64 a query head (the halves of a
    lane row that a query multiplies by zeros are not work).  None where
    the program counted no decode step."""
    c = window["counters"]
    tokens, queries = (c.get("serve.decode_context_tokens"),
                       c.get("serve.decode_slot_steps"))
    if not tokens or not queries:
        return None
    _, nq, nkv, hd = _widths(cfg)
    layers = _kinds(cfg)[1]
    return {"ops": layers * 4.0 * tokens * nq * hd,
            "bytes": elem_bytes * layers * (2.0 * tokens * nkv * hd
                                            + queries * nq * 2 * hd)}


def chunk_attn_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations of the chunk program's attention over the
    attention layers: every (query, key) pair the causal mask lets
    through (`serve.prefill_attended_keys`, counted per chunk launch and
    ONE layer) is a q . k and a p . v of 64 for each of the query heads.
    The bytes are the chunk's own q and o once a layer and the K and V of
    the positions its queries see.  None where the program counted no
    chunk."""
    c = window["counters"]
    _, nq, nkv, hd = _widths(cfg)
    layers = _kinds(cfg)[1]
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


grouped_matmul_cost = kimi_k2.grouped_matmul_cost


def short_conv_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations and bytes of the gated short convolution in
    the window's decode steps, all convolution layers: W_in and W_out
    read once a step a layer and multiplied by every row that decodes
    (`serve.decode_slot_steps`); a row's hidden state read, its addend
    written, and its state read once and written once (the program's own
    count, `serve.conv_state_bytes`).  The gates and the taps (2 + 2 K
    operations a channel) are in neither peak's reach and are left out.
    None where the program counted no decode step."""
    c = window["counters"]
    steps, rows = c.get("serve.decode_steps"), c.get(
        "serve.decode_slot_steps")
    if not steps or not rows:
        return None
    h, layers = cfg["hidden_size"], _kinds(cfg)[0]
    weights = _operator_params(cfg)[0]
    return {"ops": layers * 2.0 * rows * weights,
            "bytes": elem_bytes * layers * (steps * weights + rows * 2 * h)
            + c.get("serve.conv_state_bytes", 0)}
