"""The DeepSeek-V3.2 family: what `hetu_tpu/models/deepseek_v32`
implements and
https://huggingface.co/deepseek-ai/DeepSeek-V3.2/blob/main/config.json
describes (`model_type` `deepseek_v32`), under its published key names:
the DeepSeek-V3 block (latent attention, leading dense layers,
sigmoid-routed experts in groups beside a shared one, YaRN: families/
kimi_k2's, with families/bailing_hybrid's group-limited gate) whose
attention attends what a lightning indexer SELECTS (DeepSeek Sparse
Attention: the DeepSeek-V3.2-Exp report and the `inference/model.py`
published beside the weights).

The plain reference (`logits_at`) is float32 `jax.numpy` under
`default_matmul_precision("highest")`, reading the program's parameter
tree and nothing else of the program: no kernel, no cache, no absorption,
no bisection, and neither `hetu_tpu/models/deepseek_v32` nor
`hetu_tpu/ops/sparse_attention.py`.  Attention of the token at position t,
hn its normed hidden state, c_q = RMSNorm(hn W_qa) MLA's own:

    q^I_j = (c_q W^I_qb)_j in R^D, j = 1..H; first `qk_rope_head_dim` values
            rotated at t (MLA's tables, half-split)
    k^I_s = LayerNorm(hn_s W^I_k; gamma, beta, eps 1e-6); the same values
            rotated at s: one key a token for all heads
    w_j   = (hn W^I_w)_j * H^-1/2 * D^-1/2
    I_s   = sum_j w_j ReLU(q^I_j . k^I_s)                    for s <= t
    S     = the min(index_topk, t + 1) positions of largest I_s, by a
            STABLE sort of -I: equal scores, the lower position
    o_n   = sum_{s in S} softmax_{s in S}(q_n . k_{s,n} * scale) v_{s,n},
            MLA expanded (families/kimi_k2), then W_o

Everything runs in blocks so that the 33,792 positions a stream is padded
to fit beside the program's weights at 128 heads: the selection in blocks
of `Q_BLOCK` query rows (the scores of one block [rows, positions], the
sum over the indexer's heads a head at a time), its result kept as ONE
BIT a (query, position) pair; attention in groups of `HEAD_GROUP` heads
(a group's keys and values made from the latents once, its share of W_o
applied and summed where it stands: the heads' outputs side by side would
be 2.2 GB) by `Q_BLOCK` rows; the MLP side by `ROW_BLOCK` rows.  The LAST
layer's attention and MLP run for the rows that are read alone.

Where a choice of experts is a near tie (`router_tie_logit`; absent or 0,
`logits_at` is the plain forward): families/kimi_k2's rule, by families/
bailing_hybrid's gate, which knows the groups (the nearest edge a HELD
expert stands at, its group's or its own, decided the other way where
the margin in the router's logit is under `router_tie_logit`): one pass a
layer and one with every layer's, for the rows alone, every other token
as the plain pass has it (its latents and index keys are kept).

`control` (tests and the chip's control runs only) does ONE thing wrongly
in every pass, which the comparison has to tell from the program:
`no_relu` (the ReLU dropped), `topk_1024` (half the selection),
`recent_2048` (the last `index_topk` positions for the selected ones),
`flat_heads` (w constant).

Departures from the published code: rotation of MLA's q_rope and k_rope
is written half-split (a fixed permutation of weight columns, nothing
with random weights; the published indexer's is half-split as here); the
published indexer runs in FP8 behind a Hadamard rotation of q^I and k^I:
the rotation is orthogonal and changes no q^I . k^I, a v5e has no FP8
matrix unit, so both are left out exactly; the multi-token-prediction
layer is not built: the main model's logits do not depend on it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.families import kimi_k2
from benchmarks.families.bailing_hybrid import experts
from benchmarks.families.kimi_k2 import (_rms_norm, _rope, _swiglu,
                                         softmax_scale)
from benchmarks.families.llama import serve_config  # noqa: F401
# at import, not in `build_model`: a program without the family (the
# parent of PR 58) then fails in `run.load_cell`, at once, with exit 2
from hetu_tpu.models.deepseek_v32 import (DeepseekV32Config,
                                          DeepseekV32LMHeadModel)

F32 = jnp.float32
ROW_BLOCK = 512
Q_BLOCK = 256
HEAD_GROUP = 4
#: what `logits_at(control=)` may do wrongly, one thing each
CONTROLS = ("no_relu", "topk_1024", "recent_2048", "flat_heads")

#: the configuration file's keys that `DeepseekV32Config` takes as they are
PUBLISHED = kimi_k2.PUBLISHED + ("n_group", "topk_group", "index_n_heads",
                                 "index_head_dim", "index_topk")


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def build_model(config: dict, how: dict, strategy=None):
    """The program's model.  `n_routed_experts` of the file is how many
    experts are HELD here (`reduced`); the router keeps the published
    width, `router_experts`, and its groups."""
    for key, want in (("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("moe_layer_freq", 1),
                      ("hidden_act", "silu"), ("attention_bias", False),
                      ("num_nextn_predict_layers", 0)):
        if config[key] != want:
            raise ValueError(f"models/deepseek_v32 implements {key}="
                             f"{want!r}, the file says {config[key]!r}")
    dtype = jnp.dtype(how.get("param_dtype", "bfloat16"))
    return DeepseekV32LMHeadModel(DeepseekV32Config(
        n_routed_experts=config.get("router_experts",
                                    config["n_routed_experts"]),
        experts_held=config["n_routed_experts"],
        first_expert=config.get("first_expert", 0),
        param_dtype=dtype, compute_dtype=dtype,
        initializer_range=config.get("initializer_range", 0.02),
        embed_initializer_range=config.get("embed_initializer_range"),
        correction_bias_range=config.get("correction_bias_std", 0.02),
        **{k: config[k] for k in PUBLISHED}), strategy)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _by_blocks(fn, size: int, *xs):
    """fn over blocks of up to `size` rows of every array of `xs`."""
    s = xs[0].shape[0]
    rb = math.gcd(s, size)
    out = jax.lax.map(lambda blk: fn(*blk), tuple(
        x.reshape((s // rb, rb) + x.shape[1:]) for x in xs))
    return jax.tree.map(lambda o: o.reshape((s,) + o.shape[2:]), out)


def _rope_first(x, pos, cfg):
    """x [s, heads, D]: its first `qk_rope_head_dim` values rotated."""
    dr = cfg["qk_rope_head_dim"]
    return jnp.concatenate([_rope(x[..., :dr], pos, cfg), x[..., dr:]], -1)


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * p["weight"].astype(F32)
            + p["bias"].astype(F32))


def entries(h, pos, ap, cfg):
    """What the tokens h [q, hidden] (normed) at positions `pos` store:
    (normed c_kv [q, r], rotated k_rope [q, dr], index key [q, D])."""
    r = cfg["kv_lora_rank"]
    ckv = h @ ap["wkv_a"].astype(F32)
    ip = ap["indexer"]
    ki = _layer_norm(h @ ip["wk"].astype(F32), ip["k_norm"], 1e-6)
    return (_rms_norm(ckv[:, :r], ap["kv_norm"]["weight"],
                      cfg["rms_norm_eps"]),
            _rope(ckv[:, None, r:], pos, cfg)[:, 0],
            _rope_first(ki[:, None], pos, cfg)[:, 0])


def index_scores(cq, h, pos, keys, ip, cfg, control=None):
    """I [q, s] of the module docstring for the queries (cq, h) at
    positions `pos` over the index keys of positions 0..s-1; -inf past a
    query's own position."""
    H, D = cfg["index_n_heads"], cfg["index_head_dim"]
    q = _rope_first((cq @ ip["wq_b"].astype(F32)).reshape(-1, H, D), pos,
                    cfg)
    scale = H ** -0.5 * D ** -0.5
    w = (h @ ip["w_heads"].astype(F32)) * scale
    if control == "flat_heads":
        w = jnp.full_like(w, scale)

    def head(acc, xs):
        qj, wj = xs
        s = qj @ keys.T
        if control != "no_relu":
            s = jax.nn.relu(s)
        return acc + wj[:, None] * s, None
    scores, _ = jax.lax.scan(
        head, jnp.zeros((q.shape[0], keys.shape[0]), F32),
        (jnp.moveaxis(q, 1, 0), w.T))
    seen = jnp.arange(keys.shape[0])[None, :] <= pos[:, None]
    return jnp.where(seen, scores, -jnp.inf)


def selected(scores, pos, cfg, control=None):
    """bool [q, s]: S of the module docstring, by a stable sort."""
    k = cfg["index_topk"]
    if control == "topk_1024":
        k //= 2
    kpos = jnp.arange(scores.shape[1])[None, :]
    if control == "recent_2048":
        return (kpos <= pos[:, None]) & (kpos > pos[:, None] - k)
    # a STABLE sort of -I puts equal scores in ascending position: the
    # selection is what stands above the k-th entry's score and, of the
    # scores that equal it, the positions up to that entry's own
    scores = jnp.where(scores == 0, 0.0, scores)
    last = jnp.argsort(-scores, axis=-1, stable=True)[:, min(k, scores.shape[1]) - 1]
    thr = jnp.take_along_axis(scores, last[:, None], axis=-1)
    return ((scores > thr) | ((scores == thr) & (kpos <= last[:, None]))) \
        & (scores > -jnp.inf)


def _pack(keep):
    """bool [q, s] -> uint8 [q, ceil(s / 8)], a bit a position."""
    q, s = keep.shape
    keep = jnp.pad(keep, ((0, 0), (0, -s % 8))).reshape(q, -1, 8)
    return jnp.sum(keep.astype(jnp.uint8) << jnp.arange(8, dtype=jnp.uint8),
                   axis=-1, dtype=jnp.uint8)


def _unpack(bits, s: int):
    q = bits.shape[0]
    return ((bits[:, :, None] >> jnp.arange(8, dtype=jnp.uint8)) & 1) \
        .astype(bool).reshape(q, -1)[:, :s]


def attend(h, pos, c, k_rope, keys, ap, cfg, control=None,
           whole: bool = False):
    """Sparse latent attention of the queries h [q, hidden] (normed) at
    positions `pos` over the entries of positions 0..s-1 (c [s, r],
    k_rope [s, dr], keys [s, D]), through W_o.  `whole`: the queries ARE
    the sequence (`pos` = 0..s-1); they are then taken a quarter at a
    time, each over the positions up to its own end and no further (the
    rest lies behind the causal mask: five eighths of the work)."""
    n, s = h.shape[0], c.shape[0]
    if whole and n == s and s % (4 * Q_BLOCK) == 0:
        return jnp.concatenate([
            _attend(h[a:a + s // 4], pos[a:a + s // 4], c[:a + s // 4],
                    k_rope[:a + s // 4], keys[:a + s // 4], ap, cfg, control)
            for a in range(0, s, s // 4)])
    return _attend(h, pos, c, k_rope, keys, ap, cfg, control)


def _attend(h, pos, c, k_rope, keys, ap, cfg, control=None):
    """`attend` over all the positions it is handed: the selection by
    blocks of `Q_BLOCK` rows, then MLA expanded over the selected
    positions, `HEAD_GROUP` heads at a time."""
    dn, nh = cfg["qk_nope_head_dim"], cfg["num_attention_heads"]
    dv = cfg["v_head_dim"]
    n, s, g = h.shape[0], c.shape[0], math.gcd(nh, HEAD_GROUP)
    cq = _rms_norm(h @ ap["wq_a"].astype(F32), ap["q_norm"]["weight"],
                   cfg["rms_norm_eps"])
    bits = _by_blocks(
        lambda cq_b, h_b, at: _pack(selected(index_scores(
            cq_b, h_b, at, keys, ap["indexer"], cfg, control), at, cfg,
            control)), Q_BLOCK, cq, h, pos)
    scale = softmax_scale(cfg)
    wq_b = ap["wq_b"].reshape(cq.shape[-1], nh // g, g, -1)
    wkv_b = ap["wkv_b"].reshape(c.shape[-1], nh // g, g, -1)
    wo = ap["wo"].reshape(nh // g, g * dv, -1)

    def heads(y, ws):
        wq, wkv, wo_g = ws
        q = jnp.einsum("qr,rgd->qgd", cq, wq.astype(F32))
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, cfg)], -1)
        kv = jnp.einsum("sr,rgd->sgd", c, wkv.astype(F32))
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(
                k_rope[:, None], (s, g, k_rope.shape[-1]))], axis=-1)

        def rows(q_blk, bits_blk):
            sc = jnp.einsum("qgd,kgd->gqk", q_blk, k) * scale
            p = jax.nn.softmax(
                jnp.where(_unpack(bits_blk, s)[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kgd->qgd", p, kv[..., dn:])
        out = _by_blocks(rows, Q_BLOCK, q, bits)
        return y + out.reshape(n, -1) @ wo_g.astype(F32), None
    y, _ = jax.lax.scan(
        heads, jnp.zeros((n, ap["wo"].shape[-1]), F32),
        (jnp.moveaxis(wq_b, 1, 0), jnp.moveaxis(wkv_b, 1, 0), wo))
    return y


def _mlp(h, lp, cfg, moe: bool, tilt=None):
    """The MLP side on rows h [r, hidden]: the post-attention norm, then
    the experts (with `tilt`, also which rows were changed and the
    margins) or the dense SwiGLU."""
    h = _rms_norm(h, lp["post_norm"]["weight"], cfg["rms_norm_eps"])
    if moe:
        return experts(h, lp["mlp"], cfg, tilt)
    return _swiglu(h, lp["mlp"]["w_gate_up"], lp["mlp"]["w_down"])


def _layers(params):
    m = params["model"]
    return [(group[f"layer_{i}"], moe)
            for group, moe in ((m["dense_layers"], False),
                               (m["moe_layers"], True))
            for i in range(len(group))]


def hidden_states(params, ids, rows, cfg, control=None, keep=None):
    """Final-norm hidden states [len(rows), hidden] of one sequence `ids`
    [s] at the positions `rows`: the layers one after the other, the
    last one for the rows alone.  `keep` (a dict) is given what the
    near-tie passes start from: the rows' hidden state as it enters the
    first expert layer (`x`) and every expert layer's entries of the
    whole sequence (`entries`)."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(ids.shape[0])
        x = params["model"]["embed"]["weight"][ids].astype(F32)
        layers = _layers(params)
        for l, (lp, moe) in enumerate(layers):
            if moe and keep is not None and "x" not in keep:
                keep["x"], keep["entries"] = x[rows], []
            hn = _rms_norm(x, lp["input_norm"]["weight"], eps)
            ent = entries(hn, pos, lp["attn"], cfg)
            if moe and keep is not None:
                keep["entries"].append(ent)
            last = l == len(layers) - 1
            if last:
                x, hn, pos = x[rows], hn[rows], rows
            x = x + attend(hn, pos, *ent, lp["attn"], cfg, control,
                           whole=not last)
            x = x + _by_blocks(
                lambda hb, lp=lp, moe=moe: _mlp(hb, lp, cfg, moe),
                ROW_BLOCK, x)
        return _rms_norm(x, params["model"]["final_norm"]["weight"], eps)


def rows_tilted(params, cfg, kept, rows, tilts, control=None):
    """The expert layers again for the tokens at `rows` alone, with the
    near ties of the layers `tilts` (a bool each) decided the other way;
    every other token is as the plain pass has it (its entries, `kept`),
    and the rows attend those.  (final-norm hidden states [rows, hidden],
    which rows were changed in any layer [rows])."""
    eps = cfg["rms_norm_eps"]
    x, moved = kept["x"], jnp.zeros(rows.shape, bool)
    moe = [lp for lp, is_moe in _layers(params) if is_moe]
    for i, lp in enumerate(moe):
        hn = _rms_norm(x, lp["input_norm"]["weight"], eps)
        ent = tuple(a.at[rows].set(b) for a, b in zip(
            kept["entries"][i], entries(hn, rows, lp["attn"], cfg)))
        x = x + attend(hn, rows, *ent, lp["attn"], cfg, control)
        y, mv, _ = _mlp(x, lp, cfg, True, tilts[i])
        x, moved = x + y, moved | mv
    return _rms_norm(x, params["model"]["final_norm"]["weight"], eps), moved


def logits_at(params, ids, rows, cfg, control=None):
    """Reference logits [len(rows), vocab] of one sequence `ids` [s] at
    the positions `rows`; under `router_tie_logit`, a row's standing
    under the best of the choices its near ties allow (module docstring;
    families/kimi_k2.logits_at says how the passes are joined)."""
    with jax.default_matmul_precision("highest"):
        head = params["lm_head"].astype(F32)
        kept = {} if cfg.get("router_tie_logit") else None
        plain = hidden_states(params, ids, rows, cfg, control, kept) @ head
        if kept is None:
            return plain
        n = len(kept["entries"])
        # one float32 step under the plain pass's largest, so that a
        # row's argmax stays the plain forward's own
        under = jnp.nextafter(plain.max(-1, keepdims=True), -jnp.inf)

        def one(best, tilts):
            x, moved = rows_tilted(params, cfg, kept, rows, tilts, control)
            lg = x @ head
            standing = lg - lg.max(-1, keepdims=True) + under
            return jnp.maximum(best, jnp.where(moved[:, None], standing,
                                               plain)), None
        return jax.lax.scan(one, plain, jnp.concatenate(
            [jnp.eye(n, dtype=bool), jnp.ones((1, n), bool)]))[0]


# ---------------------------------------------------------------------------
# the counts
# ---------------------------------------------------------------------------

def _indexer_params(cfg):
    H, D = cfg["index_n_heads"], cfg["index_head_dim"]
    h = cfg["hidden_size"]
    return cfg["q_lora_rank"] * H * D + h * D + h * H


def counts(cfg: dict) -> dict:
    """families/kimi_k2.counts with the indexer's three products in every
    layer (and the two vectors of its LayerNorm among `total_params`)."""
    base = kimi_k2.counts(cfg)
    L = cfg["num_hidden_layers"]
    return dict(base,
                matmul_params=base["matmul_params"]
                + L * _indexer_params(cfg),
                total_params=base["total_params"] + L * (
                    _indexer_params(cfg) + 2 * cfg["index_head_dim"]))


grouped_matmul_cost = kimi_k2.grouped_matmul_cost


def latent_chunk_attn_cost(cfg: dict, window: dict,
                           elem_bytes: float = 2.0):
    """families/kimi_k2.latent_chunk_attn_cost: the blockwise kernel is
    handed every position a chunk's rows SEE (it runs under the mask of
    the selection: dense work), so its required work is counted by
    `serve.prefill_attended_keys` as for a layer that attends all of
    them."""
    return kimi_k2.latent_chunk_attn_cost(cfg, window, elem_bytes)


def indexer_score_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """Required operations and bytes of the indexer's scoring in the
    window's chunk launches (the scope `dsa_score` of the chunk program),
    all layers: 2 H D operations a (query, key) pair the causal mask lets
    through (`serve.prefill_attended_keys`, counted a launch and ONE
    layer); each index key the launch's rows see read once a launch
    (pairs / rows is the mean a query sees, at least half of what the
    launch's last query sees: families/longcat_flash.latent_chunk_attn_cost
    counts the latents so), a query's H D values and H weights read, its
    float32 scores written.  None where the program counted no chunk."""
    c = window["counters"]
    pairs = c.get("serve.prefill_attended_keys")
    rows, launches = c.get("serve.prefill_tokens"), c.get(
        "serve.prefill_chunks")
    if not pairs or not rows or not launches:
        return None
    L, H, D = (cfg["num_hidden_layers"], cfg["index_n_heads"],
               cfg["index_head_dim"])
    return {"ops": L * 2.0 * H * D * pairs,
            "bytes": L * (elem_bytes * (D * pairs / rows * launches
                                        + rows * H * D)
                          + 4.0 * (rows * H + pairs))}


def sparse_latent_attn_cost(cfg: dict, window: dict,
                            elem_bytes: float = 2.0):
    """Required operations and bytes of the decode steps' attention of
    the window, all layers, the scoring included (one scope holds what a
    decode step's attention does: `dsa_score`, `dsa_select`,
    `dsa_attend`): the context's index keys read once
    (`serve.decode_selectable_tokens`) and scored by every head of the
    indexer; the selected latents (`serve.decode_selected_tokens`) read
    once and attended in the absorbed form by every head (families/
    kimi_k2.paged_latent_attn_cost over the selection); both counters
    are summed over the layers that select.  None where the program
    counted no decode step."""
    c = window["counters"]
    ctx = c.get("serve.decode_selectable_tokens")
    sel = c.get("serve.decode_selected_tokens")
    queries = c.get("serve.decode_slot_steps")
    if not ctx or not sel or not queries:
        return None
    L, nh = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    H, D, r = cfg["index_n_heads"], cfg["index_head_dim"], cfg["kv_lora_rank"]
    latent = r + cfg["qk_rope_head_dim"]
    return {"ops": 2.0 * (H * D * ctx + nh * (latent + r) * sel),
            "bytes": elem_bytes * (D * ctx + latent * sel
                                   + L * queries * (H * D + nh * (latent
                                                                  + r)))}
