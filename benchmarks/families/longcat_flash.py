"""The LongCat-Flash family: what `hetu_tpu/models/longcat_flash`
implements and
https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/main/config.json
describes, under its published key names.

The plain reference (`logits_at`) is float32 `jax.numpy` under
`default_matmul_precision("highest")`, reading the program's parameter
tree and nothing else of the program: no kernel, no cache, no batching,
no absorption, one sequence at a time, one matrix cast to float32 at a
time (a held layer in float32 is 5 GB and does not fit beside the
program's weights).  One PUBLISHED layer, h a token's hidden state, all
norms RMSNorm (eps 1e-5):

    for i in (0, 1):
        h = h + MLA_i(norm_in_i(h))
        u = norm_post_i(h)
        if i == 0:  s = MoE(u)                  # the shortcut branch
        h = h + SwiGLU_i(u)                     # width ffn_hidden_size
    h = h + s

* MLA_i, EXPANDED: c_q = RMSNorm(x W_qa); [q_nope | q_rope] = (c_q W_qb)
  * a_q per head, a_q = sqrt(hidden_size / q_lora_rank)
  (`mla_scale_q_lora`); [c_kv | k_rope] = x W_kva; c = RMSNorm(c_kv) *
  a_kv, a_kv = sqrt(hidden_size / kv_lora_rank) (`mla_scale_kv_lora`);
  [k_nope | v] = c W_kvb per head; k_rope rotated once for all heads
  (theta 1e7, no scaling); causal softmax of [q_nope | RoPE(q_rope)] .
  [k_nope | k_rope] * (qk_nope_head_dim + qk_rope_head_dim)^-1/2, times
  v, through W_o; in blocks of `Q_BLOCK` query rows.
* MoE(u): p = softmax(u W_r) over `router_experts` + `zero_expert_num`
  outputs (512 routed, then 256 identity) in float32; the `moe_topk`
  chosen are the largest of p + b (`e_score_correction_bias`); weights
  w_j = `routed_scaling_factor` * p_j, not renormalised; MoE(u) = sum
  over the chosen routed j of w_j SwiGLU_j(u) + (sum over the chosen
  identity j of w_j) * u.  The configuration gives the share: the
  weights hold routed experts `first_expert` .. + held - 1, a loop walks
  them, and routed experts not held add nothing; the identity addend is
  whole (a token's is computed where the token lives): that partial
  result goes on.

No near-tie passes (families/kimi_k2's `router_tie_logit` is 0 here):
272 of the router's 768 outputs are computed here (the held and the
identity experts), and one of them stands near the edge of a token's
chosen 12 in most tokens of every layer, but the two outputs at such a
tie carry nearly the same small weight (6 x ~0.011), so a choice that
bfloat16 turns moves the layer's output by little: read on the chip with
ten passes and without, no served token needed one (the cell's file:
`assumed.router_tie_logit`).

Departures from the published code: rotation is written half-split where
the published code interleaves q_rope and k_rope (a fixed permutation of
weight columns, nothing with random weights); routed experts not held
are left out (the share, above); the head is over the vocabulary's slice.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.families.bailing_hybrid import _rope  # half-split, plain
from benchmarks.families.kimi_k2 import _rms_norm, _swiglu
from benchmarks.families.llama import serve_config  # noqa: F401
# at import, not in `build_model`: a program without the family (the
# parent of PR 51) then fails in `run.load_cell`, at once, with exit 2
from hetu_tpu.models.longcat_flash import (LongCatFlashConfig,
                                           LongCatFlashLMHeadModel)

F32 = jnp.float32
Q_BLOCK = 256
#: what `logits_at(control=)` may do wrongly, one thing each (the tests'
#: and the chip's controls)
CONTROLS = ("zero_identity", "routed_only", "branch_from_second",
            "no_mla_scales")

#: the configuration file's keys that `LongCatFlashConfig` takes as they
#: are
PUBLISHED = ("attention_bias", "vocab_size", "hidden_size",
             "ffn_hidden_size", "expert_ffn_hidden_size", "num_layers",
             "num_attention_heads", "kv_lora_rank", "q_lora_rank",
             "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim",
             "mla_scale_q_lora", "mla_scale_kv_lora",
             "routed_scaling_factor", "max_position_embeddings",
             "rms_norm_eps", "rope_theta", "attention_method",
             "zero_expert_num", "zero_expert_type", "moe_topk")


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def build_model(config: dict, how: dict, strategy=None):
    """The program's model.  `n_routed_experts` of the file is how many
    routed experts are HELD here (`reduced`); the router keeps the
    published width, `router_experts` + `zero_expert_num`."""
    dtype = jnp.dtype(how.get("param_dtype", "bfloat16"))
    cfg = LongCatFlashConfig(
        n_routed_experts=config.get("router_experts",
                                    config["n_routed_experts"]),
        experts_held=config["n_routed_experts"],
        first_expert=config.get("first_expert", 0),
        param_dtype=dtype, compute_dtype=dtype,
        initializer_range=config.get("initializer_range", 0.02),
        correction_bias_range=config.get("correction_bias_std", 0.002),
        **{k: config[k] for k in PUBLISHED})
    return LongCatFlashLMHeadModel(cfg, strategy)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _routed(cfg) -> int:
    """The router's routed outputs; the identity outputs follow them."""
    return cfg.get("router_experts", cfg["n_routed_experts"])


def _mla_scales(cfg, control=None):
    """(a_q, a_kv) of the module docstring."""
    if control == "no_mla_scales":
        return 1.0, 1.0
    h = cfg["hidden_size"]
    return (math.sqrt(h / cfg["q_lora_rank"])
            if cfg["mla_scale_q_lora"] else 1.0,
            math.sqrt(h / cfg["kv_lora_rank"])
            if cfg["mla_scale_kv_lora"] else 1.0)


def _keys_values(h, ap, cfg, control=None):
    """Expanded keys [s, heads, nope + rope] and values [s, heads, v] of
    one sequence h [s, hidden] (normed), positions 0..s-1."""
    r, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    s, nh = h.shape[0], cfg["num_attention_heads"]
    ckv = h @ ap["wkv_a"].astype(F32)
    c = _rms_norm(ckv[:, :r], ap["kv_norm"]["weight"], cfg["rms_norm_eps"]) \
        * _mla_scales(cfg, control)[1]
    kv = jnp.einsum("sr,rnd->snd", c, ap["wkv_b"].astype(F32))
    k_rope = _rope(ckv[:, None, r:], jnp.arange(s), cfg)   # one for all heads
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (s, nh, k_rope.shape[-1]))],
                        axis=-1)
    return k, kv[..., dn:]


def _mla(h, ap, cfg, control=None):
    """Expanded latent attention of one sequence h [s, hidden] (normed),
    causal, through W_o; in blocks of `Q_BLOCK` query rows."""
    dn, nh = cfg["qk_nope_head_dim"], cfg["num_attention_heads"]
    s = h.shape[0]
    pos = jnp.arange(s)
    k, v = _keys_values(h, ap, cfg, control)
    cq = _rms_norm(h @ ap["wq_a"].astype(F32), ap["q_norm"]["weight"],
                   cfg["rms_norm_eps"])
    q = (cq @ ap["wq_b"].astype(F32)).reshape(s, nh, -1) \
        * _mla_scales(cfg, control)[0]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, cfg)], axis=-1)
    scale = q.shape[-1] ** -0.5
    qb = math.gcd(s, Q_BLOCK)

    def rows(q_blk_and_pos):
        q_blk, at = q_blk_and_pos
        sc = jnp.einsum("qnd,knd->nqk", q_blk, k) * scale
        seen = pos[None, :] <= at[:, None]                      # [qb, s]
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("nqk,knd->qnd", p, v)
    out = jax.lax.map(rows, (q.reshape(s // qb, qb, nh, -1),
                             pos.reshape(s // qb, qb)))
    return out.reshape(s, -1) @ ap["wo"].astype(F32)


def gate(x, mp, cfg, control=None):
    """(output ids [s, k], weights [s, k]) of the published gate."""
    logits = x @ mp["w_gate"].astype(F32)
    bias = mp["e_score_correction_bias"].astype(F32)
    if control == "routed_only":        # the identity outputs: not there
        logits, bias = logits[:, :_routed(cfg)], bias[:_routed(cfg)]
    scores = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(scores + bias, cfg["moe_topk"])
    return idx, jnp.take_along_axis(scores, idx, axis=-1) \
        * cfg["routed_scaling_factor"]


def experts(x, mp, cfg, control=None):
    """The expert layer on x [s, hidden]: a loop over the routed experts
    held (`first_expert` .. + held - 1), each applied to every token and
    weighted by the gate's weight for it there (0 where it was not
    chosen), plus the identity addend."""
    idx, w = gate(x, mp, cfg, control)
    first = cfg.get("first_expert", 0)
    held = mp["w_gate_up"].shape[0]

    def one(acc, xs):
        w_gate_up, w_down, e = xs
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        return acc + w_e[:, None] * _swiglu(x, w_gate_up, w_down), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (mp["w_gate_up"], mp["w_down"], jnp.arange(held)))
    if control != "zero_identity":
        y = y + jnp.sum(jnp.where(idx >= _routed(cfg), w, 0.0),
                        axis=-1)[:, None] * x
    return y


def _layer(x, lp, cfg, control=None):
    """One PUBLISHED layer (the module docstring's loop) on one sequence
    x [s, hidden]."""
    eps = cfg["rms_norm_eps"]
    first = lp["sub_0"]
    for sp in (first, lp["sub_1"]):
        x = x + _mla(_rms_norm(x, sp["input_norm"]["weight"], eps),
                     sp["attn"], cfg, control)
        u = _rms_norm(x, sp["post_norm"]["weight"], eps)
        if (sp is first) != (control == "branch_from_second"):
            branch = experts(u, first["mlp"]["experts"], cfg, control)
        d = sp["mlp"]["dense"] if sp is first else sp["mlp"]
        x = x + _swiglu(u, d["w_gate_up"], d["w_down"])
    return x + branch


def logits_at(params, ids, rows, cfg, control=None):
    """Reference logits [len(rows), vocab] of one sequence `ids` [s] at
    the positions `rows`: the layers one after the other, the final norm,
    the head.  `control` (tests and the chip's control runs only; one of
    `CONTROLS`): the forward with that ONE thing done wrongly, which the
    comparison has to tell from the program."""
    with jax.default_matmul_precision("highest"):
        m = params["model"]
        x = m["embed"]["weight"][ids].astype(F32)
        for l in range(len(m["layers"])):
            x = _layer(x, m["layers"][f"layer_{l}"], cfg, control)
        x = _rms_norm(x, m["final_norm"]["weight"], cfg["rms_norm_eps"])
        return x[rows] @ params["lm_head"].astype(F32)


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
    """`matmul_params`: the weights ONE token multiplies HERE, a published
    layer: both MLAs, both dense FFNs, the router, and of the routed
    experts the share of a token's `moe_topk` that falls on the experts
    held (x held / router outputs of one expert each; an identity expert
    multiplies nothing); the sliced head.  `attn_width`: over the 2 x
    `num_layers` cache layers.  `total_params`: everything held, as
    `model.num_params` counts it (the router's weights and bias at their
    published width of `router_experts` + `zero_expert_num`)."""
    h, v, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_layers"]
    held = cfg["n_routed_experts"]
    outputs = _routed(cfg) + cfg["zero_expert_num"]
    expert = 3 * h * cfg["expert_ffn_hidden_size"]
    dense = 3 * h * cfg["ffn_hidden_size"]
    mla, norms = _mla_params(cfg), (
        2 * h + cfg["q_lora_rank"] + cfg["kv_lora_rank"])
    return {
        "matmul_params": L * (2 * mla + 2 * dense + h * outputs
                              + cfg["moe_topk"] * held / outputs * expert)
        + h * v,
        "attn_width": 2 * L * cfg["num_attention_heads"]
        * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]),
        "total_params": L * (2 * (mla + norms + dense) + h * outputs
                             + outputs + held * expert) + 2 * h * v + h}


def paged_latent_attn_cost(cfg: dict, window: dict,
                           elem_bytes: float = 2.0):
    """families/kimi_k2.paged_latent_attn_cost over this family's
    2 x `num_layers` cache layers: every cached latent (`kv_lora_rank` +
    `qk_rope_head_dim` values: the lanes it is padded to are not required
    work) read ONCE a cache layer, the absorbed query read and the latent
    output written a head.  None where the program counted no decode
    step."""
    context_tokens = window["counters"].get("serve.decode_context_tokens")
    queries = window["counters"].get("serve.decode_slot_steps")
    if not context_tokens or not queries:
        return None
    L, nh = 2 * cfg["num_layers"], cfg["num_attention_heads"]
    r = cfg["kv_lora_rank"]
    latent = r + cfg["qk_rope_head_dim"]
    return {"ops": L * 2.0 * nh * (latent + r) * context_tokens,
            "bytes": L * elem_bytes * (latent * context_tokens
                                       + queries * nh * (latent + r))}


def latent_chunk_attn_cost(cfg: dict, window: dict,
                           elem_bytes: float = 2.0):
    """Required operations and bytes of the chunk program's attention
    over a latent cache (ops/pallas/latent_chunk_attention), all
    2 x `num_layers` cache layers: every (query, key) pair the causal
    mask lets through (`serve.prefill_attended_keys`, counted per chunk
    launch and ONE layer) is a q . k of `qk_nope_head_dim` +
    `qk_rope_head_dim` and a p . v of `v_head_dim` for each head, in the
    EXPANDED form (the absorbed form would cost 3.4 times that; making a
    head's k_nope | v from a block's latents, which the kernel does once
    a launch, is not counted as required: a cache of expanded keys would
    not need it).  The bytes are the chunk's own q and o once a layer and
    the latents (the model's 576 values, not the 640 lanes) of the
    positions its queries see.  None where the program counted no
    chunk."""
    c = window["counters"]
    pairs = c.get("serve.prefill_attended_keys")
    rows, launches = c.get("serve.prefill_tokens"), c.get(
        "serve.prefill_chunks")
    if not pairs or not rows or not launches:
        return None
    L, nh = 2 * cfg["num_layers"], cfg["num_attention_heads"]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    # a launch's queries see, together, the keys its LAST query sees:
    # pairs / rows is the mean over queries, at least half of that
    seen = pairs / rows * launches
    return {"ops": L * 2.0 * nh * (dq + dv) * pairs,
            "bytes": elem_bytes * L * (rows * nh * (dq + dv)
                                       + seen * latent)}


def grouped_matmul_cost(cfg: dict, window: dict, elem_bytes: float = 2.0):
    """families/kimi_k2.grouped_matmul_cost at this family's expert width
    (`expert_ffn_hidden_size`): each held expert that has a token in an
    execution (`serve.moe_expert_hits`) has its weights read once there;
    every pair on a held expert (`serve.moe_local_assignments`)
    multiplies one expert's weights, reads its input row and writes its
    output row.  A pair on an identity expert is in neither count.  None
    where the program counted no expert layer."""
    hits = window["counters"].get("serve.moe_expert_hits")
    pairs = window["counters"].get("serve.moe_local_assignments")
    if not hits or not pairs:
        return None
    h, i = cfg["hidden_size"], cfg["expert_ffn_hidden_size"]
    return {"ops": 2.0 * pairs * 3 * h * i,
            "bytes": elem_bytes * (hits * 3 * h * i
                                   + pairs * (2 * h + 3 * i))}
