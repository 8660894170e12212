"""The yardstick's constants and counts: device peaks, and the operations
and bytes that the benchmark's utilization and roofline shares divide by.

Nothing here is measured.  A later PR may not edit this file, so the
numerators of `model_flops_util`, `flash_attn_roofline` and
`chat.paged_attn_roofline` cannot move with the code they judge.

The byte counts follow `hetu_tpu/ops/pallas/traffic.py`'s *fused* path (one
read of each input, one write of each output); its `paged_attn_traffic`
prices the whole page table, this file prices the tokens a step's slots
really hold, which is what the kernel has to read.
"""
from __future__ import annotations

#: peaks of ONE chip, keyed by `jax.devices()[0].device_kind`.  A kind that
#: is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.py; "
            f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix multiplication per token: the
    attention projections, the SwiGLU MLP and the untied head.  The
    embedding is a lookup and the norm gains are elementwise."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    per_layer = h * (q + 2 * kv) + q * h + 3 * h * i
    return cfg["num_hidden_layers"] * per_layer + h * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    return (matmul_params(cfg) + cfg["vocab_size"] * h
            + (2 * cfg["num_hidden_layers"] + 1) * h)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Operations the forward and backward passes REQUIRE per trained
    token: 6 per matmul weight, plus causal attention (QK^T and PV see on
    average seq/2 keys: 2 matmuls x 2 x seq/2 x q-width forward, twice that
    backward).  Recomputation under remat and the flash kernel's own
    recomputed scores are not counted."""
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    attn = 6.0 * cfg["num_hidden_layers"] * seq * q
    return 6.0 * matmul_params(cfg) + attn


def flash_attn_cost(cfg: dict, *, batch: int, seq: int, shards: int = 1,
                    elem_bytes: float = 2.0) -> dict:
    """Required operations and bytes of causal flash attention, forward
    and backward, for ONE train step on ONE chip (`shards` chips share the
    batch x heads evenly).  Forward: 2 matmuls over the causal half.
    Backward: 4 (dV, dP, dQ, dK); the kernel's recomputed QK^T is not
    required work.  Bytes: forward reads q,k,v and writes o and the f32
    row statistics; backward reads q,k,v,o,do and the statistics and
    writes dq,dk,dv."""
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    nq, nkv, L = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["num_hidden_layers"])
    matmul = 2.0 * batch * nq * seq * seq * hd / 2.0      # one, causal
    ops = L * 6.0 * matmul
    q_io = elem_bytes * batch * seq * nq * hd
    kv_io = elem_bytes * batch * seq * nkv * hd
    lse = 4.0 * batch * nq * seq
    fwd = q_io + 2 * kv_io + q_io + lse
    bwd = (3 * q_io + 2 * kv_io + lse) + (q_io + 2 * kv_io)
    return {"ops": ops / shards, "bytes": L * (fwd + bwd) / shards}


def paged_attn_cost(cfg: dict, *, context_tokens: int, queries: int,
                    elem_bytes: float = 2.0) -> dict:
    """Required operations and bytes of paged decode attention over all
    layers, for `queries` single-token queries whose contexts hold
    `context_tokens` cached positions in total: every cached K and V
    vector is read once, q is read and o written."""
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    nq, nkv, L = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["num_hidden_layers"])
    ops = L * 2.0 * 2.0 * context_tokens * nq * hd
    bytes_ = L * elem_bytes * (2.0 * context_tokens * nkv * hd
                               + 2.0 * queries * nq * hd)
    return {"ops": ops, "bytes": bytes_}


COST_FUNCTIONS = {"flash_attn_cost": flash_attn_cost,
                  "paged_attn_cost": paged_attn_cost}


def roofline_seconds(cost: dict, peaks: dict) -> dict:
    """The least time one chip could take, and which peak sets it."""
    t_ops = cost["ops"] / peaks["flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_bytes),
            "bound": "compute" if t_ops >= t_bytes else "memory"}
