"""Analytic HBM-traffic model for the fused-kernel layer.

Every Pallas kernel in this package is meant to cut HBM round trips, not
FLOPs — so what it should save can be counted from shapes: the bytes each
path moves (the comm/wire.py pattern).  These are byte counts, not
timings; whether a kernel is faster on the chip is a measurement.

For each kernel this module prices two paths:

  * ``unfused``: the XLA op chain the dispatcher falls back to, counted
    op by op — each elementwise op reads its operands and writes its
    result to HBM, reductions read their operand and write the (small)
    reduced row.  Activations move at the compute dtype (`elem_bytes`);
    the seed norm/rotary implementations upcast to float32, so their
    intermediates move at 4 bytes — exactly what the fallback code does.
    XLA's fuser would collapse SOME of these round trips; the op-chain
    count is the reproducible upper bound the docs table and the
    `detail.kernels` BENCH record use, and the chain is listed per op so
    the model is auditable (docs/kernels.md).
  * ``fused``: the Pallas kernel — one read of each input, one write of
    each output, statistics live in VMEM.

`reduction` = unfused / fused is the headline byte cut per kernel
(`tools_bench_kernels.py` prints it; the acceptance gate pins
residual+RMSNorm >= 3x at the bench config's bf16 activations).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

#: bytes of a float32 intermediate (the upcast the seed fallbacks do)
_F32 = 4.0

Chain = List[Tuple[str, float, float]]     # (op, read_bytes, write_bytes)


def _report(kernel: str, chain: Chain, fused_read: float,
            fused_write: float) -> Dict[str, Any]:
    ur = sum(r for _, r, _ in chain)
    uw = sum(w for _, _, w in chain)
    fused = fused_read + fused_write
    unfused = ur + uw
    return {
        "kernel": kernel,
        "unfused_bytes": unfused,
        "unfused_read_bytes": ur,
        "unfused_write_bytes": uw,
        "fused_bytes": fused,
        "fused_read_bytes": fused_read,
        "fused_write_bytes": fused_write,
        "reduction": unfused / fused if fused else float("inf"),
        "chain": [{"op": op, "read": r, "write": w}
                  for op, r, w in chain],
    }


def norm_traffic(tokens: int, hidden: int, *, elem_bytes: float = 2.0,
                 kind: str = "rms") -> Dict[str, Any]:
    """Fused residual-add + RMSNorm/LayerNorm vs the seed chain
    (`x + h` -> `ops.norms.rms_norm`): the fallback adds in the compute
    dtype, then upcasts and runs the stats/normalize/weight chain in
    float32 (ops/norms.py)."""
    n = float(tokens) * hidden
    e = float(elem_bytes)
    t = float(tokens) * _F32               # one f32 scalar per row
    chain: Chain = [
        ("residual_add", 2 * e * n, e * n),
        ("upcast_f32", e * n, _F32 * n) if e != _F32 else
        ("upcast_f32", 0.0, 0.0),
    ]
    if kind == "ln":
        chain += [("mean_reduce", _F32 * n, t),
                  ("center", _F32 * n + t, _F32 * n)]
    chain += [
        ("square", _F32 * n, _F32 * n),
        ("var_reduce", _F32 * n, t),
        ("rsqrt_scale", _F32 * n + t, _F32 * n),
        ("weight_mul", _F32 * n + _F32 * hidden, _F32 * n),
    ]
    if kind == "ln":
        chain.append(("bias_add", _F32 * n + _F32 * hidden, _F32 * n))
    chain.append(("downcast", _F32 * n, e * n) if e != _F32 else
                 ("downcast", 0.0, 0.0))
    # fused: read x and h once, write y AND the residual stream s once
    return _report(f"norm[{kind}]" if kind != "rms" else "norm",
                   chain, 2 * e * n, 2 * e * n)


def swiglu_traffic(tokens: int, inner: int, *,
                   elem_bytes: float = 2.0) -> Dict[str, Any]:
    """silu(gate) * up: the fallback chain stays in the compute dtype
    (ops.activations.silu is jax.nn.silu on the input dtype)."""
    n = float(tokens) * inner
    e = float(elem_bytes)
    chain: Chain = [
        ("sigmoid", e * n, e * n),
        ("gate_mul", 2 * e * n, e * n),
        ("up_mul", 2 * e * n, e * n),
    ]
    return _report("swiglu", chain, 2 * e * n, e * n)


def rotary_traffic(batch: int, seq: int, q_heads: int, kv_heads: int,
                   head_dim: int, *, elem_bytes: float = 2.0
                   ) -> Dict[str, Any]:
    """RoPE on q AND k: the fallback is two `ops.rotary.apply_rotary`
    calls, each upcasting to f32, forming the four half-products, the
    two sub/adds, the concat, and the downcast — and each gathering the
    cos/sin tables separately."""
    e = float(elem_bytes)
    tables = 2.0 * batch * seq * (head_dim // 2) * _F32     # cos + sin

    def one_call(heads: int) -> Chain:
        n = float(batch) * seq * heads * head_dim
        return [
            ("upcast_f32", e * n, _F32 * n),
            ("half_products", 2 * _F32 * n + tables, 2 * _F32 * n),
            ("sub_add", 2 * _F32 * n, _F32 * n),
            ("concat", _F32 * n, _F32 * n),
            ("downcast", _F32 * n, e * n),
        ]

    chain = ([("q_" + op, r, w) for op, r, w in one_call(q_heads)]
             + [("k_" + op, r, w) for op, r, w in one_call(kv_heads)])
    nq = float(batch) * seq * q_heads * head_dim
    nk = float(batch) * seq * kv_heads * head_dim
    # fused: q + k + the tables read once, q + k written once
    return _report("rotary", chain,
                   e * (nq + nk) + tables, e * (nq + nk))


def quant_traffic(n: int, block_size: int, *, bits: int = 8
                  ) -> Dict[str, Any]:
    """Blockwise quantize feeding the compressed collectives: the
    fallback chain is abs -> blockmax -> div -> round -> clip -> cast
    over the f32 flat buffer (comm/compress.quantize_blockwise)."""
    nf = float(n)
    scales = nf / block_size * _F32
    chain: Chain = [
        ("abs", _F32 * nf, _F32 * nf),
        ("blockmax_reduce", _F32 * nf, scales),
        ("div", _F32 * nf + scales, _F32 * nf),
        ("round", _F32 * nf, _F32 * nf),
        ("clip", _F32 * nf, _F32 * nf),
        ("cast_int8", _F32 * nf, 1.0 * nf),
    ]
    return _report("quant", chain, _F32 * nf, 1.0 * nf + scales)


def flash_traffic(batch: int, seq: int, heads: int, head_dim: int, *,
                  elem_bytes: float = 2.0) -> Dict[str, Any]:
    """Flash attention vs the dense composition: the dense path
    materializes the [b, h, s, s] score matrix in f32 twice (scores,
    softmax) and reads it back for the p@v contraction."""
    e = float(elem_bytes)
    s2 = float(batch) * heads * seq * seq
    io = float(batch) * seq * heads * head_dim
    chain: Chain = [
        ("qk_scores", 2 * e * io, _F32 * s2),
        ("softmax", 2 * _F32 * s2, _F32 * s2),     # max/denom + normalize
        ("pv", _F32 * s2 + e * io, e * io),
    ]
    # fused: q, k, v read once; out + the per-row lse written
    lse = float(batch) * heads * seq * _F32
    return _report("flash", chain, 3 * e * io, e * io + lse)


def paged_attn_traffic(slots: int, max_pages: int, page_size: int,
                       kv_heads: int, head_dim: int, *,
                       elem_bytes: float = 4.0,
                       quant: str = "none") -> Dict[str, Any]:
    """Paged decode vs the gather path: the fallback gathers every
    slot's pages into a dense [S, max_len] view (read pool, write
    dense) and the attention reads the dense view back — three passes
    over the cache bytes.  The kernel copies each LIVE page once and no
    page slot past a slot's length (ops/pallas/paged_attention: the
    walk's trip count is the slot's own), so its read follows the
    tokens the slots hold; this function prices the WHOLE table on both
    sides, `max_pages` a slot — the kernel's bound with every slot at
    `max_len`, which is where the ratio of the two is taken.

    ``quant="int8"`` prices the int8-page mode (serving/kv_pool.py:
    1 byte/elem + one f32 scale per head-vector): the kernel's read is
    the quantized payload, while the gather fallback additionally
    materializes the DEQUANTIZED dense view at the compute width — the
    in-kernel dequantize earns its keep on top of the payload cut.
    ``quant="int4"`` halves the payload again (two values per byte,
    same per-head-vector f32 scale)."""
    elems = 2.0 * slots * max_pages * page_size * kv_heads * head_dim
    e = float(elem_bytes)
    if quant in ("int8", "int4"):
        cache_q = elems * _kv_payload_bytes(quant, head_dim)
        chain: Chain = [
            ("gather_pages", cache_q, elems * e),   # dequantized dense
            ("attend_dense", elems * e, 0.0),
        ]
        return _report(f"paged_attn_{quant}", chain, cache_q, 0.0)
    cache = elems * e
    chain = [
        ("gather_pages", cache, cache),
        ("attend_dense", cache, 0.0),
    ]
    return _report("paged_attn", chain, cache, 0.0)


def _kv_payload_bytes(quant: str, head_dim: int) -> float:
    """Quantized-page bytes per cache ELEMENT (payload + the f32
    per-head-vector scale amortized over head_dim) — mirrors
    serving/kv_pool.kv_bytes_per_token."""
    payload = 0.5 if quant == "int4" else 1.0
    return payload + _F32 / head_dim


def paged_verify_traffic(slots: int, k: int, max_pages: int,
                         page_size: int, kv_heads: int, head_dim: int, *,
                         elem_bytes: float = 4.0,
                         quant: str = "none") -> Dict[str, Any]:
    """Multi-query verify decode (ops/pallas/paged_attention.paged_verify)
    vs the gather path: the fallback gathers every slot's pages into a
    dense [S, max_len] view and attends the k+1 query positions against
    it — the SAME three passes over the cache bytes as single-query
    decode (the dense view doesn't get cheaper because more queries read
    it).  The kernel DMAs each scheduled page once and shares it across
    all k+1 query positions in VMEM, so its cache read is IDENTICAL to
    the single-token kernel's: the verify step's extra queries ride
    free.  Quantized pages ("int8"/"int4") keep the payload cut on top;
    the gather fallback still materializes the dequantized dense view at
    the compute width."""
    if k < 1:
        raise ValueError(f"spec_k must be >= 1, got {k}")
    elems = 2.0 * slots * max_pages * page_size * kv_heads * head_dim
    e = float(elem_bytes)
    # the k+1 query/output vectors are noise next to the cache bytes but
    # the model counts them (auditable, not rounded away)
    qio = float(slots) * (k + 1) * kv_heads * head_dim * e
    if quant in ("int8", "int4"):
        cache_q = elems * _kv_payload_bytes(quant, head_dim)
        chain: Chain = [
            ("gather_pages", cache_q, elems * e),
            ("attend_dense", elems * e + qio, qio),
        ]
        return _report(f"paged_verify_{quant}", chain, cache_q + qio, qio)
    cache = elems * e
    chain = [
        ("gather_pages", cache, cache),
        ("attend_dense", cache + qio, qio),
    ]
    return _report("paged_verify", chain, cache + qio, qio)


def sample_traffic(rows: int, hidden: int, vocab: int, *,
                   elem_bytes: float = 2.0) -> Dict[str, Any]:
    """Fused sampling epilogue (ops/pallas/sample.py) vs the unfused
    verify tail: lm_head matmul materializing the [rows, vocab] f32
    logit grid in HBM, then the filter chain over it (temperature scale,
    the top-k/top-p sort + masks of serving/sampling.filtered_logits),
    the Gumbel add and the argmax.  The kernel streams vocab tiles
    through VMEM — hidden and the lm_head weight are read once, only
    the [rows] token ids ever hit HBM."""
    e = float(elem_bytes)
    nv = float(rows) * vocab
    h_in = float(rows) * hidden * e
    w = float(hidden) * vocab * e
    toks = float(rows) * _F32
    chain: Chain = [
        ("lm_head_matmul", h_in + w, _F32 * nv),
        ("temp_scale", _F32 * nv, _F32 * nv),
        ("topk_sort", _F32 * nv, _F32 * nv),
        ("topk_mask", 2 * _F32 * nv, _F32 * nv),
        ("softmax_cumsum", _F32 * nv, _F32 * nv),
        ("topp_mask", 2 * _F32 * nv, _F32 * nv),
        ("gumbel_add", _F32 * nv, _F32 * nv),
        ("argmax", _F32 * nv, toks),
    ]
    return _report("sample", chain, h_in + w, toks)


def fused_verify_chain(slots: int, k: int, max_pages: int, page_size: int,
                       kv_heads: int, head_dim: int, hidden: int,
                       vocab: int, *, num_layers: int = 1,
                       elem_bytes: float = 2.0,
                       quant: str = "int8") -> Dict[str, Any]:
    """The WHOLE fused verify step vs the gather path: per layer the
    multi-query cache read (paged_verify vs gather+dense attend), plus
    ONE sampling epilogue over the [slots*(k+1)] verify rows (fused
    in-VMEM sample vs HBM logits + filter chain).  This is the number
    the acceptance gate pins: >= 2x fewer HBM bytes than the gather
    path at k=4 (docs/kernels.md)."""
    pv = paged_verify_traffic(slots, k, max_pages, page_size, kv_heads,
                              head_dim, elem_bytes=elem_bytes, quant=quant)
    sm = sample_traffic(slots * (k + 1), hidden, vocab,
                        elem_bytes=elem_bytes)
    gather = pv["unfused_bytes"] * num_layers + sm["unfused_bytes"]
    fused = pv["fused_bytes"] * num_layers + sm["fused_bytes"]
    return {
        "kernel": "fused_verify_chain",
        "k": k, "slots": slots, "num_layers": num_layers, "quant": quant,
        "gather_bytes": gather,
        "fused_bytes": fused,
        "reduction": gather / fused if fused else float("inf"),
        "paged_verify": {kk: pv[kk] for kk in
                         ("unfused_bytes", "fused_bytes", "reduction")},
        "sample": {kk: sm[kk] for kk in
                   ("unfused_bytes", "fused_bytes", "reduction")},
    }


# ---------------------------------------------------------------------------
# model-level assembly (bench.py detail.kernels / tools_bench_kernels.py)
# ---------------------------------------------------------------------------

def kernel_traffic_report(*, batch: int, seq: int, hidden: int,
                          intermediate: int, num_layers: int,
                          q_heads: int, kv_heads: int, head_dim: int,
                          elem_bytes: float = 2.0,
                          norm_kind: str = "rms",
                          quant_elems: Optional[int] = None,
                          quant_block: int = 1024,
                          serve_slots: int = 8, serve_pages: int = 16,
                          serve_page_size: int = 16, spec_k: int = 4,
                          vocab: Optional[int] = None
                          ) -> Dict[str, Dict[str, Any]]:
    """Per-kernel fused-vs-unfused bytes for ONE forward pass of a
    transformer stack shaped like the arguments (per-step: every count
    multiplied by num_layers where the kernel runs per layer).  The
    quant entry prices one gradient-sync quantize over `quant_elems`
    (default: a [hidden, intermediate] matmul's worth per layer)."""
    tokens = batch * seq
    per_layer = {
        "norm": norm_traffic(tokens, hidden, elem_bytes=elem_bytes,
                             kind=norm_kind),
        "swiglu": swiglu_traffic(tokens, intermediate,
                                 elem_bytes=elem_bytes),
        "rotary": rotary_traffic(batch, seq, q_heads, kv_heads, head_dim,
                                 elem_bytes=elem_bytes),
        "flash": flash_traffic(batch, seq, q_heads, head_dim,
                               elem_bytes=elem_bytes),
    }
    out: Dict[str, Dict[str, Any]] = {}
    for name, rec in per_layer.items():
        scaled = dict(rec)
        # two residual+norm pairs per pre-norm block
        mult = num_layers * (2 if name == "norm" else 1)
        for k in ("unfused_bytes", "unfused_read_bytes",
                  "unfused_write_bytes", "fused_bytes",
                  "fused_read_bytes", "fused_write_bytes"):
            scaled[k] = rec[k] * mult
        scaled["per_step_multiplier"] = mult
        scaled.pop("chain", None)          # the CLI prints it on demand
        out[name] = scaled
    qn = quant_elems if quant_elems is not None else \
        num_layers * hidden * intermediate
    q = quant_traffic(qn, quant_block)
    q.pop("chain", None)
    q["per_step_multiplier"] = 1
    out["quant"] = q
    for quant in ("none", "int8", "int4"):
        p = paged_attn_traffic(serve_slots, serve_pages, serve_page_size,
                               kv_heads, head_dim, elem_bytes=elem_bytes,
                               quant=quant)
        for k in ("unfused_bytes", "unfused_read_bytes",
                  "unfused_write_bytes", "fused_bytes",
                  "fused_read_bytes", "fused_write_bytes"):
            p[k] = p[k] * num_layers
        p["per_step_multiplier"] = num_layers
        p.pop("chain", None)
        out[p["kernel"]] = p
    # the fused verify-and-sample decode path (spec decode at spec_k)
    pv = paged_verify_traffic(serve_slots, spec_k, serve_pages,
                              serve_page_size, kv_heads, head_dim,
                              elem_bytes=elem_bytes, quant="int8")
    for k in ("unfused_bytes", "unfused_read_bytes",
              "unfused_write_bytes", "fused_bytes",
              "fused_read_bytes", "fused_write_bytes"):
        pv[k] = pv[k] * num_layers
    pv["per_step_multiplier"] = num_layers
    pv.pop("chain", None)
    out["paged_verify"] = pv
    v = vocab if vocab is not None else 32 * hidden
    sm = sample_traffic(serve_slots * (spec_k + 1), hidden, v,
                        elem_bytes=elem_bytes)
    sm["per_step_multiplier"] = 1
    sm.pop("chain", None)
    out["sample"] = sm
    return out


def report_for_config(cfg, *, batch: int, seq: int,
                      elem_bytes: Optional[float] = None
                      ) -> Dict[str, Dict[str, Any]]:
    """`kernel_traffic_report` from a LLaMA/GPT-style config object."""
    if elem_bytes is None:
        import jax.numpy as jnp
        elem_bytes = float(jnp.dtype(cfg.compute_dtype).itemsize)
    kv = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
    kind = "rms" if hasattr(cfg, "rms_norm_eps") else "ln"
    return kernel_traffic_report(
        batch=batch, seq=seq, hidden=cfg.hidden_size,
        intermediate=cfg.intermediate_size,
        num_layers=cfg.num_hidden_layers,
        q_heads=cfg.num_attention_heads, kv_heads=kv,
        head_dim=cfg.head_dim, elem_bytes=elem_bytes, norm_kind=kind,
        vocab=cfg.vocab_size)
