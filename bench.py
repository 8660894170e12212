"""Benchmark: LLaMA training throughput on the available TPU chip(s).

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.
Needs a TPU whose `device_kind` has a preset in search/profiler.py: without
one it prints no metric and exits non-zero.

Metric: model FLOPs utilization (MFU) of a bf16 LLaMA training step at the
largest config that fits the chip.  vs_baseline is measured MFU / 0.45 — the
45%-MFU-on-v5p target recorded in BASELINE.md (the reference repo publishes no
absolute numbers, BASELINE.md "Published numbers: None").
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def _bench_config():
    """The single-chip v5e bench config (the measured ladder's winner) —
    shared by the measured path and the hardware-free estimate."""
    from hetu_tpu.models.llama import LlamaConfig
    return LlamaConfig(
        vocab_size=32000, hidden_size=1536, intermediate_size=4096,
        num_hidden_layers=12, num_attention_heads=12,
        num_key_value_heads=12, max_position_embeddings=2048,
        remat=True, remat_policy="dots_attn", use_scan=False)


def _hardware_free_estimate(batch: int = 8, seq: int = 2048):
    """Estimated MFU for the v5e bench config with NO device contact
    (hetu_tpu.obs.mfu roofline over analytic FLOPs + the recorded
    hardware profile).  A model evaluated from counts: it is written
    as `estimated_mfu`, never as a measured MFU."""
    from hetu_tpu.obs.mfu import analytic_transformer_estimate
    rep = analytic_transformer_estimate(_bench_config(), batch, seq)
    return {k: rep[k] for k in ("estimated_mfu", "estimated_step_s",
                                "flops_per_step", "bound", "chip")
            if k in rep}


def _hardware_free_comm(dp: int = 8):
    """DP grad-sync bytes-on-wire for the bench config at dp=8, fp32 vs
    int8 (comm/wire.py analytic model + the recorded ICI bandwidth) — the
    non-zero comm perf signal BENCH records carry when nothing can run or
    even lower (the analyzer obs.comm does the same accounting from real
    lowered HLO when a step compiles)."""
    from hetu_tpu.obs.mfu import load_hardware_profile
    from hetu_tpu.comm.wire import analytic_dp_sync
    hw = load_hardware_profile()
    cfg = _bench_config()
    return analytic_dp_sync(cfg.num_params(), dp,
                            ici_gbps=hw.get("ici_allreduce_gbps"))


def _hardware_free_comm_paths(dp: int = 8, tp: int = 4, batch: int = 8,
                              seq: int = 2048):
    """Per-path fp32-vs-quantized wire bytes for the bench config — the
    analytic sibling of `tools_comm_report.py --compare` (which measures
    the same paths from real lowered HLO on the CPU mesh).  Covers the
    DP grad sync (int8 + the two-level intra/inter split when the
    profile has a topology section), the SP activation gather/scatter
    pair, the ZeRO-1 param refresh, and the cross-mesh hetero bridge.
    NOTE the SP row here prices the bench model's BF16 activations
    (int8 ratio ~1.97x); the tool's measured SP row lowers the f32
    activations the tier-1 CPU model trains in (~3.94x)."""
    from hetu_tpu.comm.wire import (two_level_sync_bytes,
                                    wire_bytes_per_element)
    from hetu_tpu.obs.mfu import load_hardware_profile
    hw = load_hardware_profile()
    cfg = _bench_config()
    n = float(cfg.num_params())

    def row(baseline_dtype, elem_bytes, elems, ring=1.0):
        # self-describing record: the baseline is whatever width the
        # path really moves uncompressed (f32 grads/params, bf16
        # activations) — ratio_int8 is vs THAT baseline, so the SP row's
        # ~1.97x and the grad rows' ~3.94x are directly comparable
        return {
            "baseline_dtype": baseline_dtype,
            "baseline_bytes": ring * elems * elem_bytes,
            "int8_bytes": ring * elems * wire_bytes_per_element(
                "int8", elem_bytes=elem_bytes),
            "int4_bytes": ring * elems * wire_bytes_per_element(
                "int4", elem_bytes=elem_bytes),
        }

    out = {}
    out["dp_grad_sync"] = row("f32", 4.0, n, ring=2.0 * (dp - 1) / dp)
    # SP edge pair per layer: seq all-gather + reduce-scatter of one
    # [b, s, h] bf16 activation over the tp ring, x num_layers
    act_elems = batch * seq * cfg.hidden_size * cfg.num_hidden_layers
    out["sp_activations"] = row("bf16", 2.0, act_elems,
                                ring=2.0 * (tp - 1) / tp)
    out["zero_refresh"] = row("f32", 4.0, n, ring=(dp - 1) / dp)
    out["hetero_bridge"] = row("f32", 4.0, n)
    topo = hw.get("topology")
    if topo:
        k = int(topo["slice_devices"])
        out["dp_grad_sync"]["two_level_int8"] = two_level_sync_bytes(
            n, dp, k, "int8")
        out["dp_grad_sync"]["intra_gbps"] = topo["intra_gbps"]
        out["dp_grad_sync"]["inter_gbps"] = topo["inter_gbps"]
    for rec in out.values():
        if rec.get("int8_bytes"):
            rec["ratio_int8"] = rec["baseline_bytes"] / rec["int8_bytes"]
    return out


def _hardware_free_profile(batch: int = 8, seq: int = 2048, cfg=None):
    """Analytic step-profile record with NO device contact
    (obs.hlo_profile.analytic_peak_hbm + the analytic per-layer
    roofline): peak HBM vs the chip's `hbm_gbytes` and a uniform
    per-layer compute/time row — the BENCH perf signal the regression
    sentinel (tools_bench_diff.py) tracks across rounds.  The measured
    path replaces this with the real compiled-HLO attribution
    (obs.hlo_profile.profile_record); when it falls back here it passes
    the config it actually measured, so the record describes that run
    (not the default bench config at someone else's batch/seq)."""
    from hetu_tpu.obs.hlo_profile import PROFILE_SCHEMA, analytic_peak_hbm
    from hetu_tpu.obs.mfu import _rates, load_hardware_profile
    cfg = cfg if cfg is not None else _bench_config()
    hw = load_hardware_profile()
    meas = hw.get("measured") or {}
    peak = analytic_peak_hbm(
        float(cfg.num_params()), batch=batch, seq=seq,
        hidden=cfg.hidden_size, num_layers=cfg.num_hidden_layers,
        vocab=cfg.vocab_size, remat=cfg.remat,
        act_boundary_units=float(meas.get("act_boundary_units", 1.0)),
        act_full_units=float(meas.get("act_full_units", 12.0)))
    # uniform decoder layers: analytic per-step train FLOPs
    # (flops_per_token is already fwd+bwd), LM head split out.  The
    # "layer" row carries the ALL-LAYERS total — the same meaning as
    # the measured profile's scanned `layer/...` groups (trip count
    # multiplied through), so the sentinel and report readers see one
    # convention.
    L = cfg.num_hidden_layers
    tokens = float(batch) * seq
    head_flops = 6.0 * cfg.vocab_size * cfg.hidden_size * tokens
    layer_flops = max(
        cfg.flops_per_token(seq) * tokens - head_flops, 0.0)
    # measured-or-datasheet compute ceiling: ONE definition (obs.mfu)
    compute, _hbm, _peak = _rates(hw)
    rec = {
        "profile_schema": PROFILE_SCHEMA,
        "analytic": True,
        "top": [
            {"group": "layer", "layers": L, "flops": layer_flops,
             "time_s": layer_flops / compute, "bound": "compute"},
            {"group": "lm_head", "flops": head_flops,
             "time_s": head_flops / compute, "bound": "compute"},
        ],
        "peak_hbm_bytes": peak["peak_bytes"],
        "peak_hbm_breakdown": {k: v for k, v in peak.items()
                               if k.endswith("_bytes")},
        "hbm_gbytes": hw.get("hbm_gbytes"),
        "fits_hbm": peak["peak_bytes"]
        <= float(hw.get("hbm_gbytes", 0.0)) * 1e9 * 0.9,
    }
    return rec


def _hardware_free_kernels(batch: int = 8, seq: int = 2048):
    """Analytic per-kernel HBM-traffic record for the bench config
    (ops/pallas/traffic.py + obs.mfu.kernel_roofline): fused vs unfused
    byte counts and roofline times per Pallas kernel — the numbers
    tools_bench_kernels.py prints and the acceptance gate pins
    (residual+RMSNorm >= 3x at the config's bf16 activations).
    Hardware-free like the comm/serving records (docs/kernels.md)."""
    from hetu_tpu.obs.mfu import kernel_roofline, load_hardware_profile
    from hetu_tpu.ops.pallas.traffic import (fused_verify_chain,
                                             report_for_config)
    cfg = _bench_config()
    hw = load_hardware_profile()
    traffic = report_for_config(cfg, batch=batch, seq=seq)
    roof = kernel_roofline(traffic, hw=hw)
    rec = {}
    for name, rt in traffic.items():
        rr = roof[name]
        rec[name] = {
            "fused_bytes": round(rt["fused_bytes"], 1),
            "unfused_bytes": round(rt["unfused_bytes"], 1),
            "reduction": round(rt["reduction"], 3),
            "fused_s": rr["fused_s"],
            "unfused_s": rr["unfused_s"],
            "per_step_multiplier": rt["per_step_multiplier"],
        }
    # the whole fused verify step (paged_verify x layers + one sampling
    # epilogue) vs the gather path — the acceptance gate pins >= 2x at
    # the bench spec-decode profile (k=4, int8 pages)
    fc = fused_verify_chain(
        8, 4, 16, 16, cfg.num_key_value_heads, cfg.head_dim,
        cfg.hidden_size, cfg.vocab_size,
        num_layers=cfg.num_hidden_layers, quant="int8")
    hbm = float(hw["hbm_gbps"]) * 1e9
    rec["fused_verify_chain"] = {
        "fused_bytes": round(fc["fused_bytes"], 1),
        "unfused_bytes": round(fc["gather_bytes"], 1),
        "reduction": round(fc["reduction"], 3),
        "fused_s": fc["fused_bytes"] / hbm,
        "unfused_s": fc["gather_bytes"] / hbm,
        "per_step_multiplier": 1,
    }
    return rec


def _hardware_free_moe(batch: int = 8, seq: int = 2048, ep: int = 8,
                       experts: int = 64, top_k: int = 2,
                       capacity_factor: float = 1.25):
    """Analytic MoE dispatch record for an expert-parallel variant of
    the bench config (comm/wire.py moe_dispatch_report): per-mode
    bytes-on-wire of the token->expert transport — fp32 explicit a2a +
    combine gather vs int8/int4, plus the two-level intra/inter split
    when the profile declares a topology — and the expert FLOPs/token
    (6 * k * 3 * h * i, the fwd+bwd convention flops_per_token uses).
    Buffer elements = capacity_factor * top_k * tokens * hidden per
    layer, priced at the bench config's bf16 activation width (so
    ratio_int8 is ~1.97x vs bf16, directly comparable to the SP row).
    Hardware-free like the comm record; tools_comm_report.py --compare
    measures the same dispatch from real lowered HLO."""
    from hetu_tpu.comm.wire import moe_dispatch_report
    from hetu_tpu.obs.mfu import load_hardware_profile
    cfg = _bench_config()
    hw = load_hardware_profile()
    topo = hw.get("topology") or {}
    n_elems = capacity_factor * top_k * batch * seq * cfg.hidden_size
    rep = moe_dispatch_report(n_elems, ep,
                              int(topo.get("slice_devices", 0)),
                              elem_bytes=2.0)
    rep.update({
        "baseline_dtype": "bf16",
        "experts": experts, "top_k": top_k,
        "capacity_factor": capacity_factor,
        "expert_flops_per_token": 6.0 * top_k * 3.0 * cfg.hidden_size
        * cfg.intermediate_size,
        "layers": cfg.num_hidden_layers,
    })
    if topo:
        rep["intra_gbps"] = topo.get("intra_gbps")
        rep["inter_gbps"] = topo.get("inter_gbps")
    return rep


def _hardware_free_serving(slots: int = 8, ctx: int = 2048, *,
                           measure_hlo: bool = False):
    """Analytic serving record for the bench config: continuous-batching
    decode tokens/s (roofline over the profiled chip: params read once
    per step, every slot reads its context KV) + per-sequence KV-cache
    bytes across page modes (fp32 exact / fp16 / blockwise-int8 paged,
    serving/kv_pool.py).  A model evaluated from counts, like the comm
    record (docs/serving.md) — not a serving measurement.

    PR 15 rows: ``spec_decode`` prices the speculative-decoding verify
    step at the same roofline (serving/spec_decode.roofline_report —
    the acceptance gate pins >= 2x tokens/s at acceptance 0.7) and
    ``prefix_cache`` counts the prefill FLOPs a fully-shared system
    prompt avoids via the radix cache.  With ``measure_hlo=True`` (it
    compiles the tiny canonical chunk program) the per-chunk FLOPs in
    that row are COUNTED from the lowered prefill HLO's dot ops instead
    of modeled."""
    from hetu_tpu.obs.mfu import load_hardware_profile
    from hetu_tpu.serving.kv_pool import kv_bytes_per_token
    from hetu_tpu.serving.spec_decode import roofline_report
    hw = load_hardware_profile()
    cfg = _bench_config()
    n = float(cfg.num_params())
    L, hd = cfg.num_hidden_layers, cfg.head_dim
    n_kv = cfg.num_key_value_heads
    peak = float(hw["bf16_tflops"]) * 1e12
    hbm = float(hw["hbm_gbps"]) * 1e9
    # per decoded token: the 2N matmul FLOPs + attention over ctx cached
    # positions (qk + pv, 2 * 2 * ctx * hidden)
    flops_tok = 2.0 * n + 4.0 * L * ctx * cfg.hidden_size
    kv = {m: kv_bytes_per_token(L, n_kv, hd, m) * ctx
          for m in ("fp32", "fp16", "int8", "int4")}

    def tokens_per_s(kv_mode):
        # one batched decode step: params (bf16) read once, each slot
        # reads its own context KV
        step_bytes = 2.0 * n + slots * kv[kv_mode]
        step_flops = slots * flops_tok
        return slots / max(step_flops / peak, step_bytes / hbm)

    rec = {
        "slots": slots, "context": ctx,
        "decode_tokens_per_s": round(tokens_per_s("fp16"), 1),
        "decode_tokens_per_s_int8_kv": round(tokens_per_s("int8"), 1),
        "decode_tokens_per_s_int4_kv": round(tokens_per_s("int4"), 1),
        "kv_bytes_per_seq": {m: round(v, 1) for m, v in kv.items()},
        "kv_ratio_int8_vs_fp32": round(kv["fp32"] / kv["int8"], 3),
        "kv_ratio_int8_vs_fp16": round(kv["fp16"] / kv["int8"], 3),
        "kv_ratio_int4_vs_fp32": round(kv["fp32"] / kv["int4"], 3),
    }
    # speculative decoding at the measured-acceptance operating point
    # (0.7 per-draft acceptance is the Hetis/Medusa-class regime for an
    # n-gram/small-draft drafter on real text; the serving report
    # measures the actual rate per run)
    rec["spec_decode"] = roofline_report(
        n_params=n, flops_per_token=flops_tok,
        step_bytes=2.0 * n + slots * kv["fp16"], slots=slots,
        k=4, acceptance=0.7, peak_flops=peak, hbm_bytes_per_s=hbm)
    # HETU_TPU_SPEC_DECODE=model: a resident-int8 draft model at ~1/20
    # the target params raises per-draft acceptance (the stochastic p/q
    # rule accepts on distribution overlap, not exact match) and pays k
    # sequential batched draft forwards per verify step
    n_draft = n / 20.0
    rec["spec_decode_model"] = roofline_report(
        n_params=n, flops_per_token=flops_tok,
        step_bytes=2.0 * n + slots * kv["fp16"], slots=slots,
        k=4, acceptance=0.85, peak_flops=peak, hbm_bytes_per_s=hbm,
        draft_flops_per_step=slots * 4 * 2.0 * n_draft,
        draft_bytes_per_step=4 * 1.0 * n_draft)
    rec["spec_decode_model"]["draft_params_frac"] = 0.05
    rec["prefix_cache"] = _prefix_cache_flops(cfg, measure_hlo=measure_hlo)
    return rec


def _prefix_cache_flops(cfg, *, prompt: int = 512, chunk: int = 32,
                        page: int = 16, measure_hlo: bool = False):
    """Prefill FLOPs a fully-shared system prompt avoids via the radix
    prefix cache: a `prompt`-token prompt prefills in prompt/chunk
    chunks; with every full page resident, only the final page-aligned
    remainder (>= 1 token, so >= 1 chunk) runs.  Per-chunk FLOPs are
    modeled (2 * N_params * chunk) or, with ``measure_hlo=True``,
    COUNTED from the lowered canonical chunk program's dot ops
    (obs/hlo_text.dot_flops over the compiled prefill HLO — the
    hardware-free measurement discipline), then scaled from the tiny
    canonical model to the bench config by the analytic ratio."""
    total_chunks = prompt // chunk
    # shared prefix caps at the page-aligned prefix of prompt-1 tokens
    shared = ((prompt - 1) // page) * page
    suffix_chunks = -(-(prompt - shared) // chunk)
    rec = {
        "prompt_tokens": prompt, "prefill_chunk": chunk,
        "page_size": page, "shared_tokens": shared,
        "chunks_full": total_chunks, "chunks_cached": suffix_chunks,
        "prefill_flops_saved_frac": round(
            1.0 - suffix_chunks / total_chunks, 4),
        "flops_per_chunk": 2.0 * float(cfg.num_params()) * chunk,
        "flops_source": "analytic",
    }
    if measure_hlo:
        rec.update(_measured_chunk_flops(cfg, chunk))
    rec["prefill_flops_full"] = rec["flops_per_chunk"] * total_chunks
    rec["prefill_flops_cached"] = rec["flops_per_chunk"] * suffix_chunks
    return rec


def _measured_chunk_flops(cfg, chunk: int):
    """Count the canonical chunk program's dot FLOPs from its compiled
    HLO (one tiny CPU compile), then scale to the bench config by the
    analytic params ratio — the 'measured from the lowered prefill HLO'
    leg of the PR 15 acceptance gate."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models.generation import extend_cache, init_cache
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
    from hetu_tpu.obs.hlo_text import (definitions, dot_flops,
                                       split_computations)
    tiny = LlamaConfig(vocab_size=256, hidden_size=64,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=256,
                       use_flash_attention=False, remat=False,
                       use_scan=True)
    model = LlamaLMHeadModel(tiny)
    params = model.init(jax.random.key(0))
    cache = init_cache(model, 1, 64)
    text = jax.jit(
        lambda p, t, c, s: extend_cache(model, p, t, c, s)).lower(
            params, jnp.zeros((1, 8), jnp.int32), cache,
            jnp.int32(0)).compile().as_text()
    comps = split_computations(text)
    defs = definitions(comps)
    measured = sum(dot_flops(ln, defs)
                   for lines in comps.values() for ln in lines)
    # scale tiny-model 8-token chunk FLOPs to the bench config's chunk
    scale = (2.0 * float(cfg.num_params()) * chunk) / \
        (2.0 * float(tiny.num_params()) * 8)
    return {"flops_per_chunk": measured * scale,
            "flops_per_chunk_tiny_measured": measured,
            "flops_source": "lowered_hlo"}


def main():
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench.py measures on a TPU; jax.devices()[0] is "
              f"{dev.platform!r} and there is no CPU mode", file=sys.stderr)
        return 2
    # the peak comes from the one table keyed by device_kind
    # (search/profiler.py); a kind that is not in it raises
    from hetu_tpu.search.profiler import HardwareProfile
    peak_flops = HardwareProfile.for_device_kind(
        dev.device_kind).bf16_tflops * 1e12

    from hetu_tpu import optim
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel

    # Single v5e-class chip (16G HBM): ~440M params fp32 Adam + bf16 compute.
    # measured ladder at this size (tools_bench_sweep.py, v5e, 2026-07):
    # full recompute+scan 0.524 < dots+scan 0.556 < dots_attn+unrolled
    # 0.586 MFU — saving dot outputs AND the named flash-attention
    # output (no kernel re-run in bwd), layers unrolled
    cfg = _bench_config()
    batch, seq, iters = 8, 2048, 6

    def measure(cfg, batch, seq, iters):
        """(mfu, tokens/s, step_s, roofline) of one donated AdamW step."""
        import jax
        import jax.numpy as jnp
        model = LlamaLMHeadModel(cfg)
        opt = optim.AdamW(lr=1e-4)
        params = model.init(jax.random.key(0))
        opt_state = opt.init(params)
        ids = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=(batch, seq)), jnp.int32)

        def _step(params, opt_state, ids):
            loss, grads = jax.value_and_grad(
                lambda p: model(p, ids, labels=ids))(params)
            params, opt_state = opt.update(grads, opt_state, params)
            return params, opt_state, loss

        # AOT compile: the ONE compiled executable both executes the timing
        # loop and feeds the analytic roofline (obs.mfu cost_analysis)
        step = jax.jit(_step, donate_argnums=(0, 1)).lower(
            params, opt_state, ids).compile()
        from hetu_tpu.obs.comm import collective_report
        from hetu_tpu.obs.hlo_profile import profile_record
        from hetu_tpu.obs.mfu import estimate_from_compiled
        est = estimate_from_compiled(step, with_phases=False)
        # bytes-on-wire of THIS compiled step's collectives (obs.comm);
        # 0 on the single-chip config, nonzero the moment the bench
        # runs a dp/tp mesh
        est["comm"] = collective_report(step)
        # per-layer attribution + peak HBM of THIS compiled step
        est["profile"] = profile_record(step)
        # warm-up, then timed steps; each wait ends in a device sync
        params, opt_state, loss = step(params, opt_state, ids)
        jax.block_until_ready(loss)
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, ids)
            jax.block_until_ready(loss)
            times.append(time.perf_counter() - t0)
        dt = min(times)
        tokens_per_sec = batch * seq / dt
        mfu = tokens_per_sec * cfg.flops_per_token(seq) / peak_flops
        return mfu, tokens_per_sec, dt, est

    mfu, tokens_per_sec, dt, est = measure(cfg, batch, seq, iters)

    detail = {
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "step_time_s": round(dt, 4),
        "model_params_m": round(cfg.num_params() / 1e6, 1),
        "batch": batch, "seq": seq,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "peak_flops": peak_flops,
    }
    # the analytic companion number: what the roofline says this compiled
    # program COULD reach on the profiled chip (a model, not a measurement)
    if est.get("flops_per_step"):
        detail["estimated_mfu"] = round(float(est["estimated_mfu"]), 4)
        detail["roofline"] = {
            "estimated_step_s": est.get("estimated_step_s"),
            "bound": est.get("bound"), "chip": est.get("chip")}
    else:   # cost_analysis gave no FLOP count
        detail["estimate"] = _hardware_free_estimate(batch, seq)
        detail["estimated_mfu"] = detail["estimate"]["estimated_mfu"]
    # what THIS compiled step puts on the wire (0 on the single-chip config)
    detail["comm_measured"] = {
        "bytes": est["comm"]["total_wire_bytes"],
        "comm_s_est": est["comm"]["predicted_comm_s"],
    }
    # analytic companions (counts and models, each named as such in
    # docs/): the dp=8 sync comparison, per-layer top-k + peak HBM,
    # serving roofline + paged-KV bytes, MoE dispatch bytes, per-kernel
    # fused-vs-unfused HBM bytes
    comm_a = _hardware_free_comm()
    detail["comm"] = comm_a
    detail["comm_paths"] = _hardware_free_comm_paths()
    detail["comm_bytes_per_step"] = comm_a["fp32_wire_bytes"]
    detail["profile"] = est["profile"]
    detail["serving"] = _hardware_free_serving(measure_hlo=True)
    detail["moe"] = _hardware_free_moe(batch, seq)
    detail["kernels"] = _hardware_free_kernels(batch, seq)

    # Second point: the largest model one 16G v5e fits.  fp32 Adam moments
    # bound it: p*(2 bf16 param + 8 fp32 m/v + 2 grad) + ~2G logits/acts
    # <= 16G -> ~1.0-1.2B params with bf16 weights (BASELINE.md targets a
    # 7B-class DP*TP*PP run; this is the single-chip-visible ladder rung).
    if "--skip-big" not in sys.argv:
        big_ladder = [
            (2048, 18, 5632, 16),   # ~1.06B params
            (2048, 16, 5632, 16),   # ~0.96B
            (1792, 16, 4864, 14),   # ~0.74B
            (1536, 14, 4096, 12),   # ~0.50B safety rung
        ]
        for h, L, inter, heads in big_ladder:
            big_cfg = LlamaConfig(
                vocab_size=32000, hidden_size=h, intermediate_size=inter,
                num_hidden_layers=L, num_attention_heads=heads,
                num_key_value_heads=heads, max_position_embeddings=2048,
                param_dtype=jnp.bfloat16, remat=True,
                remat_policy="dots_attn", use_scan=True)
            try:
                bmfu, btps, bdt, _ = measure(big_cfg, 4, 2048,
                                             max(iters - 2, 2))
                detail["big_model"] = {
                    "model_params_m": round(big_cfg.num_params() / 1e6, 1),
                    "mfu": round(float(bmfu), 4),
                    "tokens_per_sec_per_chip": round(btps, 1),
                    "step_time_s": round(bdt, 4),
                    "batch": 4, "seq": 2048, "param_dtype": "bfloat16",
                }
                break
            except Exception as e:
                msg = str(e)
                oom = any(t in msg.lower() for t in
                          ("resource", "memory", "oom", "exhaust",
                           "allocat"))
                print(f"# big-model rung h{h}xL{L} failed "
                      f"({type(e).__name__}): {msg[:300]}", file=sys.stderr)
                if not oom:
                    # a real bug, not memory pressure: smaller rungs would
                    # hit it too — stop instead of masking the regression
                    break

    print(json.dumps({
        "metric": "llama_train_mfu",
        "value": round(float(mfu), 4),
        "unit": "fraction_of_peak",
        "vs_baseline": round(float(mfu) / 0.45, 4),
        "detail": detail,
    }), flush=True)

    # --hardware-profile PATH: probe the chip (matmul, HBM, overlap) and
    # write the measured profile there; a probe that fails fails the run
    if "--hardware-profile" in sys.argv:
        out = sys.argv[sys.argv.index("--hardware-profile") + 1]
        from hetu_tpu.search.calibrate import measure_activation_units
        from hetu_tpu.search.profiler import profile_hardware
        prof = profile_hardware(measure=True)
        units = measure_activation_units()
        if units:
            prof.measured.update(
                act_boundary_units=units["boundary_units"],
                act_full_units=units["full_units"])
        prof.save(out)
        print(f"# hardware profile saved: {out} {prof.measured}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    from hetu_tpu.utils.device import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
