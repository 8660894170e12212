"""Analytic MFU / roofline estimator.

Estimates MFU for a compiled train step WITHOUT running it, by combining

  * XLA's own FLOP count — `jit(...).lower().compile().cost_analysis()`
    (exact for the compiled program, available on any backend incl. CPU),
  * the chip peaks in `hardware_profile_v5e.json` (bf16 TFLOP/s, HBM GB/s,
    plus the measured ceilings recorded when hardware WAS reachable),
  * the per-phase HLO attribution from `utils.profiling.phase_breakdown`
    (dots ~ MXU work share, out_bytes ~ HBM traffic share).

Per phase, the roofline bound is
    t_phase = max(flops_phase / compute_rate, bytes_phase / hbm_rate)
and the estimated step time is the sum over phases (TPU phases serialize on
the single compute stream).  Estimated MFU = flops / (peak * t_est) — an
UPPER BOUND on achievable MFU for this program on this chip: it prices
compute and HBM traffic but not ICI collectives or host stalls.  It is a
model evaluated from counts, never a measurement: records carry it as
`estimated_mfu`, and a measured MFU comes only from a timed chip run.

`analytic_transformer_estimate` computes the same report from a model
config's analytic FLOPs and a parameter/activation traffic model — pure
python, no jax.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

#: required top-level keys of a hardware profile (value must be a
#: positive number unless noted) — obs.mfu and obs.comm read these
#: unconditionally, so a profile missing one must fail LOUDLY at load,
#: not as a KeyError deep in a report
_REQUIRED_KEYS = ("bf16_tflops", "hbm_gbytes", "hbm_gbps",
                  "ici_allreduce_gbps", "ici_p2p_gbps")
_TOPOLOGY_KEYS = ("slice_devices", "intra_gbps", "inter_gbps")


def validate_hardware_profile(hw: Dict[str, Any],
                              source: str = "<dict>") -> Dict[str, Any]:
    """Schema-check a hardware profile, naming the offending key.

    Required: `chip` (string) plus positive numbers for each of
    {bf16_tflops, hbm_gbytes, hbm_gbps, ici_allreduce_gbps,
    ici_p2p_gbps}.  Optional: `dcn_gbps` (positive number), `measured`
    (dict of numbers), and `topology` — which, when present, must carry
    positive {slice_devices (integer), intra_gbps, inter_gbps} and may
    carry `slice_shape` (list of positive ints whose product equals
    slice_devices).  Returns `hw` unchanged on success."""
    def fail(key, why):
        raise ValueError(
            f"invalid hardware profile ({source}): key {key!r} {why}")

    if not isinstance(hw, dict):
        raise ValueError(
            f"invalid hardware profile ({source}): expected a JSON "
            f"object, got {type(hw).__name__}")
    if not isinstance(hw.get("chip"), str) or not hw.get("chip"):
        fail("chip", "must be a non-empty string")
    for k in _REQUIRED_KEYS:
        if k not in hw:
            fail(k, "is missing")
        v = hw[k]
        if not isinstance(v, (int, float)) or isinstance(v, bool) or v <= 0:
            fail(k, f"must be a positive number, got {v!r}")
    if "dcn_gbps" in hw:
        v = hw["dcn_gbps"]
        if not isinstance(v, (int, float)) or isinstance(v, bool) or v <= 0:
            fail("dcn_gbps", f"must be a positive number, got {v!r}")
    meas = hw.get("measured", {})
    if meas is not None and not isinstance(meas, dict):
        fail("measured", f"must be an object, got {type(meas).__name__}")
    for k, v in (meas or {}).items():
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            fail(f"measured.{k}", f"must be a number, got {v!r}")
    topo = hw.get("topology")
    if topo is not None:
        if not isinstance(topo, dict):
            fail("topology", f"must be an object, got {type(topo).__name__}")
        for k in _TOPOLOGY_KEYS:
            if k not in topo:
                fail(f"topology.{k}", "is missing")
            v = topo[k]
            if (not isinstance(v, (int, float)) or isinstance(v, bool)
                    or v <= 0):
                fail(f"topology.{k}", f"must be a positive number, got {v!r}")
        if topo["slice_devices"] != int(topo["slice_devices"]):
            fail("topology.slice_devices",
                 f"must be an integer, got {topo['slice_devices']!r}")
        shape = topo.get("slice_shape")
        if shape is not None:
            if (not isinstance(shape, (list, tuple)) or not shape
                    or any(not isinstance(d, int) or isinstance(d, bool)
                           or d <= 0 for d in shape)):
                fail("topology.slice_shape",
                     f"must be a list of positive integers, got {shape!r}")
            prod = 1
            for d in shape:
                prod *= d
            if prod != int(topo["slice_devices"]):
                fail("topology.slice_shape",
                     f"product {prod} != slice_devices "
                     f"{topo['slice_devices']}")
    return hw


def load_hardware_profile(path: Optional[str] = None) -> Dict[str, Any]:
    """Load a hardware profile JSON.  Resolution: explicit `path` ->
    HETU_TPU_HW_PROFILE env -> repo-root hardware_profile_v5e.json.  The
    chosen file must open, parse and validate; each failure raises,
    naming the file (and the offending key) — there are no built-in chip
    numbers to fall back on, because a profile that silently is not the
    one asked for would skew every MFU/comm estimate."""
    from hetu_tpu.utils import flags
    chosen = path or flags.str_flag("HETU_TPU_HW_PROFILE")
    if not chosen:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        chosen = os.path.join(root, "hardware_profile_v5e.json")
    with open(chosen) as f:
        raw = f.read()
    try:
        hw = json.loads(raw)
    except ValueError as e:
        raise ValueError(
            f"invalid hardware profile ({chosen}): not valid JSON: {e}"
        ) from None
    return validate_hardware_profile(hw, source=chosen)


def _rates(hw: Dict[str, Any]):
    """(compute FLOP/s ceiling, HBM byte/s ceiling, peak FLOP/s).

    The MFU denominator is always the datasheet peak; the roofline TIME
    uses the measured ceilings when the profile carries them (what the
    chip actually sustains)."""
    peak = float(hw["bf16_tflops"]) * 1e12
    meas = hw.get("measured") or {}
    compute = float(meas.get("matmul_tflops") or 0.0) * 1e12 or peak
    hbm = (float(meas.get("hbm_gbps") or 0.0) or
           float(hw["hbm_gbps"])) * 1e9
    return compute, hbm, peak


def flops_of_compiled(compiled) -> float:
    """XLA's FLOP estimate for a compiled executable (0.0 if the backend
    does not report one).  cost_analysis() is a dict on current jax and a
    per-device list-of-dict on older releases."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return 0.0
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return 0.0
    return float(ca.get("flops", 0.0) or 0.0)


def estimate_mfu(flops_per_step: float, *,
                 hw: Optional[Dict[str, Any]] = None,
                 phases: Optional[Dict[str, Dict[str, float]]] = None,
                 total_bytes: Optional[float] = None,
                 measured_step_s: Optional[float] = None) -> Dict[str, Any]:
    """Roofline-estimate MFU for one train step.

    phases: `phase_breakdown` output ({phase: {dots, out_bytes, ...}});
    step FLOPs are apportioned to phases by their dot-count share and each
    phase is bounded by max(compute, memory) time.  Without phases, a
    single-bucket roofline over `total_bytes` (or pure compute) is used.
    measured_step_s, when available, adds the measured MFU alongside.
    """
    flops = float(flops_per_step)
    hw = hw if hw is not None else load_hardware_profile()
    compute, hbm, peak = _rates(hw)
    report: Dict[str, Any] = {
        "flops_per_step": flops,
        "peak_flops": peak,
        "chip": hw.get("chip", "unknown"),
    }
    if flops <= 0:
        report.update(estimated_step_s=None, estimated_mfu=0.0)
        return report

    if phases:
        total_dots = sum(p.get("dots", 0) for p in phases.values()) or 1
        per_phase = {}
        t_est = 0.0
        for name, p in phases.items():
            f_p = flops * p.get("dots", 0) / total_dots
            b_p = float(p.get("out_bytes", 0))
            t_c = f_p / compute
            t_m = b_p / hbm
            t_p = max(t_c, t_m)
            if t_p <= 0:
                continue
            per_phase[name] = {
                "flops": f_p, "bytes": b_p, "time_s": t_p,
                "bound": "memory" if t_m > t_c else "compute",
            }
            t_est += t_p
        report["phases"] = per_phase
    else:
        t_c = flops / compute
        t_m = (float(total_bytes) / hbm) if total_bytes else 0.0
        t_est = max(t_c, t_m)
        report["bound"] = "memory" if t_m > t_c else "compute"

    report["estimated_step_s"] = t_est
    report["estimated_mfu"] = (flops / (peak * t_est)) if t_est > 0 else 0.0
    if measured_step_s:
        report["measured_step_s"] = float(measured_step_s)
        report["measured_mfu"] = flops / (peak * float(measured_step_s))
    return report


def kernel_roofline(traffic: Dict[str, Dict[str, Any]], *,
                    hw: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Dict[str, Any]]:
    """Per-kernel roofline attribution for the fused-kernel layer: price
    each kernel's fused vs unfused analytic HBM bytes
    (ops/pallas/traffic.py) at the profiled chip's HBM rate.

    These chains are memory-bound by construction (elementwise /
    reduction work per byte is far below the ridge point), so the
    roofline time IS bytes / hbm_rate and the per-kernel efficiency win
    is the byte reduction itself: `speedup` = unfused_s / fused_s.
    A model evaluated from byte counts, not a kernel timing."""
    hw = hw if hw is not None else load_hardware_profile()
    _, hbm, _ = _rates(hw)
    out: Dict[str, Dict[str, Any]] = {}
    for name, rec in traffic.items():
        fused_s = rec["fused_bytes"] / hbm
        unfused_s = rec["unfused_bytes"] / hbm
        out[name] = {
            "fused_bytes": rec["fused_bytes"],
            "unfused_bytes": rec["unfused_bytes"],
            "fused_s": fused_s,
            "unfused_s": unfused_s,
            "speedup": unfused_s / fused_s if fused_s else float("inf"),
            "bound": "memory",
        }
    return out


def estimate_from_compiled(compiled, *, hw: Optional[Dict] = None,
                           with_phases: bool = True,
                           measured_step_s: Optional[float] = None
                           ) -> Dict[str, Any]:
    """Full hardware-free report for a compiled step: cost_analysis FLOPs +
    (optionally) the per-phase HLO attribution.  with_phases=False skips
    the HLO text parse (large programs) and uses the single-bucket
    roofline over cost_analysis' byte estimate when present."""
    flops = flops_of_compiled(compiled)
    phases = None
    total_bytes = None
    if with_phases:
        try:
            from hetu_tpu.utils.profiling import phase_breakdown
            phases = phase_breakdown(compiled)
        except Exception:
            phases = None
    if phases is None:
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            total_bytes = float(ca.get("bytes accessed", 0.0) or 0.0) or None
        except Exception:
            total_bytes = None
    return estimate_mfu(flops, hw=hw, phases=phases,
                        total_bytes=total_bytes,
                        measured_step_s=measured_step_s)


def analytic_transformer_estimate(cfg, batch: int, seq: int, *,
                                  hw: Optional[Dict] = None,
                                  param_bytes: int = 2) -> Dict[str, Any]:
    """Jax-free estimate from a model config exposing flops_per_token(seq)
    and num_params() (LlamaConfig/GPT config): analytic train FLOPs plus a
    coarse HBM traffic model — params read fwd + bwd + optimizer update
    (3 passes over the weights) and one activation write/read per layer
    boundary.  This is the bench fallback when the backend is unreachable
    and nothing can even compile."""
    flops = float(batch) * seq * float(cfg.flops_per_token(seq))
    n_params = float(cfg.num_params())
    weight_traffic = 3.0 * n_params * param_bytes
    layers = float(getattr(cfg, "num_hidden_layers", 0) or 0)
    hidden = float(getattr(cfg, "hidden_size", 0) or 0)
    act_traffic = 2.0 * batch * seq * hidden * layers * param_bytes
    rep = estimate_mfu(flops, hw=hw,
                       total_bytes=weight_traffic + act_traffic)
    rep["analytic"] = True
    rep["batch"], rep["seq"] = batch, seq
    return rep
