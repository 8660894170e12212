"""The yardstick's constants: device peaks, and how a family's counts
(`benchmarks/families/<family>.py`: its weights, widths and kernel cost
functions) become the operations a utilization or a roofline share
divides by.

Nothing here is measured.  A later PR may not edit this file or a family
module that is there, so the numerators of `model_flops_util`,
`flash_attn_roofline` and `chat.paged_attn_roofline` cannot move with the
code they judge.
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


def train_flops_per_token(counts: dict, seq: int) -> float:
    """Operations the forward and backward passes REQUIRE per trained
    token, from a family's `counts(cfg)`: 6 per weight that takes part in
    a matmul for that token (`matmul_params`: for an expert model the
    active ones), plus causal attention (QK^T and PV see on average seq/2
    keys: 2 matmuls x 2 x seq/2 x q-width forward, twice that backward;
    `attn_width` is the q-width summed over the layers).  Recomputation
    under remat and the flash kernel's own recomputed scores are not
    counted."""
    return 6.0 * counts["matmul_params"] + 6.0 * seq * counts["attn_width"]


def roofline_seconds(cost: dict, peaks: dict) -> dict:
    """The least time one chip could take, and which peak sets it."""
    t_ops = cost["ops"] / peaks["flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_bytes),
            "bound": "compute" if t_ops >= t_bytes else "memory"}
