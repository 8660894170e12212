"""Recompute (activation checkpoint) policies shared by all model families
(reference: hetu/graph/recompute/recompute.cc pass + the activation
CPU-offload pass offload/activation_cpu_offload.h — 'offload' keeps dot
outputs staged in pinned host memory)."""
from __future__ import annotations

import jax

REMAT_POLICIES = ("nothing", "dots", "dots_attn", "offload")


def remat_policy(name: str):
    cp = jax.checkpoint_policies
    if name == "nothing":
        return cp.nothing_saveable
    if name == "dots":
        return cp.dots_with_no_batch_dims_saveable
    if name == "dots_attn":
        # dots + what the attention's own backward reads, so that the
        # flash kernel (the costliest thing the dot-only policy recomputes)
        # is not launched again: the kernel's forward RULE names its
        # residuals `o` "attn_out" and `lse` "attn_lse"
        # (ops/pallas/flash_attention._flash_fwd); a name on a custom_vjp's
        # result saves none of its residuals.  The other routes name
        # their result "attn_out" (ops.attention, the XLA composition;
        # parallel.ring_attention_gspmd): a layer keeps the attention
        # output once whichever route it took, and no model names it
        return cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable,
            cp.save_only_these_names("attn_out", "attn_lse"))
    if name == "offload":
        return cp.offload_dot_with_no_batch_dims("device", "pinned_host")
    raise ValueError(f"unknown remat_policy {name!r}; one of {REMAT_POLICIES}")


def validate_remat_policy(name: str):
    if name not in REMAT_POLICIES:
        raise ValueError(
            f"unknown remat_policy {name!r}; one of {REMAT_POLICIES}")
