"""Per-kernel fused-vs-XLA HBM-traffic table for the Pallas layer.

Prints, for every kernel in `hetu_tpu/ops/pallas` (docs/kernels.md), the
analytic HBM bytes each path moves for the bench config's shapes and
the roofline time at the profiled chip's HBM rate — the SAME byte model
bench.py records in `detail.kernels`, so the CLI and the BENCH record
can never disagree (the tools_comm_report.py pattern: byte counts from
shapes, no device contact — not kernel timings).

    python tools_bench_kernels.py                  # bench-config table
    python tools_bench_kernels.py --batch 4 --seq 1024
    python tools_bench_kernels.py --json           # machine-readable
    python tools_bench_kernels.py --chain norm     # audit one kernel's
                                                   # unfused op chain

tools_obs_report.py embeds the same numbers as its `kernels` section
(--kernels).
"""
from __future__ import annotations

import argparse
import json
import sys


def kernel_section(batch: int = 8, seq: int = 2048) -> dict:
    """The analytic per-kernel record for the bench config — one shared
    producer for this CLI, bench.py detail.kernels, and
    tools_obs_report's `kernels` section."""
    import bench
    return bench._hardware_free_kernels(batch, seq)


def _fmt_bytes(b: float) -> str:
    for unit, scale in (("GB", 1e9), ("MB", 1e6), ("KB", 1e3)):
        if b >= scale:
            return f"{b / scale:8.2f} {unit}"
    return f"{b:8.0f} B "


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Analytic fused-vs-XLA HBM bytes + roofline time "
                    "per Pallas kernel (the bench.py detail.kernels "
                    "byte model).")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--json", action="store_true",
                    help="emit the record as JSON instead of the table")
    ap.add_argument("--chain", metavar="KERNEL", default=None,
                    help="print one kernel's unfused op chain (norm, "
                         "swiglu, rotary, quant, flash, paged_attn, "
                         "paged_attn_int8, paged_attn_int4, "
                         "paged_verify, sample)")
    args = ap.parse_args(argv)

    if args.chain:
        from hetu_tpu.ops.pallas import traffic as t
        import bench
        cfg = bench._bench_config()
        tokens = args.batch * args.seq
        builders = {
            "norm": lambda: t.norm_traffic(tokens, cfg.hidden_size),
            "swiglu": lambda: t.swiglu_traffic(tokens,
                                               cfg.intermediate_size),
            "rotary": lambda: t.rotary_traffic(
                args.batch, args.seq, cfg.num_attention_heads,
                cfg.num_key_value_heads, cfg.head_dim),
            "quant": lambda: t.quant_traffic(
                cfg.num_hidden_layers * cfg.hidden_size
                * cfg.intermediate_size, 1024),
            "flash": lambda: t.flash_traffic(
                args.batch, args.seq, cfg.num_attention_heads,
                cfg.head_dim),
            "paged_attn": lambda: t.paged_attn_traffic(
                8, 16, 16, cfg.num_key_value_heads, cfg.head_dim),
            "paged_attn_int8": lambda: t.paged_attn_traffic(
                8, 16, 16, cfg.num_key_value_heads, cfg.head_dim,
                quant="int8"),
            "paged_attn_int4": lambda: t.paged_attn_traffic(
                8, 16, 16, cfg.num_key_value_heads, cfg.head_dim,
                quant="int4"),
            "paged_verify": lambda: t.paged_verify_traffic(
                8, 4, 16, 16, cfg.num_key_value_heads, cfg.head_dim,
                quant="int8"),
            "sample": lambda: t.sample_traffic(
                8 * 5, cfg.hidden_size, cfg.vocab_size),
        }
        if args.chain not in builders:
            print(f"unknown kernel {args.chain!r}; "
                  f"known: {sorted(builders)}", file=sys.stderr)
            return 2
        rec = builders[args.chain]()
        print(f"# {rec['kernel']} unfused op chain "
              f"(read + write bytes per op)")
        for op in rec["chain"]:
            print(f"  {op['op']:<18} R {_fmt_bytes(op['read'])}   "
                  f"W {_fmt_bytes(op['write'])}")
        print(f"  {'TOTAL unfused':<18} {_fmt_bytes(rec['unfused_bytes'])}"
              f"   fused {_fmt_bytes(rec['fused_bytes'])}   "
              f"{rec['reduction']:.2f}x")
        return 0

    rec = kernel_section(args.batch, args.seq)
    if args.json:
        print(json.dumps({"batch": args.batch, "seq": args.seq,
                          "kernels": rec}, indent=2))
        return 0
    print(f"# Pallas fused-kernel layer: analytic HBM traffic per step "
          f"(batch={args.batch}, seq={args.seq}; docs/kernels.md)")
    hdr = (f"{'kernel':<12} {'unfused':>12} {'fused':>12} {'cut':>7} "
           f"{'unfused_ms':>11} {'fused_ms':>9} {'xlayers':>8}")
    print(hdr)
    print("-" * len(hdr))
    for name, r in rec.items():
        print(f"{name:<12} {_fmt_bytes(r['unfused_bytes']):>12} "
              f"{_fmt_bytes(r['fused_bytes']):>12} "
              f"{r['reduction']:>6.2f}x "
              f"{r['unfused_s'] * 1e3:>11.3f} {r['fused_s'] * 1e3:>9.3f} "
              f"{r['per_step_multiplier']:>8}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
