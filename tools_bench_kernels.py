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
    python tools_bench_kernels.py --grouped-product  # ON THE CHIP: the
                                                   # expert walk by block
                                                   # size (the one case
                                                   # that times anything)

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


#: the walks the expert cells run (nn/moe.dropless_local_experts):
#: (cell's program, tokens T, experts a token k, router outputs, experts
#: held, hidden, expert intermediate)
GROUPED_PRODUCT_SHAPES = (
    ("lfm2 decode", 128, 4, 32, 32, 2048, 1792),
    ("lfm2 chunk", 1536, 4, 32, 32, 2048, 1792),
    ("xing4.0 chunk", 1024, 4, 64, 64, 3584, 1024),
    ("ling-3.0 chunk", 2048, 8, 512, 64, 2560, 768),
    ("mimo-v2 chunk", 1024, 8, 256, 16, 4096, 2048),
    ("trinity-mini chunk", 512, 8, 128, 16, 2048, 1024),
    ("deepseek-v3.2 chunk", 1024, 8, 256, 8, 7168, 2048),
    ("kimi-k2.6 chunk", 512, 8, 384, 12, 7168, 2048),
    ("longcat-flash chunk", 512, 12, 768, 16, 6144, 2048),
)


def grouped_product_sweep(blocks=(64, 128, 192, 256, 512), reps: int = 5,
                          draws: int = 6, shapes=GROUPED_PRODUCT_SHAPES):
    """`nn/moe.dropless_local_experts` alone (sort, walk, combine; no
    router, no shared expert) at each expert cell's shapes in bfloat16,
    with the block `row_block` gives and with every block of `blocks`
    under today's 1.5 x expected rows forced instead: milliseconds a
    call by the host's clock over `reps` x `draws` launches that end in
    `block_until_ready` (top-k of uniform scores, `draws` routings), and
    the blocks walked.  Yields one record a (shape, block).  The numbers
    in the comment above `nn/moe.RIDGE_ROWS` are this sweep's; a time
    means something on the chip only."""
    import time

    import jax
    import jax.numpy as jnp

    from hetu_tpu.nn import moe

    rule = moe.row_block
    dev = jax.devices()[0]
    for name, T, k, outputs, held, h, inter in shapes:
        kx, kg, kd, ki = jax.random.split(jax.random.PRNGKey(63), 4)
        x = (jax.random.normal(kx, (T, h)) * 0.5).astype(jnp.bfloat16)
        wgu = (jax.random.normal(kg, (held, h, 2 * inter)) * 0.02) \
            .astype(jnp.bfloat16)
        wd = (jax.random.normal(kd, (held, inter, h)) * 0.02) \
            .astype(jnp.bfloat16)
        routed = []
        for d in range(draws):
            w, idx = jax.lax.top_k(
                jax.random.uniform(jax.random.fold_in(ki, d), (T, outputs)),
                k)
            routed.append((idx.astype(jnp.int32), w))
        share = held / outputs
        taken, expected = rule(T * k, share, held)
        for rows in sorted({b for b in blocks if b < expected}
                           | {taken, expected}):
            # the walk with `rows` forced: the rule is a module function
            # read at trace time, patched for this one trace
            moe.row_block = lambda *a, rows=rows: (rows, expected)
            try:
                f = jax.jit(lambda x, i, w, a, b: moe.dropless_local_experts(
                    x, i, w, a, b, first_expert=0, share=share))
                walked = [int(f(x, idx, w, wgu, wd)[3])
                          for idx, w in routed]
            finally:
                moe.row_block = rule
            t0 = time.perf_counter()
            for _ in range(reps):
                for idx, w in routed:
                    y = f(x, idx, w, wgu, wd)[0]
            y.block_until_ready()
            ms = (time.perf_counter() - t0) / (reps * draws) * 1e3
            yield {"shape": name, "pairs": T * k, "held": held,
                   "share": share, "expected_rows": expected,
                   "rows": rows, "taken": rows == taken,
                   "ms": round(ms, 4),
                   "blocks": round(sum(walked) / draws, 2),
                   "device": dev.device_kind}
        del x, wgu, wd


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
    ap.add_argument("--grouped-product", action="store_true",
                    help="time nn/moe.dropless_local_experts by row "
                         "block at the expert cells' shapes (run it on "
                         "the chip: a CPU's times say nothing); one "
                         "JSON record a (shape, block)")
    args = ap.parse_args(argv)

    if args.grouped_product:
        for rec in grouped_product_sweep():
            print(json.dumps(rec), flush=True)
        return 0

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
