"""Per-collective bytes-on-wire table for a compiled train step.

Lowers one Trainer train step for a tiny LLaMA on a virtual dp-mesh
(CPU — no device contact), walks the
optimized HLO with the obs.comm analyzer, and prints every collective's
payload/wire bytes plus the aggregate report — the comm twin of
tools_obs_report.py.

    python tools_comm_report.py                      # dp=4, fp32 sync
    python tools_comm_report.py --compress int8-ef   # quantized sync
    python tools_comm_report.py --compare            # per-path fp32 vs
                                                     # compressed table
    python tools_comm_report.py --dp 8 --zero        # ZeRO-1 lowering

`--compare` lowers every compressible wire path — the DP grad sync, the
SP activation gathers/scatters (dstates.convert), the ZeRO-1 param
refresh, the MoE expert dispatch (an ep=8 MoE layer's explicit a2a +
combine gather, nn/moe_dispatch.py) — flag-off vs flag-on, plus the
analytic hetero-DP/PP bridge, and prints fp32 vs compressed bytes with
predicted times at the topology's intra/inter-slice rates.

The model lowers with use_scan=False so every collective is top-level in
the HLO (the analyzer also resolves `while` trip counts for scanned
models, falling back to a `dynamic_trip_count` caveat when a bound is
not static).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

if __name__ == "__main__":
    # must precede any jax import: the analyzer needs a real dp mesh
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()


class _scoped_env:
    """Set env vars for the scope, restoring the PRIOR values on exit
    (a caller's exported flags must survive a report)."""

    def __init__(self, **vals):
        self._vals = vals
        self._prev = {}

    def __enter__(self):
        for k, v in self._vals.items():
            self._prev[k] = os.environ.get(k)
            os.environ[k] = v

    def __exit__(self, *exc):
        for k, prev in self._prev.items():
            if prev is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = prev


def lowered_step_report(mode: str, *, dp: int = 4, zero: bool = False,
                        batch: int = 8, seq: int = 64,
                        zero_compress: str = "none"):
    """(collective_report, collective_table) for one compiled tiny-LLaMA
    train step under HETU_TPU_GRAD_COMPRESS=`mode` (+ optionally
    HETU_TPU_ZERO_COMPRESS=`zero_compress`)."""
    with _scoped_env(HETU_TPU_GRAD_COMPRESS=mode,
                     HETU_TPU_ZERO_COMPRESS=zero_compress):
        return _lowered_step_report(mode, dp=dp, zero=zero, batch=batch,
                                    seq=seq)


def _lowered_step_report(mode, *, dp, zero, batch, seq):
    import numpy as np

    from hetu_tpu.core.mesh import MeshConfig
    from hetu_tpu.engine import Trainer, TrainingConfig
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
    from hetu_tpu.obs.comm import collective_report, collective_table
    from hetu_tpu.parallel import ParallelStrategy

    cfg = LlamaConfig.tiny(remat=False, use_scan=False)
    st = ParallelStrategy(mesh=MeshConfig(dp=dp), zero=zero)
    tc = TrainingConfig(global_batch_size=batch,
                        micro_batch_size=max(batch // dp, 1), seq_len=seq,
                        warmup_steps=2, total_steps=10, log_every=1000)
    tr = Trainer(LlamaLMHeadModel(cfg, st), tc, st).build()
    rng = np.random.default_rng(0)
    hb = {"input_ids": rng.integers(1, 250, (batch, seq)).astype(np.int32),
          "labels": rng.integers(1, 250, (batch, seq)).astype(np.int32)}
    key = tuple(sorted((k, tuple(v.shape)) for k, v in hb.items()))
    compiled = tr._compiled_for_shape(hb, key)
    return collective_report(compiled), collective_table(compiled)


def lowered_sp_report(mode: str, *, tp: int = 4, batch: int = 4,
                      seq: int = 256, hidden: int = 256):
    """collective_report of a lowered SP round trip through
    dstates.convert (seq all-gather into a projection, reduce-scatter
    back out — the Megatron-SP edge pair) under
    HETU_TPU_SP_COMPRESS=`mode`.  Activations lower as f32 (the dtype
    the tier-1 CPU model trains in); a bf16 SP edge halves the fp32
    column, so its int8 ratio is ~1.97x, not ~3.94x."""
    with _scoped_env(HETU_TPU_SP_COMPRESS=mode):
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from hetu_tpu.core.mesh import MeshConfig, create_mesh
        from hetu_tpu.dstates import DistributedStates as DS, convert
        from hetu_tpu.obs.comm import collective_report

        mesh = create_mesh(MeshConfig(tp=tp))
        seq_sharded = DS.make(3, {1: "tp"})
        replicated = DS.dup(3)
        partial = DS.make(3, partial=("tp",))

        def run(x, w):
            full = convert(x, seq_sharded, replicated)   # seq all-gather
            y = full @ w                                  # "row-parallel"
            # declare y partial so the layout algebra emits the fused
            # reduce-scatter back onto the seq dim (lowering-only: this
            # program is analyzed, never executed)
            return convert(y, partial, seq_sharded)

        fn = jax.jit(shard_map(
            run, mesh=mesh,
            in_specs=(P(None, "tp", None), P()),
            out_specs=P(None, "tp", None), check_vma=False))
        x = jnp.zeros((batch, seq, hidden), jnp.float32)
        w = jnp.zeros((hidden, hidden), jnp.float32)
        compiled = fn.lower(x, w).compile()
        return collective_report(compiled)


def lowered_moe_report(mode: str, *, ep: int = 8, experts: int = 8,
                       batch: int = 2, seq: int = 16, hidden: int = 32,
                       topology: str = "flat"):
    """collective_report of a lowered MoE layer forward on an ep-mesh
    under HETU_TPU_MOE_DISPATCH=`mode` (nn/moe_dispatch.py): the
    dispatch all-to-all + combine all-gather are the only collectives
    in the program, so the report IS the dispatch cost.  topology=
    "two_level" opts into the hierarchical schedule (needs the
    profile's slice topology to apply to ep)."""
    env = {"HETU_TPU_MOE_DISPATCH": mode,
           "HETU_TPU_COMM_TOPOLOGY": topology}
    with _scoped_env(**env):
        import jax
        import jax.numpy as jnp
        import numpy as np

        import hetu_tpu as ht
        from hetu_tpu.core.mesh import MeshConfig
        from hetu_tpu.nn.moe import MoEConfig, MoELayer
        from hetu_tpu.obs.comm import collective_report
        from hetu_tpu.parallel import ParallelStrategy

        moe = MoEConfig(num_experts=experts, top_k=2, capacity_factor=2.0)
        st = ParallelStrategy(mesh=MeshConfig(ep=ep))
        mesh = st.build_mesh()
        layer = MoELayer(hidden, 2 * hidden, moe, st)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(batch, seq, hidden)), jnp.float32)
        with ht.use_mesh(mesh):
            p = layer.init(jax.random.key(0), mesh=mesh)
            compiled = jax.jit(lambda p_, x_: layer(p_, x_)[0]) \
                .lower(p, x).compile()
        return collective_report(compiled)


def _print_table(mode: str, report, table, verbose: bool):
    print(f"== HETU_TPU_GRAD_COMPRESS={mode} ==")
    print(f"{'collective':<20}{'count':>6}{'wire bytes':>14}")
    for op, rec in sorted(report["collectives"].items()):
        print(f"{op:<20}{rec['count']:>6}{rec['wire_bytes']:>14,.0f}")
    print(f"{'TOTAL':<20}{report['num_collectives']:>6}"
          f"{report['total_wire_bytes']:>14,.0f}"
          f"   predicted {report['predicted_comm_s'] * 1e6:.1f}us "
          f"({report['chip']})")
    if verbose:
        for r in table:
            trip = (f"  x{r['trip_count']}" if r["trip_count"] > 1 else "")
            print(f"  {r['op']:<18}{r['out_bytes']:>10} B  "
                  f"n={r['group_size']}  wire={r['wire_bytes']:,.0f}{trip}")
    print()


def path_compare(dp: int = 4, batch: int = 8, seq: int = 64,
                 compress: str = "int8-ef"):
    """The per-path fp32-vs-compressed comparison: measured (lowered HLO,
    obs.comm) for the DP grad sync, SP activations and ZeRO refresh;
    analytic (comm/wire.py) for the cross-mesh hetero bridge.  Returns
    {path: {fp32_bytes, compressed_bytes, ratio, fp32_s, compressed_s}}."""
    from hetu_tpu.comm.wire import wire_bytes_per_element
    from hetu_tpu.models.llama import LlamaConfig
    from hetu_tpu.obs.mfu import load_hardware_profile

    hw = load_hardware_profile()
    topo = hw.get("topology") or {}
    intra = float(topo.get("intra_gbps",
                           hw.get("ici_allreduce_gbps", 45.0))) * 1e9
    inter = float(topo.get("inter_gbps", hw.get("dcn_gbps", 6.25))) * 1e9
    paths = {}

    # DP grad sync: the non-zero trainer's collectives ARE the sync
    rep32, _ = lowered_step_report("none", dp=dp, batch=batch, seq=seq)
    rep8, _ = lowered_step_report(compress, dp=dp, batch=batch, seq=seq)
    paths["dp_grad_sync"] = _path_row(
        rep32["total_wire_bytes"], rep8["total_wire_bytes"],
        rep32["predicted_comm_s"], rep8["predicted_comm_s"])

    # SP activations: the convert() gather/scatter pair, per layer
    sp_mode = "int8" if compress.startswith("int8") else "int4"
    sp32 = lowered_sp_report("none")
    spq = lowered_sp_report(sp_mode)
    paths["sp_activations"] = _path_row(
        sp32["total_wire_bytes"], spq["total_wire_bytes"],
        sp32["predicted_comm_s"], spq["predicted_comm_s"])

    # ZeRO-1 param refresh: the all-gather bytes of the zero trainer
    z32, _ = lowered_step_report("none", dp=dp, zero=True, batch=batch,
                                 seq=seq)
    zq, _ = lowered_step_report("none", dp=dp, zero=True, batch=batch,
                                seq=seq, zero_compress=sp_mode)
    ag32 = z32["collectives"].get("all-gather", {}).get("wire_bytes", 0.0)
    agq = zq["collectives"].get("all-gather", {}).get("wire_bytes", 0.0)
    paths["zero_refresh"] = _path_row(ag32, agq, ag32 / intra, agq / intra)

    # MoE expert dispatch: the explicit a2a + combine gather of an
    # ep=8 MoE layer, fp32 vs quantized (nn/moe_dispatch.py; the only
    # collectives the lowered program contains)
    m32 = lowered_moe_report("fp32")
    mq = lowered_moe_report(sp_mode)
    paths["moe_dispatch"] = _path_row(
        m32["total_wire_bytes"], mq["total_wire_bytes"],
        m32["predicted_comm_s"], mq["predicted_comm_s"])

    # hetero-DP/PP bridge: one non-resident group shipping the tiny
    # model's sum-grads across meshes (device_put rides the slow
    # inter-slice/DCN links — comm/wire.py analytic)
    n = float(LlamaConfig.tiny().num_params())
    b32 = 4.0 * n
    bq = wire_bytes_per_element(
        "int8" if compress.startswith("int8") else "int4") * n
    paths["hetero_bridge"] = _path_row(b32, bq, b32 / inter, bq / inter)
    return paths


def _path_row(b32, bq, s32, sq):
    return {"fp32_bytes": b32, "compressed_bytes": bq,
            "ratio": (b32 / bq) if bq else None,
            "fp32_s": s32, "compressed_s": sq}


def _print_paths(paths):
    print("== per-path fp32 vs compressed (measured from lowered HLO; "
          "bridge analytic) ==")
    print(f"{'path':<16}{'fp32 bytes':>14}{'q bytes':>12}{'ratio':>8}"
          f"{'fp32 time':>12}{'q time':>12}")
    for name, r in paths.items():
        print(f"{name:<16}{r['fp32_bytes']:>14,.0f}"
              f"{r['compressed_bytes']:>12,.0f}"
              f"{r['ratio']:>7.2f}x"
              f"{r['fp32_s'] * 1e6:>10.1f}us"
              f"{r['compressed_s'] * 1e6:>10.1f}us")
    print()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Bytes-on-wire table of a compiled train step "
                    "(hardware-free; obs.comm analyzer).")
    ap.add_argument("--compress", default="none",
                    choices=("none", "int8", "int8-ef", "int4", "int4-ef"))
    ap.add_argument("--compare", action="store_true",
                    help="lower fp32 AND compressed variants of every "
                         "wire path, print the per-path table + ratios")
    ap.add_argument("--dp", type=int, default=4)
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO-1 (reduce-scatter/all-gather lowering)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="also print each collective instruction")
    args = ap.parse_args(argv)

    if args.compare:
        cmode = args.compress if args.compress != "none" else "int8-ef"
        paths = path_compare(dp=args.dp, batch=args.batch, seq=args.seq,
                             compress=cmode)
        _print_paths(paths)
        summary = {"paths": paths, "compress": cmode,
                   "ratio": paths["dp_grad_sync"]["ratio"]}
        print(f"bytes-on-wire ratio fp32/{cmode} (dp sync): "
              f"{summary['ratio']:.2f}x")
        print(json.dumps(summary))
        return 0

    rep, table = lowered_step_report(
        args.compress, dp=args.dp, zero=args.zero, batch=args.batch,
        seq=args.seq)
    _print_table(args.compress, rep, table, args.verbose)
    summary = {args.compress: {
        "total_wire_bytes": rep["total_wire_bytes"],
        "num_collectives": rep["num_collectives"],
        "predicted_comm_s": rep["predicted_comm_s"]}}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
