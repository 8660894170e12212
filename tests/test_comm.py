"""Compressed gradient collectives (hetu_tpu/comm) + the bytes-on-wire
analyzer (hetu_tpu.obs.comm): quantize primitives, bucketer, the
shard_map quantized sync, trainer integration (HETU_TPU_GRAD_COMPRESS),
loss parity vs fp32, and the >=3.5x DP-sync byte reduction measured from
real lowered HLO.  See docs/comm_compression.md."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.comm import (BucketPlan, analytic_dp_sync,
                           dequantize_blockwise, ef_quantize,
                           quantize_blockwise, wire_bytes_per_element,
                           wire_factor)
from hetu_tpu.core.mesh import MeshConfig
from hetu_tpu.engine import Trainer, TrainingConfig
from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.parallel import ParallelStrategy


def _batch(n=8, seq=64, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, 250, size=(n, seq)).astype(np.int32)
    return {"input_ids": ids, "labels": ids.copy()}


def _trainer(mode, monkeypatch, *, dp=4, zero=False, scan=False, lr=3e-3):
    if mode is None:
        monkeypatch.delenv("HETU_TPU_GRAD_COMPRESS", raising=False)
    else:
        monkeypatch.setenv("HETU_TPU_GRAD_COMPRESS", mode)
    cfg = LlamaConfig.tiny(remat=False, use_scan=scan)
    st = ParallelStrategy(mesh=MeshConfig(dp=dp), zero=zero)
    tc = TrainingConfig(global_batch_size=8, micro_batch_size=8 // dp,
                        seq_len=64, lr=lr, warmup_steps=2, total_steps=40,
                        log_every=1000)
    return Trainer(LlamaLMHeadModel(cfg, st), tc, st).build()


def _lowered(tr, hb):
    key = tuple(sorted((k, tuple(v.shape)) for k, v in hb.items()))
    return tr._compiled_for_shape(hb, key)


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4096,)), jnp.float32)
    q, s = quantize_blockwise(x, 256)
    assert q.dtype == jnp.int8 and q.shape == (16, 256) and s.shape == (16,)
    err = np.abs(np.asarray(dequantize_blockwise(q, s)) - np.asarray(x))
    # absmax int8: per-block error bounded by scale/2 = absmax/254
    bound = np.repeat(np.asarray(s), 256) / 2 + 1e-9
    assert (err <= bound).all()


def test_quantize_rejects_ragged():
    with pytest.raises(ValueError, match="multiple"):
        quantize_blockwise(jnp.zeros(100), 256)


def test_stochastic_rounding_is_unbiased():
    # a constant half-step value: deterministic rounding is maximally
    # biased, stochastic rounding must average to the true value
    x = jnp.full((256,), 0.5 * (1.0 / 127.0), jnp.float32)
    x = x.at[0].set(1.0)  # pins the block scale to 1/127
    acc = np.zeros(256)
    for i in range(200):
        q, s = quantize_blockwise(x, 256, stochastic=True,
                                  rng=jax.random.key(i))
        acc += np.asarray(dequantize_blockwise(q, s))
    mean = float((acc / 200)[1:].mean())
    true = float(x[1])
    assert abs(mean - true) / true < 0.03, (mean, true)
    # the deterministic rounding of the same half-step value IS biased
    qd, sd = quantize_blockwise(x, 256)
    det = float(np.asarray(dequantize_blockwise(qd, sd))[1:].mean())
    assert abs(det - true) / true > 0.5, (det, true)


def test_ef_quantize_residual_closes_the_error():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2048,)), jnp.float32)
    r0 = jnp.asarray(rng.normal(size=(2048,)) * 0.01, jnp.float32)
    q, s, r1 = ef_quantize(x, r0, 256)
    # residual is EXACTLY what the wire lost: deq + r1 == x + r0
    np.testing.assert_allclose(
        np.asarray(dequantize_blockwise(q, s) + r1),
        np.asarray(x + r0), rtol=0, atol=1e-6)


def test_wire_model():
    assert wire_bytes_per_element("none") == 4.0
    assert wire_bytes_per_element("int8") == pytest.approx(1.015625)
    assert wire_factor("int8-ef") == pytest.approx(0.25390625)
    rep = analytic_dp_sync(1e6, 8, ici_gbps=45.0)
    assert rep["ratio"] == pytest.approx(4 / 1.015625)
    assert rep["fp32_wire_bytes"] == pytest.approx(2 * 7 / 8 * 4e6)
    assert rep["fp32_comm_s"] > rep["int8_comm_s"] > 0
    assert analytic_dp_sync(1e6, 1)["fp32_wire_bytes"] == 0.0


# ---------------------------------------------------------------------------
# bucketer
# ---------------------------------------------------------------------------

def test_bucket_plan_pack_unpack_identity():
    rng = np.random.default_rng(2)
    tree = {"a": jnp.asarray(rng.normal(size=(3, 5)), jnp.float32),
            "b": [jnp.asarray(rng.normal(size=(7,)), jnp.bfloat16),
                  jnp.asarray(rng.normal(size=(2, 2, 2)), jnp.float32)],
            "c": jnp.asarray(rng.normal(size=(1000,)), jnp.float32)}
    plan = BucketPlan.build(tree, bucket_elems=512, multiple=128)
    flats = plan.pack(tree)
    assert all(f.shape[0] % 128 == 0 for f in flats)
    # "c" (1000 >= 512) gets its own bucket; the small leaves fuse
    assert plan.num_buckets == 2
    out = plan.unpack(flats)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-2)


def test_bucket_plan_fuses_small_leaves():
    tree = [jnp.zeros((10,)) for _ in range(20)]
    plan = BucketPlan.build(tree, bucket_elems=1 << 20, multiple=64)
    assert plan.num_buckets == 1
    assert plan.total_elements == 256  # 200 padded up to 64-multiple
    assert plan.unpack(plan.pack(tree))[7].shape == (10,)


# ---------------------------------------------------------------------------
# the quantized sync itself (shard_map over dp on the virtual mesh)
# ---------------------------------------------------------------------------

def test_quantized_grad_sync_matches_psum():
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P
    from hetu_tpu.comm.grad_sync import (ef_init, ef_specs,
                                         quantized_grad_sync)
    from hetu_tpu.core.mesh import create_mesh
    dp = 8
    mesh = create_mesh(MeshConfig(dp=dp))
    tree = {"w": jax.ShapeDtypeStruct((64, 64), jnp.float32),
            "b": jax.ShapeDtypeStruct((64,), jnp.float32)}
    plan = BucketPlan.build(tree, multiple=dp * 256)
    rng = np.random.default_rng(3)
    # per-replica distinct grads, laid out [dp, ...] and split over dp
    gw = jnp.asarray(rng.normal(size=(dp, 64, 64)), jnp.float32)
    gb = jnp.asarray(rng.normal(size=(dp, 64)), jnp.float32)

    def body(gw, gb, ef):
        g = {"w": gw[0], "b": gb[0]}
        out, new_ef = quantized_grad_sync(g, "dp", dp, plan, "int8-ef", ef)
        return out, new_ef

    especs = ef_specs(plan)
    with mesh:
        ef0 = jax.jit(lambda: ef_init(plan, dp),
                      out_shardings=jax.tree.map(
                          lambda sp: NamedSharding(mesh, sp), especs,
                          is_leaf=lambda x: isinstance(x, P)))()
        out, ef1 = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P("dp"), P("dp"), especs),
            out_specs=({"w": P(), "b": P()}, especs),
            check_vma=False))(gw, gb, ef0)
    ref_w, ref_b = np.asarray(gw).sum(0), np.asarray(gb).sum(0)
    # two int8 stages: relative error ~1/127 per stage of the block absmax
    np.testing.assert_allclose(np.asarray(out["w"]), ref_w,
                               atol=0.06 * np.abs(ref_w).max())
    np.testing.assert_allclose(np.asarray(out["b"]), ref_b,
                               atol=0.06 * np.abs(ref_b).max())
    # EF state moved away from zero (it remembers this round's error)
    assert float(jnp.abs(ef1["a2a"][0]).max()) > 0
    assert float(jnp.abs(ef1["ag"][0]).max()) > 0


# ---------------------------------------------------------------------------
# trainer integration
# ---------------------------------------------------------------------------

def test_int8_ef_trains_to_fp32_loss_parity(monkeypatch):
    """Acceptance: int8+error-feedback grad sync reaches the fp32 sync's
    final loss within 1% over the test horizon."""
    hb = _batch()
    steps = 12
    tr32 = _trainer("none", monkeypatch)
    l32 = [float(tr32.train_step(hb)["loss"]) for _ in range(steps)]
    tr8 = _trainer("int8-ef", monkeypatch)
    l8 = [float(tr8.train_step(hb)["loss"]) for _ in range(steps)]
    assert l32[-1] < l32[0] - 0.5  # both actually train
    assert l8[-1] < l8[0] - 0.5
    assert abs(l8[-1] - l32[-1]) / l32[-1] < 0.01, (l8[-1], l32[-1])
    # the EF residuals ride in the optimizer state and are alive
    assert "ef" in tr8.opt_state
    assert float(jnp.abs(tr8.opt_state["ef"]["a2a"][0]).max()) > 0


def test_int8_sync_cuts_dp_bytes_3_5x(monkeypatch):
    """Acceptance: obs.comm reports >=3.5x fewer DP-sync bytes-on-wire at
    int8 vs fp32 on the same lowered step (scan-free model: static HLO
    counts are exact)."""
    from hetu_tpu.obs.comm import collective_report
    hb = _batch()
    rep32 = collective_report(_lowered(_trainer("none", monkeypatch), hb))
    rep8 = collective_report(
        _lowered(_trainer("int8-ef", monkeypatch), hb))
    assert rep32["total_wire_bytes"] >= 3.5 * rep8["total_wire_bytes"], (
        rep32, rep8)
    # the compressed step's sync rides int8 all-to-all + all-gather
    assert rep8["collectives"]["all-to-all"]["count"] >= 1
    assert rep8["collectives"]["all-gather"]["count"] >= 1
    assert rep8["predicted_comm_s"] < rep32["predicted_comm_s"]


def test_ef_residuals_rescale_on_loss_scale_change(monkeypatch):
    """Known-limit fix (PR 2 docs): EF residuals live in SCALED-grad
    units, so a dynamic loss-scale change must rescale them by
    new/old — otherwise the next step's error feedback is off by the
    ratio.  Two identical fp16 int8-ef trainers, one whose scaler GROWS
    after the first finite step (growth_interval=1) and one whose scale
    never moves: step 1's arithmetic is identical (the scale moves
    AFTER the update), so the only difference in the stored residuals
    must be exactly the growth factor."""
    from hetu_tpu.optim.grad_scaler import GradScaler

    def build(growth_interval):
        monkeypatch.setenv("HETU_TPU_GRAD_COMPRESS", "int8-ef")
        cfg = LlamaConfig.tiny(remat=False, compute_dtype=jnp.float16)
        st = ParallelStrategy(mesh=MeshConfig(dp=4))
        tc = TrainingConfig(global_batch_size=8, micro_batch_size=2,
                            seq_len=64, lr=3e-3, warmup_steps=2,
                            total_steps=40, log_every=1000)
        tr = Trainer(LlamaLMHeadModel(cfg, st), tc, st)
        assert tr._scaler is not None  # fp16 -> dynamic scaling on
        tr._scaler = GradScaler(init_scale=2.0 ** 8,
                                growth_interval=growth_interval)
        return tr.build()

    hb = _batch()
    grow = build(1)
    hold = build(10 ** 9)
    mg = grow.train_step(hb)
    mh = hold.train_step(hb)
    assert float(mg["amp_skipped"]) == float(mh["amp_skipped"]) == 0.0
    assert float(mg["loss_scale"]) == 2.0 * float(mh["loss_scale"])
    leaves_g = jax.tree.leaves(grow.opt_state["ef"])
    leaves_h = jax.tree.leaves(hold.opt_state["ef"])
    assert any(float(jnp.abs(l).max()) > 0 for l in leaves_h)
    for g, h in zip(leaves_g, leaves_h):
        np.testing.assert_allclose(np.asarray(g), 2.0 * np.asarray(h),
                                   rtol=1e-6)


def test_int8_mode_without_ef_keeps_state_layout(monkeypatch):
    tr = _trainer("int8", monkeypatch)
    hb = _batch()
    l0 = float(tr.train_step(hb)["loss"])
    l1 = float(tr.train_step(hb)["loss"])
    assert np.isfinite(l0) and l1 < l0
    assert "ef" not in tr.opt_state  # plain int8 carries no residuals


def test_compress_with_zero1_trains(monkeypatch):
    tr = _trainer("int8-ef", monkeypatch, zero=True)
    hb = _batch()
    losses = [float(tr.train_step(hb)["loss"]) for _ in range(4)]
    assert losses[-1] < losses[0]


def test_restore_pre_ef_checkpoint_with_ef_enabled(tmp_path, monkeypatch):
    """Enabling int8-ef AFTER a checkpoint was written must resume: the
    base state restores, the residuals cold-start at zero."""
    def build(mode):
        if mode is None:
            monkeypatch.delenv("HETU_TPU_GRAD_COMPRESS", raising=False)
        else:
            monkeypatch.setenv("HETU_TPU_GRAD_COMPRESS", mode)
        cfg = LlamaConfig.tiny(remat=False)
        st = ParallelStrategy(mesh=MeshConfig(dp=4), zero=False)
        tc = TrainingConfig(global_batch_size=8, micro_batch_size=2,
                            seq_len=64, lr=3e-3, warmup_steps=2,
                            total_steps=40, log_every=1000,
                            ckpt_dir=str(tmp_path))
        return Trainer(LlamaLMHeadModel(cfg, st), tc, st)

    hb = _batch()
    tr = build(None).build()
    l0 = float(tr.train_step(hb)["loss"])
    tr.save(wait=True)
    tr2 = build("int8-ef").restore()
    assert tr2.global_step == 1
    assert "ef" in tr2.opt_state  # cold-start zeros survived the repair
    assert float(jnp.abs(tr2.opt_state["ef"]["a2a"][0]).max()) == 0.0
    l1 = float(tr2.train_step(hb)["loss"])
    assert np.isfinite(l1) and l1 < l0


def test_compress_rejects_non_dp_strategies(monkeypatch):
    monkeypatch.setenv("HETU_TPU_GRAD_COMPRESS", "int8")
    cfg = LlamaConfig.tiny(remat=False)
    st = ParallelStrategy(mesh=MeshConfig(dp=2, tp=2))
    tc = TrainingConfig(global_batch_size=8, micro_batch_size=4, seq_len=64)
    with pytest.raises(ValueError, match="homogeneous DP"):
        Trainer(LlamaLMHeadModel(cfg, st), tc, st)


def test_compress_noop_on_dp1(monkeypatch):
    monkeypatch.setenv("HETU_TPU_GRAD_COMPRESS", "int8-ef")
    cfg = LlamaConfig.tiny(remat=False)
    st = ParallelStrategy(mesh=MeshConfig())
    tc = TrainingConfig(global_batch_size=4, micro_batch_size=4, seq_len=64)
    tr = Trainer(LlamaLMHeadModel(cfg, st), tc, st)
    assert tr._grad_compress == "none"  # dp=1: nothing to sync


def test_flag_rejects_unknown_mode(monkeypatch):
    from hetu_tpu.utils import flags
    monkeypatch.setenv("HETU_TPU_GRAD_COMPRESS", "int2")
    with pytest.raises(ValueError, match="choices"):
        flags.str_flag("HETU_TPU_GRAD_COMPRESS")


def test_int4_sync_trains_and_cuts_bytes_7x(monkeypatch):
    """int4 (packed two-per-byte) halves the int8 wire again: >=7x fewer
    DP-sync bytes than fp32, measured from lowered HLO, and still
    trains."""
    from hetu_tpu.obs.comm import collective_report
    hb = _batch()
    rep32 = collective_report(_lowered(_trainer("none", monkeypatch), hb))
    tr4 = _trainer("int4-ef", monkeypatch)
    rep4 = collective_report(_lowered(tr4, hb))
    assert rep32["total_wire_bytes"] >= 7.0 * rep4["total_wire_bytes"], (
        rep32["total_wire_bytes"], rep4["total_wire_bytes"])
    losses = [float(tr4.train_step(hb)["loss"]) for _ in range(4)]
    assert losses[-1] < losses[0]
    assert "ef" in tr4.opt_state


# ---------------------------------------------------------------------------
# the analyzer on synthetic HLO (exact wire formulas, group parsing)
# ---------------------------------------------------------------------------

_SYNTH = """\
HloModule m
%x1 = f32[1024]{0} all-reduce(f32[1024]{0} %a), replica_groups={{0,1,2,3}}
%x2 = f32[256]{0} reduce-scatter(f32[1024]{0} %b), replica_groups={{0,1,2,3}}, dimensions={0}
%x3 = s8[4,256]{1,0} all-gather(s8[1,256]{1,0} %c), replica_groups=[1,4]<=[4], dimensions={0}
%x4 = (f32[8]{0}, f32[8]{0}) all-to-all(f32[8]{0} %d, f32[8]{0} %e), replica_groups={{0,1}}
%x5 = f32[64]{0} collective-permute(f32[64]{0} %f), source_target_pairs={{0,1}}
%x6 = f32[32]{0} all-reduce-start(f32[32]{0} %g), replica_groups={{0,1}}
%x7 = f32[32]{0} all-reduce-done(f32[32]{0} %x6)
%x8 = (f32[1,128]{1,0}, f32[4,128]{1,0}) all-gather-start(f32[1,128]{1,0} %h), replica_groups={{0,1,2,3}}, dimensions={0}
%x9 = f32[4,128]{1,0} all-gather-done((f32[1,128]{1,0}, f32[4,128]{1,0}) %x8)
%xa = (f32[1024]{0}, f32[256]{0}) reduce-scatter-start(f32[1024]{0} %i), replica_groups={{0,1,2,3}}, dimensions={0}
"""


def test_analyzer_wire_formulas():
    from hetu_tpu.obs.comm import collective_report, collective_table
    rows = {(r["op"], r["out_bytes"]): r for r in collective_table(_SYNTH)}
    # ring all-reduce: 2(n-1)/n * payload
    assert rows[("all-reduce", 4096)]["wire_bytes"] == pytest.approx(
        2 * 3 / 4 * 4096)
    # reduce-scatter: output is the shard -> (n-1) * shard
    assert rows[("reduce-scatter", 1024)]["wire_bytes"] == pytest.approx(
        3 * 1024)
    # all-gather (iota groups [1,4]<=[4]): (n-1)/n * gathered output
    assert rows[("all-gather", 1024)]["group_size"] == 4
    assert rows[("all-gather", 1024)]["wire_bytes"] == pytest.approx(
        3 / 4 * 1024)
    # tuple all-to-all: output components sum to the local buffer
    assert rows[("all-to-all", 64)]["wire_bytes"] == pytest.approx(
        1 / 2 * 64)
    # collective-permute: one hop
    assert rows[("collective-permute", 256)]["wire_bytes"] == 256
    # -start counted once, -done skipped
    assert rows[("all-reduce", 128)]["wire_bytes"] == pytest.approx(
        2 * 1 / 2 * 128)
    # async tuple forms carry the operand buffer in the output tuple: only
    # the transfer buffer (largest component) counts, never operand+result
    assert rows[("all-gather", 2048)]["wire_bytes"] == pytest.approx(
        3 / 4 * 2048)  # result f32[4,128], NOT + operand f32[1,128]
    # reduce-scatter-start payload is the full input -> (n-1)/n form
    assert rows[("reduce-scatter", 4096)]["wire_bytes"] == pytest.approx(
        3 / 4 * 4096)
    rep = collective_report(_SYNTH, hw={"chip": "t", "ici_allreduce_gbps": 45,
                                        "ici_p2p_gbps": 90})
    assert rep["num_collectives"] == 8
    assert rep["collectives"]["all-reduce"]["count"] == 2
    assert rep["total_wire_bytes"] == pytest.approx(
        sum(r["wire_bytes"] for r in rows.values()))
    assert rep["predicted_comm_s"] > 0


def test_analyzer_empty_program():
    from hetu_tpu.obs.comm import collective_report
    rep = collective_report("HloModule m\n%r = f32[8]{0} add(%a, %b)\n",
                            hw={"chip": "t"})
    assert rep["num_collectives"] == 0
    assert rep["total_wire_bytes"] == 0.0


# ---------------------------------------------------------------------------
# RunLog compile events + the CLI tool
# ---------------------------------------------------------------------------

def test_compile_event_carries_comm_bytes(tmp_path, monkeypatch):
    monkeypatch.setenv("HETU_TPU_RUNLOG", str(tmp_path / "runlog.jsonl"))
    monkeypatch.setenv("HETU_TPU_GRAD_COMPRESS", "int8-ef")
    tr = _trainer("int8-ef", monkeypatch)
    tr.train_step(_batch())
    tr.close()
    from hetu_tpu.obs.runlog import RunLog
    recs = [r for r in RunLog.read(str(tmp_path / "runlog.jsonl"))
            if r.get("kind") == "compile"]
    assert recs and recs[-1].get("comm_bytes", 0) > 0
    assert recs[-1].get("grad_compress") == "int8-ef"
    assert recs[-1]["collectives"].get("all-to-all", 0) >= 1


def test_tools_comm_report_smoke(capsys):
    import tools_comm_report
    rc = tools_comm_report.main(["--dp", "2", "--compress", "none",
                                 "--batch", "4", "--seq", "32"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "all-reduce" in out and "TOTAL" in out
    import json
    summary = json.loads(out.strip().splitlines()[-1])
    assert summary["none"]["total_wire_bytes"] > 0


@pytest.mark.slow
def test_tools_comm_report_path_table(capsys):
    """--compare prints the per-path fp32-vs-compressed table with every
    path >= 3x (the components are tier-1-covered individually; the full
    CLI pass lowers six programs, hence slow)."""
    import tools_comm_report
    rc = tools_comm_report.main(["--compare", "--seq", "32"])
    assert rc == 0
    out = capsys.readouterr().out
    import json
    summary = json.loads(out.strip().splitlines()[-1])
    for path in ("dp_grad_sync", "sp_activations", "zero_refresh",
                 "hetero_bridge"):
        assert summary["paths"][path]["ratio"] >= 3.0, (path, summary)


# ---------------------------------------------------------------------------
# quantized ZeRO-1/2 param refresh (HETU_TPU_ZERO_COMPRESS)
# ---------------------------------------------------------------------------

def _zc_trainer(zc, monkeypatch, *, grad=None, zero=True, dp=4, lr=3e-3,
                zero_stage=1):
    for name, val in (("HETU_TPU_ZERO_COMPRESS", zc),
                      ("HETU_TPU_GRAD_COMPRESS", grad)):
        if val is None:
            monkeypatch.delenv(name, raising=False)
        else:
            monkeypatch.setenv(name, val)
    cfg = LlamaConfig.tiny(remat=False, use_scan=False)
    st = ParallelStrategy(mesh=MeshConfig(dp=dp), zero=zero,
                          zero_stage=zero_stage)
    tc = TrainingConfig(global_batch_size=8, micro_batch_size=8 // dp,
                        seq_len=64, lr=lr, warmup_steps=2, total_steps=40,
                        log_every=1000)
    return Trainer(LlamaLMHeadModel(cfg, st), tc, st).build()


def test_zero_refresh_int8_cuts_gather_bytes_3x(monkeypatch):
    """Acceptance: the ZeRO-1 param refresh moves >=3x fewer all-gather
    bytes with int8 enabled, measured from lowered HLO."""
    from hetu_tpu.obs.comm import collective_report
    hb = _batch()
    rep32 = collective_report(_lowered(_zc_trainer(None, monkeypatch), hb))
    rep8 = collective_report(_lowered(_zc_trainer("int8", monkeypatch), hb))
    ag32 = rep32["collectives"]["all-gather"]["wire_bytes"]
    ag8 = rep8["collectives"]["all-gather"]["wire_bytes"]
    assert ag32 >= 3.0 * ag8, (ag32, ag8)


def test_zero_refresh_int8_loss_parity(monkeypatch):
    """Acceptance: quantized delta-gather refresh reaches the fp32
    refresh's final loss within 1%."""
    hb = _batch()
    steps = 12
    tr32 = _zc_trainer(None, monkeypatch)
    l32 = [float(tr32.train_step(hb)["loss"]) for _ in range(steps)]
    tr8 = _zc_trainer("int8", monkeypatch)
    l8 = [float(tr8.train_step(hb)["loss"]) for _ in range(steps)]
    assert l32[-1] < l32[0] - 0.5
    assert l8[-1] < l8[0] - 0.5
    assert abs(l8[-1] - l32[-1]) / l32[-1] < 0.01, (l8[-1], l32[-1])


@pytest.mark.slow
def test_zero_refresh_composes_with_grad_compress_and_stage2(monkeypatch):
    tr = _zc_trainer("int8", monkeypatch, grad="int8-ef", zero_stage=2)
    hb = _batch()
    losses = [float(tr.train_step(hb)["loss"]) for _ in range(4)]
    assert losses[-1] < losses[0]
    assert "ef" in tr.opt_state


def test_zero_compress_requires_zero(monkeypatch):
    monkeypatch.setenv("HETU_TPU_ZERO_COMPRESS", "int8")
    cfg = LlamaConfig.tiny(remat=False)
    st = ParallelStrategy(mesh=MeshConfig(dp=4), zero=False)
    tc = TrainingConfig(global_batch_size=8, micro_batch_size=2, seq_len=64)
    with pytest.raises(ValueError, match="zero=False"):
        Trainer(LlamaLMHeadModel(cfg, st), tc, st)


# ---------------------------------------------------------------------------
# two-level (HetCCL) topology routing in the trainer
# ---------------------------------------------------------------------------

def _topo_profile(tmp_path, slice_devices=4):
    import json as _json
    from hetu_tpu.obs.mfu import load_hardware_profile
    hw = load_hardware_profile()
    hw["topology"] = {"slice_devices": slice_devices,
                      "intra_gbps": 45.0, "inter_gbps": 6.25}
    p = tmp_path / "hw.json"
    p.write_text(_json.dumps(hw))
    return str(p)


@pytest.mark.slow
def test_trainer_two_level_sync_trains_close_to_flat(tmp_path, monkeypatch):
    monkeypatch.setenv("HETU_TPU_HW_PROFILE", _topo_profile(tmp_path))
    hb = _batch()
    flat = _trainer("int8", monkeypatch, dp=8)
    lf = [float(flat.train_step(hb)["loss"]) for _ in range(6)]
    monkeypatch.setenv("HETU_TPU_COMM_TOPOLOGY", "two_level")
    two = _trainer("int8", monkeypatch, dp=8)
    assert two._comm_topology is not None
    lt = [float(two.train_step(hb)["loss"]) for _ in range(6)]
    assert lt[-1] < lt[0] - 0.3
    assert abs(lt[-1] - lf[-1]) / lf[-1] < 0.05, (lt[-1], lf[-1])


def test_trainer_two_level_ef_state_carries_chunk_residuals(tmp_path,
                                                            monkeypatch):
    # EF + two_level used to raise; the hierarchical schedule now carries
    # residuals at all four quantize points: "a2a"/"ag" reuse the flat
    # layout, "tl_inter"/"tl_intra" add the chunk-sized points
    monkeypatch.setenv("HETU_TPU_HW_PROFILE", _topo_profile(tmp_path))
    monkeypatch.setenv("HETU_TPU_COMM_TOPOLOGY", "two_level")
    tr = _trainer("int8-ef", monkeypatch, dp=8)
    assert tr._comm_topology is not None
    ef = tr.opt_state["ef"]
    assert set(ef) == {"a2a", "tl_inter", "ag", "tl_intra"}
    k = tr._comm_topology.slice_devices
    for L, ti, tx in zip(tr._bucket_plan.sizes, ef["tl_inter"],
                         ef["tl_intra"]):
        assert ti.shape == (8, L // k) and tx.shape == (8, L // k)
    tr.train_step(_batch())
    live = max(float(jnp.abs(x).max())
               for x in tr.opt_state["ef"]["tl_inter"])
    assert live > 0  # the residual memory is actually fed back


@pytest.mark.slow
def test_trainer_two_level_ef_trains_close_to_flat_ef(tmp_path, monkeypatch):
    monkeypatch.setenv("HETU_TPU_HW_PROFILE", _topo_profile(tmp_path))
    hb = _batch()
    flat = _trainer("int8-ef", monkeypatch, dp=8)
    lf = [float(flat.train_step(hb)["loss"]) for _ in range(6)]
    monkeypatch.setenv("HETU_TPU_COMM_TOPOLOGY", "two_level")
    two = _trainer("int8-ef", monkeypatch, dp=8)
    lt = [float(two.train_step(hb)["loss"]) for _ in range(6)]
    assert lt[-1] < lt[0] - 0.3
    assert abs(lt[-1] - lf[-1]) / lf[-1] < 0.05, (lt[-1], lf[-1])


# ---------------------------------------------------------------------------
# dropout keys fold the replica index (PR 2 known-limit fix)
# ---------------------------------------------------------------------------

def test_per_replica_keys_differ_across_replicas():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from hetu_tpu.comm.grad_sync import per_replica_keys
    from hetu_tpu.core.mesh import create_mesh
    mesh = create_mesh(MeshConfig(dp=4))
    keys = jax.random.split(jax.random.key(0), 2)

    def body(keys):
        k = per_replica_keys(keys, "dp")
        bits = jax.vmap(
            lambda kk: jax.random.bits(kk, (4,), jnp.uint32))(k)
        return bits[None]

    out = np.asarray(jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(),), out_specs=P("dp"),
        check_vma=False))(keys))          # [dp, n_micro, 4]
    flat = out.reshape(4, -1)
    for i in range(4):
        for j in range(i + 1, 4):
            assert not np.array_equal(flat[i], flat[j]), (i, j)


def test_compressed_sync_with_dropout_trains(monkeypatch):
    """Regression for the PR 2 limit: dropout + compressed sync now runs
    with per-replica independent masks (keys fold the dp axis index)."""
    monkeypatch.setenv("HETU_TPU_GRAD_COMPRESS", "int8-ef")
    cfg = LlamaConfig.tiny(remat=False, use_scan=False, hidden_dropout=0.1)
    st = ParallelStrategy(mesh=MeshConfig(dp=4), zero=False)
    tc = TrainingConfig(global_batch_size=8, micro_batch_size=2, seq_len=64,
                        lr=3e-3, warmup_steps=2, total_steps=40,
                        log_every=1000, dropout_deterministic=False)
    tr = Trainer(LlamaLMHeadModel(cfg, st), tc, st).build()
    hb = _batch()
    losses = [float(tr.train_step(hb)["loss"]) for _ in range(6)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# wire.py <-> analyzer cross-validation (formula drift tripwire)
# ---------------------------------------------------------------------------

def test_wire_formulas_match_analyzer_on_lowered_programs():
    """Every ring formula in comm/wire.py must agree with what the
    analyzer reports for a real lowered program emitting that collective
    — catches drift as new variants land."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from hetu_tpu.comm.wire import ring_wire_bytes
    from hetu_tpu.core.mesh import create_mesh
    from hetu_tpu.obs.comm import collective_table
    n = 4
    mesh = create_mesh(MeshConfig(dp=n))
    N = 1024                      # local f32 elements
    payload = N * 4.0

    cases = {
        "all-reduce": lambda x: jax.lax.psum(x, "dp"),
        "reduce-scatter": lambda x: jax.lax.psum_scatter(
            x, "dp", scatter_dimension=0, tiled=True),
        "all-gather": lambda x: jax.lax.all_gather(
            x, "dp", axis=0, tiled=True),
        "all-to-all": lambda x: jax.lax.all_to_all(
            x.reshape(n, N // n), "dp", split_axis=0, concat_axis=0
        ).reshape(-1),
    }
    expected_payload = {
        # analyzer formulas are output/buffer-anchored; translate each
        # op's N-element local input into its formula payload
        "all-reduce": payload,
        "reduce-scatter": payload,             # (n-1) * shard == (n-1)/n * in
        "all-gather": payload * n,             # gathered output
        "all-to-all": payload,                 # local buffer
    }
    for op, fn in cases.items():
        lowered = jax.jit(shard_map(
            fn, mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False)).lower(jnp.zeros((N,), jnp.float32)).compile()
        rows = [r for r in collective_table(lowered) if r["op"] == op]
        assert rows, f"no {op} in lowered HLO"
        measured = sum(r["wire_bytes"] for r in rows)
        analytic = ring_wire_bytes(op, expected_payload[op], n)
        assert measured == pytest.approx(analytic, rel=1e-6), (
            op, measured, analytic)


# ---------------------------------------------------------------------------
# analyzer while-loop trip counts (PR 2 static-undercount fix)
# ---------------------------------------------------------------------------

_WHILE_SYNTH = """\
HloModule m
%body.1 (p: (s32[], f32[1024])) -> (s32[], f32[1024]) {
  %x = f32[1024]{0} all-reduce(f32[1024]{0} %a), replica_groups={{0,1,2,3}}
}
%cond.1 (p: (s32[], f32[1024])) -> pred[] {
  %gte = s32[] get-tuple-element((s32[], f32[1024]) %p), index=0
  %c5 = s32[] constant(8)
  ROOT %cmp = pred[] compare(s32[] %gte, s32[] %c5), direction=LT
}
ENTRY %main (a: f32[1024]) -> f32[1024] {
  %w = (s32[], f32[1024]) while((s32[], f32[1024]) %t), condition=%cond.1, body=%body.1
  %y = f32[512]{0} all-gather(f32[128]{0} %b), replica_groups={{0,1,2,3}}, dimensions={0}
}
"""


def test_analyzer_multiplies_while_body_collectives():
    from hetu_tpu.obs.comm import collective_report
    rep = collective_report(_WHILE_SYNTH, hw={
        "chip": "t", "ici_allreduce_gbps": 45, "ici_p2p_gbps": 90})
    assert rep["collectives"]["all-reduce"]["count"] == 8
    assert rep["collectives"]["all-reduce"]["wire_bytes"] == pytest.approx(
        8 * 2 * 3 / 4 * 4096)
    assert rep["collectives"]["all-gather"]["count"] == 1  # outside loop
    assert "dynamic_trip_count" not in rep


def test_analyzer_flags_dynamic_trip_count():
    from hetu_tpu.obs.comm import collective_report
    dyn = _WHILE_SYNTH.replace("  %c5 = s32[] constant(8)\n", "").replace(
        "%c5", "%gte2")
    rep = collective_report(dyn, hw={"chip": "t"})
    assert rep.get("dynamic_trip_count") is True
    assert rep["collectives"]["all-reduce"]["count"] == 1  # counted once


def test_analyzer_trip_count_nonzero_start_fori_loop():
    """fori_loop(2, 10) must count 8 trips: XLA's while canonicalization
    rebases the induction to 0 and folds the start into the compare
    bound before the post-optimization text the analyzer parses — this
    pins that assumption."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from hetu_tpu.core.mesh import create_mesh
    from hetu_tpu.obs.comm import collective_report

    mesh = create_mesh(MeshConfig(dp=4))

    def step(x):
        def body(i, c):
            return c + jax.lax.psum(c, "dp")
        return jax.lax.fori_loop(2, 10, body, x[0])[None]

    compiled = jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"),
        check_vma=False)).lower(jnp.ones((4, 256))).compile()
    rep = collective_report(compiled, hw={
        "chip": "t", "ici_allreduce_gbps": 45, "ici_p2p_gbps": 90})
    assert rep["collectives"]["all-reduce"]["count"] == 8
    assert "dynamic_trip_count" not in rep


def test_analyzer_counts_real_scanned_collectives():
    """A real lax.scan with a psum inside lowers to a while whose trip
    count the analyzer must recover (the documented PR 2 undercount)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from hetu_tpu.core.mesh import create_mesh
    from hetu_tpu.obs.comm import collective_report

    mesh = create_mesh(MeshConfig(dp=4))

    def step(x):
        def body(c, _):
            return c + jax.lax.psum(c, "dp"), None
        y, _ = jax.lax.scan(body, x[0], None, length=5)
        return y[None]

    compiled = jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"),
        check_vma=False)).lower(jnp.ones((4, 512))).compile()
    rep = collective_report(compiled, hw={
        "chip": "t", "ici_allreduce_gbps": 45, "ici_p2p_gbps": 90})
    assert rep["collectives"]["all-reduce"]["count"] == 5
    assert "dynamic_trip_count" not in rep


# ---------------------------------------------------------------------------
# hardware-profile schema validation (obs.mfu)
# ---------------------------------------------------------------------------

def test_hardware_profile_validates_on_load():
    from hetu_tpu.obs.mfu import load_hardware_profile
    hw = load_hardware_profile()          # the repo profile must be valid
    assert hw["topology"]["slice_devices"] >= 1


@pytest.mark.parametrize("mutate,key", [
    (lambda hw: hw.pop("ici_allreduce_gbps"), "ici_allreduce_gbps"),
    (lambda hw: hw.update(bf16_tflops=-1), "bf16_tflops"),
    (lambda hw: hw.update(chip=7), "chip"),
    (lambda hw: hw["topology"].pop("inter_gbps"), "topology.inter_gbps"),
    (lambda hw: hw["topology"].update(slice_shape=[3, 2]),
     "topology.slice_shape"),
    (lambda hw: hw.update(measured={"x": "nan?"}), "measured.x"),
])
def test_hardware_profile_schema_names_offending_key(mutate, key):
    import copy
    from hetu_tpu.obs.mfu import (load_hardware_profile,
                                  validate_hardware_profile)
    hw = copy.deepcopy(load_hardware_profile())
    mutate(hw)
    with pytest.raises(ValueError, match=key.replace(".", r"\.")):
        validate_hardware_profile(hw, "unit")


def test_hardware_profile_bad_file_is_loud(tmp_path, monkeypatch):
    bad = tmp_path / "hw.json"
    bad.write_text('{"chip": "v5e"}')
    monkeypatch.setenv("HETU_TPU_HW_PROFILE", str(bad))
    from hetu_tpu.obs.mfu import load_hardware_profile
    with pytest.raises(ValueError, match="bf16_tflops"):
        load_hardware_profile()
    bad.write_text("{not json")
    with pytest.raises(ValueError, match="not valid JSON"):
        load_hardware_profile()


# ---------------------------------------------------------------------------
# cost model: the searcher sees the quantized wire factors
# ---------------------------------------------------------------------------

def test_cost_model_ranking_reflects_wire_factors():
    from hetu_tpu.search.cost_model import CostModel, StrategyCandidate
    from hetu_tpu.search.profiler import HardwareProfile
    hw = HardwareProfile(topology={"slice_devices": 4, "intra_gbps": 45.0,
                                   "inter_gbps": 6.25})
    cm = CostModel(hw=hw, num_layers=12, hidden=1024, intermediate=4096,
                   vocab=32000, num_params=4e8, global_batch=64,
                   seq_len=2048)
    base = cm.step_time(StrategyCandidate(dp=8, zero=True))
    gc8 = cm.step_time(StrategyCandidate(dp=8, zero=True,
                                         grad_compress="int8"))
    gc4 = cm.step_time(StrategyCandidate(dp=8, zero=True,
                                         grad_compress="int4"))
    two = cm.step_time(StrategyCandidate(dp=8, zero=True,
                                         grad_compress="int8",
                                         comm_topology="two_level"))
    zr = cm.step_time(StrategyCandidate(dp=8, zero=True,
                                        zero_refresh="int8"))
    assert base > gc8 > gc4          # more compression, faster
    assert gc8 > two                 # hierarchy beats the flat pod ring
    assert base > zr                 # refresh compression alone helps
    sp0 = cm.step_time(StrategyCandidate(dp=2, tp=4,
                                         sequence_parallel=True))
    sp8 = cm.step_time(StrategyCandidate(dp=2, tp=4,
                                         sequence_parallel=True,
                                         sp_compress="int8"))
    sp4 = cm.step_time(StrategyCandidate(dp=2, tp=4,
                                         sequence_parallel=True,
                                         sp_compress="int4"))
    assert sp0 > sp8 > sp4
    # describe() carries the knobs so ranked tables stay readable
    d = StrategyCandidate(dp=8, zero=True, grad_compress="int4",
                          zero_refresh="int8",
                          comm_topology="two_level").describe()
    assert "gc4" in d and "zr8" in d and "2lvl" in d
