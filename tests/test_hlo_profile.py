"""Analytic step profiler + regression sentinel tests (obs.hlo_profile,
obs.budget, tools_bench_diff): per-layer HLO attribution reconciles with
the coarse phase totals and the comm analyzer, the liveness peak-HBM
estimate lands within 20% of XLA's memory_analysis, and the sentinel
catches injected regressions while passing on the real BENCH pair."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.obs import hlo_profile as hp
from hetu_tpu.obs.budget import (PerfBudget, check_absolute, diff_metrics,
                                 extract_metrics)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILED = {}


def _compiled(L=2, scan=False, remat=True, batch=2, seq=64, donate=False):
    """One grad (or donated AdamW) step per config, compiled once per
    session — every test reads the same executables."""
    key = (L, scan, remat, batch, seq, donate)
    if key in _COMPILED:
        return _COMPILED[key]
    cfg = LlamaConfig.tiny(num_hidden_layers=L, remat=remat, use_scan=scan)
    model = LlamaLMHeadModel(cfg)
    params = model.init(jax.random.key(0))
    ids = jnp.zeros((batch, seq), jnp.int32)
    if donate:
        from hetu_tpu import optim
        opt = optim.AdamW(lr=1e-4)
        opt_state = opt.init(params)

        def step(params, opt_state, ids):
            loss, grads = jax.value_and_grad(
                lambda p: model(p, ids, labels=ids))(params)
            params, opt_state = opt.update(grads, opt_state, params)
            return params, opt_state, loss

        c = jax.jit(step, donate_argnums=(0, 1)).lower(
            params, opt_state, ids).compile()
    else:
        c = jax.jit(jax.grad(
            lambda p: model(p, ids, labels=ids))).lower(params).compile()
    _COMPILED[key] = c
    return c


# ---------------------------------------------------------------------------
# scope parsing + grouping
# ---------------------------------------------------------------------------

def test_scope_segments_unwrap_transforms():
    assert hp.scope_segments(
        "jit(f)/jit(main)/transpose(jvp(layer_1))/attn/dot_general"
    ) == ["f", "main", "layer_1", "attn", "dot_general"]
    assert hp.scope_segments("jit(f)/layer/mlp/add") == \
        ["f", "layer", "mlp", "add"]


def test_group_of_layer_phase_combinations():
    assert hp.group_of("jit(f)/layer_3/attn/dot_general") == "layer_3/attn"
    assert hp.group_of("jit(f)/transpose(jvp(layer_0))/mlp/x") == \
        "layer_0/mlp"
    assert hp.group_of("jit(f)/layer/attn/dot") == "layer/attn"
    assert hp.group_of("jit(f)/embed/gather") == "embed"
    assert hp.group_of("jit(f)/optimizer/add") == "optimizer"
    assert hp.group_of("jit(f)/grad_sync/all-reduce") == "grad_sync"
    assert hp.group_of("jit(f)/something/else") == "other"


def test_per_layer_groups_in_unrolled_model():
    """The model stack's named scopes reach the optimized HLO: each
    unrolled decoder layer is individually attributable, with equal
    per-layer dot counts and FLOPs (the layers are identical)."""
    tab = hp.layer_table(_compiled(L=2, scan=False))
    for g in ("layer_0/attn", "layer_0/mlp", "layer_1/attn",
              "layer_1/mlp", "embed", "lm_head"):
        assert g in tab, sorted(tab)
    assert tab["layer_0/attn"]["dots"] == tab["layer_1/attn"]["dots"] > 0
    assert tab["layer_0/mlp"]["flops"] == pytest.approx(
        tab["layer_1/mlp"]["flops"])
    assert tab["layer_0/attn"]["flops"] > 0


# ---------------------------------------------------------------------------
# attribution consistency (the ISSUE acceptance contract)
# ---------------------------------------------------------------------------

def test_static_sums_equal_phase_breakdown():
    """Satellite: per-layer sums (static counting) must equal the coarse
    `phase_breakdown` totals on a lowered 2-layer model — both walks
    count the same op_name lines with the same output-shape anchoring."""
    from hetu_tpu.utils.profiling import phase_breakdown
    c = _compiled(L=2, scan=False)
    tab = hp.layer_table(c, apply_multipliers=False)
    pb = phase_breakdown(c)
    for k in ("instructions", "dots", "out_bytes"):
        per_layer = sum(r[k] for g, r in tab.items() if g != "_meta")
        per_phase = sum(p[k] for p in pb.values())
        assert per_layer == pytest.approx(per_phase), (k, per_layer,
                                                       per_phase)
    # and the per-phase split itself reconciles: layer_*/attn + any
    # bare attn == phase "attn"
    attn_layers = sum(r["dots"] for g, r in tab.items()
                     if g.endswith("/attn") or g == "attn")
    assert attn_layers == pb["attn"]["dots"]


def test_wire_sums_equal_comm_analyzer(devices):
    """Satellite: per-group wire-byte sums (trip multipliers ON) must
    equal obs.comm.collective_report's total on a lowered program with
    real collectives — one byte model, two walks."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from hetu_tpu.core.mesh import MeshConfig, create_mesh
    from hetu_tpu.obs.comm import collective_report
    mesh = create_mesh(MeshConfig(dp=8))

    def f(x):
        with jax.named_scope("grad_sync"):
            s = jax.lax.psum(x, "dp")
        with jax.named_scope("layer_0"):
            return x * s

    c = jax.jit(shard_map(f, mesh=mesh, in_specs=P("dp"),
                          out_specs=P("dp"))).lower(
        jnp.ones((8, 128), jnp.float32)).compile()
    tab = hp.layer_table(c)
    total = sum(r["wire_bytes"] for g, r in tab.items() if g != "_meta")
    rep = collective_report(c)
    assert total == pytest.approx(rep["total_wire_bytes"])
    assert total > 0
    # the explicit collective carries the grad_sync scope
    assert tab["grad_sync"]["wire_bytes"] == pytest.approx(total)


def test_wire_sums_reconcile_on_gspmd_trainer(tmp_path, monkeypatch,
                                              devices):
    """The reconciliation holds on a REAL GSPMD-partitioned train step
    too, where some partitioner-inserted collectives carry no op_name
    metadata (their wire bytes land in "other")."""
    from hetu_tpu.core.mesh import MeshConfig
    from hetu_tpu.engine import Trainer, TrainingConfig
    from hetu_tpu.obs.comm import collective_report
    from hetu_tpu.parallel import ParallelStrategy
    st = ParallelStrategy(mesh=MeshConfig(dp=2, tp=2),
                          sequence_parallel=True)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, use_scan=False)
    tc = TrainingConfig(global_batch_size=4, micro_batch_size=2,
                        seq_len=32, total_steps=10, log_every=100)
    tr = Trainer(LlamaLMHeadModel(cfg, st), tc, st).build()
    hb = {"input_ids": np.ones((4, 32), np.int32),
          "labels": np.ones((4, 32), np.int32)}
    key = tuple(sorted((k, tuple(v.shape)) for k, v in hb.items()))
    compiled = tr._compiled_for_shape(hb, key)
    tab = hp.layer_table(compiled)
    total = sum(r["wire_bytes"] for g, r in tab.items() if g != "_meta")
    rep = collective_report(compiled)
    assert total == pytest.approx(rep["total_wire_bytes"])
    assert total > 0


def test_scan_trip_multiplier_matches_unrolled():
    """A scanned stack's `layer/...` groups carry the while trip count:
    dot counts equal L x one unrolled layer's."""
    scan_tab = hp.layer_table(_compiled(L=4, scan=True))
    unr_tab = hp.layer_table(_compiled(L=2, scan=False))
    per_layer_dots = unr_tab["layer_0/attn"]["dots"]
    assert scan_tab["layer/attn"]["dots"] == pytest.approx(
        4 * per_layer_dots)
    assert scan_tab["layer/mlp"]["flops"] == pytest.approx(
        2 * (unr_tab["layer_0/mlp"]["flops"]
             + unr_tab["layer_1/mlp"]["flops"]), rel=1e-6)


def test_dot_flops_parser():
    """Parsed dot FLOPs = 2 * out_elems * contraction on a plain matmul
    (both operand orders / contraction dims)."""
    def f(a, b):
        with jax.named_scope("layer_0"):
            with jax.named_scope("mlp"):
                return a @ b

    a = jnp.ones((32, 48), jnp.float32)
    b = jnp.ones((48, 16), jnp.float32)
    c = jax.jit(f).lower(a, b).compile()
    tab = hp.layer_table(c)
    assert tab["layer_0/mlp"]["flops"] == pytest.approx(2 * 32 * 48 * 16)


# ---------------------------------------------------------------------------
# peak HBM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(L=2, scan=False, remat=True),
    dict(L=2, scan=False, remat=False),
    dict(L=4, scan=True, remat=True),
    dict(L=2, scan=False, remat=True, donate=True),
])
def test_peak_hbm_within_20pct_of_xla(kw):
    """Acceptance: the liveness-based peak-HBM estimate lands within 20%
    of XLA's own buffer assignment (args + temp + unaliased outputs)
    wherever memory_analysis is exposed — incl. the donated AdamW step
    (the real trainer shape)."""
    rep = hp.peak_hbm_estimate(_compiled(**kw))
    if "vs_xla" not in rep:
        pytest.skip("backend exposes no memory_analysis")
    assert 0.8 <= rep["vs_xla"] <= 1.2, rep
    assert rep["peak_bytes"] > rep["args_bytes"] > 0


def test_peak_hbm_remat_reduces_working_set():
    """Remat awareness: the same scanned stack without remat holds a
    larger estimated working set (every layer's residuals are stacked
    for the backward; with remat, the layers' inputs alone).  Scanned,
    because there the saving is the program's structure: unrolled, it is
    the compiler's schedule, and XLA:CPU's own temp arena is no smaller
    with remat at these sizes."""
    with_remat = hp.peak_hbm_estimate(_compiled(L=4, scan=True,
                                                remat=True))
    without = hp.peak_hbm_estimate(_compiled(L=4, scan=True,
                                             remat=False))
    assert without["temp_peak_bytes"] > 2 * with_remat["temp_peak_bytes"]
    assert without["xla_temp_bytes"] > 2 * with_remat["xla_temp_bytes"]


def test_analytic_peak_hbm_model():
    base = dict(batch=8, seq=128, hidden=256, num_layers=4, vocab=2048)
    remat = hp.analytic_peak_hbm(8e6, remat=True, **base)
    full = hp.analytic_peak_hbm(8e6, remat=False, **base)
    assert full["peak_bytes"] > remat["peak_bytes"]
    assert remat["params_bytes"] == 32e6
    assert remat["opt_state_bytes"] == 64e6
    zero = hp.analytic_peak_hbm(8e6, dp=4, zero=True, **base)
    assert zero["opt_state_bytes"] == 16e6
    tp = hp.analytic_peak_hbm(8e6, tp=2, **base)
    assert tp["params_bytes"] == 16e6


# ---------------------------------------------------------------------------
# profile record
# ---------------------------------------------------------------------------

def test_profile_record_schema_and_topk():
    rec = hp.profile_record(_compiled(L=2, scan=False), top_k=3)
    assert rec["profile_schema"] == hp.PROFILE_SCHEMA
    assert len(rec["top"]) == 3
    assert rec["total_flops"] > 0
    assert rec["peak_hbm_bytes"] > 0
    assert 0 < rec["hbm_headroom_frac"] < 1
    assert json.loads(json.dumps(rec))  # JSONL-safe


def test_layer_profile_totals_and_order():
    prof = hp.layer_profile(_compiled(L=2, scan=False))
    assert prof["estimated_step_s"] == pytest.approx(
        sum(r["time_s"] for r in prof["groups"].values()))
    groups = list(prof["groups"])
    assert groups.index("embed") < groups.index("layer_0/attn") \
        < groups.index("layer_1/attn") < groups.index("lm_head")


# ---------------------------------------------------------------------------
# budgets + the regression sentinel
# ---------------------------------------------------------------------------

def test_budget_load_rejects_unknown_keys(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"max_step_time": 1.0}))  # typo'd key
    with pytest.raises(ValueError, match="unknown keys"):
        PerfBudget.load(str(p))
    p.write_text(json.dumps({"thresholds": {"bogus": 0.1}}))
    with pytest.raises(ValueError, match="unknown threshold"):
        PerfBudget.load(str(p))
    p.write_text("{not json")
    with pytest.raises(ValueError, match="not valid JSON"):
        PerfBudget.load(str(p))


def test_budget_absolute_and_diff_directions(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({
        "max_step_time_s": 0.5, "min_estimated_mfu": 0.4,
        "thresholds": {"step_time_s": 0.08}}))
    b = PerfBudget.load(str(p))
    breaches = check_absolute(
        {"step_time_s": 0.6, "estimated_mfu": 0.3}, b)
    assert {x["metric"] for x in breaches} == \
        {"step_time_s", "estimated_mfu"}
    assert not check_absolute(
        {"step_time_s": 0.4, "estimated_mfu": 0.5}, b)
    # diffs: step time may rise 8% under this budget; MFU keeps the
    # default -5% rule; an mfu GAIN never breaches
    d = diff_metrics({"step_time_s": 1.0, "estimated_mfu": 0.5},
                     {"step_time_s": 1.07, "estimated_mfu": 0.6}, b)
    assert not d["breaches"]
    d = diff_metrics({"step_time_s": 1.0, "estimated_mfu": 0.5},
                     {"step_time_s": 1.09, "estimated_mfu": 0.47}, b)
    assert {x["metric"] for x in d["breaches"]} == \
        {"step_time_s", "estimated_mfu"}


def test_extract_metrics_across_record_shapes():
    bench = {"tail": 'noise\n' + json.dumps(
        {"metric": "llama_train_mfu", "value": 0.5,
         "detail": {"estimated_mfu": 0.6, "predicted_step_s": 0.4,
                    "comm_bytes_per_step": 1e9,
                    "profile": {"peak_hbm_bytes": 2e9}}}) + "\n"}
    m = extract_metrics(bench)
    assert m == {"mfu": 0.5, "estimated_mfu": 0.6, "step_time_s": 0.4,
                 "comm_bytes": 1e9, "peak_hbm_bytes": 2e9}
    prof = {"kind": "profile", "estimated_step_s": 0.1,
            "total_wire_bytes": 5.0, "peak_hbm_bytes": 3e9}
    assert extract_metrics(prof)["peak_hbm_bytes"] == 3e9
    comp = {"kind": "compile", "estimated_mfu": 0.7,
            "estimated_step_s": 0.2}
    assert extract_metrics(comp) == {"estimated_mfu": 0.7,
                                     "step_time_s": 0.2}


def _bench_record(step_s=0.40, peak=10e9):
    return {"metric": "llama_train_mfu", "value": 0.5,
            "unit": "fraction_of_peak",
            "detail": {"estimated_mfu": 0.6, "predicted_step_s": step_s,
                       "comm_bytes_per_step": 1e9,
                       "profile": {"peak_hbm_bytes": peak}}}


def test_bench_diff_sentinel_catches_injected_regression(tmp_path):
    """CI satellite: tools_bench_diff must exit nonzero on an injected
    +10% step-time / +15% peak-HBM regression between two synthetic
    BENCH records, and exit zero on identical records."""
    import tools_bench_diff
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(_bench_record()))
    new.write_text(json.dumps(_bench_record(step_s=0.44, peak=11.5e9)))
    assert tools_bench_diff.main([str(old), str(new)]) == 1
    assert tools_bench_diff.main([str(old), str(old)]) == 0
    # a step-time IMPROVEMENT passes
    new.write_text(json.dumps(_bench_record(step_s=0.30, peak=9e9)))
    assert tools_bench_diff.main([str(old), str(new)]) == 0


def test_bench_diff_skips_analytic_vs_measured_peak(tmp_path):
    """Estimator-skew guard: a BENCH round whose profile is the analytic
    config twin ("analytic": true) must not be peak-HBM
    diffed against a measured-HLO round — the estimators legitimately
    differ by ~10-20%."""
    import tools_bench_diff
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    rec_a = _bench_record(peak=10e9)
    rec_a["detail"]["profile"]["analytic"] = True
    rec_m = _bench_record(peak=11.8e9)   # +18%: would breach if compared
    old.write_text(json.dumps(rec_a))
    new.write_text(json.dumps(rec_m))
    assert tools_bench_diff.main([str(old), str(new)]) == 0
    # same provenance: the +18% peak regression IS caught
    rec_m2 = _bench_record(peak=10e9)
    old.write_text(json.dumps(rec_m2))
    assert tools_bench_diff.main([str(old), str(new)]) == 1


def test_bench_diff_reads_runlogs(tmp_path):
    """The sentinel also diffs per-compile profile records straight from
    two RunLog JSONLs (HETU_TPU_PROFILE output)."""
    import tools_bench_diff
    old = tmp_path / "old.jsonl"
    new = tmp_path / "new.jsonl"

    def rl(step_s, peak):
        return "\n".join([
            json.dumps({"schema": 1, "kind": "step", "t": 1.0, "step": 0}),
            json.dumps({"schema": 1, "kind": "profile", "t": 2.0,
                        "profile_schema": 1, "estimated_step_s": step_s,
                        "total_wire_bytes": 100.0,
                        "peak_hbm_bytes": peak}),
        ])
    old.write_text(rl(0.40, 10e9))
    new.write_text(rl(0.46, 10e9))       # +15% step time
    assert tools_bench_diff.main([str(old), str(new)]) == 1
    new.write_text(rl(0.41, 10e9))       # +2.5%: within threshold
    assert tools_bench_diff.main([str(old), str(new)]) == 0


# ---------------------------------------------------------------------------
# trainer wiring
# ---------------------------------------------------------------------------

def _tiny_trainer(tmp_path, monkeypatch, **env):
    from hetu_tpu.engine import Trainer, TrainingConfig
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setenv("HETU_TPU_RUNLOG",
                       str(tmp_path / "runlog.jsonl"))
    # one layer at seq 16: the wiring tests only need a compile that
    # leaves records, not a representative model — keep tier-1 cheap
    cfg = LlamaConfig.tiny(num_hidden_layers=1, use_scan=False)
    tc = TrainingConfig(global_batch_size=2, micro_batch_size=2,
                        seq_len=16, total_steps=10, log_every=100)
    return Trainer(LlamaLMHeadModel(cfg), tc)


def _tiny_batch():
    return {"input_ids": np.ones((2, 16), np.int32),
            "labels": np.ones((2, 16), np.int32)}


def test_trainer_profile_record_flag_gated(tmp_path, monkeypatch):
    """HETU_TPU_PROFILE=1 leaves a schema-versioned `profile` record per
    fresh compile; unset leaves none (and the traced program is
    byte-identical either way — the profile is post-compile analysis)."""
    from hetu_tpu.obs.runlog import RunLog
    tr = _tiny_trainer(tmp_path, monkeypatch, HETU_TPU_PROFILE="1")
    tr.build()
    tr.train_step(_tiny_batch())
    tr.close()
    recs = RunLog.read(str(tmp_path / "runlog.jsonl"))
    profs = [r for r in recs if r["kind"] == "profile"]
    assert len(profs) == 1
    assert profs[0]["profile_schema"] == hp.PROFILE_SCHEMA
    assert profs[0]["peak_hbm_bytes"] > 0
    assert any(t["group"].startswith("layer_") for t in profs[0]["top"])
    assert any(t["group"] == "optimizer" for t in profs[0]["top"])
    # HLO byte-identity: the flag changes analysis, never the program
    hb = _tiny_batch()
    key = tuple(sorted((k, tuple(v.shape)) for k, v in hb.items()))
    from hetu_tpu.analysis.flag_identity import fingerprint
    with_flag = tr._compiled_for_shape(hb, key).as_text()
    monkeypatch.delenv("HETU_TPU_PROFILE")
    tr2 = _tiny_trainer(tmp_path, monkeypatch)
    tr2.build()
    without = tr2._compiled_for_shape(hb, key).as_text()
    assert fingerprint(with_flag) == fingerprint(without)


def test_trainer_budget_check_and_enforce(tmp_path, monkeypatch):
    """A declared budget the compile breaches leaves a failing `budget`
    record + counter (observe mode), and raises BudgetError when the
    file declares enforce=true."""
    from hetu_tpu.obs.budget import BudgetError
    from hetu_tpu.obs.runlog import RunLog
    budgets = tmp_path / "budgets.json"
    # impossible ceiling: every compile breaches
    budgets.write_text(json.dumps({"max_step_time_s": 1e-12}))
    tr = _tiny_trainer(tmp_path, monkeypatch, HETU_TPU_PROFILE="1",
                       HETU_TPU_BUDGETS=str(budgets))
    tr.build()
    tr.train_step(_tiny_batch())
    tr.close()
    recs = RunLog.read(str(tmp_path / "runlog.jsonl"))
    buds = [r for r in recs if r["kind"] == "budget"]
    assert buds and buds[0]["ok"] is False
    assert buds[0]["breaches"][0]["metric"] == "step_time_s"
    # enforce=true turns the breach into a loud failure
    budgets.write_text(json.dumps({"max_step_time_s": 1e-12,
                                   "enforce": True}))
    tr2 = _tiny_trainer(tmp_path, monkeypatch, HETU_TPU_PROFILE="1",
                        HETU_TPU_BUDGETS=str(budgets))
    tr2.build()
    with pytest.raises(BudgetError):
        tr2.train_step(_tiny_batch())
    # a generous budget passes clean
    budgets.write_text(json.dumps({"max_step_time_s": 1e6}))
    tr3 = _tiny_trainer(tmp_path, monkeypatch, HETU_TPU_PROFILE="1",
                        HETU_TPU_BUDGETS=str(budgets))
    tr3.build()
    tr3.train_step(_tiny_batch())
    tr3.close()


def test_obs_report_profile_section(tmp_path, monkeypatch):
    """tools_obs_report surfaces the profile + budget summary: top-k
    layers, peak HBM vs the chip, pass/fail."""
    import tools_obs_report
    tr = _tiny_trainer(tmp_path, monkeypatch, HETU_TPU_PROFILE="1")
    tr.build()
    tr.train_step(_tiny_batch())
    if tr.run_log is not None:
        tr.run_log.log("budget", name="train_step", ok=False,
                       breaches=[{"metric": "peak_hbm_bytes"}],
                       budget="b.json")
    tr.close()
    from hetu_tpu.obs.runlog import RunLog
    s = tools_obs_report.summarize(
        RunLog.read(str(tmp_path / "runlog.jsonl")))
    assert s["profile"]["peak_hbm_bytes"] > 0
    assert s["profile"]["top_layers"]
    assert s["profile"]["hbm_headroom_frac"] < 1
    assert s["budget"] == {"checks": 1, "failed": 1, "ok": False,
                           "last_breaches": ["peak_hbm_bytes"]}


def test_trainer_profile_report_api(tmp_path, monkeypatch):
    tr = _tiny_trainer(tmp_path, monkeypatch)
    tr.build()
    rep = tr.profile_report(_tiny_batch())
    assert "layer_0/attn" in rep["groups"]
    assert rep["peak_hbm"]["peak_bytes"] > 0
    # memoized per shape: same object back
    assert tr.profile_report(_tiny_batch()) is rep


# ---------------------------------------------------------------------------
# cost-model feasibility gate + profile calibration
# ---------------------------------------------------------------------------

def _cost(**kw):
    from hetu_tpu.search.cost_model import CostModel
    from hetu_tpu.search.profiler import HardwareProfile
    d = dict(hw=HardwareProfile.preset("v5e"), num_layers=4, hidden=256,
             intermediate=704, vocab=2048, num_params=8_000_000,
             global_batch=4, seq_len=128)
    d.update(kw)
    return CostModel(**d)


def test_cost_model_hbm_feasibility_gate():
    from hetu_tpu.search.cost_model import StrategyCandidate
    cost = _cost()
    c = StrategyCandidate()
    assert cost.peak_hbm_bytes(c) == cost.per_device_memory(c)
    assert cost.fits_hbm(c)
    # a model far beyond one chip's HBM must be rejected analytically
    big = _cost(num_params=20_000_000_000)
    assert not big.fits_hbm(StrategyCandidate())
    # ...and the searcher inherits the gate (no feasible single-device
    # plan for a 20B model on a 16G chip)
    from hetu_tpu.search.searcher import search_strategy
    assert search_strategy(big, num_devices=1) == []


def test_profile_calibration_feeds_cost_model():
    from hetu_tpu.search.calibrate import apply_profile_calibration
    from hetu_tpu.search.cost_model import StrategyCandidate
    prof = hp.layer_profile(_compiled(L=2, scan=False, remat=True))
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    cost = _cost(num_layers=2, hidden=cfg.hidden_size,
                 vocab=cfg.vocab_size, num_params=2_000_000,
                 global_batch=2, seq_len=64)
    before = cost.step_time(StrategyCandidate())
    # the tiny config's remat_policy defaults to "nothing" (full
    # recompute), so the profiled backward re-ran every dot once
    apply_profile_calibration(cost, prof, batch=2, seq=64,
                              dot_recompute=1.0)
    assert cost.measured_layer_flops_per_token is not None
    assert cost.measured_layer_flops_per_token > 0
    after = cost.step_time(StrategyCandidate())
    assert after > 0 and after != before
    # the measured rate is the profiled program's own dots: reconstruct
    layer_flops = sum(r["flops"] for g, r in prof["groups"].items()
                      if g.startswith("layer"))
    expect = layer_flops * 0.75 / 2 / (2 * 64)
    assert cost.measured_layer_flops_per_token == pytest.approx(expect)
    # a dot-saving policy ("dots"/"dots_attn") needs no normalization
    cal2 = _cost(num_layers=2, hidden=cfg.hidden_size,
                 vocab=cfg.vocab_size, num_params=2_000_000,
                 global_batch=2, seq_len=64)
    apply_profile_calibration(cal2, prof, batch=2, seq=64,
                              dot_recompute=0.0)
    assert cal2.measured_layer_flops_per_token == pytest.approx(
        expect * 4.0 / 3.0)


def test_profile_calibration_without_layer_scopes_is_noop():
    from hetu_tpu.search.calibrate import apply_profile_calibration
    cost = _cost()
    apply_profile_calibration(
        cost, {"groups": {"other": {"flops": 123.0}}}, batch=2, seq=64)
    assert cost.measured_layer_flops_per_token is None


# ---------------------------------------------------------------------------
# bench surface
# ---------------------------------------------------------------------------

def test_bench_hardware_free_profile_record():
    import bench
    rec = bench._hardware_free_profile()
    assert rec["profile_schema"] == hp.PROFILE_SCHEMA
    assert rec["analytic"] is True
    assert rec["peak_hbm_bytes"] > 0
    assert rec["top"][0]["group"].startswith("layer")
    assert isinstance(rec["fits_hbm"], bool)
    # the sentinel can diff it
    m = extract_metrics({"metric": "x", "value": 0.0,
                         "detail": {"profile": rec}})
    assert m["peak_hbm_bytes"] == rec["peak_hbm_bytes"]
