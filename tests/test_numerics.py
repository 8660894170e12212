"""Numerics observatory tests (obs/numerics.py, HETU_TPU_NUMERICS;
docs/observability.md): in-graph tensor stats at named scopes, exact
quantization SNR on every compressed path, MoE router telemetry, the
numerics health detectors, loss-scale transition events, and the
report/CLI surfaces.  The byte-identity half (unset flag == flag never
existed, all three canonical programs) lives in the flag-identity sweep
(tests/test_lint.py)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.core.mesh import MeshConfig
from hetu_tpu.engine import Trainer, TrainingConfig
from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.obs.metrics import MetricsRegistry, get_registry
from hetu_tpu.obs.runlog import RunLog
from hetu_tpu.parallel import ParallelStrategy


def _tiny_cfg(**kw):
    d = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
             num_hidden_layers=1, num_attention_heads=2,
             num_key_value_heads=2, max_position_embeddings=64,
             remat=False, use_scan=True)
    d.update(kw)
    return LlamaConfig(**d)


def _batch(gbs=4, seq=16, seed=0, vocab=120):
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, vocab, size=(gbs, seq)).astype(np.int32)
    return {"input_ids": ids, "labels": ids.copy()}


def _trainer(cfg, dp=1, gbs=4, seq=16, zero=False, **tc_kw):
    st = ParallelStrategy(mesh=MeshConfig(dp=dp), zero=zero)
    d = dict(global_batch_size=gbs, micro_batch_size=gbs // max(dp, 1),
             seq_len=seq, lr=1e-3, warmup_steps=2, total_steps=50,
             log_every=1000)
    d.update(tc_kw)
    return Trainer(LlamaLMHeadModel(cfg, st), TrainingConfig(**d), st)


def _stats(metrics):
    return jax.device_get(metrics["numerics"])


# ---------------------------------------------------------------------------
# in-graph stats: scopes, values, gating
# ---------------------------------------------------------------------------

def test_step_stats_scopes_and_values(monkeypatch):
    monkeypatch.setenv("HETU_TPU_NUMERICS", "1")
    tr = _trainer(_tiny_cfg()).build()
    st = _stats(tr.train_step(_batch()))
    # model boundaries + step-level trees + optimizer taps
    for scope in ("embed", "hidden", "logits", "params", "grads",
                  "update", "adam_m"):
        assert scope in st, sorted(st)
        s = st[scope]
        for key in ("absmax", "rms", "l2", "nonfinite",
                    "underflow_frac", "overflow_frac"):
            assert key in s, (scope, sorted(s))
        assert np.isfinite(float(s["rms"])) and float(s["rms"]) > 0
        assert float(s["nonfinite"]) == 0
        assert 0.0 <= float(s["underflow_frac"]) <= 1.0
    # healthy init: nothing underflows bf16's normal range
    assert float(st["params"]["underflow_frac"]) == 0.0
    tr.close()


def test_flag_off_means_no_stats():
    assert "HETU_TPU_NUMERICS" not in os.environ
    tr = _trainer(_tiny_cfg()).build()
    m = tr.train_step(_batch())
    assert "numerics" not in m
    tr.close()


def test_tree_stats_flags_underflow_overflow_nonfinite():
    from hetu_tpu.obs.numerics import tree_stats
    # 5e-38 sits in the bf16 underflow zone (within 2^8 of the smallest
    # normal — the FTZ-safe early-warning band); exact zeros don't count
    x = jnp.asarray([1.0, 5e-38, np.inf, np.nan, 0.0, 2.0], jnp.float32)
    st = jax.device_get(tree_stats({"x": x}))
    assert int(st["nonfinite"]) == 2
    # denominated over finite NONZERO values (3 of them) — a mostly-zero
    # tensor whose live values are dying must read ~1.0, not ~0.1
    assert np.isclose(float(st["underflow_frac"]), 1 / 3)
    assert float(st["absmax"]) == 2.0   # nonfinite excluded from absmax
    # 3.4e38 is finite in f32 but above bf16's max (3.3895e38)
    big = jnp.asarray([1.0, 3.4e38], jnp.float32)
    st2 = jax.device_get(tree_stats(big))
    assert np.isclose(float(st2["overflow_frac"]), 0.5)


def test_taps_under_foreign_transforms_are_skipped_not_leaked():
    """A tap inside a scan body with no frame of its own must be
    silently dropped (counted), never leak a tracer."""
    from hetu_tpu.obs import numerics

    seen = {}

    def f(x):
        with numerics.collecting() as col:
            numerics.tap_stats("outer", value=jnp.sum(x) * 2)

            def body(c, y):
                numerics.tap_stats("inner", value=y)   # foreign trace
                return c + y, y

            c, _ = jax.lax.scan(body, 0.0, x)
            stats = col.finalize()
            seen["skipped"] = col.skipped
            seen["scopes"] = sorted(stats)
        return c, stats

    c, stats = jax.jit(f)(jnp.arange(3.0))
    assert float(c) == 3.0
    assert seen["skipped"] >= 1
    assert seen["scopes"] == ["outer"]
    assert float(stats["outer"]["value"]) == 6.0


# ---------------------------------------------------------------------------
# compressed-path SNR (exact, hardware-free)
# ---------------------------------------------------------------------------

def test_grad_sync_snr_and_ef_scopes(monkeypatch):
    monkeypatch.setenv("HETU_TPU_NUMERICS", "1")
    monkeypatch.setenv("HETU_TPU_GRAD_COMPRESS", "int8-ef")
    tr = _trainer(_tiny_cfg(), dp=4, gbs=8).build()
    st = _stats(tr.train_step(_batch(gbs=8)))
    for scope in ("grad_sync/a2a", "grad_sync/ag", "ef"):
        assert scope in st, sorted(st)
    # blockwise int8 at block 256: SNR lands ~40 dB on gaussian-ish grads
    assert float(st["grad_sync/a2a"]["snr_db"]) > 20.0
    assert float(st["grad_sync/ag"]["snr_db"]) > 20.0
    assert float(st["ef"]["rms"]) > 0.0          # residuals are nonzero
    # model scopes crossed the shard_map + micro scan intact
    assert "logits" in st
    tr.close()


def test_zero_refresh_snr_scope(monkeypatch):
    monkeypatch.setenv("HETU_TPU_NUMERICS", "1")
    monkeypatch.setenv("HETU_TPU_ZERO_COMPRESS", "int8")
    tr = _trainer(_tiny_cfg(), dp=4, gbs=8, zero=True).build()
    st = _stats(tr.train_step(_batch(gbs=8)))
    assert "zero_refresh" in st, sorted(st)
    assert float(st["zero_refresh"]["snr_db"]) > 20.0
    # the update ran inside the shard_map body; its taps folded over dp
    assert "update" in st
    tr.close()


def test_sp_collective_probe(monkeypatch):
    """The dstates.convert SNR probe measures the exact int8 roundtrip
    of an SP payload when a frame is open in the same trace."""
    monkeypatch.setenv("HETU_TPU_SP_COMPRESS", "int8")
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from hetu_tpu import dstates as ds
    from hetu_tpu.core.mesh import MeshConfig, create_mesh
    from hetu_tpu.obs import numerics

    mesh = create_mesh(MeshConfig(dp=4))
    src = ds.DistributedStates.make(2, {0: "dp"})
    dst = ds.DistributedStates.make(2, {})

    def f(x):
        with numerics.collecting() as col:
            def body(xs):
                with numerics.frame() as nf:
                    full = ds.convert(xs, src, dst)
                return full, numerics.reduce_axis(nf.stats, "dp")

            full, stats = shard_map(
                body, mesh=mesh, in_specs=(P("dp"),),
                out_specs=(P(), P()), check_vma=False)(x)
            numerics.merge(stats)
            out = col.finalize()
        return full, out

    x = jax.random.normal(jax.random.key(0), (1024, 8), jnp.float32)
    full, stats = jax.jit(f)(x)
    assert "sp/all_gather" in stats, sorted(stats)
    assert float(stats["sp/all_gather"]["snr_db"]) > 20.0


def test_kv_page_snr_recorded(monkeypatch, tmp_path):
    monkeypatch.setenv("HETU_TPU_NUMERICS", "1")
    monkeypatch.setenv("HETU_TPU_HEALTH", "1")
    monkeypatch.setenv("HETU_TPU_KV_QUANT", "int8")
    from hetu_tpu.serving import ServeConfig, ServingEngine
    from hetu_tpu.serving.request import Request
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=256,
                      use_flash_attention=False, remat=False,
                      use_scan=True)
    model = LlamaLMHeadModel(cfg)
    params = model.init(jax.random.key(0))
    rl = RunLog(str(tmp_path / "serve.jsonl"))
    eng = ServingEngine(model, params, ServeConfig.from_flags(
        page_size=8, max_len=32, prefill_chunk=8), run_log=rl)
    eng.submit(Request(rid="r0", prompt=[1, 2, 3, 4, 5],
                       max_new_tokens=6), now=0.0)
    for i in range(12):
        eng.step(now=float(i))
    # the serving side doesn't just RECORD the SNR — the numerics
    # detectors watch it too (same HETU_TPU_HEALTH gate as training):
    # the monitor exists and its kv_pages SNR baseline was actually fed
    assert eng._num_health is not None
    assert eng._num_health._e("snr", "kv_pages").n > 0
    eng.close()
    recs = RunLog.read(str(tmp_path / "serve.jsonl"))
    nums = [r for r in recs if r.get("kind") == "numerics"]
    assert nums, "no numerics records from the serving engine"
    snrs = [r["scopes"]["kv_pages"]["snr_db"] for r in nums
            if "kv_pages" in r["scopes"]]
    assert snrs and all(s > 20.0 for s in snrs)


# ---------------------------------------------------------------------------
# MoE router telemetry + capacity-drop counters
# ---------------------------------------------------------------------------

def test_sort_routing_reports_load_and_dropped():
    from hetu_tpu.nn.moe import sort_routing
    # 6 tokens all pick expert 0 at capacity 4 -> 2 drops
    idx = jnp.zeros((6, 1), jnp.int32)
    gates = jnp.ones((6, 1), jnp.float32)
    plan = sort_routing(idx, gates, num_experts=2, capacity=4)
    assert int(plan["dropped"]) == 2
    assert plan["load"].tolist() == [6, 0]


def test_topk_routing_returns_dropped():
    from hetu_tpu.nn.moe import MoEConfig, topk_routing
    moe = MoEConfig(num_experts=2, top_k=1)
    logits = jnp.stack([jnp.full((6,), 5.0), jnp.full((6,), -5.0)],
                       axis=1)  # everyone routes to expert 0
    disp, comb, aux, dropped = topk_routing(
        logits, jnp.arange(6), moe, capacity=4)
    assert int(dropped) == 2
    assert int(jnp.sum(disp)) == 4


def test_moe_stats_and_capacity_counter(monkeypatch, tmp_path):
    monkeypatch.setenv("HETU_TPU_NUMERICS", "1")
    monkeypatch.setenv("HETU_TPU_RUNLOG", str(tmp_path / "rl.jsonl"))
    cfg = _tiny_cfg(num_experts=4, moe_top_k=2, use_scan=False,
                    moe_capacity_factor=0.5)   # tight: forces drops
    tr = _trainer(cfg).build()
    reg = get_registry()
    before = reg.counter_value("moe.capacity_dropped")
    tr.train([_batch(seed=i) for i in range(2)])
    tr.close()
    recs = RunLog.read(str(tmp_path / "rl.jsonl"))
    nums = [r for r in recs if r.get("kind") == "numerics"]
    assert nums
    moe = nums[-1]["scopes"]["moe"]
    assert len(moe["load"]) == 4
    assert 0.0 < moe["load_max"] <= 1.0
    assert moe["entropy"] > 0.0
    assert moe["dropped"] > 0          # the tight capacity factor bit
    # the ROADMAP-named gauges/counters
    assert reg.counter_value("moe.capacity_dropped") > before
    assert reg.gauge_value("moe.expert_load", expert="0") is not None
    assert reg.gauge_value("moe.router_entropy") is not None


# ---------------------------------------------------------------------------
# health detectors
# ---------------------------------------------------------------------------

def test_numerics_detectors_fire_on_synthetic_signals():
    from hetu_tpu.obs.health import NumericsHealthMonitor
    reg = MetricsRegistry()
    mon = NumericsHealthMonitor(registry=reg, warmup=3,
                                cooldown_steps=1, router_streak=2)
    # healthy baseline
    for i in range(6):
        fired = mon.observe(i, {
            "grads": {"underflow_frac": 0.0},
            "grad_sync/a2a": {"snr_db": 40.0},
            "ef": {"rms": 0.01},
            "moe": {"load_max": 0.25, "entropy": 1.3}})
        assert fired == []
    # four simultaneous failures
    fired = mon.observe(10, {
        "grads": {"underflow_frac": 0.6},          # underflow ramp
        "grad_sync/a2a": {"snr_db": 4.0},          # SNR collapse
        "ef": {"rms": 5.0},                        # EF blowup
        "moe": {"load_max": 0.95, "entropy": 0.01}})
    kinds = {f["anomaly"] for f in fired}
    assert {"underflow_creep", "quant_snr_collapse",
            "ef_residual_blowup"} <= kinds
    # router level rule needs its streak
    fired2 = mon.observe(11, {"moe": {"load_max": 0.95, "entropy": 0.01}})
    kinds |= {f["anomaly"] for f in fired2}
    assert "router_collapse" in kinds
    for k in ("underflow_creep", "quant_snr_collapse",
              "ef_residual_blowup", "router_collapse"):
        assert reg.counter_value(f"health.{k}") >= 1, k


def test_acceptance_underflow_ramp_and_router_collapse_e2e(monkeypatch,
                                                          tmp_path):
    """ISSUE 12 acceptance: a tiny MoE training run with a synthetic
    underflow ramp + a collapsing router fires the numerics detectors
    (health.* counters + `anomaly` run events) while per-path
    quantization SNR lands hardware-free in the RunLog."""
    monkeypatch.setenv("HETU_TPU_NUMERICS", "1")
    monkeypatch.setenv("HETU_TPU_HEALTH", "1")
    monkeypatch.setenv("HETU_TPU_GRAD_COMPRESS", "int8")
    monkeypatch.setenv("HETU_TPU_RUNLOG", str(tmp_path / "rl.jsonl"))
    cfg = _tiny_cfg(num_experts=4, moe_top_k=2, use_scan=False)
    tr = _trainer(cfg, dp=4, gbs=8).build()
    from hetu_tpu.obs.health import NumericsHealthMonitor
    reg = get_registry()
    tr._num_health = NumericsHealthMonitor(
        runlog=tr.run_log, registry=reg, warmup=2, cooldown_steps=1,
        router_streak=2)
    uf0 = reg.counter_value("health.underflow_creep")
    rc0 = reg.counter_value("health.router_collapse")

    # healthy baseline steps build the EWMA baselines
    tr.train([_batch(gbs=8, seed=i) for i in range(4)])

    # synthetic injection, host-side between steps: (a) an underflow
    # ramp — push the lm_head weights into the bf16 subnormal range (a
    # visible slice of the watched `params` scope, without starving the
    # rest of the model), and (b) a collapsing router — sharpen every
    # router's logits ~100x so per-token routing entropy pins to ~0
    # (the overconfident-router collapse signature; sign-proof, unlike
    # biasing one column through zero-mean activations)
    def poison(path, p):
        a = np.asarray(jax.device_get(p)).copy()
        name = str(path)
        if "lm_head" in name:
            a = a * 1e-35   # into the bf16 underflow zone, above f32 FTZ
        elif "router" in name:
            a = a * 100.0
        return jnp.asarray(a)

    tr.params = jax.tree_util.tree_map_with_path(poison, tr.params)
    tr.train([_batch(gbs=8, seed=10 + i) for i in range(4)])
    tr.close()

    assert reg.counter_value("health.underflow_creep") > uf0
    assert reg.counter_value("health.router_collapse") > rc0
    recs = RunLog.read(str(tmp_path / "rl.jsonl"))
    kinds = {r.get("anomaly") for r in recs if r.get("kind") == "anomaly"}
    assert "underflow_creep" in kinds and "router_collapse" in kinds
    # per-path SNR recorded hardware-free alongside
    nums = [r for r in recs if r.get("kind") == "numerics"]
    assert any("grad_sync/a2a" in r["scopes"] for r in nums)
    assert all(np.isfinite(r["scopes"]["grad_sync/a2a"]["snr_db"])
               for r in nums if "grad_sync/a2a" in r["scopes"])


# ---------------------------------------------------------------------------
# loss-scale events (satellite: scaler observability)
# ---------------------------------------------------------------------------

def test_scaler_events_for_seeded_overflow(monkeypatch, tmp_path):
    """A seeded fp16 run with a guaranteed-overflow initial scale pins
    the scaler event sequence: backoffs until the update lands, then a
    growth once the finite streak completes; the gauge tracks the final
    scale and every event's prev/scale ratio matches its kind."""
    monkeypatch.setenv("HETU_TPU_RUNLOG", str(tmp_path / "rl.jsonl"))
    from hetu_tpu.optim.grad_scaler import GradScaler
    cfg = _tiny_cfg(compute_dtype=jnp.float16)
    tr = _trainer(cfg, gbs=4, seq=16)
    # guaranteed-overflow initial scale; growth_interval=1 so the first
    # finite step after the backoff ladder immediately grows
    tr._scaler = GradScaler(init_scale=2.0 ** 30, growth_interval=1)
    tr.build()
    tr.train([_batch(seed=i) for i in range(24)])
    tr.close()
    recs = RunLog.read(str(tmp_path / "rl.jsonl"))
    evs = [r for r in recs if r.get("kind") == "scaler"]
    assert evs, "no scaler events"
    # the absurd initial scale overflows fp16: first transition must be
    # a backoff; a growth follows once the streak completes
    assert evs[0]["event"] == "backoff"
    assert any(e["event"] == "growth" for e in evs)
    for e in evs:
        ratio = e["scale"] / e["prev"]
        assert ratio == (2.0 if e["event"] == "growth" else 0.5)
    reg = get_registry()
    assert reg.counter_value("scaler.backoff") >= 1
    assert reg.counter_value("scaler.growth") >= 1
    gauge = reg.gauge_value("scaler.loss_scale")
    assert gauge is not None and gauge > 0


# ---------------------------------------------------------------------------
# histogram NaN guard (satellite: obs/metrics.py)
# ---------------------------------------------------------------------------

def test_histogram_nan_guard():
    from hetu_tpu.obs.metrics import Histogram
    h = Histogram()
    for v in (1.0, 2.0, float("nan"), 3.0, float("inf"), float("-inf")):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 3 and s["nonfinite"] == 3
    assert s["min"] == 1.0 and s["max"] == 3.0
    assert np.isfinite(s["sum"]) and np.isfinite(s["p50"])
    # a clean histogram's summary shape is unchanged (no nonfinite key)
    h2 = Histogram()
    h2.observe(1.0)
    assert "nonfinite" not in h2.summary()


def test_registry_observe_nan_does_not_poison_percentiles():
    reg = MetricsRegistry()
    reg.observe("x", 1.0)
    reg.observe("x", float("nan"))
    reg.observe("x", 2.0)
    h = reg.histogram("x")
    assert h.count == 2 and np.isfinite(h.percentile(50))
    snap = reg.snapshot()["histograms"][0]
    assert snap["nonfinite"] == 1 and np.isfinite(snap["p95"])


# ---------------------------------------------------------------------------
# reader + CLI + trace surfaces
# ---------------------------------------------------------------------------

def _fake_records():
    return [
        {"kind": "numerics", "t": 1.0, "numerics_schema": 1, "step": 1,
         "scopes": {"grads": {"rms": 0.1, "absmax": 1.0,
                              "underflow_frac": 0.0, "nonfinite": 0},
                    "grad_sync/a2a": {"snr_db": 41.0}}},
        {"kind": "numerics", "t": 2.0, "numerics_schema": 1, "step": 2,
         "scopes": {"grads": {"rms": 0.2, "absmax": 2.0,
                              "underflow_frac": 0.3, "nonfinite": 1},
                    "grad_sync/a2a": {"snr_db": 8.0}}},
        {"kind": "scaler", "t": 2.5, "event": "backoff", "scale": 1024.0,
         "prev": 2048.0, "step": 2},
        {"kind": "anomaly", "t": 2.6, "anomaly": "quant_snr_collapse",
         "step": 2, "value": 8.0, "baseline": 41.0},
    ]


def test_summarize_numerics_reader():
    from hetu_tpu.obs.numerics import summarize_numerics
    s = summarize_numerics(_fake_records())
    assert s["records"] == 2 and s["steps"] == [1, 2]
    g = s["scopes"]["grads"]
    assert g["max_underflow_frac"] == 0.3 and g["nonfinite"] == 1
    assert s["scopes"]["grad_sync/a2a"]["min_snr_db"] == 8.0
    # grads ranks worst (nonfinite beats low SNR)
    assert s["worst"][0] == "grads"


def test_tools_numerics_cli_and_report_section(tmp_path, capsys):
    path = tmp_path / "rl.jsonl"
    with open(path, "w") as f:
        for r in _fake_records():
            f.write(json.dumps(dict(r, schema=1)) + "\n")
    import tools_numerics
    assert tools_numerics.main([str(path), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["numerics_schema"] == 1
    assert out["summary"]["records"] == 2
    assert out["scaler"]["backoff"] == 1
    assert out["anomalies"]["quant_snr_collapse"] == 1
    # text mode renders the table
    assert tools_numerics.main([str(path)]) == 0
    txt = capsys.readouterr().out
    assert "grad_sync/a2a" in txt and "scaler" in txt
    # tools_obs_report reuses the SAME reader (no second parser)
    import tools_obs_report
    rep = tools_obs_report.summarize(_fake_records())
    assert rep["numerics"]["records"] == 2
    assert rep["numerics"]["worst"][0] == "grads"
    assert rep["numerics"]["anomalies"]["quant_snr_collapse"] == 1
    assert rep["scaler"]["events"] == 1


def test_numerics_chrome_trace_lanes(tmp_path):
    from hetu_tpu.obs.trace import numerics_trace, trace_from_runlog
    events = json.loads(numerics_trace(_fake_records()).to_json())
    counters = [e for e in events if e.get("ph") == "C"]
    assert any(e["name"] == "numerics/grads" for e in counters)
    assert any(e["name"] == "numerics/grad_sync/a2a" for e in counters)
    assert any(e.get("cat") == "scaler" for e in events)
    # the full-run exporter carries the same lanes
    full = json.loads(trace_from_runlog(_fake_records()).to_json())
    assert any(e.get("ph") == "C" and e["name"].startswith("numerics/")
               for e in full)


# ---------------------------------------------------------------------------
# flags
# ---------------------------------------------------------------------------

def test_numerics_flags_registered_with_identity_contract():
    from hetu_tpu.utils import flags
    assert flags.bool_flag("HETU_TPU_NUMERICS") is False
    assert flags.int_flag("HETU_TPU_NUMERICS_EVERY") == 1
    assert flags.REGISTRY["HETU_TPU_NUMERICS"].identity == "0"
    assert flags.identity_flags()["HETU_TPU_NUMERICS"] == "0"


def test_numerics_every_throttles_records(monkeypatch, tmp_path):
    monkeypatch.setenv("HETU_TPU_NUMERICS", "1")
    monkeypatch.setenv("HETU_TPU_NUMERICS_EVERY", "2")
    monkeypatch.setenv("HETU_TPU_RUNLOG", str(tmp_path / "rl.jsonl"))
    tr = _trainer(_tiny_cfg()).build()
    tr.train([_batch(seed=i) for i in range(4)])
    tr.close()
    recs = RunLog.read(str(tmp_path / "rl.jsonl"))
    nums = [r for r in recs if r.get("kind") == "numerics"]
    assert len(nums) == 2          # steps 2 and 4 of 4
    assert all(r["step"] % 2 == 0 for r in nums)
