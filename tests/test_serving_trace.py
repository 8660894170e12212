"""Serving flight recorder + SLO-class analytics (tier-1, CPU, seeded):
the span model's invariants, the tracer's tiling/reconciliation
property (the acceptance criterion: span durations reconcile with every
request's e2e within one engine-step quantum), two-class SLO attainment
separation, the per-slot Chrome-trace render, the serving health
detectors, serving telemetry through the cluster aggregator, and the
CLI smoke tests for tools_serving.py --trace/--chrome-trace and
tools_serving_report.py (JSON schema pinned)."""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu import serving
from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.obs.metrics import MetricsRegistry
from hetu_tpu.obs.runlog import RunLog
from hetu_tpu.obs.spans import (STALL_REASONS, FleetTrace, RequestTrace,
                                Span, collect_traces)
from hetu_tpu.serving import slo_report
from hetu_tpu.serving.request import Request, SLOClass
from hetu_tpu.serving.tracing import RequestTracer


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = LlamaConfig.tiny(remat=False, compute_dtype=jnp.float32,
                           use_flash_attention=False)
    model = LlamaLMHeadModel(cfg)
    return model, model.init(jax.random.key(0))


def _engine(model, params, **kw):
    cfg_kw = dict(num_slots=3, page_size=8, max_len=64, prefill_chunk=8)
    for k in ("num_slots", "page_size", "max_len", "prefill_chunk",
              "num_pages"):
        if k in kw:
            cfg_kw[k] = kw.pop(k)
    kw.setdefault("registry", MetricsRegistry())
    return serving.ServingEngine(model, params,
                                 serving.ServeConfig(**cfg_kw), **kw)


# ------------------------------------------------------------ span model
def test_span_record_roundtrip():
    sp = Span("queued", 1.0, 2.5, rid=7, trace="tr0.7", slot=None,
              slo_class="gold", attrs={"reason": "no_slot"})
    rec = dict(sp.record(), kind="span", schema=1, t=0.0)
    back = Span.from_record(rec)
    assert back.kind == "queued" and back.rid == 7
    assert back.t0 == 1.0 and back.t1 == 2.5
    assert back.slo_class == "gold"
    assert back.attrs["reason"] == "no_slot"
    assert "span_schema" not in back.attrs      # structure, not attrs
    with pytest.raises(ValueError):
        Span("warp", 0, 1, rid=0, trace="t")


def _mk_trace(spans):
    tr = RequestTrace(rid=0, trace="t0")
    for kind, t0, t1, attrs in spans:
        tr.spans.append(Span(kind, t0, t1, rid=0, trace="t0",
                             attrs=attrs))
    return tr


def test_trace_validation_catches_violations():
    ok = _mk_trace([("queued", 0, 1, {"reason": "none"}),
                    ("prefill", 1, 2, {"chunk": 1}),
                    ("decode", 2, 4, {"tokens": 3}),
                    ("done", 4, 4, {"reason": "eos"})])
    ok.validate()
    assert ok.total_s == pytest.approx(4.0)
    assert ok.reconcile(4.0) == pytest.approx(0.0)

    with pytest.raises(AssertionError, match="terminal"):
        _mk_trace([("queued", 0, 1, {"reason": "none"})]).validate()
    with pytest.raises(AssertionError, match="stall reason"):
        _mk_trace([("queued", 0, 1, {}),
                   ("done", 1, 1, {})]).validate()
    with pytest.raises(AssertionError, match="overlap"):
        _mk_trace([("queued", 0, 1, {"reason": "none"}),
                   ("decode", 0.5, 2, {}),
                   ("done", 2, 2, {})]).validate()
    with pytest.raises(AssertionError, match="terminal"):
        _mk_trace([("queued", 0, 1, {"reason": "none"}),
                   ("done", 1, 1, {}),
                   ("evicted", 1, 1, {})]).validate()
    with pytest.raises(AssertionError, match="first span"):
        _mk_trace([("decode", 0, 1, {}),
                   ("done", 1, 1, {})]).validate()


def test_tracer_lifecycle_without_engine():
    """The tracer's host-only API tiles a synthetic lifecycle (the same
    call sequence the engine makes) into a valid trace."""
    tracer = RequestTracer()          # keep=True (no runlog)
    req = Request(rid=3, prompt=np.ones(4, np.int32), max_new_tokens=4,
                  arrival_t=1.0)
    tracer.on_submit(req)
    tracer.on_stall([3], "no_pages")
    tracer.on_admit(req, slot=1, now=2.0)
    tracer.on_chunk(req, 2.5, 1)
    tracer.on_first_token(req, 1, 3.0, chunk=2)
    tracer.on_token(req, 3.5)
    tracer.on_split([3], 3.5, "evict")
    tracer.on_token(req, 4.0)
    tracer.on_pause([3], 4.0, 4.5, tier=1)
    tracer.on_token(req, 5.0)
    tracer.on_finish(req, 1, "length", 5.0, tokens=4, e2e_s=4.0)
    tr = tracer.traces[3]
    tr.validate()
    assert tr.stall_reason == "no_pages"
    assert [s.kind for s in tr.spans] == [
        "queued", "prefill", "prefill", "decode", "decode",
        "reshard_pause", "decode", "done"]
    assert tr.duration_s("reshard_pause") == pytest.approx(0.5)
    assert tr.reconcile(4.0) == pytest.approx(0.0)
    segs = tr.by_kind("decode")
    assert [s.attrs["tokens"] for s in segs] == [1, 1, 1]
    assert tracer.open_requests() == []


# --------------------------------------------------- engine integration
def test_engine_spans_reconcile_with_e2e(tiny_llama):
    """THE acceptance property: on a seeded Poisson trace, every
    request's queued + prefill + decode + pause span durations
    reconcile with its recorded e2e_s (within one engine-step quantum;
    the tracer's tiling makes it exact to float rounding)."""
    model, params = tiny_llama
    registry = MetricsRegistry()
    tracer = RequestTracer(registry=registry)
    arrivals = serving.poisson_arrivals(8, 50.0, seed=3)
    reqs = serving.synthetic_requests(8, vocab_size=256,
                                      prompt_lens=(3, 20), max_new=(2, 8),
                                      arrivals=arrivals, seed=3)
    eng = _engine(model, params, registry=registry, tracer=tracer,
                  num_slots=2, num_pages=10)
    results = eng.run(reqs)
    assert len(results) == 8
    quantum = registry.histogram("serve.token_latency_s").vmax
    assert len(tracer.traces) == 8
    for res in results:
        tr = tracer.traces[res.rid]
        tr.validate()
        resid = tr.reconcile(res.stats.e2e_s)
        assert resid is not None and resid <= max(quantum, 1e-9)
        assert resid <= 1e-6          # tiling is exact, not just bounded
        # the queued span IS the queue wait; prefill ends at TTFT
        assert tr.duration_s("queued") == \
            pytest.approx(res.stats.queue_wait_s, abs=1e-9)
        assert (tr.duration_s("queued") + tr.duration_s("prefill")) == \
            pytest.approx(res.stats.ttft_s, abs=1e-9)
        assert tr.terminal.attrs["tokens"] == len(res.tokens)
    # under-provisioned run: some request must have actually stalled
    assert any(tr.stall_reason in ("no_slot", "no_pages")
               for tr in tracer.traces.values())
    assert registry.counter_value("serve.spans", span="done") == 8


def test_two_class_slo_attainment_separates(tiny_llama, tmp_path):
    """Acceptance: a two-class trace with deliberately tight class-B
    targets shows class-separated attainment in BOTH report surfaces
    (tools_serving_report's path and tools_obs_report's section)."""
    model, params = tiny_llama
    gold = SLOClass("gold", ttft_s=60.0, token_gap_s=60.0)   # lax
    bulk = SLOClass("tight", ttft_s=1e-9, token_gap_s=1e-9)  # impossible
    log_path = str(tmp_path / "two_class.jsonl")
    run_log = RunLog(log_path)
    registry = MetricsRegistry()
    tracer = RequestTracer(run_log=run_log, registry=registry)
    reqs = serving.synthetic_requests(
        6, vocab_size=256, prompt_lens=(3, 10), max_new=(2, 5),
        arrivals=serving.poisson_arrivals(6, 50.0, seed=5),
        slo_classes=[gold, bulk], seed=5)
    eng = _engine(model, params, registry=registry, run_log=run_log,
                  tracer=tracer, num_slots=2)
    results = eng.run(reqs)
    run_log.close()
    assert len(results) == 6

    records = RunLog.read(log_path)
    rep = slo_report.serving_report(records)
    assert set(rep["classes"]) == {"gold", "tight"}
    assert rep["classes"]["gold"]["attainment"]["slo"] == 1.0
    assert rep["classes"]["tight"]["attainment"]["slo"] == 0.0
    # goodput counts only within-SLO tokens: tight contributes zero
    assert rep["classes"]["tight"]["goodput_tokens"] == 0
    assert rep["classes"]["gold"]["goodput_tokens"] == \
        rep["classes"]["gold"]["tokens_out"] > 0
    assert rep["goodput_tokens"] < rep["tokens_out"]

    # per-class labeled histograms exist alongside the aggregates
    assert registry.histogram("serve.ttft_s_class",
                              slo_class="gold").count == 3
    assert registry.histogram("serve.ttft_s").count == 6

    # the same classes surface through tools_obs_report's section
    import tools_obs_report
    summary = tools_obs_report.summarize(records)
    srv = summary["serving"]
    assert set(srv["classes"]) == {"gold", "tight"}
    assert srv["slo_attainment"] == pytest.approx(0.5)
    assert srv["goodput_tokens_per_s"] is not None
    assert srv["stall_breakdown"]["requests"]    # span-traced run
    assert srv["reconciliation"]["max_residual_s"] <= 1e-6


def test_reshard_pause_spans(tiny_llama):
    """A LoadAdaptiveMesh reshard shows up as reshard_pause spans that
    split decode segments — and the tiling still reconciles."""
    from hetu_tpu.core.mesh import MeshConfig
    from hetu_tpu.parallel.strategy import ParallelStrategy
    model, params = tiny_llama
    mgr = serving.LoadAdaptiveMesh(
        lambda st: model,
        [(0, ParallelStrategy(mesh=MeshConfig(dp=1, tp=1))),
         (3, ParallelStrategy(mesh=MeshConfig(dp=1, tp=1)))],
        patience=1)
    tracer = RequestTracer()
    reqs = serving.synthetic_requests(8, vocab_size=256, prompt_lens=(3, 6),
                                      max_new=(3, 6), seed=5)
    eng = serving.ServingEngine(
        model, params,
        serving.ServeConfig(num_slots=1, page_size=8, max_len=32,
                            prefill_chunk=8),
        registry=MetricsRegistry(), reshard=mgr, tracer=tracer)
    results = eng.run(reqs)
    assert len(results) == 8 and mgr.reshards >= 2
    pauses = [s for tr in tracer.traces.values()
              for s in tr.by_kind("reshard_pause")]
    assert pauses, "reshards happened but no pause spans"
    assert all(s.dur_s > 0 for s in pauses)
    for res in results:
        tr = tracer.traces[res.rid]
        tr.validate()
        assert tr.reconcile(res.stats.e2e_s) <= 1e-6


def test_serve_trace_flag_gates_tracer(tiny_llama, monkeypatch):
    model, params = tiny_llama
    eng = _engine(model, params)
    assert eng.tracer is None, "tracer without the flag"
    monkeypatch.setenv("HETU_TPU_SERVE_TRACE", "1")
    eng2 = _engine(model, params)
    assert eng2.tracer is not None
    res = eng2.run([Request(rid=0, prompt=np.ones(4, np.int32),
                            max_new_tokens=2)])
    assert len(res) == 1
    eng2.tracer.traces[0].validate()


# ------------------------------------------------------ chrome rendering
def test_serving_trace_renders_per_slot_lanes(tiny_llama, tmp_path):
    """Acceptance (a): the Chrome trace has per-slot lanes with every
    request's spans present, a queue lane, counter lanes and
    admission/eviction instants — and parses as Trace Event JSON."""
    from hetu_tpu.obs.trace import merge_runlogs, serving_trace
    model, params = tiny_llama
    log_path = str(tmp_path / "render.jsonl")
    run_log = RunLog(log_path)
    tracer = RequestTracer(run_log=run_log)
    reqs = serving.synthetic_requests(
        6, vocab_size=256, prompt_lens=(3, 16), max_new=(2, 6),
        arrivals=serving.poisson_arrivals(6, 60.0, seed=7), seed=7)
    eng = _engine(model, params, run_log=run_log, tracer=tracer,
                  num_slots=2)
    results = eng.run(reqs)
    run_log.close()
    assert len(results) == 6

    records = RunLog.read(log_path)
    out = str(tmp_path / "trace.json")
    serving_trace(records).save(out)
    with open(out) as f:
        events = json.load(f)
    lanes = {e["args"]["name"] for e in events
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert any(lane.startswith("decode slot") for lane in lanes)
    assert "queue (stall attribution)" in lanes
    # every request contributes spans on slot lanes AND a queued span
    for rid in range(6):
        mine = [e for e in events if e.get("ph") == "X"
                and e["name"].startswith(f"r{rid} ")]
        kinds = {e["cat"] for e in mine}
        assert "queued" in kinds and "prefill" in kinds, (rid, kinds)
        assert any(str(e["tid"]).startswith("slot ") for e in mine)
    # counter lanes + instants
    assert any(e.get("ph") == "C" and e["name"] == "queue_depth"
               for e in events)
    assert any(e.get("ph") == "C" and e["name"] == "page_util"
               for e in events)
    assert any(e.get("ph") == "i" and e["cat"] == "serve:admit"
               for e in events)
    assert any(e.get("ph") == "i" and e["cat"] == "serve:done"
               for e in events)

    # the same records merge into a cluster timeline (serving lane)
    merged = merge_runlogs({"w0": records})
    mev = merged.events
    assert any(e.get("tid") == "serving" and e.get("ph") == "X"
               for e in mev)


# ------------------------------------------------------ health detectors
def test_serving_health_ttft_regression():
    from hetu_tpu.obs.health import ServingHealthMonitor
    reg = MetricsRegistry()
    mon = ServingHealthMonitor(registry=reg, warmup=4, cooldown_steps=2)
    for i in range(8):
        assert mon.observe_ttft(0.05, step=i, t=float(i)) == []
    fired = mon.observe_ttft(1.0, step=9, t=9.0)
    assert [f["anomaly"] for f in fired] == ["ttft_regression"]
    assert reg.counter_value("health.ttft_regression") == 1
    # cooldown: an immediate second spike at the same step is quiet
    assert mon.observe_ttft(1.2, step=9, t=9.1) == []


def test_serving_health_queue_and_pages():
    from hetu_tpu.obs.health import ServingHealthMonitor
    reg = MetricsRegistry()
    mon = ServingHealthMonitor(registry=reg, warmup=4, queue_min=4,
                               page_streak=3, cooldown_steps=100)
    for i in range(8):
        fired = mon.observe_step(i, queue_depth=1, page_util=0.2, t=float(i))
        assert fired == []
    fired = mon.observe_step(9, queue_depth=40, page_util=0.2, t=9.0)
    assert [f["anomaly"] for f in fired] == ["queue_depth_blowup"]

    # page exhaustion needs the streak AND queued demand
    mon2 = ServingHealthMonitor(registry=reg, warmup=2, page_streak=3)
    fired = []
    for i in range(2):
        fired += mon2.observe_step(i, queue_depth=0, page_util=0.99,
                                   t=float(i))
    assert fired == [], "no queued demand -> hot pool is fine"
    for i in range(2, 5):
        fired += mon2.observe_step(i, queue_depth=2, page_util=0.99,
                                   t=float(i))
    assert [f["anomaly"] for f in fired] == ["page_exhaustion_imminent"]
    assert reg.counter_value("health.page_exhaustion_imminent") == 1


def test_health_flag_gates_serving_monitor(monkeypatch):
    from hetu_tpu.obs.health import maybe_serving_health_monitor
    assert maybe_serving_health_monitor() is None
    monkeypatch.setenv("HETU_TPU_HEALTH", "1")
    assert maybe_serving_health_monitor() is not None


# ----------------------------------------------------- cluster telemetry
def test_serving_telemetry_reaches_cluster_snapshot():
    """serve.* counters/gauges and serve events ride the telemetry push;
    the aggregator's snapshot grows a 'serving' digest and
    tools_cluster renders the serving-workers table."""
    from hetu_tpu.obs.aggregate import ClusterAggregator, TelemetrySource
    import tools_cluster
    reg = MetricsRegistry()
    src = TelemetrySource(worker=0, registry=reg)
    reg.inc("serve.requests_done", 5)
    reg.inc("serve.tokens_out", 120)
    reg.set_gauge("serve.queue_depth", 3)
    reg.set_gauge("serve.page_util", 0.5)
    src.note_event({"kind": "serve", "event": "done", "t": 1.0, "req": 0})
    agg = ClusterAggregator(registry=MetricsRegistry())
    ack = agg.ingest(src.payload())
    assert ack["applied"]
    snap = agg.snapshot()
    srv = snap["workers"]["0"]["serving"]
    assert srv["requests_done"] == 5 and srv["tokens_out"] == 120
    assert srv["queue_depth"] == 3
    assert any(e.get("kind") == "serve"
               for e in agg._workers[0].events)
    text = tools_cluster.render_dashboard(snap, {})
    assert "serving workers:" in text and "120" in text


# ----------------------------------------------------------- fuzz + CLI
def test_chaos_serving_scenario(tmp_path):
    """The chaos-harness serving scenario: burst arrivals + an injected
    slow-decode window; the recovery report carries per-class SLO
    attainment from the slo_report path."""
    from hetu_tpu.chaos.harness import named_plan, run_serving_chaos_demo
    plan = named_plan("serve-burst", at_step=4, count=6, delay_s=0.1)
    report = run_serving_chaos_demo(str(tmp_path), plan, requests=10,
                                    rate=80.0, burst=5)
    assert report["completed"]
    assert report["injected"].get("slow_worker") == 6
    slo = report["slo"]
    assert set(slo["classes"]) == {"gold", "bulk"}
    assert slo["requests"] == 10
    # bulk is uncontracted -> vacuously attained; gold pays for the burst
    assert slo["classes"]["bulk"]["attainment"]["slo"] == 1.0
    assert slo["reconciliation"]["max_residual_s"] <= 1e-6


def test_chaos_serve_preempt_scenario(tmp_path):
    """The serve-preempt schedule (the PR 7 follow-up closed in PR 15):
    the slow-decode window pins bulk decodes on both slots, so gold
    (priority 2) arrivals evict-and-requeue them — preemptions land in
    the engine counter AND the report's preemptions section, victims
    all bulk, and every request (including the bumped ones) still
    completes."""
    from hetu_tpu.chaos.harness import named_plan, run_serving_chaos_demo
    plan = named_plan("serve-preempt", at_step=4, count=12, delay_s=0.15)
    report = run_serving_chaos_demo(str(tmp_path), plan, requests=12,
                                    rate=80.0, burst=6, preempt=True)
    assert report["completed"]
    assert report["preemptions"] >= 1
    pre = report["slo"]["preemptions"]
    assert pre["preemptions"] == report["preemptions"]
    assert set(pre["victim_classes"]) == {"bulk"}
    assert set(pre["preemptor_classes"]) == {"gold"}
    # span tiling survives the requeues exactly
    assert report["slo"]["reconciliation"]["max_residual_s"] <= 1e-6


def test_chaos_serve_failover_flake_checked(tmp_path):
    """The serve-failover schedule through the real engine, run at five
    different workload seeds (the flake check): the kill fires exactly
    once, every in-flight request requeues under its budget and replays
    to completion (no retry_exhausted, all `length` finishes), and the
    report's failover section carries the retry accounting per class."""
    from hetu_tpu.chaos.harness import named_plan, run_serving_chaos_demo
    for seed in range(5):
        # before the fifth step: the first burst is decoding at every
        # seed (at the schedule's own step 6, seed 2's only live slot has
        # its last token queued on the device: `fail_over` fetches it
        # first, and the kill then finds no slot to requeue)
        plan = named_plan("serve-failover", at_step=4)
        report = run_serving_chaos_demo(
            str(tmp_path / f"s{seed}"), plan, requests=10, rate=80.0,
            burst=5, retry_budget=2, seed=seed)
        assert report["completed"], f"seed {seed} lost requests"
        assert report["faults"]["serve.failovers"] == 1
        fo = report["slo"]["failover"]
        assert fo["failovers"] == 1
        assert fo["requeued"] >= 1, f"seed {seed}: kill hit empty slots"
        assert fo["retry_exhausted"] == 0
        assert fo["finished_after_retry"] == fo["requeued"]
        assert sum(fo["retried_by_class"].values()) == fo["requeued"]
        assert report["finished_reasons"] == {"length": 10}
        assert report["slo"]["reconciliation"]["max_residual_s"] <= 1e-6


def test_chaos_serve_brownout_flake_checked(tmp_path):
    """The serve-brownout schedule: a decode-stall window over a
    starved pool trips the sustained-pressure policy at every one of
    five seeds — queued low-priority requests terminate `brownout_shed`
    (real terminal outcomes: completed + shed partitions the workload),
    the report's brownout section attributes the sheds per class, and
    the health detectors metered the shedding."""
    from hetu_tpu.chaos.harness import named_plan, run_serving_chaos_demo
    for seed in range(5):
        plan = named_plan("serve-brownout")
        report = run_serving_chaos_demo(
            str(tmp_path / f"s{seed}"), plan, requests=18, rate=80.0,
            burst=6, brownout=True, brownout_page_high=0.5,
            brownout_streak=2, num_pages=8, seed=seed)
        reasons = report["finished_reasons"]
        shed = reasons.get("brownout_shed", 0)
        assert shed >= 1, f"seed {seed}: pressure never tripped"
        assert shed + reasons.get("length", 0) \
            + reasons.get("eos", 0) == 18
        bo = report["slo"]["brownout"]
        assert bo["shed"] == shed
        assert sum(bo["by_class"].values()) == shed
        # the lowest-priority band pays first
        assert bo["by_class"].get("bulk", 0) >= 1
        assert report["faults"]["serve.brownout_shed"] == shed
        assert any("brownout" in k for k in report["detectors"]), \
            "health detectors missed the shed burst"


def test_cli_serving_trace_and_report(tmp_path, capsys):
    """CLI smoke (mirrors test_cli_self_is_clean): one tools_serving.py
    --trace run with classes + chrome trace, then
    tools_serving_report.py over its runlog — JSON schemas pinned."""
    import tools_serving
    import tools_serving_report
    runlog = str(tmp_path / "cli.jsonl")
    chrome = str(tmp_path / "cli_trace.json")
    rc = tools_serving.main([
        "--requests", "4", "--trace", "poisson", "--rate", "50",
        "--slots", "2", "--page", "8", "--max-len", "32", "--chunk", "8",
        "--prompt-lens", "3,8", "--max-new", "2,4",
        "--slo-class", "gold:30:30", "--slo-class", "bulk",
        "--runlog", runlog, "--chrome-trace", chrome, "--seed", "1"])
    out = capsys.readouterr().out
    assert rc == 0
    rep = json.loads(out)
    for key in ("requests", "tokens_out", "ttft_s", "e2e_s",
                "finished_by", "slo_classes"):
        assert key in rep, key
    assert rep["requests"] == 4
    with open(chrome) as f:
        events = json.load(f)
    assert any(e.get("ph") == "X" for e in events)

    rc = tools_serving_report.main([runlog])
    text = capsys.readouterr().out
    assert rc == 0
    assert "serving report: 4 requests" in text
    assert "stall attribution" in text and "span reconciliation" in text

    rc = tools_serving_report.main([runlog, "--json", "--per-request"])
    out = capsys.readouterr().out
    assert rc == 0
    rep = json.loads(out)
    for key in ("report_schema", "requests", "classes", "slo_attainment",
                "goodput_tokens", "stall_breakdown", "reconciliation",
                "per_request"):
        assert key in rep, key
    assert rep["report_schema"] == 1
    assert set(rep["classes"]) == {"gold", "bulk"}
    assert len(rep["per_request"]) == 4
    row = rep["per_request"][0]
    for key in ("rid", "slo_class", "ttft_s", "e2e_s", "tokens",
                "stall_reason", "slo_ok", "residual_s"):
        assert key in row, key

    # a runlog with no serving records is a loud nonzero exit
    empty = str(tmp_path / "empty.jsonl")
    RunLog(empty).close()
    with open(empty, "w") as f:
        f.write(json.dumps({"schema": 1, "kind": "step", "t": 0.0,
                            "step": 1, "step_time_s": 0.1}) + "\n")
    assert tools_serving_report.main([empty]) == 1
    capsys.readouterr()


def test_single_token_request_gap_is_vacuously_attained():
    """A gap-contracted request that finishes on its first token has no
    inter-token gap to violate: it must count as attained, not a miss."""
    done = {"kind": "serve", "event": "done", "t": 0.0, "req": 0,
            "reason": "eos", "tokens": 1, "ttft_s": 0.01, "e2e_s": 0.01,
            "now": 1.0, "slo_class": "gold", "slo_ttft_s": 0.5,
            "slo_token_gap_s": 0.05}
    rep = slo_report.serving_report([done])
    assert rep["classes"]["gold"]["attainment"]["slo"] == 1.0
    assert rep["classes"]["gold"]["goodput_tokens"] == 1


# -------------------------------------------------- fleet stitch (PR 20)
def _hop(rid, trace, spans, *, tier=None, replica=None, clock="driver",
         slo="default"):
    tr = RequestTrace(rid=rid, trace=trace, slo_class=slo)
    for kind, t0, t1, attrs in spans:
        tr.spans.append(Span(kind, t0, t1, rid=rid, trace=trace,
                             slo_class=slo, clock=clock, tier=tier,
                             replica=replica, attrs=attrs))
    return tr


def _disagg_fleet_trace():
    """One rid through the two-tier pipeline: a prefill-tier hop that
    ships, plus the decode hop that adopts the KV and finishes."""
    pf = _hop(5, "pf.5", [("queued", 0.0, 1.0, {"reason": "none"}),
                          ("prefill", 1.0, 3.0, {"chunk": 2}),
                          ("done", 3.0, 3.0, {"reason": "shipped"})],
              tier="prefill", replica=0)
    dec = _hop(5, "d.5", [("queued", 0.0, 4.0, {"reason": "none"}),
                          ("prefill", 4.0, 4.0, {"chunk": 0,
                                                 "last": True}),
                          ("decode", 4.0, 6.0, {"tokens": 5}),
                          ("done", 6.0, 6.0, {"reason": "eos",
                                              "tokens": 5})],
               tier="decode")
    events = [{"event": "dispatch", "req": 5, "tier": "prefill",
               "now": 0.0},
              {"event": "ship", "req": 5, "seq": 0, "now": 3.0},
              {"event": "admit", "req": 5, "disagg": True, "now": 4.0}]
    return FleetTrace.stitch(traces=[pf, dec], events=events)[5]


def test_span_clock_basis_stamped_and_schema_pinned():
    """Satellite: every span record carries its ``clock`` basis; the
    hop-identity fields ride only when stamped (a colocated engine's
    records keep their pre-fleet shape); the runlog schema docstring
    documents the new rows."""
    rec = Span("decode", 0.0, 1.0, rid=1, trace="t1").record()
    assert rec["clock"] == "driver"
    assert "tier" not in rec and "replica" not in rec
    rec2 = Span("decode", 0.0, 1.0, rid=1, trace="t1", tier="prefill",
                replica=3, clock="wall").record()
    assert (rec2["clock"], rec2["tier"], rec2["replica"]) \
        == ("wall", "prefill", 3)
    back = Span.from_record(dict(rec2, kind="span", schema=1, t=0.0))
    assert (back.clock, back.tier, back.replica) == ("wall", "prefill", 3)
    assert "clock" not in back.attrs and "tier" not in back.attrs
    with pytest.raises(ValueError, match="clock"):
        Span("decode", 0, 1, rid=1, trace="t", clock="gps")
    # the schema rows are doc-pinned: obs/runlog.py's record table names
    # the clock basis, the hop-identity fields, the hedge_withdrawn
    # terminal and the dispatch/hedge_dupe serve events
    import hetu_tpu.obs.runlog as runlog_mod
    for needle in ("clock", "hedge_withdrawn", "dispatch", "hedge_dupe",
                   "replica"):
        assert needle in runlog_mod.__doc__


def test_stitch_refuses_mixed_clock_bases():
    a = _hop(1, "ta", [("queued", 0, 1, {"reason": "none"}),
                       ("done", 1, 1, {"reason": "eos"})])
    b = _hop(1, "tb", [("queued", 0, 1, {"reason": "none"}),
                       ("done", 1, 1, {"reason": "eos"})], clock="wall")
    with pytest.raises(ValueError, match="mixed clock bases"):
        FleetTrace.stitch(traces=[a, b])


def test_fleet_stitch_disagg_edges_and_critical_path():
    """The tentpole in miniature: a prefill hop + decode hop + the
    frontend/shipment events stitch into one DAG whose edges name the
    causal story and whose critical path sums exactly to e2e/TTFT."""
    from hetu_tpu.obs.critpath import critical_path
    ft = _disagg_fleet_trace()
    ft.validate()
    assert sorted(e["kind"] for e in ft.edges) \
        == ["adopt", "dispatch", "ship"]
    assert ft.primary.trace == "d.5"
    assert ft.span_seconds == pytest.approx(ft.lifetime_seconds)
    assert ft.span_seconds == pytest.approx(3.0 + 6.0)
    cp = critical_path(ft)
    segs = cp["segments"]
    # the decode hop's queued 0->4 is carved by the pf hop's boundaries:
    # 0-1 frontend_queue (pf admission wait), 1-3 remote prefill,
    # 3-4 shipment wait; decode then runs 4->6
    assert segs["frontend_queue"] == pytest.approx(1.0)
    assert segs["prefill"] == pytest.approx(2.0)
    assert segs["shipment_wait"] == pytest.approx(1.0)
    assert segs["decode"] == pytest.approx(2.0)
    assert sum(segs.values()) == pytest.approx(cp["e2e_s"])
    assert abs(cp["residual_s"]) < 1e-9
    # TTFT clips at the adopted last-chunk boundary (t=4): the same
    # pieces minus decode
    assert cp["ttft_s"] == pytest.approx(4.0)
    assert abs(cp["ttft_residual_s"]) < 1e-9
    assert cp["ttft_segments"]["decode"] == pytest.approx(0.0)


def test_hedge_withdrawn_closes_loser_with_exact_accounting():
    """Satellite: the losing hedge copy gets a ``hedge_withdrawn``
    terminal, so stitched span-seconds equal the sum of per-hop
    lifetimes INCLUDING the loser's discarded work — and the stitch
    still sees exactly one client terminal."""
    win = RequestTracer(keep=True, replica=0)
    lose = RequestTracer(keep=True, replica=1)
    req = Request(rid=9, prompt=np.ones(4, np.int32), max_new_tokens=4,
                  arrival_t=0.0)
    win.on_submit(req, at=0.0)
    win.on_admit(req, 0, 1.0)
    win.on_first_token(req, 0, 2.0, chunk=1)
    win.on_finish(req, 0, "eos", 3.0, tokens=4, e2e_s=3.0)
    lose.on_submit(req, at=1.5)
    lose.on_admit(req, 1, 2.0)
    lose.on_first_token(req, 1, 2.5, chunk=1)
    lose.on_withdraw(req, 3.0, reason="hedge_lost")
    events = [{"event": "hedge", "req": 9, "primary": 0, "hedge": 1,
               "now": 1.5}]
    ft = FleetTrace.stitch(traces=win.completed + lose.completed,
                           events=events)[9]
    ft.validate()
    loser_hop = next(h for h in ft.hops if h.replica == 1)
    assert loser_hop.terminal.kind == "hedge_withdrawn"
    assert loser_hop.terminal.attrs["reason"] == "hedge_lost"
    kinds = {e["kind"] for e in ft.edges}
    assert {"hedge_fork", "hedge_withdraw"} <= kinds
    assert ft.primary.replica == 0
    assert ft.span_seconds == pytest.approx(ft.lifetime_seconds)
    assert ft.span_seconds == pytest.approx(3.0 + 1.5)
    assert ft.e2e_s == pytest.approx(3.0)


def test_request_tree_schema_and_render():
    """`tools_serving_report.py --request` shape pin: the stitched hop
    tree's JSON schema, and the text render's primary-hop star +
    highlighted critical path."""
    ft = _disagg_fleet_trace()
    recs = [dict(sp.record(), kind="span", schema=1, t=0.0)
            for h in ft.hops for sp in h.spans]
    recs += [dict(ev, kind="serve", schema=1, t=0.0)
             for ev in ft.events]
    tree = slo_report.request_tree(slo_report.collect(recs), 5)
    assert tree["request_tree_schema"] == slo_report.REQUEST_TREE_SCHEMA
    assert sorted(tree) == ["clock", "critical_path", "e2e_s", "edges",
                            "hops", "lifetime_seconds",
                            "request_tree_schema", "rid", "slo_class",
                            "span_seconds"]
    assert sorted(tree["hops"][0]) == [
        "attempts", "hop", "lifetime_s", "primary", "replica", "spans",
        "t0", "t1", "terminal", "tier", "trace"]
    assert {h["hop"]: h["primary"] for h in tree["hops"]} \
        == {"prefill/0": False, "decode": True}
    # edges are labelled by hop identity, not raw trace ids
    assert {(e["src"], e["dst"]) for e in tree["edges"]} \
        == {("frontend", "prefill/0"), ("prefill/0", "decode"),
            ("wire", "decode")}
    txt = slo_report.render_request_tree(tree)
    assert "* decode" in txt and "critical path" in txt
    assert "--ship-->" in txt and "dominant" in txt
    # the missing-rid path returns None (the CLI exits loudly)
    assert slo_report.request_tree(slo_report.collect(recs), 404) is None


def test_stitched_trace_emits_matched_flow_pairs():
    """Satellite: the Chrome-trace fleet render draws every causal edge
    as a ph "s"/"f" flow pair (matched by id, finish bound to the
    enclosing slice) between the tier lanes."""
    from hetu_tpu.obs.trace import stitched_trace
    ft = _disagg_fleet_trace()
    tr = stitched_trace({5: ft})
    starts = [e for e in tr.events if e["ph"] == "s"]
    finishes = [e for e in tr.events if e["ph"] == "f"]
    assert len(starts) == len(ft.edges) == 3
    assert sorted((e["cat"], e["id"]) for e in starts) \
        == sorted((e["cat"], e["id"]) for e in finishes)
    assert all(e["bp"] == "e" for e in finishes)
    # the ship edge leaves the prefill lane and lands on the decode lane
    ship_s = next(e for e in starts if e["cat"] == "edge:ship")
    ship_f = next(e for e in finishes if e["cat"] == "edge:ship")
    assert ship_s["tid"] == "prefill/0" and ship_f["tid"] == "decode"
    lanes = {e["args"]["name"] for e in tr.events
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert {"frontend / client", "prefill/0 hop", "decode hop"} <= lanes
    json.dumps(tr.events)   # the file form is plain JSON


def test_spans_collect_ignores_foreign_records():
    recs = [
        {"kind": "step", "t": 0.0},
        {"kind": "span", "t": 0.0, "span": "queued", "req": 1,
         "trace": "a", "t0": 0.0, "t1": 1.0, "reason": "none"},
        {"kind": "span", "t": 0.0, "span": "done", "req": 1,
         "trace": "a", "t0": 1.0, "t1": 1.0, "reason": "eos",
         "tokens": 3},
    ]
    traces = collect_traces(recs)
    assert set(traces) == {1}
    traces[1].validate()
    assert traces[1].tokens == 3
    assert traces[1].stall_reason in STALL_REASONS
