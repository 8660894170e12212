"""Cluster-scope telemetry (ISSUE 5): rpc metric aggregation with
delta-encoded pushes, online straggler scoring, the training health
monitor, merged cluster traces, and RunLog rotation.  All tier-1:
CPU-only, seeded, no model compile."""
import json
import os
import time

import pytest

from hetu_tpu import chaos
from hetu_tpu.chaos import FaultPlan, FaultSpec
from hetu_tpu.obs.aggregate import (ClusterAggregator, TelemetrySource,
                                    merge_offsets, push_interval,
                                    straggler_report)
from hetu_tpu.obs.health import HealthMonitor, maybe_health_monitor
from hetu_tpu.obs.metrics import MetricsRegistry
from hetu_tpu.obs.runlog import RunLog
from hetu_tpu.rpc.client import CoordinationClient, fetch_cluster_snapshot
from hetu_tpu.rpc.server import CoordinationServer

# the first save of a process loads orbax, for seconds: before a heartbeat
# is timed, not while (conftest.orbax_loaded)
pytestmark = pytest.mark.usefixtures("orbax_loaded")


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    chaos.reset()
    yield
    chaos.reset()


@pytest.fixture
def server():
    s = CoordinationServer(world_size=4, heartbeat_timeout=1.0)
    yield s
    s.close()


def _client(server, **kw):
    kw.setdefault("auto_heartbeat", False)
    kw.setdefault("op_timeout", 10.0)
    kw.setdefault("max_reconnect_wait", 15.0)
    return CoordinationClient("127.0.0.1", server.port, **kw)


# ------------------------------------------------------------ delta source
def test_source_delta_encodes_counters():
    reg = MetricsRegistry()
    reg.inc("work.done", 3)            # pre-source history: NOT shipped
    src = TelemetrySource(worker=0, registry=reg)
    reg.inc("work.done", 5)
    reg.inc("rpc.op_retries", 2, op="put")
    reg.set_gauge("epoch", 4)
    p1 = src.payload()
    assert p1["worker"] == 0 and p1["seq"] == 1
    assert p1["counters"] == {"work.done": 5.0,
                              "rpc.op_retries{op=put}": 2.0}
    assert p1["gauges"]["epoch"] == 4.0
    # nothing new -> empty delta, seq advances
    p2 = src.payload()
    assert p2["seq"] == 2 and p2["counters"] == {}
    reg.inc("work.done")
    assert src.payload()["counters"] == {"work.done": 1.0}


def test_source_unpush_remerges_undelivered_deltas():
    reg = MetricsRegistry()
    src = TelemetrySource(worker=1, registry=reg)
    reg.inc("c", 7)
    src.note_step(1, 0.1, loss=2.0)
    p = src.payload()
    assert p["counters"] == {"c": 7.0} and len(p["steps"]) == 1
    src.unpush(p)                      # delivery failed: merge back
    p2 = src.payload()
    assert p2["counters"] == {"c": 7.0} and len(p2["steps"]) == 1
    assert p2["seq"] == 2              # seq always advances (new identity)


def test_source_ships_runlog_tail(tmp_path):
    log = RunLog(str(tmp_path / "r.jsonl"), tail_records=16)
    src = TelemetrySource(worker=0, registry=MetricsRegistry(),
                          runlog_fn=lambda: log)
    log.log("compile", name="train_step", estimated_mfu=0.41,
            comm_bytes=1234)
    log.step(1, 0.1)                   # step kinds do NOT ride the tail
    p = src.payload()
    kinds = [e["kind"] for e in p["events"]]
    assert kinds == ["compile"]
    assert p["events"][0]["estimated_mfu"] == 0.41


def test_pusher_retries_same_seq_when_delivery_fails():
    """A failed delivery is re-sent with the SAME (boot, seq) identity —
    so a push the server applied but whose ack was lost dedupes
    server-side instead of double-counting on a rebuilt payload."""
    from hetu_tpu.obs.aggregate import TelemetryPusher

    class FlakyClient:
        rank = 0

        def __init__(self):
            self.seen = []
            self.fail_next = True

        def telemetry_push(self, payload):
            self.seen.append(payload["seq"])
            if self.fail_next:
                self.fail_next = False
                raise ConnectionError("ack lost in the tear")
            return {"applied": True, "seq": payload["seq"]}

    reg = MetricsRegistry()
    client = FlakyClient()
    pusher = TelemetryPusher(client, interval=0, registry=reg, start=False)
    reg.inc("c", 3)
    assert pusher.push_now() is False
    reg.inc("c", 2)                      # accumulates BEHIND the pending
    assert pusher.push_now() is True
    assert client.seen == [1, 1]         # same seq, not a rebuilt one
    nxt = pusher.source.payload()
    assert nxt["seq"] == 2 and nxt["counters"]["c"] == 2.0
    assert "rpc.telemetry_pushes" in nxt["counters"]   # self-accounting


# ------------------------------------------------------------- aggregator
def test_aggregator_dedupes_duplicate_and_accumulates_restart():
    agg = ClusterAggregator(registry=MetricsRegistry())
    p = {"worker": 3, "boot": "a", "seq": 1, "t": time.time(),
         "counters": {"steps": 10.0}, "gauges": {}, "steps": [],
         "events": []}
    assert agg.ingest(p)["applied"] is True
    # duplicated delivery (rpc_dup / client retry): applied exactly once
    assert agg.ingest(dict(p))["applied"] is False
    assert agg.worker_counter(3, "steps") == 10.0
    # worker restart: new boot, seq resets, totals ACCUMULATE
    p2 = dict(p, boot="b", seq=1, counters={"steps": 4.0})
    assert agg.ingest(p2)["applied"] is True
    assert agg.worker_counter(3, "steps") == 14.0
    snap = agg.snapshot()
    assert snap["workers"]["3"]["dup_pushes"] == 1
    assert snap["workers"]["3"]["counters"]["steps"] == 14.0


def test_snapshot_windows_steps_and_estimates_offset():
    agg = ClusterAggregator(registry=MetricsRegistry())
    now = time.time()
    agg.ingest({"worker": 0, "boot": "a", "seq": 1, "t": now,
                "hb_rtt_s": 0.2, "counters": {}, "gauges": {},
                "steps": [[i, now - 200 + i, 0.5, 2.0, None]
                          for i in range(5)]        # stale: outside window
                + [[10 + i, now - i * 0.1, 0.25, 1.5, 100.0]
                   for i in range(4)],              # recent
                "events": [{"kind": "compile", "estimated_mfu": 0.4,
                            "comm_bytes": 99.0},
                           {"kind": "anomaly", "anomaly": "loss_spike"}]},
               recv_t=now + 0.4)
    snap = agg.snapshot(window_s=60.0, now=now)
    w = snap["workers"]["0"]
    assert w["steps_total"] == 9 and w["steps_window"] == 4
    assert w["step_time_p50"] == pytest.approx(0.25)
    assert w["loss"] == 1.5 and w["tokens_per_s"] == 100.0
    assert w["estimated_mfu"] == 0.4
    assert w["comm_bytes_per_step"] == 99.0
    assert w["anomalies"] == {"loss_spike": 1}
    # offset ~ recv - send - rtt/2 = 0.4 - 0.1 = 0.3
    assert w["clock_offset_s"] == pytest.approx(0.3, abs=0.05)
    assert merge_offsets(snap) == {"0": w["clock_offset_s"]}


# ------------------------------------------------------ straggler scoring
def _snap(p50s, n=10):
    return {"t": 0.0, "window_s": 60.0,
            "workers": {str(r): {"step_time_p50": v, "steps_window": n}
                        for r, v in p50s.items()}}


def test_straggler_report_flags_slow_rank():
    rep = straggler_report(_snap({0: 0.10, 1: 0.11, 2: 0.31}))
    assert rep["stragglers"] == [2]
    w2 = rep["workers"]["2"]
    # nearest-rank median of the other two medians is 0.10
    assert w2["straggler"] and w2["ratio"] == pytest.approx(0.31 / 0.10)
    # healthy spread does not flag
    assert straggler_report(_snap({0: 0.10, 1: 0.11}))["stragglers"] == []
    # two-worker degenerate-MAD case still works (the acceptance shape)
    rep2 = straggler_report(_snap({0: 0.04, 1: 0.19}))
    assert rep2["stragglers"] == [1]
    # too few samples: no verdict at all
    assert straggler_report(_snap({0: 0.04, 1: 0.19}, n=1))["workers"] == {}


def test_straggler_flagged_within_three_pushes():
    """The acceptance bound: with a slowed worker pushing inflated step
    times, the aggregator's report flags it within 3 telemetry pushes."""
    agg = ClusterAggregator(registry=MetricsRegistry())
    reg0, reg1 = MetricsRegistry(), MetricsRegistry()
    s0 = TelemetrySource(worker=0, registry=reg0)
    s1 = TelemetrySource(worker=1, registry=reg1)
    flagged_at = None
    for push in range(1, 4):
        for i in range(4):             # 4 steps per push interval
            step = push * 10 + i
            s0.note_step(step, 0.04)
            s1.note_step(step, 0.19)   # the slow_worker inflation
        agg.ingest(s0.payload())
        agg.ingest(s1.payload())
        rep = agg.straggler_report()
        if rep["stragglers"]:
            flagged_at = push
            break
    assert flagged_at is not None and flagged_at <= 3
    assert rep["stragglers"] == [1]


def test_aggregator_straggler_gauges_and_runlog_event(tmp_path):
    log = RunLog(str(tmp_path / "coord.jsonl"))
    reg = MetricsRegistry()
    agg = ClusterAggregator(registry=reg, runlog=log)
    now = time.time()
    for rank, dt in ((0, 0.04), (1, 0.19)):
        agg.ingest({"worker": rank, "boot": "x", "seq": 1, "t": now,
                    "counters": {}, "gauges": {},
                    "steps": [[i, now, dt, None, None] for i in range(5)],
                    "events": []})
    rep = agg.straggler_report()
    assert rep["stragglers"] == [1]
    assert reg.gauge_value("cluster.straggler_ratio", rank="1") > 2.0
    assert reg.counter_value("cluster.stragglers_flagged") == 1.0
    # flag transition logged once; an unchanged set logs nothing new
    agg.straggler_report()
    log.close()
    events = [r for r in RunLog.read(str(tmp_path / "coord.jsonl"))
              if r["kind"] == "straggler"]
    assert len(events) == 1 and events[0]["stragglers"] == [1]


# --------------------------------------------------------- health monitor
def test_health_monitor_step_time_regression_and_cooldown():
    hm = HealthMonitor(registry=MetricsRegistry(), warmup=4,
                       cooldown_steps=8)
    fired = []
    for step in range(20):
        dt = 0.05 if step < 10 else 0.25     # 5x regression at step 10
        fired += hm.observe_step(step, dt, loss=2.0)
    kinds = [f["anomaly"] for f in fired]
    assert "step_time_regression" in kinds
    first = next(f for f in fired if f["anomaly"] == "step_time_regression")
    assert first["step"] == 10
    # cooldown: the sustained regression does not fire every step
    assert kinds.count("step_time_regression") <= 2
    assert hm.registry.counter_value(
        "health.step_time_regression") == kinds.count(
            "step_time_regression")


def test_health_monitor_loss_spike_and_nan():
    hm = HealthMonitor(registry=MetricsRegistry(), warmup=4)
    for step in range(8):
        assert hm.observe_step(step, 0.1, loss=2.0 - 0.01 * step) == []
    spike = hm.observe_step(8, 0.1, loss=50.0)
    assert [f["anomaly"] for f in spike] == ["loss_spike"]
    nan = hm.observe_step(9, 0.1, loss=float("nan"), grad_norm=float("inf"))
    assert sorted(f["anomaly"] for f in nan) == ["nan_grad", "nan_loss"]


def test_health_monitor_data_stall_uses_inter_step_gap():
    hm = HealthMonitor(registry=MetricsRegistry(), warmup=4,
                       stall_min_s=0.5)
    t = 1000.0
    for step in range(8):
        t += 0.11                       # 0.1s step + ~0.01s fetch
        hm.observe_step(step, 0.1, t=t)
    t += 0.1 + 3.0                      # the input pipeline stalls 3s
    fired = hm.observe_step(8, 0.1, t=t)
    assert [f["anomaly"] for f in fired] == ["data_stall"]
    assert fired[0]["value"] == pytest.approx(3.0, abs=0.1)


def test_health_monitor_emergency_hook_and_runlog(tmp_path):
    log = RunLog(str(tmp_path / "r.jsonl"))
    saves = []
    hm = HealthMonitor(runlog=log, registry=MetricsRegistry(), warmup=2,
                       emergency_hook=lambda: saves.append(1))
    hm.observe_step(0, 0.1, loss=2.0)
    hm.observe_step(1, 0.1, loss=2.0)
    hm.observe_step(2, 0.1, loss=float("nan"))
    assert saves == [1]                 # nan_loss invoked the hook
    assert hm.registry.counter_value("health.emergency_saves") == 1.0
    log.close()
    recs = [r for r in RunLog.read(str(tmp_path / "r.jsonl"))
            if r["kind"] == "anomaly"]
    assert recs and recs[0]["anomaly"] == "nan_loss"


def test_health_flag_gate(monkeypatch):
    monkeypatch.delenv("HETU_TPU_HEALTH", raising=False)
    assert maybe_health_monitor() is None
    monkeypatch.setenv("HETU_TPU_HEALTH", "1")
    assert isinstance(maybe_health_monitor(), HealthMonitor)


# ----------------------------------------------------------- rpc plumbing
def test_telemetry_wire_codec_roundtrip():
    from hetu_tpu.rpc.wire import decode_telemetry, encode_telemetry
    payload = {"worker": 0, "seq": 3, "counters": {"a{op=x}": 1.5},
               "steps": [[1, 2.0, 0.1, None, None]]}
    assert decode_telemetry(encode_telemetry(payload)) == payload


def test_telemetry_push_and_snapshot_over_rpc(server):
    c0, c1 = _client(server), _client(server)
    for c, dt in ((c0, 0.05), (c1, 0.21)):
        src = TelemetrySource(worker=c.rank, registry=MetricsRegistry())
        for i in range(5):
            src.note_step(i, dt, loss=2.0)
        c.telemetry_push(src.payload())
    # heartbeats so the snapshot can report gaps
    c0._call({"op": "heartbeat", "rank": c0.rank})
    resp = c0.telemetry_snapshot()
    snap, rep = resp["snapshot"], resp["straggler"]
    assert set(snap["workers"]) == {str(c0.rank), str(c1.rank)}
    assert snap["workers"][str(c0.rank)]["heartbeat_gap_s"] is not None
    assert rep["stragglers"] == [c1.rank]
    # an OBSERVER fetch never joins membership
    alive_before = server.alive_ranks()
    obs = fetch_cluster_snapshot("127.0.0.1", server.port)
    assert set(obs["snapshot"]["workers"]) == set(snap["workers"])
    assert server.alive_ranks() == alive_before
    c0.exit(), c1.exit()


def test_push_counters_exact_across_reattach_and_dup(server):
    """The acceptance exactness property: counter aggregation survives a
    mid-push reconnect (drop -> transparent retry after reattach) AND a
    duplicated delivery without double-counting."""
    c = _client(server)
    reg = MetricsRegistry()
    src = TelemetrySource(worker=c.rank, registry=reg)
    chaos.install(FaultPlan([
        FaultSpec(kind="rpc_drop", op="telemetry_push", count=1),
        FaultSpec(kind="rpc_dup", op="telemetry_push", after_calls=2,
                  count=1),
    ]))
    reg.inc("work.steps", 10)
    c.telemetry_push(src.payload())     # dropped -> reconnect -> retried
    assert c.reconnects == 1
    assert server.telemetry.worker_counter(c.rank, "work.steps") == 10.0
    reg.inc("work.steps", 7)
    c.telemetry_push(src.payload())     # duplicated -> applied once
    assert server.telemetry.worker_counter(c.rank, "work.steps") == 17.0
    snap = server.cluster_snapshot()
    w = snap["workers"][str(c.rank)]
    assert w["dup_pushes"] == 1 and w["pushes"] == 2
    c.exit()


# -------------------------------------------------------- elastic consumer
class _HookClient:
    def __init__(self, rank=0, alive=(0, 2)):
        self.rank = rank
        self._alive = list(alive)
        self.stops = 0

    def membership(self):
        return self._alive

    def worker_stop(self, ranks=None):
        self.stops += 1


def test_elastic_straggler_hook_budgeted_replan():
    from hetu_tpu.engine.elastic import ElasticController
    from hetu_tpu.obs.metrics import get_registry
    reports = [{"stragglers": [2]}] * 5
    client = _HookClient()
    ctl = ElasticController(client, trainer_factory=lambda p: None,
                            planner_fn=lambda alive: {},
                            straggler_hook=lambda c: reports.pop(0),
                            straggler_budget=1, straggler_patience=2)
    reg = get_registry()
    before = reg.counter_value("elastic.straggler_replans")
    ctl._check_stragglers()             # strike 1: observe only
    assert client.stops == 0
    ctl._check_stragglers()             # strike 2: persistent -> re-mesh
    assert client.stops == 1
    assert reg.counter_value("elastic.straggler_replans") == before + 1
    ctl._check_stragglers()             # budget exhausted: observe only
    ctl._check_stragglers()
    assert client.stops == 1


def test_elastic_straggler_replan_is_leader_only():
    """The report is cluster-global; only the leader (min alive rank)
    may spend budget on it, or one straggler would trigger up to
    world_size re-meshes."""
    from hetu_tpu.engine.elastic import ElasticController
    follower = _HookClient(rank=2, alive=(0, 2))
    ctl = ElasticController(follower, trainer_factory=lambda p: None,
                            planner_fn=lambda alive: {},
                            straggler_hook=lambda c: {"stragglers": [1]},
                            straggler_budget=5, straggler_patience=1)
    for _ in range(3):
        ctl._check_stragglers()
    assert follower.stops == 0


def test_elastic_observation_only_by_default():
    from hetu_tpu.engine.elastic import ElasticController
    client = _HookClient()
    ctl = ElasticController(client, trainer_factory=lambda p: None,
                            planner_fn=lambda alive: {},
                            straggler_hook=lambda c: {"stragglers": [1]},
                            straggler_patience=1)   # budget defaults to 0
    for _ in range(4):
        ctl._check_stragglers()
    assert client.stops == 0            # flagged, counted, never re-meshed


# ------------------------------------------------------------ merged trace
def test_merge_runlogs_aligns_workers_on_offsets():
    from hetu_tpu.obs.trace import merge_runlogs
    w0 = [{"kind": "step", "t": 100.0, "step": 1, "step_time_s": 0.1},
          {"kind": "anomaly", "t": 100.5, "anomaly": "loss_spike",
           "step": 2}]
    w1 = [{"kind": "step", "t": 90.0, "step": 1, "step_time_s": 0.1}]
    # worker 1's clock is 10s behind the server: offset +10 aligns it
    tr = merge_runlogs({"0": w0, "1": w1}, offsets_s={"1": 10.0})
    pids = {e["pid"] for e in tr.events}
    assert pids == {"worker 0", "worker 1"}
    steps = {e["pid"]: e for e in tr.events
             if e.get("cat") == "step"}
    # both step ENDS land at t=100 server time -> equal ts after shift
    assert steps["worker 0"]["ts"] + steps["worker 0"]["dur"] == \
        pytest.approx(steps["worker 1"]["ts"] + steps["worker 1"]["dur"])
    anomalies = [e for e in tr.events if e.get("cat") == "anomaly"]
    assert len(anomalies) == 1 and anomalies[0]["pid"] == "worker 0"


# ------------------------------------------------------- slow_worker fault
def test_slow_worker_plan_windows_and_roundtrip(tmp_path):
    plan = FaultPlan([FaultSpec(kind="slow_worker", rank=1, at_step=3,
                                count=2, delay_s=0.05)])
    assert plan.step_delay(1, 2) == 0.0
    assert plan.step_delay(1, 3) == 0.05
    assert plan.step_delay(1, 4) == 0.05
    assert plan.step_delay(1, 5) == 0.0
    assert plan.step_delay(0, 3) == 0.0          # wrong rank
    assert plan.summary() == {"slow_worker": 2}
    p = tmp_path / "s.json"
    p.write_text(json.dumps(plan.to_dict()))
    assert FaultPlan.load(str(p)).to_dict() == plan.to_dict()


def test_maybe_slow_step_identity_without_plan():
    from hetu_tpu.chaos import maybe_slow_step
    t0 = time.perf_counter()
    assert maybe_slow_step(None, 0, 5) == 0.0
    assert time.perf_counter() - t0 < 0.05


# ------------------------------------------------------- runlog rotation
def test_runlog_rotation_and_segment_following(tmp_path):
    path = str(tmp_path / "r.jsonl")
    log = RunLog(path, max_bytes=600)
    for i in range(40):
        log.step(i, 0.1, loss=float(i))
    log.close()
    assert log.rotations >= 2
    segs = RunLog.segments(path)
    assert len(segs) == log.rotations + 1
    assert segs[-1] == path and segs[0].endswith(".1")
    recs = RunLog.read(path)
    steps = [r["step"] for r in recs if r["kind"] == "step"]
    assert steps == list(range(40))     # chronological across segments
    markers = [r for r in recs if r["kind"] == "rotated"]
    assert len(markers) == log.rotations
    # each rotated segment ENDS with its marker
    for seg in segs[:-1]:
        last = RunLog.read(seg)[-1] if RunLog.read(seg) else None
        assert last and last["kind"] == "rotated"
    # downstream consumers see the whole run
    from hetu_tpu.obs.trace import trace_from_runlog
    tr = trace_from_runlog(recs)
    assert sum(1 for e in tr.events if e.get("cat") == "step") == 40


def test_runlog_rotation_flag(tmp_path, monkeypatch):
    monkeypatch.setenv("HETU_TPU_RUNLOG_MAX_MB", "1")
    log = RunLog(str(tmp_path / "r.jsonl"))
    assert log._max_bytes == 1 << 20
    log.close()
    monkeypatch.delenv("HETU_TPU_RUNLOG_MAX_MB")
    log2 = RunLog(str(tmp_path / "r2.jsonl"))
    assert log2._max_bytes is None
    log2.close()


def test_runlog_tail_rides_past_disk_failure(tmp_path):
    log = RunLog(str(tmp_path / "r.jsonl"), tail_records=8)
    log.step(1, 0.1)
    log._f.close()                      # simulate the disabled writer
    log.step(2, 0.1)
    tail = log.drain_tail()
    assert [r["step"] for r in tail] == [1, 2]
    assert log.drain_tail() == []


# --------------------------------------------------- memory-profile record
def test_memory_profile_lands_in_step_profiler(monkeypatch):
    from hetu_tpu.utils import profiling
    monkeypatch.setenv("HETU_TPU_MEMORY_PROFILE", "1")
    monkeypatch.setattr(profiling, "device_mem_bytes", lambda: 123456)
    prof = profiling.StepProfiler()
    assert prof.mem_profile
    with prof.step(0):
        pass
    assert prof.last_mem_bytes == 123456


# ------------------------------------------------------ report + dashboard
def test_obs_report_straggler_anomaly_sections():
    from tools_obs_report import summarize
    records = [
        {"kind": "step", "step": i, "step_time_s": 0.1} for i in range(4)
    ] + [
        {"kind": "anomaly", "anomaly": "loss_spike", "step": 2, "t": 10.0},
        {"kind": "anomaly", "anomaly": "step_time_regression", "step": 3,
         "t": 11.0},
        {"kind": "straggler", "t": 12.0, "stragglers": [1],
         "workers": {"0": {"ratio": 1.0}, "1": {"ratio": 3.5}}},
    ]
    out = summarize(records)
    assert out["anomalies"]["total"] == 2
    assert out["anomalies"]["by_kind"] == {"loss_spike": 1,
                                           "step_time_regression": 1}
    assert out["anomalies"]["first"]["step"] == 2
    assert out["anomalies"]["last"]["anomaly"] == "step_time_regression"
    assert out["stragglers"]["events"] == 1
    assert out["stragglers"]["flagged_by_rank"] == {"1": 1}
    assert out["stragglers"]["top_ratio"] == 3.5
    assert out["stragglers"]["top_rank"] == "1"


def test_tools_cluster_dashboard_renders():
    from tools_cluster import render_dashboard
    snap = {"t": 123.0, "window_s": 60.0, "workers": {
        "0": {"steps_total": 20, "step_rate": 2.0, "step_time_p50": 0.05,
              "step_time_p95": 0.06, "loss": 2.1, "estimated_mfu": 0.4,
              "heartbeat_gap_s": 0.1, "last_push_age_s": 0.2,
              "anomalies": {}},
        "1": {"steps_total": 20, "step_rate": 0.5, "step_time_p50": 0.21,
              "step_time_p95": 0.30, "loss": 2.1,
              "heartbeat_gap_s": 0.1, "last_push_age_s": 0.2,
              "anomalies": {"step_time_regression": 1}},
    }}
    rep = straggler_report(_snap({0: 0.05, 1: 0.21}))
    text = render_dashboard(snap, rep)
    assert "stragglers flagged: [1]" in text
    assert "YES" in text
    assert "step_time_regression=1" in text


# ----------------------------------------------------- flags-unset identity
def test_flags_unset_no_push_no_health(monkeypatch, tmp_path):
    """With both new flags unset the hot paths are unchanged: no
    telemetry op on the wire, no health monitor, no per-slot runlogs."""
    monkeypatch.delenv("HETU_TPU_TELEMETRY_PUSH", raising=False)
    monkeypatch.delenv("HETU_TPU_HEALTH", raising=False)
    assert push_interval() == 0.0
    assert maybe_health_monitor() is None

    from hetu_tpu.chaos.harness import run_chaos_demo
    from hetu_tpu.obs.metrics import get_registry
    reg = get_registry()
    before = reg.counter_value("cluster.telemetry_pushes")
    rep = run_chaos_demo(str(tmp_path), FaultPlan([]), num_steps=6,
                         workers=2, pace=0.01)
    assert rep["completed"]
    # no push op ever hit the wire; the coordinator aggregated nothing
    assert reg.counter_value("cluster.telemetry_pushes") == before
    assert rep["cluster"]["workers"] == {}
    assert rep["straggler"]["stragglers"] == []
    # no per-slot observability files appeared
    assert not [f for f in os.listdir(tmp_path)
                if f.startswith("runlog_slot")]


# ------------------------------------------------------------- acceptance
@pytest.mark.parametrize("seed", [0])
def test_acceptance_slow_worker_cluster(monkeypatch, tmp_path, seed):
    """ISSUE 5 acceptance: an in-process 2-worker chaos-harness run with
    a seeded slow_worker fault — the coordinator's straggler report flags
    the slowed rank, the slowed worker's health monitor logs a
    step-time-regression anomaly, and the merged cluster trace carries
    both workers."""
    from hetu_tpu.chaos.harness import named_plan, run_chaos_demo
    monkeypatch.setenv("HETU_TPU_TELEMETRY_PUSH", "0.05")
    monkeypatch.setenv("HETU_TPU_HEALTH", "1")
    plan = named_plan("slow", rank=1, at_step=6, delay_s=0.12, seed=seed)
    rep = run_chaos_demo(str(tmp_path), plan, num_steps=28, workers=2,
                         pace=0.02)
    assert rep["completed"], rep
    assert rep["injected"]["slow_worker"] > 0

    # (1) the coordinator's straggler report flags the slowed rank
    assert rep["straggler"]["stragglers"] == [1], rep["straggler"]
    w1 = rep["straggler"]["workers"]["1"]
    assert w1["ratio"] > 2.0 and w1["straggler"]
    # both workers aggregated into the ClusterSnapshot
    assert set(rep["cluster"]["workers"]) >= {"0", "1"}
    assert rep["cluster"]["workers"]["1"]["steps_window"] >= 3

    # (2) the slowed worker's health monitor logged the regression
    slowed_slot = next(i for i, w in rep["workers"].items()
                       if w["rank"] == 1)
    log_path = str(tmp_path / f"runlog_slot{slowed_slot}.jsonl")
    recs = RunLog.read(log_path)
    anomalies = [r for r in recs if r["kind"] == "anomaly"]
    assert any(r["anomaly"] == "step_time_regression" for r in anomalies)

    # (3) telemetry actually flowed, exactly (pushes applied > 0, and the
    # aggregate saw every completed step of the slowed worker)
    assert rep["metrics"].get("cluster.telemetry_pushes", 0) > 0
    assert rep["cluster"]["workers"]["1"]["steps_total"] >= 28

    # (4) the merged cluster trace renders both workers + the anomaly
    from hetu_tpu.obs.trace import merge_runlogs
    logs = {i: RunLog.read(str(tmp_path / f"runlog_slot{i}.jsonl"))
            for i in (0, 1)}
    offsets = {rep["workers"][i]["rank"]: 0.0 for i in (0, 1)}
    tr = merge_runlogs(logs, offsets_s=offsets)
    pids = {e["pid"] for e in tr.events}
    assert pids == {"worker 0", "worker 1"}
    assert any(e.get("cat") == "anomaly" for e in tr.events)
