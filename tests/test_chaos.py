"""Chaos-hardened control plane: deterministic fault injection drives the
recovery paths the paper's elasticity story promises (SURVEY §5.3) and the
metrics reconcile injections against recoveries.  All tier-1: CPU-only,
seeded, no model compile (the harness's StubTrainer checkpoints real bytes
through orbax but does no jax math)."""
import json
import time

import pytest

from hetu_tpu import chaos
from hetu_tpu.chaos import FaultPlan, FaultSpec
from hetu_tpu.obs.metrics import get_registry
from hetu_tpu.rpc import CoordinationClient, CoordinationServer

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


# ---------------------------------------------------------------- FaultPlan
def test_plan_json_roundtrip(tmp_path):
    plan = FaultPlan([FaultSpec(kind="rpc_drop", op="put", after_calls=2,
                                count=3),
                      FaultSpec(kind="ckpt_corrupt", at_step=5,
                                mode="truncate")], seed=7)
    p = tmp_path / "sched.json"
    p.write_text(json.dumps(plan.to_dict()))
    loaded = FaultPlan.load(str(p))
    assert loaded.seed == 7
    assert loaded.to_dict() == plan.to_dict()


def test_serving_fault_kinds_json_roundtrip(tmp_path):
    """The serving fault kinds (engine_kill / reshard_storm /
    decode_stall) survive the schedule JSON roundtrip with their
    step-clock fields, and the injection queries honor them: the kill
    latches one-shot, the down-window is a pure read over [at_step,
    at_step+count), and the decode stall rides the slow-path delay."""
    plan = FaultPlan([FaultSpec(kind="engine_kill", rank=0, at_step=4,
                                count=3),
                      FaultSpec(kind="reshard_storm", at_step=6,
                                count=2),
                      FaultSpec(kind="decode_stall", at_step=8, count=4,
                                delay_s=0.25)], seed=3)
    p = tmp_path / "serve_sched.json"
    p.write_text(json.dumps(plan.to_dict()))
    loaded = FaultPlan.load(str(p))
    assert loaded.to_dict() == plan.to_dict()
    # one-shot kill latch at the spec's rank...
    assert not loaded.should_kill_engine(3, rank=0)
    assert loaded.should_kill_engine(4, rank=0)
    assert not loaded.should_kill_engine(5, rank=0)
    # ...but the down-window stays a pure read over [at_step, +count)
    assert loaded.engine_down(4, rank=0)
    assert loaded.engine_down(6, rank=0)
    assert not loaded.engine_down(7, rank=0)
    # a rank-pinned spec does NOT match a rank-less query (the fleet
    # passes rank=None; the live harness must pass its rank)
    assert not loaded.engine_down(4)
    # decode_stall inflates the step like slow_worker, inside its window
    assert loaded.step_delay(None, 8) == 0.25
    assert loaded.step_delay(None, 11) == 0.25
    assert loaded.step_delay(None, 12) == 0.0


def test_plan_rejects_unknown_kind_and_fields(tmp_path):
    with pytest.raises(ValueError):
        FaultSpec(kind="rpc_explode")
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"faults": [{"kind": "rpc_drop",
                                         "bogus_field": 1}]}))
    with pytest.raises(ValueError):
        FaultPlan.load(str(p))


def test_wire_fault_window_is_call_counted():
    plan = FaultPlan([FaultSpec(kind="rpc_drop", op="put",
                                after_calls=2, count=2)])
    hits = [plan.wire_fault("put", 0) is not None for _ in range(6)]
    assert hits == [False, False, True, True, False, False]
    # non-matching ops never advance the window
    assert plan.wire_fault("get", 0) is None
    assert plan.summary() == {"rpc_drop": 2}


def test_probabilistic_faults_are_seed_deterministic():
    def pattern(seed):
        plan = FaultPlan([FaultSpec(kind="rpc_drop", op="*", count=64,
                                    prob=0.5)], seed=seed)
        return [plan.wire_fault("put", 0) is not None for _ in range(64)]

    assert pattern(3) == pattern(3)         # replayable
    assert any(pattern(3)) and not all(pattern(3))
    assert pattern(3) != pattern(4)         # seed actually steers


def test_get_plan_identity_by_default(monkeypatch):
    monkeypatch.delenv("HETU_TPU_CHAOS", raising=False)
    chaos.reset()
    assert chaos.get_plan() is None


def test_get_plan_resolves_flag(tmp_path, monkeypatch):
    p = tmp_path / "sched.json"
    p.write_text(json.dumps({"seed": 1, "faults": [
        {"kind": "rpc_delay", "op": "put", "delay_s": 0.01}]}))
    monkeypatch.setenv("HETU_TPU_CHAOS", str(p))
    chaos.reset()
    plan = chaos.get_plan()
    assert plan is not None and plan.seed == 1
    chaos.reset()


# ------------------------------------------------------------- wire faults
def test_rpc_drop_reconnects_and_retries_idempotent(server):
    reg = get_registry()
    before = reg.counter_value("rpc.reconnects")
    c = _client(server)
    chaos.install(FaultPlan([FaultSpec(kind="rpc_drop", op="put",
                                       count=1)]))
    c.put("a", 1)          # first put dropped -> reconnect -> retried
    assert c.get("a") == 1
    assert c.reconnects == 1
    assert reg.counter_value("rpc.reconnects") - before == 1
    # the rank survived the reconnect: no worker-loss event
    assert c.rank in c.membership()
    c.exit()


def test_rpc_drop_does_not_retry_nonidempotent(server):
    c = _client(server)
    chaos.install(FaultPlan([FaultSpec(kind="rpc_dup", op="ps_push")]))
    # a DUPLICATED add-mode push would double-apply if blindly retried;
    # here the chaos dup exercises server-side behavior instead: assert
    # the client refuses transport-level retry for a dropped ps_push
    chaos.install(FaultPlan([FaultSpec(kind="rpc_drop", op="ps_push")]))
    c.ps_init("t", rows=4, dim=2)
    with pytest.raises(ConnectionError):
        c.ps_push("t", [0], [[1.0, 1.0]], mode="add")
    # transport was still re-established for later ops
    assert c.membership() == [c.rank]
    c.exit()


def test_rpc_delay_adds_latency(server):
    c = _client(server)
    chaos.install(FaultPlan([FaultSpec(kind="rpc_delay", op="put",
                                       delay_s=0.25)]))
    t0 = time.perf_counter()
    c.put("slow", 1)
    assert time.perf_counter() - t0 >= 0.25
    c.exit()


def test_rpc_dup_is_idempotent_for_kv_reads_and_writes(server):
    """Duplicate delivery of kv ops is harmless: put is last-write-wins,
    get/membership are reads."""
    c0, c1 = _client(server), _client(server)
    chaos.install(FaultPlan([FaultSpec(kind="rpc_dup", op="put"),
                             FaultSpec(kind="rpc_dup", op="get", count=2),
                             FaultSpec(kind="rpc_dup", op="membership")]))
    c0.put("k", {"v": 1})          # delivered twice; last write wins
    assert c1.get("k") == {"v": 1}
    assert c0.get("k") == {"v": 1}
    assert sorted(c0.membership()) == [c0.rank, c1.rank]
    assert chaos.get_plan().summary() == {"rpc_dup": 4}
    c0.exit(); c1.exit()


def test_rpc_dup_barrier_enter_is_round_pinned(server):
    """Review regression: a duplicated barrier ENTER spanning the release
    boundary must not leak into the next round (gen_expect pins it) —
    both this round and the NEXT complete cleanly."""
    import threading
    c0, c1 = _client(server), _client(server)
    chaos.install(FaultPlan([FaultSpec(kind="rpc_dup", op="barrier",
                                       rank=c0.rank, count=4)]))
    for _rnd in range(2):          # the second round detects poisoning
        done = []
        t = threading.Thread(target=lambda: (c0.barrier("b", count=2),
                                             done.append(0)))
        t.start()
        c1.barrier("b", count=2)
        t.join(10)
        assert done == [0]
    c0.exit(); c1.exit()


def test_vote_survives_dropped_submission(server):
    """A partition eating a vote submission must not wedge the round:
    consistent() re-submits the SAME round (idempotent server-side)."""
    import threading
    c0, c1 = _client(server), _client(server)
    chaos.install(FaultPlan([FaultSpec(kind="rpc_drop", op="consistent",
                                       rank=c0.rank, count=1)]))
    res = {}
    t = threading.Thread(target=lambda: res.update(
        a=c0.consistent("plan", "tp4", count=2, timeout=20)))
    t.start()
    res["b"] = c1.consistent("plan", "tp4", count=2, timeout=20)
    t.join(20)
    assert res == {"a": "tp4", "b": "tp4"}
    c0.exit(); c1.exit()


# ------------------------------------------------------- heartbeat faults
def test_heartbeat_stall_declares_worker_dead(server):
    """A stall longer than the server timeout (the long-XLA-compile false
    positive) kills the rank; the stalled client may NOT resurrect into
    the old mesh and its flags say so."""
    stalled = CoordinationClient("127.0.0.1", server.port,
                                 heartbeat_interval=0.1)
    watcher = CoordinationClient("127.0.0.1", server.port,
                                 heartbeat_interval=0.1)
    chaos.install(FaultPlan([FaultSpec(kind="heartbeat_stall",
                                       rank=stalled.rank, at_beat=3,
                                       stall_s=2.0)]))
    deadline = time.time() + 15.0
    while stalled.rank in watcher.membership():
        assert time.time() < deadline, "stalled worker never declared dead"
        time.sleep(0.1)
    assert watcher.should_stop or watcher.check_stop()  # survivors re-mesh
    with pytest.raises(RuntimeError):
        stalled.resume()
    watcher.exit(); stalled.exit()


# ------------------------------------------------ step-failure recovery
def _controller(tmp_path, server, fail_at, recovery_budget):
    """An elastic controller over a StubTrainer whose train_step raises
    once at `fail_at` (the chaos-free step-exception path)."""
    from hetu_tpu.chaos.harness import StubTrainer
    from hetu_tpu.engine.elastic import ElasticController

    client = CoordinationClient("127.0.0.1", server.port,
                                heartbeat_interval=0.1)

    class FailingTrainer(StubTrainer):
        fired = {"n": 0}

        def train_step(self, batch):
            if self.global_step + 1 == fail_at and not self.fired["n"]:
                self.fired["n"] += 1
                raise RuntimeError("injected step failure")
            return super().train_step(batch)

    ctl = ElasticController(
        client, lambda plan: FailingTrainer(str(tmp_path / "ck"), plan),
        lambda alive: {"strategy": {"dp": len(alive)}},
        recovery_budget=recovery_budget)
    return client, ctl


def test_step_exception_emergency_checkpoints_then_raises(tmp_path, server):
    """Satellite: with no recovery budget, a train_step exception still
    writes an emergency checkpoint before surfacing — a crash loses at
    most one step, not one checkpoint interval."""
    reg = get_registry()
    before = reg.counter_value("elastic.emergency_saves")
    client, ctl = _controller(tmp_path, server, fail_at=5,
                              recovery_budget=0)
    batches = iter([{"x": 0}] * 100)
    with pytest.raises(RuntimeError, match="injected step failure"):
        ctl.run(batches, num_steps=10)
    assert reg.counter_value("elastic.emergency_saves") - before == 1
    # the emergency checkpoint holds every completed step
    from hetu_tpu.chaos.harness import StubTrainer
    t = StubTrainer(str(tmp_path / "ck"), {})
    t.restore_latest_valid()
    assert t.global_step == 4
    client.exit()


def test_step_exception_recovers_within_budget(tmp_path, server):
    """With a recovery budget, a step exception triggers emergency save +
    re-mesh + resume from the checkpoint, and the run completes."""
    reg = get_registry()
    before = {k: reg.counter_value(k)
              for k in ("elastic.recovery_attempts",
                        "elastic.recovery_success")}
    client, ctl = _controller(tmp_path, server, fail_at=5,
                              recovery_budget=2)
    batches = iter([{"x": 0}] * 100)
    trainer = ctl.run(batches, num_steps=10)
    assert trainer.global_step >= 10
    assert ctl.generation >= 2   # the recovery re-mesh happened
    for k in before:
        assert reg.counter_value(k) - before[k] == 1, k
    client.exit()


# -------------------------------------------------- acceptance (tentpole)
def test_chaos_acceptance_kill_partition_corrupt(tmp_path):
    """The ISSUE acceptance scenario: a 2-worker elastic run under one
    seeded schedule — 1 worker kill + 1 rpc partition window + 1 corrupted
    newest checkpoint — completes all steps, resumes from the newest VALID
    checkpoint, and the registry's chaos.injected_* counts reconcile with
    the recovery accounting."""
    from hetu_tpu.chaos.harness import named_plan, run_chaos_demo
    plan = named_plan("kill-partition-corrupt")
    report = run_chaos_demo(str(tmp_path), plan, num_steps=48)

    workers = report["workers"]
    ranks = {w["rank"]: w for w in workers.values() if w}
    assert set(ranks) == {0, 1}, report
    # the scheduled victim died; the survivor finished every step
    assert ranks[1]["killed"], report
    assert ranks[0]["error"] is None, report
    assert ranks[0]["final_step"] >= report["num_steps"], report

    inj = report["injected"]
    m = report["metrics"]
    assert inj["worker_kill"] == 1
    assert inj["rpc_drop"] == 2
    assert inj["ckpt_corrupt"] == 1
    # partition accounting: the drops forced reconnects and the rank
    # survived them (no extra worker loss).  reconnects may be FEWER than
    # drops: when a drop tears the socket under both the heartbeat thread
    # and the controller thread at once, the conn_gen guard deliberately
    # coalesces their recoveries into one reconnect
    assert 1 <= m.get("rpc.reconnects", 0) <= inj["rpc_drop"]
    assert m.get("rpc.workers_lost", 0) == 1          # only the kill
    # corruption accounting: the newest checkpoint fell back exactly once
    # and the corrupt step was quarantined
    assert m.get("ckpt.fallbacks", 0) == 1
    assert m.get("ckpt.quarantined", 0) == 1
    # the survivor re-meshed: initial plan + post-kill re-plan, and the
    # post-kill generation resumed from a checkpoint written BEFORE the
    # corrupted one (newest valid)
    assert m.get("elastic.replans", 0) >= 2
    resumed = ranks[0]["resumed_steps"]
    assert len(resumed) >= 2 and resumed[-1] > 0, report
    assert report["replan_s"] is not None and \
        report["replan_s"]["count"] >= 2
    # recovery latency is measured, so regressions are visible in BENCH
    assert report["replan_s"]["p95_s"] > 0


def test_chaos_demo_corrupt_truncate(tmp_path):
    """Truncation (torn write) variant: same fallback guarantee."""
    from hetu_tpu.chaos.harness import named_plan, run_chaos_demo
    report = run_chaos_demo(str(tmp_path), named_plan("corrupt"))
    ranks = {w["rank"]: w for w in report["workers"].values() if w}
    assert ranks[0]["error"] is None, report
    assert ranks[0]["final_step"] >= report["num_steps"], report
    assert report["injected"]["ckpt_corrupt"] == 1
    assert report["metrics"].get("ckpt.fallbacks", 0) == 1
