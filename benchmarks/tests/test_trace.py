"""The reduction from trace events to per-layer metrics: on hand-made
events (exact answers) and on a small trace recorded on a TPU v5e
(`tiny-chat.xplane.pb`: the tiny rehearsal configuration served for a
second with `--trace 1`, my chip run, PR 23)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import trace  # noqa: E402
from benchmarks.trace import Event, Trace  # noqa: E402

RECORDED = os.path.join(HERE, "tiny-chat.xplane.pb")
DEV = "/device:TPU:0"


def ev(name, start, dur):
    return Event(name, float(start), float(dur))


@pytest.fixture
def toy():
    """One device, window [0, 10]: two engine steps with a decode program
    each, a gap inside the first step, a gap between the steps."""
    ops = [ev("while.1", 0.0, 3.0),                # container of the next two
           ev("pallas_paged_attention.3", 0.0, 1.0),
           ev("fusion.7", 1.0, 2.0),
           ev("fusion.7", 4.0, 1.0),               # after a 1 s gap in step 1
           ev("pallas_paged_attention.3", 6.0, 1.0),
           ev("fusion.9", 7.0, 1.5)]
    modules = [ev("jit_decode_fn(1)", 0.0, 3.0), ev("jit_chunk_fn(2)", 4.0, 1.0),
               ev("jit_decode_fn(1)", 6.0, 2.5)]
    host = [ev(trace.WINDOW_START, 0.0, 0.0), ev("engine.step", 0.0, 5.5),
            ev("submit", 5.6, 0.2), ev("engine.step", 6.0, 3.0),
            ev(trace.WINDOW_END, 10.0, 0.0)]
    return Trace({DEV: trace.leaves(ops)}, {DEV: modules}, host)


def test_leaves_drop_containers():
    out = trace.leaves([ev("while", 0, 10), ev("a", 0, 4), ev("call", 4, 6),
                        ev("b", 4, 3), ev("c", 7, 3), ev("d", 11, 1)])
    assert [e.name for e in out] == ["a", "b", "c", "d"]


def test_union_clip_subtract():
    u = trace.union([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert u == [(0, 3), (5, 6)]
    assert trace.total(trace.clip(u, 2, 5.5)) == pytest.approx(1.5)
    assert trace.subtract([(0, 10)], [(1, 2), (4, 12)]) == [(0, 1), (2, 4)]
    assert trace.subtract([(0, 1)], [(0, 1)]) == []


def test_busy_idle_and_window(toy):
    w = trace.window_of(toy)
    assert w == (0.0, 10.0)
    assert trace.busy_seconds(toy, w) == pytest.approx(6.5)   # 3 + 1 + 2.5
    assert trace.rule_idle_pct({}, toy, w, {}) == pytest.approx(35.0)
    assert trace.rule_busy_ms_per_step({}, toy, w, {"steps": 2}) == \
        pytest.approx(3250.0)


def test_busy_is_averaged_over_device_planes(toy):
    two = Trace(dict(toy.ops, **{"/device:TPU:1": [ev("fusion.1", 0, 10)]}),
                toy.modules, toy.host)
    assert trace.busy_seconds(two, (0.0, 10.0)) == pytest.approx(8.25)


def test_gap_attribution(toy):
    gaps = dict(trace.attribute_gaps(toy, (0.0, 10.0)))
    # idle: [3,4] and [5,5.5] and [8.5,9] in engine.step; [5.6,5.8] in
    # submit; [5.5,5.6], [5.8,6] and [9,10] under no span
    assert gaps["engine.step"] == pytest.approx(2.0)
    assert gaps["submit"] == pytest.approx(0.2)
    assert gaps[trace.NO_SPAN] == pytest.approx(1.3)
    assert sum(gaps.values()) == pytest.approx(3.5)


def test_top_ops_sum_by_name_without_containers(toy):
    top = trace.top_device_ops(toy, (0.0, 10.0))
    assert top[0] == ("fusion.7", pytest.approx(3.0))
    assert dict(top)["pallas_paged_attention.3"] == pytest.approx(2.0)
    assert "while.1" not in dict(top)


def test_module_rules(toy):
    w = (0.0, 10.0)
    assert trace.rule_module_median_ms(
        {"match": ["decode_fn"]}, toy, w, {}) == pytest.approx(2750.0)
    assert trace.rule_module_median_ms(
        {"match": ["decode_fn", "chunk_fn"]}, toy, w, {}) == \
        pytest.approx(3750.0)
    assert trace.rule_module_median_ms(
        {"match": ["verify_fn"]}, toy, w, {}) is None
    assert trace.rule_module_gap_median_ms(
        {"match": "decode_fn"}, toy, w, {}) == pytest.approx(3000.0)


def test_snap_to_whole_module_executions():
    mods = [ev("jit__train_step(1)", t, 0.3) for t in (0.1, 0.42, 0.74, 1.06)]
    mods.insert(2, ev("jit__threefry_fold_in(2)", 0.73, 0.001))
    tr = Trace({DEV: []}, {DEV: mods}, [])
    w, steps = trace.snap_to_modules(tr, (0.2, 1.2), "train_step")
    assert w == (0.42, 1.06) and steps == 2
    assert trace.snap_to_modules(tr, (0.2, 0.5), "train_step") == (None, 0)


def test_host_self_time(toy):
    # step 1: 5.5 s with 4 s busy inside; step 2: 3 s with 2.5 s busy
    assert trace.rule_host_self_ms(
        {"span": "engine.step"}, toy, (0.0, 10.0), {}) == pytest.approx(1000.0)


def test_roofline_share_uses_the_familys_cost_function(toy):
    from benchmarks.families import llama
    cfg = {"hidden_size": 2048, "num_attention_heads": 16,
           "num_key_value_heads": 8, "num_hidden_layers": 24}
    ctx = {"config": cfg, "family": llama,
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "window_counts": {"steps": 2, "counters": {
               "serve.decode_context_tokens": 8_000_000,
               "serve.decode_slot_steps": 36}}}
    p = {"match": "pallas_paged_attention", "cost": "paged_attn_cost"}
    pct = trace.rule_roofline_pct(p, toy, (0.0, 10.0), ctx)
    kv_bytes = 24 * 2 * (2 * 8_000_000 * 8 * 128 + 2 * 36 * 16 * 128)
    assert pct == pytest.approx(100 * (kv_bytes / 819e9) / 2.0)
    assert ctx["notes"]["paged_attn_cost"]["bound"] == "memory"
    # a window in which the program counted no decode step: nothing to read
    assert trace.rule_roofline_pct(
        p, toy, (0.0, 10.0), dict(ctx, window_counts={"counters": {}})) is None
    with pytest.raises(KeyError):
        trace.rule_roofline_pct(dict(p, cost="no_such_cost"), toy,
                                (0.0, 10.0), ctx)


def test_flash_cost_scales_with_the_windows_steps_and_shards():
    from benchmarks.families import llama
    cfg = {"hidden_size": 4096, "num_attention_heads": 32,
           "num_key_value_heads": 8, "num_hidden_layers": 2}
    one = llama.flash_attn_cost(cfg, {"steps": 1, "batch": 2, "seq": 4096,
                                      "shards": 1})
    assert one["ops"] == 2 * 6.0 * (2.0 * 2 * 32 * 4096 * 4096 * 128 / 2.0)
    many = llama.flash_attn_cost(cfg, {"steps": 12, "batch": 2, "seq": 4096,
                                       "shards": 4})
    assert many["ops"] == pytest.approx(3 * one["ops"])
    assert many["bytes"] == pytest.approx(3 * one["bytes"])


def test_collective_time_and_its_exposed_part():
    ops = [ev("fusion.1", 0, 4), ev("all-reduce-start.2", 1, 0.1),
           ev("all-gather.5", 4, 2),            # nothing else runs: exposed
           ev("all-reduce-done.2", 6, 1), ev("fusion.3", 6.5, 2)]
    tr = Trace({DEV: ops}, {}, [])
    ctx = {"steps": 2}
    w = (0.0, 10.0)
    assert trace.rule_collective_ms_per_step({}, tr, w, ctx) == \
        pytest.approx(1e3 * 3.1 / 2)
    assert trace.rule_collective_ms_per_step({"exposed": True}, tr, w, ctx) \
        == pytest.approx(1e3 * 2.5 / 2)


def test_a_rule_that_finds_nothing_returns_nothing():
    empty = Trace({}, {}, [])
    spec = {"name": "x", "reduce": {"rule": "idle_pct"}}
    assert trace.reduce_metric(spec, empty, (0.0, 1.0), {}) is None
    assert trace.reduce_metric(spec, None, None, {}) is None
    spec = {"name": "y", "reduce": {"rule": "counter", "counter": "n"}}
    assert trace.reduce_metric(spec, None, None, {"counters": {}}) is None
    assert trace.reduce_metric(spec, None, None,
                               {"counters": {"n": 3}}) == 3.0
    with pytest.raises(KeyError):
        trace.reduce_metric({"reduce": {"rule": "nope"}}, None, None, {})


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_tpu_trace():
    tr = trace.read_xplane(RECORDED)
    assert list(tr.ops) == [DEV] and list(tr.modules) == [DEV]
    w = trace.window_of(tr)
    assert w is not None and 0.5 < w[1] - w[0] < 5.0
    busy = trace.busy_seconds(tr, w)
    assert 0.0 < busy < w[1] - w[0]
    names = {e.name for e in tr.host}
    assert {"engine.step", "submit"} <= names
    # op-name matching: the serving programs are found by their names
    assert trace.rule_module_median_ms({"match": ["decode_fn"]}, tr, w, {}) > 0
    assert trace.rule_module_median_ms(
        {"match": ["chunk_fn", "write_fn"]}, tr, w, {}) > 0
    # a tiny model leaves the chip idle most of the time, nearly all of it
    # inside engine.step or outside any span (the generator sleeping)
    gaps = dict(trace.attribute_gaps(tr, w))
    assert sum(gaps.values()) == pytest.approx(w[1] - w[0] - busy, rel=1e-6)
    assert trace.rule_idle_pct({}, tr, w, {}) > 50.0
    # no event of the ops line contains another after `leaves`
    evs = sorted(tr.ops[DEV], key=lambda e: e.start)
    assert all(a.end <= b.start + 1e-9 for a, b in zip(evs, evs[1:]))
    assert trace.rule_host_self_ms({"span": "engine.step"}, tr, w, {}) > 0
