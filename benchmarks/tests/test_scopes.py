"""The readings taken from inside the program (`benchmarks/trace.py`'s span,
scope and counter rules): each rule on hand-made events with exact
answers, the scope join on a hand-made trace of two programs that share
an instruction name, the counter rule on hand-made registry snapshots
through the metric files that use it, and `run.py` end to end at the
rehearsal size."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks import trace, traffic  # noqa: E402
from benchmarks.trace import Event, Trace  # noqa: E402

DEV = "/device:TPU:0"
RECORDED = os.path.join(HERE, "tiny-chat.xplane.pb")
SYNC = list(trace.SYNC_SPANS)


def ev(name, start, dur):
    return Event(name, float(start), float(dur))


@pytest.fixture
def stepped():
    """Window [0, 20], two engine steps.  Step one [0, 10]: the host
    builds for 2 s while the device is idle (starved), dispatches, and
    waits in `serve.token_fetch` [3, 9], the device busy [3, 8] of it
    (1 s of sync idle).  Step two [10, 20]: a first-token wait [11, 13]
    with the device busy [10.5, 12], then decode as before."""
    ops = [ev("fusion.1", 3.0, 5.0), ev("fusion.2", 10.5, 1.5),
           ev("fusion.1", 14.0, 4.0)]
    host = [ev(trace.WINDOW_START, 0.0, 0.0),
            ev("engine.step", 0.0, 10.0), ev("serve.step", 0.0, 10.0),
            ev("serve.admit", 0.0, 0.5), ev("serve.decode_build", 0.5, 2.0),
            ev("serve.decode_dispatch", 2.5, 0.5),
            ev("serve.token_fetch", 3.0, 6.0), ev("serve.emit", 9.0, 1.0),
            ev("engine.step", 10.0, 10.0), ev("serve.step", 10.0, 10.0),
            ev("serve.prefill_chunk", 10.0, 1.0),
            ev("serve.first_token", 11.0, 2.0),
            ev("serve.decode_build", 13.0, 1.0),
            ev("serve.token_fetch", 14.0, 5.0), ev("serve.emit", 19.0, 1.0),
            ev(trace.WINDOW_END, 20.0, 0.0)]
    return Trace({DEV: ops}, {DEV: []}, sorted(host, key=lambda e: e.start))


def test_span_self_is_the_step_minus_its_sync_phases(stepped):
    w = trace.window_of(stepped)
    p = {"span": "serve.step", "minus": SYNC}
    # step one 10 - 6, step two 10 - (2 + 5)
    assert trace.rule_span_self_ms(p, stepped, w, {}) == \
        pytest.approx(1e3 * (4.0 + 3.0) / 2)
    assert trace.rule_span_self_ms({"span": "serve.step"}, stepped, w,
                                    {}) == pytest.approx(1e4)


def test_starved_and_sync_idle_split_the_idle_in_the_step(stepped):
    w = trace.window_of(stepped)
    starved = trace.rule_span_idle_ms(
        {"span": "serve.step", "exclude": SYNC}, stepped, w, {})
    sync = trace.rule_span_idle_ms(
        {"span": "serve.step", "only": SYNC}, stepped, w, {})
    # starved: [0,3] + [9,10] in step one; [10,10.5] + [13,14] + [19,20]
    assert starved == pytest.approx(1e3 * (4.0 + 2.5) / 2)
    # sync idle: [8,9] in step one; [12,13] + [18,19] in step two
    assert sync == pytest.approx(1e3 * (1.0 + 2.0) / 2)
    # together: the same idle `host_self_ms` reads from outside
    outside = trace.rule_host_self_ms({"span": "engine.step"}, stepped, w,
                                      {})
    assert starved + sync == pytest.approx(outside)


def test_idle_position_in_a_sync_span(stepped):
    w = trace.window_of(stepped)
    # token_fetch [3, 9] busy [3, 8]; [14, 19] busy [14, 18]
    assert trace.idle_position_ms(stepped, w, "serve.token_fetch") == \
        pytest.approx({"head": 0.0, "middle": 0.0, "tail": 1000.0})
    # first_token [11, 13] busy until 12
    assert trace.idle_position_ms(stepped, w, "serve.first_token") == \
        pytest.approx({"head": 0.0, "middle": 0.0, "tail": 1000.0})
    # decode_build [0.5, 2.5] and [13, 14]: idle throughout
    assert trace.idle_position_ms(stepped, w, "serve.decode_build")[
        "head"] == pytest.approx(1500.0)
    assert trace.idle_position_ms(stepped, w, "serve.nothing") is None


def test_idle_gaps_name_the_phase(stepped):
    w = trace.window_of(stepped)
    gaps = dict(trace.attribute_gaps(stepped, w))
    assert gaps == pytest.approx({
        "serve.token_fetch": 2.0, "serve.first_token": 1.0,
        "serve.decode_build": 3.0, "serve.decode_dispatch": 0.5,
        "serve.admit": 0.5, "serve.prefill_chunk": 0.5, "serve.emit": 2.0})
    assert "engine.step" not in gaps and trace.NO_SPAN not in gaps


def test_rules_find_nothing_where_the_program_has_no_spans():
    """The parent of PR 24 (and the trace recorded from it): every rule
    gives None, none raises."""
    tr = trace.read_xplane(RECORDED)
    w = trace.window_of(tr)
    for rule, p in [
            ("span_self_ms", {"span": "serve.step", "minus": SYNC}),
            ("span_idle_ms", {"span": "serve.step", "only": SYNC}),
            ("scope_ms", {"program": "decode_fn", "phase": ["attn"]}),
            ("scope_pct", {"program": "decode_fn", "group": "unscoped"})]:
        assert trace.RULES[rule](p, tr, w, {"hlo_texts": []}) is None
    assert trace.rule_span_self_ms({"span": "serve.step"}, None, None,
                                    {}) is None


# ---------------------------------------------------------------------------
# the scope join
# ---------------------------------------------------------------------------

def hlo(module, lines):
    return (f"HloModule {module}, is_scheduled=true\n\nENTRY %main {{\n"
            + "\n".join(lines) + "\n}\n")


def instr(name, shape, op_name, root=False):
    meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
    return (f"  {'ROOT ' if root else ''}%{name} = {shape}{{0}} "
            f"fusion(%p){meta}")


DECODE = hlo("jit_decode_fn", [
    instr("fusion.1", "bf16[8,128]",
          "jit(decode_fn)/layer/while/body/closed_call/attn/dot_general"),
    instr("fusion.2", "bf16[8,128]",
          "jit(decode_fn)/layer/while/body/closed_call/attn/kv_write/scatter"),
    instr("pallas_paged_attention.3", "bf16[8,16,128]",
          "jit(decode_fn)/layer/while/body/closed_call/attn/"
          "pallas_paged_attention/pallas_call"),
    instr("fusion.4", "bf16[8,512]",
          "jit(decode_fn)/layer/while/body/closed_call/mlp/dot_general"),
    # the partitioner's reduction of the MLP's partial sums: it carries
    # the scope of what it reduces
    instr("all-reduce.6", "bf16[8,128]",
          "jit(decode_fn)/layer/while/body/closed_call/mlp/dot_general"),
    instr("copy.5", "bf16[24,2049,16,8,128]", "", root=True)])
# the SAME instruction name and shape as decode's attention matmul, in
# another program and another scope
CHUNK = hlo("jit_chunk_fn", [
    instr("fusion.1", "bf16[8,128]",
          "jit(chunk_fn)/layer/while/body/closed_call/mlp/dot_general")])


@pytest.fixture
def two_programs():
    """Window [0, 30]: decode, chunk, an eager one-operation program,
    decode again, and a decode the window's end cuts."""
    def short(text, name):
        line = next(ln for ln in text.splitlines() if f"%{name} = " in ln)
        return trace.short_name(line.strip().replace("ROOT ", ""))
    d = {n: short(DECODE, n) for n in (
        "fusion.1", "fusion.2", "pallas_paged_attention.3", "fusion.4",
        "all-reduce.6", "copy.5")}
    ops, modules = [], []
    for t0 in (0.0, 15.0, 26.0):
        modules.append(ev("jit_decode_fn(7)", t0, 6.0))
        ops += [ev(d["fusion.1"], t0, 1.0), ev(d["fusion.2"], t0 + 1, 0.5),
                ev(d["pallas_paged_attention.3"], t0 + 1.5, 2.0),
                ev(d["fusion.4"], t0 + 3.5, 1.0),
                ev(d["all-reduce.6"], t0 + 4.5, 0.5),
                ev(d["copy.5"], t0 + 5, 1.0)]
    modules.append(ev("jit_chunk_fn(9)", 7.0, 3.0))
    ops.append(ev(short(CHUNK, "fusion.1"), 7.0, 3.0))
    modules.append(ev("jit_dynamic_slice(11)", 11.0, 0.25))
    ops.append(ev("dynamic-slice.1_bf16_8_", 11.0, 0.25))
    host = [ev(trace.WINDOW_START, 0.0, 0.0), ev(trace.WINDOW_END, 30.0, 0.0)]
    return Trace({DEV: sorted(ops, key=lambda e: e.start)},
                 {DEV: sorted(modules, key=lambda e: e.start)}, host)


def test_scope_index_keys_are_the_trace_names():
    name, index = trace.scope_index(DECODE)
    assert name == "jit_decode_fn"
    assert index["fusion.1_bf16_8_128_"] == ("layer/attn", "fwd")
    assert index["fusion.2_bf16_8_128_"] == ("layer/kv_write", "fwd")
    assert index["copy.5_bf16_24_2049_16_8_128_"] == ("unscoped", "fwd")
    assert index["pallas_paged_attention.3_bf16_8_16_128_"] == \
        ("layer/attn/pallas_paged_attention", "fwd")


def test_join_gives_an_operation_to_the_program_that_ran_it(two_programs):
    emitted = []
    ctx = {"hlo_texts": [DECODE, CHUNK],
           "emit": lambda **rec: emitted.append(rec)}
    table = trace.scope_table(two_programs, (0.0, 30.0), ctx)
    dec, chunk = table["jit_decode_fn"], table["jit_chunk_fn"]
    assert dec["executions"] == 2               # the third is cut
    assert dec["device_s"] == pytest.approx(12.0)
    assert dec["rows"] == pytest.approx({
        ("layer/attn", "fwd", "compute"): 2.0,
        ("layer/kv_write", "fwd", "compute"): 1.0,
        ("layer/attn/pallas_paged_attention", "fwd", "compute"): 4.0,
        ("layer/mlp", "fwd", "compute"): 2.0,
        ("layer/mlp", "fwd", "collective"): 1.0,
        ("unscoped", "fwd", "compute"): 2.0})
    # `fusion.1_bf16_8_128_` is attention in decode and MLP in the chunk
    assert chunk["rows"] == pytest.approx(
        {("layer/mlp", "fwd", "compute"): 3.0})
    assert table["jit_dynamic_slice"]["rows"] == \
        pytest.approx({("unscoped", "fwd", "compute"): 0.25})
    assert list(dec["ops"]["unscoped"]) == ["copy.5_bf16_24_2049_16_8_128_"]
    # built once, printed once
    assert trace.scope_table(two_programs, (0.0, 30.0), ctx) is table
    assert [r["phase"] for r in emitted] == ["scopes"]
    printed = emitted[0]["programs"]["jit_decode_fn"]
    assert printed["top_ops"] == {
        "unscoped": [["copy.5_bf16_24_2049_16_8_128_", 1000.0]]}
    assert printed["unscoped_by_opcode"] == [["copy", 1000.0]]
    rows = printed["rows"]
    assert sum(r["ms"] for r in rows) == pytest.approx(6000.0)
    assert sum(r["pct"] for r in rows) == pytest.approx(100.0)


@pytest.mark.parametrize("p,expect", [
    ({"program": "decode_fn", "phase": ["attn"]}, 3000.0),
    ({"program": "decode_fn", "phase": ["kv_write"]}, 500.0),
    ({"program": "decode_fn", "phase": ["mlp"]}, 1500.0),
    # the same layer without its communication, and the communication
    ({"program": "decode_fn", "phase": ["mlp"], "kind": "compute"}, 1000.0),
    ({"program": "decode_fn", "phase": ["mlp"], "kind": "collective"}, 500.0),
    ({"program": "decode_fn", "kind": "collective"}, 500.0),
    ({"program": "decode_fn", "phase": ["attn"], "kind": "collective"}, None),
    ({"program": "decode_fn", "group": "unscoped"}, 1000.0),
    ({"program": "decode_fn", "kernel": "pallas_paged_attention"}, 2000.0),
    ({"program": "decode_fn", "pass": "bwd"}, None),
    ({"program": "chunk_fn", "phase": ["mlp"]}, 3000.0),
    ({"program": "train_step", "phase": ["attn"]}, None),
])
def test_scope_ms_per_execution(two_programs, p, expect):
    got = trace.rule_scope_ms(p, two_programs, (0.0, 30.0),
                               {"hlo_texts": [DECODE, CHUNK]})
    assert got == (None if expect is None else pytest.approx(expect))


def test_scope_pct_and_the_groups_sum_to_the_program(two_programs):
    ctx = {"hlo_texts": [DECODE, CHUNK]}
    w = (0.0, 30.0)
    assert trace.rule_scope_pct(
        {"program": "decode_fn", "group": "unscoped"}, two_programs, w,
        ctx) == pytest.approx(100.0 / 6)
    parts = [trace.rule_scope_ms({"program": "decode_fn", **sel},
                                  two_programs, w, ctx)
             for sel in ({"phase": ["attn"]}, {"phase": ["kv_write"]},
                         {"phase": ["mlp"]}, {"group": "unscoped"})]
    assert sum(parts) == pytest.approx(6000.0)


@pytest.mark.parametrize("group,phase,kernel", [
    ("layer/attn/pallas_flash_attention", "attn", "pallas_flash_attention"),
    ("layer_3/mlp", "mlp", None), ("optimizer/pallas_adam", "optimizer",
                                   "pallas_adam"),
    ("layer", "layer", None), ("lm_head", "lm_head", None),
    ("pallas_quantize", "pallas_quantize", "pallas_quantize"),
    ("unscoped", "unscoped", None)])
def test_phase_and_kernel_of_a_group(group, phase, kernel):
    assert trace.phase_of(group) == phase
    assert trace.kernel_of(group) == kernel


def test_by_opcode_sums_instances_of_one_kind():
    assert trace.by_opcode({
        "all-gather.172_bf16_16_2048_2_4096_": 2.0,
        "all-gather.169_bf16_46272_2048_": 1.0,
        "copy_bitcast_fusion.13_bf16_2_4096_2_4096_": 2.5,
        "copy-done_bf16_2049_16_8_128_": 0.5, "slice-start.51_": 0.25,
        "slice-start_": 0.25}, 2) == [
        ["all-gather", 1500.0], ["copy_bitcast_fusion", 1250.0],
        ["copy-done", 250.0], ["slice-start", 250.0]]


def test_eager_dispatches_are_counted_once_under_their_phase():
    host = [("serve.step", 0.0, 10.0), ("serve.prefill_chunk", 0.0, 2.0),
            ("PjitFunction(convert_element_type)", 0.5, 0.7),
            ("PjitFunction(convert_element_type)", 0.55, 0.65),  # inner
            ("PjitFunction(chunk_fn)", 1.0, 1.5),
            ("serve.first_token", 2.0, 4.0),
            ("PjitFunction(dynamic_slice)", 2.1, 2.2),
            ("PjitFunction(squeeze)", 2.3, 2.4),
            ("PjitFunction(dynamic_slice)", 11.0, 11.1)]          # outside
    assert trace.eager_dispatches(host, (0.0, 10.0)) == {
        "serve.prefill_chunk": {"PjitFunction(convert_element_type)": 1,
                                "PjitFunction(chunk_fn)": 1},
        "serve.first_token": {"PjitFunction(dynamic_slice)": 1,
                              "PjitFunction(squeeze)": 1}}


# ---------------------------------------------------------------------------
# the program's counters, the entries and the entry point
# ---------------------------------------------------------------------------

def snapshot(**counters):
    """A `MetricsRegistry.snapshot()` with these counters; a dict value is
    one series per label value of `reason`."""
    out = []
    for name, v in counters.items():
        name = name.replace("__", ".")
        series = v if isinstance(v, dict) else {None: v}
        out += [{"name": name, "labels": {"reason": r} if r else {},
                 "value": float(x)} for r, x in series.items()]
    return {"counters": out, "gauges": [], "histograms": []}


START = snapshot(serve__decode_steps=10, serve__decode_slot_steps=30,
                 serve__decode_context_tokens=3000, serve__prefill_tokens=500,
                 serve__tokens_out=30,
                 serve__admission_stalls={"no_pages": 1},
                 serve__steps=20, serve__step_wall_s=1.0,
                 serve__caller_s=0.5, serve__empty_s=0.25,
                 serve__fetch_wait_total_s=0.1,
                 serve__decode_steps_overlapped=9)
# `serve.stalled_steps` is labelled by phase in the program; any label
# does here: the bare name is the sum over the series
END = snapshot(serve__decode_steps=12, serve__decode_slot_steps=40,
               serve__decode_context_tokens=6000, serve__prefill_tokens=628,
               serve__tokens_out=40,
               serve__admission_stalls={"no_pages": 1, "no_slot": 1},
               serve__steps=24, serve__step_wall_s=1.6,
               serve__caller_s=0.6, serve__empty_s=0.55,
               serve__fetch_wait_total_s=0.112,
               serve__decode_steps_overlapped=10,
               serve__stalled_steps={"token_fetch": 1})


def window_ctx():
    return {"counters": {"engine_steps": 2},
            "registry": trace.counter_diff(trace.counter_values(START),
                                           trace.counter_values(END))}


def test_counter_values_are_flat_by_name_and_by_series():
    flat = trace.counter_values(END)
    assert flat["serve.admission_stalls"] == 2.0          # every reason
    assert flat["serve.admission_stalls{reason=no_slot}"] == 1.0
    assert flat["serve.tokens_out"] == 40.0
    diff = window_ctx()["registry"]
    # a series that did not exist at the start counted from 0
    assert diff["serve.admission_stalls{reason=no_slot}"] == 1.0
    assert diff["serve.admission_stalls{reason=no_pages}"] == 0.0
    assert trace.counter_key("a.b", {"z": 1, "k": "v"}) == "a.b{k=v,z=1}"


@pytest.mark.parametrize("metric,expect", [
    # the readings `run_inside.window_counters` made by hand (PR 24),
    # now one rule and a data file each
    ("chat.decode_ctx_ktokens_step", 1.5),
    ("chat.decode_batch_inside", 5.0),
    ("prefill_token_share_inside", 100.0 * 128 / (128 + 10)),
    # what the step record's counters (PRs 37, 38) were built for: each a
    # ratio, so that a counter the window never touched counts 0
    ("engine_empty_pct", 100.0 * 0.3 / (0.6 + 0.1 + 0.3)),
    ("chat.engine_empty_pct", 30.0),
    ("stalled_steps_pct", 25.0),
    ("chat.stalled_steps_pct", 25.0),
    ("fetch_wait_ms_step", 3.0),
    ("chat.fetch_wait_ms_step", 3.0),
    ("decode_overlap_pct", 50.0),
    ("chat.decode_overlap_pct", 50.0),
])
def test_counter_rule_files_read_the_registrys_difference(metric, expect):
    spec = traffic.load_json("metrics", metric)
    assert spec["reduce"]["rule"] == "counter"
    assert trace.reduce_metric(spec, None, None, window_ctx()) == \
        pytest.approx(expect)
    # a program that has no such counter: nothing to read, no error
    assert trace.reduce_metric(spec, None, None,
                               {"counters": {}, "registry": {}}) is None


def test_a_ratio_counts_an_untouched_counter_as_nought():
    """A window in which the engine was never empty and no step stalled
    has no `serve.empty_s` and no `serve.stalled_steps` in the registry's
    difference: the two metrics read 0, they are not missing from the
    run's line (a metric missing where its list names the cell is a
    fault of the run)."""
    ctx = {"counters": {}, "registry": {
        "serve.steps": 8.0, "serve.step_wall_s": 0.9, "serve.caller_s": 0.1}}
    for name in ("engine_empty_pct", "stalled_steps_pct"):
        spec = traffic.load_json("metrics", name)
        assert trace.reduce_metric(spec, None, None, ctx) == 0.0


def test_counter_rule_by_labels_and_with_an_untouched_numerator():
    ctx = window_ctx()
    by_label = {"rule": "counter", "counter": "serve.admission_stalls",
                "labels": {"reason": "no_slot"}}
    assert trace.rule_counter(by_label, None, None, ctx) == 1.0
    # a counter the program never touched counts 0 over steps that ran,
    # and alone it is nothing to read
    never = {"rule": "counter", "counter": "serve.preemptions"}
    assert trace.rule_counter(never, None, None, ctx) is None
    assert trace.rule_counter(dict(never, over="engine_steps", scale=100.0),
                              None, None, ctx) == 0.0
    # the loop's own values are found by the same rule
    assert trace.rule_counter({"counter": "engine_steps"}, None, None,
                              ctx) == 2


def test_every_metric_of_the_benchmark_is_rehearsed():
    """The rehearsal files (`rehearsal.json`, and one more a family that
    needs a configuration of its own: `rehearsal-kimi.json`) list between
    them exactly the per-layer metrics of `BENCHMARK.json` (84 of the 128
    it may hold since PR 40 folded the entries that repeated a rule once
    a cell), each as the benchmark has it, on cells that report the
    end-to-end metric it moves, and each has its file.  A later PR's
    metrics come with a rehearsal file of their own."""
    import glob
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    rehearsals = []
    for path in sorted(glob.glob(os.path.join(HERE, "rehearsal*.json"))):
        with open(path) as f:
            rehearsals.append(json.load(f))
    assert len(rehearsals) >= 2 and len(bench["per_layer"]) <= 128
    rehearsed = [m for r in rehearsals for m in r["per_layer"]]
    # an entry whose list names several cells is rehearsed once in the
    # file of each family it reads (PR 40): sets, not counts
    assert {m["name"] for m in rehearsed} == \
        {m["name"] for m in bench["per_layer"]}
    for r in rehearsals:
        names = [m["name"] for m in r["per_layer"]]
        assert len(names) == len(set(names))
    for f in [bench] + rehearsals:
        cells = {w["name"] for w in f["workloads"]}
        for m in f["per_layer"]:
            assert traffic.load_json("metrics", m["name"])["reduce"][
                "rule"] in trace.RULES, m["name"]
            moved = next(e for e in f["end_to_end"]
                         if e["name"] == m["moves"])
            assert m["workloads"] and set(m["workloads"]) <= cells \
                & set(moved.get("workloads", cells)), m["name"]
    same = ("unit", "better", "source", "layer", "moves")
    as_benched = {m["name"]: m for m in bench["per_layer"]}
    for b in rehearsed:
        a = as_benched[b["name"]]
        assert [a[k] for k in same] == [b[k] for k in same], b["name"]


@pytest.mark.parametrize("cell,present,ratio", [
    ("tiny-chat", ("chat.host_work_ms_step", "chat.token_gap_p99_ms",
                   "chat.engine_empty_pct", "chat.decode_ctx_ktokens_step"),
     ("chat.decode_batch_inside", "serve.decode_slot_steps",
      ("serve.decode_steps",), 1.0)),
    ("tiny-batch", ("host_work_ms_step", "engine_empty_pct",
                    "stalled_steps_pct", "decode_overlap_pct"),
     ("prefill_token_share_inside", "serve.prefill_tokens",
      ("serve.prefill_tokens", "serve.tokens_out"), 100.0)),
    ("tiny-gpt2-chat", ("chat.host_work_ms_step", "chat.stalled_steps_pct"),
     ("chat.decode_batch_inside", "serve.decode_slot_steps",
      ("serve.decode_steps",), 1.0)),
])
def test_rehearsal_reports_the_program_readings(cell, present, ratio):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--rehearse",
         "--benchmark-file", os.path.join(HERE, "rehearsal.json"),
         "--workload", cell, "--seed", "2147483659", "--seconds",
         "2", "--trace", "1"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    summary = next(ln for ln in lines if ln.get("phase") == "summary")
    assert set(summary["slowest_step"]["phases"]) <= set(
        summary["phase_max_ms"])
    assert "serve.page_write" in summary["eager_dispatches"]
    metrics = lines[-1]["metrics"]
    for name in present:
        assert "cpu_rehearsal." + name in metrics
    # a counter metric is the difference of the program's registry over
    # the window, which the readings file keeps, and nothing else
    path = next(ln for ln in lines if ln.get("phase") == "readings")["path"]
    with open(os.path.join(ROOT, path)) as f:
        readings = json.load(f)
    name, counter, over, scale = ratio
    diff = readings["registry"]
    assert metrics["cpu_rehearsal." + name]["value"] == pytest.approx(
        scale * diff[counter] / sum(diff[o] for o in over), rel=1e-12)
    # the judged rate's per-step token counts are the program's counters:
    # whole steps of the window sum to within one step of the difference
    steps = readings["steps"]
    assert all(len(s) == 3 for s in steps)
    assert sum(s[2] for s in steps) == pytest.approx(
        diff["serve.tokens_out"], abs=2 * max(s[2] for s in steps) + 1)
    assert lines[-1]["correct"] and not lines[-1]["failed"], next(
        ln for ln in lines if ln.get("phase") == "check")
