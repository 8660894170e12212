"""The readings taken from inside the program (`benchmarks/scopes.py`):
each rule on hand-made events with exact answers, the scope join on a
hand-made trace of two programs that share an instruction name, the
entries of `inside_metrics.json`, and `run_inside.py` end to end at the
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

from benchmarks import scopes, trace  # noqa: E402
from benchmarks.trace import Event, Trace  # noqa: E402

DEV = "/device:TPU:0"
RECORDED = os.path.join(HERE, "tiny-chat.xplane.pb")
SYNC = list(scopes.SYNC_SPANS)


def ev(name, start, dur):
    return Event(name, float(start), float(dur))


@pytest.fixture
def program_spans(monkeypatch):
    """What `run_inside.main` does to `trace.HOST_SPANS`."""
    monkeypatch.setattr(trace, "HOST_SPANS",
                        scopes.PROGRAM_SPANS + trace.HOST_SPANS)


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
    assert scopes.rule_span_self_ms(p, stepped, w, {}) == \
        pytest.approx(1e3 * (4.0 + 3.0) / 2)
    assert scopes.rule_span_self_ms({"span": "serve.step"}, stepped, w,
                                    {}) == pytest.approx(1e4)


def test_starved_and_sync_idle_split_the_idle_in_the_step(stepped):
    w = trace.window_of(stepped)
    starved = scopes.rule_span_idle_ms(
        {"span": "serve.step", "exclude": SYNC}, stepped, w, {})
    sync = scopes.rule_span_idle_ms(
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
    assert scopes.idle_position_ms(stepped, w, "serve.token_fetch") == \
        pytest.approx({"head": 0.0, "middle": 0.0, "tail": 1000.0})
    # first_token [11, 13] busy until 12
    assert scopes.idle_position_ms(stepped, w, "serve.first_token") == \
        pytest.approx({"head": 0.0, "middle": 0.0, "tail": 1000.0})
    # decode_build [0.5, 2.5] and [13, 14]: idle throughout
    assert scopes.idle_position_ms(stepped, w, "serve.decode_build")[
        "head"] == pytest.approx(1500.0)
    assert scopes.idle_position_ms(stepped, w, "serve.nothing") is None


def test_idle_gaps_name_the_phase(stepped, program_spans):
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
        assert scopes.RULES[rule](p, tr, w, {"hlo_texts": []}) is None
    assert scopes.rule_span_self_ms({"span": "serve.step"}, None, None,
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
    name, index = scopes.scope_index(DECODE)
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
    table = scopes.scope_table(two_programs, (0.0, 30.0), ctx)
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
    assert scopes.scope_table(two_programs, (0.0, 30.0), ctx) is table
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
    got = scopes.rule_scope_ms(p, two_programs, (0.0, 30.0),
                               {"hlo_texts": [DECODE, CHUNK]})
    assert got == (None if expect is None else pytest.approx(expect))


def test_scope_pct_and_the_groups_sum_to_the_program(two_programs):
    ctx = {"hlo_texts": [DECODE, CHUNK]}
    w = (0.0, 30.0)
    assert scopes.rule_scope_pct(
        {"program": "decode_fn", "group": "unscoped"}, two_programs, w,
        ctx) == pytest.approx(100.0 / 6)
    parts = [scopes.rule_scope_ms({"program": "decode_fn", **sel},
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
    assert scopes.phase_of(group) == phase
    assert scopes.kernel_of(group) == kernel


def test_by_opcode_sums_instances_of_one_kind():
    assert scopes.by_opcode({
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
    assert scopes.eager_dispatches(host, (0.0, 10.0)) == {
        "serve.prefill_chunk": {"PjitFunction(convert_element_type)": 1,
                                "PjitFunction(chunk_fn)": 1},
        "serve.first_token": {"PjitFunction(dynamic_slice)": 1,
                              "PjitFunction(squeeze)": 1}}


# ---------------------------------------------------------------------------
# the entries and the entry point
# ---------------------------------------------------------------------------

def test_inside_metrics_are_entries_a_benchmark_pr_can_move():
    """Each entry names cells of `BENCHMARK.json` and then of
    `tests/rehearsal.json` (no third list), and in each file every named
    cell reports the end-to-end metric the entry moves."""
    with open(os.path.join(BENCH, "inside_metrics.json")) as f:
        added = json.load(f)["per_layer"]
    files = []
    for path in (os.path.join(ROOT, "BENCHMARK.json"),
                 os.path.join(HERE, "rehearsal.json")):
        with open(path) as f:
            files.append(json.load(f))
    bench = files[0]
    layers = {m["layer"] for m in bench["per_layer"]}
    have = {m["name"] for m in bench["per_layer"]}
    names = [m["name"] for m in added]
    assert len(names) == len(set(names)) and not set(names) & have
    rules = dict(trace.RULES, **scopes.RULES)
    for m in added:
        assert m["layer"] in layers, m["name"]
        assert m["reduce"]["rule"] in rules, m["name"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        left = set(m["workloads"])
        for f in files:
            cells = {w["name"] for w in f["workloads"]} & left
            assert cells, m["name"]
            moved = next(e for e in f["end_to_end"]
                         if e["name"] == m["moves"])
            assert cells <= set(moved["workloads"]), m["name"]
            left -= cells
        assert not left, (m["name"], left)


def test_window_counters_take_the_steps_that_end_in_the_window():
    """`account()`'s window: the engine steps whose END lies in
    [0, seconds); the counters' differences are taken from the last step
    that ended before it."""
    from benchmarks import run_inside

    def counts(decode_steps, slot_steps, ctx, prefill, out, stalls):
        return dict(decode_steps=decode_steps, slot_steps=slot_steps,
                    ctx=ctx, prefill=prefill, out=out, stalls=stalls)
    seen = {"steps": [(-0.5, counts(10, 30, 3000, 500, 30, 1)),
                      (0.5, counts(11, 34, 4000, 628, 34, 1)),
                      (1.5, counts(12, 40, 6000, 628, 40, 2)),
                      (2.5, counts(13, 50, 9000, 756, 50, 3))]}
    got = run_inside.window_counters(seen, 2.0)
    assert got == pytest.approx({
        "admit_stall_pct": 50.0, "decode_ctx_ktokens_step": 1.5,
        "decode_batch_inside": 5.0,
        "prefill_token_share_inside": 100.0 * 128 / (128 + 10)})
    assert run_inside.window_counters({}, 2.0) == {}


@pytest.mark.parametrize("cell,present,twins", [
    ("tiny-chat", ("chat.host_work_ms_step", "chat.token_gap_p99_ms",
                   "chat.admit_stall_pct", "chat.decode_ctx_ktokens_step"),
     ("chat.decode_batch_inside", "decode_batch_mean")),
    ("tiny-batch", ("batch.host_work_ms_step",),
     ("batch.prefill_token_share_inside", "batch.prefill_token_share")),
])
def test_run_inside_rehearsal_reports_the_program_readings(cell, present,
                                                           twins):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run_inside.py"), "--rehearse",
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
    # what the program counts where the work happens against what
    # `account()` reconstructs from the scheduler's slots: the same
    inside, outside = (metrics["cpu_rehearsal." + n]["value"]
                       for n in twins)
    assert inside == pytest.approx(outside, rel=1e-9)
    assert lines[-1]["correct"] and not lines[-1]["failed"]
